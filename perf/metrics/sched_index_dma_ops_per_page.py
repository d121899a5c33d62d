"""DMA operations the dispatched decode steps' index-score kernel performs
for an index-key page it reads: the sum of `index_dma_ops` over the sum of
`index_pages_read` on the program's `serving.decode_tick` spans of the
window that carry both (summed over lanes and selecting layers: one pool of
index keys).  `index_dma_ops` is `decoder.tick_counts`'s account of the
kernel's starts and waits (`kernels.paged_attention.dma_ops` at the
index-score kernel's chunk and issue group): a start a group of 16 table
entries that are a run of consecutive blocks and a start a page elsewhere,
and for each chunk of a lane's pages a wait for each set bit of the pages
copied into it.  A kernel that starts every page reads just over 1.0 (the
table one chunk: a lane's waits are a handful); one that finds every group
a run reads under 0.1.  Nothing where the program sets no such attribute (a
parent before PR 54, the gather path, a model without an indexer) or keeps
no span store under a listener."""
LAYER = "kernels"
UNIT = "ops/page"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and lo <= s["ts"] + s["dur"] <= hi
             and "index_dma_ops" in s["attrs"]]
    pages = sum(a["index_pages_read"] for a in ticks)
    return sum(a["index_dma_ops"] for a in ticks) / pages if pages else None
