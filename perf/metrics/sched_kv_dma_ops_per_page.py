"""DMA operations the dispatched decode steps' attention kernel performs
for a K/V page it reads: the sum of `kv_dma_ops` over the sum of
`kv_pages_read` on the program's `serving.decode_tick` spans of the window
that carry both (summed over slots, attention layers and, `kv_dma_ops`, the
pools: a K and a V pool, or the one latent pool).  `kv_dma_ops` is
`decoder.tick_counts`'s account of the kernel's starts and waits
(`kernels.paged_attention.dma_ops`): a start a page, and for each chunk of a
slot's pages a wait for each set bit of the pages copied into it.  A kernel
that starts and waits every page reads 2.0 a pool (4.0 with K and V, 2.0 on
a latent pool); waits on a chunk's summed bytes read 1.05 to 1.15 a pool
where a slot fills chunks and some 1.3 where it has a page or two.  Nothing
where the program sets no such attribute (a parent before PR 46, the gather
path) or keeps no span store under a listener."""
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
             and "kv_dma_ops" in s["attrs"]]
    pages = sum(a["kv_pages_read"] for a in ticks)
    return sum(a["kv_dma_ops"] for a in ticks) / pages if pages else None
