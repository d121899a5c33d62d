"""Share of the index-key pages the lanes' tables hold that the dispatched
decode steps' lightning indexer READS: the sum of `index_pages_read` over
the sum of `index_pages_table` on the program's `serving.decode_tick` spans
of the window (both summed over lanes and selecting layers; pages of
`block_size` positions, one index key a position).  Where the selecting
layers score through the streaming Pallas kernel
(`paddle_tpu/kernels/paged_index_scores.py`) a lane reads the pages its
cursor has reached, `ceil((cursor + 1) / block_size)`, and one where it
holds no sequence; on the XLA gather path it reads every page whatever the
cursor, and this is 100.  Nothing where the program sets no such attribute
(a program without the kernel, a model without an indexer) or keeps no span
store under a listener."""
LAYER = "kernels"
UNIT = "%"
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
             and "index_pages_read" in s["attrs"]]
    table = sum(a["index_pages_table"] for a in ticks)
    return (100.0 * sum(a["index_pages_read"] for a in ticks) / table
            if table else None)
