"""Share of the K/V pages the slots' tables and rings hold that the
dispatched decode steps' attention READS: the sum of `kv_pages_read` over
the sum of `kv_pages_table` on the program's `serving.decode_tick` spans of
the window (both summed over slots and attention layers; pages of
`block_size` positions).  Where the resident step attends through the
streaming Pallas kernel (`paddle_tpu/kernels/paged_attention.py`) a slot
reads the pages its cursor has reached, `ceil((cursor + 1) / block_size)` on
a table and the window's at most on a ring; on the XLA gather path it reads
every page whatever the cursor, and this is 100.  Nothing where the program
sets no such attribute (a program without the kernel) or keeps no span store
under a listener."""
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
             and "kv_pages_read" in s["attrs"]]
    table = sum(a["kv_pages_table"] for a in ticks)
    return (100.0 * sum(a["kv_pages_read"] for a in ticks) / table
            if table else None)
