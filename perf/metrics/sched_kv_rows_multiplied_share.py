"""K/V rows the dispatched decode steps' two attention products RUN OVER,
of the rows of the pages they read: 100 x the sum of `kv_rows_multiplied`
over the sum of `kv_pages_read` x the traffic mix's `block_size`, on the
program's `serving.decode_tick` spans of the window (both summed over slots
and attention layers).  The streaming Pallas kernel
(`paddle_tpu/kernels/paged_attention.py`) copies a slot's pages a chunk at a
time and multiplies them a ROW TILE at a time, over the tiles the copied
pages reach: a slot's rows rounded up to the tile a chunk, so 100 means the
products follow the pages, and a kernel that multiplied whole chunks of 256
rows for a mean slot of 91 would read 290.  On the XLA gather path both are
every row of the table: 100.  Nothing where the program sets no such
attribute (a program whose kernel multiplies the chunk, or without the
kernel) or keeps no span store under a listener."""
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
             and "kv_rows_multiplied" in s["attrs"]]
    rows = (sum(a["kv_pages_read"] for a in ticks)
            * int(run.cell.traffic["block_size"]))
    return (100.0 * sum(a["kv_rows_multiplied"] for a in ticks) / rows
            if rows else None)
