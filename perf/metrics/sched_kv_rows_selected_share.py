"""Rows the dispatched decode steps' attention is OVER, of the rows under
the lanes' cursors: 100 x the sum of `kv_rows_selected` (min(cursor + 1,
index_topk) a lane a latent plane) over the sum of `latent_rows` (cursor
+ 1 a lane a plane), on the program's `serving.decode_tick` spans of the
window.  100 while every cursor is under `index_topk`; at a cursor of
5 k and 2048 selected, 41.  Nothing where the program sets no such
attribute (a block without an indexer, a program before PR 53) or keeps
no span store under a listener."""
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
             and "kv_rows_selected" in s["attrs"]]
    rows = sum(a["latent_rows"] for a in ticks)
    return (100.0 * sum(a["kv_rows_selected"] for a in ticks) / rows
            if rows else None)
