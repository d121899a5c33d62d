"""Share of the window's ticks that the scheduler dispatched while the tick
before was still unread on the device: the mean of `ahead` (1 or 0) on the
program's `serving.decode_tick` spans.  Near 100 the host's work between two
decode steps (delivery, admission, building and dispatching the next step)
runs under the device's step; at 0 the loop is serial and that work is the
device's idle time.  Nothing where the program sets no such attribute or
keeps no span store under a listener."""
LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ahead = [s["attrs"]["ahead"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and lo <= s["ts"] + s["dur"] <= hi and "ahead" in s["attrs"]]
    return 100.0 * sum(ahead) / len(ahead) if ahead else None
