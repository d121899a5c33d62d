"""Submit to admission, 95th percentile over the requests that ended in
the window: `queue_s` on the program's `serving.request` spans.  In a
closed loop of as many clients as slots it is the wait for the next tick's
admission; under open arrivals it is the queue.  Nothing where the program
does not split the request span."""
LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "ttft_p95_ms"
SOURCE = "program_span"


def compute(run):
    import numpy as np

    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    waits = [s["attrs"]["queue_s"] for s in tracing.finished_spans()
             if s["name"] == "serving.request"
             and lo <= s["ts"] + s["dur"] <= hi and "queue_s" in s["attrs"]]
    return 1e3 * float(np.percentile(waits, 95)) if waits else None
