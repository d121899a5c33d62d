"""Share of the window's steps whose batch was already prepared (read,
packed and on the device) when the loop asked for it: the mean of
`feed_ready` (1 or 0) on the program's `trainer.step` spans.  Near 100 the
worker thread that prepares batch n+1 under step n is the faster side and
the device's step sets the pace; near 0 the loop waits for the worker at
every step (the span's `feed_wait_s` says how long) and reading and packing
set the pace.  Nothing where the program sets no such attribute or keeps no
span store under a listener."""
LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ready = [s["attrs"]["feed_ready"] for s in tracing.finished_spans()
             if s["name"] == "trainer.step"
             and lo <= s["ts"] + s["dur"] <= hi
             and "feed_ready" in s["attrs"]]
    return 100.0 * sum(ready) / len(ready) if ready else None
