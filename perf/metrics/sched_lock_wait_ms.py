"""Mean time an iteration of the scheduler waits for the server's lock at
the head of `admit`: the mean of `lock_wait_s` on the program's
`generation.phase.admit` spans of the window.  The lock is shared with every
submitting thread and every `stats()` reader, so this is the scheduler held
up by its callers; part of `sched_admit_ms`.  The attribute lives in the
program's span store, a ring: nothing when the ring has dropped records
(`tracing.dropped_spans()`: the window's first seconds would be missing, and
the mean would be of another window), where the program sets no such
attribute, or where it keeps no span store under a listener."""
LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans or tracing.dropped_spans():
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    waits = [s["attrs"]["lock_wait_s"] for s in tracing.finished_spans()
             if s["name"] == "generation.phase.admit"
             and lo <= s["ts"] + s["dur"] <= hi
             and "lock_wait_s" in s["attrs"]]
    return 1e3 * sum(waits) / len(waits) if waits else None
