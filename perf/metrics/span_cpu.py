"""Not a metric: what the six readers of a span's `cpu` share
(`sched_host_offcpu_share`, `sched_dispatch_offcpu_ms`,
`sched_iteration_max_offcpu_ms`, `reader_pack_offcpu_share`,
`reader_run_offcpu_share`, `train_step_max_ms`).

A live span of the program records `cpu` beside `dur`: the seconds its
own thread spent on a CPU between entry and exit
(docs/observability.md).  `dur - cpu` is the time that thread was NOT
running: a blocking read, a lock, the interpreter lock, or the kernel
running something else.  The field lives in the program's span store, a
ring, and the tap's own list has no place for it, so the readers take
the store's records whose end lies in the window the tap's first and
last spans give.  Nothing when the ring has dropped records
(`tracing.dropped_spans()`: the window's first seconds would be
missing), and nothing from a program whose records have no `cpu`.
"""
import os
import types


def window(run, names):
    """The store's full records called one of `names` (a prefix or a
    tuple of prefixes) whose end lies in the tap's window, in the order
    they ended; None when there is no window or the ring dropped."""
    from paddle_tpu.observability import tracing

    if not run.spans or tracing.dropped_spans():
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    return [s for s in tracing.finished_spans()
            if s["name"].startswith(names)
            and lo <= s["ts"] + s["dur"] <= hi]


def offcpu_share(run, names):
    """100 x the sum of `dur - cpu` over the sum of `dur` on the
    window's spans called `names` that carry `cpu`."""
    spans = [s for s in window(run, names) or ()
             if s.get("cpu") is not None]
    dur = sum(s["dur"] for s in spans)
    return 100.0 * sum(s["dur"] - s["cpu"] for s in spans) / dur \
        if dur else None


def sched_walks(run):
    """-> (iterations, their off-CPU twins) by `sched_iterations.py`'s
    walk over the store's `generation.phase.*` records of the window:
    the first as `sched_iteration_max_ms` counts them, the second the
    same walk over the same records with every `dur` replaced by `dur -
    cpu`, so that entry i of both is the same iteration (which
    iterations the walk counts hangs on the names alone) and a part of
    the second is the seconds of that part in which the scheduler's
    thread did not run.  None where a record has no `cpu`."""
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    spans = window(run, walk.PHASE)
    if not spans or any(s.get("cpu") is None for s in spans):
        return None
    off = [dict(s, dur=s["dur"] - s["cpu"]) for s in spans]
    return (walk.iterations(types.SimpleNamespace(spans=spans)),
            walk.iterations(types.SimpleNamespace(spans=off)))
