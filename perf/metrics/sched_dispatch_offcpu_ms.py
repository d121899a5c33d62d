"""The part of `sched_dispatch_ms` that is waiting: the mean an iteration
of `dur - cpu` on the window's `generation.phase.decode` and `.prefill`
spans, over the iterations `sched_iterations.py` counts.  The dispatch is
the select, the jitted call with the step's states as its arguments and
the cursors: near 0 those milliseconds are the scheduler's thread at
work, near `sched_dispatch_ms` it stood inside them (the interpreter
lock, a thread of the runtime).  `span_cpu.py` says when it reads
nothing."""
import os

LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    walks = common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).sched_walks(run)
    if not walks or not walks[1]:
        return None
    off = walks[1]
    return 1e3 * sum(i["dispatch"] for i in off) / len(off)
