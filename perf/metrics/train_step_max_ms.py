"""The longest step of the window: 1e3 x the largest `period_s` on its
`executor.run` spans, the seconds from the end of the executor's
previous `run` to the end of this one.  The training cells' twin of
`sched_iteration_max_ms`: beside `train_step_ms` (a median) it says
whether a slow window lost one gap or every step (the executor's
`slow_steps()` says where the gap went).  Nothing where the program sets
no such attribute; `span_cpu.py` says when else."""
import os

LAYER = "trainer / core.executor"
UNIT = "ms"
MOVES = "train_throughput"
SOURCE = "program_span"


def compute(run):
    import common

    spans = common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).window(
            run, "executor.run")
    periods = [s["attrs"]["period_s"] for s in spans or ()
               if "period_s" in s["attrs"]]
    return 1e3 * max(periods) if periods else None
