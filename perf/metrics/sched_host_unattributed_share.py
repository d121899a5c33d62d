"""Share of the scheduler's own time under none of its phases: an
iteration's period less its `sample` is what the host was busy, and what of
that lies under neither `deliver`, `admit`, `build` nor the dispatch phase
is unattributed (the loop's own statements, span bookkeeping, and in a
traced benchmark run the job's counting wrapper around `_tick`); summed over
the window's iterations (`sched_iterations.py`) and given over the busy
time.  The guard that the phases tile the iteration: past 10 some host work
has moved out from under every span, and the four `sched_*_ms` no longer add
up to the host's part.  Nothing where the program has no `build` phase."""
import os

LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    return walk.unattributed_share(run)
