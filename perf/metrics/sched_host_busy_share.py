"""Share of the scheduler's iterations that was the host's own work: 1 less
the seconds under `generation.phase.sample` (the one place the host waits
for the device: the blocking read of the tick before) over the sum of the
periods, over the iterations whose end lies in the window
(`sched_iterations.py`: an iteration runs from the end of one read to the
end of the next).  Near 0 the host's work hides under the device's step;
at 100 the read returns at once and the scheduler sets the pace, and a
faster device step then moves nothing end to end.  Nothing where the
program's phases do not tile the iteration (no `build` phase)."""
import os

LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    return walk.host_busy_share(run)
