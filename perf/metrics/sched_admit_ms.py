"""Mean time an iteration of the scheduler spends under
`generation.phase.admit`: taking the server's lock (`sched_lock_wait_ms`
is that part alone), shedding, admission with its `kv_alloc`, the gauges,
over the iterations whose end lies in the window (`sched_iterations.py`).
Host work that hides under the device's step until `sched_host_busy_share`
nears 100.  Nothing where the program's phases do not tile the iteration."""
import os

LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    return walk.mean_ms(run, "admit")
