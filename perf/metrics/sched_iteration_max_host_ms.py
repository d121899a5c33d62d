"""What of the window's longest iteration (`sched_iteration_max_ms`) was the
host's own: its period less its `generation.phase.sample`, the wait inside
the blocking read.  Near 0 the device or the runtime held that iteration
(the read did not return); near `sched_iteration_max_ms` host code did
(`GenerationServer.stats()["slow_ticks"]` names the phase).  Nothing where
the program's phases do not tile the iteration."""
import os

LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    return walk.longest_ms(run, host_only=True)
