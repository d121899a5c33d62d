"""The longest iteration of the scheduler whose end lies in the window: the
largest distance between the ends of two consecutive blocking reads
(`sched_iterations.py`).  A run without a stall reads under three periods;
one of the stalls of PERF.md section 7 (0.5 to 4.8 s with no delivery) shows
here, and `sched_iteration_max_host_ms` beside it says whose it was.
Nothing where the program's phases do not tile the iteration."""
import os

LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    import common

    walk = common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_iterations.py"))
    return walk.longest_ms(run)
