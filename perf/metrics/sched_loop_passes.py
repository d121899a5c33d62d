"""Passes over the stack that a dispatched tick's step runs: the mean
of `loop_passes` on the program's `serving.decode_tick` spans of the
window.  `total_ut_steps` (4) for a looped stack served whole; less,
and a pass is being skipped.  Read like `sched_pool_wait_share`, whose
reader it uses: nothing where the program sets no such attribute (a
step that is not looped)."""
import os

LAYER = "model step"
UNIT = "count"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "sched_pool_wait_share.py")
    ).window_mean(run, "loop_passes")
