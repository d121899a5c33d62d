"""Of the window's longest iteration (the one `sched_iteration_max_ms`
reports), the host time in which the scheduler's thread did not run: the
sum of `dur - cpu` over its `deliver`, `admit`, `build` and dispatch
spans.  Beside `sched_iteration_max_host_ms` it says whether the stall's
host time was work (near 0: the named phase's call was computing) or
waiting (near the host time: the thread was blocked or taken off the
CPU; `GenerationServer.stats()["slow_ticks"]` says which).
`span_cpu.py` says when it reads nothing."""
import os

LAYER = "serving.generation scheduler"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    import common

    here = os.path.dirname(__file__)
    walks = common.load_module(os.path.join(
        here, "span_cpu.py")).sched_walks(run)
    if not walks or not walks[0]:
        return None
    its, off = walks
    longest = max(range(len(its)), key=lambda i: its[i]["period"])
    host = common.load_module(os.path.join(
        here, "sched_iterations.py")).HOST
    return 1e3 * sum(off[longest][p] for p in host)
