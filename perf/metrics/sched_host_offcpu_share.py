"""Of the scheduler's own host time, the share in which its thread did not
run: 100 x the sum of `dur - cpu` over the sum of `dur` on the window's
`generation.phase.deliver`, `.admit`, `.build`, `.decode` and `.prefill`
spans (the four host parts of `sched_iterations.py`; `sample` is the wait
for the device and is left out).  Near 0 the host phases are Python at
work; what is above it is a wait inside them: the server's lock
(`sched_lock_wait_ms`), the interpreter lock that the clients' threads
also take, or the kernel running something else.  `span_cpu.py` says
when it reads nothing."""
import os

LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"
NAMES = tuple("generation.phase." + p for p in (
    "deliver", "admit", "build", "decode", "prefill"))


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).offcpu_share(run, NAMES)
