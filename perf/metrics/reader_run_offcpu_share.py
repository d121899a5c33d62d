"""What of `Executor.run`'s own host work the loop's thread did not run:
100 x the sum of `dur - cpu` over the sum of `dur` on the window's
`executor.feed` and `executor.dispatch` spans (`executor.fetch` is the
wait for the device and is left out).  Near 0 the feed's placing and the
jitted call's argument handling are the thread at work; what is above it
is a wait inside them (the interpreter lock under the prefetch worker's
pack, a thread of the runtime).  `span_cpu.py` says when it reads
nothing."""
import os

LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("executor.feed", "executor.dispatch")


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).offcpu_share(run, NAMES)
