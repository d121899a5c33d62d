"""Share of the spanned interval (see `reader_input_share`, whose reader
this uses) inside `executor.dispatch`: the executable looked up, the
jitted call with the step's feeds and states as its arguments, and the
written states put back into the scope.  `reader_h2d_share`'s twin for
the second child of `executor.run`: with the default `sync_every_n` of 1
both are host work serial with the device's step.  Nothing where the
program has no such span."""
import os

LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("executor.dispatch",)


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "reader_input_share.py")
    ).span_share(run, NAMES)
