"""Share of the spanned interval (see `reader_input_share`, whose reader
this uses) inside `executor.fetch`: the host waits there for the step's
results, so it is the share in which the device, not the host, sets the
pace.  Nothing where the program has no such span."""
import os

LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("executor.fetch",)


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "reader_input_share.py")
    ).span_share(run, NAMES)
