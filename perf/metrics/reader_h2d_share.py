"""Share of the spanned interval (see `reader_input_share`, whose reader
this uses) inside `executor.feed`: placing the step's feeds on the device
(the 154 MB copy) and committing its states.  Nothing where the program
has no such span."""
import os

LAYER = "trainer / core.executor"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("executor.feed",)


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "reader_input_share.py")
    ).span_share(run, NAMES)
