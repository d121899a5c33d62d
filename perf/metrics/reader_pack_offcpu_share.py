"""What of `reader_pack_ms` the prefetch worker did not run: 100 x the sum
of `dur - cpu` over the sum of `dur` on the window's
`trainer.phase.feed_pack` spans, which lie on the worker's thread.  The
pack is NumPy copies and Python between them beside a loop that holds
the interpreter lock for its own host work, so this is mostly the
worker's wait for that lock.  `span_cpu.py` says when it reads
nothing."""
import os

LAYER = "reader / data_feeder"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("trainer.phase.feed_pack",)


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "span_cpu.py")).offcpu_share(run, NAMES)
