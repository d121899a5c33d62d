"""Share of the window's batches whose every dense column was packed into
a host buffer the prefetch pipeline already held (no allocation, no page
faults) and not into a new array: 100 x the mean of `reused` (1 or 0) on
the window's `trainer.phase.feed_pack` spans (`bytes` beside it is what
was packed).  Near 100 once a pass's first batch has made the buffers;
0 where every batch allocates.  Nothing where the program sets no such
attribute (a program that always allocates) or keeps no span store
under a listener."""
import os

LAYER = "reader / data_feeder"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"


def compute(run):
    import common

    spans = common.load_module(os.path.join(
        os.path.dirname(__file__), "reader_pack_ms.py")).pack_spans(run)
    reused = [s["attrs"]["reused"] for s in spans if "reused" in s["attrs"]]
    return 100.0 * sum(reused) / len(reused) if reused else None
