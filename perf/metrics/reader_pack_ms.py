"""Mean time the prefetch worker spent packing one batch's rows into feed
arrays (`DataFeeder.feed`, on the worker's thread beside the device's
step): the mean duration in ms of the window's `trainer.phase.feed_pack`
spans.  It is the worker's side of the period: while it plus the
transfer (`trainer.phase.h2d`) is under the loop's own period the batch
is ready when the loop asks (`reader_feed_ready_share`) and the device's
step sets the pace.  In the loop it reads longer than the same pack
alone by what the worker waits for the GIL.  Nothing where the program
has no such span or keeps no span store under a listener."""
LAYER = "reader / data_feeder"
UNIT = "ms"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAME = "trainer.phase.feed_pack"


def pack_spans(run):
    """The window's `trainer.phase.feed_pack` spans, full records
    (`reader_pack_reuse_share` reads their attributes through here)."""
    from paddle_tpu.observability import tracing

    if not run.spans:
        return []
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    return [s for s in tracing.finished_spans()
            if s["name"] == NAME and lo <= s["ts"] + s["dur"] <= hi]


def compute(run):
    spans = pack_spans(run)
    return 1e3 * sum(s["dur"] for s in spans) / len(spans) \
        if spans else None
