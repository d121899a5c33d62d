"""Share of the spanned interval the loop spent getting its next batch:
inside the user's reader (`trainer.phase.reader`) and packing its rows
(`trainer.phase.feed_pack`), from the program's own spans
(`train_reader_wait_share`'s inside twin).  The interval runs from the
start of the first to the end of the last of the window's `trainer.*` and
`executor.*` spans.  Nothing where the program has no reader span."""
LAYER = "reader / data_feeder"
UNIT = "%"
MOVES = "train_reader_throughput"
SOURCE = "program_span"
NAMES = ("trainer.phase.reader", "trainer.phase.feed_pack")


def span_share(run, names):
    """Percent of the spanned interval inside the spans called `names`
    (`reader_h2d_share` and `reader_device_wait_share` read through
    here too); None where the program recorded none of them."""
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    spans = [s for s in tracing.finished_spans()
             if lo <= s["ts"] + s["dur"] <= hi
             and s["name"].startswith(("trainer.", "executor."))]
    mine = sum(s["dur"] for s in spans if s["name"] in names)
    if not mine:
        return None
    interval = max(s["ts"] + s["dur"] for s in spans) \
        - min(s["ts"] for s in spans)
    return 100.0 * mine / interval


def compute(run):
    return span_share(run, NAMES)
