"""Share of slot-ticks in the window whose cursor was at or past the
sliding window, so that the slot's ring had wrapped and its sliding
layers attended over the whole ring: the sum of `past_window` over the
sum of `active` on the program's `serving.decode_tick` spans.  It says
how much of the traffic the ring actually bounds (below it a sliding
layer reads what a full one reads).  Nothing where the program sets no
such attribute or keeps no span store under a listener."""
LAYER = "serving.generation scheduler"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and lo <= s["ts"] + s["dur"] <= hi
             and "past_window" in s["attrs"]]
    active = sum(a["active"] for a in ticks)
    return 100.0 * sum(a["past_window"] for a in ticks) / active \
        if active else None
