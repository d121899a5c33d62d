"""Share of slot-ticks in the window that teacher-forced a prompt position
and so delivered no token, read from the scheduler's own count: the sum of
`prefill` over the sum of `active` on the program's `serving.decode_tick`
spans (`prefill_slot_share`'s inside twin).  Nothing where the program
sets no such attribute or keeps no span store under a listener."""
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
             and lo <= s["ts"] + s["dur"] <= hi and "prefill" in s["attrs"]]
    active = sum(a["active"] for a in ticks)
    return 100.0 * sum(a["prefill"] for a in ticks) / active if active \
        else None
