"""Share of slot-ticks in the window that started a sequence in their
lane (cursor 0), so that the step took the lane's recurrent state and
convolution tail from zero whatever its last occupant left: the sum of
`state_resets` over the sum of `active` on the program's
`serving.decode_tick` spans.  It says how fast lanes turn over (one
reset a request: the inverse of a request's length in ticks).  Nothing
where the program sets no such attribute (a block without Mamba layers)
or keeps no span store under a listener."""
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
             and "state_resets" in s["attrs"]]
    active = sum(a["active"] for a in ticks)
    return 100.0 * sum(a["state_resets"] for a in ticks) / active \
        if active else None
