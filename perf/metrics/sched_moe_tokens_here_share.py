"""Share of the window's (live lane, layer with experts) pairs with AT
LEAST ONE assignment on an expert HELD here: the sum of
`moe_tokens_here` (counted by the step on the device, over the live
lanes) over the sum of `active` x `moe_layers` on the program's
`serving.decode_tick` spans that carry the count.  It is the share of a
stage's tokens whose hidden state this chip's experts need, which is
what a GROUP LIMIT bounds: with 8 groups of which a token keeps 3 and a
chip that holds 2, even routing reads 1 - C(6,3) / C(8,3) = 64.3, where
a plain top-6 of 160 would read 82.  The count is of the tick READ and
`active` of the tick dispatched, one later: under 1% apart on a replica
kept full.  Nothing where the program sets no such attribute (a router
without groups, a block that holds every expert it routes over)."""
LAYER = "model step"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and lo <= s["ts"] + s["dur"] <= hi
             and "moe_tokens_here" in s["attrs"]]
    pairs = sum(a["active"] * a["moe_layers"] for a in ticks)
    return (100.0 * sum(a["moe_tokens_here"] for a in ticks) / pairs
            if pairs else None)
