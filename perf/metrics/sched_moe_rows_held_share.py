"""Share of the window's assignments (live lanes x experts per token x
layers with experts) that fell on experts HELD here: the sum of
`moe_rows_held` (counted by the step on the device, over the live
lanes) over the sum of `active` x `num_experts_per_tok` x `moe_layers`
on the program's `serving.decode_tick` spans that carry the count.  A
chip that holds 16 of 128 experts gets 12.5% under uniform routing;
times the lanes and experts per token over the experts held it is the
rows a held expert sees a layer (64 x 8 x 12.5% / 16 = 4).  The count
is of the tick READ and `active` of the tick dispatched, one later:
under 1% apart on a replica kept full.  Nothing where the program sets
no such attribute (a block that holds every expert it routes over)."""
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
             and "moe_rows_held" in s["attrs"]]
    k = run.cell.config.get("num_experts_per_tok", 0)
    sent = sum(a["active"] * k * a["moe_layers"] for a in ticks)
    return (100.0 * sum(a["moe_rows_held"] for a in ticks) / sent
            if sent else None)
