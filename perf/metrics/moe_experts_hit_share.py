"""Experts a tick touches, as a share of the experts the served layers
hold: `moe_experts_hit` on the program's `serving.decode_tick` spans
(distinct experts routed to, summed over layers, counted by the step on
the device) over layers x experts, averaged over the window's ticks.
It says how much of the expert weights a tick has to read (uniform
routing of 32 x 8 assignments over 64 experts touches 98.6%).  Nothing
where the program sets no such attribute."""
LAYER = "model step"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    hit = [s["attrs"]["moe_experts_hit"] for s in tracing.finished_spans()
           if s["name"] == "serving.decode_tick"
           and lo <= s["ts"] + s["dur"] <= hi
           and "moe_experts_hit" in s["attrs"]]
    m = run.cell.config
    held = m["num_hidden_layers"] * m.get("num_experts", 0)
    return 100.0 * sum(hit) / len(hit) / held if hit and held else None
