"""Experts a tick touches, as a share of the experts the served layers
HOLD: `moe_experts_hit` on the program's `serving.decode_tick` spans
(distinct held experts routed to, summed over the layers with experts,
counted by the step on the device) over `moe_layers` (the same spans:
the layers that have experts, a leading dense layer not among them)
times the experts held here (the configuration's `num_experts`),
averaged over the window's ticks.  `moe_experts_hit_share` divides by
`num_hidden_layers` and so counts a dense layer's absent experts.  It
says how much of the held expert weights a tick has to read: uniform
routing of 64 x 8 assignments over 128 experts touches 98.4% of the 16
held.  Nothing where the program sets no `moe_layers`."""
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
             and "moe_experts_hit" in s["attrs"]
             and s["attrs"].get("moe_layers")]
    held = run.cell.config.get("num_experts", 0)
    if not ticks or not held:
        return None
    return 100.0 * sum(a["moe_experts_hit"] / (a["moe_layers"] * held)
                       for a in ticks) / len(ticks)
