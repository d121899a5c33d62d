"""Share of the window's assignments that went to IDENTITY experts
(router columns with no matrices, `BlockSpec.zero_experts`): the sum of
`moe_zero_assignments` over the sum of `moe_assignments` (both counted
by the step on the device, over the live lanes and the layers with
experts) on the program's `serving.decode_tick` spans that carry the
counts.  It is the share of a token's chosen experts that cost no
matmul and no exchange: a router whose 768 columns (512 routed, 256
identity) take even loads reads 33.3, which is 8 routed experts a token
of 12.  Both counts are of the tick READ, so the share is exact
whatever the lanes did.  Nothing where the program sets no such
attribute (a block without identity experts, a program before PR 48)."""
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
             and "moe_zero_assignments" in s["attrs"]]
    sent = sum(a["moe_assignments"] for a in ticks)
    return (100.0 * sum(a["moe_zero_assignments"] for a in ticks) / sent
            if sent else None)
