"""Share of the traced slice's device seconds under the expert layer's
four named scopes of the resident decode step: `paged_decoder/moe_router`
(float32 logits, softmax, top-k), `moe_dispatch` (sort by expert, group
sizes, the row gather), `moe_experts` (the three grouped matmuls and the
gate) and `moe_combine` (unsort, weigh, sum, residual add).  Read like
`serve_kv_gather_share`, whose reader it uses: nothing where the program
has no scope table or the step no such scope, or where under 90% of the
device seconds resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/moe_"


def scope_share(run, scope):
    import common

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_kv_gather_share.py")
    ).scope_share(run, scope)
    return share or None        # 0.0: the step has no such scope


def compute(run):
    return scope_share(run, SCOPE)
