"""Share of the traced slice's device seconds under the Mamba-2 mixer's
five named scopes of the resident decode step: `paged_decoder/
ssm_in_proj` (the input norm and the projection to z, x B C and dt),
`ssm_conv` (the lane's convolution tail, the depthwise convolution, its
update), `ssm_scan` (the lane's state read, decayed, updated, `h . C`,
written), `ssm_gate_norm` (the gate and the norm over all columns) and
`ssm_out_proj` (the projection back and the residual add).  Read like
`serve_kv_gather_share`, whose reader it uses: nothing where the
program has no scope table or the step no such scope, or where under
90% of the device seconds resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/ssm_"


def scope_share(run, scope):
    import common

    share = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_kv_gather_share.py")
    ).scope_share(run, scope)
    return share or None        # 0.0: the step has no such scope


def compute(run):
    return scope_share(run, SCOPE)
