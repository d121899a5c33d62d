"""Share of the traced slice's device seconds under the gated delta
rule's six named scopes of the resident decode step: `paged_decoder/
delta_in_proj` (the input norm and the projection to q | k | v),
`delta_conv` (the lane's tail reset, shifted and held, the depthwise
convolutions, the SiLU, the L2 norms), `delta_gates` (the log decay a
channel and the write strength), `delta_rule` (the lane's state read,
decayed, `k^T S'`, corrected, `S^T q`, written), `delta_gate_norm` (the
norm a head and the output gate) and `delta_out_proj` (the projection
back and the residual add).  Read like `serve_ssm_share`, whose reader
it uses: nothing where the program has no scope table or the step no
such scope (a program before PR 59, a block without delta-rule layers),
or where under 90% of the device seconds resolve to a `paged_decoder/`
scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/delta_"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
