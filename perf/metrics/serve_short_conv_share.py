"""Share of the traced slice's device seconds under the gated short
convolution's three named scopes of the resident decode step:
`paged_decoder/conv_in_proj` (the input norm and the projection to the
gates B, C and u), `conv_gate` (the product B * u, the lane's tail
reset, shifted and held, the three multiply-adds a column, the second
gate) and `conv_out_proj` (the projection back and the residual add).
Read like `serve_ssm_share`, whose reader it uses: nothing where the
program has no scope table or the step no such scope, or where under
90% of the device seconds resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/conv_"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
