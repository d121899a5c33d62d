"""Share of the traced slice's device seconds under the named scope
`paged_decoder/dense_ffn` of the resident decode step: the SwiGLU of a
DENSE layer among sparse ones (its norm and three matmuls over all
lanes' rows at the dense width: 0.68 GB of weights a tick at 6144 x
18432 in bf16).  Read like `serve_ssm_share`, whose reader it uses:
nothing where the step has no such scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/dense_ffn"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
