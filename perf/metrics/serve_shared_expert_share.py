"""Share of the traced slice's device seconds under the named scope
`paged_decoder/shared_expert` of the resident decode step: the SwiGLU
expert every token takes beside the routed ones (three dense matmuls
over all lanes' rows).  Read like `serve_ssm_share`, whose reader it
uses: nothing where the step has no such scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/shared_expert"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_ssm_share.py")
    ).scope_share(run, SCOPE)
