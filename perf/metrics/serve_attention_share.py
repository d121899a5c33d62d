"""Share of the traced slice's device seconds under the named scope
`paged_decoder/attention` of the resident decode step: scores, mask,
softmax and the weighted sum over the gathered K and V (or the Pallas
paged-attention call, where selection takes it).  Read like
`serve_kv_gather_share`, whose reader it uses: nothing where the program
has no scope table, or where under 90% of the device seconds resolve to a
`paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/attention"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_kv_gather_share.py")
    ).scope_share(run, SCOPE)
