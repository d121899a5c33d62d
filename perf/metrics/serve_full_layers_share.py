"""Share of the traced slice's device seconds that the FULL layers'
cache costs: the gather through the block table and the attention over
it, the named scopes `paged_decoder/kv_gather/full` and
`paged_decoder/attention/full` (`serve_window_layers_share`'s twin,
whose reader it uses; the two add up to `serve_kv_gather_share` plus
`serve_attention_share`).  Nothing where that reader has nothing."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KIND = "full"


def compute(run):
    import common

    return common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_window_layers_share.py")
    ).kind_share(run, KIND)
