"""`serve_moe_share` without `paged_decoder/moe_experts`: the router, the
dispatch (sort, group sizes, row gather) and the combine, which is what a
better dispatch can remove; the grouped matmuls themselves are bound by
the expert matrices they read.  Nothing where `serve_moe_share` has
nothing."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(run):
    import common

    moe = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_moe_share.py"))
    whole = moe.scope_share(run, moe.SCOPE)
    if whole is None:
        return None
    return whole - (moe.scope_share(run, moe.SCOPE + "experts") or 0.0)
