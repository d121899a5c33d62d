"""Of the bytes the window's decode ticks must move, the share that follows
the cursors: 100 x the sum of `step_bytes_cache` over the sum of
`step_bytes_weights` + `step_bytes_cache` + `expert_bytes` x
`moe_experts_hit` on the window's `serving.decode_tick` spans
(`serve_step_bytes_roofline.py`'s sums, span for span).  It says which bytes
a cell wants fewer of next: closed32 reads 0.6 GB of pages beside 2.6 GB of
weights a tick, so an 8-bit pool could win it a tenth and an 8-bit weight
four tenths.  No direction is better; `lower` is given because the cache's
bytes grow with the contexts a replica is kept full of, and the weights'
are paid once whatever the lanes.  Nothing where that reader has nothing."""
import os

LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    import common

    got = common.load_module(os.path.join(
        os.path.dirname(__file__),
        "serve_step_bytes_roofline.py")).window_bytes(run)
    if got is None or not sum(got[:3]):
        return None
    return 100.0 * got[1] / sum(got[:3])
