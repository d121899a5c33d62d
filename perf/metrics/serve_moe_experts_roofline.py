"""`moe_experts_roofline` for a configuration that says which of its
keys holds ONE expert's width (`block.d_inner`; `moe_experts_roofline`
takes `intermediate_size`, which where a model also publishes a dense
layer's width is that and not an expert's, and would count eight times
the bytes here): that reader's own reading, given the configuration
with the expert's width under the key it reads.  So: the least seconds
the chip could take to read the expert matrices the traced slice
touched (`moe_experts_hit` on `serving.decode_tick`, summed over the
ticks whose middle lies in the slice, times one expert's three
matrices, `perf/moe_flops.py`), over the device seconds under
`paged_decoder/moe_experts`.  Nothing where that reader has nothing, or
the configuration does not describe its block."""
import copy
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(run):
    import common

    m = run.cell.config
    if "block" not in m:
        return None
    view = copy.copy(run)
    view.cell = copy.copy(run.cell)
    view.cell.config = dict(m, intermediate_size=m[m["block"]["d_inner"]])
    return common.load_module(os.path.join(
        os.path.dirname(__file__), "moe_experts_roofline.py")).compute(view)
