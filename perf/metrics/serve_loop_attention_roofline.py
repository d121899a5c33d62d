"""Share of the HBM roofline that the paged-attention kernel reaches
under a looped stack: the least seconds the chip could take to read the
K/V pages the traced slice's ticks attended over, over the device
seconds of the `paged_attention` calls in that slice.

Bytes: `kv_pages_read` on `serving.decode_tick` (pages the dispatched
step's attention reads, summed over slots and over all `kv_planes`
planes: every pass of every layer), summed over the ticks whose middle
lies in the slice, times one page's K and V (`perf/loop_bytes.py`: 2 x
64 KB).  Seconds: the trace's instructions named `paged_attention*`
(the kernel's `name`), not the scope, which also holds the
block-diagonal query and the heads' own columns around the call.
Nothing where the program sets no `kv_planes` (a step that is not
looped) or the job did not note the slice, or where the trace holds no
such call."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KERNEL = "paged_attention"


def compute(run):
    import common

    ticks = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_loop_weights_roofline.py")
    ).slice_ticks(run, "kv_planes")
    if ticks is None:
        return None
    seconds = sum(t for op, t in run.trace["op_seconds"].items()
                  if op.startswith(KERNEL))
    if not seconds:
        return None
    m, t = run.cell.config, run.cell.traffic
    least = sum(a["kv_pages_read"] for a in ticks) * common.load_module(
        os.path.join(common.PERF_DIR, "loop_bytes.py")).page_bytes(
        m, t["block_size"], {"bf16": 2, "fp32": 4}[t["kv_dtype"]]
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
