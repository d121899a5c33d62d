"""Share of its roofline that decode attention over the LATENT RINGS of
the sliding layers reaches: the least seconds the chip could take to read
the ring rows of the traced slice's ticks, over the device seconds under
`paged_decoder/attention/sliding` (the ring's kernel calls) in that
slice.

Work: `ring_bytes` on the program's `serving.decode_tick` spans (the
ring rows the lanes with a sequence read, min(cursor + 1, the ring's
rows), times the ring's stored row, summed over the sliding layers),
summed over the ticks whose middle lies in the slice, taken back to ROWS
by the stored row's bytes of `perf/latent_ring_cost.py` (2304 B at 1024
+ 64 columns on the lane grid in bf16) and priced by that file: the
LARGER of the bytes over the HBM peak and the operations over the bf16
peak (117 operations a byte at 64 heads: the bytes bound it on a v5e,
ridge 240.5).  It counts rows read, not rows held, and none of the pages
an idle lane or a last page's tail costs, so it reads low, never above
what the chip did.  Nothing where the program sets no such attribute or
has no such scope (a block without a latent ring, a program before PR
65), the job did not note the slice, or the scope table does not
resolve."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/attention/sliding"


def compute(run):
    import common

    sparse = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_sparse_attention_roofline.py"))
    seconds, ring_bytes = sparse.scope_seconds(run, SCOPE), sparse.slice_sum(
        run, "ring_bytes")
    if not seconds or not ring_bytes:
        return None
    m = run.cell.config
    if "swa_kv_lora_rank" not in m:
        return None
    cost = common.load_module(os.path.join(
        common.PERF_DIR, "latent_ring_cost.py"))
    elem = {"bf16": 2, "fp32": 4}[run.cell.traffic["kv_dtype"]]
    sizes = m["swa_kv_lora_rank"], m["swa_qk_rope_head_dim"], elem
    need = cost.ring_call(ring_bytes / cost.stored_row_bytes(*sizes),
                          *sizes, n_heads=m["swa_num_attention_heads"])
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
