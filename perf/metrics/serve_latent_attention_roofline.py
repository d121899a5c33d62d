"""Share of its roofline that decode attention over the LATENT cache
reaches: the least seconds the chip could take for the rows the traced
slice's ticks attended over, over the device seconds under
`paged_decoder/attention` (the kernel's calls) in that slice.

Work: `latent_rows` on the program's `serving.decode_tick` spans (cursor
+ 1 summed over the lanes with a sequence and the layers), summed over
the ticks whose middle lies in the slice, times a row's operations and
bytes (`perf/latent_attention_cost.py`: 278 528 operations and 1152
bytes at 128 heads on 512 + 64 columns in bf16).  The least seconds are
the LARGER of the bytes over the HBM peak and the operations over the
bf16 peak: at 242 operations a byte the two lie within a hundredth of
each other on the v5e (ridge 240.5), so either can bound the kernel.
Nothing where the program sets no such attribute (a block without a
latent cache, a program before PR 45) or the job did not note the slice,
or where the scope table does not resolve."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(run):
    import common
    from paddle_tpu.observability import tracing

    slice_ = run.notes.get("trace_slice_wall")
    got = common.load_module(os.path.join(
        os.path.dirname(__file__), "serve_window_layers_share.py")
    ).cache_seconds(run) if slice_ else None
    if not got or not got[0]:
        return None
    rows = sum(s["attrs"]["latent_rows"]
               for s in tracing.finished_spans()
               if s["name"] == "serving.decode_tick"
               and "latent_rows" in s["attrs"]
               and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1])
    m = run.cell.config
    if not rows or "kv_lora_rank" not in m:
        return None
    need = common.load_module(os.path.join(
        common.PERF_DIR, "latent_attention_cost.py")).attention_call(
        rows, m["num_attention_heads"], m["kv_lora_rank"],
        m["qk_rope_head_dim"],
        {"bf16": 2, "fp32": 4}[run.cell.traffic["kv_dtype"]])
    least = max(need["bytes"] / run.peaks["hbm_bytes_per_s"],
                need["flops"] / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / got[0]
