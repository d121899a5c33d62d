"""Share of the HBM roofline that the grouped expert matmuls reach: the
least seconds the chip could take to read the expert matrices the traced
slice actually touched, over the device seconds under
`paged_decoder/moe_experts` in that slice.

Bytes: `moe_experts_hit` (distinct experts routed to, summed over
layers; the step counts it on the device and the server puts it on
`serving.decode_tick`) summed over the ticks whose middle lies in the
slice, times one expert's three matrices (`perf/moe_flops.py`; 12.58 MB
at 2048 x 1024 in bf16).  The layer is bound by memory: at 256 rows its
operations (0.1 ms a layer at the bf16 peak) are a tenth of its bytes'
time.  Seconds: the trace's seconds per instruction joined with the
step's scope table.  Nothing where the program sets no such attribute or
the job did not note the slice, or where under 90% of the device seconds
resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/moe_experts"


def compute(run):
    import common
    from paddle_tpu import profiler
    from paddle_tpu.observability import tracing

    slice_ = run.notes.get("trace_slice_wall")
    if not run.trace or not slice_ or not hasattr(profiler,
                                                  "scope_seconds"):
        return None
    by_scope = profiler.scope_seconds(run.trace["op_seconds"],
                                      "paged_decoder.step")
    total = sum(by_scope.values())
    named = sum(t for s, t in by_scope.items() if "paged_decoder/" in s)
    seconds = sum(t for s, t in by_scope.items() if SCOPE in s)
    if not seconds or named < 0.9 * total:
        return None
    hit = sum(s["attrs"]["moe_experts_hit"]
              for s in tracing.finished_spans()
              if s["name"] == "serving.decode_tick"
              and "moe_experts_hit" in s["attrs"]
              and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1])
    if not hit:
        return None
    m = run.cell.config
    flops = common.load_module(os.path.join(common.PERF_DIR,
                                            "moe_flops.py"))
    least = hit * flops.expert_bytes(
        m["hidden_size"], m["intermediate_size"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
