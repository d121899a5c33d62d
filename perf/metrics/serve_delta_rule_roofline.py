"""Share of the HBM roofline that the gated delta rule's recurrence
reaches: the least seconds the chip could take to move what the
recurrence of the traced slice's ticks had to move between its
projections, over the device seconds under `paged_decoder/delta_conv`,
`delta_gates`, `delta_rule` and `delta_gate_norm` in that slice.

Bytes (`perf/delta_rule_bytes.py`): for each lane that ran a position
and each delta-rule layer, its matrix state once in and once out (2 x
4.19 MB at 64 heads of 128, float32), its tail read and written and the
float32 rows between the projections; the lanes are `state_lanes` of
the program's `serving.decode_tick` spans that carry `delta_layers` and
whose middle lies in the slice.  The recurrence is bound by memory.
Seconds: the trace's seconds per instruction joined with the step's
scope table.  Nothing where the program sets no such attribute (a
program before PR 59, a block without delta-rule layers) or the job did
not note the slice, where the step has no such scope, or where under
90% of the device seconds resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPES = tuple("paged_decoder/delta_" + part for part in (
    "conv", "gates", "rule", "gate_norm"))


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
    seconds = sum(t for s, t in by_scope.items()
                  if any(part in s for part in SCOPES))
    if not seconds or named < 0.9 * total:
        return None
    lanes = sum(s["attrs"]["state_lanes"]
                for s in tracing.finished_spans()
                if s["name"] == "serving.decode_tick"
                and "delta_layers" in s["attrs"]
                and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1])
    if not lanes:
        return None
    m = run.cell.config
    lin = m["linear_attn_config"]
    least = common.load_module(os.path.join(
        common.PERF_DIR, "delta_rule_bytes.py")).rule_bytes(
        lanes, m["layer_types"], lin["num_heads"], lin["head_dim"],
        lin["short_conv_kernel_size"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
