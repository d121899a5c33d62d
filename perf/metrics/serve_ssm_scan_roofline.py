"""Share of the HBM roofline that the Mamba-2 recurrence reaches: the
least seconds the chip could take to read and write the recurrent state
of the lanes that ran a position in the traced slice, over the device
seconds under `paged_decoder/ssm_scan` in that slice.

Bytes: `state_lanes` (lanes of a tick with a recurrent state; the
server puts it on `serving.decode_tick`) summed over the ticks whose
middle lies in the slice, times the Mamba layers, times one lane's
state read and written (`perf/ssm_bytes.py`: 2 x 4.19 MB at 128 heads
of 64, state 128, float32).  The recurrence is bound by memory: a
handful of operations a state element.  Seconds: the trace's seconds
per instruction joined with the step's scope table.  Nothing where the
program sets no such attribute or the job did not note the slice, where
the step has no such scope, or where under 90% of the device seconds
resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/ssm_scan"


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
    lanes = sum(s["attrs"]["state_lanes"]
                for s in tracing.finished_spans()
                if s["name"] == "serving.decode_tick"
                and "state_lanes" in s["attrs"]
                and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1])
    if not lanes:
        return None
    m = run.cell.config
    least = common.load_module(os.path.join(
        common.PERF_DIR, "ssm_bytes.py")).scan_bytes(
        lanes, m["layer_types"], m["mamba_n_heads"], m["mamba_d_head"],
        m["mamba_d_state"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
