"""Share of the HBM roofline that the gated short convolutions reach:
the least seconds the chip could take to move what the conv mixers of
the traced slice's ticks had to move, over the device seconds under
their three scopes (`paged_decoder/conv_in_proj`, `conv_gate`,
`conv_out_proj`) in that slice.

Bytes (`perf/short_conv_bytes.py`): every conv layer's matrices once a
tick (33.6 MB a layer at hidden 2048 in bf16), and for each lane that
ran a position its float32 tail read and written back and the three
float32 rows of the gates; the ticks are the program's
`serving.decode_tick` spans that carry `conv_layers` and whose middle
lies in the slice, the lanes their `state_lanes`.  The mixer is bound
by memory: at 128 lanes its operations take half its bytes' time.
Seconds: the trace's seconds per instruction joined with the step's
scope table.  Nothing where the program sets no such attribute
(a block without conv layers) or the job did not note the slice, where
the step has no such scope, or where under 90% of the device seconds
resolve to a `paged_decoder/` scope."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/conv_"


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
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and "conv_layers" in s["attrs"]
             and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1]]
    if not ticks:
        return None
    m = run.cell.config
    least = common.load_module(os.path.join(
        common.PERF_DIR, "short_conv_bytes.py")).mixer_bytes(
        len(ticks), sum(a["state_lanes"] for a in ticks), m["layer_types"],
        m["hidden_size"], m["conv_L_cache"],
        2 if m["dtype"] == "bfloat16" else 4
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
