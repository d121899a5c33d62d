"""Share of the HBM roofline that a looped stack's weights reach: the
least seconds the chip could take to read the stack's weights once a
PASS for every tick of the traced slice, over the slice's device-busy
seconds.

Bytes: `loop_passes` on `serving.decode_tick` (the passes the dispatched
step runs), summed over the ticks whose middle lies in the slice, times
one stack's weight bytes (`perf/loop_bytes.py`: 4.93 GB at 48 layers in
bfloat16).  At 12 rows a matmul the stack is bound by memory.  Seconds:
ALL the device's busy seconds of the slice (`busy_s`), not those under
`qkv`, `attn_out` and `mlp` alone: the compiler streams a matmul's
weight in slices that are in flight under whatever runs before it (the
attention kernel, the norms), so the matmuls' own seconds leave out
part of the streaming and a share over them alone read 137% (my chip
run, PR 38).  It says what part of a tick the four passes over the same
weights explain at the memory's speed; the rest is what a tick spends
on work that no weight streams under.  Nothing where the program sets
no such attribute or the job did not note the slice, or where under 90%
of the device seconds resolve to a `paged_decoder/` scope (the slice is
then not the step's)."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def slice_ticks(run, attr):
    """Attributes of the `serving.decode_tick` spans that carry `attr`
    and whose middle lies in the traced slice, or None where there is
    none, no slice, or under 90% of the slice's device seconds resolve
    to a `paged_decoder/` scope."""
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
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick" and attr in s["attrs"]
             and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1]]
    if not total or named < 0.9 * total:
        return None
    return ticks or None


def compute(run):
    import common

    ticks = slice_ticks(run, "loop_passes")
    if ticks is None or not run.trace["busy_s"]:
        return None
    least = sum(a["loop_passes"] for a in ticks) * common.load_module(
        os.path.join(common.PERF_DIR, "loop_bytes.py")).stack_weight_bytes(
        run.cell.config) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / run.trace["busy_s"]
