"""Share of its roofline that decode attention over the rows a lightning
indexer SELECTED reaches: the least seconds the chip could take to read
the selected rows of the traced slice's ticks, over the device seconds
under `paged_decoder/attention/selected` in that slice.

Work: `kv_rows_selected` on the program's `serving.decode_tick` spans
(min(cursor + 1, index_topk) summed over the lanes with a sequence and
the latent planes), summed over the ticks whose middle lies in the
slice, times the STORED row's bytes (`perf/sparse_attention_cost.py`:
1280 B at 512 + 64 columns on the lane grid in bf16), against the HBM
peak.  It counts the same rows and bytes whatever implements the read: a
kernel that copies every page under the cursor and masks reads low.
Nothing where the program sets no such attribute or has no such scope (a
block without an indexer, a program before PR 53), the job did not note
the slice, or the scope table does not resolve."""
import os

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/attention/selected"


def scope_seconds(run, scope):
    """Device seconds of the traced slice under the scopes that contain
    `scope`, or None (no table, under 90% resolved, or no such scope)."""
    from paddle_tpu import profiler

    if not run.trace or not hasattr(profiler, "scope_seconds"):
        return None
    by_scope = profiler.scope_seconds(run.trace["op_seconds"],
                                      "paged_decoder.step")
    total = sum(by_scope.values())
    named = sum(t for s, t in by_scope.items() if "paged_decoder/" in s)
    if not total or named < 0.9 * total:
        return None
    return sum(t for s, t in by_scope.items() if scope in s) or None


def slice_sum(run, attr):
    """The sum of `attr` over the `serving.decode_tick` spans whose
    middle lies in the traced slice, or None."""
    from paddle_tpu.observability import tracing

    slice_ = run.notes.get("trace_slice_wall")
    if not slice_:
        return None
    return sum(s["attrs"][attr] for s in tracing.finished_spans()
               if s["name"] == "serving.decode_tick"
               and attr in s["attrs"]
               and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1]) or None


def cost():
    import common

    return common.load_module(os.path.join(
        common.PERF_DIR, "sparse_attention_cost.py"))


def compute(run):
    seconds, rows = scope_seconds(run, SCOPE), slice_sum(
        run, "kv_rows_selected")
    m = run.cell.config
    if not seconds or not rows or "kv_lora_rank" not in m:
        return None
    need = cost().attention_call(
        rows, m["kv_lora_rank"], m["qk_rope_head_dim"],
        {"bf16": 2, "fp32": 4}[run.cell.traffic["kv_dtype"]])
    return 100.0 * need["bytes"] / run.peaks["hbm_bytes_per_s"] / seconds
