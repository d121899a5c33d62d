"""Share of the HBM roofline that decode attention reaches: the least
seconds the chip could take to read the K and V that the traced slice's
ticks had to attend over, over the device seconds under the gather and
attention scopes (`paged_decoder/kv_gather`, `paged_decoder/attention`,
both kinds of layer) in that slice.

Bytes: per slot and layer `2 x (cursor + 1)` rows on a full layer and
`2 x min(cursor + 1, window)` on a sliding one (`perf/attention_bytes
.py`), a row the K/V heads side by side in the pool's type; the server
puts the cursors' sums on `serving.decode_tick` as `kv_rows_full` and
`kv_rows_win`, and they are summed over the ticks whose middle lies in
the slice.  Attention at one query a slot is bound by memory (its
operations are a hundredth of its bytes' time).  A gather through the
whole table reads rows past the cursor and writes a copy that the
attention reads back: that is why this share is low, and what a kernel
that skips blocks would raise.  Nothing where the program sets no such
attribute or the job did not note the slice, or where the scope table
does not resolve."""
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
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and "kv_rows_full" in s["attrs"]
             and slice_[0] <= s["ts"] + s["dur"] / 2 < slice_[1]]
    if not ticks:
        return None
    m = run.cell.config
    least = common.load_module(os.path.join(
        common.PERF_DIR, "attention_bytes.py")).kv_read_bytes(
        sum(a["kv_rows_full"] for a in ticks),
        sum(a["kv_rows_win"] for a in ticks), m["layer_types"],
        m["num_key_value_heads"], m["head_dim"],
        {"bf16": 2, "fp32": 4}[run.cell.traffic["kv_dtype"]]
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / got[0]
