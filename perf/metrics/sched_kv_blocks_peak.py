"""Peak of KV pool blocks in use over blocks in the pool, read at every
tick from `kv_used` and `kv_total` on the program's `serving.decode_tick`
spans (`kv_pool_util_peak` polls four times a second and can miss a peak
between two polls).  Nothing where the program sets no such attribute."""
LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    used = [s["attrs"]["kv_used"] / s["attrs"]["kv_total"]
            for s in tracing.finished_spans()
            if s["name"] == "serving.decode_tick"
            and lo <= s["ts"] + s["dur"] <= hi and "kv_used" in s["attrs"]]
    return 100.0 * max(used) if used else None
