"""Peak of `GenerationServer.stats()['kv_pool_utilization']` (blocks in use
over blocks in the pool), sampled four times a second in the window."""
LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def compute(run):
    peak = run.counters.get("kv_pool_util_peak")
    return 100.0 * peak if peak else None
