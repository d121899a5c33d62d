"""Share of the window's dispatched ticks whose admission left the
queue's head WAITING FOR BLOCKS with a slot free: the mean of `kv_wait`
on the program's `serving.decode_tick` spans (1 where `PagedKVCache
.can_admit` refused the head of the queue while a slot stood empty).
Above 0 the pool, not the slot count, bounds the batch at that moment;
a cell sized so that no admission ever waits reads 0.  Nothing where
the program sets no such attribute or keeps no span store under a
listener."""
LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"
ATTR = "kv_wait"


def window_mean(run, attr):
    """Mean of `attr` over the window's `serving.decode_tick` spans
    that carry it, or None."""
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    got = [s["attrs"][attr] for s in tracing.finished_spans()
           if s["name"] == "serving.decode_tick"
           and lo <= s["ts"] + s["dur"] <= hi and attr in s["attrs"]]
    return sum(got) / len(got) if got else None


def compute(run):
    mean = window_mean(run, ATTR)
    return None if mean is None else 100.0 * mean
