"""Requests that started from a RESTORED snapshot of their lane's
state, of the requests that ended in the window: 100 x the count of
`serving.request` spans with `state_snapshots_restored` 1 over those
that carry the attribute.  100 where every prompt's prefix ends in a
cached block that still carries a snapshot; a cell where it falls says
the snapshots' LRU lost a document (its requests then run the whole
document again).  Nothing where the program sets no such attribute (a
program before PR 59, a block without a lane state, a server without a
prefix cache) or keeps no span store under a listener."""
LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    done = [s["attrs"]["state_snapshots_restored"]
            for s in tracing.finished_spans()
            if s["name"] == "serving.request"
            and lo <= s["ts"] + s["dur"] <= hi
            and "state_snapshots_restored" in s["attrs"]]
    return 100.0 * sum(done) / len(done) if done else None
