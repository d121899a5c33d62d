"""Prompt tokens served from the prefix cache, of the prompt tokens of
the requests that ended in the window: 100 x the sum of
`prefix_hit_tokens` over the sum of `prompt_tokens` on the program's
`serving.request` spans.  A hit position costs no tick: a request that
asks a cached document starts at the document's end.  0 where no prompt
shares a block with an earlier one; nothing where the program sets no
such attribute (a program before PR 53) or keeps no span store under a
listener."""
LAYER = "serving.kv_cache"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    done = [s["attrs"] for s in tracing.finished_spans()
            if s["name"] == "serving.request"
            and lo <= s["ts"] + s["dur"] <= hi
            and "prefix_hit_tokens" in s["attrs"]]
    prompt = sum(a["prompt_tokens"] for a in done)
    return (100.0 * sum(a["prefix_hit_tokens"] for a in done) / prompt
            if prompt else None)
