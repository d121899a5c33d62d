"""Share of the traced slice's device seconds that the SLIDING layers'
cache costs: the gather through their ring and the attention over it,
the named scopes `paged_decoder/kv_gather/sliding` and
`paged_decoder/attention/sliding` of the resident decode step
(`serve_kv_gather_share` and `serve_attention_share` read the sums over
both kinds of layer).  Read like `serve_kv_gather_share`: nothing where
the program has no scope table or the step no such scope, or where
under 90% of the device seconds resolve to a `paged_decoder/` scope."""
import re

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
KIND = "sliding"


def cache_seconds(run, kind=None):
    """(device seconds under the gather and attention scopes of layer
    kind `kind`, or of every layer; all device seconds), or None."""
    from paddle_tpu import profiler

    if not run.trace or not hasattr(profiler, "scope_seconds"):
        return None
    by_scope = profiler.scope_seconds(run.trace["op_seconds"],
                                      "paged_decoder.step")
    total = sum(by_scope.values())
    named = sum(t for s, t in by_scope.items() if "paged_decoder/" in s)
    if not total or named < 0.9 * total:
        return None
    want = re.compile(r"paged_decoder/(kv_gather|attention)"
                      + (f"/{kind}(/|$)" if kind else "(/|$)"))
    return sum(t for s, t in by_scope.items() if want.search(s)), total


def kind_share(run, kind):
    got = cache_seconds(run, kind)
    # 0.0: the step has no such scope (every layer is of one kind)
    return 100.0 * got[0] / got[1] if got and got[0] else None


def compute(run):
    return kind_share(run, KIND)
