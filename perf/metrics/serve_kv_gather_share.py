"""Share of the traced slice's device seconds under the named scope
`paged_decoder/kv_gather` of the resident decode step: the gather of K and
V through the block table, its cast or dequantisation and the reshape.
The trace's seconds per HLO instruction are joined with the compiled
step's scope table (`paddle_tpu.profiler.scope_seconds`).  A fusion counts
under the scope of its root, so producers XLA fused into a consumer count
with the consumer.  An instruction the compiler made itself carries no
scope and counts under its producer's: stderr says how many points of the
share resolved that way and how many by the instruction's own metadata.
Nothing where the program has no such table, or where under 90% of the
device seconds resolve to a `paged_decoder/` scope."""
import sys

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"
SCOPE = "paged_decoder/kv_gather"


def scope_share(run, scope):
    """Percent of `run`'s device seconds under `paged_decoder/...`
    scope `scope` (`serve_attention_share` reads through here too)."""
    from paddle_tpu import profiler

    if not run.trace or not hasattr(profiler, "scope_seconds"):
        return None
    ops = run.trace["op_seconds"]
    by_scope = profiler.scope_seconds(ops, "paged_decoder.step")
    total = sum(by_scope.values())
    named = sum(t for s, t in by_scope.items() if "paged_decoder/" in s)
    if not total:
        return None         # no device plane in the trace
    if named < 0.9 * total:
        print(f"{scope}: {named:.3f} of {total:.3f} device seconds "
              "resolve to a paged_decoder/ scope: under 90%, no share",
              file=sys.stderr)
        return None
    inherited = profiler.scope_seconds(ops, "paged_decoder.step",
                                       inherited_only=True)
    mine, theirs = (
        100.0 * sum(t for s, t in d.items() if scope in s) / total
        for d in (by_scope, inherited))
    print(f"{scope}: {mine:.2f}% of the device seconds: "
          f"{mine - theirs:.2f} by the instructions' own metadata, "
          f"{theirs:.2f} by their producer's scope", file=sys.stderr)
    return mine


def compute(run):
    return scope_share(run, SCOPE)
