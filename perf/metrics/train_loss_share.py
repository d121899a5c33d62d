"""Share of the traced slice's device seconds inside the language-model
loss, forward and gradient: `train_attention_share`'s reading (the
trace's seconds per HLO instruction joined with the compiled block's
scope table, `paddle_tpu.profiler.scope_seconds`, each scope reduced to
its innermost `<op type>:<output>` component) for the Program op types
that start with `softmax_with_cross_entropy`.  A fusion counts under the
scope of its ROOT, so what the compiler fuses of the loss's gradient into
the head's two gradient products counts with `mul_grad`: what is read
here are the passes over [tokens, vocab] that the loss makes on its own
(the upcast copy, log_p, the row reductions, the pick).  Nothing where
the program has no such table, or where under 90% of the device seconds
resolve to a Program op type."""
import os
import sys

LAYER = "kernels"
UNIT = "%"
MOVES = "train_throughput"
SOURCE = "device_trace"


def compute(run):
    import common
    from paddle_tpu import profiler

    by_op_type = common.load_module(os.path.join(
        os.path.dirname(__file__), "train_attention_share.py")).by_op_type

    if not run.trace or not hasattr(profiler, "scope_seconds"):
        return None
    by_type = by_op_type(profiler.scope_seconds(
        run.trace["op_seconds"], "executor.block"))
    total = sum(by_type.values())
    if not total:
        return None         # no device plane in the trace
    named = total - by_type.get(None, 0.0)
    if named < 0.9 * total:
        print(f"{__name__}: {named:.3f} of {total:.3f} device seconds "
              "resolve to a Program op type: under 90%, no share",
              file=sys.stderr)
        return None
    mine = {k: t for k, t in by_type.items()
            if k and k.startswith("softmax_with_cross_entropy")}
    for kind, t in sorted(mine.items()):
        print(f"{__name__}: {kind}: {t:.6f} s of {total:.6f} in the slice",
              file=sys.stderr)
    return 100.0 * sum(mine.values()) / total
