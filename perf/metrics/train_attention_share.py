"""Share of the traced slice's device seconds inside flash attention,
forward and gradient: the trace's seconds per HLO instruction joined with
the compiled block's scope table (`paddle_tpu.profiler.scope_seconds`),
each scope reduced to its innermost `<op type>:<output>` component (the
Executor names every Program op so) and counted where the type starts with
`flash_attention`.  An instruction the compiler made itself carries no
scope and counts under its producer's: stderr says how many points of the
share resolved that way.  Nothing where the program has no such table, or
where under 90% of the device seconds resolve to a Program op type."""
import sys

LAYER = "kernels"
UNIT = "%"
MOVES = "train_throughput"
SOURCE = "device_trace"


def op_type(scope):
    """`jit(fn)/mul:fc_0.tmp_0/dot_general` -> `mul`; None without a
    `<type>:<output>` component."""
    typed = [c for c in scope.split("/") if ":" in c]
    return typed[-1].split(":", 1)[0] if typed else None


def by_op_type(by_scope):
    out = {}
    for scope, t in by_scope.items():
        kind = op_type(scope)
        out[kind] = out.get(kind, 0.0) + t
    return out


def flash_seconds(by_type):
    return sum(t for k, t in by_type.items()
               if k and k.startswith("flash_attention"))


def compute(run):
    from paddle_tpu import profiler

    if not run.trace or not hasattr(profiler, "scope_seconds"):
        return None
    ops = run.trace["op_seconds"]
    by_type = by_op_type(profiler.scope_seconds(ops, "executor.block"))
    total = sum(by_type.values())
    named = total - by_type.get(None, 0.0)
    if not total:
        return None         # no device plane in the trace
    if named < 0.9 * total:
        print(f"{__name__}: {named:.3f} of {total:.3f} device seconds "
              "resolve to a Program op type: under 90%, no share",
              file=sys.stderr)
        return None
    mine = 100.0 * flash_seconds(by_type) / total
    theirs = 100.0 * flash_seconds(by_op_type(profiler.scope_seconds(
        ops, "executor.block", inherited_only=True))) / total
    print(f"flash_attention: {mine:.2f}% of the device seconds: "
          f"{mine - theirs:.2f} by the instructions' own metadata, "
          f"{theirs:.2f} by their producer's scope", file=sys.stderr)
    return mine
