"""Passes a training step makes over [tokens, vocab] that evaluate an
exponential: the instructions the traced slice timed whose own
operation, or whose fused computation, holds an `exponential` of at
least tokens x vocab elements.  At a language model's head that is the
forward's sum of exponentials plus every place `softmax - onehot` is
formed: 4 where the compiler clones that producer into the prologues of
the head's three gradient consumers (the hidden-state product, the
weight-gradient product, the bias's reduction), 2 where the gradient is
written once and the consumers read it.

Read like `train_attention_passes`: from the compiled text of the step
the profiler holds a provider for (`executor.block`).  Of several
executables under the label (a startup and a main program) the one with
the most such instructions counts.  Nothing without a device plane or
where the program holds no such text."""
import math
import re
import sys

LAYER = "kernels"
UNIT = "count"
MOVES = "train_throughput"
SOURCE = "device_trace"

COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
EXPONENTIAL = re.compile(r" = \w+\[([\d,]*)\][^ ]* exponential\(")
CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def exponential_passes(hlo_text, elements):
    """Names of the text's instructions that evaluate an `exponential`
    over `elements` or more: their own operation, or one inside the
    computation they call, however deep (the compiler nests a cloned
    producer as a fusion inside its consumer's fusion).  Instructions
    inside a fusion's body are not named: the device runs, and a trace
    times, the outermost one."""
    # (computation, instruction, own, callee) of those that matter
    lines = []
    computation = None
    for line in hlo_text.splitlines():
        head = COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        name = INSTRUCTION.match(line)
        if not name:
            continue
        exp = EXPONENTIAL.search(line)
        own = bool(exp) and math.prod(
            int(d) for d in exp.group(1).split(",") if d) >= elements
        called = CALLS.search(line)
        if own or called:
            lines.append((computation, name.group(1), own,
                          called.group(1) if called else None))
    bodies = {callee for _, _, _, callee in lines if callee}
    holds = {c for c, _, own, _ in lines if own}
    grew = True
    while grew:                 # a holder's callers hold it too
        more = {c for c, _, _, callee in lines if callee in holds} - holds
        holds |= more
        grew = bool(more)
    return [name for c, name, own, callee in lines
            if c not in bodies and (own or callee in holds)]


def compute(run):
    from paddle_tpu import profiler

    providers = getattr(profiler, "_hlo_text_providers", None)
    if not run.trace or not run.trace["op_seconds"] or providers is None:
        return None
    timed = run.trace["op_seconds"]
    t, m = run.cell.traffic, run.cell.config
    elements = (int(t["sequence_length"]) * int(t["sequences_per_step"])
                * int(m["vocab_size"]))
    best = None
    for label, provider, _ in list(providers):
        if label != "executor.block":
            continue
        passes = [n for n in exponential_passes(provider(), elements)
                  if n in timed]
        if best is None or len(passes) > len(best):
            best = passes
    if best is None:
        return None
    for name in sorted(best):       # for people: each pass's seconds
        print(f"{__name__}: {name}: {timed[name]:.6f} s in the slice",
              file=sys.stderr)
    return len(best)
