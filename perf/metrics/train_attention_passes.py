"""Mosaic kernel calls a layer under the Program op types
`flash_attention*` in the traced step: how many times a layer's score
tiles are walked.  4 where the grad op runs the forward kernel again and
then a dq and a dk/dv kernel, 3 where it uses what the forward saved, 2
where one kernel gives dq, dk and dv.

Read from the compiled text of the step the profiler holds a provider
for (`executor.block`, the text `train_attention_share`'s scope table is
made from): the instructions whose `custom_call_target` is
`tpu_custom_call`, whose `op_name` names a Program op of such a type
and whose name the trace's slice timed, over `num_hidden_layers`.  Of
several executables under the label (a startup and a main program) the
one with the most such calls counts.  Nothing without a device plane or
where the program holds no such text."""
import os
import re
import sys

LAYER = "kernels"
UNIT = "count"
MOVES = "train_throughput"
SOURCE = "device_trace"

MOSAIC = 'custom_call_target="tpu_custom_call"'
INSTRUCTION = re.compile(r"^\s*%?([\w.\-]+) = ")
OP_NAME = re.compile(r'metadata={[^}]*op_name="([^"]+)"')


def mosaic_calls(hlo_text):
    """{instruction name: op_name} of the text's Mosaic custom calls."""
    out = {}
    for line in hlo_text.splitlines():
        if MOSAIC not in line:
            continue
        name, meta = INSTRUCTION.match(line), OP_NAME.search(line)
        if name:
            out[name.group(1)] = meta.group(1) if meta else ""
    return out


def compute(run):
    import common
    from paddle_tpu import profiler

    op_type = common.load_module(os.path.join(
        os.path.dirname(__file__), "train_attention_share.py")).op_type

    providers = getattr(profiler, "_hlo_text_providers", None)
    if not run.trace or not run.trace["op_seconds"] or providers is None:
        return None
    timed = run.trace["op_seconds"]
    best = None
    for label, provider, _ in list(providers):
        if label != "executor.block":
            continue
        calls = [name for name, scope in mosaic_calls(provider()).items()
                 if name in timed and (op_type(scope) or "").startswith(
                     "flash_attention")]
        if best is None or len(calls) > len(best):
            best = calls
    if best is None:
        return None
    # for people: each kernel's seconds in the slice, by the call's place
    # in its op (`<op>_<output>.tmp_0.<n>`: the same n in every layer)
    by_place = {}
    for name in best:
        place = re.sub(r"_\d+(\.tmp_\d+\.\d+)$", r"_*\1", name)
        by_place.setdefault(place, []).append(timed[name])
    steps = run.trace["window_s"] / (1e-3 * (common.step_ms(run) or 1e300))
    for place, secs in sorted(by_place.items()):
        print(f"{__name__}: {place}: {len(secs)} calls a step, "
              f"{sum(secs):.6f} s in the slice, "
              f"{1e3 * sum(secs) / len(secs) / max(steps, 1e-9):.3f} ms a "
              f"call over {steps:.2f} steps", file=sys.stderr)
    return len(best) / run.cell.config["num_hidden_layers"]
