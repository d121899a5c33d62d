"""Milliseconds a call of the training step's FORWARD attention kernel:
the traced slice's device seconds of the Mosaic calls under the Program
op type `flash_attention` (the grad op's are `flash_attention_grad`),
over their count and over the steps the slice's busy seconds hold.  The
kernels are judged by their ms a call (ROADMAP S2):
`train_attention_share` rises whenever anything else in the step gets
faster.

The calls are `train_attention_passes`'s: the instructions of the
registered step's compiled text (`executor.block`) whose
`custom_call_target` is `tpu_custom_call`, whose `op_name` names such a
Program op and whose name the trace's slice timed; of several
executables under the label the one with the most such calls counts.
The steps are the slice's BUSY seconds over the measured step
(`common.step_ms`): the step is serial on the device and the device idle
0.02% of it, and a slice that holds a stall of the host (the device
idle a second: my chip run, PR 58) still reads the kernel's own time.
A slice of 3 s holds 14.6 steps and ends inside one, so it can hold a
step's forward calls without its backward's: the reading is good to a
few percent (1.651 and 1.583 on the same kernel, my chip runs, PR 58);
`tools/kernel_pace.py` times the kernel alone to four digits.  Nothing
without a device plane, where the program holds no such text, or where
no such call was timed."""
import os

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_throughput"
SOURCE = "device_trace"


def compute(run):
    import common
    from paddle_tpu import profiler

    here = os.path.dirname(__file__)
    op_type = common.load_module(os.path.join(
        here, "train_attention_share.py")).op_type
    mosaic_calls = common.load_module(os.path.join(
        here, "train_attention_passes.py")).mosaic_calls

    providers = getattr(profiler, "_hlo_text_providers", None)
    step_ms = common.step_ms(run)
    if (not run.trace or not run.trace["op_seconds"] or providers is None
            or not step_ms):
        return None
    timed = run.trace["op_seconds"]
    best = []
    for label, provider, _ in list(providers):
        if label != "executor.block":
            continue
        calls = [timed[name] for name, scope in mosaic_calls(
            provider()).items()
            if name in timed and op_type(scope) == "flash_attention"]
        if len(calls) > len(best):
            best = calls
    if not best:
        return None
    steps = run.trace["busy_s"] / (1e-3 * step_ms)
    return 1e3 * sum(best) / len(best) / steps
