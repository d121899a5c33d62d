"""Job `train_trainer`: `Trainer.train` with its default options, fed by
a Python reader of float32 image rows through `DataFeeder`: the path of
the book examples and the v2 API.

The reader holds a few seeded batches in memory and yields each as a
list of (image, label) rows, as a v2 reader does; `DataFeeder` packs
them and `Executor.run` copies them to the device inside the step.  The
job does nothing to this loop but time it: the reader is wrapped, and
the event handler stamps `BeginIteration` and `EndIteration`.  With the
default options every iteration ends on the loss as a float, so each
`EndIteration` is a completed step.

The window opens when the reader is asked for the first batch after the
warm-up steps and closes on the last `EndIteration`; the reader stops
the pass when the window's seconds are over.

End-to-end reading: `train_reader_throughput`, images of the steps completed
in the window over the window's seconds of wall time.
"""
from __future__ import annotations

import math
import time

import common
import flops
import train_lib

# First step's loss (bf16 AMP convolutions over float32 master weights,
# float32 batch-norm statistics) against the float32 reference on the
# same batch and weights.  Every one of 53 convolutions rounds its
# inputs to bf16 and batch norm renormalises after each, so the error
# does not grow with depth: measured 3.5e-4 to 2.5e-3 relative at 224 x
# 224 and batch 256 on the v5e (my chip runs, PR 23; a 32 x 32 toy is
# 7 to 15% off, which is why the rehearsal runs without AMP).  A wrong
# stride or a missing block moves the loss by several percent; 1e-2
# is four times the worst bf16 reading.
LOSS_REL_TOL = 1e-2


def run(cell):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import trainer as trainer_mod
    from paddle_tpu.core.executor import xla_compile_counts

    run_ = common.Run()
    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    batch, warm = int(t["batch"]), int(t["warmup_steps"])
    if m["amp_bf16"]:
        fluid.amp.enable_bf16()

    main, startup, img, label, avg = train_lib.build_resnet(
        fluid, m, cell.seed)
    scope = fluid.global_scope()     # where Trainer keeps its state
    trainer = trainer_mod.Trainer(avg, place=place, feed_list=[img, label],
                                  main_program=main,
                                  startup_program=startup)
    cell.mark("program built")
    trainer.start()
    cell.mark("startup program run")
    batches = train_lib.image_batches(int(t["ring"]), batch, m, cell.seed)
    want = cell.reference().loss(
        train_lib.parameters(main, scope.find_var), *batches[0])

    cell.mark("batches and reference loss")

    tap = common.SpanTap()
    state = {"served": 0, "open": None, "deadline": None, "reader_s": 0.0,
             "wait_s": 0.0, "last_end": None, "c0": None, "trace": None}
    losses, done = [], []

    def reader():
        while True:
            t0 = time.perf_counter()
            if state["served"] == warm:
                # ---- the measured window opens ---------------------------
                if cell.trace:
                    tap.arm()
                    state["trace"] = common.TraceWindow(
                        cell, tap, float(t["trace_delay_seconds"]),
                        float(t["trace_seconds"]))
                state["c0"] = xla_compile_counts()
                t0 = state["open"] = state["last_end"] = time.perf_counter()
                state["deadline"] = t0 + cell.seconds
                if state["trace"] is not None:
                    state["trace"].start()
            elif state["deadline"] is not None and t0 >= state["deadline"]:
                return
            x, y = batches[state["served"] % len(batches)]
            rows = list(zip(x, y))
            state["served"] += 1
            if state["open"] is not None:
                state["reader_s"] += time.perf_counter() - t0
            yield rows

    def on_event(ev):
        now = time.perf_counter()
        if isinstance(ev, trainer_mod.BeginIteration):
            if state["open"] is not None:
                state["wait_s"] += now - state["last_end"]
        elif isinstance(ev, trainer_mod.EndIteration):
            losses.append(float(ev.cost))
            if state["open"] is not None:
                done.append(now)
                state["last_end"] = now

    trainer.train(1, reader, event_handler=on_event)
    run_.t_window_open = t_open = state["open"]
    run_.t_window_close = t_close = done[-1]
    c1 = xla_compile_counts()
    tap.disarm()
    stats = trainer.exe.cache_stats()
    if state["trace"] is not None:
        run_.trace = state["trace"].finish()
        run_.spans = tap.records
    n = len(done)
    run_.notes["reference"] = train_lib.compare_loss(losses[0], want,
                                                     LOSS_REL_TOL)
    bad = sum(1 for v in losses if not math.isfinite(v))
    run_.attempted, run_.failed = n, bad
    run_.end_to_end = {
        "train_reader_throughput": n * batch / (t_close - t_open)}
    run_.samples = {"step_done": [t_open] + done}
    run_.counters = {
        "steps": n, "items_per_step": batch,
        "input_wait_s": state["wait_s"], "reader_s": state["reader_s"],
        "compiles_in_window": c1["compiles"] - state["c0"]["compiles"],
        "executor_recompiles": stats["recompiles_after_warmup"],
        "train_flops_per_item": flops.resnet_train_flops_per_image(
            m["depth"], m["image_size"], m["num_classes"]),
        "loss_first": losses[0], "loss_last": losses[-1],
    }
    run_.correct = bool(run_.notes["reference"]["ok"] and bad == 0
                        and n > 0)
    trainer.exe.close()
    fluid.amp.disable_bf16()
    return run_
