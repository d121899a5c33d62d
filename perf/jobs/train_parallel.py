"""Job `train_parallel`: data parallelism through `ParallelExecutor`
over a mesh given in the traffic file (`{"dp": 4}`), fed a ring of
seeded global batches from the host through `pe.run`, which shards and
copies each to the chips.

The loop is the one a user writes: `pe.run(feed)` and read the loss.
`pe.run(..., return_numpy=False)` returns at dispatch, so the loop
waits for the step two before it, as `train_executor` does, and the
window closes on `block_until_ready` of the last step.

The first warm-up batch is one chip's 256 rows repeated on every chip:
batch norm under GSPMD takes its statistics over the global batch, so
on that batch the global loss equals the loss of one shard alone, which
the reference computes on one chip at 256 rows.  The ring that the
window runs holds distinct global batches.

End-to-end reading: `train_throughput`, images of the steps completed
in the window over the window's seconds of wall time, all chips
together.
"""
from __future__ import annotations

import math

import numpy as np

import common
import flops
import train_lib

# as in train_trainer: bf16 AMP against the float32 reference
LOSS_REL_TOL = 1e-2


def run(cell):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.core.executor import xla_compile_counts

    run_ = common.Run()
    m, t = cell.config, cell.traffic
    mesh = {k: int(v) for k, v in t["mesh"].items()}
    n_dev = int(np.prod(list(mesh.values())))
    per_chip = int(t["batch_per_chip"])
    batch = per_chip * n_dev
    if m["amp_bf16"]:
        fluid.amp.enable_bf16()

    main, startup, img, label, avg = train_lib.build_resnet(
        fluid, m, cell.seed)
    cell.mark("program built")
    pe = parallel.ParallelExecutor(main, ["img", "label"], [avg],
                                   mesh=mesh, startup_program=startup)
    cell.mark("ParallelExecutor built (host startup, placement)")
    ring = [{"img": x, "label": y.astype(np.int32)}
            for x, y in train_lib.image_batches(int(t["ring"]), batch, m,
                                                cell.seed)]
    shard = {k: v[:per_chip] for k, v in ring[0].items()}
    want = cell.reference().loss(
        train_lib.parameters(
            main, lambda n: pe.state(n, return_numpy=False)),
        shard["img"], shard["label"])
    tiled = {k: np.concatenate([v] * n_dev) for k, v in shard.items()}
    cell.mark("batches and reference loss")

    def step(feed):
        return pe.run(feed, return_numpy=False)[0]

    first = float(np.asarray(step(tiled)).ravel()[0])
    run_.notes["reference"] = train_lib.compare_loss(first, want,
                                                     LOSS_REL_TOL)
    cell.mark("first step (compile or cache load)")
    collectives = pe.compiled_collectives(tiled)
    run_.notes["collectives"] = collectives
    del tiled
    for i in range(1, int(t["warmup_steps"])):
        jax.block_until_ready(step(ring[i % len(ring)]))

    tap = common.SpanTap()
    trace = None
    if cell.trace:
        tap.arm()
        trace = common.TraceWindow(cell, tap,
                                   float(t["trace_delay_seconds"]),
                                   float(t["trace_seconds"]))
    c0 = xla_compile_counts()
    # ---- the measured window ---------------------------------------------
    t_open, t_close, losses, done = train_lib.pipelined_window(
        lambda n: step(ring[n % len(ring)]), cell.seconds,
        trace.start if trace is not None else None)
    run_.t_window_open, run_.t_window_close = t_open, t_close
    c1 = xla_compile_counts()
    tap.disarm()
    if trace is not None:
        run_.trace = trace.finish()
        run_.spans = tap.records
    values = [float(np.asarray(v).ravel()[0]) for v in losses]
    n = len(values)
    bad = sum(1 for v in values if not math.isfinite(v))
    run_.attempted, run_.failed = n, bad
    run_.end_to_end = {"train_throughput": n * batch / (t_close - t_open)}
    run_.samples = {"step_done": done}
    run_.counters = {
        "steps": n, "items_per_step": batch,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "train_flops_per_item": flops.resnet_train_flops_per_image(
            m["depth"], m["image_size"], m["num_classes"]),
        "loss_first": first,
        "loss_window_last": values[-1] if values else None,
    }
    run_.correct = bool(run_.notes["reference"]["ok"] and bad == 0
                        and n > 0
                        and collectives.get("all-reduce", 0) >= 1)
    pe.close()
    fluid.amp.disable_bf16()
    return run_
