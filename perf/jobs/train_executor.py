"""Job `train_executor`: a language model trained through
`Executor.run` with bf16 AMP and Adam, on token batches that are
resident on the device, so that the step and its kernels do all the
work and no reader is in the way.

The loop dispatches a step and then waits for the step two before it,
which keeps the device's queue at most two deep and gives one
completion stamp a step.  The window opens on a drained device and
closes when the last step it dispatched has finished
(`block_until_ready`), so dispatched and unfinished work is never
counted.

End-to-end reading: `train_throughput`, tokens of the steps completed
in the window over the window's seconds of wall time.
"""
from __future__ import annotations

import math

import numpy as np

import common
import flops
import train_lib

# First step's loss (bf16 AMP: bf16 matrix multiplications and flash
# attention over float32 master weights) against the float32 reference
# on the same batch and weights.  At random initialisation the loss is
# ln(vocab) = 10.8 plus a little, and the mean over 8192 tokens
# averages the bf16 rounding of single logits away: measured 4e-7 to
# 7e-6 relative on the v5e (my chip runs, PR 23).  A wrong mask, a
# missing block or positions off by one move the loss by 1e-3 or more,
# so 1e-4 separates them with a factor of ten on each side.
LOSS_REL_TOL = 1e-4


def token_batches(n: int, batch: int, seq: int, vocab: int, seed: int):
    """A ring of `n` batches made on the device in one jitted call:
    ids [B, S] and next-token labels [B, S, 1]."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gen(key):
        toks = jax.random.randint(key, (n, batch, seq + 1), 0, vocab,
                                  jnp.int32)
        return toks[:, :, :-1], toks[:, :, 1:, None]

    ids, lbl = gen(jax.random.key(common.seed31(seed) ^ 0x70C5))
    return [{"ids": ids[i], "lbl": lbl[i]} for i in range(n)]


def run(cell):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import xla_compile_counts

    run_ = common.Run()
    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    seq, batch = int(t["sequence_length"]), int(t["sequences_per_step"])
    if m["amp_bf16"]:
        fluid.amp.enable_bf16()

    main, startup, avg = train_lib.build_lm(fluid, m, seq, cell.seed)
    scope = fluid.Scope()
    exe = fluid.Executor(place)
    cell.mark("program built")
    exe.run(startup, scope=scope)
    cell.mark("startup program run")
    ring = token_batches(int(t["ring"]), batch, seq, m["vocab_size"],
                         cell.seed)

    # the reference first: the first step updates the weights in place
    ref = cell.reference()
    want = ref.loss(
        ref.structure(train_lib.parameters(main, scope.find_var),
                      m["num_hidden_layers"]),
        np.asarray(ring[0]["ids"]), np.asarray(ring[0]["lbl"]),
        m["num_attention_heads"])

    cell.mark("reference loss")

    def step(i):
        return exe.run(main, feed=ring[i % len(ring)], fetch_list=[avg],
                       scope=scope, return_numpy=False)[0]

    first = float(np.asarray(step(0)).ravel()[0])
    run_.notes["reference"] = train_lib.compare_loss(first, want,
                                                     LOSS_REL_TOL)
    cell.mark("first step (compile or cache load)")
    for i in range(1, int(t["warmup_steps"])):
        jax.block_until_ready(step(i))
    stats0 = exe.cache_stats()

    tap = common.SpanTap()
    trace = None
    if cell.trace:
        tap.arm()
        trace = common.TraceWindow(cell, tap,
                                   float(t["trace_delay_seconds"]),
                                   float(t["trace_seconds"]))
    c0 = xla_compile_counts()
    # ---- the measured window ---------------------------------------------
    warm = int(t["warmup_steps"])
    t_open, t_close, losses, done = train_lib.pipelined_window(
        lambda n: step(warm + n), cell.seconds,
        trace.start if trace is not None else None)
    run_.t_window_open, run_.t_window_close = t_open, t_close
    c1 = xla_compile_counts()
    tap.disarm()
    stats1 = exe.cache_stats()
    if trace is not None:
        run_.trace = trace.finish()
        run_.spans = tap.records
    values = [float(np.asarray(v).ravel()[0]) for v in losses]
    n = len(values)
    finite = all(math.isfinite(v) for v in values)
    tokens = batch * seq
    run_.attempted, run_.failed = n, sum(
        1 for v in values if not math.isfinite(v))
    run_.end_to_end = {"train_throughput": n * tokens / (t_close - t_open)}
    run_.samples = {"step_done": done}
    run_.counters = {
        "steps": n, "items_per_step": tokens,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "executor_recompiles": stats1["recompiles_after_warmup"]
        - stats0["recompiles_after_warmup"],
        "train_flops_per_item": flops.lm_train_flops_per_token(
            m["hidden_size"], m["ffn_dim"], m["num_hidden_layers"],
            m["vocab_size"], seq),
        "loss_first": first, "loss_window_first": values[0] if values
        else None, "loss_window_last": values[-1] if values else None,
    }
    run_.correct = bool(run_.notes["reference"]["ok"] and finite and n > 0)
    exe.close()
    fluid.amp.disable_bf16()
    return run_
