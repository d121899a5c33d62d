"""Train a decoder-only transformer LM with the TPU-first feature set
composed: bf16 amp, a rematerialized (jax.checkpoint) transformer body,
and data-parallel mesh execution.

Run (CPU demo, 8 virtual devices):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/transformer_lm.py

On a TPU pod slice, run one process per host with
`paddle_tpu.parallel.mesh.init_distributed()` (see tools/launch.py) and
the same script scales over ICI without changes.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import parallel
from paddle_tpu.models.transformer import transformer_lm

VOCAB, SEQ, BATCH, STEPS = 1000, 64, 32, 30


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[SEQ], dtype="int64")
        nxt = fluid.layers.data(name="nxt", shape=[SEQ, 1], dtype="int64")
        # rematerialize the transformer body: its activations re-run in
        # backward instead of living in HBM (layers.recompute)
        probs = fluid.layers.recompute(
            lambda: transformer_lm(ids, VOCAB, d_model=128, n_heads=4,
                                   n_layers=2))
        probs2d = fluid.layers.reshape(probs, shape=[-1, VOCAB])
        lbl2d = fluid.layers.reshape(nxt, shape=[-1, 1])
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=probs2d, label=lbl2d))
        fluid.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss


def main():
    fluid.amp.enable_bf16()          # bf16 compute, f32 master weights
    main_prog, startup, loss = build()

    n = len(__import__("jax").devices())
    pe = parallel.ParallelExecutor(main_prog, ["ids", "nxt"], [loss],
                                   mesh={"dp": n},
                                   startup_program=startup)
    r = np.random.RandomState(0)
    # synthetic periodic data the model can actually learn
    base = np.arange(BATCH * SEQ).reshape(BATCH, SEQ) % 97
    for step in range(STEPS):
        ids = ((base + step) % 97).astype(np.int32)
        nxt = ((base + step + 1) % 97).astype(np.int32)[..., None]
        out, = pe.run({"ids": ids, "nxt": nxt})
        if step % 5 == 0:
            print(f"step {step:3d}  loss "
                  f"{np.asarray(out).reshape(-1)[0].item():.4f}")
    print("final loss", np.asarray(out).reshape(-1)[0].item())


if __name__ == "__main__":
    main()
