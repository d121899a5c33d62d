"""Pipeline-parallel transformer LM from the Program DSL.

The r4 feature end-to-end: annotate the model's block stack with
`fluid.pipeline_stage(i)` (transformer_lm does it for you via
`pipeline_stages=S`), then run the SAME Program either serially
(Executor — the annotation is inert) or pipelined over a {dp, pp} mesh
(parallel.PipelineExecutor, GPipe schedule, the Program's own optimizer
ops applying the update).  Reference analogue: per-layer device
placement via the `parallel_nn` flag
(/root/reference/paddle/gserver/gradientmachines/ParallelNeuralNetwork.h,
/root/reference/paddle/utils/Flags.cpp:37) — here it is a context
manager in the DSL instead of a gconf flag.

Run on the 8-device virtual CPU mesh (no TPU pod needed):

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/pipeline_transformer_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import parallel
from paddle_tpu.models.transformer import transformer_lm

VOCAB, SEQ, D_MODEL, LAYERS, STAGES = 64, 16, 32, 4, 4
DP = max(1, len(jax.devices()) // STAGES)


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[SEQ], dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[SEQ, 1], dtype="int64")
        logits = transformer_lm(ids, VOCAB, d_model=D_MODEL, n_heads=4,
                                n_layers=LAYERS, max_len=SEQ,
                                return_logits=True,
                                pipeline_stages=STAGES)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.reshape(logits, shape=[-1, VOCAB]),
                fluid.layers.reshape(lbl, shape=[-1, 1])))
        fluid.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    return main, startup, loss


def batch(r, n):
    ids = r.randint(0, VOCAB, (n, SEQ)).astype(np.int64)
    # learnable synthetic task: next token = (token + 1) mod vocab
    lbl = ((ids + 1) % VOCAB)[:, :, None]
    return {"ids": ids, "lbl": lbl}


def main():
    main_prog, startup, loss = build()
    pe = parallel.PipelineExecutor(
        main_prog, ["ids", "lbl"], [loss],
        mesh={"dp": DP, "pp": STAGES}, startup_program=startup,
        n_micro=2)
    r = np.random.RandomState(0)
    first = last = None
    for step in range(30):
        l, = pe.run(batch(r, 4 * DP))
        last = float(np.asarray(l).reshape(-1)[0])
        if first is None:
            first = last
        if step % 10 == 0:
            print(f"step {step:3d}  loss {last:.4f}")
    print(f"dp={DP} pp={STAGES}: {first:.4f} -> {last:.4f}")
    assert last < first, "pipelined training must reduce the loss"


if __name__ == "__main__":
    main()
