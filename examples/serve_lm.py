"""Train a tiny LM, then serve it three ways: greedy full-forward decode,
KV-cache incremental decode (the fast path, token-identical), and beam
search — all on-device, single-jit loops (docs/design/generation.md).

Run:  JAX_PLATFORMS=cpu python examples/serve_lm.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
import paddle_tpu.core.framework as fw
from paddle_tpu.models.transformer import (
    build_lm_beam_search,
    build_lm_generator,
    build_lm_kv_decoder,
    transformer_lm,
)

V, L, B = 16, 16, 32
ARCH = dict(d_model=48, n_heads=2, n_layers=1)


def train():
    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[L], dtype="int64")
        nxt = fluid.layers.data(name="nxt", shape=[L, 1], dtype="int64")
        probs = transformer_lm(ids, V, max_len=L, **ARCH)
        loss = fluid.layers.mean(fluid.layers.cross_entropy(
            input=fluid.layers.reshape(probs, shape=[-1, V]),
            label=fluid.layers.reshape(nxt, shape=[-1, 1])))
        fluid.Adam(learning_rate=5e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    for step in range(200):
        starts = r.randint(0, V, (B, 1))
        seq = (starts + np.arange(L + 1)) % V       # successor language
        out, = exe.run(main, feed={
            "ids": seq[:, :L].astype(np.int32),
            "nxt": seq[:, 1:, None].astype(np.int32)},
            fetch_list=[loss], scope=scope)
        if step % 50 == 0:
            print(f"train step {step:3d} "
                  f"loss {np.asarray(out).reshape(-1)[0].item():.3f}")
    return scope


def main():
    scope = train()
    prompt = np.array([[3, 4, 5, 6]], np.int32)

    fw.reset_unique_names()
    _, gen = build_lm_generator(V, L, **ARCH)
    states = {n: np.asarray(scope.find_var(n)) for n in gen.state_names}
    print("greedy (full forward):", np.asarray(
        gen(states, prompt, num_steps=8))[0, :12])

    fw.reset_unique_names()
    _, kv = build_lm_kv_decoder(V, L, **ARCH)
    print("greedy (KV cache):    ", np.asarray(
        kv(states, prompt, num_steps=8))[0, :12])

    fw.reset_unique_names()
    _, beam = build_lm_beam_search(V, L, beam_size=4, **ARCH)
    ids, scores = beam(states, prompt, num_steps=8)
    print("beam-4 best:          ", np.asarray(ids)[0, 0, :12],
          " score", float(np.asarray(scores)[0, 0]))


if __name__ == "__main__":
    main()
