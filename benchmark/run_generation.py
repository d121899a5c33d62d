"""Autoregressive decode throughput: full-forward loop vs KV-cache loop.

No reference analogue (the reference's generation path is host-side beam
search over LoD); this benchmarks the transformer serving path added by
models/transformer.py (build_lm_generator / build_lm_kv_decoder).

Usage: python benchmark/run_generation.py [--batch 8] [--ctx 512]
       [--prompt 16] [--d-model 512] [--layers 6] [--heads 8] [--iters 3]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB = 32000


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ctx", type=int, default=512)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--beam", type=int, default=4,
                    help="beam width for the beam-search row")
    a = ap.parse_args()

    import jax

    import paddle_tpu as fluid
    import paddle_tpu.core.framework as fw
    from paddle_tpu.models.transformer import (build_lm_generator,
                                               build_lm_kv_decoder)

    steps = a.ctx - a.prompt
    r = np.random.RandomState(0)
    # distinct prompt per iteration: no timed dispatch repeats an
    # (executable, inputs) pair
    prompts = [r.randint(0, VOCAB, (a.batch, a.prompt)).astype(np.int32)
               for _ in range(a.iters + 1)]

    from paddle_tpu.models.transformer import build_lm_beam_search

    results = {}
    beam = max(1, a.beam)
    for name, builder in (("full_forward", build_lm_generator),
                          ("kv_cache", build_lm_kv_decoder),
                          (f"beam_search_k{beam}", None)):
        fw.reset_unique_names()
        if builder is not None:
            startup, gen = builder(VOCAB, a.ctx, d_model=a.d_model,
                                   n_heads=a.heads, n_layers=a.layers)
        else:
            # on-device static-shape beam search: the beam is a [B, K]
            # lane structure folded into the batch, ONE jit for the
            # whole search — the architecture replacing the reference's
            # host-side beam_search ops (beam_search_op.cc LoD loop)
            startup, gen = build_lm_beam_search(
                VOCAB, a.ctx, beam_size=beam, d_model=a.d_model,
                n_heads=a.heads, n_layers=a.layers)
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        states = {n: jax.device_put(np.asarray(scope.find_var(n)))
                  for n in gen.state_names}
        out = gen(states, prompts[-1], steps)      # compile + warmup
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for i in range(a.iters):
            out = gen(states, prompts[i], steps)
            jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / a.iters
        tok_s = a.batch * steps / dt
        row = {
            "bench": "decode", "mode": name, "batch": a.batch,
            "ctx": a.ctx, "d_model": a.d_model, "layers": a.layers,
            "decode_tokens_per_sec": round(tok_s, 1),
            "ms_per_token": round(dt / steps * 1000, 3),
            # the whole decode loop is ONE dispatch (lax.fori_loop inside
            # one jit), so host cost is one dispatch + one sync
            # per `steps` tokens — the time is chip time, not round-trips
            "dispatches_per_iter": 1,
            "tokens_per_dispatch": steps}
        if builder is None:
            # beam search scores `beam` hypotheses per emitted position
            row["beam_size"] = beam
            row["hypothesis_tokens_per_sec"] = round(tok_s * beam, 1)
        results[name] = tok_s
        print(json.dumps(row))
    if "kv_cache" in results:
        print(json.dumps({
            "bench": "decode", "kv_speedup_vs_full":
            round(results["kv_cache"] / results["full_forward"], 2),
            f"beam{beam}_vs_full_forward":
            round(results[f"beam_search_k{beam}"]
                  / results["full_forward"], 2)}))


if __name__ == "__main__":
    main()
