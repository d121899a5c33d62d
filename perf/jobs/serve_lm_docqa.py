"""Job `serve_lm_docqa`: document sessions on a latent table whose rows a
LIGHTNING INDEXER selects (`BlockSpec.index_topk`), with the PREFIX CACHE
on: a few long documents are built once in set-up through the server's
own `submit`, and every request of the closed loop is one of them plus a
fresh question and an answer, so that its prompt's blocks up to the
document's end are prefix hits (latent rows and index keys alike).

Imported unedited: the clients, the permutation of the length table, the
window and its accounting (`serve_closed`, `serve_lm_closed.measure`,
`count_slot_ticks`, `block_of`), the recorded streams, the lowered
step's kernel flag and the comparison before the window
(`serve_lm_ring`), the walk through lane 0 of the served step and the
reference warmed on threads (`serve_lm_latent`), the walks that read
every layer's router scores and the float32 zero bias
(`serve_lm_latent_bias`), the sign rule (`serve_lm_balanced.fit`).

Its own, because no job has them:
  `make_weights`  `serve_lm_latent.make_weights`'s arrays, letter for
        letter, its two dozen small programs compiled on threads (a run
        that starts with no compiled program spent 43 s of its 360
        compiling them one after the other).
  `build_server`  `serve_lm_closed.build_server` with the pool the
        traffic file gives (`pool_blocks`: documents are shared, so the
        pool is not lanes x context).
  `balance`  K-EXAONE's fit of the sigmoid router's choice bias over a
        ONE-table walk.
  `build_documents`  the 16 documents through `GenerationServer.submit(
        document, max_new_tokens=1)`, all at once, before any client.
  `DocLoad`  client c's k-th request asks document (c + k) mod 16.
  `check_served`  after the window: delivered tokens of requests on at
        least two documents against the reference's logits at the
        positions that sampled them (rows from cached prefix blocks,
        most of them dropped by the selection).  The requests come from
        the documents of `served.document_lengths` and are padded to
        `served.padded`, ONE length known before the window, so that
        `warm_served` compiles the reference's programs for it on a
        thread under the comparison's walk (a run with no compiled
        program spent 25 s of its 360 compiling them after the window;
        under the documents' build they would count as the server's
        recompiles after its warm-up).
"""
from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
latent = common.load_module(os.path.join(_HERE, "serve_lm_latent.py"))
latent_bias = common.load_module(os.path.join(_HERE,
                                              "serve_lm_latent_bias.py"))
balanced = common.load_module(os.path.join(_HERE, "serve_lm_balanced.py"))
ring = latent.ring
base = ring.base
serve_closed = base.serve_closed

# positions each lane walks for the fit (tokens: lanes x positions), and
# the passes, as `serve_lm_balanced`'s.  Tried on the chip and NOT kept
# (my chip runs, PR 53, six seeds each): a last pass on four walks'
# tokens (`serve_lm_latent_bias`'s recipe) and a last pass on the step's
# own greedy answers: `itl_p95_ms` spread 0.63% as here, 0.78% and 0.94%
# with them, the same seeds landing 0.1 ms either side of each other:
# the spread is the machine's two paces (PERF.md section 6), not the fit's.
FIT_POSITIONS, FIT_PASSES = latent.FIT_POSITIONS, 3


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_lm_latent.make_weights`'s arrays, letter for letter (the
    same committed key folded by the name's place, normal(0, 0.02)
    matrices, norm scales 1 + that, the embedding at sigma 1, a slice
    of the leading axis at a time), its programs (one a shape: two
    dozen here) compiled and run from a pool of threads: a machine
    with no compiled program compiles them side by side."""
    import math
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    def gen(key, shape, sigma, scale):
        parts = math.gcd(shape[0], 64)

        def part(k):
            v = sigma * jax.random.normal(
                k, (shape[0] // parts,) + shape[1:], jnp.float32)
            return ((1.0 + v) if scale else v).astype(dtype)

        return jax.lax.map(part, jax.random.split(key, parts)).reshape(
            shape)

    gen = jax.jit(gen, static_argnums=(1, 2, 3))
    key = jax.device_put(jax.random.key(common.seed31(seed)),
                         jax.devices()[0])
    names = sorted(shapes)

    def make(i):
        n = names[i]
        return gen(jax.random.fold_in(key, i), tuple(shapes[n]),
                   latent.SIGMA_EMBEDDING if n == "tok_embedding.w_0"
                   else latent.SIGMA, ".scale_" in n)

    with ThreadPoolExecutor(max_workers=8,
                            thread_name_prefix="perf-weights") as pool:
        return dict(zip(names, pool.map(make, range(len(names)))))


def sigmoid_of(inputs, w):
    """sigmoid(inputs @ w) a layer, in float32 at `highest`."""
    import jax
    import jax.numpy as jnp

    return jax.nn.sigmoid(jnp.einsum(
        "lsd,lde->lse", inputs, w, precision=jax.lax.Precision.HIGHEST))


def balance(cell, dec, g, n_tokens: int) -> dict:
    """Fit every sparse layer's choice bias in `g`, in place, from the
    float32 zeros `unbiased` left there: `serve_lm_balanced.fit`'s sign
    rule over the sigmoid scores of `FIT_POSITIONS` x lanes seeded
    tokens walked through the served step, `FIT_PASSES` times (a
    layer's router input depends on the biases of the layers before
    it).  -> the largest expert's load over the mean, a layer, under no
    bias and as the last walk found the fitted one."""
    import jax
    import jax.numpy as jnp

    m, slots = cell.config, int(cell.traffic["slots"])
    k = int(m["num_experts_per_tok"])
    rng = np.random.default_rng([common.seed31(cell.seed), 0xB1A5])
    toks = rng.integers(0, m["vocab_size"],
                        (FIT_POSITIONS, slots)).astype(np.int32)
    names = sorted((n for n in g if n.endswith("router_bias.b_0")),
                   key=lambda n: int(n.split(".")[0].split("_")[1]))
    routers = [n.replace("router_bias.b_0", "router.w_0") for n in names]
    fit_ = jax.jit(balanced.fit, static_argnums=(1, 2))
    scores_of = jax.jit(sigmoid_of)

    @jax.jit
    def worst(scores, bias):
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        load = jnp.zeros(scores.shape[-1]).at[chosen.reshape(-1)].add(1.0)
        return load.max() / load.mean()

    found = []
    for _ in range(FIT_PASSES):
        scores = latent_bias.router_probs(
            dec, g, routers, toks, slots,
            latent.walk_blocks(dec, slots, n_tokens), scores_of)
        found.append([worst(s, g[name]) for s, name in zip(scores, names)])
        for s, name in zip(scores, names):
            g[name] = fit_(s, k, g[name].dtype)
    return {"tokens": int(toks.size), "layers": len(names),
            "passes": FIT_PASSES,
            "max_load_over_mean_unbiased":
                [round(float(x), 3) for x in found[0]],
            "max_load_over_mean_fitted":
                [round(float(x), 3) for x in found[-1]]}


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_ring.check_against_reference` over weights whose choice
    biases `balance` has fitted first, the reference's two programs
    compiling on threads under the fit's walks and its programs for the
    served requests' length under the comparison's: `g` is the dict
    `build_server` goes on to serve."""
    latent_bias.unbiased(g)
    warming = latent.warm_reference(cell, dict(g), n_tokens)
    fitted = balance(cell, dec, g, n_tokens)
    for t in warming:
        t.join()
    cell.mark("choice bias fitted")
    warming = warm_served(cell, dict(g))
    out = latent._compare(cell, dec, g, n_tokens)
    warming.join()
    out["balance"] = fitted
    return out


def build_server(cell, run_):
    """`serve_lm_closed.build_server` with the pool's size from the
    traffic file: decoder from the configuration's block, weights on
    the device from the seed, the comparison with the reference, the
    warm server with the prefix cache on.  -> (decoder, server)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.serving import GenerationServer

    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    dtype = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    block, d_inner = base.block_of(m)
    fw.reset_unique_names()
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], int(t["block_size"]),
        int(t["context"]) // int(t["block_size"]),
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=d_inner,
        kv_dtype=t["kv_dtype"], platform=platform, block=block)
    cell.mark("decoder built")
    g = make_weights(dec.state_shapes, cell.seed, dtype)
    jax.block_until_ready(g)
    cell.mark("weights made on the device")
    run_.notes["reference"] = check_against_reference(
        cell, dec, g, int(t["correct_tokens"]))
    cell.mark("compared with the reference")
    states = jax.device_get(g)
    del g
    gc.collect()
    cell.mark("weights copied to the host")
    server = GenerationServer(
        dec, states, slots=int(t["slots"]), kv_blocks=int(t["pool_blocks"]),
        place=place, max_queue=int(t["max_queue"]),
        prefix_cache=bool(t["prefix_cache"]))
    del states
    run_.notes["decoder_kernels"] = dict(dec.kernels)
    cell.mark("server built and warm")
    return dec, server


def build_documents(cell, server):
    """The documents' token ids from the seed, each through
    `server.submit(document, max_new_tokens=1)`, all at once: when the
    last returns the prefix cache holds every document's full blocks
    but its last (a block becomes shareable when the tick that passed
    its end is read, and a document's last tick ends its request).
    -> the documents."""
    m, t = cell.config, cell.traffic
    rng = np.random.default_rng([common.seed31(cell.seed), 0xD0C5])
    docs = [rng.integers(0, m["vocab_size"], int(n)).astype(np.int32)
            for n in t["documents"]["lengths"]]
    streams = [server.submit(doc, 1, temperature=0.0, seed=i)
               for i, doc in enumerate(docs)]
    for s in streams:
        s.result()
    return docs


def warm_served(cell, g):
    """The reference's forward at the length `check_served` pads its
    requests to, on a thread of its own: its programs depend on shapes
    and dtypes alone, so any tokens do.  -> the thread, to be joined."""
    ref, m = cell.reference(), cell.config
    n = int(cell.traffic["served"]["padded"])
    thread = threading.Thread(
        target=lambda: ref.forward(g, m, np.zeros(n, np.int32),
                                   logits_from=n - 1),
        name="perf-reference-warm-served")
    thread.start()
    return thread


class DocLoad(serve_closed.Load):
    """`serve_closed.Load` whose request is a document and a fresh
    question: client c's k-th request asks document (c + k) mod the
    documents, its (question, answer) lengths the next pair of the
    permuted table."""

    def __init__(self, cell, server, table, vocab: int, docs):
        super().__init__(cell, server, table, vocab)
        self.docs = docs
        self.asked = {}                 # request index: document
        self._turn = {c.name: [i, 0] for i, c in enumerate(self.clients)}

    def next_request(self):
        idx, question, out_len = super().next_request()
        turn = self._turn[threading.current_thread().name]
        doc = (turn[0] + turn[1]) % len(self.docs)
        turn[1] += 1
        self.asked[idx] = doc
        return idx, np.concatenate([self.docs[doc], question]), out_len


def check_served(cell, run_, server, records, streams, asked) -> dict:
    """`served.requests` requests of this run on at least
    `served.documents` documents against the reference's `served`: the
    latest that finished inside the window, two a document, the
    documents (of a length inside `served.document_lengths`: more than
    half of such a request's rows are dropped, and it fits
    `served.padded`) those asked last.  The numbers are bounded by the
    configuration's `compare.served_limits`."""
    m, want = cell.config, cell.traffic["served"]
    ref = cell.reference()
    limits = m["compare"]["served_limits"]
    shortest, longest = want["document_lengths"]
    lengths = cell.traffic["documents"]["lengths"]
    done = sorted(
        (r for r in records if r["error"] is None and r["done"] is not None
         and run_.t_window_open <= r["done"] < run_.t_window_close
         and len(r["stamps"]) == r["want"]
         and shortest <= lengths[asked[r["idx"]]] <= longest),
        key=lambda r: -r["done"])
    per_doc = -(-int(want["requests"]) // int(want["documents"]))
    by_doc = {}
    for r in done:
        rows = by_doc.setdefault(asked[r["idx"]], [])
        if len(rows) < per_doc:
            rows.append(r["idx"])
    take = [i for rows in list(by_doc.values())[:int(want["documents"])]
            for i in rows]
    if len(take) < int(want["requests"]):
        return {"ok": False, "limits": limits,
                "why": f"{len(take)} finished requests on "
                       f"{len(by_doc)} documents in the window"}
    requests = [(np.concatenate([streams[i].prompt,
                                 streams[i].tokens_so_far()]).astype(
                                     np.int32), len(streams[i].prompt))
                for i in take]
    # the reference wants the room the pools held
    states = server._states
    server._pool_k = server._pool_v = server._inflight = None
    gc.collect()
    out = ref.served(states, m, requests, pad_to=int(want["padded"]))
    out.update(requests=take, documents=sorted({asked[i] for i in take}),
               prompt_lengths=[start for _, start in requests],
               limits=limits,
               ok=all(out[k] is not None
                      and (lo is None or out[k] >= lo)
                      and (hi is None or out[k] <= hi)
                      for k, (lo, hi) in limits.items()))
    return out


def run(cell):
    base.attention_kernel_in_step = ring.attention_kernel_in_step
    ring.system_outputs = latent.system_outputs
    run_ = common.Run()
    m, t = cell.config, cell.traffic
    dec, server = build_server(cell, run_)
    docs = build_documents(cell, server)
    built = server.stats()
    run_.notes["documents"] = {
        "lengths": [len(d) for d in docs],
        "kv_blocks_cached": built.get("kv_blocks_cached"),
        "decode_ticks": built.get("decode_ticks")}
    cell.mark("documents built")
    streams = ring.record_streams(server)
    slot_ticks = base.count_slot_ticks(cell, server)
    load = DocLoad(cell, server,
                   serve_closed.permuted_table(t["lengths"], cell.seed),
                   m["vocab_size"], docs)
    t_ramp = time.perf_counter()
    gap = float(t["stagger_seconds"]) / len(load.clients)
    for i, c in enumerate(load.clients):
        time.sleep(max(0.0, t_ramp + i * gap - time.perf_counter()))
        c.start()
    time.sleep(max(0.0, t_ramp + float(t["ramp_seconds"])
                   - time.perf_counter()))
    base.measure(cell, run_, dec, server, slot_ticks, load.records,
                 load.stop.set, load.clients)
    stats = run_.notes["server"]
    run_.notes["served"] = check_served(cell, run_, server,
                                        list(load.records), streams,
                                        load.asked)
    run_.correct = bool(run_.correct and run_.notes["served"]["ok"]
                        and stats.get("prefix_hits", 0) > 0)
    return run_
