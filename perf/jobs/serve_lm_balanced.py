"""Job `serve_lm_balanced`: `serve_lm_ring` for a block whose router has
a CHOICE BIAS (`BlockSpec.router_bias`: added to the scores for the
choice of the k experts alone), with that bias FITTED to the seed's
weights and not drawn from the seed: everything else, the clients, the
load, the two comparisons, the window and its accounting, is
`serve_lm_ring`'s, imported unedited.

Why.  A checkpoint's choice bias is what balances its experts' load
(the auxiliary-loss-free rule of the family these keys come from: after
every batch an over-loaded expert's bias goes down a step and an
under-loaded one's up, until the loads are even).  Weights drawn from a
seed have no such history: every token's router input shares a large
common component, so each seed makes some experts popular and starves
others.  On a chip that holds 16 of 128 experts and sees 4 rows an
expert a layer that decides how many held experts a tick touches, and
with it the tick: over six seeds `itl_p95_ms` spread 0.91% and the mean
experts hit 106.6 to 108.6 of 112 (my chip runs, PR 40), which no
deployment shows and a cell's tails cannot carry.  So the bias is made
as a checkpoint's is: `balance` walks `FIT_POSITIONS` positions of
seeded tokens through all the lanes of the served step and `fit` runs
the sign rule over each layer's scores of those 4096 tokens until its
128 loads are even, `FIT_PASSES` times over (a layer's router input
depends on the biases of the layers before it: the second walk sees
them fitted, the third sees them settled).  The comparison with
the reference is over the same arrays, fitted bias included (it is far
from zero, so a bias left out or leaked into the weights still fails).
"""
from __future__ import annotations

import os

import numpy as np

import common

ring = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_lm_ring.py"))
_compare = ring.check_against_reference

# positions each lane walks for the fit (tokens: lanes x positions),
# and the walks
FIT_POSITIONS, FIT_PASSES = 64, 3
# the sign rule's steps, its first and last step size (score units)
FIT_STEPS, FIT_FIRST, FIT_LAST = 400, 0.05, 0.0005


def fit(scores, k: int, dtype):
    """The choice bias [E] under which the k largest of scores + bias
    load the E experts evenly over these tokens' router scores [T, E]:
    the sign rule (a step down where an expert has more than the mean
    load, up where fewer), its step shrinking geometrically; kept at
    each step in `dtype`, the weights' own, so that what is fitted is
    what is served."""
    import jax
    import jax.numpy as jnp

    e_n = scores.shape[-1]
    mean = scores.shape[0] * k / e_n
    ratio = (FIT_LAST / FIT_FIRST) ** (1.0 / (FIT_STEPS - 1))

    def step(i, bias):
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        load = jnp.zeros(e_n, jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return (bias.astype(jnp.float32) - FIT_FIRST * ratio ** i
                * jnp.sign(load - mean)).astype(dtype)

    return jax.lax.fori_loop(0, FIT_STEPS, step, jnp.zeros(e_n, dtype))


def router_scores(dec, g, routers, toks, slots: int):
    """`toks` [positions, slots] through every lane of the served step,
    each lane its own table blocks and ring, position by position:
    -> the router's scores of every layer with experts, sigmoid(its
    input @ its matrix) in float32, [layers, positions x slots, E].
    Only the scores are kept: a layer's inputs over 4096 tokens are
    100 MB, and the chip holds the weights."""
    import jax
    import jax.numpy as jnp

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(
        slots * need + 1, jax.devices()[0],
        window_blocks=slots * dec.window_blocks_per_seq + 1)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[:, :need] = 1 + np.arange(slots * need).reshape(slots, need)
    rings = dec.slot_rings(slots)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.ones(slots, bool)
    w = jnp.stack([g[name] for name in routers]).astype(jnp.float32)

    @jax.jit
    def scores_of(inputs):
        return jax.nn.sigmoid(jnp.einsum(
            "lsd,lde->lse", inputs, w,
            precision=jax.lax.Precision.HIGHEST))

    seen = []
    for pos in range(n):
        args = (g, pool_k, pool_v, (tables, rings),
                np.full(slots, pos, np.int32), toks[pos], zs, zt, act)
        seen.append(scores_of(dec.step_routing(*args)[1]["inputs"]))
        _, pool_k, pool_v, *_ = dec.step(*args)
    return jnp.concatenate(seen, axis=1)


def balance(cell, dec, g) -> dict:
    """Fit every layer's choice bias in `g`, in place, `FIT_PASSES`
    walks over.  -> what the fit did to the loads, for the run's
    notes: the largest expert's load over the mean, a layer, under the
    seeded bias and under the fitted one as the LAST walk found it
    (before that walk's own fit: tokens routed by biases fitted to
    slightly other inputs)."""
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
    fit_ = jax.jit(fit, static_argnums=(1, 2))

    @jax.jit
    def worst(scores, bias):
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        load = jnp.zeros(scores.shape[-1]).at[chosen.reshape(-1)].add(1.0)
        return load.max() / load.mean()

    found = []
    for _ in range(FIT_PASSES):
        scores = router_scores(dec, g, routers, toks, slots)
        found.append([worst(s, g[name]) for s, name in zip(scores, names)])
        for s, name in zip(scores, names):
            g[name] = fit_(s, k, g[name].dtype)
    return {"tokens": int(toks.size), "layers": len(names),
            "passes": FIT_PASSES,
            "max_load_over_mean_seeded":
                [round(float(x), 3) for x in found[0]],
            "max_load_over_mean_fitted":
                [round(float(x), 3) for x in found[-1]]}


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_ring.check_against_reference` over weights whose choice
    biases `balance` has fitted first: `g` is the dict `build_server`
    goes on to serve, so the server holds what was compared."""
    fitted = balance(cell, dec, g)
    cell.mark("choice bias fitted")
    out = _compare(cell, dec, g, n_tokens)
    out["balance"] = fitted
    return out


def run(cell):
    ring.check_against_reference = check_against_reference
    return ring.run(cell)
