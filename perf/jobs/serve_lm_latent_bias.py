"""Job `serve_lm_latent_bias`: `serve_lm_latent` for a block whose
softmax router has a CHOICE BIAS (`BlockSpec.router_bias`) and is WIDER
than its experts (`BlockSpec.zero_experts`: identity columns), with that
bias FITTED to the seed's weights in place of `serve_lm_latent`'s fit
of the router matrix: the clients, the load, the weights, the walks,
the two comparisons, the window and its accounting are `serve_lm_ring`'s
and `serve_lm_latent`'s, imported unedited.

Why the bias and not the matrix.  A checkpoint of this family balances
its columns with exactly this bias: a controller moves a column's bias
down where it is over-loaded and up where it is under-loaded, the
identity columns' among them, which is how the share of a token's
assignments that cost nothing is held at its target.  Weights drawn
from a seed have no such history, and a bias drawn from a seed (sigma
0.02 beside probabilities near 1/768) would BE the choice: every token
the same twelve columns.  So `unbiased` sets the biases to zero and
`balance` walks `FIT_POSITIONS` positions of seeded tokens through all
the lanes of the served step and runs the sign rule (`fit`,
`serve_lm_balanced`'s, its steps in units of the mean probability 1 /
columns) over each layer's probabilities until every column's load is
even: 12 / 768 of the tokens each, which is 8 routed experts a token in
the mean, a third of the assignments on identity columns (the model
card's 27 B active) and every routed expert's load equal.  Three
passes, because a layer's router input depends on the biases of the
layers before it, and the LAST over four walks of other tokens
(`FIT_WALKS`): a column gets 12 / 768 of the tokens, so 4096 tokens fit
a column's load on 64 assignments and leave it 12% from even on other
tokens, the 64 held (expert, layer) pairs together 1.6%
(`sched_moe_rows_held_share` read 2.05 to 2.13 over seeds,
`moe_held_experts_hit_share` 62.3 to 64.1, and an expert touched is
0.09 ms of a tick: 0.7% of `itl_p95_ms` between seeds, my chip runs, PR
48); 16 384 tokens halve both.  The four walks start again at position
0 in the same blocks (a lane reads no row past its cursor), so every
walk before the window keeps the one pool shape of
`serve_lm_latent.walk_blocks`: one walk of 256 positions took a pool of
four times the blocks, which `step_routing`, whose pool is not donated,
copies eight times a call (10 s of the comparison's walk).  The
comparison with the reference is over the same arrays, fitted bias
included: it is far from zero, so a bias left out or leaked into the
weights still fails.  No equation, flag or bias path changes for it.
"""
from __future__ import annotations

import os

import numpy as np

import common

latent = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_lm_latent.py"))
ring = latent.ring

# positions each lane walks for the fit (tokens: lanes x positions,
# `serve_lm_latent`'s), and the walks a pass: the last pass fits on
# four walks' tokens
FIT_POSITIONS, FIT_WALKS = latent.FIT_POSITIONS, (1, 1, 4)
# the sign rule's steps, its first and last step size in units of the
# mean probability (1 / the router's columns)
FIT_STEPS, FIT_FIRST, FIT_LAST = 600, 1.0, 0.002


def fit(probs, k: int, dtype):
    """The choice bias [C] under which the k largest of probs + bias
    load the C columns evenly over these tokens' router probabilities
    [T, C]: the sign rule (a step down where a column has more than the
    mean load, up where fewer), its step shrinking geometrically; kept
    at each step in `dtype`, the one it is served in, so that what is
    fitted is what is served."""
    import jax
    import jax.numpy as jnp

    c_n = probs.shape[-1]
    mean = probs.shape[0] * k / c_n
    first = FIT_FIRST / c_n
    ratio = (FIT_LAST / FIT_FIRST) ** (1.0 / (FIT_STEPS - 1))

    def step(i, bias):
        _, chosen = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        load = jnp.zeros(c_n, jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return (bias.astype(jnp.float32) - first * ratio ** i
                * jnp.sign(load - mean)).astype(dtype)

    return jax.lax.fori_loop(0, FIT_STEPS, step, jnp.zeros(c_n, dtype))


def softmax_of(inputs, w):
    """softmax(inputs @ w) a layer, in float32 at `highest`."""
    import jax
    import jax.numpy as jnp

    return jax.nn.softmax(jnp.einsum(
        "lsd,lde->lse", inputs, w, precision=jax.lax.Precision.HIGHEST),
        axis=-1)


def router_probs(dec, g, routers, toks, slots: int, blocks: int,
                 probs_of):
    """`toks` [positions, slots] through every lane of the served step,
    each lane its own table blocks of a pool of `blocks` (the one pool
    shape of the walks before the window, `serve_lm_latent
    .walk_blocks`), position by position -> the router's probabilities
    of every layer with experts, `probs_of(its input, its matrix)`
    (`softmax_of` under one `jax.jit` for all the walks), float32
    [layers, positions x slots, columns].  Only they are kept:
    a layer's inputs over 4096 tokens are 100 MB, and the chip holds
    the weights."""
    import jax
    import jax.numpy as jnp

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(blocks + 1, jax.devices()[0])
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[:, :need] = 1 + np.arange(slots * need).reshape(slots, need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.ones(slots, bool)
    w = jnp.stack([g[name] for name in routers]).astype(jnp.float32)

    seen = []
    for pos in range(n):
        args = (g, pool_k, pool_v, tables, np.full(slots, pos, np.int32),
                toks[pos], zs, zt, act)
        seen.append(probs_of(dec.step_routing(*args)[1]["inputs"], w))
        _, pool_k, pool_v, *_ = dec.step(*args)
    return jnp.concatenate(seen, axis=1)


def balance(cell, dec, g, n_tokens: int) -> dict:
    """Fit every layer's choice bias in `g`, in place, from the zeros
    `unbiased` left there, `FIT_WALKS` walks a pass.  -> what the fit
    did to the loads, for the run's notes: the largest column's load
    over the mean, a layer, under no bias and under the fitted one as
    the LAST walk found it (before that walk's own fit), and the
    identity columns' share of the assignments there."""
    import jax
    import jax.numpy as jnp

    m, slots = cell.config, int(cell.traffic["slots"])
    k, zero = int(m["moe_topk"]), int(m["zero_expert_num"])
    rng = np.random.default_rng([common.seed31(cell.seed), 0xB1A5])
    toks = rng.integers(0, m["vocab_size"], (
        max(FIT_WALKS), FIT_POSITIONS, slots)).astype(np.int32)
    names = sorted((n for n in g if n.endswith("router_bias.b_0")),
                   key=lambda n: int(n.split(".")[0].split("_")[1]))
    routers = [n.replace("router_bias.b_0", "router.w_0") for n in names]
    fit_ = jax.jit(fit, static_argnums=(1, 2))
    probs_of = jax.jit(softmax_of)

    @jax.jit
    def loads(probs, bias):
        _, chosen = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        load = jnp.zeros(probs.shape[-1]).at[chosen.reshape(-1)].add(1.0)
        return load.max() / load.mean(), load[-zero:].sum() / load.sum()

    found = []
    for walks in FIT_WALKS:
        probs = jnp.concatenate([
            router_probs(dec, g, routers, toks[i], slots,
                         latent.walk_blocks(dec, slots, n_tokens), probs_of)
            for i in range(walks)], axis=1)
        found.append([loads(p, g[name]) for p, name in zip(probs, names)])
        for p, name in zip(probs, names):
            g[name] = fit_(p, k, g[name].dtype)
    return {"tokens": [w * FIT_POSITIONS * slots for w in FIT_WALKS],
            "layers": len(names),
            "max_load_over_mean_unbiased":
                [round(float(x[0]), 3) for x in found[0]],
            "max_load_over_mean_fitted":
                [round(float(x[0]), 3) for x in found[-1]],
            "zero_share_unbiased": [round(float(x[1]), 4) for x in found[0]],
            "zero_share_fitted": [round(float(x[1]), 4) for x in found[-1]]}


def unbiased(g) -> None:
    """Every choice bias in `g` a zero in FLOAT32, in place, whatever
    the weights' dtype: the router is float32 throughout and the
    family's checkpoints keep this bias so (a popular column's bias is
    tens of mean probabilities, where bfloat16 steps by a fifth of one:
    fitted in it, loads stayed 0.73 to 1.31 of the mean).  Committed to
    the device like the other weights (`serve_lm_latent.make_weights`),
    and done before any program sees the dict: the steps and the
    reference then compile once, for this dtype."""
    import jax
    import jax.numpy as jnp

    for name in g:
        if name.endswith("router_bias.b_0"):
            g[name] = jax.device_put(
                jnp.zeros(g[name].shape, jnp.float32), jax.devices()[0])


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_ring.check_against_reference` over weights whose choice
    biases `balance` has fitted first, the reference's two programs
    compiling on threads under the fit's walks (`serve_lm_latent
    .warm_reference`): `g` is the dict `build_server` goes on to serve,
    so the server holds what was compared."""
    unbiased(g)
    warming = latent.warm_reference(cell, dict(g), n_tokens)
    fitted = balance(cell, dec, g, n_tokens)
    for t in warming:
        t.join()
    cell.mark("choice bias fitted")
    out = latent._compare(cell, dec, g, n_tokens)
    out["balance"] = fitted
    return out


def run(cell):
    ring.make_weights = latent.make_weights
    ring.system_outputs = latent.system_outputs
    ring.check_against_reference = check_against_reference
    return ring.run(cell)
