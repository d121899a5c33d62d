"""Plain reference of the OPT decoder (Zhang et al., arXiv:2205.01068;
`facebook/opt-1.3b` config.json): float32 `jax.numpy`, full causal
forward over one sequence, no cache, no kernels, no batching, matrix
multiplications at `highest` precision.  It knows nothing of
paddle_tpu: it takes a dict of named arrays and reads them by the
creation-order names the model builders give (`embedding_0.w_0`,
`create_parameter_0.w_0`, `fc_<6l+i>`, `layer_norm_<2l+j>`), which is
the repo's own contract between training and serving
(`fw.reset_unique_names()` before either build).

Block, as published: x + Attn(LN(x)), then x + W2 relu(W1 LN(x)), LN
eps 1e-5, biases on every projection, queries scaled by
1/sqrt(head size), a final LayerNorm before the output head.

Departures from the published model, all of them the repo's
(`models/transformer.py`), listed in the configuration files under
`assumed`:
  * the output head is a separate [hidden, vocab] matrix with a bias;
    OPT ties it to the token embedding and has no bias;
  * learned positions index a table of exactly the context's rows from
    0; OPT's table has 2 + 2048 rows and an offset of 2;
  * weights are random from the seed, not the trained checkpoint.

To bound memory at the published widths each block runs as one jitted
call and upcasts its own weights, so a bf16 model of 1.3 B parameters
never exists in float32 as a whole.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def structure(states, n_layers: int) -> dict:
    """Named arrays -> {"tok", "pos", "blocks": [...], "ln_f", "head"}."""
    def fc(i):
        return states[f"fc_{i}.w_0"], states[f"fc_{i}.b_0"]

    def ln(i):
        return (states[f"layer_norm_{i}.scale_0"],
                states[f"layer_norm_{i}.shift_0"])

    blocks = [{"ln1": ln(2 * l), "q": fc(6 * l), "k": fc(6 * l + 1),
               "v": fc(6 * l + 2), "o": fc(6 * l + 3),
               "ln2": ln(2 * l + 1), "w1": fc(6 * l + 4),
               "w2": fc(6 * l + 5)} for l in range(n_layers)]
    return {"tok": states["embedding_0.w_0"],
            "pos": states["create_parameter_0.w_0"],
            "blocks": blocks, "ln_f": ln(2 * n_layers),
            "head": fc(6 * n_layers)}


def _ln(x, p):
    scale, shift = (t.astype(F32) for t in p)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * scale + shift


def _fc(x, p):
    w, b = (t.astype(F32) for t in p)
    return x @ w + b


@functools.partial(jax.jit, static_argnames=("n_heads",))
def _block(x, p, n_heads: int):
    s, d = x.shape
    h = _ln(x, p["ln1"])
    q, k, v = (_fc(h, p[n]).reshape(s, n_heads, d // n_heads)
               for n in ("q", "k", "v"))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d // n_heads, F32))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + _fc(ctx.reshape(s, d), p["o"])
    return x + _fc(jax.nn.relu(_fc(_ln(x, p["ln2"]), p["w1"])), p["w2"])


@jax.jit
def _embed(tok, pos, ids):
    return tok[ids].astype(F32) + pos[: ids.shape[0]].astype(F32)


@jax.jit
def _head(x, ln_f, head):
    return _fc(_ln(x, ln_f), head)


def logits(params: dict, ids, n_heads: int):
    """[S] token ids -> [S, vocab] float32 next-token logits."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["tok"], params["pos"], jnp.asarray(ids))
        for p in params["blocks"]:
            x = _block(x, p, n_heads=n_heads)
        return _head(x, params["ln_f"], params["head"])


@jax.jit
def _nll(lg, labels):
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


def loss(params: dict, ids, labels, n_heads: int) -> float:
    """Mean next-token cross-entropy over a [B, S] batch, one sequence
    at a time (the [S, vocab] logits of one sequence are the largest
    array alive)."""
    total, count = 0.0, 0
    for row_ids, row_lbl in zip(ids, labels):
        lg = logits(params, row_ids, n_heads)
        total += float(_nll(lg, jnp.asarray(row_lbl).reshape(-1)))
        count += int(row_ids.shape[0])
    return total / count
