"""Plain reference of the Ouro looped decoder (ByteDance, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741;
`ByteDance/Ouro-2.6B` config.json, `model_type: ouro`): float32
`jax.numpy`, one full causal forward over one token sequence, no cache,
no planes, no batching, every matrix multiplication at `highest`
precision.  It knows nothing of paddle_tpu: it takes a dict of named
arrays under the names the served decoder's `state_shapes` gives
(`layer_<l>.q_proj.w_0`, ...; weights are stored [in, out]) and the
configuration's own keys.

The model (C: stated by config.json; A: assumed from the released
modelling code and the paper, each listed in the configuration file):

  x_0 = E[token]                         no multiplier, no position (A)
  for pass t = 1 .. total_ut_steps (C: 4), h = x_{t-1}, and for layer
  l = 1 .. num_hidden_layers (C: 48) WITH THE SAME WEIGHTS IN EVERY PASS:
    n = RMSNorm(h; g1_l, rms_norm_eps)
    q = RoPE(Wq n)  k = RoPE(Wk n)  v = Wv n      [S, 16 heads x 128],
        no bias (A), no norm on q or k (A); rotate-half RoPE at the
        token's own position, theta `rope_theta` (C: 1e6), the same in
        every pass
    a = Wo . causal softmax(q k^T / sqrt(head_dim)) v
        over the keys and values THIS pass of THIS layer computed at
        the earlier positions: no pass sees another pass's
    h = h + RMSNorm(a; g2_l)                 the norm on the OUTPUT (A:
                                             "sandwich" normalisation)
    u = RMSNorm(h; g3_l);  m = Wdown(silu(Wgate u) * (Wup u))
    h = h + RMSNorm(m; g4_l)
  x_t = RMSNorm(h; g_final)   the ONE final norm, after EVERY pass; its
                              output is what the next pass starts from (A)
  lambda_t = sigmoid(w_exit . x_t + b_exit)        the exit gate (A)
  logits = Whead x_T          untied head (C), no bias

`early_exit_threshold` is 1 (C): no pass is ever skipped, the logits
are the last pass's, and the gate is computed and decides nothing.
`use_sliding_window` false (C): every layer is full attention;
`max_window_layers` and `sliding_window` are read by nothing.

Departures from the published model: weights are random from the seed,
not the trained checkpoint.  Departures from the issue's sketch of this
file: a layer runs as one jitted call that upcasts its own bf16 arrays
(one float32 layer, 0.2 GB at these widths, exists at a time), but the
positions are NOT cut into blocks: at the 512 positions compared a
layer's largest intermediates are the [16, 512, 512] scores (17 MB)
and the [512, 5632] gate (12 MB), the head's [512, 49152] logits 101
MB.

`forward` returns the logits and, as the served step's `step_routing`
does, x_t of every pass and lambda_t.  `compare` holds a system's
against them; `below` is the reading one precision down (all
bfloat16), and `faults` the readings of five DIFFERENT models that a
wrong cache, a wrong loop or a wrong scalar would compute, all of which
the configuration's limits have to refuse:

  shared_planes  all passes share layer l's ONE plane (the paper's
                 cheaper "last pass only" cache): at position i pass t
                 sees its own K/V at i and, at every j < i, what the
                 LAST pass left there.  Causal but not parallel over
                 positions: computed a position at a time.
  three_passes   total_ut_steps - 1 passes
  no_final_norm  the final norm once, after the last pass alone
  no_post_norm   g2 and g4 left out (a plain pre-norm block)
  theta_1e4      RoPE at theta 10000
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("shared_planes", "three_passes", "no_final_norm",
          "no_post_norm", "theta_1e4")


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, pos, theta):
    """x [S, H, Dh] at positions `pos` [S], rotate-half."""
    dh = x.shape[-1]
    inv = theta ** -(jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return x * jnp.cos(ang).astype(x.dtype) + turned * jnp.sin(
        ang).astype(x.dtype)


def _qkv(x, p, pos, *, n_heads, eps, theta):
    s = x.shape[0]
    n = _rms(x, p["attn_norm"], eps)
    q, k, v = ((n @ p[w]).reshape(s, n_heads, -1) for w in "qkv")
    return _rope(q, pos, theta), _rope(k, pos, theta), v


def _after_attention(x, ctx, p, *, eps, post_norm):
    a = ctx.reshape(x.shape) @ p["o"]
    h = x + (_rms(a, p["attn_post_norm"], eps) if post_norm else a)
    u = _rms(h, p["ffn_norm"], eps)
    m = (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"]
    return h + (_rms(m, p["ffn_post_norm"], eps) if post_norm else m)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "eps", "theta", "post_norm", "dtype"))
def _layer(x, p, *, n_heads, eps, theta, post_norm, dtype):
    """One layer over all positions [S, D], keys and values its own."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    q, k, v = _qkv(x, p, jnp.arange(s), n_heads=n_heads, eps=eps,
                   theta=theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], dtype))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return _after_attention(x, ctx, p, eps=eps, post_norm=post_norm)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "eps", "theta", "post_norm", "dtype"))
def _layer_at(x, kc, vc, i, p, *, n_heads, eps, theta, post_norm, dtype):
    """One layer at position `i` alone, x [1, D], over a cache of this
    layer's keys and values kc, vc [S, H, Dh] that it writes at `i`
    first: whatever the rows before `i` hold is what it sees."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    q, k, v = _qkv(x, p, i[None], n_heads=n_heads, eps=eps, theta=theta)
    kc, vc = kc.at[i].set(k[0]), vc.at[i].set(v[0])
    scores = jnp.einsum("qhd,khd->hqk", q, kc) / jnp.sqrt(
        jnp.asarray(q.shape[-1], dtype))
    scores = jnp.where(jnp.arange(kc.shape[0])[None, None, :] <= i,
                       scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), vc)
    return _after_attention(x, ctx, p, eps=eps,
                            post_norm=post_norm), kc, vc


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _pass_end(h, scale, w_exit, b_exit, *, eps, dtype):
    """-> (x_t, lambda_t) of the pass that left h."""
    x = _rms(h, scale.astype(dtype), eps)
    gate = jax.nn.sigmoid((x @ w_exit.astype(dtype))[:, 0]
                          + b_exit.astype(dtype)[0])
    return x, gate.astype(F32)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _head(x, head, *, dtype):
    return (x @ head.astype(dtype)).astype(F32)


LAYER_KEYS = {"attn_norm": "attn_norm.scale_0", "q": "q_proj.w_0",
              "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
              "attn_post_norm": "attn_post_norm.scale_0",
              "ffn_norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
              "up": "ffn_up.w_0", "down": "ffn_down.w_0",
              "ffn_post_norm": "ffn_post_norm.scale_0"}


def forward(states: dict, config: dict, ids, dtype=F32, fault=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, {"passes":
    [T, S, D] float32, x_t of every pass; "gates": [T, S] float32,
    lambda_t}), from the named arrays and the configuration's own
    keys.  `fault` computes one of the DIFFERENT models of `FAULTS`
    (module docstring); a model with a pass fewer reports its last
    pass twice, as a system that skipped one would."""
    if config["early_exit_threshold"] < 1:
        raise NotImplementedError("adaptive exit (early_exit_threshold "
                                  "under 1) is not the model computed")
    n_layers = int(config["num_hidden_layers"])
    passes = int(config["total_ut_steps"]) - (fault == "three_passes")
    kw = dict(n_heads=int(config["num_attention_heads"]),
              eps=float(config["rms_norm_eps"]),
              theta=1e4 if fault == "theta_1e4"
              else float(config["rope_theta"]),
              post_norm=fault != "no_post_norm", dtype=dtype)
    layers = [{k: states[f"layer_{l}.{n}"] for k, n in LAYER_KEYS.items()}
              for l in range(n_layers)]
    end = functools.partial(
        _pass_end, scale=states["final_norm.scale_0"],
        w_exit=states["exit_gate.w_0"], b_exit=states["exit_gate.b_0"],
        eps=kw["eps"], dtype=dtype)
    s = len(ids)
    xs, gates = [], []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        if fault == "shared_planes":
            # a position at a time, every pass of it through the ONE
            # cache a layer has
            heads = kw["n_heads"]
            shape = (s, heads, layers[0]["q"].shape[1] // heads)
            kc = [jnp.zeros(shape, dtype) for _ in layers]
            vc = [jnp.zeros(shape, dtype) for _ in layers]
            rows = []
            for i in range(s):
                row, at = x[i:i + 1], jnp.asarray(i, jnp.int32)
                per_pass = []
                for _ in range(passes):
                    for l, p in enumerate(layers):
                        row, kc[l], vc[l] = _layer_at(
                            row, kc[l], vc[l], at, p, **kw)
                    row, gate = end(row)
                    per_pass.append((row, gate))
                rows.append(per_pass)
            xs = [jnp.concatenate([r[t][0] for r in rows])
                  for t in range(passes)]
            gates = [jnp.concatenate([r[t][1] for r in rows])
                     for t in range(passes)]
            x = xs[-1]
        else:
            for t in range(passes):
                for p in layers:
                    x = _layer(x, p, **kw)
                last = t == passes - 1
                normed, gate = end(x)
                if fault != "no_final_norm" or last:
                    x = normed
                xs.append(normed)
                gates.append(gate)
        out = _head(x, states["lm_head.w_0"], dtype=dtype)
    while len(xs) < int(config["total_ut_steps"]):
        xs.append(xs[-1])
        gates.append(gates[-1])
    return out, {"passes": jnp.stack(xs).astype(F32),
                 "gates": jnp.stack(gates)}


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


def _rms_err(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def compare(states: dict, config: dict, ids, got, loop) -> dict:
    """A system's [S, vocab] logits and what its loop gave (`forward`'s
    second value as the system computed it: "passes" [T, S, D],
    "gates" [T, S]) against this reference on the same weights and
    tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed
      late_rms_err    `logits_rms_err` over the second half of the
                      positions alone (256 to 511 of 512): what a cache
                      gets wrong grows with the positions behind
      pass_rms_err    the worst over the passes t of x_t's difference
                      by root mean square over x_t's (`pass_rms_errs`
                      has every pass's): a pass that read another
                      pass's plane shows at the pass where it happened
      gate_abs_err    largest |lambda_t difference|
    """
    want, ref = forward(states, config, ids)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    x_ref = np.asarray(ref["passes"])
    x_got = np.asarray(loop["passes"], np.float32)
    per_pass = [_rms_err(a, b) for a, b in zip(x_got, x_ref)]
    half = len(got) // 2
    return {"logits_rel_err": float(np.max(np.abs(got - want))
                                    / np.max(np.abs(want))),
            "logits_rms_err": _rms_err(got, want),
            "late_rms_err": _rms_err(got[half:], want[half:]),
            "pass_rms_err": max(per_pass), "pass_rms_errs": per_pass,
            "gate_abs_err": float(np.max(np.abs(
                np.asarray(loop["gates"], np.float32)
                - np.asarray(ref["gates"])))),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "finite": bool(np.isfinite(got).all()
                           and np.isfinite(x_got).all())}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids, which=FAULTS) -> dict:
    """`compare`'s numbers for float32 models that a wrong cache, a
    wrong loop or a wrong scalar would compute, as if each were the
    system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids,
                           *forward(states, config, ids, fault=fault))
            for fault in which}


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           length=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1].

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens: 0
                      where they agree, and small at a near-tie that
                      rounding turned

    `length`: every request is run at this many positions (cut, or
    padded with token 0 past its end, which no earlier position sees),
    so that all share one compiled forward pass."""
    agree, gap = [], []
    for ids, start in requests:
        ids = np.asarray(ids)
        n = len(ids) - 1 if length is None else min(len(ids) - 1, length)
        fed = ids[:n] if length is None else np.concatenate(
            [ids[:n], np.zeros(length - n, ids.dtype)])
        want = np.asarray(forward(states, config, fed, dtype=dtype,
                                  fault=fault)[0],
                          np.float32)[start - 1:n]
        got = want[np.arange(len(want)), ids[start:n + 1]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
    agree, gap = np.concatenate(agree), np.concatenate(gap)
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "tokens": int(len(agree))}
