"""Plain reference of the OLMoE decoder (Muennighoff et al.,
arXiv:2409.02060; `allenai/OLMoE-1B-7B-0125-Instruct` config.json,
`model_type: olmoe`): float32 `jax.numpy`, full causal forward over one
token sequence, no cache, no sort, no batching, every matrix
multiplication at `highest` precision.  It knows nothing of paddle_tpu:
it takes a dict of named arrays under the names the served decoder's
`state_shapes` gives (`layer_<l>.q_proj.w_0`, ...; weights are stored
[in, out], the experts [expert, in, out]) and the configuration's own
keys.

The layer, letter for letter:

  n = RMSNorm_in(x)
  q = RoPE(RMSNorm_q(Wq n))   k = RoPE(RMSNorm_k(Wk n))   v = Wv n
      RMSNorm_q and RMSNorm_k act on all hidden columns, before the
      split into heads; RoPE is the rotate-half form over each head's
      columns at the token's own position
  h = x + Wo . causal softmax(q k^T / sqrt(head size)) v
  m = RMSNorm_post(h);  p = softmax(Wr m) over ALL experts, float32
  out = h + sum over the top-k experts e of
            p_e . Wdown_e ( silu(Wgate_e m) * (Wup_e m) )
      the k largest p_e as they are (renormalised only where the
      configuration says `norm_topk_prob`); no token is dropped
  logits = Whead . RMSNorm_final(out);  no bias, no position table.

Every expert is applied densely to every token and masked by the top-k
weights: 8 times the operations of the routed form and none of its
machinery.

Departures from the published model: weights are random from the seed,
not the trained checkpoint; nothing else.

To bound memory at the published widths each layer runs as one jitted
call and upcasts its own weights, so beside the served bf16 weights
only ONE float32 layer (1.7 GB at hidden 2048, 64 experts of 1024)
exists at a time.

What decides `correct` is `compare`.  With random weights the k-th and
k+1-th of a token's probabilities often lie closer than the bf16
rounding of the served matmuls moves them, and the served step then
takes the other expert than a free-running float32 forward: a swap,
not an error, that moves a logit by more than all rounding together.
So the reference FOLLOWS the system's choice of experts (`follow`) and
weighs them with its own float32 probabilities, which leaves rounding
alone in `logits_rel_err`; and the choice itself is judged where it
can be judged exactly, on the router's OWN input as the system gives
it (`router_rel_err`): float32 routing agrees with the float32 router
on the same input to 1e-6, a bf16 pass to 1e-3.  `below` is the
reading one precision down (every array and intermediate in bfloat16)
that the configuration's limits have to refuse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, rotate-half."""
    s, _, dh = x.shape
    inv = theta ** -(jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return x * jnp.cos(ang).astype(x.dtype) + turned * jnp.sin(
        ang).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "top_k", "eps", "theta", "renorm", "dtype"))
def _layer(x, p, follow, *, n_heads, top_k, eps, theta, renorm, dtype):
    """-> (the layer's output, its routing: the router's input, the
    top-k weights and experts of its own choice).  `follow` [S, k]:
    the experts to apply instead of its own choice, each weighed by
    the probability computed here."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s, d = x.shape
    n = _rms(x, p["attn_norm"], eps)
    q = _rms(n @ p["q"], p["q_norm"], eps).reshape(s, n_heads, -1)
    k = _rms(n @ p["k"], p["k_norm"], eps).reshape(s, n_heads, -1)
    v = (n @ p["v"]).reshape(s, n_heads, -1)
    q, k = _rope(q, theta), _rope(k, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d // n_heads, dtype))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = x + ctx.reshape(s, d) @ p["o"]

    m = _rms(h, p["ffn_norm"], eps)
    probs = jax.nn.softmax(m @ p["router"], -1)               # [S, E]
    own_w, own_e = jax.lax.top_k(probs, top_k)
    if renorm:
        own_w = own_w / own_w.sum(-1, keepdims=True)
    use_e, use_w = own_e, own_w
    if follow is not None:
        use_e = follow
        use_w = jnp.take_along_axis(probs, follow, -1)
        if renorm:
            use_w = use_w / use_w.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(s)[:, None], use_e].set(use_w)             # [S, E]
    act = jax.nn.silu(jnp.einsum("sd,edf->sef", m, p["gate"])) \
        * jnp.einsum("sd,edf->sef", m, p["up"])
    y = jnp.einsum("sef,efd->sed", act, p["down"])
    routing = {"inputs": m.astype(F32), "weights": own_w.astype(F32),
               "experts": own_e}
    return h + (y * weight[..., None]).sum(1), routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


LAYER_KEYS = {"attn_norm": "attn_norm.scale_0", "q": "q_proj.w_0",
              "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
              "q_norm": "q_norm.scale_0", "k_norm": "k_norm.scale_0",
              "ffn_norm": "ffn_norm.scale_0", "router": "router.w_0",
              "gate": "experts_gate.w_0", "up": "experts_up.w_0",
              "down": "experts_down.w_0"}


def forward(states: dict, config: dict, ids, follow=None, dtype=F32):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every layer stacked: "inputs" [L, S, D], "weights" and
    "experts" [L, S, k]), from the named arrays and the
    configuration's own keys.  `follow` [L, S, k]: the experts each
    layer applies in place of its own choice."""
    kw = dict(n_heads=int(config["num_attention_heads"]),
              top_k=int(config["num_experts_per_tok"]),
              eps=float(config["rms_norm_eps"]),
              theta=float(config["rope_theta"]),
              renorm=bool(config["norm_topk_prob"]), dtype=dtype)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            x, r = _layer(x, {k: states[f"layer_{l}.{n}"]
                              for k, n in LAYER_KEYS.items()},
                          None if follow is None else jnp.asarray(follow[l]),
                          **kw)
            routed.append(r)
        out = _head(x, states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=kw["eps"], dtype=dtype)
    return out, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _router(m, w):
    return jax.nn.softmax(m @ w.astype(F32), -1)


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|, the reference following the system's
                      experts: rounding, and every fault but a swap
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed than a
                      largest of 2.4 million, so it tells precisions
                      apart that the largest does not
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies,
                      and how far its weights lie from the float32
                      probabilities, both relative to the probability
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
    """
    exp = np.asarray(routing["experts"])
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own = np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own.reshape(-1, exp.shape[-1]))])
    with jax.default_matmul_precision("highest"):
        probs = np.stack([np.asarray(_router(
            jnp.asarray(routing["inputs"][l], F32),
            states[f"layer_{l}.router.w_0"]))
            for l in range(exp.shape[0])])                    # [L, S, E]
    chosen = np.take_along_axis(probs, exp, -1)
    left_out = probs.copy()
    np.put_along_axis(left_out, exp, -np.inf, -1)
    least = chosen.min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / least
    weights = (chosen / chosen.sum(-1, keepdims=True)
               if config["norm_topk_prob"] else chosen)
    off = np.abs(np.asarray(routing["weights"], np.float32)
                 - weights) / weights
    return {"logits_rel_err": float(np.max(np.abs(got - want))
                                    / np.max(np.abs(want))),
            "logits_rms_err": float(np.sqrt(np.mean((got - want) ** 2)
                                            / np.mean(want ** 2))),
            "router_rel_err": float(max(gap.max(), off.max())),
            "routing_agree": float(agree),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "finite": bool(np.isfinite(got).all())}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))
