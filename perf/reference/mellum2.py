"""Plain reference of the Mellum 2 decoder (JetBrains,
`Mellum2-12B-A2.5B-Instruct` config.json, `model_type: mellum`):
float32 `jax.numpy`, one full forward over one token sequence under a
causal or a banded mask, no cache, no ring, no sort, no batching, every
matrix multiplication at `highest` precision.  It knows nothing of
paddle_tpu: it takes a dict of named arrays under the names the served
decoder's `state_shapes` gives (`layer_<l>.q_proj.w_0`, ...; weights
are stored [in, out], the experts [expert, in, out]) and the
configuration's own keys.

The layer, from config.json letter for letter:

  n = RMSNorm_in(x; rms_norm_eps)
  q = RoPE_kind(Wq n)  [S, 32 heads x 128]     k = RoPE_kind(Wk n)
  v = Wv n             [S, 4 heads x 128]; no bias, no norm on q or k;
      `num_attention_heads * head_dim` (4096) is not `hidden_size`
      (2304); query head h reads K/V head h // 8
  h = x + Wo . softmax(q k^T / sqrt(head_dim) + mask_kind) v
      `layer_types[l]` is the layer's kind.  sliding_attention:
      position i sees j with i - sliding_window < j <= i (1024 keys,
      itself included) and RoPE is `rope_parameters.sliding_attention`
      (default, theta 500000).  full_attention: all j <= i, and RoPE
      is `rope_parameters.full_attention`, YaRN: with d = head_dim and
      pair index i in 0..d/2-1,
          extrap_i = theta^(-2i/d),  interp_i = extrap_i / factor,
          c(r) = d ln(original_max / (2 pi r)) / (2 ln theta),
          low = max(floor(c(beta_fast)), 0),
          high = min(ceil(c(beta_slow)), d - 1),
          ramp_i = clip((i - low) / (high - low), 0, 1),
          inv_freq_i = interp_i ramp_i + extrap_i (1 - ramp_i),
      and cos and sin both times `attention_factor`.  Static: the same
      table at every length.  Rotate-half form, per head.
  m = RMSNorm_post(h);  p = softmax(Wr m) over ALL experts, float32
  out = h + sum over the top-k experts e of
            p_e / (sum of the k) . Wdown_e (silu(Wgate_e m) * (Wup_e m))
      (`norm_topk_prob` true: the k weights are renormalised to sum
      1); experts of width `moe_intermediate_size`; no shared expert;
      no token is dropped
  logits = Whead . RMSNorm_final(out);  untied head, no bias.

Every expert is applied densely to every token and masked by the top-k
weights, a block of `TOKEN_BLOCK` tokens at a time so that the
[tokens, experts, hidden] intermediate fits beside the served weights.

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_hidden_layers` and the two lists of
layer kinds are whatever the configuration holds (the benchmark's cut
keeps whole periods).  config.json has no key for a norm on Q and K nor
for a multi-token-prediction module, so neither exists here;
`intermediate_size` (a dense layer's width) is read by nothing, since
`mlp_layer_types` is `sparse` throughout.

What decides `correct` is `compare`, as in `olmoe.py`: the reference
FOLLOWS the system's choice of experts (with random weights the k-th
and k+1-th probabilities often lie closer than the served bf16
rounding moves them: a swap, not an error) and judges the choice on
the router's own input (`router_rel_err`).  Beside the whole sequence
it reads the positions at and past the window on their own
(`past_window_rms_err`): only there does a ring differ from a table.
`below` is the reading one precision down (all bfloat16); `faults`
are two readings a wrong cache or table has to give: a full mask on
the sliding layers, and plain RoPE on the full ones.

`served` judges what a SERVER delivered, of which only tokens are
known: requests it decoded greedily, each teacher-forced through
`forward` (this reference's own experts: the server's choice is not
known), and each delivered token held against the logits of the
position that produced it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
TOKEN_BLOCK = 128


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def inv_freq(params: dict, d: int) -> np.ndarray:
    """[d/2] float64 frequencies of one layer kind's RoPE."""
    theta = float(params["rope_theta"])
    extrap = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)])
    if params["rope_type"] == "default":
        return extrap
    assert params["rope_type"] == "yarn", params["rope_type"]
    interp = extrap / float(params["factor"])

    def c(r):
        return d * math.log(params["original_max_position_embeddings"]
                            / (2.0 * math.pi * r)) / (
                                2.0 * math.log(theta))

    low = max(math.floor(c(params["beta_fast"])), 0)
    high = min(math.ceil(c(params["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def _rope(x, inv, gain):
    """x [S, H, Dh] at positions 0..S-1, rotate-half, cos and sin
    times `gain`."""
    s, _, dh = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return (x * (jnp.cos(ang) * gain).astype(x.dtype)
            + turned * (jnp.sin(ang) * gain).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "d_head", "top_k", "eps", "renorm", "dtype"))
def _layer(x, p, inv, gain, window, follow, *, n_heads, n_kv, d_head,
           top_k, eps, renorm, dtype):
    """-> (the layer's output, its routing: the router's input, the
    top-k weights and experts of its own choice).  `window`: keys a
    position sees, 0 for all before it.  `follow` [S, k]: the experts
    to apply instead of its own choice, each weighed by the
    probability computed here; a position whose row is negative takes
    its own.  `inv`, `gain`, `window` and `follow` are arrays, so both
    kinds of layer, following or not, are ONE compiled program a
    precision and a length (a float32 one compiles for a quarter of a
    minute)."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    n = _rms(x, p["attn_norm"], eps)
    q = _rope((n @ p["q"]).reshape(s, n_heads, d_head), inv, gain)
    k = _rope((n @ p["k"]).reshape(s, n_kv, d_head), inv, gain)
    v = (n @ p["v"]).reshape(s, n_kv, d_head)
    # query head h reads K/V head h // (n_heads / n_kv)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d_head, dtype))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sees = (j <= i) & ((window == 0) | (j > i - window))
    scores = jnp.where(sees[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    h = x + ctx.reshape(s, n_heads * d_head) @ p["o"]

    m = _rms(h, p["ffn_norm"], eps)
    probs = jax.nn.softmax(m @ p["router"], -1)               # [S, E]
    own_w, own_e = jax.lax.top_k(probs, top_k)
    if renorm:
        own_w = own_w / own_w.sum(-1, keepdims=True)
    use_e = jnp.where(follow < 0, own_e, follow)
    use_w = jnp.take_along_axis(probs, use_e, -1)
    if renorm:
        use_w = use_w / use_w.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(s)[:, None], use_e].set(use_w)             # [S, E]

    def experts(args):
        mb, wb = args                                         # a block
        act = jax.nn.silu(jnp.einsum("sd,edf->sef", mb, p["gate"])) \
            * jnp.einsum("sd,edf->sef", mb, p["up"])
        y = jnp.einsum("sef,efd->sed", act, p["down"])
        return (y * wb[..., None]).sum(1)

    pad = -s % TOKEN_BLOCK
    blocks = [jnp.pad(a, ((0, pad), (0, 0))).reshape(
        -1, TOKEN_BLOCK, a.shape[-1]) for a in (m, weight)]
    y = jax.lax.map(experts, tuple(blocks)).reshape(s + pad, -1)[:s]
    routing = {"inputs": m.astype(F32), "weights": own_w.astype(F32),
               "experts": own_e}
    return h + y, routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


LAYER_KEYS = {"attn_norm": "attn_norm.scale_0", "q": "q_proj.w_0",
              "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
              "ffn_norm": "ffn_norm.scale_0", "router": "router.w_0",
              "gate": "experts_gate.w_0", "up": "experts_up.w_0",
              "down": "experts_down.w_0"}


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every layer stacked: "inputs" [L, S, D], "weights" and
    "experts" [L, S, k]), from the named arrays and the
    configuration's own keys.  `follow` [L, S, k]: the experts each
    layer applies in place of its own choice, where they are not
    negative.  `fault` computes a DIFFERENT model, for the readings
    `faults` gives: "full_mask" lets the sliding layers see every
    earlier position, "plain_rope" turns the full layers by the
    sliding layers' RoPE."""
    d_head = int(config["head_dim"])
    top_k = int(config["num_experts_per_tok"])
    kw = dict(n_heads=int(config["num_attention_heads"]),
              n_kv=int(config["num_key_value_heads"]), d_head=d_head,
              top_k=top_k, eps=float(config["rms_norm_eps"]),
              renorm=bool(config["norm_topk_prob"]), dtype=dtype)
    rope = config["rope_parameters"]
    own = np.full((len(ids), top_k), -1, np.int32)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            kind = config["layer_types"][l]
            params = rope[SLIDING if (fault == "plain_rope"
                                      and kind == FULL) else kind]
            window = (int(config["sliding_window"])
                      if kind == SLIDING and fault != "full_mask" else 0)
            x, r = _layer(
                x, {k: states[f"layer_{l}.{n}"]
                    for k, n in LAYER_KEYS.items()},
                jnp.asarray(inv_freq(params, d_head), F32),
                jnp.asarray(params.get("attention_factor", 1.0), F32),
                jnp.asarray(window, jnp.int32),
                jnp.asarray(own if follow is None else follow[l],
                            jnp.int32), **kw)
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
                      logits': steadier from seed to seed
      past_window_rms_err  `logits_rms_err` over the positions at and
                      past `sliding_window` alone (nothing where the
                      sequence is shorter): where a ring has wrapped
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies,
                      and how far its weights lie from the float32
                      probabilities (renormalised), both relative
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

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    out = {"logits_rel_err": float(np.max(np.abs(got - want))
                                   / np.max(np.abs(want))),
           "logits_rms_err": rms(got, want),
           "router_rel_err": float(max(gap.max(), off.max())),
           "routing_agree": float(agree),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "finite": bool(np.isfinite(got).all())}
    w = int(config["sliding_window"])
    if len(got) > w:
        out["past_window_rms_err"] = rms(got[w:], want[w:])
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for two float32 models that a wrong cache
    or a wrong table would compute, as if each were the system: the
    limits have to refuse both."""
    return {fault: compare(states, config, ids,
                           *forward(states, config, ids, fault=fault))
            for fault in ("full_mask", "plain_rope")}


def served(states: dict, config: dict, requests, dtype=F32,
           fault=None) -> dict:
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
                      rounding or an expert swap turned
      past_window_argmax_agree, past_window_gap_rms  the same over the
                      tokens sampled at positions at and past
                      `sliding_window` alone (None where there is
                      none): where a ring has wrapped

    Sequences of one length share one compiled forward pass."""
    w = int(config["sliding_window"])
    agree, gap, past = [], [], []
    for ids, start in requests:
        ids = np.asarray(ids)
        want = np.asarray(forward(states, config, ids[:-1], dtype=dtype,
                                  fault=fault)[0], np.float32)[start - 1:]
        got = want[np.arange(len(want)), ids[start:]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
        past.append(np.arange(start - 1, len(ids) - 1) >= w)
    agree, gap, past = (np.concatenate(x) for x in (agree, gap, past))
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "past_window_argmax_agree":
                float(agree[past].mean()) if past.any() else None,
            "past_window_gap_rms":
                float(np.sqrt(np.mean(gap[past] ** 2)))
                if past.any() else None,
            "tokens": int(len(agree)), "tokens_past_window": int(past.sum())}
