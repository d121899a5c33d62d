"""Plain reference of the LongCat-Flash decoder (Meituan,
`meituan-longcat/LongCat-Flash-Chat` config.json, `model_type:
longcat_flash`; the technical report is arXiv:2509.01322) in the
EXPANDED form: float32 `jax.numpy`, one full forward over one token
sequence under a causal mask, keys and values widened from the latent
for every head, no cache, no absorbed products, no sort, no batching,
every matrix multiplication at `highest` precision.  It knows nothing
of paddle_tpu: it takes a dict of named arrays under the names the
served decoder's `state_shapes` gives (`layer_<l>.sub_<i>.q_a_proj.w_0`,
`layer_<l>.router.w_0`, ...; weights are stored [in, out], the experts
[expert, in, out]) and the configuration's OWN keys (`num_layers`,
`moe_topk`, `zero_expert_num`, `mla_scale_q_lora`, ...).

One of the `num_layers` layers, a DOUBLE layer, on the stream x [S, d]
(d 6144, H 64 heads), with N an RMSNorm (eps `rms_norm_eps`) of its own
scale each time it appears:

  for i in (0, 1):
      h = N_in[i](x)
      x = x + MLA_i(h)                 # a latent attention of its own
      u = N_post[i](x)
      if i == 0:  s = MoE(u)           # the SHORTCUT: computed here ...
      x = x + SwiGLU_i(u)              # dense, `ffn_hidden_size`, SAME u
      if i == 1:  x = x + s            # ... and joined here

  MLA_i(h): c_q = N(h W_qa) * sqrt(d / q_lora_rank) (`mla_scale_q_lora`);
      q = c_q W_qb, H heads of `qk_nope_head_dim` unrotated +
      `qk_rope_head_dim` rotated columns; [c | k_pe] = h W_kva;
      c = N(c) * sqrt(d / kv_lora_rank) (`mla_scale_kv_lora`), the norm
      over the latent ALONE; k_pe rotated: ONE key part for all heads,
      taken BEFORE any norm; [k_nope | v] = c W_kvb a head; scores
      (nope + rope)^-0.5 q . k, causal softmax, the contexts side by
      side times W_o.  No bias.  RoPE: rotate-half over the rotary
      columns as they lie, at `rope_theta`, no scaling.  (A cache would
      hold [c | k_pe] a position a sub-block and nothing else.)
  MoE(u): p = softmax(u W_r) in float32 over ALL the router's columns:
      `n_routed` routed experts first (the router's width less
      `zero_expert_num`), then `zero_expert_num` IDENTITY experts; the
      `moe_topk` chosen are the largest of p + b (b the choice bias: it
      decides the choice alone, a tie to the lower index); w_e =
      `routed_scaling_factor` p_e, NOT renormalised;  MoE(u) = sum over
      the chosen routed e HELD here of w_e SwiGLU_e(u)
      (`expert_ffn_hidden_size`) + sum over the chosen identity e of
      w_e u.  The experts held are those whose matrices `states` holds,
      from `first_local_expert`: an assignment to an absent expert adds
      nothing and its weight is NOT shared out; the identity part needs
      no weights and is whole on every chip.
  logits = N(x) W_head, over the rows of the vocabulary the
      configuration holds.

ASSUMED (config.json has no key for them; the model card, the report
and the released modelling code say so; each is one field of the served
description and one fault below):
  * where the shortcut is taken (the first sub-block's normed state
    after attention, which its dense FFN also reads) and where it joins
    (after the second dense FFN): faults `moe_second_input`,
    `join_early`;
  * the identity expert returns the expert layer's INPUT u (normed),
    times its weight: faults `zero_nothing`, `zero_unnormed`;
  * the bias enters the choice and not the weight (`bias_in_weight`);
    one softmax over all columns (`softmax_512`); no renormalisation
    (`renormalised`);
  * the constants multiply the NORMED latents (`no_q_scale`,
    `no_kv_scale`);
  * pre-norm placement; rotate-half over the rotary columns as they lie
    (the released code un-interleaves them first: with seeded weights a
    relabelling of columns common to q and k); `k_pe` rotated
    (`k_pe_unrotated`);
  * the training losses and the bias's controller are absent.

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_layers`, the experts held and the
vocabulary are whatever the configuration and the arrays hold.

Memory: the served weights (10 GB of bfloat16) stand beside this, so
attention runs a block of heads at a time (a scan: scores of 16 heads
over 1152 positions are 85 MB), an expert is widened as it is applied
(a scan over the held experts, each applied densely to every token and
masked by the weights), and a dense FFN's matrices go through the same
scan as column blocks of one expert's width.

What decides `correct` is `compare`: the reference FOLLOWS the system's
choice of experts (a near-tie is a swap, not an error) and judges the
choice on the router's own input (`router_rel_err`).  `below` is the
reading one precision down (all bfloat16); `faults` are ten readings a
wrong step has to give.  `served` judges what a SERVER delivered, of
which only tokens are known.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("zero_nothing", "zero_unnormed", "moe_second_input",
          "join_early", "no_q_scale", "no_kv_scale", "renormalised",
          "bias_in_weight", "softmax_512", "k_pe_unrotated")
# heads a step of the attention's scan widens keys and values for
HEADS_BLOCK = 16


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, freq):
    """x [S, ..., Dr] at positions 0..S-1, rotate-half."""
    s, dr = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (s,) + (1,) * (x.ndim - 2) + (dr,))
    turned = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


def inv_freq(config: dict) -> np.ndarray:
    """The rotation's per-pair frequencies [qk_rope_head_dim / 2]."""
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    return theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)


def latent_scales(config: dict, fault=None):
    """(the constant on the normed query latent, on the normed
    key/value latent): sqrt(hidden / rank) under `mla_scale_q_lora` /
    `mla_scale_kv_lora`, else 1."""
    d = float(config["hidden_size"])
    q = (math.sqrt(d / config["q_lora_rank"])
         if config["mla_scale_q_lora"] and fault != "no_q_scale" else 1.0)
    kv = (math.sqrt(d / config["kv_lora_rank"])
          if config["mla_scale_kv_lora"] and fault != "no_kv_scale" else 1.0)
    return q, kv


def _experts(m, gate, up, down, weight, dtype):
    """sum over e of weight[:, e] * SwiGLU_e(m): a scan over the
    experts [E, ...], each widened to `dtype` as it is applied."""
    def one(acc, e):
        g, u, d, w = e
        act = jax.nn.silu(m @ g.astype(dtype)) * (m @ u.astype(dtype))
        return acc + (act @ d.astype(dtype)) * w[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (gate, up, down, weight.T.astype(dtype)))[0]


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_nope", "d_rope", "d_v", "eps", "rotate_k", "dtype"))
def _attention(x, p, freq, q_scale, kv_scale, *, n_heads, d_nope, d_rope,
               d_v, eps, rotate_k=True, dtype=F32):
    """x + MLA(RMSNorm(x)), expanded: keys and values widened from the
    latent, `HEADS_BLOCK` heads at a time.  `rotate_k=False` computes
    the fault `k_pe_unrotated`."""
    s = x.shape[0]
    d_lat = p["kv_a_norm"].shape[0]
    h = _rms(x, p["attn_norm"].astype(dtype), eps)
    c_q = _rms(h @ p["q_a"].astype(dtype), p["q_a_norm"].astype(dtype),
               eps) * q_scale.astype(dtype)
    ckv = h @ p["kv_a"].astype(dtype)
    c_kv = _rms(ckv[:, :d_lat], p["kv_a_norm"].astype(dtype),
                eps) * kv_scale.astype(dtype)
    k_pe = ckv[:, d_lat:]
    if rotate_k:
        k_pe = _rope(k_pe, freq)
    hb = math.gcd(n_heads, HEADS_BLOCK)
    nb = n_heads // hb
    dq = d_nope + d_rope
    q_b = p["q_b"].reshape(-1, nb, hb * dq).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(d_lat, nb, hb * (d_nope + d_v)).transpose(
        1, 0, 2)
    o = p["o"].reshape(nb, hb * d_v, -1)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    scale = jnp.asarray(dq ** -0.5, dtype)

    def heads(acc, w):
        w_q, w_kv, w_o = (a.astype(dtype) for a in w)
        q = (c_q @ w_q).reshape(s, hb, dq)
        q = jnp.concatenate(
            [q[..., :d_nope], _rope(q[..., d_nope:], freq)], -1)
        kv = (c_kv @ w_kv).reshape(s, hb, d_nope + d_v)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, None, :], (s, hb, d_rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv[..., d_nope:])
        return acc + ctx.reshape(s, hb * d_v) @ w_o, None

    return x + jax.lax.scan(heads, jnp.zeros_like(x), (q_b, kv_b, o))[0]


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _post_norm(x, scale, *, eps, dtype):
    return _rms(x, scale.astype(dtype), eps)


@functools.partial(jax.jit, static_argnames=("width", "dtype"))
def _dense(u, p, *, width, dtype):
    """SwiGLU(u) at the dense width, its columns in blocks of `width`
    (the sum over a block is the sum over its columns: the same
    mathematics, a matrix's float32 never whole)."""
    d, f = p["gate"].shape
    width = width if f % width == 0 else f
    gate, up = (w.reshape(d, f // width, width).transpose(1, 0, 2)
                for w in (p["gate"], p["up"]))
    down = p["down"].reshape(f // width, width, d)
    return _experts(u, gate, up, down,
                    jnp.ones((u.shape[0], f // width), dtype), dtype)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_zero", "first", "renorm", "bias_in_weight", "split_softmax",
    "zero", "dtype"))
def _moe(u, x, p, follow, scaling, *, top_k, n_zero, first, renorm=False,
         bias_in_weight=False, split_softmax=False, zero="input",
         dtype=F32):
    """-> (MoE(u) [S, d], its routing: the router's input, the top-k
    weights and experts of its own choice).  `p`: "router" [d, routed +
    identity], "bias" [routed + identity], "gate", "up", "down" [held,
    ...] (the experts `first` onward).  `x`: the un-normed stream (the
    fault `zero_unnormed` returns it from an identity expert).
    `follow` [S, k]: the experts to apply instead of its own choice,
    each weighed by the probability computed here; a position whose row
    is negative takes its own.  `zero`: "input" (an identity expert
    returns u), "nothing" or "unnormed" (faults); `renorm`,
    `bias_in_weight`, `split_softmax` (one softmax over the routed
    columns, one over the identity ones): faults."""
    s = u.shape[0]
    logits = u @ p["router"].astype(dtype)
    n_routed = logits.shape[-1] - n_zero
    if split_softmax:
        probs = jnp.concatenate(
            [jax.nn.softmax(logits[:, :n_routed], -1),
             jax.nn.softmax(logits[:, n_routed:], -1)], -1)
    else:
        probs = jax.nn.softmax(logits, -1)                      # [S, E+Z]
    biased = probs + p["bias"].astype(dtype)
    _, own_e = jax.lax.top_k(biased, top_k)

    def weights_of(experts):
        w = jnp.take_along_axis(biased if bias_in_weight else probs,
                                experts, -1)
        if renorm:
            return w / w.sum(-1, keepdims=True)
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(s)[:, None], use_e].set(weights_of(use_e))
    held = p["gate"].shape[0]
    y = _experts(u, p["gate"], p["up"], p["down"],
                 weight[:, first:first + held], dtype)
    if zero != "nothing":
        y = y + weight[:, n_routed:].sum(-1, keepdims=True) * (
            u if zero == "input" else x)
    routing = {"inputs": u.astype(F32),
               "weights": weights_of(own_e).astype(F32), "experts": own_e}
    return y, routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


ATTN_KEYS = {"attn_norm": "attn_norm.scale_0", "q_a": "q_a_proj.w_0",
             "q_a_norm": "q_a_norm.scale_0", "q_b": "q_b_proj.w_0",
             "kv_a": "kv_a_proj.w_0", "kv_a_norm": "kv_a_norm.scale_0",
             "kv_b": "kv_b_proj.w_0", "o": "o_proj.w_0"}
DENSE_KEYS = {"gate": "ffn_gate.w_0", "up": "ffn_up.w_0",
              "down": "ffn_down.w_0"}
MOE_KEYS = {"router": "router.w_0", "bias": "router_bias.b_0",
            "gate": "experts_gate.w_0", "up": "experts_up.w_0",
            "down": "experts_down.w_0"}


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every layer's expert layer stacked: "inputs" [L, S, D],
    "weights" and "experts" [L, S, k]), from the named arrays and the
    configuration's own keys.  `follow` [L, S, k]: the experts each
    layer applies in place of its own choice, where they are not
    negative.  `fault` computes a DIFFERENT model, one of `FAULTS`:
    "zero_nothing": the identity experts add nothing; "zero_unnormed":
    they return the un-normed stream; "moe_second_input": the expert
    layer reads the SECOND sub-block's normed state; "join_early": its
    result joins right after the first dense FFN, before the second
    attention; "no_q_scale", "no_kv_scale": a latent's constant left
    out; "renormalised": the chosen probabilities over their sum;
    "bias_in_weight": the weights are of p + b; "softmax_512": one
    softmax over the routed columns and another over the identity ones;
    "k_pe_unrotated": the shared key part as the projection made it."""
    assert fault is None or fault in FAULTS, fault
    top_k = int(config["moe_topk"])
    eps = float(config["rms_norm_eps"])
    attn = dict(n_heads=int(config["num_attention_heads"]),
                d_nope=int(config["qk_nope_head_dim"]),
                d_rope=int(config["qk_rope_head_dim"]),
                d_v=int(config["v_head_dim"]), eps=eps,
                rotate_k=fault != "k_pe_unrotated", dtype=dtype)
    if config.get("zero_expert_type", "identity") != "identity":
        raise NotImplementedError(config["zero_expert_type"])
    moe = dict(top_k=top_k, n_zero=int(config["zero_expert_num"]),
               first=int(config["first_local_expert"]),
               renorm=fault == "renormalised",
               bias_in_weight=fault == "bias_in_weight",
               split_softmax=fault == "softmax_512",
               zero={"zero_nothing": "nothing",
                     "zero_unnormed": "unnormed"}.get(fault, "input"),
               dtype=dtype)
    freq = jnp.asarray(inv_freq(config), F32)
    q_scale, kv_scale = (jnp.asarray(c, F32)
                         for c in latent_scales(config, fault))
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    width = int(config["expert_ffn_hidden_size"])
    own = np.full((len(ids), top_k), -1, np.int32)
    moe_at = 1 if fault == "moe_second_input" else 0
    routed = []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_layers"])):
            for i in (0, 1):
                def named(keys, prefix=f"layer_{l}.sub_{i}."):
                    return {k: states[prefix + n] for k, n in keys.items()}

                x = _attention(x, named(ATTN_KEYS), freq, q_scale,
                               kv_scale, **attn)
                u = _post_norm(x, states[f"layer_{l}.sub_{i}."
                                         "ffn_norm.scale_0"],
                               eps=eps, dtype=dtype)
                if i == moe_at:
                    s, r = _moe(
                        u, x, named(MOE_KEYS, f"layer_{l}."),
                        jnp.asarray(own if follow is None
                                    else follow[len(routed)], jnp.int32),
                        scaling, **moe)
                    routed.append(r)
                x = x + _dense(u, named(DENSE_KEYS), width=width,
                               dtype=dtype)
                if i == (0 if fault == "join_early" else 1):
                    x = x + s
        out = _head(x, states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    return out, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _probs(m, w):
    return jax.nn.softmax(m @ w.astype(F32), -1)


def router_rel_err(config: dict, probs, bias, experts, weights) -> float:
    """How far a system's routing (its `experts` and `weights` [..., k])
    lies from the rule, on the float32 probabilities `probs` [..., E + Z]
    of its OWN router inputs and the choice bias `bias` [..., E + Z],
    relative to the least chosen probability: the larger of
      * how far below a column it left out its least chosen one lies,
        by p + b;
      * how far its weights lie from p[chosen] x
        `routed_scaling_factor`."""
    probs = np.asarray(probs, np.float64)
    biased = probs + np.asarray(bias, np.float64)
    chosen = np.take_along_axis(probs, experts, -1)
    left_out = biased.copy()
    np.put_along_axis(left_out, experts, -np.inf, -1)
    least = np.take_along_axis(biased, experts, -1).min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / chosen.min(-1)
    want = chosen * float(config["routed_scaling_factor"])
    off = np.abs(np.asarray(weights, np.float64) - want) / want
    return float(max(gap.max(), off.max()))


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|, the reference following the system's
                      experts: rounding, and every fault but a swap;
                      one position decides it (reported)
      logits_p99_err  the same, a position's largest difference taken
                      at the 99th percentile over the positions
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed
      late_rms_err    `logits_rms_err` over the last half of the
                      positions alone: where the cache is longest
      router_rel_err  `router_rel_err` above on the system's own router
                      inputs: the choice under the bias, and the weights
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
      zero_share      share of the system's assignments that went to
                      identity experts (reported)
    """
    exp = np.asarray(routing["experts"])
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own = np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own.reshape(-1, exp.shape[-1]))])
    layers = range(int(config["num_layers"]))
    with jax.default_matmul_precision("highest"):
        probs = np.stack([np.asarray(_probs(
            jnp.asarray(routing["inputs"][l], F32),
            states[f"layer_{l}.router.w_0"])) for l in layers])
    bias = np.stack([np.asarray(
        jnp.asarray(states[f"layer_{l}.router_bias.b_0"], F32))
        for l in layers])[:, None, :]
    n_routed = probs.shape[-1] - int(config["zero_expert_num"])

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    worst = np.abs(got - want).max(-1)      # of each position
    return {"logits_rel_err": float(worst.max() / np.max(np.abs(want))),
            "logits_p99_err": float(np.percentile(worst, 99)
                                    / np.max(np.abs(want))),
            "logits_rms_err": rms(got, want),
            "late_rms_err": rms(got[half:], want[half:]),
            "router_rel_err": router_rel_err(config, probs, bias, exp,
                                             routing["weights"]),
            "routing_agree": float(agree),
            "zero_share": float(np.mean(exp >= n_routed)),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "finite": bool(np.isfinite(got).all())}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for the ten float32 models of `FAULTS`, as
    if each were the system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids,
                           *forward(states, config, ids, fault=fault))
            for fault in FAULTS}


def served(states: dict, config: dict, requests, dtype=F32,
           fault=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] (its OWN
    experts: the server's choice is not known).

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens: 0
                      where they agree, and small at a near-tie that
                      rounding or an expert swap turned

    Sequences of one length share one compiled forward pass."""
    agree, gap = [], []
    for ids, start in requests:
        ids = np.asarray(ids)
        want = np.asarray(forward(states, config, ids[:-1], dtype=dtype,
                                  fault=fault)[0], np.float32)[start - 1:]
        got = want[np.arange(len(want)), ids[start:]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
    agree, gap = np.concatenate(agree), np.concatenate(gap)
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "tokens": int(len(agree))}
