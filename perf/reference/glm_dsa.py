"""Plain reference of the GLM-5.2 decoder (`zai-org/GLM-5.2` config.json,
`model_type: glm_moe_dsa`: multi-head latent attention under DeepSeek-
V3.2's sparse attention with the selection SHARED across layers) in the
EXPANDED form: float32 `jax.numpy`, one full forward over one token
sequence, keys and values widened from the latent for every head, the
selection a MASK over positions, no cache, no absorbed products, no
kernel, no batching, every matrix multiplication at `highest`
precision.  It knows nothing of paddle_tpu: it takes a dict of named
arrays under the names the served decoder's `state_shapes` gives
(`layer_<l>.q_a_proj.w_0`, `layer_<l>.indexer_q.w_0`, ...; weights are
stored [in, out], the experts [expert, in, out]) and the configuration's
OWN keys (`index_topk`, `indexer_types`, `mlp_layer_types`, ...).

One of the `num_hidden_layers` layers on the stream x [S, d] (d 6144,
H 64 heads), N an RMSNorm (eps `rms_norm_eps`) of its own scale:

  h = N(x);  x = x + MLA(h, S_l);  x = x + FFN_l(N(x))

  MLA(h, S): c_q = N(h W_qa); q = c_q W_qb, H heads of `qk_nope_head_dim`
      unrotated + `qk_rope_head_dim` rotated columns; [c | k_r] = h W_kva;
      c = N(c) over the latent alone; k_r rotated, ONE key part for all
      heads, taken BEFORE any norm; [k_nope | v] = c W_kvb a head (v is
      `v_head_dim` wide); scores (nope + rope)^-0.5 q . k; position t's
      softmax is over the positions s in S(t) ALONE; the contexts side by
      side times W_o.  No bias.  RoPE: rotate-half over the rotary
      columns as they lie, theta `rope_parameters.rope_theta`.
  S_l, on a layer whose `indexer_types` entry is "full" (the LIGHTNING
      INDEXER): q_I = c_q W_Iq, `index_n_heads` heads of `index_head_dim`;
      k_I(s) = LayerNorm(h_s W_Ik) (scale and shift, eps 1e-6): ONE key
      a position for all index heads; RoPE on the first
      `qk_rope_head_dim` columns of q_I and k_I; w = h W_Iw a head;
      I(t, s) = sum_j w_j(t) (heads x head size)^-0.5 relu(q_I,j(t) .
      k_I(s)); S(t) = the `index_topk` positions s <= t of largest
      I(t, s), all of them while t < `index_topk`, a tie to the lower
      position.  On a "shared" layer S_l is the nearest EARLIER "full"
      layer's.
  FFN_l: `mlp_layer_types` "dense": SwiGLU of `intermediate_size`;
      "sparse": scores sigmoid(u W_r) in float32 over all the router's
      columns, the `num_experts_per_tok` of largest score + choice bias
      (a tie to the lower index), weights the chosen SCORES renormalised
      times `routed_scaling_factor`; sum over the chosen experts HELD
      here (those whose matrices `states` holds, from
      `first_local_expert`) of w_e SwiGLU_e(u) (`moe_intermediate_size`),
      plus ONE shared expert of the same width on every token.  An
      assignment to an absent expert adds nothing and its weight is NOT
      shared out.
  logits = N(x) W_head, over the rows of the vocabulary held.

ASSUMED (config.json has no key for them; each is one field of the
served description and one fault below where a fault can show it):
pre-norm placement; `k_r` taken before the norm; the LayerNorm (shift,
eps 1e-6) on the index key (`key_unnormed`); RoPE on the first
`qk_rope_head_dim` index columns (`no_index_rope`); the two constants
on w and w itself (`no_head_weights`), the relu (`no_relu`); rotate-half
over the rotary columns as they lie (`rope_interleave`,
`indexer_rope_interleave`: with seeded weights a relabelling of columns
common to query and key); the index keys in the cache's own precision
(the released kernels hold them in 8 bits after a Hadamard rotation,
which is orthogonal and changes no score; the 8-bit store is a
different result and is not computed here).

Departures from the published model: weights are random from the seed;
`num_hidden_layers`, the experts held and the vocabulary are whatever
the configuration and the arrays hold; no multi-token-prediction layer.

Memory: the served weights (8 GB of bfloat16) stand beside this, so
attention runs a block of heads at a time (a scan), an expert is
widened as it is applied (a scan over the held experts, each applied
densely to every token and masked by the weights), and a dense FFN's
matrices go through the same scan as column blocks of an expert's width.

What decides `correct` is `compare`: the reference FOLLOWS the system's
experts and the system's SELECTION (a near-tie at the `index_topk`-th
score is a swap, not an error), and judges the index scores on the
system's own inputs (`index_rel_err`), the selection on the system's
own scores (`selection_gap`) and the router on its own input
(`router_rel_err`).  `below` is the reading one precision down (all
bfloat16); `faults` are nine readings a wrong step has to give.
`served` judges what a SERVER delivered, of which only tokens are known.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("dense_attention", "no_relu", "no_head_weights", "key_unnormed",
          "no_index_rope", "shared_later", "topk_future",
          "not_renormalised", "bias_in_weight")
# heads a step of the attention's (the indexer's) scan computes
HEADS_BLOCK = 4
FULL, SHARED = "full", "shared"


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, freq, positions):
    """x [S, ..., Dr] at `positions` [S], rotate-half."""
    s, dr = x.shape[0], x.shape[-1]
    ang = positions.astype(F32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (s,) + (1,) * (x.ndim - 2) + (dr,))
    turned = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


def inv_freq(config: dict) -> np.ndarray:
    """The rotation's per-pair frequencies [qk_rope_head_dim / 2]."""
    d = int(config["qk_rope_head_dim"])
    theta = float(config["rope_parameters"]["rope_theta"])
    return theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)


def _experts(m, gate, up, down, weight, dtype):
    """sum over e of weight[:, e] * SwiGLU_e(m): a scan over the
    experts [E, ...], each widened to `dtype` as it is applied."""
    def one(acc, e):
        g, u, d, w = e
        act = jax.nn.silu(m @ g.astype(dtype)) * (m @ u.astype(dtype))
        return acc + (act @ d.astype(dtype)) * w[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (gate, up, down, weight.T.astype(dtype)))[0]


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _latents(x, p, *, eps, dtype):
    """-> (h = N(x), c_q = N(h W_qa)): what attention and the indexer
    both read."""
    h = _rms(x, p["attn_norm"].astype(dtype), eps)
    return h, _rms(h @ p["q_a"].astype(dtype), p["q_a_norm"].astype(dtype),
                   eps)


def index_scores(h, c_q, p, freq, positions, *, n_heads, d_rope,
                 relu=True, weights=True, normed=True, rotated=True,
                 dtype=F32):
    """The lightning indexer's I [S queries, S keys] from the block's
    normed input h [S, d] and the normed query latent c_q [S,
    q_lora_rank], every pair computed (the caller masks what a position
    may not see).  `relu`, `weights`, `normed`, `rotated` False: the
    faults `no_relu`, `no_head_weights`, `key_unnormed`,
    `no_index_rope`."""
    s, di = h.shape[0], p["idx_k"].shape[1]
    k = h @ p["idx_k"].astype(dtype)
    if normed:
        mu = k.mean(-1, keepdims=True)
        var = ((k - mu) ** 2).mean(-1, keepdims=True)
        k = ((k - mu) / jnp.sqrt(var + jnp.asarray(1e-6, dtype))
             * p["idx_k_scale"].astype(dtype)
             + p["idx_k_shift"].astype(dtype))
    w = (h @ p["idx_w"].astype(dtype) if weights
         else jnp.ones((s, n_heads), dtype))
    w = w * jnp.asarray(1.0 / math.sqrt(n_heads * di), dtype)
    if rotated:
        k = jnp.concatenate(
            [_rope(k[:, :d_rope], freq, positions), k[:, d_rope:]], -1)
    hb = math.gcd(n_heads, HEADS_BLOCK)
    w_q = p["idx_q"].reshape(-1, n_heads // hb, hb * di).transpose(1, 0, 2)

    def heads(acc, blk):
        w_qb, w_b = blk
        q = (c_q @ w_qb.astype(dtype)).reshape(s, hb, di)
        if rotated:
            q = jnp.concatenate(
                [_rope(q[..., :d_rope], freq, positions), q[..., d_rope:]],
                -1)
        dots = jnp.einsum("qhd,kd->hqk", q, k)
        if relu:
            dots = jax.nn.relu(dots)
        return acc + jnp.einsum("hqk,qh->qk", dots, w_b), None

    return jax.lax.scan(
        heads, jnp.zeros((s, s), dtype),
        (w_q, w.reshape(s, n_heads // hb, hb).transpose(1, 0, 2)))[0]


_index_scores = jax.jit(index_scores, static_argnames=(
    "n_heads", "d_rope", "relu", "weights", "normed", "rotated", "dtype"))


def top_rows(scores, valid, k: int):
    """bool [S, R]: each row's `k` valid entries of largest score (all
    of them where there are `k` or fewer), a tie at the k-th to the
    lower index.  By a sort: the k-th largest value, the entries above
    it, and of those AT it the lowest until there are `k`."""
    scores = jnp.where(valid, scores.astype(F32), -jnp.inf)
    if k >= scores.shape[-1]:
        return valid
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    above = scores > kth
    tied = (scores == kth) & valid
    room = k - above.sum(-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_rope", "top_k", "relu", "weights", "normed", "rotated",
    "future", "dtype"))
def _indexer(h, c_q, p, freq, *, n_heads, d_rope, top_k, relu=True,
             weights=True, normed=True, rotated=True, future=False,
             dtype=F32):
    """-> (I [S, S] float32, minus infinity where s > t; S(t) as a mask
    [S, S]).  `future`: the fault `topk_future`, the `index_topk`
    chosen among ALL positions and only then cut at the cursor."""
    s = h.shape[0]
    scores = index_scores(h, c_q, p, freq, jnp.arange(s), n_heads=n_heads,
                          d_rope=d_rope, relu=relu, weights=weights,
                          normed=normed, rotated=rotated,
                          dtype=dtype).astype(F32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    chosen = top_rows(scores, jnp.ones_like(causal) if future else causal,
                      top_k) & causal
    if future:
        # a position whose every chosen row lies ahead attends itself
        chosen |= jnp.eye(s, dtype=bool)
    return jnp.where(causal, scores, -jnp.inf), chosen


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_nope", "d_rope", "d_v", "eps", "dtype"))
def _attention(x, h, c_q, sees, p, freq, *, n_heads, d_nope, d_rope, d_v,
               eps, dtype=F32):
    """x + MLA(h, S), expanded: keys and values widened from the latent,
    `HEADS_BLOCK` heads at a time; `sees` [S, S] bool is S(t) as a mask
    (the causal mask itself where nothing selects)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    d_lat = p["kv_a_norm"].shape[0]
    ckv = h @ p["kv_a"].astype(dtype)
    c_kv = _rms(ckv[:, :d_lat], p["kv_a_norm"].astype(dtype), eps)
    k_pe = _rope(ckv[:, d_lat:], freq, pos)
    hb = math.gcd(n_heads, HEADS_BLOCK)
    nb = n_heads // hb
    dq = d_nope + d_rope
    q_b = p["q_b"].reshape(-1, nb, hb * dq).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(d_lat, nb, hb * (d_nope + d_v)).transpose(
        1, 0, 2)
    o = p["o"].reshape(nb, hb * d_v, -1)
    scale = jnp.asarray(dq ** -0.5, dtype)

    def heads(acc, w):
        w_q, w_kv, w_o = (a.astype(dtype) for a in w)
        q = (c_q @ w_q).reshape(s, hb, dq)
        q = jnp.concatenate(
            [q[..., :d_nope], _rope(q[..., d_nope:], freq, pos)], -1)
        kv = (c_kv @ w_kv).reshape(s, hb, d_nope + d_v)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, None, :], (s, hb, d_rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(sees[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv[..., d_nope:])
        return acc + ctx.reshape(s, hb * d_v) @ w_o, None

    return x + jax.lax.scan(heads, jnp.zeros_like(x), (q_b, kv_b, o))[0]


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _post_norm(x, scale, *, eps, dtype):
    return _rms(x, scale.astype(dtype), eps)


@functools.partial(jax.jit, static_argnames=("width", "dtype"))
def _dense(u, p, *, width, dtype):
    """SwiGLU(u), its columns in blocks of `width` (the sum over a
    block is the sum over its columns: the same mathematics, a matrix's
    float32 never whole)."""
    d, f = p["gate"].shape
    width = width if f % width == 0 else f
    gate, up = (w.reshape(d, f // width, width).transpose(1, 0, 2)
                for w in (p["gate"], p["up"]))
    down = p["down"].reshape(f // width, width, d)
    return _experts(u, gate, up, down,
                    jnp.ones((u.shape[0], f // width), dtype), dtype)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "renorm", "bias_in_weight", "dtype"))
def _moe(u, p, follow, scaling, *, top_k, first, renorm=True,
         bias_in_weight=False, dtype=F32):
    """-> (the held experts' part of MoE(u) and the shared expert's
    [S, d], its routing: the router's input, the top-k weights and
    experts of its own choice).  `follow` [S, k]: the experts to apply
    instead of its own choice, each weighed by the score computed here;
    a position whose row is negative takes its own.  `renorm` False,
    `bias_in_weight`: faults."""
    s = u.shape[0]
    scores = jax.nn.sigmoid(u @ p["router"].astype(dtype))
    biased = scores + p["bias"].astype(dtype)
    _, own_e = jax.lax.top_k(biased, top_k)

    def weights_of(experts):
        w = jnp.take_along_axis(biased if bias_in_weight else scores,
                                experts, -1)
        if renorm:
            w = w / w.sum(-1, keepdims=True)
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(s)[:, None], use_e].set(weights_of(use_e))
    held = p["gate"].shape[0]
    y = _experts(u, p["gate"], p["up"], p["down"],
                 weight[:, first:first + held], dtype)
    y = y + _experts(u, p["shared_gate"][None], p["shared_up"][None],
                     p["shared_down"][None], jnp.ones((s, 1), dtype), dtype)
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
INDEX_KEYS = {"idx_q": "indexer_q.w_0", "idx_k": "indexer_k.w_0",
              "idx_k_scale": "indexer_k_norm.scale_0",
              "idx_k_shift": "indexer_k_norm.shift_0",
              "idx_w": "indexer_w.w_0"}
DENSE_KEYS = {"gate": "ffn_gate.w_0", "up": "ffn_up.w_0",
              "down": "ffn_down.w_0"}
MOE_KEYS = {"router": "router.w_0", "bias": "router_bias.b_0",
            "gate": "experts_gate.w_0", "up": "experts_up.w_0",
            "down": "experts_down.w_0", "shared_gate": "shared_gate.w_0",
            "shared_up": "shared_up.w_0", "shared_down": "shared_down.w_0"}


def selecting_layers(config: dict):
    """The layers whose `indexer_types` entry is "full", in order."""
    kinds = config["indexer_types"][:int(config["num_hidden_layers"])]
    return [l for l, kind in enumerate(kinds) if kind == FULL]


def forward(states: dict, config: dict, ids, follow=None, select=None,
            dtype=F32, fault=None, logits_from: int = 0):
    """[S] token ids -> ([S - logits_from, vocab] float32 next-token
    logits of positions `logits_from` onward, what the model chose:
    the routing of every sparse layer stacked ("inputs" [M, S, D],
    "weights" and "experts" [M, S, k]) and of every selecting layer
    ("index_inputs" [F, S, D], "index_latents" [F, S, q_lora_rank],
    "index_scores" [F, S, S] float32 and "selected" [F, S, S] bool: the
    rows ATTENDED OVER)).  `follow` [M, S, k]: the experts each sparse
    layer applies in place of its own choice, where not negative;
    `select` [F, S, S] bool: the selection each selecting layer (and
    the shared layers after it) attends over in place of its own.
    `fault` computes a DIFFERENT model, one of `FAULTS`:
    "dense_attention": every layer attends over all the positions up to
    its own; "no_relu", "no_head_weights", "key_unnormed",
    "no_index_rope": a part of the index score left out; "shared_later":
    a shared layer attends over the LATER selecting layer's rows (of a
    first pass); "topk_future": the `index_topk` chosen among all
    positions, the cursor's cut after; "not_renormalised": the chosen
    scores as they are (times the factor); "bias_in_weight": the
    weights are of score + bias."""
    assert fault is None or fault in FAULTS, fault
    later = None
    if fault == "shared_later":
        # the later layer's selection exists only after a whole pass
        later = np.asarray(forward(states, config, ids, follow=follow,
                                   select=select, dtype=dtype)[1]["selected"])
    n = int(config["num_hidden_layers"])
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    attn = dict(n_heads=int(config["num_attention_heads"]),
                d_nope=int(config["qk_nope_head_dim"]),
                d_rope=int(config["qk_rope_head_dim"]),
                d_v=int(config["v_head_dim"]), eps=eps, dtype=dtype)
    index = dict(n_heads=int(config["index_n_heads"]),
                 d_rope=int(config["qk_rope_head_dim"]),
                 top_k=int(config["index_topk"]),
                 relu=fault != "no_relu",
                 weights=fault != "no_head_weights",
                 normed=fault != "key_unnormed",
                 rotated=fault != "no_index_rope",
                 future=fault == "topk_future", dtype=dtype)
    moe = dict(top_k=top_k, first=int(config["first_local_expert"]),
               renorm=(bool(config["norm_topk_prob"])
                       and fault != "not_renormalised"),
               bias_in_weight=fault == "bias_in_weight", dtype=dtype)
    freq = jnp.asarray(inv_freq(config), F32)
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    width = int(config["moe_intermediate_size"])
    own = np.full((len(ids), top_k), -1, np.int32)
    causal = jnp.tril(jnp.ones((len(ids), len(ids)), bool))
    kinds = config["indexer_types"][:n]
    routed, picked, sees, carried = [], [], causal, causal
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(n):
            def named(keys, prefix=f"layer_{l}."):
                return {k: states[prefix + n_] for k, n_ in keys.items()}

            h, c_q = _latents(x, named(ATTN_KEYS), eps=eps, dtype=dtype)
            if kinds[l] == FULL:
                scores, chosen = _indexer(h, c_q, named(INDEX_KEYS), freq,
                                          **index)
                sees = (chosen if select is None
                        else jnp.asarray(select[len(picked)]))
                if fault == "dense_attention":
                    sees = causal
                # what the shared layers after this one attend over
                carried = sees
                if later is not None and len(picked) + 1 < len(later):
                    carried = jnp.asarray(later[len(picked) + 1])
                picked.append({"index_inputs": h.astype(F32),
                               "index_latents": c_q.astype(F32),
                               "index_scores": scores, "selected": sees,
                               "own": chosen})
            x = _attention(x, h, c_q, sees if kinds[l] == FULL else carried,
                           named(ATTN_KEYS), freq, **attn)
            u = _post_norm(x, states[f"layer_{l}.ffn_norm.scale_0"],
                           eps=eps, dtype=dtype)
            if config["mlp_layer_types"][l] == "dense":
                x = x + _dense(u, named(DENSE_KEYS), width=width,
                               dtype=dtype)
                continue
            y, r = _moe(u, named(MOE_KEYS), jnp.asarray(
                own if follow is None else follow[len(routed)], jnp.int32),
                scaling, **moe)
            routed.append(r)
            x = x + y
        out = _head(x[logits_from:], states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    chose = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    chose.update({k: jnp.stack([p[k] for p in picked]) for k in picked[0]})
    return out, chose


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _sigmoid(m, w):
    return jax.nn.sigmoid(m @ w.astype(F32))


def router_rel_err(config: dict, scores, bias, experts, weights) -> float:
    """How far a system's routing (its `experts` and `weights` [..., k])
    lies from the rule, on the float32 scores [..., E] of its OWN router
    inputs and the choice bias [..., E], relative to the least chosen
    score: the larger of how far below an expert it left out its least
    chosen one lies, by score + bias, and how far its weights lie from
    the chosen scores renormalised times `routed_scaling_factor`."""
    scores = np.asarray(scores, np.float64)
    biased = scores + np.asarray(bias, np.float64)
    chosen = np.take_along_axis(scores, experts, -1)
    left_out = biased.copy()
    np.put_along_axis(left_out, experts, -np.inf, -1)
    least = np.take_along_axis(biased, experts, -1).min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / chosen.min(-1)
    want = chosen * float(config["routed_scaling_factor"])
    if config["norm_topk_prob"]:
        want = want / chosen.sum(-1, keepdims=True)
    off = np.abs(np.asarray(weights, np.float64) - want) / want
    return float(max(gap.max(), off.max()))


def selection_gap(config: dict, scores, selected) -> float:
    """How far a system's selection [F, S, S] lies from the rule on its
    OWN index scores [F, S, S]: the largest amount by which a position
    it left out (at or before the query's) outscores the least position
    it selected, over the scores' root mean square; a selection past
    the query's position, or of another size than min(t + 1,
    `index_topk`), reads infinity."""
    scores = np.asarray(scores, np.float64)
    selected = np.asarray(selected, bool)
    s = scores.shape[-1]
    causal = np.tril(np.ones((s, s), bool))
    want = np.minimum(np.arange(s) + 1, int(config["index_topk"]))
    if (selected & ~causal).any() or (selected.sum(-1) != want).any():
        return float("inf")
    least = np.where(selected, scores, np.inf).min(-1)
    left = np.where(causal & ~selected, scores, -np.inf).max(-1)
    rms = np.sqrt(np.mean(scores[:, causal] ** 2))
    return float(np.maximum(0.0, left - least).max() / rms)


def compare(states: dict, config: dict, ids, got, routing,
            late_from=None) -> dict:
    """A system's [S, vocab] logits and what it chose (what `forward`
    returns beside the logits, as the system computed it; its index
    arrays may be cut to the first S rows of a longer table) against
    this reference on the same weights and tokens:

      logits_rms_err  root mean square of the logits' difference over
                      theirs, the reference FOLLOWING the system's
                      experts and selection: rounding, and every fault
                      but a swap at a near-tie
      logits_p99_err  a position's largest |difference| at the 99th
                      percentile over the positions, over the largest
                      |logit|
      logits_rel_err  the largest of them: one position decides it
                      (reported)
      late_rms_err    `logits_rms_err` over the positions from
                      `late_from` (default: `index_topk`, else the last
                      half) alone: where a selection leaves rows out
      own_rms_err     `logits_rms_err` against the reference on its OWN
                      experts and selection (reported: near-ties count)
      index_rel_err   the system's index scores against the reference's
                      on the system's own inputs, by root mean square
                      over the causal pairs
      selection_gap   `selection_gap` above on its own scores
      selection_agree share of the rows it selected that the reference,
                      following it, would have selected too (reported)
      router_rel_err  `router_rel_err` above on its own router inputs
      routing_agree   share of its assignments the reference would have
                      made too (reported)
    """
    s = len(ids)
    exp = np.asarray(routing["experts"])
    sel = np.asarray(routing["selected"])[:, :, :s]
    sys_scores = np.asarray(routing["index_scores"], np.float32)[:, :, :s]
    want, own = forward(states, config, ids, follow=exp, select=sel)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    mine = np.asarray(forward(states, config, ids)[0], np.float32)
    own_e, own_s = np.asarray(own["experts"]), np.asarray(own["own"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own_e.reshape(-1, exp.shape[-1]))])
    sparse = [l for l in range(int(config["num_hidden_layers"]))
              if config["mlp_layer_types"][l] == "sparse"]
    causal = np.tril(np.ones((s, s), bool))
    freq = jnp.asarray(inv_freq(config), F32)
    index_err = []
    with jax.default_matmul_precision("highest"):
        scores = np.stack([np.asarray(_sigmoid(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(sparse)])
        for i, l in enumerate(selecting_layers(config)):
            ref = np.asarray(_index_scores(
                jnp.asarray(routing["index_inputs"][i], F32),
                jnp.asarray(routing["index_latents"][i], F32),
                {k: states[f"layer_{l}.{n}"]
                 for k, n in INDEX_KEYS.items()}, freq, jnp.arange(s),
                n_heads=int(config["index_n_heads"]),
                d_rope=int(config["qk_rope_head_dim"])), np.float32)
            index_err.append(
                np.sqrt(np.mean((sys_scores[i][causal] - ref[causal]) ** 2)
                        / np.mean(ref[causal] ** 2)))
    bias = np.stack([np.asarray(
        jnp.asarray(states[f"layer_{l}.router_bias.b_0"], F32))
        for l in sparse])[:, None, :]

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    late = int(late_from if late_from is not None
               else config["index_topk"] if s > config["index_topk"]
               else s // 2)
    worst = np.abs(got - want).max(-1)      # of each position
    top = np.max(np.abs(want))
    return {"logits_rel_err": float(worst.max() / top),
            "logits_p99_err": float(np.percentile(worst, 99) / top),
            "logits_rms_err": rms(got, want),
            "late_rms_err": rms(got[late:], want[late:]),
            "own_rms_err": rms(got, mine),
            "index_rel_err": float(max(index_err)),
            "selection_gap": selection_gap(config, sys_scores, sel),
            "selection_agree": float((sel & own_s).sum() / sel.sum()),
            "rows_dropped_share": float(
                1.0 - sel[:, late:].sum() / np.broadcast_to(
                    causal, sel.shape)[:, late:].sum()),
            "router_rel_err": router_rel_err(config, scores, bias, exp,
                                             routing["weights"]),
            "routing_agree": float(agree),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "late_from": late,
            "finite": bool(np.isfinite(got).all())}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for the nine float32 models of `FAULTS`, as
    if each were the system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids,
                           *forward(states, config, ids, fault=fault))
            for fault in FAULTS}


def served(states: dict, config: dict, requests, dtype=F32,
           fault=None, pad_to=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] (its OWN
    experts and selection: the server's are not known).

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens
      rows_dropped_share  share of the rows under the sampled positions'
                      cursors that the selection left out

    Every request is padded to ONE length (a causal model's earlier
    positions do not see the pad), so one compiled forward serves all:
    `pad_to`, or the longest rounded up to 128."""
    agree, gap, kept, under = [], [], 0, 0
    longest = pad_to or -(-max(len(ids) - 1
                               for ids, _ in requests) // 128) * 128
    topk = int(config["index_topk"])
    for ids, start in requests:
        ids = np.asarray(ids)
        n = len(ids) - 1
        padded = np.zeros(longest, ids.dtype)
        padded[:n] = ids[:-1]
        want = np.asarray(forward(
            states, config, padded, dtype=dtype, fault=fault,
            logits_from=start - 1)[0], np.float32)[:n - start + 1]
        got = want[np.arange(len(want)), ids[start:]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
        rows = np.arange(start, n + 1)
        kept += int(np.minimum(rows, topk).sum())
        under += int(rows.sum())
    agree, gap = np.concatenate(agree), np.concatenate(gap)
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "rows_dropped_share": 1.0 - kept / under,
            "tokens": int(len(agree))}
