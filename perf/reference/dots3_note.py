"""Plain reference of the dots3-note-prev language model (`dots-studio/
dots3-note-prev` config.json, `model_type: dots3_note`: latent attention
on every layer, the FULL layers under DeepSeek-V3.2's sparse attention,
the SLIDING layers a window of 513 keys at a latent geometry of their
own) in the EXPANDED form: float32 `jax.numpy`, one full forward over
one token sequence, keys and values widened from the latent for every
head, the window a BAND MASK over the whole sequence (no ring), the
selection a top-`index_topk` of the full score matrix a query row (a
mask over positions), no cache, no absorbed products, no kernel, no
batching, every matrix multiplication at `highest` precision.  It knows
nothing of paddle_tpu: it takes a dict of named arrays under the names
the served decoder's `state_shapes` gives (`layer_<l>.q_a_proj.w_0`,
`layer_<l>.indexer_q.w_0`, ...; weights are stored [in, out], the
experts [expert, in, out]) and the configuration's OWN keys (`swa_*`,
`sliding_window_size`, `layer_types`, `index_topk`, ...).

One of the `num_hidden_layers` layers on the stream x [S, d] (d 5120),
N an RMSNorm (eps `rms_norm_eps`) of its own scale:

  z = N(x);  x = x + MLA_kind(z);  x = x + FFN_l(N(x))

  MLA_F, a `full_attention` layer (H 128 heads): c_q = N(z W_qa) times
      sqrt(d / `q_lora_rank`); q = c_q W_qb, H heads of
      `qk_nope_head_dim` unrotated + `qk_rope_head_dim` rotated columns;
      [c | k_r] = z W_kva; c = N(c) over the latent alone, times sqrt(d /
      `kv_lora_rank`); k_r rotated at `rope_theta`, ONE key part for all
      heads, taken BEFORE any norm; [k_nope | v] = c W_kvb a head; scores
      (nope + rope)^-0.5 q . k; position t's softmax is over the
      positions s in S(t) ALONE (the lightning indexer's selection, as
      `glm_dsa.py` has it: q_I = c_q W_Iq reads the RESCALED c_q; k_I =
      LayerNorm(z W_Ik), eps 1e-6; RoPE on the first `qk_rope_head_dim`
      columns of both; w = z W_Iw (heads x head size)^-0.5; I(t, s) =
      sum_j w_j relu(q_Ij . k_Is); S(t) the `index_topk` positions s <= t
      of largest I, all while there are fewer, a tie to the lower
      position; EVERY full layer computes its own); each head's context
      times sigmoid(z W_g)_h, ONE scalar a head (`attention_gate_type:
      headwise`); the contexts side by side times W_o.  No bias.
  MLA_S, a `sliding_attention` layer: the same at the `swa_*` sizes
      (`swa_num_attention_heads` 64 heads, `swa_q_lora_rank`,
      `swa_kv_lora_rank` 1024, `swa_qk_nope_head_dim` 192,
      `swa_qk_rope_head_dim`, `swa_v_head_dim`, `swa_rope_theta`), each
      rescale by ITS rank, NO indexer: position t attends over positions
      t - (`sliding_window_size` - 1) to t, itself included.
  FFN_l: the first `first_k_dense_replace` layers a SwiGLU of
      `intermediate_size`; the others: scores sigmoid(u W_r) in float32
      over all the router's columns, the `num_experts_per_tok` of largest
      score + choice bias (a tie to the lower index; one group, no
      limit), weights the chosen SCORES renormalised times
      `routed_scaling_factor`; sum over the chosen experts HELD here
      (those whose matrices `states` holds, from `first_local_expert`)
      of w_e SwiGLU_e(u) (`moe_intermediate_size`), plus ONE shared
      expert of the same width on every token.  An assignment to an
      absent expert adds nothing and its weight is NOT shared out.
  logits = N(x) W_head, over the rows of the vocabulary held.

READINGS of keys that config.json states and does not define (each one
field of the served description and one fault below): pre-norm
placement; `sliding_window_size` 513 = 513 keys, the query's own among
them (`window_minus_1`, `window_plus_1`); `apply_mla_qkv_lora_rescale`
= the two square roots above, by each kind's own rank (`no_q_rescale`,
`no_kv_rescale`, `rescale_swapped`); q_I from the rescaled c_q;
`attention_gate_type: headwise` = a sigmoid scalar a head of the
layer's normed input on the head's context before W_o (`no_gate_full`,
`no_gate_sliding`, `gate_elementwise`); the indexer's four readings
(`key_unnormed`, `no_index_rope`, `no_head_weights`, `no_relu`); one
group in the router (no `n_group` key); `k_r` before the norm;
rotate-half over the rotary columns as they lie.  The vision and audio
encoders and the multi-token-prediction layer have no key in the
language model's config and are absent: the reference takes text ids.

Memory: the served weights (8 GB of bfloat16) stand beside this, so
attention runs a block of heads at a time (a scan), an expert is
widened as it is applied, and a dense FFN's matrices go through the
same scan as column blocks of an expert's width.

What decides `correct` is `compare`: the reference FOLLOWS the system's
experts and the system's SELECTION, judges the index scores on the
system's own inputs (`index_rel_err`), the selection on the system's
own scores (`selection_gap`), the router on its own input
(`router_rel_err`), the cache ROWS of both kinds against its own
(`latent_rms_err`: the table's rows of the full layers; `ring_rms_err`:
what the rings of the sliding layers hold after the walk, ring row r
being position c - (c - r) mod rows at the last cursor c), and the
window's edge by how far the logits past the window have moved towards
the model with one key more and one key fewer (`window_edge_share`,
`window_short_share`).  `below` is the reading one precision down (all
bfloat16); `faults` are the readings a wrong step has to give.
`served` judges what a SERVER delivered, of which only tokens are
known; its faults `ring_wrong_document` and `ring_shifted_block` are a
prefix hit that restored the wrong rows into the rings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
FAULTS = ("window_minus_1", "window_plus_1", "sliding_at_full_theta",
          "full_at_sliding_theta", "sliding_rank_full",
          "sliding_scale_full", "no_q_rescale", "no_kv_rescale",
          "rescale_swapped", "no_gate_full", "no_gate_sliding",
          "gate_elementwise", "sliding_selects", "full_shared",
          "dense_attention", "key_unnormed", "no_index_rope",
          "no_head_weights", "no_relu", "bias_in_weight",
          "not_renormalised", "dense_as_sparse", "ring_wrong_document",
          "ring_shifted_block")
# the two that are a restore's, and show only past a restored prefix
RING_FAULTS = ("ring_wrong_document", "ring_shifted_block")
# heads a step of the attention's (the indexer's) scan computes
HEADS_BLOCK = 4
# positions a cache block holds: what a restored ring is shifted by
BLOCK = 16


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


def geometry(config: dict, kind: str) -> dict:
    """The latent attention's sizes on a layer of kind `kind`, from the
    configuration's own keys (the `swa_*` ones on a sliding layer)."""
    p = "swa_" if kind == SLIDING else ""
    return {"n_heads": int(config[p + "num_attention_heads"]),
            "q_rank": int(config[p + "q_lora_rank"]),
            "kv_rank": int(config[p + "kv_lora_rank"]),
            "d_nope": int(config[p + "qk_nope_head_dim"]),
            "d_rope": int(config[p + "qk_rope_head_dim"]),
            "d_v": int(config[p + "v_head_dim"]),
            "theta": float(config[p + "rope_theta"])}


def inv_freq(geo: dict) -> np.ndarray:
    """The rotation's per-pair frequencies [d_rope / 2]."""
    d = geo["d_rope"]
    return geo["theta"] ** -(np.arange(0, d, 2, dtype=np.float64) / d)


def _experts(m, gate, up, down, weight, dtype):
    """sum over e of weight[:, e] * SwiGLU_e(m): a scan over the
    experts [E, ...], each widened to `dtype` as it is applied."""
    def one(acc, e):
        g, u, d, w = e
        act = jax.nn.silu(m @ g.astype(dtype)) * (m @ u.astype(dtype))
        return acc + (act @ d.astype(dtype)) * w[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (gate, up, down, weight.T.astype(dtype)))[0]


@functools.partial(jax.jit, static_argnames=("eps", "factor", "dtype"))
def _latents(x, p, *, eps, factor, dtype):
    """-> (z = N(x), c_q = N(z W_qa) times `factor`): what attention and
    the indexer both read."""
    z = _rms(x, p["attn_norm"].astype(dtype), eps)
    c_q = _rms(z @ p["q_a"].astype(dtype), p["q_a_norm"].astype(dtype), eps)
    return z, c_q * jnp.asarray(factor, dtype)


def index_scores(h, c_q, p, freq, positions, *, n_heads, d_rope,
                 relu=True, weights=True, normed=True, rotated=True,
                 dtype=F32):
    """The lightning indexer's I [S queries, S keys] from the block's
    normed input h [S, d] and the normed (rescaled) query latent c_q
    [S, q_lora_rank], every pair computed (the caller masks what a
    position may not see).  `relu`, `weights`, `normed`, `rotated`
    False: the faults `no_relu`, `no_head_weights`, `key_unnormed`,
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
    "dtype"))
def _indexer(h, c_q, p, freq, *, n_heads, d_rope, top_k, relu=True,
             weights=True, normed=True, rotated=True, dtype=F32):
    """-> (I [S, S] float32, minus infinity where s > t; S(t) as a mask
    [S, S])."""
    s = h.shape[0]
    scores = index_scores(h, c_q, p, freq, jnp.arange(s), n_heads=n_heads,
                          d_rope=d_rope, relu=relu, weights=weights,
                          normed=normed, rotated=rotated,
                          dtype=dtype).astype(F32)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return (jnp.where(causal, scores, -jnp.inf),
            top_rows(scores, causal, top_k) & causal)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_nope", "d_rope", "d_v", "eps", "factor", "scale_dim",
    "rank", "gate", "dtype"))
def _attention(x, z, c_q, sees, p, freq, restored, upto, *, n_heads,
               d_nope, d_rope, d_v, eps, factor, scale_dim, rank=0,
               gate="head", dtype=F32):
    """x + MLA(z) over the positions `sees` [S, S] shows each query,
    expanded: keys and values widened from the latent, `HEADS_BLOCK`
    heads at a time.  -> (x, this layer's cache rows [S, latent + rope]
    float32: the normed latent times `factor`, then the rotated key
    part).  `restored` [S, latent + rope] and `upto`: the rows of the
    positions under `upto` are THOSE, whatever this pass computes (a
    prefix hit's restored ring; `upto` 0: none).  `scale_dim`: scores
    times its -0.5th power.  `rank` > 0: the fault `sliding_rank_full`
    (the latent's first `rank` columns alone).  `gate`: "head" (a
    sigmoid scalar a head), "none", "elementwise" (column j of the
    heads' values takes head j mod H's scalar)."""
    s = x.shape[0]
    pos = jnp.arange(s)
    d_lat = p["kv_a_norm"].shape[0]
    ckv = z @ p["kv_a"].astype(dtype)
    use = rank or d_lat
    c_kv = _rms(ckv[:, :use], p["kv_a_norm"][:use].astype(dtype), eps)
    c_kv = jnp.pad(c_kv * jnp.asarray(factor, dtype),
                   ((0, 0), (0, d_lat - use)))
    k_pe = _rope(ckv[:, d_lat:], freq, pos)
    rows = jnp.concatenate([c_kv, k_pe], -1)
    rows = jnp.where((pos < upto)[:, None], restored.astype(dtype), rows)
    c_kv, k_pe = rows[:, :d_lat], rows[:, d_lat:]
    hb = math.gcd(n_heads, HEADS_BLOCK)
    nb = n_heads // hb
    dq = d_nope + d_rope
    q_b = p["q_b"].reshape(-1, nb, hb * dq).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(d_lat, nb, hb * (d_nope + d_v)).transpose(
        1, 0, 2)
    o = p["o"].reshape(nb, hb * d_v, -1)
    scale = jnp.asarray(scale_dim ** -0.5, dtype)
    g = jax.nn.sigmoid(z @ p["gate"].astype(dtype))             # [S, H]
    if gate == "elementwise":
        gates = jnp.tile(g, (1, d_v)).reshape(s, nb, hb, d_v)
    else:
        gates = jnp.broadcast_to(g.reshape(s, nb, hb, 1), (s, nb, hb, d_v))

    def heads(acc, w):
        w_q, w_kv, w_o, gt = w
        q = (c_q @ w_q.astype(dtype)).reshape(s, hb, dq)
        q = jnp.concatenate(
            [q[..., :d_nope], _rope(q[..., d_nope:], freq, pos)], -1)
        kv = (c_kv @ w_kv.astype(dtype)).reshape(s, hb, d_nope + d_v)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, None, :], (s, hb, d_rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where(sees[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv[..., d_nope:])
        if gate != "none":
            ctx = ctx * gt.astype(dtype)
        return acc + ctx.reshape(s, hb * d_v) @ w_o.astype(dtype), None

    out = jax.lax.scan(heads, jnp.zeros_like(x),
                       (q_b, kv_b, o, gates.transpose(1, 0, 2, 3)))[0]
    return x + out, rows.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _post_norm(x, scale, *, eps, dtype):
    return _rms(x, scale.astype(dtype), eps)


@functools.partial(jax.jit, static_argnames=("width", "whole", "dtype"))
def _dense(u, p, *, width, whole=True, dtype=F32):
    """SwiGLU(u), its columns in blocks of `width` (the sum over a
    block is the sum over its columns: the same mathematics, a matrix's
    float32 never whole).  `whole` False: the fault `dense_as_sparse`,
    the first `width` columns alone (a sparse layer's shared expert)."""
    d, f = p["gate"].shape
    width = width if f % width == 0 else f
    gate, up = (w.reshape(d, f // width, width).transpose(1, 0, 2)
                for w in (p["gate"], p["up"]))
    down = p["down"].reshape(f // width, width, d)
    n = f // width if whole else 1
    return _experts(u, gate[:n], up[:n], down[:n],
                    jnp.ones((u.shape[0], n), dtype), dtype)


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
             "kv_b": "kv_b_proj.w_0", "o": "o_proj.w_0",
             "gate": "attn_gate.w_0"}
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


def layer_kinds(config: dict):
    return list(config["layer_types"][:int(config["num_hidden_layers"])])


def ring_rows_of(config: dict, block: int = BLOCK) -> int:
    """Rows of a lane's ring: the blocks that hold a window."""
    return -(-int(config["sliding_window_size"]) // block) * block


def ring_content(rows, cursor: int, ring_rows: int):
    """What a ring of `ring_rows` rows holds at cursor `cursor` of the
    per-position rows [.., S, width]: ring row r is position cursor -
    (cursor - r) mod ring_rows; (the content, which ring rows hold a
    position at all)."""
    r = np.arange(ring_rows)
    held = cursor - (cursor - r) % ring_rows
    return np.asarray(rows)[..., np.maximum(held, 0), :], held >= 0


def forward(states: dict, config: dict, ids, follow=None, select=None,
            dtype=F32, fault=None, logits_from: int = 0, restored=0,
            block: int = BLOCK):
    """[S] token ids -> ([S - logits_from, vocab] float32 next-token
    logits of positions `logits_from` onward, what the model chose and
    kept: the routing of every sparse layer stacked ("inputs" [M, S, D],
    "weights" and "experts" [M, S, k]), of every full layer
    ("index_inputs" [F, S, D], "index_latents" [F, S, q_lora_rank],
    "index_scores" [F, S, S] float32 and "selected" [F, S, S] bool: the
    rows ATTENDED OVER), the full layers' cache rows "latent_rows" [F, S,
    latent + rope] and the sliding layers' "sliding_rows" [W, S, latent +
    rope]).  `follow` [M, S, k]: the experts each sparse layer applies
    in place of its own choice, where not negative; `select` [F, S, S]
    bool: the selection each full layer attends over in place of its
    own.  `fault` computes a DIFFERENT model, one of `FAULTS` (module
    docstring); the two `RING_FAULTS` act on the sliding layers' rows of
    the positions under `restored` (a block boundary: what a prefix hit
    restored into the rings): "ring_wrong_document": they are the rows
    of ANOTHER prefix (this one's tokens in reverse order);
    "ring_shifted_block": position p's row is position p - `block`'s."""
    assert fault is None or fault in FAULTS, fault
    n = int(config["num_hidden_layers"])
    s = len(ids)
    wrong = None
    if fault in RING_FAULTS and restored:
        other = np.asarray(ids).copy()
        if fault == "ring_wrong_document":
            other[:restored] = other[:restored][::-1]
        wrong = np.asarray(forward(states, config, other, follow=follow,
                                   select=select,
                                   dtype=dtype)[1]["sliding_rows"])
        if fault == "ring_shifted_block":
            wrong = np.concatenate(
                [np.zeros_like(wrong[:, :block]), wrong[:, :-block]], 1)
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    d = int(config["hidden_size"])
    geo = {kind: geometry(config, kind) for kind in (FULL, SLIDING)}
    freq = {kind: jnp.asarray(inv_freq(geo[kind]), F32) for kind in geo}
    if fault == "sliding_at_full_theta":
        freq[SLIDING] = freq[FULL]
    if fault == "full_at_sliding_theta":
        freq[FULL] = freq[SLIDING]
    rescale = bool(config["apply_mla_qkv_lora_rescale"])

    def factors(kind):
        """(on the normed query latent, on the normed latent)."""
        q = math.sqrt(d / geo[kind]["q_rank"]) if rescale else 1.0
        kv = math.sqrt(d / geo[kind]["kv_rank"]) if rescale else 1.0
        if fault == "rescale_swapped":
            q, kv = kv, q
        return (1.0 if fault == "no_q_rescale" else q,
                1.0 if fault == "no_kv_rescale" else kv)

    index = dict(n_heads=int(config["index_n_heads"]),
                 d_rope=geo[FULL]["d_rope"],
                 top_k=int(config["index_topk"]),
                 relu=fault != "no_relu",
                 weights=fault != "no_head_weights",
                 normed=fault != "key_unnormed",
                 rotated=fault != "no_index_rope", dtype=dtype)
    moe = dict(top_k=top_k, first=int(config["first_local_expert"]),
               renorm=(bool(config["norm_topk_prob"])
                       and fault != "not_renormalised"),
               bias_in_weight=fault == "bias_in_weight", dtype=dtype)
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    width = int(config["moe_intermediate_size"])
    own = np.full((s, top_k), -1, np.int32)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    causal = j <= i
    window = int(config["sliding_window_size"]) + {
        "window_minus_1": -1, "window_plus_1": 1}.get(fault, 0)
    band = causal & (i - j < window)
    kinds = layer_kinds(config)
    dense_layers = int(config["first_k_dense_replace"])
    routed, picked, kept = [], [], {FULL: [], SLIDING: []}
    last = causal                      # the newest full layer's selection
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l, kind in enumerate(kinds):
            def named(keys, prefix=f"layer_{l}."):
                return {k: states[prefix + n_] for k, n_ in keys.items()}

            g = geo[kind]
            f_q, f_kv = factors(kind)
            z, c_q = _latents(x, named(ATTN_KEYS), eps=eps, factor=f_q,
                              dtype=dtype)
            if kind == FULL:
                scores, chosen = _indexer(z, c_q, named(INDEX_KEYS),
                                          freq[FULL], **index)
                sees = (chosen if select is None
                        else jnp.asarray(select[len(picked)]))
                if fault == "dense_attention":
                    sees = causal
                if fault == "full_shared" and picked:
                    sees = last
                last = sees
                picked.append({"index_inputs": z.astype(F32),
                               "index_latents": c_q.astype(F32),
                               "index_scores": scores, "selected": sees,
                               "own": chosen})
            else:
                sees = band & last if fault == "sliding_selects" else band
            gate = ("none" if fault == {FULL: "no_gate_full", SLIDING:
                                        "no_gate_sliding"}[kind]
                    else "elementwise" if fault == "gate_elementwise"
                    else "head")
            over = (jnp.asarray(wrong[len(kept[SLIDING])])
                    if wrong is not None and kind == SLIDING else
                    jnp.zeros((s, g["kv_rank"] + g["d_rope"]), F32))
            x, rows = _attention(
                x, z, c_q, sees, named(ATTN_KEYS), freq[kind], over,
                restored if wrong is not None and kind == SLIDING else 0,
                n_heads=g["n_heads"], d_nope=g["d_nope"],
                d_rope=g["d_rope"], d_v=g["d_v"], eps=eps, factor=f_kv,
                scale_dim=(geo[FULL] if fault == "sliding_scale_full"
                           else g)["d_nope"] + g["d_rope"],
                rank=(geo[FULL]["kv_rank"] if kind == SLIDING
                      and fault == "sliding_rank_full" else 0),
                gate=gate, dtype=dtype)
            kept[kind].append(rows)
            u = _post_norm(x, states[f"layer_{l}.ffn_norm.scale_0"],
                           eps=eps, dtype=dtype)
            if l < dense_layers:
                x = x + _dense(u, named(DENSE_KEYS), width=width,
                               whole=fault != "dense_as_sparse",
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
    chose["latent_rows"] = jnp.stack(kept[FULL])
    chose["sliding_rows"] = jnp.stack(kept[SLIDING])
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


def _rms_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def compare(states: dict, config: dict, ids, got, routing,
            late_from=None) -> dict:
    """A system's [S, vocab] logits and what it chose and kept (what
    `forward` returns beside the logits, as the system computed it; its
    index arrays may be cut to the first S rows of a longer table;
    "latent_rows" [F, S, width >= latent + rope] the table's rows of the
    sequence, and in place of "sliding_rows" the rings themselves,
    "ring_rows" [W, ring rows, width >= latent + rope], as they stand
    after the last position) against this reference on the same weights
    and tokens:

      logits_rms_err  root mean square of the logits' difference over
                      theirs, the reference FOLLOWING the system's
                      experts and selection
      logits_p99_err  a position's largest |difference| at the 99th
                      percentile over the positions, over the largest
                      |logit|
      logits_rel_err  the largest of them (reported)
      late_rms_err    `logits_rms_err` over the positions from
                      `late_from` (default: `index_topk`, else the last
                      half) alone: where a selection leaves rows out
      own_rms_err     against the reference on its OWN experts and
                      selection (reported: near-ties count)
      index_rel_err, selection_gap, selection_agree, router_rel_err,
      routing_agree   as `glm_dsa.py` has them
      latent_rms_err  the table's rows of the full layers against the
                      reference's [c | k_r], the larger layer's
      ring_rms_err    the rings' content against the reference's rows
                      of the positions a ring holds at the last cursor,
                      the larger layer's: a ring that wrapped wrongly,
                      a row at the wrong width or under the wrong theta
      window_edge_share, window_short_share  over the positions at and
                      past the window: how far the system's logits have
                      gone from this reference's towards those of the
                      model whose window is ONE key longer (shorter), as
                      a share of that step: 0 for the right window, 1
                      for a window of one key more (fewer)
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
    kinds = layer_kinds(config)
    sparse = list(range(int(config["first_k_dense_replace"]), len(kinds)))
    full = [l for l, kind in enumerate(kinds) if kind == FULL]
    causal = np.tril(np.ones((s, s), bool))
    geo = geometry(config, FULL)
    freq = jnp.asarray(inv_freq(geo), F32)
    index_err = []
    with jax.default_matmul_precision("highest"):
        scores = np.stack([np.asarray(_sigmoid(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(sparse)])
        for i, l in enumerate(full):
            ref = np.asarray(_index_scores(
                jnp.asarray(routing["index_inputs"][i], F32),
                jnp.asarray(routing["index_latents"][i], F32),
                {k: states[f"layer_{l}.{n}"]
                 for k, n in INDEX_KEYS.items()}, freq, jnp.arange(s),
                n_heads=int(config["index_n_heads"]),
                d_rope=geo["d_rope"]), np.float32)
            index_err.append(
                np.sqrt(np.mean((sys_scores[i][causal] - ref[causal]) ** 2)
                        / np.mean(ref[causal] ** 2)))
    bias = np.stack([np.asarray(
        jnp.asarray(states[f"layer_{l}.router_bias.b_0"], F32))
        for l in sparse])[:, None, :]
    # the cache rows: the table's by position, a ring's by what it holds
    ref_rows = np.asarray(own["latent_rows"], np.float32)
    latent_err = max(
        _rms_err(np.asarray(rows, np.float32)[:s, :ref.shape[-1]], ref)
        for rows, ref in zip(routing["latent_rows"], ref_rows))
    rings = np.asarray(routing["ring_rows"], np.float32)
    held, written = ring_content(own["sliding_rows"], s - 1, rings.shape[1])
    ring_err = max(
        _rms_err(ring[written][:, :ref.shape[-1]], ref[written])
        for ring, ref in zip(rings, held))
    late = int(late_from if late_from is not None
               else config["index_topk"] if s > config["index_topk"]
               else s // 2)
    worst = np.abs(got - want).max(-1)      # of each position
    top = np.max(np.abs(want))
    out = {"logits_rel_err": float(worst.max() / top),
           "logits_p99_err": float(np.percentile(worst, 99) / top),
           "logits_rms_err": _rms_err(got, want),
           "late_rms_err": _rms_err(got[late:], want[late:]),
           "own_rms_err": _rms_err(got, mine),
           "index_rel_err": float(max(index_err)),
           "selection_gap": selection_gap(config, sys_scores, sel),
           "selection_agree": float((sel & own_s).sum() / sel.sum()),
           "rows_dropped_share": float(
               1.0 - sel[:, late:].sum() / np.broadcast_to(
                   causal, sel.shape)[:, late:].sum()),
           "router_rel_err": router_rel_err(config, scores, bias, exp,
                                            routing["weights"]),
           "routing_agree": float(agree),
           "latent_rms_err": float(latent_err),
           "ring_rms_err": float(ring_err),
           "ring_rows_written": int(written.sum()),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "late_from": late,
           "finite": bool(np.isfinite(got).all()
                          and np.isfinite(rings[:, written]).all())}
    w = int(config["sliding_window_size"])
    if s > w:
        for name, fault in (("window_edge_share", "window_plus_1"),
                            ("window_short_share", "window_minus_1")):
            other = np.asarray(forward(states, config, ids, follow=exp,
                                       select=sel, fault=fault)[0],
                               np.float32)
            step = (other - want)[w - 1:].astype(np.float64)
            out[name] = float(np.sum((got - want)[w - 1:] * step)
                              / np.sum(step * step))
    return out


def as_system(config: dict, ids, out, block: int = BLOCK):
    """What `forward` returned, as `compare` takes a system's: the
    sliding layers' rows as the rings that would hold them after the
    last position."""
    logits_, chose = out
    chose = dict(chose)
    chose["ring_rows"] = ring_content(
        chose.pop("sliding_rows"), len(ids) - 1,
        ring_rows_of(config, block))[0]
    return logits_, chose


def below(states: dict, config: dict, ids, block: int = BLOCK) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids, *as_system(
        config, ids, forward(states, config, ids, dtype=jnp.bfloat16),
        block))


def faults(states: dict, config: dict, ids, block: int = BLOCK,
           which=FAULTS) -> dict:
    """`compare`'s numbers for the float32 models of `FAULTS`, as if each
    were the system: the limits have to refuse every one.  The two
    `RING_FAULTS` with the first half of the sequence (whole blocks)
    restored."""
    restored = len(ids) // 2 // block * block
    return {fault: compare(states, config, ids, *as_system(
        config, ids, forward(states, config, ids, fault=fault,
                             restored=restored, block=block), block))
            for fault in which}


def served(states: dict, config: dict, requests, dtype=F32,
           fault=None, pad_to=None, block: int = BLOCK) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] (its OWN
    experts and selection: the server's are not known).  Under a
    `RING_FAULTS` fault the rings' rows of the positions under the last
    block boundary before the prompt's last position are the wrong
    restore's (what a prefix hit brought).

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
            logits_from=start - 1, restored=(start - 1) // block * block,
            block=block)[0], np.float32)[:n - start + 1]
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
