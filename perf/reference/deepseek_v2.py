"""Plain reference of the DeepSeek-V2 decoder (arXiv:2405.04434,
`deepseek-ai/DeepSeek-V2` config.json, `model_type: deepseek_v2`) in
the EXPANDED form: float32 `jax.numpy`, one full forward over one token
sequence under a causal mask, keys and values widened from the latent
for every head, no cache, no absorbed products, no sort, no batching,
every matrix multiplication at `highest` precision.  It knows nothing
of paddle_tpu: it takes a dict of named arrays under the names the
served decoder's `state_shapes` gives (`layer_<l>.q_a_proj.w_0`, ...;
weights are stored [in, out], the experts [expert, in, out]) and the
configuration's OWN keys (`rope_scaling`, `rope_theta`, `n_group`, ...),
never the keys the configuration file derives for the served
description (`softmax_scale`, `rope_parameters`): the scale and the
frequencies are derived twice.

The layer l over tokens x [S, d] (d 5120, H 128 heads, eps 1e-6):

  h = RMSNorm(x; g1)
  c_q = RMSNorm(h W_dq; g_q) [S, q_lora_rank];  q = c_q W_uq [S, H x
      (qk_nope_head_dim + qk_rope_head_dim)]: per head q_i = [q_nope_i |
      q_pe_i], q_pe_i = RoPE(q_pe_i, t).
  h W_dkv [S, kv_lora_rank + qk_rope_head_dim] = [c | k_pe];
      c_kv = RMSNorm(c; g_kv) over the latent ALONE; k_pe = RoPE(k_pe,
      t): ONE key part for all heads.  (A cache would hold [c_kv |
      k_pe] and nothing else.)
  c_kv W_ukv [S, H x (qk_nope_head_dim + v_head_dim)] = [k_nope_i |
      v_i];  k_i = [k_nope_i | k_pe];  s_ij = scale q_i(t) . k_i(j), j
      <= t, softmax over j, o_i = sum_j p_ij v_i(j);
      x = x + concat_i(o_i) W_o.
  RoPE: rotate-half over the `qk_rope_head_dim` columns as they lie,
      YaRN frequencies from `rope_scaling` (pairs that turn more than
      `beta_fast` times over `original_max_position_embeddings` keep
      theta's frequency, fewer than `beta_slow` take it over `factor`, a
      linear ramp between); the tables' gain m(factor, mscale) /
      m(factor, mscale_all_dim) with m(s, a) = 0.1 a ln s + 1;
      scale = (nope + rope)^-0.5 x m(factor, mscale_all_dim)^2.
  h2 = RMSNorm(x; g2).  Layers under `first_k_dense_replace`: x = x +
      (silu(h2 Wg) * (h2 Wu)) Wd at `intermediate_size`.  The others:
      p = softmax(h2 W_r) over ALL routed experts; they lie in `n_group`
      consecutive groups; a group's score is its largest p; the
      `topk_group` best groups are kept (`group_limited_greedy`), every
      other expert's score set to 0, the `num_experts_per_tok` largest
      of what is left chosen; w = p[chosen] x `routed_scaling_factor`
      (`norm_topk_prob` false: no renormalisation); x = x + sum over
      the chosen e HELD here of w_e SwiGLU_e(h2) (`moe_intermediate_
      size`) + SwiGLU_shared(h2) (`n_shared_experts` x that width,
      weight 1, every token).  The experts held are those whose
      matrices `states` holds, from `first_local_expert`: an assignment
      to an absent expert adds nothing and its weight is NOT shared out.
      Ties, of groups and of experts, go to the lower index.
  logits = RMSNorm(x; gf) W_head, over the rows of the vocabulary the
      configuration holds.

ASSUMED (each one field of the served description and one fault
below):
  * the released code stores the rotary columns interleaved and
    un-interleaves them before a rotate-half; with seeded weights that
    is a relabelling of columns of W_uq and W_dkv common to q and k, so
    rotate-half is applied to the columns as they lie (a checkpoint
    loader would permute them);
  * pre-norm placement;
  * `k_pe` is taken before any norm (the norm is over the latent
    alone);
  * `aux_loss_alpha`, `seq_aux` and the device- and communication-
    balance losses are training's, and absent.

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_hidden_layers`, the experts held and
the vocabulary are whatever the configuration and the arrays hold.

Memory: the served weights (10 GB of bfloat16) stand beside this, so
attention runs a block of heads at a time (a scan: scores of 16 heads
over 1152 positions are 85 MB), an expert is widened as it is applied
(a scan over the held experts, each applied densely to every token and
masked by the weights), and the dense layer's and the shared expert's
matrices go through the same scan as column blocks of one expert's
width.

What decides `correct` is `compare`: the reference FOLLOWS the system's
choice of experts (a near-tie of two experts or two groups is a swap,
not an error) and judges the choice on the router's own input
(`router_rel_err`).  `below` is the reading one precision down (all
bfloat16); `faults` are six readings a wrong step has to give.
`served` judges what a SERVER delivered, of which only tokens are
known.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FAULTS = ("no_mscale", "norm_576", "no_group_limit", "renormalised",
          "plain_rope", "k_pe_unrotated")
# heads a step of the attention's scan widens keys and values for
HEADS_BLOCK = 16


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def mscale(factor: float, a: float) -> float:
    """YaRN's attention gain m(s, a) = 0.1 a ln s + 1 (1 at s <= 1)."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(config: dict, plain: bool = False) -> np.ndarray:
    """The rotation's per-pair frequencies [qk_rope_head_dim / 2],
    float64: YaRN's from `rope_scaling`, or (`plain`, a fault) RoPE's
    at `rope_theta`."""
    d, theta = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    extra = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    sc = config.get("rope_scaling")
    if plain or not sc:
        return extra

    def pair_of(turns):
        return d * math.log(sc["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_of(sc["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return extra / float(sc["factor"]) * ramp + extra * (1.0 - ramp)


def softmax_scale(config: dict, fault=None) -> float:
    """(nope + rope)^-0.5 times m(factor, mscale_all_dim)^2."""
    d = int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
    sc = config.get("rope_scaling")
    if fault == "no_mscale" or not sc:
        return d ** -0.5
    return d ** -0.5 * mscale(sc["factor"], sc["mscale_all_dim"]) ** 2


def table_gain(config: dict) -> float:
    sc = config.get("rope_scaling")
    if not sc:
        return 1.0
    return (mscale(sc["factor"], sc["mscale"])
            / mscale(sc["factor"], sc["mscale_all_dim"]))


def _rope(x, freq, gain):
    """x [S, ..., Dr] at positions 0..S-1, rotate-half."""
    s, dr = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (s,) + (1,) * (x.ndim - 2) + (dr,))
    turned = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], -1)
    return (x * (jnp.cos(ang) * gain).astype(x.dtype)
            + turned * (jnp.sin(ang) * gain).astype(x.dtype))


def _experts(m, gate, up, down, weight, dtype):
    """sum over e of weight[:, e] * SwiGLU_e(m): a scan over the
    experts [E, ...], each widened to `dtype` as it is applied."""
    def one(acc, e):
        g, u, d, w = e
        act = jax.nn.silu(m @ g.astype(dtype)) * (m @ u.astype(dtype))
        return acc + (act @ d.astype(dtype)) * w[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        (gate, up, down, weight.T.astype(dtype)))[0]


def _dense(m, gate, up, down, width, dtype):
    """SwiGLU(m) at any width, its columns in blocks of `width` (the
    sum over a block is the sum over its columns: the same mathematics,
    a matrix's float32 never whole)."""
    d, f = gate.shape
    width = width if f % width == 0 else f
    gate, up = (w.reshape(d, f // width, width).transpose(1, 0, 2)
                for w in (gate, up))
    down = down.reshape(f // width, width, d)
    return _experts(m, gate, up, down,
                    jnp.ones((m.shape[0], f // width), dtype), dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_nope", "d_rope", "d_v", "eps", "norm_all", "rotate_k",
    "dtype"))
def _attention(x, p, freq, gain, scale, *, n_heads, d_nope, d_rope, d_v,
               eps, norm_all=False, rotate_k=True, dtype=F32):
    """x + attention of RMSNorm(x), expanded: keys and values widened
    from the latent, `HEADS_BLOCK` heads at a time.  `norm_all` and
    `rotate_k=False` compute the faults `norm_576` (the key/value norm
    taken over latent AND key part together) and `k_pe_unrotated`."""
    s = x.shape[0]
    d_lat = p["kv_a_norm"].shape[0]
    h = _rms(x, p["attn_norm"].astype(dtype), eps)
    c_q = _rms(h @ p["q_a"].astype(dtype), p["q_a_norm"].astype(dtype), eps)
    ckv = h @ p["kv_a"].astype(dtype)
    c, k_pe = ckv[:, :d_lat], ckv[:, d_lat:]
    if norm_all:
        ms = (ckv * ckv).mean(-1, keepdims=True)
        ckv = ckv / jnp.sqrt(ms + jnp.asarray(eps, dtype))
        c_kv, k_pe = ckv[:, :d_lat] * p["kv_a_norm"].astype(dtype), \
            ckv[:, d_lat:]
    else:
        c_kv = _rms(c, p["kv_a_norm"].astype(dtype), eps)
    if rotate_k:
        k_pe = _rope(k_pe, freq, gain)
    hb = math.gcd(n_heads, HEADS_BLOCK)
    nb = n_heads // hb
    dq = d_nope + d_rope
    q_b = p["q_b"].reshape(-1, nb, hb * dq).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(d_lat, nb, hb * (d_nope + d_v)).transpose(
        1, 0, 2)
    o = p["o"].reshape(nb, hb * d_v, -1)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]

    def heads(acc, w):
        w_q, w_kv, w_o = (a.astype(dtype) for a in w)
        q = (c_q @ w_q).reshape(s, hb, dq)
        q = jnp.concatenate(
            [q[..., :d_nope], _rope(q[..., d_nope:], freq, gain)], -1)
        kv = (c_kv @ w_kv).reshape(s, hb, d_nope + d_v)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, None, :], (s, hb, d_rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale.astype(dtype)
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv[..., d_nope:])
        return acc + ctx.reshape(s, hb * d_v) @ w_o, None

    return x + jax.lax.scan(heads, jnp.zeros_like(x), (q_b, kv_b, o))[0]


@functools.partial(jax.jit, static_argnames=("width", "eps", "dtype"))
def _dense_ffn(x, p, *, width, eps, dtype):
    m = _rms(x, p["norm"].astype(dtype), eps)
    return x + _dense(m, p["gate"], p["up"], p["down"], width, dtype)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "first", "eps", "renorm", "dtype"))
def _ffn(x, p, follow, scaling, *, top_k, n_group, topk_group, first, eps,
         renorm=False, dtype=F32):
    """-> (x + the expert layer of RMSNorm(x), its routing: the
    router's input, the top-k weights and experts of its own choice).
    `p`: "norm", "router" [d, E routed], "gate", "up", "down" [held,
    ...] (the experts `first` onward) and "shared_gate", "shared_up",
    "shared_down".  `follow` [S, k]: the experts to apply instead of
    its own choice, each weighed by the probability computed here; a
    position whose row is negative takes its own.  `n_group` 1 is the
    fault `no_group_limit` (plain top-k of all), `renorm` the fault
    `renormalised` (the chosen probabilities over their sum in place of
    times `scaling`)."""
    s = x.shape[0]
    m = _rms(x, p["norm"].astype(dtype), eps)
    probs = jax.nn.softmax(m @ p["router"].astype(dtype), -1)   # [S, E]
    grouped = probs.reshape(s, n_group, -1)
    _, kept = jax.lax.top_k(grouped.max(-1), topk_group)
    keep = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], kept].set(True)
    _, own_e = jax.lax.top_k(
        jnp.where(keep[:, :, None], grouped, 0.0).reshape(s, -1), top_k)

    def weights_of(experts):
        w = jnp.take_along_axis(probs, experts, -1)
        if renorm:
            return w / w.sum(-1, keepdims=True)
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(s)[:, None], use_e].set(weights_of(use_e))
    held, width = p["gate"].shape[0], p["gate"].shape[2]
    y = _experts(m, p["gate"], p["up"], p["down"],
                 weight[:, first:first + held], dtype)
    shared = _dense(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                    width, dtype)
    routing = {"inputs": m.astype(F32),
               "weights": weights_of(own_e).astype(F32), "experts": own_e}
    return x + y + shared, routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


ATTN_KEYS = {"attn_norm": "attn_norm.scale_0", "q_a": "q_a_proj.w_0",
             "q_a_norm": "q_a_norm.scale_0", "q_b": "q_b_proj.w_0",
             "kv_a": "kv_a_proj.w_0", "kv_a_norm": "kv_a_norm.scale_0",
             "kv_b": "kv_b_proj.w_0", "o": "o_proj.w_0"}
DENSE_KEYS = {"norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
              "up": "ffn_up.w_0", "down": "ffn_down.w_0"}
SPARSE_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
               "gate": "experts_gate.w_0", "up": "experts_up.w_0",
               "down": "experts_down.w_0",
               "shared_gate": "shared_gate.w_0",
               "shared_up": "shared_up.w_0",
               "shared_down": "shared_down.w_0"}


def sparse_layers(config: dict) -> list:
    """The layers with experts, in order: what a system's routing is
    stacked over."""
    return list(range(int(config["first_k_dense_replace"]),
                      int(config["num_hidden_layers"])))


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every SPARSE layer stacked: "inputs" [Ls, S, D],
    "weights" and "experts" [Ls, S, k]), from the named arrays and the
    configuration's own keys.  `follow` [Ls, S, k]: the experts each
    sparse layer applies in place of its own choice, where they are
    not negative.  `fault` computes a DIFFERENT model, one of `FAULTS`:
    "no_mscale" leaves mscale^2 out of the softmax scale, "norm_576"
    takes the key/value norm over latent and key part together,
    "no_group_limit" chooses the k of all experts, "renormalised"
    weighs the chosen by their probabilities over their sum,
    "plain_rope" turns by theta's own frequencies, "k_pe_unrotated"
    leaves the shared key part as the projection made it."""
    assert fault is None or fault in FAULTS, fault
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    attn = dict(n_heads=int(config["num_attention_heads"]),
                d_nope=int(config["qk_nope_head_dim"]),
                d_rope=int(config["qk_rope_head_dim"]),
                d_v=int(config["v_head_dim"]), eps=eps,
                norm_all=fault == "norm_576",
                rotate_k=fault != "k_pe_unrotated", dtype=dtype)
    freq = jnp.asarray(inv_freq(config, plain=fault == "plain_rope"), F32)
    gain = jnp.asarray(table_gain(config), F32)
    scale = jnp.asarray(softmax_scale(config, fault), F32)
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    groups = ((1, 1) if fault == "no_group_limit"
              else (int(config["n_group"]), int(config["topk_group"])))
    width = int(config["moe_intermediate_size"])
    own = np.full((len(ids), top_k), -1, np.int32)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"]
                        for k, n in keys.items()}

            x = _attention(x, named(ATTN_KEYS), freq, gain, scale, **attn)
            if l < int(config["first_k_dense_replace"]):
                x = _dense_ffn(x, named(DENSE_KEYS), eps=eps, dtype=dtype,
                               width=width)
                continue
            x, r = _ffn(
                x, named(SPARSE_KEYS),
                jnp.asarray(own if follow is None else follow[len(routed)],
                            jnp.int32), scaling, top_k=top_k,
                n_group=groups[0], topk_group=groups[1],
                first=int(config["first_local_expert"]), eps=eps,
                renorm=bool(config["norm_topk_prob"])
                or fault == "renormalised", dtype=dtype)
            routed.append(r)
        out = _head(x, states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    return out, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _probs(m, w):
    return jax.nn.softmax(m @ w.astype(F32), -1)


def router_rel_err(config: dict, probs, experts, weights) -> float:
    """How far a system's routing (its `experts` and `weights` [..., k])
    lies from the rule, on the float32 probabilities `probs` [..., E]
    of its OWN router inputs, relative: the largest of
      * how far below the `topk_group`-th best group's score the score
        of a chosen expert's group lies (0: every chosen expert is in a
        group that may be kept);
      * how far below an expert it left out of the kept groups its
        least chosen one lies (the kept groups: the system's own, filled
        up by score where it used fewer);
      * how far its weights lie from p[chosen] x `routed_scaling_factor`.
    """
    probs = np.asarray(probs, np.float64)
    n_group, keep_n = int(config["n_group"]), int(config["topk_group"])
    per = probs.shape[-1] // n_group
    group_score = probs.reshape(probs.shape[:-1] + (n_group, per)).max(-1)
    least_kept = np.sort(group_score, -1)[..., -keep_n]
    group_of = experts // per
    off_group = np.maximum(0.0, least_kept[..., None] - np.take_along_axis(
        group_score, group_of, -1)) / least_kept[..., None]
    used = np.zeros_like(group_score)
    np.put_along_axis(used, group_of, 1.0, -1)
    kept = np.argsort(-(group_score + 2.0 * used), -1,
                      kind="stable")[..., :keep_n]
    keep = np.zeros_like(group_score, bool)
    np.put_along_axis(keep, kept, True, -1)
    left_out = np.where(np.repeat(keep, per, -1), probs, 0.0)
    np.put_along_axis(left_out, experts, 0.0, -1)
    chosen = np.take_along_axis(probs, experts, -1)
    least = chosen.min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / least
    if config["norm_topk_prob"]:
        want = chosen / chosen.sum(-1, keepdims=True)
    else:
        want = chosen * float(config["routed_scaling_factor"])
    off = np.abs(np.asarray(weights, np.float64) - want) / want
    return float(max(off_group.max(), gap.max(), off.max()))


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|, the reference following the system's
                      experts: rounding, and every fault but a swap.
                      ONE position of the sequence decides it: the one
                      whose HELD experts carry the most weight (p x 16,
                      not renormalised: 3 to 6 a layer where the median
                      is 1.1), so that several times more of its
                      hidden state is routed experts' output and any
                      rounding reads two to four times its median
                      there, this file's own bfloat16 too.  Over seeds
                      that is a tail no limit can stand under:
                      reported, and bounded where the tail is cut off:
      logits_p99_err  the same, a position's largest difference taken
                      at the 99th percentile over the positions in
                      place of the worst of them: what a fault at every
                      block's edge or from some length on moves, and
                      one ill-conditioned position does not
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed
      late_rms_err    `logits_rms_err` over the last half of the
                      positions alone: where the cache is longest and a
                      wrong frequency has turned farthest
      router_rel_err  `router_rel_err` above on the system's own router
                      inputs: groups, experts and weights
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
        probs = np.stack([np.asarray(_probs(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(sparse_layers(config))])     # [Ls, S, E]

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    worst = np.abs(got - want).max(-1)      # of each position
    return {"logits_rel_err": float(worst.max() / np.max(np.abs(want))),
            "logits_p99_err": float(np.percentile(worst, 99)
                                    / np.max(np.abs(want))),
            "logits_rms_err": rms(got, want),
            "late_rms_err": rms(got[half:], want[half:]),
            "router_rel_err": router_rel_err(config, probs, exp,
                                             routing["weights"]),
            "routing_agree": float(agree),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "finite": bool(np.isfinite(got).all())}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for the six float32 models of `FAULTS`,
    as if each were the system: the limits have to refuse every one."""
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
