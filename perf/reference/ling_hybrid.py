"""Plain reference of the Ling-3.0-flash decoder (inclusionAI,
`Ling-3.0-flash` config.json, `model_type: bailing_hybrid`): float32
`jax.numpy`, one full forward over one token sequence: the gated delta
rule as a LOOP OVER POSITIONS on a matrix state from zeros (no lane, no
tail, no snapshot), its convolutions as causal convolutions over the
sequence, latent attention in its EXPANDED form (keys and values a head
widened from the latent, a masked product); no cache, no sort, no
batching, every matrix multiplication at `highest` precision.  It knows
nothing of paddle_tpu: it takes a dict of named arrays under the names
the served decoder's `state_shapes` gives (`layer_<l>.delta_in_proj.w_0`,
...; weights are stored [in, out], the experts [expert, in, out]) and
the configuration's own keys.

The model, from config.json's keys (`kda_*` are Kimi Delta Attention's,
arXiv:2510.26692; d = `hidden_size`; every norm an RMSNorm with a scale
and `rms_norm_eps`; z the normed input of a mixer).  Each READING of a
key that the key alone does not settle is marked (assumed) and is one
entry of the configuration file's `assumed`:

  x = E[token]
  every layer l:  h = x + mixer_l(RMSNorm(x));  x = h + ffn_l(RMSNorm(h))
      (pre-norm placement: assumed)
  layer l is LATENT attention (M) where (l + 1) % `layer_group_size`
      == 0 and a delta rule (K) elsewhere: the period K K K K K M
      (assumed: the family's convention; where `described_as` says
      "3 KDA : 1 MLA" the config's `layer_group_size` 6 is trusted)
  K: H = `num_attention_heads` heads (`num_kv_heads_for_linear_attn`
      0: as many K/V heads) whose keys and values are `head_dim`
      columns, `short_conv_kernel_size` taps, `linear_silu`:
      q, k, v = silu(conv(z W_q)), silu(conv(z W_k)), silu(conv(z W_v)),
          depthwise, causal, zeros before position 0, no bias
      q = q / |q| * K^-0.5, k = k / |k| a head (x * rsqrt(sum x^2 +
          1e-6)): `use_qk_norm` is read as these unit norms (assumed)
      g = `kda_lower_bound` * sigmoid(exp(A_log[h]) * (z W_f + dt_bias))
          [H, K]: `kda_safe_gate`'s bounded log decay a key CHANNEL
          (assumed: the released gate's form), W_f ONE full matrix
          (`no_kda_lora` true / `use_kda_lora` false)
      beta = sigmoid(z W_b)  [H]  (no `allow_neg_eigval` key: no factor
          2; assumed)
      S' = diag(exp(g_t)) S_(t-1);  S_t = S' + beta_t k_t (v_t - k_t^T S')^T
      o_t = S_t^T q_t                    S [K keys, K values] a head
      mixer = [RMSNorm_head(o_t) * w * sigmoid(z W_g)] W_o, W_g full
          (`group_norm_size` 1: the norm over ONE head's columns, one
          scale of K for all heads)
      no position signal on these layers
  M (DeepSeek-V2's latent attention): `q_lora_rank` null: q = z W_q,
      H heads of `qk_nope_head_dim` + `qk_rope_head_dim`;
      c = RMSNorm(z W_kva[:, :`kv_lora_rank`]), k_r = rope(z W_kva[:,
      `kv_lora_rank`:]) at `rope_theta` over `qk_rope_head_dim` columns
      (rotate-half; `rope_interleave` relabels columns, which seeded
      weights do not know); a head's key [W_uk c | k_r], value W_uv c;
      scores times (nope + rope)^-0.5, causal;
      `gated_attention_proj_granularity_type: head_wise`: a head's value
      times sigmoid(z W_g)_h, W_g [d, H] (assumed); then W_o
  ffn, l < `first_k_dense_replace`: one SwiGLU of `intermediate_size`
  ffn, other layers: s = sigmoid(m W_r) float32; c = s + b
      (`moe_router_enable_expert_bias`); the experts in `n_group`
      consecutive groups, a group's score the SUM OF ITS TWO LARGEST c
      (`topk_method: noaux_tc`; assumed), the `topk_group` best kept (a
      tie to the lower group); the `num_experts_per_tok` largest c among
      the kept groups' experts (a tie to the lower index); weights
      s_i / sum of the chosen s (`norm_topk_prob`) times
      `routed_scaling_factor`; each chosen expert adds
      w_e Wd_e (silu(Wg_e m) * (Wu_e m)); one shared expert of
      `moe_shared_expert_intermediate_size` on every token.  With
      `expert_swiglu_limit_list[l]` = L > 0 an expert's gate input is
      min(., L) and its up input clip(., -L, L), and
      `share_expert_swiglu_limit_list[l]` the same for the shared
      expert (the plain clamp: assumed)
  logits = RMSNorm(x) W_head            (untied)

Departures from the published model: weights are random from the seed;
`num_hidden_layers` is whatever the configuration holds (the cut keeps
the first period); the experts HELD are the arrays' own count from
`first_local_expert` on (the chip's share of an expert-parallel layer:
the router keeps its published columns and what an absent expert would
add is left out, as in `k_exaone.py` and `deepseek_v2.py`); `vocab_size`
is a slice; the multi-token-prediction module is left out
(`num_nextn_predict_layers` 0); q | k | v are ONE stored matrix
[d, 3 H K] and one convolution [taps, 3 H K] (row j multiplies the row
`taps - 1 - j` positions back): storage, not mathematics.

Memory: the served weights (7.4 GB of bfloat16) stand beside this, so an
expert is widened as it is applied (a scan over the held experts), a
dense FFN in blocks of an expert's width, attention `HEADS_BLOCK` heads
at a time, and the logits are computed from position `logits_from` on.

What decides `correct` is `compare`: the reference FOLLOWS the system's
choice of experts and judges the choice on the router's own input
(`router_rel_err`).  `below` is the reading one precision down (all
bfloat16, the state too); `faults` are the readings a wrong step has to
give (`FAULTS`).  `served` judges what a SERVER delivered, of which only
tokens are known.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# the softplus decay (Solar's) in place of the bounded one; a bound of
# -1; beta times 2; a decay a head and not a channel; no unit norm on q
# and k; the output gate left out of K; the head gate left out of M, and
# laid over the columns the wrong way round (column j takes head j mod
# H's scalar: an elementwise gate); RoPE on the K layers; no RoPE on M;
# scores times nope^-0.5; the latent's norm left out; a group's score its
# largest; no group limit; the bias inside the weights; no times 2.5; no
# renormalisation; a dense layer run as a sparse one's shared expert
# (its first `moe_intermediate_size` columns); the tail shifted by one
# position; a lane not reset (state and tail a predecessor left); the M
# layer at position 0 of the period (M K K K K K over the same arrays)
FAULTS = ("softplus_decay", "bound_minus_1", "beta_times_2",
          "decay_per_head", "no_l2norm", "no_output_gate", "no_head_gate",
          "gate_elementwise", "rope_on_delta", "no_rope", "scale_nope",
          "no_latent_norm", "group_max", "no_group_limit",
          "bias_in_weights", "no_scaling", "no_renorm", "dense_as_sparse",
          "tail_shifted", "lane_not_reset", "latent_first")
_DELTA_FAULTS = ("softplus_decay", "bound_minus_1", "beta_times_2",
                 "decay_per_head", "no_l2norm", "no_output_gate",
                 "rope_on_delta", "tail_shifted", "lane_not_reset")
_LATENT_FAULTS = ("no_head_gate", "gate_elementwise", "no_rope",
                  "scale_nope", "no_latent_norm")
_ROUTER_FAULTS = ("group_max", "no_group_limit", "bias_in_weights",
                  "no_scaling", "no_renorm")
# heads a step of the attention's scan widens keys and values for
HEADS_BLOCK = 16


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _unit(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                             + jnp.asarray(1e-6, x.dtype))


def _rope(x, theta):
    """x [S, ..., Dr] at positions 0..S-1, rotate-half."""
    s, dr = x.shape[0], x.shape[-1]
    inv = jnp.asarray(
        [float(theta) ** (-2.0 * i / dr) for i in range(dr // 2)], F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1).reshape(
        (s,) + (1,) * (x.ndim - 2) + (dr,))
    turned = jnp.concatenate([-x[..., dr // 2:], x[..., : dr // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "eps", "floor", "theta", "dtype", "fault"))
def _delta(x, p, *, n_heads, eps, floor, theta, dtype, fault=None):
    """x [S, D] -> (x + the gated delta rule of RMSNorm(x), the state
    after the last position [H, K, K] float32, the last `taps - 1` rows
    of q | k | v before the convolution [taps - 1, 3 H K] float32).
    `fault`: one of `_DELTA_FAULTS`."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    z = _rms(x, p["norm"], eps)
    rows = z @ p["in"]                                   # [S, 3 H K]
    hk = rows.shape[1] // 3
    k_n = hk // n_heads
    f = (z @ p["f"] + p["dt"]).reshape(s, n_heads, k_n)
    a = jnp.exp(p["a_log"])[None, :, None]
    if fault == "softplus_decay":
        g = -a * jax.nn.softplus(f)
    else:
        g = jnp.asarray(-1.0 if fault == "bound_minus_1" else floor,
                        dtype) * jax.nn.sigmoid(a * f)
    if fault == "decay_per_head":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(z @ p["b"])                    # [S, H]
    if fault == "beta_times_2":
        beta = 2.0 * beta
    taps = p["w"].shape[0]
    # a lane not reset: the rows a predecessor (the same sequence) left
    before = rows[s - (taps - 1):] if fault == "lane_not_reset" \
        else jnp.zeros((taps - 1, rows.shape[1]), dtype)
    hist = jnp.concatenate([before, rows])
    conv = jnp.zeros_like(rows)
    for j in range(taps):
        back = taps - 1 - j + (fault == "tail_shifted" and j < taps - 1)
        src = np.arange(s) + (taps - 1) - back
        conv = conv + p["w"][j] * jnp.where(
            jnp.asarray(src >= 0)[:, None], hist[np.maximum(src, 0)], 0.0)
    conv = jax.nn.silu(conv)
    q, k, v = (conv[:, i * hk:(i + 1) * hk].reshape(s, n_heads, k_n)
               for i in range(3))
    if fault == "rope_on_delta":
        q, k = _rope(q, theta), _rope(k, theta)
    if fault != "no_l2norm":
        q, k = _unit(q), _unit(k)
    q = q * jnp.asarray(k_n ** -0.5, dtype)

    def one(state, t):
        q_t, k_t, v_t, a_t, b_t = t
        state = a_t[..., None] * state
        seen = (k_t[..., None] * state).sum(axis=1)             # k^T S'
        state = state + (b_t[:, None] * k_t)[..., None] * (
            v_t - seen)[:, None, :]
        return state, (state * q_t[..., None]).sum(axis=1)      # S^T q

    walk = (q, k, v, jnp.exp(g), beta)
    state = jnp.zeros((n_heads, k_n, k_n), dtype)
    if fault == "lane_not_reset":
        state = jax.lax.scan(one, state, walk)[0]
    state, o = jax.lax.scan(one, state, walk)
    y = _rms(o, p["o_norm"], eps).reshape(s, hk)
    if fault != "no_output_gate":
        y = y * jax.nn.sigmoid(z @ p["gw"])
    return (x + y @ p["out"], state.astype(F32),
            rows[s - (taps - 1):].astype(F32))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "d_nope", "d_rope", "d_v", "eps", "theta", "dtype",
    "fault"))
def _latent(x, p, *, n_heads, d_nope, d_rope, d_v, eps, theta, dtype,
            fault=None):
    """x [S, D] -> (x + latent attention of RMSNorm(x), expanded: keys
    and values widened from the latent `HEADS_BLOCK` heads at a time;
    the rows a cache would hold, [S, latent + rope] float32: the normed
    latent and the one rotated key part).  `fault`: one of
    `_LATENT_FAULTS`."""
    s = x.shape[0]
    d_lat = p["kv_a_norm"].shape[0]
    z = _rms(x, p["norm"].astype(dtype), eps)
    ckv = z @ p["kv_a"].astype(dtype)
    c_kv = ckv[:, :d_lat] if fault == "no_latent_norm" else _rms(
        ckv[:, :d_lat], p["kv_a_norm"].astype(dtype), eps)
    k_pe = ckv[:, d_lat:]
    if fault != "no_rope":
        k_pe = _rope(k_pe, theta)
    gate = jax.nn.sigmoid(z @ p["gate"].astype(dtype))          # [S, H]
    hb = math.gcd(n_heads, HEADS_BLOCK)
    nb = n_heads // hb
    dq = d_nope + d_rope
    scale = jnp.asarray((d_nope if fault == "scale_nope" else dq) ** -0.5,
                        dtype)
    w_q = p["q"].reshape(-1, nb, hb * dq).transpose(1, 0, 2)
    kv_b = p["kv_b"].reshape(d_lat, nb, hb * (d_nope + d_v)).transpose(
        1, 0, 2)
    w_o = p["o"].reshape(nb, hb * d_v, -1)
    if fault == "gate_elementwise":
        # column j of the heads' values takes head j mod H's scalar
        gates = jnp.tile(gate, (1, d_v)).reshape(s, nb, hb, d_v)
    else:
        gates = jnp.broadcast_to(gate.reshape(s, nb, hb, 1),
                                 (s, nb, hb, d_v))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]

    def heads(acc, w):
        wq, wkv, wo, gt = w
        q = (z @ wq.astype(dtype)).reshape(s, hb, dq)
        q_pe = q[..., d_nope:]
        if fault != "no_rope":
            q_pe = _rope(q_pe, theta)
        q = jnp.concatenate([q[..., :d_nope], q_pe], -1)
        kv = (c_kv @ wkv.astype(dtype)).reshape(s, hb, d_nope + d_v)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, None, :], (s, hb, d_rope))], -1)
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        scores = jnp.where((j <= i)[None], scores, -jnp.inf)
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                         kv[..., d_nope:])
        if fault != "no_head_gate":
            ctx = ctx * gt.astype(dtype)
        return acc + ctx.reshape(s, hb * d_v) @ wo.astype(dtype), None

    out = jax.lax.scan(heads, jnp.zeros_like(x),
                       (w_q, kv_b, w_o, gates.transpose(1, 0, 2, 3)))[0]
    return x + out, jnp.concatenate([c_kv, k_pe], -1).astype(F32)


def _swiglu(m, g, u, d, limit, dtype):
    gate, up = m @ g.astype(dtype), m @ u.astype(dtype)
    if limit:
        gate = jnp.minimum(gate, jnp.asarray(limit, dtype))
        up = jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ d.astype(dtype)


def _experts(m, gate, up, down, weight, limit, dtype):
    """sum over e of weight[:, e] * SwiGLU_e(m): a scan over the
    experts [E, ...], each widened to `dtype` as it is applied."""
    def one(acc, e):
        g, u, d, w = e
        return acc + _swiglu(m, g, u, d, limit, dtype) * w[:, None], None

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
                    jnp.ones((m.shape[0], f // width), dtype), 0.0, dtype)


@functools.partial(jax.jit, static_argnames=("width", "eps", "dtype",
                                             "fault"))
def _dense_ffn(x, p, *, width, eps, dtype, fault=None):
    m = _rms(x, p["norm"].astype(dtype), eps)
    if fault == "dense_as_sparse":
        return x + _swiglu(m, p["gate"][:, :width], p["up"][:, :width],
                           p["down"][:width], 0.0, dtype)
    return x + _dense(m, p["gate"], p["up"], p["down"], width, dtype)


def choose(scores, bias, *, top_k, n_group, topk_group, fault=None):
    """The router's choice from its scores s [S, E] and its bias [E] ->
    experts [S, k]: c = s + b, a group's score the sum of its two
    largest c, the `topk_group` best groups kept, the k largest c among
    their experts.  Faults: "group_max" (a group's score its largest
    c), "no_group_limit" (the k largest c of all)."""
    s = scores.shape[0]
    c = scores + bias
    if fault == "no_group_limit":
        return jax.lax.top_k(c, top_k)[1]
    grouped = c.reshape(s, n_group, -1)
    group = grouped.max(-1) if fault == "group_max" \
        else jax.lax.top_k(grouped, 2)[0].sum(-1)
    kept = jax.lax.top_k(group, topk_group)[1]
    keep = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], kept].set(True)
    return jax.lax.top_k(
        jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(s, -1),
        top_k)[1]


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "first", "eps", "renorm", "limit",
    "shared_limit", "dtype", "fault"))
def _moe(x, p, follow, scaling, *, top_k, n_group, topk_group, first, eps,
         renorm, limit, shared_limit, dtype=F32, fault=None):
    """-> (x + the HELD experts' part and the shared expert of
    RMSNorm(x), its routing: the router's input, the top-k weights and
    experts of its own choice).  `p`: "norm", "router" [d, E], "bias"
    [E], "gate", "up", "down" [held, ...] (the experts `first` onward),
    the shared expert's three.  `follow` [S, k]: the experts to apply
    instead of its own choice, each weighed by the score computed here;
    a position whose row is negative takes its own.  `fault`: one of
    `_ROUTER_FAULTS`."""
    s = x.shape[0]
    m = _rms(x, p["norm"].astype(dtype), eps)
    scores = jax.nn.sigmoid(m @ p["router"].astype(dtype))
    bias = p["bias"].astype(dtype)
    own_e = choose(scores, bias, top_k=top_k, n_group=n_group,
                   topk_group=topk_group, fault=fault)

    def weights_of(experts):
        w = jnp.take_along_axis(
            scores + bias if fault == "bias_in_weights" else scores,
            experts, -1)
        if renorm and fault != "no_renorm":
            w = w / w.sum(-1, keepdims=True)
        return w if fault == "no_scaling" else w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    held, width = p["gate"].shape[0], p["gate"].shape[2]
    here = (use_e >= first) & (use_e < first + held)
    # an absent expert's column is `held`: past the last one, dropped
    weight = jnp.zeros((s, held + 1), scores.dtype).at[
        jnp.arange(s)[:, None], jnp.where(here, use_e - first, held)
    ].set(weights_of(use_e))[:, :held]
    y = _experts(m, p["gate"], p["up"], p["down"], weight, limit, dtype)
    shared = _swiglu(m, p["shared_gate"], p["shared_up"], p["shared_down"],
                     shared_limit, dtype)
    routing = {"inputs": m.astype(F32),
               "weights": weights_of(own_e).astype(F32), "experts": own_e}
    return x + y + shared, routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


DELTA_KEYS = {"norm": "mixer_norm.scale_0", "in": "delta_in_proj.w_0",
              "w": "delta_conv.w_0", "f": "delta_decay.w_0",
              "dt": "delta_dt.b_0", "a_log": "delta_a_log.w_0",
              "b": "delta_beta.w_0", "gw": "delta_gate.w_0",
              "o_norm": "delta_o_norm.scale_0",
              "out": "delta_out_proj.w_0"}
LATENT_KEYS = {"norm": "attn_norm.scale_0", "q": "q_proj.w_0",
               "gate": "attn_gate.w_0", "kv_a": "kv_a_proj.w_0",
               "kv_a_norm": "kv_a_norm.scale_0", "kv_b": "kv_b_proj.w_0",
               "o": "o_proj.w_0"}
DENSE_KEYS = {"norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
              "up": "ffn_up.w_0", "down": "ffn_down.w_0"}
MOE_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
            "bias": "router_bias.b_0",
            "gate": "experts_gate.w_0", "up": "experts_up.w_0",
            "down": "experts_down.w_0", "shared_gate": "shared_gate.w_0",
            "shared_up": "shared_up.w_0", "shared_down": "shared_down.w_0"}


def latent_layers(config: dict) -> list:
    """The layers that are latent attention, in order: the last of every
    `layer_group_size`."""
    period = int(config["layer_group_size"])
    return [l for l in range(int(config["num_hidden_layers"]))
            if (l + 1) % period == 0]


def delta_layers(config: dict) -> list:
    """The layers that are gated delta rules, in order: what a system's
    states and tails are stacked over."""
    latent = latent_layers(config)
    return [l for l in range(int(config["num_hidden_layers"]))
            if l not in latent]


def sparse_layers(config: dict) -> list:
    """The layers with experts, in order: what a system's routing is
    stacked over."""
    return list(range(int(config["first_k_dense_replace"]),
                      int(config["num_hidden_layers"])))


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None, logits_from: int = 0):
    """[S] token ids -> ([S - logits_from, vocab] float32 next-token
    logits of positions `logits_from` onward, the routing of every
    SPARSE layer stacked: "inputs" [Ls, S, D], "weights" and "experts"
    [Ls, S, k], and under "state" each delta layer's matrix state after
    the last position [delta layers, H, K, K], under "tails" its last
    rows of q | k | v [delta layers, taps - 1, 3 H K], under "latent"
    each latent layer's cache rows [latent layers, S, latent + rope]),
    from the named arrays and the configuration's own keys.  `follow`
    [Ls, S, k]: the experts each sparse layer applies in place of its
    own choice, where they are not negative.  `fault` computes a
    DIFFERENT model, one of `FAULTS`."""
    assert fault is None or fault in FAULTS, fault
    n_layers = int(config["num_hidden_layers"])
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    n_heads = int(config["num_attention_heads"])
    latent, sparse = latent_layers(config), sparse_layers(config)
    # which layer's mixer arrays stand at layer l: the fault that puts M
    # first rotates the period's mixers over the same arrays
    mixer_at = list(range(n_layers))
    if fault == "latent_first":
        period = int(config["layer_group_size"])
        mixer_at = [l - l % period + (l % period - 1) % period
                    for l in range(n_layers)]
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    own = np.full((len(ids), top_k), -1, np.int32)
    routed, held_states, tails, rows = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(n_layers):
            def named(keys, at=l):
                return {k: states[f"layer_{at}.{n}"]
                        for k, n in keys.items()}

            if mixer_at[l] in latent:
                x, row = _latent(
                    x, named(LATENT_KEYS, mixer_at[l]), n_heads=n_heads,
                    d_nope=int(config["qk_nope_head_dim"]),
                    d_rope=int(config["qk_rope_head_dim"]),
                    d_v=int(config["v_head_dim"]), eps=eps, theta=theta,
                    dtype=dtype,
                    fault=fault if fault in _LATENT_FAULTS else None)
                rows.append(row)
            else:
                x, state, tail = _delta(
                    x, named(DELTA_KEYS, mixer_at[l]), n_heads=n_heads,
                    eps=eps, floor=float(config["kda_lower_bound"]),
                    theta=theta, dtype=dtype,
                    fault=fault if fault in _DELTA_FAULTS else None)
                held_states.append(state)
                tails.append(tail)
            if l not in sparse:
                x = _dense_ffn(
                    x, named(DENSE_KEYS),
                    width=int(config["moe_intermediate_size"]), eps=eps,
                    dtype=dtype,
                    fault=fault if fault == "dense_as_sparse" else None)
                continue
            x, r = _moe(
                x, named(MOE_KEYS),
                jnp.asarray(own if follow is None else follow[len(routed)],
                            jnp.int32), scaling, top_k=top_k,
                n_group=int(config["n_group"]),
                topk_group=int(config["topk_group"]),
                first=int(config["first_local_expert"]), eps=eps,
                renorm=bool(config["norm_topk_prob"]),
                limit=float(config["expert_swiglu_limit_list"][l]),
                shared_limit=float(
                    config["share_expert_swiglu_limit_list"][l]),
                dtype=dtype,
                fault=fault if fault in _ROUTER_FAULTS else None)
            routed.append(r)
        out = _head(x[logits_from:], states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    routing = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    routing["state"] = jnp.stack(held_states)
    routing["tails"] = jnp.stack(tails)
    routing["latent"] = jnp.stack(rows)
    return np.asarray(out), routing


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _scores(m, w):
    return jax.nn.sigmoid(m @ w.astype(F32))


def router_rel_err(states: dict, config: dict, routing) -> float:
    """A system's routing judged on its OWN router inputs, in float64
    from float32 scores: how far the groups of its chosen experts lie
    below the `topk_group`-th best group's score, how far below an
    expert it left out (of the kept groups) its least chosen one lies,
    and how far its weights lie from the scores renormalised and
    scaled: the largest of the three, each relative."""
    exp = np.asarray(routing["experts"])
    n_group, keep_n = int(config["n_group"]), int(config["topk_group"])
    layers = sparse_layers(config)
    with jax.default_matmul_precision("highest"):
        s = np.stack([np.asarray(_scores(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(layers)]).astype(np.float64)
    bias = np.stack([np.asarray(states[f"layer_{l}.router_bias.b_0"],
                                np.float64) for l in layers])
    c = s + bias[:, None, :]
    size = c.shape[-1] // n_group
    grouped = c.reshape(c.shape[:-1] + (n_group, size))
    group = np.sort(grouped, -1)[..., -2:].sum(-1)           # [L, S, G]
    edge = np.sort(group, -1)[..., -keep_n]                  # the k-th best
    own = np.take_along_axis(group, exp // size, -1)         # [L, S, k]
    off_group = np.maximum(0.0, edge[..., None] - own) / np.abs(
        edge[..., None])
    kept = group >= edge[..., None]
    left_out = np.where(np.repeat(kept, size, -1), c, -np.inf)
    np.put_along_axis(left_out, exp, -np.inf, -1)
    least = np.take_along_axis(c, exp, -1).min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / np.abs(least)
    chosen = np.take_along_axis(s, exp, -1)
    if config["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weights = chosen * float(config["routed_scaling_factor"])
    off = np.abs(np.asarray(routing["weights"], np.float64)
                 - weights) / weights
    return float(max(off_group.max(), gap.max(), off.max()))


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rms_err  the logit difference by root mean square over the
                      logits', the reference following the system's
                      experts: rounding, and every fault but a swap
      late_rms_err    the same over the second half of the positions
      state_rms_err   where the system gives its lane's states after the
                      last position (`routing["state"]`, [delta layers,
                      H, K, K]): their distance from this reference's,
                      by root mean square over the reference's, all
                      delta layers together: a state in fewer bits, a
                      wrong decay or correction, a lane not reset
      tail_rms_err    the same of its tails (`routing["tails"]`)
      latent_rms_err  the same of the rows its latent layers cached
                      (`routing["latent"]`, [latent layers, S, latent +
                      rope]): a norm left out, a key part not rotated
      router_rel_err  on the system's own router inputs
                      (`router_rel_err`)
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
    """
    exp = np.asarray(routing["experts"])
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own_e = np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own_e.reshape(-1, exp.shape[-1]))])

    def rms(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    out = {"logits_rms_err": rms(got, want),
           "late_rms_err": rms(got[half:], want[half:]),
           "logits_rel_err": float(np.max(np.abs(got - want))
                                   / np.max(np.abs(want))),
           "router_rel_err": router_rel_err(states, config, routing),
           "routing_agree": float(agree),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "finite": bool(np.isfinite(got).all())}
    for name, key in (("state", "state_rms_err"), ("tails", "tail_rms_err"),
                      ("latent", "latent_rms_err")):
        if name in routing:
            out[key] = rms(routing[name], own[name])
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16 (state, tails and latent rows too), as if that were the
    system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids, which=FAULTS) -> dict:
    """`compare`'s numbers for the float32 models of `FAULTS`, as if
    each were the system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids, *forward(
        states, config, ids, fault=fault)) for fault in which}


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           length=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] (its OWN
    experts: the server's choice is not known).  `length`: each request
    is judged over its first `length` positions (all of them, if None),
    and every request is padded to ONE length (a causal model's earlier
    positions do not see the pad), so one compiled forward serves all.

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens
    """
    agree, gap = [], []
    requests = [(np.asarray(ids)[:(length or len(ids) - 1) + 1], start)
                for ids, start in requests]
    longest = length or -(-max(len(ids) - 1
                               for ids, _ in requests) // 128) * 128
    for ids, start in requests:
        n = len(ids) - 1
        if n < start:
            continue                    # no token sampled inside `length`
        padded = np.zeros(longest, ids.dtype)
        padded[:n] = ids[:-1]
        want = np.asarray(forward(
            states, config, padded, dtype=dtype, fault=fault,
            logits_from=start - 1)[0], np.float32)[:n - start + 1]
        got = want[np.arange(len(want)), ids[start:]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
    if not agree:
        return {"served_argmax_agree": None, "served_gap_rms": None,
                "tokens": 0}
    agree, gap = np.concatenate(agree), np.concatenate(gap)
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "tokens": int(len(agree))}
