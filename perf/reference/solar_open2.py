"""Plain reference of the Solar-Open2 decoder (Upstage,
`Solar-Open2-250B` config.json, `model_type: solar_open2`): float32
`jax.numpy`, one full forward over one token sequence: the gated delta
rule as a LOOP OVER POSITIONS on a matrix state from zeros (no lane, no
tail, no snapshot), its convolutions as causal convolutions over the
sequence, attention as a masked product without positions; no cache, no
sort, no batching, every matrix multiplication at `highest` precision.
It knows nothing of paddle_tpu: it takes a dict of named arrays under
the names the served decoder's `state_shapes` gives
(`layer_<l>.delta_in_proj.w_0`, ...; weights are stored [in, out], the
experts [expert, in, out]) and the configuration's own keys.

The model, from config.json's keys, the catalog's `described_as`
("gated delta-rule linear (neg. eigenvalues, conv4); softmax NoPE GQA
64Q/8KV, 48L 3:1; 320 experts, top-8, 1 shared") and the released layer
of Kimi Delta Attention (arXiv:2510.26692), whose `kda_*` keys the
linear mixer carries (d = `hidden_size`; every norm an RMSNorm with a
scale and `rms_norm_eps`; z the normed input of a mixer):

  x = E[token]
  every layer l:  h = x + mixer_l(RMSNorm(x));  x = h + moe_l(RMSNorm(h))
  mixer, l in `gqa_layers` (softmax attention): `num_attention_heads`
      query heads over `num_key_value_heads` K/V heads of `head_dim`,
      no bias, NO position signal (`use_rope` false), scores times
      head_dim^-0.5, causal; `use_gqa_gate`: the heads' output times
      sigmoid(z W_g) elementwise, then W_o
  mixer, every other layer (`linear_attn_config`: H = `num_heads` heads
      whose keys and values are K = `head_dim` columns,
      `short_conv_kernel_size` taps):
      q, k, v = silu(conv(z W_q)), silu(conv(z W_k)), silu(conv(z W_v)),
          the convolution depthwise and causal over the last taps rows,
          zeros before position 0, no bias
      q = q / |q| * K^-0.5, k = k / |k| a head (x * rsqrt(sum x^2 + 1e-6))
      g = -exp(A_log[h]) * softplus((z W_fa) W_fb + dt_bias)  [H, K]: a
          log decay a key CHANNEL (`kda_use_full_proj` false: the
          low-rank pair)
      beta = 2 * sigmoid(z W_b)  [H]     (the 2: `kda_allow_neg_eigval`)
      S' = diag(exp(g_t)) S_(t-1);  S_t = S' + beta_t k_t (v_t - k_t^T S')^T
      o_t = S_t^T q_t                    S [K keys, K values] a head
      mixer = [RMSNorm_head(o_t) * w * sigmoid((z W_ga) W_gb + b_g)] W_o
          (the norm over a head's K columns, ONE scale of K for all heads)
  moe (every layer: `first_k_dense_replace` 0): p = softmax(m W_r) over
      all the router's columns, float32; the `num_experts_per_tok`
      largest (a tie to the lower index), weights p_i / sum of the chosen
      (`norm_topk_prob`) times `routed_scaling_factor`; each chosen
      expert adds w_e Wd_e (silu(Wg_e m) * (Wu_e m)) at
      `moe_intermediate_size`; plus one shared expert of
      `n_shared_experts` x `moe_intermediate_size` on every token; no
      token dropped, no capacity
  logits = RMSNorm(x) W_head            (untied)

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_hidden_layers` is whatever the
configuration holds and layer l is an attention layer iff l is in
`gqa_layers` (the benchmark's cut keeps one period: G K K K); the
experts HELD are `n_routed_experts` of them from `first_local_expert`
on (the chip's share of an expert-parallel layer: the router keeps its
published columns, and what an absent expert would add is left out, as
in `granite_hybrid.py` and `k_exaone.py`); `vocab_size` is a slice; the
gates' rank (`kda_gate_rank`), the L2 norms' eps and the shared expert's
width are the configuration's `assumed`; q | k | v are ONE stored matrix
[d, 3 H K] and one convolution [taps, 3 H K] (row j multiplies the row
`taps - 1 - j` positions back): storage, not mathematics; the norms'
names are the served decoder's (`mixer_norm` on a delta layer,
`attn_norm` on an attention layer).

Memory: the served weights (6.6 GB of bfloat16) stand beside this, so
an expert is widened as it is applied (a scan over the held experts,
each applied densely to every token and masked by the weights),
attention runs a block of query rows at a time, and the logits are
computed from position `logits_from` on.

What decides `correct` is `compare`, as in `lfm2_moe.py` and
`granite_hybrid.py`: the reference FOLLOWS the system's choice of
experts and judges the choice on the router's own input
(`router_rel_err`).  `below` is the reading one precision down (all
bfloat16, the state too); `faults` are thirteen readings a wrong step or
a wrong SNAPSHOT has to give (`FAULTS`: the three snapshot faults
tamper with the state and the rows at position `cut`, where a served
request would start from a restored snapshot).  `served` judges what a
SERVER delivered, of which only tokens are known.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# the decay applied AFTER the rank-one correction; beta without its 2; a
# decay a head and not a channel; no L2 norm on q and k; SiLU in place of
# the sigmoid on the output gate; no SiLU after the convolutions; the
# tail shifted by one position; a state and tail that start from ZEROS
# at `cut` (a hit with no restore); a snapshot one position late (the
# last token before `cut` applied twice); tails left out of the snapshot
# (zero rows before `cut`, the state kept); RoPE on the attention
# layers; their gate left out; the top-k weights not renormalised
FAULTS = ("decay_after_update", "beta_no_2", "decay_per_head",
          "no_l2norm", "gate_silu", "no_conv_silu", "tail_shifted",
          "hit_no_restore", "snapshot_late", "snapshot_no_tails",
          "rope_on_attention", "no_attention_gate", "no_renorm")
SNAPSHOT_FAULTS = ("hit_no_restore", "snapshot_late", "snapshot_no_tails")
# positions from `cut` on that `cut_rms_err` reads
CUT_SPAN = 4


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _unit(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True)
                             + jnp.asarray(1e-6, x.dtype))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "eps", "neg_eigval", "dtype", "fault", "cut"))
def _delta(x, p, *, n_heads, eps, neg_eigval, dtype, fault=None, cut=0):
    """x [S, D] -> (x + the gated delta rule of RMSNorm(x), the state
    after the last position [H, K, K] float32, the last `taps - 1` rows
    of q | k | v before the convolution [taps - 1, 3 H K] float32).
    `fault`: one of `FAULTS` that this layer computes; the snapshot
    faults act at position `cut`."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    z = _rms(x, p["norm"], eps)
    rows = z @ p["in"]                                   # [S, 3 H K]
    hk = rows.shape[1] // 3
    k_n = hk // n_heads
    f = (z @ p["fa"]) @ p["fb"] + p["dt"]
    g = -jnp.exp(p["a_log"])[None, :, None] * jax.nn.softplus(
        f).reshape(s, n_heads, k_n)
    if fault == "decay_per_head":
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(z @ p["b"])                    # [S, H]
    if neg_eigval and fault != "beta_no_2":
        beta = 2.0 * beta
    # the positions as the recurrence walks them: a snapshot one position
    # late walks the last one before `cut` twice
    walk = np.arange(s)
    if fault == "snapshot_late":
        walk = np.concatenate([walk[:cut], walk[cut - 1:]])
    at = np.arange(len(walk))
    resumed = at >= cut if fault in ("hit_no_restore",
                                     "snapshot_no_tails") else None
    taps = p["w"].shape[0]
    hist = rows[walk]
    conv = jnp.zeros_like(hist)
    for j in range(taps):
        back = taps - 1 - j + (fault == "tail_shifted" and j < taps - 1)
        src = at - back
        ok = src >= 0
        if resumed is not None:
            # rows before `cut` are zeros for the positions after it
            ok = ok & ~(resumed & (src < cut))
        conv = conv + p["w"][j] * jnp.where(
            jnp.asarray(ok)[:, None], hist[np.maximum(src, 0)], 0.0)
    if fault != "no_conv_silu":
        conv = jax.nn.silu(conv)
    q, k, v = (conv[:, i * hk:(i + 1) * hk].reshape(-1, n_heads, k_n)
               for i in range(3))
    if fault != "no_l2norm":
        q, k = _unit(q), _unit(k)
    q = q * jnp.asarray(k_n ** -0.5, dtype)
    keep = jnp.asarray(
        np.where(at == cut, 0.0, 1.0) if fault == "hit_no_restore"
        else np.ones(len(at)), dtype)

    def one(state, t):
        q_t, k_t, v_t, a_t, b_t, keep_t = t
        state = state * keep_t
        if fault == "decay_after_update":
            seen = (k_t[..., None] * state).sum(axis=1)
            state = a_t[..., None] * (
                state + (b_t[:, None] * k_t)[..., None]
                * (v_t - seen)[:, None, :])
        else:
            state = a_t[..., None] * state
            seen = (k_t[..., None] * state).sum(axis=1)         # k^T S'
            state = state + (b_t[:, None] * k_t)[..., None] * (
                v_t - seen)[:, None, :]
        return state, (state * q_t[..., None]).sum(axis=1)      # S^T q

    state, o = jax.lax.scan(
        one, jnp.zeros((n_heads, k_n, k_n), dtype),
        (q, k, v, jnp.exp(g)[walk], beta[walk], keep))
    if fault == "snapshot_late":
        o = jnp.concatenate([o[:cut], o[cut + 1:]])     # the repeat's own
    gate = (z @ p["ga"]) @ p["gb"] + p["g"]
    gate = jax.nn.silu(gate) if fault == "gate_silu" \
        else jax.nn.sigmoid(gate)
    y = _rms(o, p["o_norm"], eps).reshape(s, hk) * gate
    return (x + y @ p["out"], state.astype(F32),
            hist[len(walk) - (taps - 1):].astype(F32))


def _rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, rotate-half (the
    `rope_on_attention` fault alone)."""
    s, _, dh = x.shape
    inv = jnp.asarray(
        [float(theta) ** (-2.0 * i / dh) for i in range(dh // 2)], F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "gated", "dtype"))
def _attention(x, p, *, n_heads, n_kv, eps, theta=None, gated=True,
               dtype=F32):
    """x [S, D] -> x + causal grouped-query attention of RMSNorm(x)
    without positions, its output times sigmoid(z W_g) (`gated`), a
    block of query rows at a time.  `theta`: RoPE at that base (the
    fault)."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    z = _rms(x, p["norm"], eps)
    q = (z @ p["q"]).reshape(s, n_heads, -1)
    k = (z @ p["k"]).reshape(s, n_kv, -1)
    v = (z @ p["v"]).reshape(s, n_kv, -1)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scale = jnp.asarray(q.shape[-1] ** -0.5, dtype)
    size = math.gcd(s, 512)

    def block(i):
        rows = i * size + jnp.arange(size)
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) * scale
        scores = jnp.where(jnp.arange(s)[None, None, :]
                           <= rows[None, :, None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(block, jnp.arange(s // size)).reshape(s, -1)
    if gated:
        ctx = ctx * jax.nn.sigmoid(z @ p["gate"])
    return x + ctx @ p["o"]


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
    "top_k", "first", "eps", "renorm", "dtype"))
def _moe(x, p, follow, scaling, *, top_k, first, eps, renorm=True,
         dtype=F32):
    """-> (x + the HELD experts' part and the shared expert of
    RMSNorm(x), its routing: the router's input, the top-k weights and
    experts of its own choice).  `p`: "norm", "router" [d, E], "gate",
    "up", "down" [held, ...] (the experts `first` onward), the shared
    expert's three.  `follow` [S, k]: the experts to apply instead of
    its own choice, each weighed by the probability computed here; a
    position whose row is negative takes its own."""
    s = x.shape[0]
    m = _rms(x, p["norm"].astype(dtype), eps)
    probs = jax.nn.softmax(m @ p["router"].astype(dtype), axis=-1)
    _, own_e = jax.lax.top_k(probs, top_k)

    def weights_of(experts):
        w = jnp.take_along_axis(probs, experts, -1)
        if renorm:
            w = w / w.sum(-1, keepdims=True)
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    held = p["gate"].shape[0]
    here = (use_e >= first) & (use_e < first + held)
    # an absent expert's column is `held`: past the last one, dropped
    weight = jnp.zeros((s, held + 1), probs.dtype).at[
        jnp.arange(s)[:, None], jnp.where(here, use_e - first, held)
    ].set(weights_of(use_e))[:, :held]
    y = _experts(m, p["gate"], p["up"], p["down"], weight, dtype)
    shared = (jax.nn.silu(m @ p["shared_gate"].astype(dtype))
              * (m @ p["shared_up"].astype(dtype))
              ) @ p["shared_down"].astype(dtype)
    routing = {"inputs": m.astype(F32),
               "weights": weights_of(own_e).astype(F32), "experts": own_e}
    return x + y + shared, routing


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head(x, scale, head, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ head.astype(dtype)).astype(F32)


DELTA_KEYS = {"norm": "mixer_norm.scale_0", "in": "delta_in_proj.w_0",
              "w": "delta_conv.w_0", "fa": "delta_decay_a.w_0",
              "fb": "delta_decay_b.w_0", "dt": "delta_dt.b_0",
              "a_log": "delta_a_log.w_0", "b": "delta_beta.w_0",
              "ga": "delta_gate_a.w_0", "gb": "delta_gate_b.w_0",
              "g": "delta_gate.b_0", "o_norm": "delta_o_norm.scale_0",
              "out": "delta_out_proj.w_0"}
ATTN_KEYS = {"norm": "attn_norm.scale_0", "q": "q_proj.w_0",
             "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
             "gate": "attn_gate.w_0"}
MOE_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
            "gate": "experts_gate.w_0", "up": "experts_up.w_0",
            "down": "experts_down.w_0", "shared_gate": "shared_gate.w_0",
            "shared_up": "shared_up.w_0", "shared_down": "shared_down.w_0"}


def delta_layers(config: dict) -> list:
    """The layers that are gated delta rules, in order: what a system's
    states and tails are stacked over."""
    return [l for l in range(int(config["num_hidden_layers"]))
            if l not in config["gqa_layers"]]


def cut_of(n: int, block: int = 16) -> int:
    """Where the snapshot faults act on a sequence of `n` positions when
    nobody says: the last multiple of `block` at or under its middle."""
    return min(max(block, n // 2 // block * block), max(n - CUT_SPAN, 0))


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None, cut=None, logits_from: int = 0):
    """[S] token ids -> ([S - logits_from, vocab] float32 next-token
    logits of positions `logits_from` onward, the routing of every layer
    stacked: "inputs" [L, S, D], "weights" and "experts" [L, S, k], and
    under "state" each delta layer's matrix state after the last
    position [delta layers, H, K, K], under "tails" its last rows of
    q | k | v [delta layers, taps - 1, 3 H K]), from the named arrays
    and the configuration's own keys.  `follow` [L, S, k]: the experts
    each layer applies in place of its own choice, where they are not
    negative.  `fault` computes a DIFFERENT model, one of `FAULTS`, the
    snapshot faults at position `cut` (`cut_of`, if None)."""
    assert fault is None or fault in FAULTS, fault
    lin = config["linear_attn_config"]
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    cut = cut_of(len(ids)) if cut is None else int(cut)
    attn = dict(n_heads=int(config["num_attention_heads"]),
                n_kv=int(config["num_key_value_heads"]), eps=eps,
                theta=(float(config["rope_theta"])
                       if config["use_rope"] or fault == "rope_on_attention"
                       else None),
                gated=(bool(config["use_gqa_gate"])
                       and fault != "no_attention_gate"), dtype=dtype)
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    own = np.full((len(ids), top_k), -1, np.int32)
    routed, held_states, tails = [], [], []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"]
                        for k, n in keys.items()}

            if l in config["gqa_layers"]:
                x = _attention(x, named(ATTN_KEYS), **attn)
            else:
                x, state, tail = _delta(
                    x, named(DELTA_KEYS), n_heads=int(lin["num_heads"]),
                    eps=eps,
                    neg_eigval=bool(config["kda_allow_neg_eigval"]),
                    dtype=dtype, cut=cut,
                    fault=fault if fault not in (
                        "rope_on_attention", "no_attention_gate",
                        "no_renorm") else None)
                held_states.append(state)
                tails.append(tail)
            x, r = _moe(
                x, named(MOE_KEYS),
                jnp.asarray(own if follow is None else follow[len(routed)],
                            jnp.int32), scaling, top_k=top_k,
                first=int(config["first_local_expert"]), eps=eps,
                renorm=(bool(config["norm_topk_prob"])
                        and fault != "no_renorm"), dtype=dtype)
            routed.append(r)
        out = _head(x[logits_from:], states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    routing = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    routing["state"] = jnp.stack(held_states)
    routing["tails"] = jnp.stack(tails)
    return np.asarray(out), routing


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


@jax.jit
def _probs(m, w):
    return jax.nn.softmax(m @ w.astype(F32), axis=-1)


def compare(states: dict, config: dict, ids, got, routing,
            cut=None) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rms_err  the logit difference by root mean square over the
                      logits', the reference following the system's
                      experts: rounding, and every fault but a swap
      late_rms_err    the same over the second half of the positions
      cut_rms_err     the same over the `CUT_SPAN` positions from `cut`
                      on, where a state or a tail that a snapshot lost,
                      zeroed or took a position late shows whole
      state_rms_err   where the system gives its lane's states after the
                      last position (`routing["state"]`, [delta layers,
                      H, K, K]): their distance from this reference's,
                      by root mean square over the reference's, all
                      delta layers together: a state in fewer bits, a
                      wrong decay or correction, a lane not reset
      tail_rms_err    the same of its tails (`routing["tails"]`)
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies,
                      and how far its weights lie from the float32
                      probabilities renormalised and scaled, both
                      relative
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
    """
    exp = np.asarray(routing["experts"])
    cut = cut_of(len(ids)) if cut is None else int(cut)
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own_e = np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own_e.reshape(-1, exp.shape[-1]))])
    with jax.default_matmul_precision("highest"):
        probs = np.stack([np.asarray(_probs(
            jnp.asarray(routing["inputs"][l], F32),
            states[f"layer_{l}.router.w_0"]))
            for l in range(exp.shape[0])]).astype(np.float64)
    left_out = probs.copy()
    np.put_along_axis(left_out, exp, -np.inf, -1)
    chosen = np.take_along_axis(probs, exp, -1)
    least = chosen.min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / np.abs(least)
    if config["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weights = chosen * float(config["routed_scaling_factor"])
    off = np.abs(np.asarray(routing["weights"], np.float64)
                 - weights) / weights

    def rms(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    out = {"logits_rms_err": rms(got, want),
           "late_rms_err": rms(got[half:], want[half:]),
           "cut_rms_err": rms(got[cut:cut + CUT_SPAN],
                              want[cut:cut + CUT_SPAN]),
           "logits_rel_err": float(np.max(np.abs(got - want))
                                   / np.max(np.abs(want))),
           "router_rel_err": float(max(gap.max(), off.max())),
           "routing_agree": float(agree),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "finite": bool(np.isfinite(got).all())}
    for name in ("state", "tails"):
        if name in routing:
            out[name.rstrip("s") + "_rms_err"] = rms(routing[name],
                                                     own[name])
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16 (state and tail too), as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids, which=FAULTS,
           cut=None) -> dict:
    """`compare`'s numbers for the float32 models of `FAULTS`, as if
    each were the system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids, *forward(
        states, config, ids, fault=fault, cut=cut), cut=cut)
        for fault in which}


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           pad_to=None, cuts=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] (its OWN
    experts: the server's choice is not known) on a state that it walked
    from position 0: the server's came out of a snapshot.

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens
      early_argmax_agree, early_gap_rms  the same over each request's
                      first 32 delivered tokens alone: nearest to where
                      its lane's state was restored

    Every request is padded to ONE length (a causal model's earlier
    positions do not see the pad), so one compiled forward serves all:
    `pad_to`, or the longest rounded up to 128.  `cuts`: where a
    snapshot fault acts a request (its prompt's last multiple of 16
    under the last 32 positions, if None: about where a document
    ends)."""
    agree, gap, early = [], [], []
    longest = pad_to or -(-max(len(ids) - 1
                               for ids, _ in requests) // 128) * 128
    for i, (ids, start) in enumerate(requests):
        ids = np.asarray(ids)
        n = len(ids) - 1
        padded = np.zeros(longest, ids.dtype)
        padded[:n] = ids[:-1]
        cut = cuts[i] if cuts is not None else max(
            16, (start - 32) // 16 * 16)
        want = np.asarray(forward(
            states, config, padded, dtype=dtype, fault=fault, cut=cut,
            logits_from=start - 1)[0], np.float32)[:n - start + 1]
        got = want[np.arange(len(want)), ids[start:]]
        top = want.max(-1)
        agree.append(got >= top)
        gap.append((top - got) / np.abs(want).max())
        early.append(np.arange(len(want)) < 32)
    agree, gap, early = (np.concatenate(x) for x in (agree, gap, early))
    return {"served_argmax_agree": float(agree.mean()),
            "served_gap_rms": float(np.sqrt(np.mean(gap ** 2))),
            "early_argmax_agree": float(agree[early].mean()),
            "early_gap_rms": float(np.sqrt(np.mean(gap[early] ** 2))),
            "tokens": int(len(agree)), "tokens_early": int(early.sum())}
