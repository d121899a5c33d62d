"""Plain reference of the LFM2-MoE decoder (LiquidAI, `LFM2-24B-A2B`
config.json, `model_type: lfm2_moe`): float32 `jax.numpy`, one full
forward over one token sequence, the short convolution written as a
causal convolution over the sequence (no tail, no step), attention as a
masked product; no cache, no lanes, no sort, no batching, every matrix
multiplication at `highest` precision.  It knows nothing of paddle_tpu:
it takes a dict of named arrays under the names the served decoder's
`state_shapes` gives (`layer_<l>.conv_in_proj.w_0`, ...; weights are
stored [in, out], the experts [expert, in, out]) and the
configuration's own keys.

The model, from config.json's keys and the published `lfm2_moe`
modelling code (d = `hidden_size`; every norm an RMSNorm with a scale
and `norm_eps`):

  x = E[token]
  every layer l:  h = x + mixer_l(RMSNorm(x));  x = h + ffn_l(RMSNorm(h))
  mixer, `layer_types[l]` "conv" (a gated short convolution,
      `conv_L_cache` taps, `conv_bias` false), z the normed input:
      B, C, u = split3(z W_in)       in THAT order, d columns each
      p_t = B_t * u_t
      c_t = sum_j w[j] * p_(t - (taps - 1) + j)    depthwise, causal,
            zeros before position 0, NO activation (Mamba's has one)
      mixer = (C_t * c_t) W_out
  mixer, "full_attention": `num_attention_heads` query heads over
      `num_key_value_heads` K/V heads of d / heads columns, no bias; an
      RMSNorm over each head's columns of q and of k (one scale a
      projection) BEFORE RoPE; rotate-half RoPE over all of a head's
      columns at `rope_parameters.rope_theta`; scores / sqrt(head
      size); causal; query head i reads K/V head i // (heads / kv)
  ffn, l < `num_dense_layers`: SwiGLU at `intermediate_size`
  ffn, else: s = sigmoid(z W_r) over `num_experts`, float32; the
      `num_experts_per_tok` chosen are the largest of s + expert_bias
      (`use_expert_bias`: the bias decides the CHOICE alone; a tie to
      the lower index); w = s[chosen] / (sum of s[chosen] + 1e-6)
      (`norm_topk_prob`; the configuration holds the literal as
      `norm_topk_eps`), times `routed_scaling_factor`; each chosen
      expert adds w_e Wd_e (silu(Wg_e z) * (Wu_e z)) at
      `moe_intermediate_size`; no shared expert, no token dropped, no
      capacity
  logits = RMSNorm(x) E^T      (`embedding_norm`; the tied head)

Departures from the published model: weights are random from the seed,
not the trained checkpoint (`expert_bias`, a trained buffer there, is
seeded too); `num_hidden_layers`, `num_dense_layers` and `layer_types`
are whatever the configuration holds (the benchmark's cut keeps one
leading dense layer and two whole periods); the head is TIED to the
embedding (`tie_word_embeddings`, which the catalog's config does not
carry: the family's published models tie it); the convolution's taps
are stored [taps, d] (row j multiplies the row `taps - 1 - j` positions
back), the published Conv1d weight's transpose: storage, not
mathematics; the norms' names are the served decoder's
(`operator_norm` on a conv layer, `attn_norm` on an attention layer,
`final_norm` for `embedding_norm`).

Memory: the served weights (10.4 GB of bfloat16) stand beside this, so
nothing here holds a layer's matrices in float32 at once: an expert is
widened as it is applied (a scan over the experts, each applied densely
to every token and masked by the weights), the dense layer's matrices
go through the same scan as column blocks, and the logits are computed
on the host a block of the vocabulary's rows at a time.

What decides `correct` is `compare`, as in `olmoe.py` and
`granite_hybrid.py`: the reference FOLLOWS the system's choice of
experts (with random weights the k-th and k+1-th scores often lie
closer than the served bf16 rounding moves them: a swap, not an error)
and judges the choice on the router's own input (`router_rel_err`).
`below` is the reading one precision down (all bfloat16, the tail too);
`faults` are seven readings a wrong step has to give (`FAULTS`).
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
CONV = "conv"
# no reset of the tail at position 0; the tail shifted by one position;
# a SiLU on the convolution (Mamba's form); the two gates exchanged
# (B * conv(C * u)); the bias added to the weights and not to the
# choice alone; the chosen weights not renormalised; the head norms
# after RoPE
FAULTS = ("no_reset", "tail_shifted", "conv_silu", "gates_exchanged",
          "bias_in_weights", "no_renorm", "norm_after_rope")


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, rotate-half."""
    s, _, dh = x.shape
    inv = jnp.asarray(
        [float(theta) ** (-2.0 * i / dh) for i in range(dh // 2)], F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=("eps", "dtype", "fault"))
def _conv(x, p, before, *, eps, dtype, fault=None):
    """x [S, D] -> (x + the gated short convolution of RMSNorm(x), the
    last `taps - 1` rows of the product B * u [taps - 1, D] float32).
    `before` [taps - 1, D]: the product's rows before position 0
    (zeros, but for the `no_reset` fault).  `fault`: "tail_shifted"
    reads every earlier row one position too far back (a tail shifted
    before the newest row joins it), "conv_silu" puts Mamba's SiLU on
    the convolution, "gates_exchanged" computes B * conv(C * u)."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s, d = x.shape
    bcu = _rms(x, p["norm"], eps) @ p["in"]
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    if fault == "gates_exchanged":
        b, c = c, b
    taps = p["w"].shape[0]
    padded = jnp.concatenate([before.astype(dtype), b * u], 0)
    late = jnp.concatenate([jnp.zeros((1, d), dtype), padded], 0)
    conv = p["w"][taps - 1] * padded[taps - 1:]
    for j in range(taps - 1):
        rows = late[j:j + s] if fault == "tail_shifted" \
            else padded[j:j + s]
        conv = conv + p["w"][j] * rows
    if fault == "conv_silu":
        conv = jax.nn.silu(conv)
    return x + (c * conv) @ p["out"], padded[s:].astype(F32)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "norm_after_rope", "dtype"))
def _attention(x, p, *, n_heads, n_kv, eps, theta, norm_after_rope=False,
               dtype=F32):
    """x [S, D] -> x + causal grouped-query attention of RMSNorm(x),
    the per-head norms of q and k before RoPE (`norm_after_rope`: the
    fault of that name)."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    n = _rms(x, p["norm"], eps)
    q = (n @ p["q"]).reshape(s, n_heads, -1)
    k = (n @ p["k"]).reshape(s, n_kv, -1)
    v = (n @ p["v"]).reshape(s, n_kv, -1)
    if norm_after_rope:
        q = _rms(_rope(q, theta), p["q_norm"], eps)
        k = _rms(_rope(k, theta), p["k_norm"], eps)
    else:
        q = _rope(_rms(q, p["q_norm"], eps), theta)
        k = _rope(_rms(k, p["k_norm"], eps), theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(q.shape[-1], dtype))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return x + ctx.reshape(s, -1) @ p["o"]


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
def _dense_ffn(x, p, *, eps, dtype):
    """x + SwiGLU(RMSNorm(x)) at the dense layer's width, its columns
    in blocks (the sum over a block is the sum over its columns: the
    same mathematics, a layer's float32 never whole)."""
    m = _rms(x, p["norm"].astype(dtype), eps)
    d, f = p["gate"].shape
    width = math.gcd(f, 2048)
    gate, up = (p[n].reshape(d, f // width, width).transpose(1, 0, 2)
                for n in ("gate", "up"))
    down = p["down"].reshape(f // width, width, d)
    return x + _experts(m, gate, up, down,
                        jnp.ones((x.shape[0], f // width), dtype), dtype)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "eps", "renorm", "bias_in_weights", "dtype"))
def _ffn(x, p, follow, scaling, sum_eps, *, top_k, eps, renorm=True,
         bias_in_weights=False, dtype=F32):
    """-> (x + the expert layer of RMSNorm(x), its routing: the
    router's input, the top-k weights and experts of its own choice).
    `p`: "norm", "router" [d, E], "bias" [E], "gate", "up", "down"
    [E, ...].  `follow` [S, k]: the experts to apply instead of its own
    choice, each weighed by the score computed here; a position whose
    row is negative takes its own.  `scaling`, `sum_eps` (arrays): the
    factor on the weights and what is added to the sum they are divided
    by.  `renorm` False and `bias_in_weights` compute the faults
    "no_renorm" and "bias_in_weights"."""
    s = x.shape[0]
    m = _rms(x, p["norm"].astype(dtype), eps)
    scores = jax.nn.sigmoid(m @ p["router"].astype(dtype))      # [S, E]
    choice = scores + p["bias"].astype(dtype)
    _, own_e = jax.lax.top_k(choice, top_k)
    take = choice if bias_in_weights else scores

    def weights_of(experts):
        w = jnp.take_along_axis(take, experts, -1)
        if renorm:
            w = w / (w.sum(-1, keepdims=True) + sum_eps.astype(dtype))
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(s)[:, None], use_e].set(weights_of(use_e))
    y = _experts(m, p["gate"], p["up"], p["down"], weight, dtype)
    routing = {"inputs": m.astype(F32),
               "weights": weights_of(own_e).astype(F32), "experts": own_e}
    return x + y, routing


HEAD_BLOCKS = 8


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_block(x, scale, rows, *, eps, dtype):
    return (_rms(x, scale.astype(dtype), eps)
            @ rows.astype(dtype).T).astype(F32)


def _head(x, scale, emb, *, eps, dtype):
    """RMSNorm(x) E^T -> [S, vocab] float32 on the HOST, a block of the
    vocabulary's rows at a time: the whole embedding in float32 is 0.5
    GB beside the served weights."""
    size = -(-emb.shape[0] // HEAD_BLOCKS)
    return np.concatenate([np.asarray(_head_block(
        x, scale, emb[i:i + size], eps=eps, dtype=dtype))
        for i in range(0, emb.shape[0], size)], axis=1)


CONV_KEYS = {"norm": "operator_norm.scale_0", "in": "conv_in_proj.w_0",
             "w": "conv.w_0", "out": "conv_out_proj.w_0"}
ATTN_KEYS = {"norm": "attn_norm.scale_0", "q": "q_proj.w_0",
             "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
             "q_norm": "q_norm.scale_0", "k_norm": "k_norm.scale_0"}
DENSE_KEYS = {"norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
              "up": "ffn_up.w_0", "down": "ffn_down.w_0"}
SPARSE_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
               "bias": "router_bias.b_0", "gate": "experts_gate.w_0",
               "up": "experts_up.w_0", "down": "experts_down.w_0"}


def conv_layers(config: dict) -> list:
    """The layers that are gated short convolutions, in order: what a
    system's tails are stacked over."""
    return [l for l in range(int(config["num_hidden_layers"]))
            if config["layer_types"][l] == CONV]


def sparse_layers(config: dict) -> list:
    """The layers with experts, in order: what a system's routing is
    stacked over."""
    return list(range(int(config["num_dense_layers"]),
                      int(config["num_hidden_layers"])))


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None, before=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every SPARSE layer stacked: "inputs" [Ls, S, D],
    "weights" and "experts" [Ls, S, k], and under "tails" each conv
    layer's last `conv_L_cache - 1` rows of B * u, [conv layers,
    taps - 1, D]), from the named arrays and the configuration's own
    keys.  `follow` [Ls, S, k]: the experts each sparse layer applies
    in place of its own choice, where they are not negative.
    `before`: each conv layer's rows before position 0 (zeros, if
    None).  `fault` computes a DIFFERENT model, one of `FAULTS` (but
    "no_reset", which is `before`: `_forward_fault`)."""
    assert fault is None or fault in FAULTS, fault
    assert not config["conv_bias"], "conv_bias true is not written here"
    d = int(config["hidden_size"])
    taps = int(config["conv_L_cache"])
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["norm_eps"])
    attn = dict(n_heads=int(config["num_attention_heads"]),
                n_kv=int(config["num_key_value_heads"]), eps=eps,
                theta=float(config["rope_parameters"]["rope_theta"]),
                norm_after_rope=fault == "norm_after_rope", dtype=dtype)
    scaling = jnp.asarray(config["routed_scaling_factor"], F32)
    sum_eps = jnp.asarray(config["norm_topk_eps"], F32)
    own = np.full((len(ids), top_k), -1, np.int32)
    routed, tails = [], []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"]
                        for k, n in keys.items()}

            if config["layer_types"][l] == CONV:
                x, tail = _conv(
                    x, named(CONV_KEYS),
                    before[len(tails)] if before is not None
                    else jnp.zeros((taps - 1, d), F32), eps=eps,
                    dtype=dtype,
                    fault=fault if fault in ("tail_shifted", "conv_silu",
                                             "gates_exchanged") else None)
                tails.append(tail)
            else:
                x = _attention(x, named(ATTN_KEYS), **attn)
            if l < int(config["num_dense_layers"]):
                x = _dense_ffn(x, named(DENSE_KEYS), eps=eps, dtype=dtype)
                continue
            x, r = _ffn(
                x, named(SPARSE_KEYS),
                jnp.asarray(own if follow is None else follow[len(routed)],
                            jnp.int32), scaling, sum_eps, top_k=top_k,
                eps=eps, renorm=(bool(config["norm_topk_prob"])
                                 and fault != "no_renorm"),
                bias_in_weights=fault == "bias_in_weights", dtype=dtype)
            routed.append(r)
        out = _head(x, states["final_norm.scale_0"],
                    states["tok_embedding.w_0"], eps=eps, dtype=dtype)
    routing = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    routing["tails"] = jnp.stack(tails)
    return out, routing


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


def predecessor(ids, vocab: int):
    """The tokens of the sequence that the `no_reset` fault lets leak:
    a lane's previous occupant, made from the ids themselves."""
    return (np.asarray(ids)[::-1].astype(np.int64) * 7 + 3) % vocab


def _forward_fault(states, config, ids, fault, **kw):
    """`forward` under `fault`; `no_reset` first runs the predecessor
    and starts every conv layer from the rows it left."""
    if fault == "no_reset":
        kw["before"] = forward(
            states, config, predecessor(ids, int(config["vocab_size"])),
            **{k: v for k, v in kw.items() if k != "follow"})[1]["tails"]
        fault = None
    return forward(states, config, ids, fault=fault, **kw)


@jax.jit
def _scores(m, w):
    return jax.nn.sigmoid(m @ w.astype(F32))


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's [S, vocab] logits and its routing (what `forward`
    returns beside the logits, as the system computed it) against this
    reference on the same weights and tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|, the reference following the system's
                      experts: rounding, and every fault but a swap
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed
      late_rms_err    `logits_rms_err` over the second half of the
                      positions alone
      tail_rms_err    where the system gives its lane's tails after the
                      last position (`routing["tails"]`, [conv layers,
                      taps - 1, D]): their distance from this
                      reference's last rows of B * u, by root mean
                      square over the reference's, all conv layers
                      together: a tail kept in fewer bits, shifted, or
                      not the product of THESE two gates
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies
                      (by score + bias, the choice's own measure), and
                      how far its weights lie from the float32 scores
                      renormalised (the 1e-6 in the sum) and scaled,
                      both relative
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
    """
    exp = np.asarray(routing["experts"])
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own_tails, own = np.asarray(own["tails"]), np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own.reshape(-1, exp.shape[-1]))])
    layers = sparse_layers(config)
    with jax.default_matmul_precision("highest"):
        scores = np.stack([np.asarray(_scores(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(layers)]).astype(np.float64)
    bias = np.stack([np.asarray(states[f"layer_{l}.router_bias.b_0"],
                                np.float64) for l in layers])[:, None, :]
    choice = scores + bias
    left_out = choice.copy()
    np.put_along_axis(left_out, exp, -np.inf, -1)
    least = np.take_along_axis(choice, exp, -1).min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / np.abs(least)
    chosen = np.take_along_axis(scores, exp, -1)
    if config["norm_topk_prob"]:
        chosen = chosen / (chosen.sum(-1, keepdims=True)
                           + float(config["norm_topk_eps"]))
    weights = chosen * float(config["routed_scaling_factor"])
    off = np.abs(np.asarray(routing["weights"], np.float64)
                 - weights) / weights

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    out = {"logits_rel_err": float(np.max(np.abs(got - want))
                                   / np.max(np.abs(want))),
           "logits_rms_err": rms(got, want),
           "late_rms_err": rms(got[half:], want[half:]),
           "router_rel_err": float(max(gap.max(), off.max())),
           "routing_agree": float(agree),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "finite": bool(np.isfinite(got).all())}
    if "tails" in routing:
        out["tail_rms_err"] = rms(
            np.asarray(routing["tails"], np.float32), own_tails)
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16 (the tail too), as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids, which=FAULTS) -> dict:
    """`compare`'s numbers for the float32 models of `FAULTS`, as if
    each were the system: the limits have to refuse every one."""
    return {fault: compare(states, config, ids, *_forward_fault(
        states, config, ids, fault)) for fault in which}


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           length=None) -> dict:
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
      early_argmax_agree, early_gap_rms  the same over each request's
                      first 32 delivered tokens alone: where what a
                      lane's previous occupant left in its tails, were
                      it not reset, is nearest

    `length`: every request is run at this many positions (cut, or
    padded with token 0 past its end, which no earlier position sees),
    so that all share one compiled forward pass."""
    agree, gap, early = [], [], []
    for ids, start in requests:
        ids = np.asarray(ids)
        n = len(ids) - 1 if length is None else min(len(ids) - 1, length)
        fed = ids[:n] if length is None else np.concatenate(
            [ids[:n], np.zeros(length - n, ids.dtype)])
        want = np.asarray(_forward_fault(states, config, fed, fault,
                                         dtype=dtype)[0],
                          np.float32)[start - 1:n]
        got = want[np.arange(len(want)), ids[start:n + 1]]
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
