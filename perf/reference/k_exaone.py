"""Plain reference of the K-EXAONE decoder (LG AI Research,
`K-EXAONE-236B-A23B` config.json, `model_type: exaone_moe`): float32
`jax.numpy`, one full forward over one token sequence under a causal or
a banded mask, no cache, no ring, no sort, no batching, every matrix
multiplication at `highest` precision.  It knows nothing of paddle_tpu:
it takes a dict of named arrays under the names the served decoder's
`state_shapes` gives (`layer_<l>.q_proj.w_0`, ...; weights are stored
[in, out], the experts [expert, in, out]) and the configuration's own
keys.

The layer l over tokens x [S, d], from config.json's keys:

  h = RMSNorm(x; g1, rms_norm_eps)
  q = h Wq [S, 64 heads x 128];  k = h Wk, v = h Wv [S, 8 heads x 128];
      no bias.  Per head q_i = RMSNorm_128(q_i; gq), k_j =
      RMSNorm_128(k_j; gk): ONE scale vector of `head_dim` for all
      heads of Q and one for K, before any rotation.
  `layer_types[l]` sliding_attention: rotate-half RoPE over all of
      `head_dim` (`rope_parameters`: default, theta 1e6) on q and k;
      position i sees j with i - sliding_window < j <= i (128 keys,
      itself included).  full_attention: NO rotation, every j <= i.
      Scale 1 / sqrt(head_dim); query head i reads K/V head
      i // (64 / 8).  x = x + attn Wo.
  h2 = RMSNorm(x; g2)
  `mlp_layer_types[l]` dense (layer 0): x = x + (silu(h2 Wg) * (h2
      Wu)) Wd at width `intermediate_size`.  sparse: s = sigmoid(h2 Wr)
      [S, 128 routed]; the chosen k are the k largest of s + b (b
      decides the CHOICE alone; `n_group` 1 and `topk_group` 1 make the
      group limit the identity; a tie goes to the lower index);
      w = s[chosen] / sum(s[chosen]) (`norm_topk_prob`) times
      `routed_scaling_factor`;  x = x + sum over the chosen e HELD here
      of w_e SwiGLU_e(h2) (width `moe_intermediate_size`) +
      SwiGLU_shared(h2) (width `num_shared_experts` x that, weight 1,
      every token).  The experts held are `num_experts` of the
      `num_routed_experts` the router routes over, from
      `first_local_expert`: an assignment to an absent expert adds
      nothing and its weight is NOT shared out (the chip that holds the
      expert adds that part).
  logits = RMSNorm(x; gf) W_head;  untied head, over the rows of the
      vocabulary the configuration holds.

ASSUMED (config.json has no key for them; the family's released code is
the ground, each is one field of the served description, and each has a
fault below that the comparison refuses):
  * pre-norm placement, as written above (EXAONE 4.0 normed each
    sub-block's OUTPUT; `exaone_moe`'s keys are DeepSeek-V3's, whose
    layer is pre-norm);
  * the QK-norm per head, with one scale a projection;
  * no rotation on full layers ("SWA-only RoPE");
  * the router's choice bias b (a checkpoint without one is these
    equations at b = 0; the seeded b is not zero).

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_hidden_layers` and the two lists of
layer kinds are whatever the configuration holds; the multi-token-
prediction module (`num_nextn_predict_layers`) is not computed: the
main layers alone define the next-token distribution.

Memory: the served weights (12 GB of bfloat16) stand beside this, so
nothing here holds a layer's matrices in float32 at once: an expert is
widened as it is applied (a scan over the held experts, each applied
densely to every token and masked by the weights), and the dense
layer's matrices go through the same scan as column blocks of one
expert's width.

What decides `correct` is `compare`, as in `mellum2.py`: the reference
FOLLOWS the system's choice of experts and judges the choice on the
router's own input (`router_rel_err`); positions at and past the window
are read on their own, and `window_edge_share` says how far the
system's logits there have moved towards the model whose window is one
key longer (the error a wrong ring or mask edge makes is one key in 129
and hides under bfloat16 rounding in every other number).  `below` is
the reading one precision down (all bfloat16); `faults` are five
readings a wrong step has to give.  `served` judges what a SERVER
delivered, of which only tokens are known.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SLIDING, FULL = "sliding_attention", "full_attention"
FAULTS = ("full_rope", "bias_in_weights", "no_scaling", "window_129",
          "softmax")


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
    "n_heads", "n_kv", "d_head", "eps", "theta", "rotate", "dtype"))
def _attention(x, p, window, *, n_heads, n_kv, d_head, eps, theta, rotate,
               dtype):
    """x + attention of RMSNorm(x).  `window`: keys a position sees (an
    array: one program for both kinds), 0 for all before it."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    n = _rms(x, p["attn_norm"], eps)
    q = _rms((n @ p["q"]).reshape(s, n_heads, d_head), p["q_norm"], eps)
    k = _rms((n @ p["k"]).reshape(s, n_kv, d_head), p["k_norm"], eps)
    v = (n @ p["v"]).reshape(s, n_kv, d_head)
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    # query head h reads K/V head h // (n_heads / n_kv)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d_head, dtype))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    sees = (j <= i) & ((window == 0) | (j > i - window))
    scores = jnp.where(sees[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return x + ctx.reshape(s, n_heads * d_head) @ p["o"]


@functools.partial(jax.jit, static_argnames=("width", "eps", "dtype"))
def _dense_ffn(x, p, *, width, eps, dtype):
    """x + SwiGLU(RMSNorm(x)) at the dense layer's width, its columns
    in blocks of `width` (the sum over a block is the sum over its
    columns: the same mathematics, a layer's float32 never whole)."""
    m = _rms(x, p["norm"].astype(dtype), eps)
    d, f = p["gate"].shape
    width = width if f % width == 0 else f
    gate, up = (p[n].reshape(d, f // width, width).transpose(1, 0, 2)
                for n in ("gate", "up"))
    down = p["down"].reshape(f // width, width, d)
    ones = jnp.ones((x.shape[0], f // width), dtype)
    return x + _experts(m, gate, up, down, ones, dtype)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "eps", "renorm", "scoring", "bias_in_weights",
    "dtype"))
def _ffn(x, p, follow, scaling, *, top_k, first, eps, renorm=True,
         scoring="sigmoid", bias_in_weights=False, dtype=F32):
    """-> (x + the expert layer of RMSNorm(x), its routing: the
    router's input, the top-k weights and experts of its own choice).
    `p`: "norm", "router" [d, E routed], "bias" [E], "gate", "up",
    "down" [held, ...] (the experts `first` onward) and
    "shared_gate", "shared_up", "shared_down".  `follow` [S, k]: the
    experts to apply instead of its own choice, each weighed by the
    score computed here; a position whose row is negative takes its
    own.  `scaling` (an array): the factor on the weights.  `scoring`
    "softmax" and `bias_in_weights` compute the faults of those
    names."""
    s = x.shape[0]
    m = _rms(x, p["norm"].astype(dtype), eps)
    logits = m @ p["router"].astype(dtype)                    # [S, E]
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, -1))
    choice = scores + p["bias"].astype(dtype)
    _, own_e = jax.lax.top_k(choice, top_k)
    take = choice if bias_in_weights else scores

    def weights_of(experts):
        w = jnp.take_along_axis(take, experts, -1)
        if renorm:
            w = w / w.sum(-1, keepdims=True)
        return w * scaling.astype(dtype)

    use_e = jnp.where(follow < 0, own_e, follow)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(s)[:, None], use_e].set(weights_of(use_e))
    held = p["gate"].shape[0]
    y = _experts(m, p["gate"], p["up"], p["down"],
                 weight[:, first:first + held], dtype)
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


ATTN_KEYS = {"attn_norm": "attn_norm.scale_0", "q": "q_proj.w_0",
             "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0",
             "q_norm": "q_norm.scale_0", "k_norm": "k_norm.scale_0"}
DENSE_KEYS = {"norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
              "up": "ffn_up.w_0", "down": "ffn_down.w_0"}
SPARSE_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
               "bias": "router_bias.b_0", "gate": "experts_gate.w_0",
               "up": "experts_up.w_0", "down": "experts_down.w_0",
               "shared_gate": "shared_gate.w_0",
               "shared_up": "shared_up.w_0",
               "shared_down": "shared_down.w_0"}


def sparse_layers(config: dict) -> list:
    """The layers with experts, in order: what a system's routing is
    stacked over."""
    return [l for l in range(int(config["num_hidden_layers"]))
            if config["mlp_layer_types"][l] == "sparse"]


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every SPARSE layer stacked: "inputs" [Ls, S, D],
    "weights" and "experts" [Ls, S, k]), from the named arrays and the
    configuration's own keys.  `follow` [Ls, S, k]: the experts each
    sparse layer applies in place of its own choice, where they are
    not negative.  `fault` computes a DIFFERENT model, one of `FAULTS`:
    "full_rope" turns the full layers too, "bias_in_weights" weighs
    the chosen by score + bias, "no_scaling" leaves
    `routed_scaling_factor` out, "window_129" lets a sliding layer see
    one key more, "softmax" scores by a softmax over the routed
    experts."""
    assert fault is None or fault in FAULTS, fault
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    attn = dict(n_heads=int(config["num_attention_heads"]),
                n_kv=int(config["num_key_value_heads"]),
                d_head=int(config["head_dim"]), eps=eps,
                theta=float(config["rope_parameters"]["rope_theta"]),
                dtype=dtype)
    window = int(config["sliding_window"]) + (fault == "window_129")
    scaling = jnp.asarray(1.0 if fault == "no_scaling"
                          else config["routed_scaling_factor"], F32)
    own = np.full((len(ids), top_k), -1, np.int32)
    routed = []
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"]
                        for k, n in keys.items()}

            sliding = config["layer_types"][l] == SLIDING
            x = _attention(
                x, named(ATTN_KEYS),
                jnp.asarray(window if sliding else 0, jnp.int32),
                rotate=sliding or fault == "full_rope", **attn)
            if config["mlp_layer_types"][l] == "dense":
                x = _dense_ffn(x, named(DENSE_KEYS), eps=eps, dtype=dtype,
                               width=int(config["moe_intermediate_size"]))
                continue
            x, r = _ffn(
                x, named(SPARSE_KEYS),
                jnp.asarray(own if follow is None else follow[len(routed)],
                            jnp.int32), scaling, top_k=top_k,
                first=int(config["first_local_expert"]), eps=eps,
                renorm=bool(config["norm_topk_prob"]),
                scoring="softmax" if fault == "softmax"
                else config["scoring_func"],
                bias_in_weights=fault == "bias_in_weights", dtype=dtype)
            routed.append(r)
        out = _head(x, states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype)
    return out, {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


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
      past_window_rms_err  `logits_rms_err` over the positions at and
                      past `sliding_window` alone (nothing where the
                      sequence is shorter): where a ring has wrapped
      window_edge_share  over those positions: how far the system's
                      logits have gone from this reference's towards
                      those of the model whose window is ONE key
                      longer, as a share of that step (the projection
                      of got - want on wider - want): 0 for a system
                      with the right window, 1 for one that sees
                      `sliding_window` + 1 keys; rounding adds to it
                      what it adds to any one direction among millions
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies
                      (by score + bias, the choice's own measure), and
                      how far its weights lie from the float32 scores
                      renormalised and scaled, both relative
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
    layers = sparse_layers(config)
    with jax.default_matmul_precision("highest"):
        scores = np.stack([np.asarray(_scores(
            jnp.asarray(routing["inputs"][i], F32),
            states[f"layer_{l}.router.w_0"]))
            for i, l in enumerate(layers)])                   # [Ls, S, E]
    bias = np.stack([np.asarray(states[f"layer_{l}.router_bias.b_0"],
                                np.float32) for l in layers])[:, None, :]
    choice = scores + bias
    left_out = choice.copy()
    np.put_along_axis(left_out, exp, -np.inf, -1)
    least = np.take_along_axis(choice, exp, -1).min(-1)
    gap = np.maximum(0.0, left_out.max(-1) - least) / np.abs(least)
    chosen = np.take_along_axis(scores, exp, -1)
    if config["norm_topk_prob"]:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    weights = chosen * float(config["routed_scaling_factor"])
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
        wider = np.asarray(forward(states, config, ids, follow=exp,
                                   fault="window_129")[0], np.float32)
        step = (wider - want)[w:].astype(np.float64)
        out["window_edge_share"] = float(
            np.sum((got - want)[w:] * step) / np.sum(step * step))
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16, as if that were the system."""
    return compare(states, config, ids,
                   *forward(states, config, ids, dtype=jnp.bfloat16))


def faults(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for the five float32 models of `FAULTS`,
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
