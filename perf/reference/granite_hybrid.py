"""Plain reference of the Granite 4.0-H decoder (IBM,
`ibm-granite/granite-4.0-h-small` config.json, `model_type:
granitemoehybrid`): float32 `jax.numpy`, one full forward over one
token sequence, no cache, no lanes, no sort, no batching, every matrix
multiplication at `highest` precision.  It knows nothing of paddle_tpu:
it takes a dict of named arrays under the names the served decoder's
`state_shapes` gives (`layer_<l>.ssm_in_proj.w_0`, ...; weights are
stored [in, out], the experts [expert, in, out]) and the
configuration's own keys.

The model, from config.json letter for letter (H = `mamba_n_heads`,
P = `mamba_d_head`, N = `mamba_d_state`, one group of B and C,
`mamba_expand` x `hidden_size` = H x P):

  x = E[token] * embedding_multiplier
      no position is added anywhere (`position_embedding_type: nope`);
      the attention layers apply no RoPE
  every layer l, of kind `layer_types[l]`:
    x = x + residual_multiplier * mixer_l(RMSNorm(x))
    h = RMSNorm(x);  x = x + residual_multiplier * (moe(h) + shared(h))
  mixer, "attention": causal grouped-query attention, query head i
      reads K/V head i // (heads / kv heads), no bias, softmax of
      q.k * attention_multiplier (NOT 1 / sqrt(head size))
  mixer, "mamba" (Mamba-2, arXiv:2405.21060), u the normed input:
      [z, xBC, dt] = u W_in     widths H*P, H*P + 2N, H; no bias
      xBC_t = silu(sum_j w_conv[j] * xBC_(t-3+j) + b_conv)
              depthwise, causal, width `mamba_d_conv`, zeros before
              position 0
      x [H, P], B [N], C [N] = xBC_t;  dt = softplus(dt + dt_bias) [H]
      h_t = exp(dt * -exp(A_log)) * h_(t-1) + dt * (x_t outer B_t)
            [H, P, N], h before position 0 is zero
      y_t = h_t . C_t + D * x_t
      out = (RMSNorm(y * silu(z)) * w) W_out
            the norm over all H*P columns (one group); no bias
  moe: logits = h W_r over ALL `num_routed_experts`, float32; the
      `num_experts_per_tok` largest, softmax over THOSE; each chosen
      expert e adds p_e . Wd_e (silu(Wg_e h) * (Wu_e h)), width
      `intermediate_size`; no token dropped, no capacity
  shared: the same SwiGLU once, width `shared_intermediate_size`,
      weight 1, every token
  logits = (RMSNorm(x) E^T) / logits_scaling   (`tie_word_embeddings`)

Departures from the published model: weights are random from the seed,
not the trained checkpoint; `num_hidden_layers` and `layer_types` are
whatever the configuration holds (the benchmark's cut keeps one whole
period); and THE EXPERT SHARE: the configuration holds
`num_local_experts` of the `num_routed_experts` the router routes
over, from `first_local_expert` on (one chip of an expert-parallel
layer).  The router and the top-k are the published ones; an
assignment to an expert that is not held adds nothing HERE, its weight
is not shared out, and that partial result goes on to the next layer,
as in the system.  With every expert held (`first_local_expert` 0 and
all the routed experts' arrays) this is the model whole.

Each expert is applied densely to every token and masked by its
weight, one expert at a time (a scan), so that beside the served
weights only one expert is ever float32.

What decides `correct` is `compare`, as in `olmoe.py` and
`mellum2.py`: the reference FOLLOWS the system's choice of experts
(with random weights the k-th and k+1-th logits often lie closer than
the served bf16 rounding moves them: a swap, not an error) and judges
the choice on the router's own input (`router_rel_err`).  `below` is
the reading one precision down (all bfloat16, the state too); `faults`
are readings a wrong state or a wrong scalar has to give: the state
rounded to bfloat16 at every position, no reset at position 0 (what a
lane's previous occupant left is still there), `D * x` left out, and
1 / sqrt(head size) for the attention multiplier.

`served` judges what a SERVER delivered, of which only tokens are
known: requests it decoded greedily, each teacher-forced through
`forward` (this reference's own experts), each delivered token held
against the logits of the position that produced it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAMBA = "mamba"
FAULTS = ("state_bf16", "no_reset", "no_d_skip", "attention_scale")


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "eps", "dtype", "state_dtype"))
def _mamba(x, p, h0, tail0, d_gain, res_mult, *, heads, d_head, d_state,
           eps, dtype, state_dtype):
    """x [S, D] -> (x + res_mult * mixer(RMSNorm(x)), the state after
    the last position [H, P, N], the last rows of xBC before the
    convolution [width - 1, H*P + 2N], and what the recurrence was
    given: xBC after the convolution and dt after the softplus side by
    side [S, H*P + 2N + H]).  `h0`, `tail0`: what was there
    before position 0 (zeros, but for the `no_reset` fault); `d_gain`
    multiplies D (1, but for `no_d_skip`); `state_dtype` is what the
    state is rounded to after every position."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s, di = x.shape[0], heads * d_head
    zxd = _rms(x, p["norm"], eps) @ p["in"]
    z, xbc, dt = zxd[:, :di], zxd[:, di:-heads], zxd[:, -heads:]
    width = p["conv_w"].shape[0]
    padded = jnp.concatenate([tail0.astype(dtype), xbc], 0)
    conv = sum(p["conv_w"][j] * padded[j:j + s] for j in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[:, :di].reshape(s, heads, d_head)
    b, c = xbc[:, di:di + d_state], xbc[:, di + d_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [S, H]
    h, y = _recurrence(xs, b, c, dt, p["a_log"], h0, state_dtype)
    y = y + (d_gain.astype(dtype) * p["d"])[None, :, None] * xs
    y = _rms(y.reshape(s, di) * jax.nn.silu(z), p["gate_norm"], eps)
    return (x + res_mult.astype(dtype) * (y @ p["out"]), h.astype(F32),
            padded[s:].astype(F32),
            jnp.concatenate([xbc, dt], -1).astype(F32))


def _recurrence(xs, b, c, dt, a_log, h0, state_dtype):
    """h_t = exp(dt_t * -exp(A_log)) * h_(t-1) + dt_t * (x_t outer
    B_t), y_t = h_t . C_t over the positions, in the inputs' dtype, the
    state rounded to `state_dtype` after every position: xs [S, H, P],
    b and c [S, N], dt [S, H] -> (h after the last position [H, P, N],
    y [S, H, P])."""
    dtype = xs.dtype
    decay = jnp.exp(-dt * jnp.exp(a_log))

    def position(h, args):
        decay_t, dtx_t, b_t, c_t = args
        h = (decay_t[:, None, None] * h.astype(dtype)
             + dtx_t[..., None] * b_t[None, None, :])
        return h.astype(state_dtype), (h * c_t[None, None, :]).sum(-1)

    return jax.lax.scan(position, h0.astype(state_dtype),
                        (decay, dt[..., None] * xs, b, c))


@functools.partial(jax.jit, static_argnames=("heads", "d_head", "d_state"))
def _state_of(given, a_log, *, heads, d_head, d_state):
    """The float32 state after the last position of a recurrence that
    was given `given` [S, H*P + 2N + H] (`_mamba`'s last result, or a
    system's own), from a zero state."""
    di = heads * d_head
    return _recurrence(
        given[:, :di].reshape(-1, heads, d_head),
        given[:, di:di + d_state], given[:, di + d_state:-heads],
        given[:, -heads:], a_log.astype(F32),
        jnp.zeros((heads, d_head, d_state), F32), F32)[0]


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps",
                                             "dtype"))
def _attention(x, p, scale, res_mult, *, n_heads, n_kv, eps, dtype):
    """x [S, D] -> x + res_mult * causal GQA(RMSNorm(x)), scores times
    `scale`, no position signal."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    s = x.shape[0]
    n = _rms(x, p["norm"], eps)
    q = (n @ p["q"]).reshape(s, n_heads, -1)
    k = jnp.repeat((n @ p["k"]).reshape(s, n_kv, -1), n_heads // n_kv, 1)
    v = jnp.repeat((n @ p["v"]).reshape(s, n_kv, -1), n_heads // n_kv, 1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale.astype(dtype)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return x + res_mult.astype(dtype) * (ctx.reshape(s, -1) @ p["o"])


@functools.partial(jax.jit, static_argnames=("top_k", "first", "eps",
                                             "dtype"))
def _ffn(x, p, follow, res_mult, *, top_k, first, eps, dtype):
    """x [S, D] -> (x + res_mult * (moe(h) + shared(h)), the routing:
    the router's input h, the top-k weights and experts of its own
    choice).  The experts' arrays are those HELD, `first` the index of
    the first among the routed.  `follow` [S, k]: the experts to weigh
    (each by the softmax over the followed logits) instead of its own
    choice; a position whose row is negative takes its own."""
    experts = [p[k] for k in ("gate", "up", "down")]    # cast one by one
    p = {k: v.astype(dtype) for k, v in p.items()
         if k not in ("gate", "up", "down")}
    s = x.shape[0]
    m = _rms(x, p["norm"], eps)
    logits = m @ p["router"]                                  # [S, E]
    own_l, own_e = jax.lax.top_k(logits, top_k)
    own_w = jax.nn.softmax(own_l, -1)
    use_e = jnp.where(follow < 0, own_e, follow)
    use_w = jax.nn.softmax(jnp.take_along_axis(logits, use_e, -1), -1)
    weight = jnp.zeros_like(logits).at[
        jnp.arange(s)[:, None], use_e].set(use_w)             # [S, E]
    weight = weight[:, first:first + experts[0].shape[0]]

    def expert(y, args):
        gate, up, down = (w.astype(dtype) for w in args[:3])
        return y + args[3][:, None] * (
            (jax.nn.silu(m @ gate) * (m @ up)) @ down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(m),
                        (*experts, weight.T))
    y = y + (jax.nn.silu(m @ p["shared_gate"])
             * (m @ p["shared_up"])) @ p["shared_down"]
    routing = {"inputs": m.astype(F32), "weights": own_w.astype(F32),
               "experts": own_e}
    return x + res_mult.astype(dtype) * y, routing


HEAD_BLOCKS = 16


@functools.partial(jax.jit, static_argnames=("eps", "dtype"))
def _head_block(x, scale, rows, divide, *, eps, dtype):
    return ((_rms(x, scale.astype(dtype), eps) @ rows.astype(dtype).T)
            / divide.astype(dtype)).astype(F32)


def _head(x, scale, emb, divide, *, eps, dtype):
    """RMSNorm(x) E^T / logits_scaling -> [S, vocab] float32 on the
    HOST, a block of the vocabulary's rows at a time: the whole
    embedding in float32 is 1.6 GB and the logits of 512 positions 0.2
    GB a copy, beside the served weights and the lanes' states."""
    size = -(-emb.shape[0] // HEAD_BLOCKS)
    return np.concatenate([np.asarray(_head_block(
        x, scale, emb[i:i + size], divide, eps=eps, dtype=dtype))
        for i in range(0, emb.shape[0], size)], axis=1)


MAMBA_KEYS = {"norm": "mixer_norm.scale_0", "in": "ssm_in_proj.w_0",
              "conv_w": "ssm_conv.w_0", "conv_b": "ssm_conv.b_0",
              "dt_bias": "ssm_dt.b_0", "a_log": "ssm_a_log.w_0",
              "d": "ssm_d.w_0", "gate_norm": "ssm_gate_norm.scale_0",
              "out": "ssm_out_proj.w_0"}
ATTENTION_KEYS = {"norm": "attn_norm.scale_0", "q": "q_proj.w_0",
                  "k": "k_proj.w_0", "v": "v_proj.w_0", "o": "o_proj.w_0"}
FFN_KEYS = {"norm": "ffn_norm.scale_0", "router": "router.w_0",
            "gate": "experts_gate.w_0", "up": "experts_up.w_0",
            "down": "experts_down.w_0", "shared_gate": "shared_gate.w_0",
            "shared_up": "shared_up.w_0", "shared_down": "shared_down.w_0"}


def forward(states: dict, config: dict, ids, follow=None, dtype=F32,
            fault=None, before=None):
    """[S] token ids -> ([S, vocab] float32 next-token logits, the
    routing of every layer stacked: "inputs" [L, S, D], "weights" and
    "experts" [L, S, k], each Mamba layer's (state, tail) after the
    last position under "after" and what its recurrence was given
    under "ssm_inputs" [Mamba layers, S, H*P + 2N + H]), from the named
    arrays and the configuration's own keys.  `follow` [L, S, k]: the experts each layer weighs in
    place of its own choice, where they are not negative.  `before`: each Mamba layer's (state,
    tail) before position 0 (zeros, if None).  `fault` computes a
    DIFFERENT model, for the readings `faults` gives."""
    assert fault in (None,) + FAULTS, fault
    heads, d_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    d_state, width = int(config["mamba_d_state"]), int(config["mamba_d_conv"])
    assert int(config["mamba_n_groups"]) == 1
    assert heads * d_head == int(config["mamba_expand"]) * int(
        config["hidden_size"])
    n_heads = int(config["num_attention_heads"])
    top_k = int(config["num_experts_per_tok"])
    eps = float(config["rms_norm_eps"])
    first = int(config["first_local_expert"])
    scale = (1.0 / math.sqrt(int(config["hidden_size"]) // n_heads)
             if fault == "attention_scale"
             else float(config["attention_multiplier"]))
    res = jnp.asarray(config["residual_multiplier"], F32)
    own = np.full((len(ids), top_k), -1, np.int32)
    conv = heads * d_head + 2 * d_state
    routed, after, givens = [], [], []
    with jax.default_matmul_precision("highest"):
        x = (states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
             * jnp.asarray(config["embedding_multiplier"], dtype))
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"] for k, n in keys.items()}

            if config["layer_types"][l] == MAMBA:
                h0, tail0 = (before[len(after)] if before is not None
                             else (jnp.zeros((heads, d_head, d_state), F32),
                                   jnp.zeros((width - 1, conv), F32)))
                x, h, tail, given = _mamba(
                    x, named(MAMBA_KEYS), h0, tail0,
                    jnp.asarray(0.0 if fault == "no_d_skip" else 1.0, F32),
                    res, heads=heads, d_head=d_head, d_state=d_state,
                    eps=eps, dtype=dtype,
                    state_dtype=(jnp.bfloat16 if fault == "state_bf16"
                                 else dtype))
                after.append((h, tail))
                givens.append(given)
            else:
                x = _attention(
                    x, named(ATTENTION_KEYS), jnp.asarray(scale, F32), res,
                    n_heads=n_heads,
                    n_kv=int(config["num_key_value_heads"]), eps=eps,
                    dtype=dtype)
            x, r = _ffn(x, named(FFN_KEYS),
                        jnp.asarray(own if follow is None else follow[l],
                                    jnp.int32),
                        res, top_k=top_k, first=first, eps=eps, dtype=dtype)
            routed.append(r)
        out = _head(x, states["final_norm.scale_0"],
                    states["tok_embedding.w_0"],
                    jnp.asarray(config["logits_scaling"], F32), eps=eps,
                    dtype=dtype)
    routing = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    routing["after"] = after
    routing["ssm_inputs"] = jnp.stack(givens)
    return out, routing


def logits(states: dict, config: dict, ids):
    return forward(states, config, ids)[0]


def predecessor(ids, vocab: int):
    """The tokens of the sequence that the `no_reset` fault lets leak:
    a lane's previous occupant, made from the ids themselves."""
    return (np.asarray(ids)[::-1].astype(np.int64) * 7 + 3) % vocab


def _forward_fault(states, config, ids, fault, **kw):
    """`forward` under `fault`; `no_reset` first runs the predecessor
    and starts every Mamba layer from what it left."""
    if fault == "no_reset":
        kw["before"] = forward(
            states, config, predecessor(ids, int(config["vocab_size"])),
            **{k: v for k, v in kw.items() if k != "follow"})[1]["after"]
        fault = None
    return forward(states, config, ids, fault=fault, **kw)


@jax.jit
def _router(m, w):
    return m @ w.astype(F32)


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
                      positions alone: where a state that decays or
                      rounds wrongly has drifted furthest
      state_rms_err   where the system gives its lane's SSM states
                      after the last position (`routing["state"]`, one
                      [H, P, N] a Mamba layer): their distance from
                      this reference's, by root mean square over the
                      reference's, all Mamba layers together.  What
                      the logits cannot tell from the served matmuls'
                      own rounding: a state kept in fewer bits, whose
                      rounding at every position adds up in the state
                      itself
      router_rel_err  on the system's own router inputs: how far below
                      an expert it left out its least chosen one lies
                      (as probabilities: exp of the logits' distance,
                      less 1), and how far its weights lie from the
                      float32 softmax over its chosen logits, relative
      scan_rel_err    where it also gives what each Mamba layer's
                      recurrence was given at every position
                      (`routing["ssm_inputs"]`): the distance of its
                      state from the float32 recurrence over ITS OWN
                      inputs from a zero state, by root mean square
                      over that state's, the largest of the layers.
                      As the router is judged on its own input: the
                      upstream rounding cancels, and what is left is
                      the recurrence itself: a state kept in fewer
                      bits, a lane that did not start from zero
      routing_agree   share of its assignments that the reference,
                      following it, would have made too: the near-ties
    """
    exp = np.asarray(routing["experts"])
    want, own = forward(states, config, ids, follow=exp)
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    own_after, own = own["after"], np.asarray(own["experts"])
    agree = np.mean([len(set(a) & set(b)) / len(a)
                     for a, b in zip(exp.reshape(-1, exp.shape[-1]),
                                     own.reshape(-1, exp.shape[-1]))])
    with jax.default_matmul_precision("highest"):
        lg = np.stack([np.asarray(_router(
            jnp.asarray(routing["inputs"][l], F32),
            states[f"layer_{l}.router.w_0"]))
            for l in range(exp.shape[0])]).astype(np.float64)  # [L, S, E]
    chosen = np.take_along_axis(lg, exp, -1)
    left_out = lg.copy()
    np.put_along_axis(left_out, exp, -np.inf, -1)
    gap = np.maximum(0.0, np.expm1(left_out.max(-1) - chosen.min(-1)))
    weights = np.exp(chosen - chosen.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    off = np.abs(np.asarray(routing["weights"], np.float64)
                 - weights) / weights

    def rms(a, b):
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(got) // 2
    state = {}
    if "state" in routing:
        ref_h = np.stack([np.asarray(h) for h, _ in own_after])
        state["state_rms_err"] = rms(
            np.asarray(routing["state"], np.float32), ref_h)
    if "state" in routing and "ssm_inputs" in routing:
        dims = dict(heads=int(config["mamba_n_heads"]),
                    d_head=int(config["mamba_d_head"]),
                    d_state=int(config["mamba_d_state"]))
        mamba = [l for l, kind in enumerate(config["layer_types"][
            :int(config["num_hidden_layers"])]) if kind == MAMBA]
        state["scan_rel_err"] = max(
            rms(np.asarray(routing["state"][i], np.float32), np.asarray(
                _state_of(jnp.asarray(routing["ssm_inputs"][i], F32),
                          states[f"layer_{l}.ssm_a_log.w_0"], **dims)))
            for i, l in enumerate(mamba))
    return {**state, "logits_rel_err": float(np.max(np.abs(got - want))
                                    / np.max(np.abs(want))),
            "logits_rms_err": rms(got, want),
            "late_rms_err": rms(got[half:], want[half:]),
            "router_rel_err": float(max(gap.max(), off.max())),
            "routing_agree": float(agree),
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "finite": bool(np.isfinite(got).all())}


def _as_system(out):
    """`forward`'s result as the (logits, routing) a system hands to
    `compare`."""
    logits_, routing = out
    return logits_, {
        "state": np.stack([np.asarray(h) for h, _ in routing["after"]]),
        **{k: routing[k] for k in ("inputs", "weights", "experts",
                                   "ssm_inputs")}}


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16 (the state too), as if that were the system."""
    return compare(states, config, ids, *_as_system(
        forward(states, config, ids, dtype=jnp.bfloat16)))


def faults(states: dict, config: dict, ids, which=FAULTS) -> dict:
    """`compare`'s numbers for float32 models that a wrong state or a
    wrong scalar would compute, as if each were the system: the limits
    have to refuse every one."""
    return {fault: compare(states, config, ids, *_as_system(
        _forward_fault(states, config, ids, fault))) for fault in which}


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           length=None) -> dict:
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
      early_argmax_agree, early_gap_rms  the same over each request's
                      first 32 delivered tokens alone: where what a
                      lane's previous occupant left, were it not
                      reset, has decayed least

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
