"""Plain reference of the Falcon-H1 decoder (TII,
`tiiuae/Falcon-H1-34B-Instruct` config.json, `model_type: falcon_h1`):
float32 `jax.numpy`, one full forward over one token sequence, no cache,
no lanes, no batching, every matrix multiplication at `highest`
precision.  It knows nothing of paddle_tpu: it takes a dict of named
arrays under the names the served decoder's `state_shapes` gives
(`layer_<l>.ssm_in_proj.w_0`, ...; weights are stored [in, out]) and the
configuration's own keys.

The model, from config.json's keys and the published `falcon_h1` model
code as the author of ISSUE 69 read it; EACH READING OF A KEY is noted
here and listed under `assumed` in the configuration file (H =
`mamba_n_heads`, P = `mamba_d_head`, G = `mamba_n_groups`, N =
`mamba_d_state`; every norm an RMSNorm with a scale, `rms_norm_eps`; no
bias but the convolution's):

  x = E[token] * embedding_multiplier          no position is added
  every layer (all alike: `attn_layer_indices` null, `mamba_use_mlp`):
    u = RMSNorm_1(x)                   ONE norm for BOTH mixers (read:
                                       `input_layernorm`; a second norm
                                       for the attention is a fault)
    x = x + ssm_out_multiplier * mamba(ssm_in_multiplier * u)
          + attention_out_multiplier * attn(attention_in_multiplier * u)
                                       side by side (read: the mixers
                                       in sequence is a fault)
    x = x + mlp(RMSNorm_2(x))          `pre_ff_layernorm`
  mamba(v), Mamba-2 (arXiv:2405.21060):
    [z | xBC | dt] = (v W_in) * m      widths H*P (`mamba_d_ssm`, read
                                       OVER `mamba_expand` x hidden),
                                       H*P + 2*G*N, H; m the muP vector
                                       `ssm_multipliers` by segment: [0]
                                       on z, [1] on x, [2] on B, [3] on
                                       C, [4] on dt (read: on the
                                       projection's RESULT, so x, B, C
                                       carry theirs INTO the convolution)
    xBC_t = silu(sum_j w_conv[j] * xBC_(t-3+j) + b_conv)
                                       depthwise, causal, width
                                       `mamba_d_conv`, zeros before 0
    dt = softplus(dt + dt_bias) [H];   g(h) = h // (H / G)  (read: a
                                       group is H / G consecutive heads)
    S_h = exp(-dt_h exp(A_log_h)) S_h + dt_h (x_h outer B_g(h))
                                       [P, N], zero before position 0
    y_h = S_h C_g(h) + D_h x_h
    y = RMSNorm_by_group(y * silu(z)) * w      `mamba_rms_norm` true,
                                       `mamba_norm_before_gate` false:
                                       the gate FIRST, then the norm,
                                       over each group's H*P/G columns
                                       apart (read), one scale [H*P]
    out = y W_out
  attn(v): `num_attention_heads` query heads over
    `num_key_value_heads` K/V heads of `head_dim`; k = (v W_k) *
    key_multiplier BEFORE RoPE (read); RoPE (rotate-half) on q and k at
    `rope_theta`, `rope_scaling` null; scores * head_dim**-0.5, causal
  mlp(w) = ((w W_up) * silu((w W_gate) * mlp_multipliers[0])) W_down
           * mlp_multipliers[1]        width `intermediate_size`
  logits = (RMSNorm(x) W_head) * lm_head_multiplier     untied head

`mamba_chunk_size`, `mlp_expansion_factor` and `num_logits_to_keep` are
read by nothing here.  Departures from the published model: weights are
random from the seed, and `num_hidden_layers` is what the configuration
holds (the benchmark's cut keeps the first five).

What decides `correct` is `compare` (one sequence walked through the
system: logits at the positions the system kept, each layer's state and
tail after the last position, the recurrence judged on ITS OWN inputs,
the K and V rows of the table) and `served` (tokens a server delivered,
each held against this reference's logits at the position that sampled
it).  `below` is the reading one precision down (all bfloat16, the state
too); `FAULTS` are readings a wrong reading of a key, a wrong state or a
wrong snapshot has to give, each of which the limits must refuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 16          # positions a block of the table (the snapshot faults)
FAULTS = (
    "no_mamba", "no_attention", "sequential", "second_norm", "one_group",
    "groups_swapped", "norm_all_columns", "norm_before_gate",
    "no_mup_vector", "mup_after_conv", "mup_bc_swapped", "no_ssm_in",
    "no_ssm_out", "no_attention_out", "out_swapped", "no_key_multiplier",
    "key_multiplier_on_scores", "rope_theta_1e4", "no_rope", "mlp_swapped",
    "no_lm_head_multiplier", "no_embedding_multiplier", "no_d_skip",
    "no_reset", "state_bf16", "wrong_snapshot", "shifted_blocks")
MAMBA_FAULTS = ("one_group", "groups_swapped", "norm_all_columns",
                "norm_before_gate", "no_mup_vector", "mup_after_conv",
                "mup_bc_swapped", "no_d_skip", "state_bf16")


def _rms(x, scale, eps):
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + jnp.asarray(eps, x.dtype)) * scale


def _recurrence(xs, b, c, dt, a_log, h0, state_dtype, swap=None):
    """S_t = exp(-dt_t exp(A_log)) S_(t-1) + dt_t (x_t outer B_t), y_t =
    S_t C_t over the positions, a plain scan, in the inputs' dtype, the
    state rounded to `state_dtype` after every position: xs [S, H, P], b
    and c [S, H, N] (a head's group's), dt [S, H] -> (the state after
    the last position and the state that position found, both [H, P,
    N]; y [S, H, P]).  `swap` (at [S] bool, a state): the state BEFORE
    the position where `at` is true is that one (a snapshot restored
    there)."""
    dtype = xs.dtype
    decay = jnp.exp(-dt * jnp.exp(a_log))

    def position(carry, args):
        h, _ = carry
        decay_t, dtx_t, b_t, c_t, at_t = args
        if swap is not None:
            h = jnp.where(at_t, swap[1].astype(state_dtype), h)
        new = (decay_t[:, None, None] * h.astype(dtype)
               + dtx_t[..., None] * b_t[:, None, :])
        return (new.astype(state_dtype), h), (new * c_t[:, None, :]).sum(-1)

    at = (jnp.zeros(xs.shape[0], bool) if swap is None else swap[0])
    h0 = h0.astype(state_dtype)
    return jax.lax.scan(position, (h0, h0),
                        (decay, dt[..., None] * xs, b, c, at))


def _by_group(y, groups, eps):
    """RMS-normalise y [S, C] over each of `groups` equal runs of its
    columns apart (no scale)."""
    s, c = y.shape
    return _rms(y.reshape(s, groups, c // groups),
                jnp.asarray(1.0, y.dtype), eps).reshape(s, c)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "groups", "eps", "mults", "dtype",
    "state_dtype", "fault", "cut"))
def _mamba(v, p, h0, tail0, restored, *, heads, d_head, d_state, groups,
           eps, mults, dtype, state_dtype, fault=None, cut=0):
    """v [S, D] (the mixer's input: the layer's normed input times
    `ssm_in_multiplier`) -> (mamba(v) [S, D], the state after the last
    position and the state that position found, stacked [2, H, P, N],
    the last rows of xBC before the convolution [width - 1, H*P + 2GN],
    what the recurrence was given: xBC after the convolution and dt
    after the softplus side by side [S, H*P + 2GN + H]).  `h0`, `tail0`:
    what was there before position 0 (zeros, but for `no_reset`);
    `restored` (state, tail) or None: what stands in
    their place from position `cut` on (the `wrong_snapshot` fault);
    `mults` the muP vector's five factors; `fault` one of
    `MAMBA_FAULTS`."""
    p = {k: w.astype(dtype) for k, w in p.items()}
    s, di, gn = v.shape[0], heads * d_head, groups * d_state
    m_z, m_x, m_b, m_c, m_dt = (
        (1.0,) * 5 if fault == "no_mup_vector" else
        (mults[0], mults[1], mults[3], mults[2], mults[4])
        if fault == "mup_bc_swapped" else mults)
    late = fault == "mup_after_conv"
    segments = np.repeat(np.asarray(
        [m_z, 1.0 if late else m_x, 1.0 if late else m_b,
         1.0 if late else m_c, m_dt], np.float32), (di, di, gn, gn, heads))
    zxd = (v @ p["in"]) * jnp.asarray(segments, dtype)
    z, xbc, dt = zxd[:, :di], zxd[:, di:-heads], zxd[:, -heads:]
    width = p["conv_w"].shape[0]

    def convolved(before, rows):
        padded = jnp.concatenate([before.astype(dtype), rows], 0)
        return sum(p["conv_w"][j] * padded[j:j + len(rows)]
                   for j in range(width)), padded[len(rows):]

    conv, tail = convolved(tail0, xbc)
    swap = None
    if restored is not None:
        # from `cut` on the rows before are the restored tail's
        late_conv, tail = convolved(restored[1], xbc[cut:])
        conv = jnp.concatenate([conv[:cut], late_conv], 0)
        swap = (jnp.arange(s) == cut, restored[0])
    xbc = jax.nn.silu(conv + p["conv_b"])
    if late:
        xbc = xbc * jnp.asarray(np.repeat(np.asarray(
            [mults[1], mults[2], mults[3]], np.float32), (di, gn, gn)),
            dtype)
    xs = xbc[:, :di].reshape(s, heads, d_head)
    group_of = np.arange(heads) // (heads // groups)
    if fault == "one_group":
        group_of = np.zeros(heads, np.int64)
    if fault == "groups_swapped":
        group_of = groups - 1 - group_of
    b = xbc[:, di:di + gn].reshape(s, groups, d_state)[:, group_of]
    c = xbc[:, di + gn:].reshape(s, groups, d_state)[:, group_of]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [S, H]
    hs, y = _recurrence(xs, b, c, dt, p["a_log"], h0, state_dtype, swap)
    if fault != "no_d_skip":
        y = y + p["d"][None, :, None] * xs
    y, gate = y.reshape(s, di), jax.nn.silu(z)
    if fault == "norm_before_gate":
        y = _by_group(y, groups, eps) * p["gate_norm"] * gate
    elif fault == "norm_all_columns":
        y = _rms(y * gate, p["gate_norm"], eps)
    else:
        y = _by_group(y * gate, groups, eps) * p["gate_norm"]
    return (y @ p["out"], jnp.stack(hs).astype(F32), tail.astype(F32),
            jnp.concatenate([xbc, dt], -1).astype(F32))


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "groups"))
def _step_of(before, given, a_log, *, heads, d_head, d_state, groups):
    """The float32 state ONE position of the recurrence leaves that
    found the state `before` [H, P, N] and was given `given` [H*P + 2GN
    + H] (a row of `_mamba`'s last result, or a system's own)."""
    di, gn = heads * d_head, groups * d_state
    group_of = np.arange(heads) // (heads // groups)
    given = given[None]
    return _recurrence(
        given[:, :di].reshape(1, heads, d_head),
        given[:, di:di + gn].reshape(1, groups, d_state)[:, group_of],
        given[:, di + gn:-heads].reshape(1, groups, d_state)[:, group_of],
        given[:, -heads:], a_log.astype(F32), before.astype(F32), F32)[0][0]


def _rope(x, theta):
    """x [S, H, Dh] at positions 0..S-1, rotate-half: column j and
    column j + Dh/2 of a head turn by position * theta**(-2j/Dh)."""
    s, _, dh = x.shape
    inv = jnp.asarray(
        [float(theta) ** (-2.0 * i / dh) for i in range(dh // 2)], F32)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., dh // 2:], x[..., : dh // 2]], -1)
    return (x * jnp.cos(ang).astype(x.dtype)
            + turned * jnp.sin(ang).astype(x.dtype))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "theta", "key_multiplier", "score_multiplier",
    "dtype", "shift"))
def _attention(v, p, *, n_heads, n_kv, theta, key_multiplier,
               score_multiplier=1.0, dtype=F32, shift=0):
    """v [S, D] (the attention's input) -> (attn(v) [S, D], the K rows
    and the V rows a cache would hold [S, n_kv * Dh] each): causal
    grouped-query attention as a masked product, a block of query rows
    at a time.  `theta` None: no RoPE; `score_multiplier` on the scores
    beside head_dim**-0.5; `shift` > 0: the keys and values of the first
    `shift` positions stand one block (`BLOCK` rows) off (the
    `shifted_blocks` fault)."""
    p = {k: w.astype(dtype) for k, w in p.items()}
    s = v.shape[0]
    q = (v @ p["q"]).reshape(s, n_heads, -1)
    k = ((v @ p["k"]) * jnp.asarray(key_multiplier, dtype)).reshape(
        s, n_kv, -1)
    val = (v @ p["v"]).reshape(s, n_kv, -1)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    if shift:
        k, val = (jnp.concatenate([jnp.roll(t[:shift], -BLOCK, 0),
                                   t[shift:]], 0) for t in (k, val))
    rows = k.reshape(s, -1), val.reshape(s, -1)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    val = jnp.repeat(val, n_heads // n_kv, axis=1)
    scale = jnp.asarray(q.shape[-1] ** -0.5 * score_multiplier, dtype)
    size = math.gcd(s, 512)

    def block(i):
        at = i * size + jnp.arange(size)
        scores = jnp.einsum("qhd,khd->hqk", q[at], k) * scale
        scores = jnp.where(jnp.arange(s)[None, None, :]
                           <= at[None, :, None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), val)

    ctx = jax.lax.map(block, jnp.arange(s // size)).reshape(s, -1)
    return ctx @ p["o"], rows[0].astype(F32), rows[1].astype(F32)


MLP_PARTS = 8


@functools.partial(jax.jit, static_argnames=("eps", "mults", "dtype"))
def _mlp(x, p, *, eps, mults, dtype):
    """x + mlp(RMSNorm_2(x)), the gate input times mults[0] before the
    SiLU and the result times mults[1]; `MLP_PARTS` runs of the
    intermediate columns one after the other (a sum over them), each
    widened to `dtype` as it is used: the three matrices whole in
    float32 are 1.3 GB at the published widths, and the activations of
    6016 positions as much again."""
    w = _rms(x, p["norm"].astype(dtype), eps)
    width = p["up"].shape[1]
    parts = MLP_PARTS if width % MLP_PARTS == 0 else 1
    size = width // parts

    def part(acc, i):
        def run(name, axis):
            return jax.lax.dynamic_slice_in_dim(
                p[name], i * size, size, axis).astype(dtype)

        act = (w @ run("up", 1)) * jax.nn.silu(
            (w @ run("gate", 1)) * jnp.asarray(mults[0], dtype))
        return acc + act @ run("down", 0), None

    y, _ = jax.lax.scan(part, jnp.zeros_like(x), jnp.arange(parts))
    return x + y * jnp.asarray(mults[1], dtype)


HEAD_BLOCKS = 16


@functools.partial(jax.jit, static_argnames=("eps", "multiplier", "dtype"))
def _head_block(x, scale, columns, *, eps, multiplier, dtype):
    return ((_rms(x, scale.astype(dtype), eps) @ columns.astype(dtype))
            * jnp.asarray(multiplier, dtype)).astype(F32)


def _head(x, scale, head, *, eps, multiplier, dtype):
    """(RMSNorm(x) W_head) * lm_head_multiplier -> [rows, vocab] float32
    on the HOST, a block of the vocabulary's columns at a time: the
    whole head in float32 is 5.3 GB at the published widths, beside the
    served weights."""
    size = -(-head.shape[1] // HEAD_BLOCKS)
    return np.concatenate([np.asarray(_head_block(
        x, scale, head[:, i:i + size], eps=eps, multiplier=multiplier,
        dtype=dtype)) for i in range(0, head.shape[1], size)], axis=1)


MAMBA_KEYS = {"in": "ssm_in_proj.w_0", "conv_w": "ssm_conv.w_0",
              "conv_b": "ssm_conv.b_0", "dt_bias": "ssm_dt.b_0",
              "a_log": "ssm_a_log.w_0", "d": "ssm_d.w_0",
              "gate_norm": "ssm_gate_norm.scale_0",
              "out": "ssm_out_proj.w_0"}
ATTENTION_KEYS = {"q": "q_proj.w_0", "k": "k_proj.w_0", "v": "v_proj.w_0",
                  "o": "o_proj.w_0"}
MLP_KEYS = {"norm": "ffn_norm.scale_0", "gate": "ffn_gate.w_0",
            "up": "ffn_up.w_0", "down": "ffn_down.w_0"}


def dims(config: dict) -> dict:
    """The Mamba mixer's sizes from the configuration's own keys."""
    out = dict(heads=int(config["mamba_n_heads"]),
               d_head=int(config["mamba_d_head"]),
               d_state=int(config["mamba_d_state"]),
               groups=int(config["mamba_n_groups"]))
    assert out["heads"] * out["d_head"] == int(config["mamba_d_ssm"])
    assert out["heads"] % out["groups"] == 0
    return out


def cut_of(n: int) -> int:
    """Where the snapshot faults act on a sequence of n positions: the
    last block boundary with 136 positions or more after it (a document's
    end before a question and an answer; the comparison's late positions
    lie right behind it), two blocks in at the least."""
    return max(2 * BLOCK, (n - 136) // BLOCK * BLOCK)


def compared_positions(config: dict, n: int):
    """The positions of a walk of n whose logits a system keeps and
    `compare` reads: the first `compare.positions.early` and the last
    `compare.positions.late` (all of them where the configuration says
    nothing: the whole vocabulary at every position of a long walk is
    gigabytes)."""
    want = config.get("compare", {}).get("positions")
    if not want or want["early"] + want["late"] >= n:
        return np.arange(n)
    return np.concatenate([np.arange(int(want["early"])),
                           np.arange(n - int(want["late"]), n)])


def predecessor(ids, vocab: int):
    """ANOTHER sequence of the same length, made from the ids
    themselves: a lane's previous occupant (`no_reset`), the wrong
    document (`wrong_snapshot`)."""
    return (np.asarray(ids)[::-1].astype(np.int64) * 7 + 3) % vocab


def forward(states: dict, config: dict, ids, dtype=F32, fault=None,
            cut=None, logits_from: int = 0, logits_at=None, before=None):
    """[S] token ids -> (float32 next-token logits [rows, vocab] of the
    positions `logits_at` (an index array), or of `logits_from` onward,
    on the host; what the pass left: "state" [L, H, P, N] and "tails"
    [L, width - 1, H*P + 2GN] after the last position, "state_before"
    [L, H, P, N] (the states the last position found), "ssm_inputs" [L,
    S, H*P + 2GN + H] (what each layer's recurrence was given),
    "ssm_states" (what it left at the last position: "state" again) and
    "k_rows", "v_rows" [L, S, n_kv * Dh] (what a cache would hold)),
    from the named arrays and the configuration's own keys.  `before`:
    each layer's (state, tail) before position 0 (zeros, if None).
    `fault` computes a DIFFERENT model, one of `FAULTS`, the snapshot
    faults at position `cut` (`cut_of`, if None)."""
    assert fault is None or fault in FAULTS, fault
    d = dims(config)
    eps = float(config["rms_norm_eps"])
    width = int(config["mamba_d_conv"])
    conv = d["heads"] * d["d_head"] + 2 * d["groups"] * d["d_state"]
    mults = tuple(float(m) for m in config["ssm_multipliers"])
    ssm_in, ssm_out, attn_in, attn_out = (
        float(config[k]) for k in (
            "ssm_in_multiplier", "ssm_out_multiplier",
            "attention_in_multiplier", "attention_out_multiplier"))
    if fault == "no_ssm_in":
        ssm_in = 1.0
    if fault == "no_ssm_out":
        ssm_out = 1.0
    if fault == "no_attention_out":
        attn_out = 1.0
    if fault == "out_swapped":
        ssm_out, attn_out = attn_out, ssm_out
    key_mult = float(config["key_multiplier"])
    attention = dict(
        n_heads=int(config["num_attention_heads"]),
        n_kv=int(config["num_key_value_heads"]),
        theta=(None if fault == "no_rope" else 1e4
               if fault == "rope_theta_1e4" else float(config["rope_theta"])),
        key_multiplier=(1.0 if fault in ("no_key_multiplier",
                                         "key_multiplier_on_scores")
                        else key_mult),
        score_multiplier=(key_mult * key_mult
                          if fault == "key_multiplier_on_scores" else 1.0),
        dtype=dtype)
    mlp_mults = tuple(float(m) for m in config["mlp_multipliers"])
    if fault == "mlp_swapped":
        mlp_mults = mlp_mults[::-1]
    cut = cut_of(len(ids)) if cut is None else int(cut)
    restored = None
    if fault == "wrong_snapshot":
        # every layer's state and tail after `cut` positions of ANOTHER
        # document: what a snapshot taken of it would restore
        other = forward(states, config, predecessor(
            ids, int(config["vocab_size"]))[:cut], dtype=dtype,
            logits_from=cut)[1]
        restored = list(zip(other["state"], other["tails"]))
    if fault == "no_reset" and before is None:
        other = forward(states, config, predecessor(
            ids, int(config["vocab_size"])), dtype=dtype,
            logits_from=len(ids))[1]
        before = list(zip(other["state"], other["tails"]))
    left = {k: [] for k in ("state", "state_before", "tails", "ssm_inputs",
                            "k_rows", "v_rows")}
    with jax.default_matmul_precision("highest"):
        x = states["tok_embedding.w_0"][jnp.asarray(ids)].astype(dtype)
        if fault != "no_embedding_multiplier":
            x = x * jnp.asarray(config["embedding_multiplier"], dtype)
        for l in range(int(config["num_hidden_layers"])):
            def named(keys):
                return {k: states[f"layer_{l}.{n}"] for k, n in keys.items()}

            def norm_1(x, name="mixer_norm.scale_0"):
                return _rms(x, states[f"layer_{l}.{name}"].astype(dtype),
                            eps)

            u = norm_1(x)
            h0, tail0 = (before[l] if before is not None else (
                jnp.zeros((d["heads"], d["d_head"], d["d_state"]), F32),
                jnp.zeros((width - 1, conv), F32)))
            ssm, (h, h_before), tail, given = _mamba(
                u * jnp.asarray(ssm_in, dtype), named(MAMBA_KEYS), h0,
                tail0, None if restored is None else restored[l], **d,
                eps=eps, mults=mults, dtype=dtype,
                state_dtype=(jnp.bfloat16 if fault == "state_bf16"
                             else dtype),
                fault=fault if fault in MAMBA_FAULTS else None,
                cut=cut if restored is not None else 0)
            if fault == "no_mamba":
                ssm = jnp.zeros_like(ssm)
            # what the attention reads: the SAME normed input
            a = u
            if fault == "sequential":
                a = norm_1(x + jnp.asarray(ssm_out, dtype) * ssm)
            if fault == "second_norm":
                a = norm_1(x, "ffn_norm.scale_0")
            att, k_rows, v_rows = _attention(
                a * jnp.asarray(attn_in, dtype), named(ATTENTION_KEYS),
                shift=cut if fault == "shifted_blocks" else 0, **attention)
            if fault == "no_attention":
                att = jnp.zeros_like(att)
            x = (x + jnp.asarray(ssm_out, dtype) * ssm
                 + jnp.asarray(attn_out, dtype) * att)
            x = _mlp(x, named(MLP_KEYS), eps=eps, mults=mlp_mults,
                     dtype=dtype)
            # (to the host a layer: at the published widths what five
            # layers leave is half a gigabyte beside the served weights)
            for k, t in zip(left, (h, h_before, tail, given, k_rows,
                                   v_rows)):
                left[k].append(np.asarray(t))
        rows = (x[jnp.asarray(logits_at)] if logits_at is not None
                else x[logits_from:])
        out = _head(rows, states["final_norm.scale_0"],
                    states["lm_head.w_0"], eps=eps, dtype=dtype,
                    multiplier=(1.0 if fault == "no_lm_head_multiplier"
                                else float(config["lm_head_multiplier"])))
    left = {k: np.stack(t) for k, t in left.items()}
    return out, dict(left, ssm_states=left["state"])


def compare(states: dict, config: dict, ids, got, routing) -> dict:
    """A system's logits [rows, vocab] at `compared_positions` and what
    its walk left (`routing`: "state", "tails", "state_before",
    "ssm_inputs" (its last row is read), "ssm_states", "k_rows",
    "v_rows", as `forward` returns them, each optional) against this
    reference on the same weights and tokens:

      logits_rel_err  largest |logit difference| over the largest
                      |logit|: rounding, and every fault of an equation
      logits_rms_err  the same difference by root mean square over the
                      logits': steadier from seed to seed
      late_rms_err    `logits_rms_err` over the LATE positions alone
                      (the second half of those compared): where a state
                      that decays or rounds wrongly has drifted
                      furthest, and right behind where the snapshot
                      faults act
      state_rms_err   the system's lane's states after the last position
                      against this reference's, by root mean square, all
                      layers together
      tail_rms_err    the same of the convolution tails
      scan_rel_err    the recurrence judged on ITS OWN inputs, on every
                      layer, the largest of them: the state a layer's
                      recurrence LEFT at the last position
                      (`ssm_states`) against ONE float32 position of
                      this reference's recurrence from the state the
                      lane held before it (`state_before`) over what
                      the system says the recurrence was given there
                      (the last row of `ssm_inputs`): upstream rounding
                      cancels, and what is left is the recurrence
                      itself: a state kept or advanced in fewer bits
                      (each position rounds every element: 1e-3 and
                      more in bfloat16).  A system gives the inputs and
                      the result from ONE compiled program: a second
                      program rounds the matmuls before a deeper layer
                      its own way, and its inputs are then not the ones
                      that advanced the state (a walk of 2560 positions
                      read 1e-7 to 2.6e-3 that way on the fifth layer,
                      my chip runs, PR 69, too near a bfloat16 state's
                      3.7e-3 to bound).  Every layer's reading is
                      reported beside it, `scan_rel_err_by_layer`.  A
                      lane that did not start from zero or a snapshot
                      that is another document's shows in the states
                      (`state_rms_err`) and in the logits behind it
      kv_rms_err      the table's K and V rows of every layer against
                      this reference's rotated keys and values: blocks
                      that are not the sequence's own
    """
    at = compared_positions(config, len(ids))
    want, own = forward(states, config, ids, logits_at=at)
    got = np.asarray(got, np.float32)

    def rms(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))

    half = len(at) // 2
    out = {"logits_rel_err": float(np.max(np.abs(got - want))
                                   / np.max(np.abs(want))),
           "logits_rms_err": rms(got, want),
           "late_rms_err": rms(got[half:], want[half:]),
           "argmax_agree": float(np.mean(got.argmax(-1)
                                         == want.argmax(-1))),
           "finite": bool(np.isfinite(got).all())}
    if "state" in routing:
        out["state_rms_err"] = rms(routing["state"], own["state"])
    if "tails" in routing:
        out["tail_rms_err"] = rms(routing["tails"], own["tails"])
    if all(k in routing for k in ("state_before", "ssm_inputs",
                                  "ssm_states")):
        by_layer = [
            rms(routing["ssm_states"][l], _step_of(
                jnp.asarray(routing["state_before"][l], F32),
                jnp.asarray(routing["ssm_inputs"][l][-1], F32),
                states[f"layer_{l}.ssm_a_log.w_0"], **dims(config)))
            for l in range(int(config["num_hidden_layers"]))]
        out["scan_rel_err"] = max(by_layer)
        out["scan_rel_err_by_layer"] = by_layer      # reported
    if "k_rows" in routing:
        out["kv_rms_err"] = max(rms(routing["k_rows"], own["k_rows"]),
                                rms(routing["v_rows"], own["v_rows"]))
    return out


def below(states: dict, config: dict, ids) -> dict:
    """`compare`'s numbers for these equations computed wholly in
    bfloat16 (the state too), as if that were the system."""
    at = compared_positions(config, len(ids))
    return compare(states, config, ids, *forward(
        states, config, ids, dtype=jnp.bfloat16, logits_at=at))


def faults(states: dict, config: dict, ids, which=FAULTS) -> dict:
    """`compare`'s numbers for float32 models that a wrong reading of a
    key, a wrong state or a wrong snapshot would compute, as if each
    were the system: the limits have to refuse every one."""
    at = compared_positions(config, len(ids))
    return {fault: compare(states, config, ids, *forward(
        states, config, ids, fault=fault, logits_at=at))
        for fault in which}


SERVED_ROWS = 528


def served(states: dict, config: dict, requests, dtype=F32, fault=None,
           pad_to=None, cuts=None) -> dict:
    """Requests a server decoded greedily (temperature 0) against this
    reference.  `requests`: (ids, start) pairs, `ids` the prompt and
    then the tokens delivered, `start` the prompt's length; token
    ids[i + 1] for i >= start - 1 was sampled at position i, from the
    logits this reference computes there over ids[: i + 1] on states
    that it walked from position 0 and keys it computed itself: the
    server's came out of a snapshot and out of shared blocks.

      served_argmax_agree  share of the delivered tokens that are this
                      reference's argmax at their position
      served_gap_rms  how far below its argmax this reference puts the
                      delivered token, over the largest |logit| of the
                      request, by root mean square over the tokens
      early_argmax_agree, early_gap_rms  the same over each request's
                      first 32 delivered tokens alone: nearest to where
                      its lane's state was restored

    Every request is padded to ONE length (a causal model's earlier
    positions do not see the pad) and read at `SERVED_ROWS` positions
    from its prompt's last on, so one compiled forward serves all:
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
            BLOCK, (start - 32) // BLOCK * BLOCK)
        first = min(start - 1, longest - min(SERVED_ROWS, longest))
        want = forward(
            states, config, padded, dtype=dtype, fault=fault, cut=cut,
            logits_at=first + np.arange(min(SERVED_ROWS, longest)))[0][
                start - 1 - first:n - first]
        got = want[np.arange(len(want)), ids[start:start + len(want)]]
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
