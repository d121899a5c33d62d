"""The LongCat-Flash block (a DOUBLE layer: two latent attentions with a
cache plane each, two dense FFNs and ONE shortcut expert layer across
them; a softmax router with a choice bias over routed experts AND
identity experts that have no matrices; a constant on each normed
latent) through `build_lm_paged_decoder` against the plain EXPANDED
reference `perf/reference/longcat_flash.py`, at toy widths on the CPU
with seeded random float32 weights.

The toy keeps what makes the model: two double layers (four cache
planes), a query/key head (8 + 8) wider than a value head (8), a row
(32 + 8 = 40) that needs the pad to the lane grid (128), 16 routed
experts of which 4 are held (from the fourth) beside 8 identity
columns, 6 a token, a bias far from zero beside probabilities near
1/24, both latent constants other than 1.  What is compared is LOGITS,
never tokens.
"""
import functools
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.kernels import paged_attention
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, L = 97, 48, 8, 2
QL, KVL, DN, DR, DV = 24, 32, 8, 8, 8   # ranks; nope, rope, value a head
E, Z, HELD, FIRST, K = 16, 8, 4, 4, 6   # routed, identity, held, from, k
F, FD = 16, 40                          # an expert, a dense FFN
BS, NB = 4, 10                          # 40 positions
CONFIG = {"num_attention_heads": H, "hidden_size": D, "q_lora_rank": QL,
          "kv_lora_rank": KVL, "qk_nope_head_dim": DN,
          "qk_rope_head_dim": DR, "v_head_dim": DV, "rms_norm_eps": 1e-5,
          "rope_theta": 10000000, "mla_scale_q_lora": True,
          "mla_scale_kv_lora": True, "num_layers": L, "moe_topk": K,
          "zero_expert_num": Z, "zero_expert_type": "identity",
          "routed_scaling_factor": 6, "expert_ffn_hidden_size": F,
          "first_local_expert": FIRST}
# float32 weights and pool: the same float32 sums in another order
# (absorbed against expanded, grouped matmul against a masked scan):
# measured 3e-7 to 6e-7
TOL_FP32 = 1e-4
# bf16 pool: the latent row rounded to 8 bits of mantissa on its way
# into the table, four planes deep: measured 4e-3 to 9e-3
TOL_BF16_POOL = 4e-2


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(ROOT, "perf", "reference", "longcat_flash.py"),
            "ref_longcat_flash")


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="longcat_flash", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, norm_eps=1e-5, rope_theta=1e7,
        n_experts=E, experts_per_token=K, norm_topk_prob=False,
        experts_first=FIRST, experts_held=HELD, dense_d_inner=FD,
        router="softmax", router_bias=True, routed_scaling_factor=6.0,
        q_lora_rank=QL, kv_lora_rank=KVL, qk_nope_head_dim=DN,
        qk_rope_head_dim=DR, v_head_dim=DV, scale_q_lora=True,
        scale_kv_lora=True, sub_blocks=2, zero_experts=Z), **over))


def _decoder(kv_dtype="fp32", **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu", block=_block(**over))
    assert startup is None
    return dec


def _interpreted(monkeypatch, chunk_bytes=4 * BS * 128 * 4, tile_rows=8):
    """The latent form of the Pallas kernel, under the interpreter,
    through a whole decoder: pages in several chunks of several
    tiles."""
    monkeypatch.setattr(paged_attention, "_CHUNK_BYTES", chunk_bytes)
    monkeypatch.setattr(paged_attention, "_TILE_ROWS", tile_rows)
    monkeypatch.setattr(
        paged_attention, "select_paged_attention", functools.partial(
            paged_attention.select_paged_attention, interpret=True))


def _weights(dec, seed=0):
    """Matrices at sigma 0.1, the router at 0.3, and a choice bias at
    0.02: half a mean probability (1/24), so that it moves the choice
    of most tokens."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router.w" in n else
                     0.02 if "router_bias" in n else 0.1,
                     shape).astype(np.float32)
        g[n] = jnp.asarray(1.0 + w if ".scale_" in n else w)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None,
           routing=False):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); returns each
    sequence's [len, V] logits (and lane 0's routing stacked over its
    positions, and what the steps counted)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = dec.init_pool(1 + slots * NB)
    # one array, a plane a SUB-BLOCK
    assert pool_v == () and pool_k.shape[0] == 2 * L
    assert pool_k.shape[-1] == 128
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed, counted = [[] for _ in seqs], [], []
    for tick in range(max(t + len(s) for s, t in zip(seqs, starts))):
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane, t0 in zip(seqs, lanes, starts):
            if t0 <= tick < t0 + len(s):
                toks[lane], pos[lane], act[lane] = s[tick - t0], tick - t0, \
                    True
        args = (g, pool_k, pool_v, tables, pos, toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        if act[lanes[0]]:
            routed.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                           for k, v in r.items()})
        _, pool_k, pool_v, *counts = dec.step(*args)
        assert pool_v == () and len(counts) == len(dec.step_counters)
        counted.append(dict(zip(dec.step_counters,
                                (np.asarray(c) for c in counts)),
                            experts=np.asarray(r["experts"])[:, act]))
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if routing:
        return out, {k: np.concatenate([r[k] for r in routed], 1)
                     for k in routed[0]}, counted
    return out


SEQ = list(np.random.RandomState(7).randint(0, V, 37))   # over 9 blocks
IDS = np.asarray(SEQ, np.int32)


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_two_plane_latent_table_matches_the_expanded_reference(kv_dtype,
                                                                tol):
    """37 positions (prompt, then decode: one position a step either
    way) through two double layers, the ABSORBED step over four planes
    of the latent table against the reference's keys and values
    widened for every head, its shortcut and its identity experts."""
    dec = _decoder(kv_dtype)
    assert (dec.table_layers, dec.kv_planes, dec.ring_layers,
            dec.moe_layers, dec.n_layers) == (4, 4, 0, 2, 2)
    # one row of 40 columns a position a PLANE, stored 128 wide
    assert dec.bytes_per_block == 2 * L * BS * 128 * (
        4 if kv_dtype == "fp32" else 2)
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    assert routing["experts"].shape == (L, len(SEQ), K)
    assert routing["experts"].max() >= E        # an identity column
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["late_rms_err"] <= tol and out["router_rel_err"] <= 1e-4, out
    assert 0.15 < out["zero_share"] < 0.6, out


def test_absorbed_equals_expanded_at_float32_to_rounding():
    dec = _decoder()
    g = _weights(dec, seed=4)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, IDS, got, routing)
    assert ok["logits_rel_err"] <= 1e-5 and ok["logits_rms_err"] <= 1e-5
    assert ok["router_rel_err"] <= 1e-5 and ok["routing_agree"] == 1.0


ROUTER_FAULTS = ("renormalised", "bias_in_weight", "softmax_512")


@pytest.mark.parametrize("what", ["below"] + list(REF.FAULTS))
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """What `compare` must tell apart at these widths: the whole model
    in bfloat16, and the ten seeded faults, each by at least one of the
    numbers the cell bounds and far above the float32 decoder (which
    reads 4e-7 here: `below` reads 1.0e-2, the least fault 3e-2)."""
    g = _weights(_decoder())
    out = (REF.below(g, CONFIG, IDS) if what == "below"
           else REF.compare(g, CONFIG, IDS, *REF.forward(
               g, CONFIG, IDS, fault=what)))
    by = "router_rel_err" if what in ROUTER_FAULTS else "logits_rms_err"
    assert out[by] > 50 * TOL_FP32, (what, out)
    if what not in ROUTER_FAULTS + ("below",):
        # the router is right: only the logits see these
        assert out["router_rel_err"] <= 1e-5, (what, out)
    if what == "k_pe_unrotated":
        # a position signal: the first position has nothing to turn
        moved = np.abs(np.asarray(REF.forward(g, CONFIG, IDS, fault=what)[0])
                       - np.asarray(REF.forward(g, CONFIG, IDS)[0])).max(-1)
        assert moved[0] == 0.0 and moved[1:].min() > 0.0


def test_the_faults_are_those_the_issue_lists_and_each_is_one_field():
    assert set(REF.FAULTS) == {
        "zero_nothing", "zero_unnormed", "moe_second_input", "join_early",
        "no_q_scale", "no_kv_scale", "renormalised", "bias_in_weight",
        "softmax_512", "k_pe_unrotated"}
    assert REF.latent_scales(CONFIG) == (2 ** 0.5, 1.5 ** 0.5)
    assert REF.latent_scales(CONFIG, "no_q_scale") == (1.0, 1.5 ** 0.5)
    assert REF.latent_scales(dict(CONFIG, mla_scale_kv_lora=False))[1] == 1
    full = {"hidden_size": 6144, "q_lora_rank": 1536, "kv_lora_rank": 512,
            "mla_scale_q_lora": True, "mla_scale_kv_lora": True}
    assert REF.latent_scales(full) == (2.0, 12 ** 0.5)
    # the decoder's constants are the description's: left out, it is
    # the reference's fault
    dec = _decoder(scale_q_lora=False)
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ[:12]], routing=True)
    ids = IDS[:12]
    want = REF.compare(g, CONFIG, ids, *REF.forward(
        g, CONFIG, ids, fault="no_q_scale"))
    out = REF.compare(g, CONFIG, ids, got, routing)
    assert out["logits_rms_err"] == pytest.approx(want["logits_rms_err"],
                                                  rel=1e-3)


def _moe_arrays(seed, n_routed, n_zero, tokens=13):
    r = np.random.RandomState(seed)
    u = jnp.asarray(r.normal(0, 1, (tokens, D)), jnp.float32)
    whole = {n: jnp.asarray(r.normal(0, 0.1, s), jnp.float32)
             for n, s in (("gate", (n_routed, D, F)),
                          ("up", (n_routed, D, F)),
                          ("down", (n_routed, F, D)))}
    router = jnp.asarray(r.normal(0, 0.3, (D, n_routed + n_zero)),
                         jnp.float32)
    bias = jnp.asarray(r.normal(0, 0.5 / (n_routed + n_zero),
                                n_routed + n_zero), jnp.float32)
    return u, whole, router, bias


def test_the_32_shares_and_the_identity_part_add_up_to_the_whole_layer():
    """The routed parts that 32 shares give (each its 2 of 64 routed
    experts, through `moe_ffn`), with the identity part (which every
    chip computes alike) counted ONCE, are the uncut reference's expert
    layer; 31 shares are not; and each share is the reference given the
    same share."""
    n_routed, n_zero, held = 64, 32, 2
    u, whole, router, bias = _moe_arrays(3, n_routed, n_zero)
    own = jnp.full((u.shape[0], K), -1, jnp.int32)

    def reference(experts, first, zero="input"):
        out, _ = REF._moe(u, u, {"router": router, "bias": bias, **experts},
                          own, jnp.asarray(6.0), top_k=K, n_zero=n_zero,
                          first=first, zero=zero)
        return np.asarray(out)

    def share(first, count, zeros=n_zero):
        cut = {n: w[first:first + count] for n, w in whole.items()}
        y, hit, _ = lm_block.moe_ffn(
            _block(n_experts=n_routed, zero_experts=zeros,
                   experts_first=first, experts_held=count),
            u, router, *cut.values(), b_router=bias)
        assert 0 <= int(hit) <= count
        return np.asarray(y), cut

    identity = reference({n: w[:1] for n, w in whole.items()}, 0) \
        - reference({n: w[:1] for n, w in whole.items()}, 0, zero="nothing")
    assert np.abs(identity).max() > 1e-2
    parts = []
    for first in range(0, n_routed, held):
        y, cut = share(first, held)
        np.testing.assert_allclose(y, reference(cut, first), atol=5e-5)
        parts.append(y - identity)              # its routed part alone
    want = reference(whole, 0)
    np.testing.assert_allclose(sum(parts) + identity, want, atol=5e-5)
    assert np.abs(sum(parts[:31]) + identity - want).max() > 1e-3
    # counted on every chip, the identity part would be there 32 times
    assert np.abs(sum(parts) + 32 * identity - want).max() > 1e-1
    # a block that holds EVERY routed expert takes the same path
    np.testing.assert_allclose(share(0, n_routed)[0], want, atol=5e-5)


class _Recorder:
    """A stand-in for the Pallas grouped matmul that records the group
    sizes it was planned for and computes with `ragged_dot`."""
    name = "recorder"

    def __init__(self):
        self.sizes = []

    def plan(self, sizes):
        self.sizes.append(np.asarray(sizes))
        return (sizes, jnp.zeros((), jnp.int32))

    def gate_up(self, rows, w_gate, w_up, plan):
        f32 = jnp.float32
        gate = jax.lax.ragged_dot(rows, w_gate, plan[0],
                                  preferred_element_type=f32)
        up = jax.lax.ragged_dot(rows, w_up, plan[0],
                                preferred_element_type=f32)
        return (jax.nn.silu(gate) * up).astype(w_gate.dtype)

    def down(self, act, w_down, plan):
        return jax.lax.ragged_dot(act, w_down, plan[0],
                                  preferred_element_type=jnp.float32)


def test_an_identity_assignment_sends_no_row_to_the_grouped_matmul():
    """The sorted rows inside groups are the held routed assignments
    alone: identity ones (and absent experts') lie past the last group;
    a token whose k are all identity gets 6 * sum(p) * u and no more."""
    u, whole, router, bias = _moe_arrays(5, E, Z, tokens=29)
    spec = _block()
    cut = [w[FIRST:FIRST + HELD] for w in whole.values()]
    rec = _Recorder()
    y, hit, (top_w, top_e) = lm_block.moe_ffn(spec, u, router, *cut,
                                              experts=rec, b_router=bias)
    top_e = np.asarray(top_e)
    held = (top_e >= FIRST) & (top_e < FIRST + HELD)
    (sizes,) = rec.sizes
    assert sizes.shape == (HELD,)
    assert np.array_equal(sizes, [np.sum(top_e == FIRST + i)
                                  for i in range(HELD)])
    assert sizes.sum() == held.sum() < top_e.size - np.sum(top_e >= E)
    assert int(hit) == np.sum(sizes > 0)
    # the same result as the fallback's
    plain, _, _ = lm_block.moe_ffn(spec, u, router, *cut, b_router=bias)
    np.testing.assert_allclose(y, plain, atol=1e-6)
    # every choice an identity column: a bias that lifts them all
    lifted = bias.at[E:].add(10.0)
    y0, hit0, (w0, e0) = lm_block.moe_ffn(spec, u, router, *cut,
                                          experts=_Recorder(),
                                          b_router=lifted)
    assert np.asarray(e0).min() >= E and int(hit0) == 0
    logits = np.asarray(u, np.float64) @ np.asarray(router, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.take_along_axis(p, np.asarray(e0), -1)
    np.testing.assert_allclose(w0, 6.0 * chosen, rtol=1e-5)
    np.testing.assert_allclose(
        y0, 6.0 * chosen.sum(-1, keepdims=True) * np.asarray(u), rtol=1e-5)


def test_the_choice_bias_under_softmax_moves_the_choice_not_the_weights():
    """`lm_block.route` under softmax with a bias against a plain loop:
    the k largest of p + b, a tie to the lower index, the weights p x 6
    of the chosen (never p + b, never renormalised); without a bias the
    branch every other softmax block takes, bit for bit."""
    r = np.random.RandomState(6)
    m = jnp.asarray(r.normal(0, 1, (33, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.5, (D, E + Z)), jnp.float32)
    b = jnp.asarray(r.normal(0, 0.03, E + Z), jnp.float32)
    spec = _block()
    top_w, top_e = lm_block.route(spec, m, w, b)
    logits = np.asarray(m, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    biased = p + np.asarray(b, np.float64)
    want_e = np.asarray([sorted(range(E + Z), key=lambda e: (-row[e], e))[:K]
                         for row in biased])
    assert np.array_equal(np.asarray(top_e), want_e)
    np.testing.assert_allclose(
        top_w, 6.0 * np.take_along_axis(p, want_e, -1), rtol=1e-5)
    free_w, free_e = lm_block.route(spec, m, w)
    assert not np.array_equal(np.asarray(free_e), want_e)   # it moved some
    # ... and where both chose a column its weight is the same
    both = np.asarray(free_e)[:, :1] == want_e[:, :1]
    assert both.any()
    assert np.array_equal(np.asarray(free_w)[:, :1][both],
                          np.asarray(top_w)[:, :1][both])
    # all scores equal and a bias that ties columns 3 and 20 on top:
    # the lower index first, then the rest from 0
    tie = jnp.zeros(E + Z).at[jnp.asarray([20, 3])].set(0.5)
    _, tied = lm_block.route(spec, m, jnp.zeros((D, E + Z)), tie)
    assert np.array_equal(np.asarray(tied)[0], [3, 20, 0, 1, 2, 4])
    # no bias: the plain top-k of the softmax, bit for bit
    probs = jax.nn.softmax(jnp.dot(m, w, precision="highest"), axis=-1)
    want_w, want_plain = jax.lax.top_k(probs, K)
    assert np.array_equal(np.asarray(free_e), np.asarray(want_plain))
    assert np.array_equal(np.asarray(free_w), np.asarray(want_w * 6.0))


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["xla_gather", "pallas_interpreted"])
def test_lanes_out_of_step_bit_identical_to_the_same_sequence_alone(
        streamed, monkeypatch):
    """Three sequences of different lengths that start at different
    ticks in one four-lane step, the sequence in another lane and other
    blocks than alone: bit for bit the same logits, through the XLA
    gather and through the latent form of the Pallas kernel under the
    interpreter; the step's counts are the routing's own, of the live
    lanes alone."""
    if streamed:
        _interpreted(monkeypatch)
    dec = _decoder()
    assert dec.kernels["paged_attention_decode"] == (
        "pallas:latent" if streamed else "xla:not_tpu")
    g = _weights(dec, seed=3)
    seq = SEQ[:21]
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 9), (12, 14))]
    (alone,) = _drive(dec, g, [seq], slots=4, lanes=[2])
    together, _, counted = _drive(
        dec, g, [others[0], seq, others[1]], slots=4, lanes=[3, 1, 0],
        starts=[2, 0, 5], routing=True)
    assert np.array_equal(together[1], alone)
    for c in counted:
        experts = c["experts"]                       # [layers, live, k]
        on = (experts >= FIRST) & (experts < FIRST + HELD)
        assert np.array_equal(c["moe_rows_held"], on.sum((1, 2)))
        assert np.array_equal(c["moe_tokens_here"], on.any(2).sum(1))
        assert np.array_equal(c["moe_zero_assignments"],
                              (experts >= E).sum((1, 2)))
        assert np.array_equal(c["moe_assignments"],
                              [experts[0].size] * L)
        assert c["moe_experts_hit"].shape == (L,)
        assert (c["moe_experts_hit"] <= HELD).all()
    assert sum(int(c["moe_zero_assignments"].sum()) for c in counted) > 0
    if streamed:
        monkeypatch.undo()
        plain = _decoder()
        (want,) = _drive(plain, g, [seq], slots=4, lanes=[2])
        assert np.abs(alone - want).max() <= 2e-5 * np.abs(want).max()


def test_description_is_checked_and_laid_out():
    """What is not built is refused by name; a layer is two sub-blocks
    of the seven latent arrays, two dense FFNs and four norms, and ONE
    router, bias and set of experts."""
    with pytest.raises(ValueError, match="sub_blocks 3"):
        _block(sub_blocks=3)
    for over in ({"shared_d_inner": 8}, {"post_norm": True},
                 {"mlp_layer_types": [lm_block.SPARSE] * L},
                 {"layer_types": [lm_block.FULL] * L}, {"kv_lora_rank": 0},
                 {"dense_d_inner": 0}, {"passes": 2}):
        with pytest.raises(NotImplementedError, match="a double layer"):
            _block(**over)
    for over in ({"router": "sigmoid"}, {"norm_topk_prob": True},
                 {"n_group": 2, "topk_group": 1}):
        with pytest.raises(NotImplementedError, match="identity experts"):
            _block(**over)
    with pytest.raises(ValueError, match="scale_q_lora and scale_kv_lora"):
        _block(sub_blocks=1, kv_lora_rank=0, q_lora_rank=0)
    with pytest.raises(ValueError, match="not among the"):
        _block(experts_first=E - 2)        # identity columns are not held
    with pytest.raises(NotImplementedError, match="sigmoid router alone"):
        _decoder(zero_experts=0, norm_topk_prob=True)
    with pytest.raises(NotImplementedError, match="int8 pool"):
        _decoder(kv_dtype="int8")
    dec = _decoder()
    shapes = dec.state_shapes
    for l in range(L):
        for i in range(2):
            p = f"layer_{l}.sub_{i}."
            assert shapes[p + "attn_norm.scale_0"] == (D,)
            assert shapes[p + "q_a_proj.w_0"] == (D, QL)
            assert shapes[p + "q_b_proj.w_0"] == (QL, H * (DN + DR))
            assert shapes[p + "kv_a_proj.w_0"] == (D, KVL + DR)
            assert shapes[p + "kv_b_proj.w_0"] == (KVL, H * (DN + DV))
            assert shapes[p + "o_proj.w_0"] == (H * DV, D)
            assert shapes[p + "ffn_norm.scale_0"] == (D,)
            assert shapes[p + "ffn_gate.w_0"] == (D, FD)
            assert shapes[p + "ffn_down.w_0"] == (FD, D)
            assert p + "router.w_0" not in shapes
        # wider than its experts: routed and identity columns
        assert shapes[f"layer_{l}.router.w_0"] == (D, E + Z)
        assert shapes[f"layer_{l}.router_bias.b_0"] == (E + Z,)
        assert shapes[f"layer_{l}.experts_gate.w_0"] == (HELD, D, F)
        assert shapes[f"layer_{l}.experts_down.w_0"] == (HELD, F, D)
    assert len(shapes) == L * (2 * 12 + 5) + 3
    assert dec.step_counters == (
        "moe_experts_hit", "moe_rows_held", "moe_tokens_here",
        "moe_zero_assignments", "moe_assignments")
    assert set(dec.refuses) == {"draft_model"}
    # the rows and the pages are counted over all four planes
    counts = dec.tick_counts(np.asarray([0, 5, 17]), 4)
    assert counts["latent_rows"] == 2 * L * (1 + 6 + 18)
    assert counts["kv_planes"] == 2 * L and "loop_passes" not in counts
    assert counts["kv_pages_table"] == 4 * 2 * L * NB
    assert counts["moe_layers"] == L
    # the single-layer latent block's step and counts are what they were
    single = _decoder(sub_blocks=1, zero_experts=0, router_bias=False,
                      scale_q_lora=False, scale_kv_lora=False,
                      dense_d_inner=0)
    assert single.step_counters == ("moe_experts_hit", "moe_rows_held")
    assert single.kv_planes == L
    assert "kv_planes" not in single.tick_counts(np.asarray([3]), 2)
    assert single.state_shapes["layer_1.router.w_0"] == (D, E)


def test_generation_server_serves_the_block_and_refuses_by_name():
    """Requests of several blocks through `GenerationServer`,
    continuously batched, give the tokens of the same request alone;
    the tick spans carry `latent_rows` over all planes, `kv_planes` and,
    with the tokens of the tick read, the identity assignments and all
    of them; a draft model and `step_window` are refused by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    assert "latent cache" in dec.refuses["draft_model"]
    with pytest.raises(ValueError, match="no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                         draft_decoder=dec, draft_states=g)
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(1, np.int32)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(_weights(dec), pool_k, pool_v,
                        np.zeros((1, NB), np.int32), z,
                        np.zeros((1, 2), np.int32), z.astype(np.uint32),
                        z.astype(np.float32), z)
    prompts = [list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 5), (2, 11), (3, 3))]
    solo = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                            prefix_cache=False)
    try:
        want = [solo.generate(p, 20) for p in prompts]
    finally:
        solo.close()
    spans = []
    tracing.add_span_listener(spans.append)
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                           prefix_cache=False)
    try:
        streams = [srv.submit(p, 20) for p in prompts]
        assert [s.result(timeout=120) for s in streams] == want
        assert srv.stats()["decode_kernel"] == "xla:not_tpu"
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(a["moe_layers"] == L and a["kv_planes"] == 2 * L
                         and a["latent_rows"] > 0 for a in ticks)
    read = [a for a in ticks if a["ahead"]]
    assert read and all(("moe_zero_assignments" in a) == bool(a["ahead"])
                        for a in ticks)
    assert all(a["moe_assignments"] in (K * L, 2 * K * L)
               and 0 <= a["moe_zero_assignments"] <= a["moe_assignments"]
               - a["moe_rows_held"] for a in read)
    assert sum(a["moe_zero_assignments"] for a in read) > 0
    assert max(a["latent_rows"] for a in ticks) >= 2 * L * 2 * 20


def test_the_prefix_cache_works_on_the_two_plane_latent_table():
    """A cached block holds every plane's latent rows, so a shared
    prefix's blocks are reused and the tokens are those of the unshared
    run."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec, seed=5).items()}
    prefix = list(np.random.RandomState(9).randint(0, V, 3 * BS))
    prompts = [prefix + list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 3), (2, 6), (3, 2))]
    out = {}
    for cached in (False, True):
        srv = GenerationServer(dec, g, slots=2, kv_blocks=3 * NB,
                               place=fluid.CPUPlace(), prefix_cache=cached)
        try:
            out[cached] = [srv.generate(p, 8) for p in prompts]
            hits = srv.stats()["prefix_hits"]
        finally:
            srv.close()
    assert out[True] == out[False]
    assert hits >= 2                      # the second and third requests


def test_served_tokens_are_judged_by_the_reference_alone():
    """`served` knows only the tokens a server delivered: greedy
    requests agree with the reference's argmax, and a fault reads the
    same tokens as disagreeing."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB,
                           place=fluid.CPUPlace(), prefix_cache=False)
    try:
        prompts = [list(np.random.RandomState(s).randint(0, V, n))
                   for s, n in ((7, 4), (8, 9), (9, 6))]
        streams = [srv.submit(p, 24) for p in prompts]
        requests = [(np.asarray(p + s.result(timeout=120), np.int32),
                     len(p)) for p, s in zip(prompts, streams)]
    finally:
        srv.close()
    out = REF.served(g, CONFIG, requests)
    assert out["tokens"] == 72
    assert out["served_argmax_agree"] == 1.0 and out["served_gap_rms"] == 0.0
    wrong = REF.served(g, CONFIG, requests, fault="zero_nothing")
    assert wrong["served_argmax_agree"] < 0.9, wrong
    assert wrong["served_gap_rms"] > 1e-3


def test_scopes_name_the_sub_blocks_the_identity_part_and_the_join():
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("latent_q", "latent_kv", "latent_absorb", "attention",
                 "attn_out", "dense_ffn"):
        for i in (0, 1):
            assert f"paged_decoder/{part}/sub{i}" in text, (part, i)
    for part in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
                 "moe_zero", "moe_shortcut_join", "kv_write", "rope",
                 "head"):
        assert f"paged_decoder/{part}" in text, part
        assert f"paged_decoder/{part}/sub0" not in text, part
        assert f"paged_decoder/{part}/sub1" not in text, part
    assert "paged_decoder/qkv" not in text
    assert "paged_decoder/mlp" not in text
    scopes = dec.compiler_scopes
    assert scopes["g[\\'layer_0.sub_1.q_b_proj.w_0\\']"] == \
        "paged_decoder/latent_q"
    assert scopes["g[\\'layer_1.sub_0.kv_b_proj.w_0\\']"] == \
        "paged_decoder/latent_absorb"
    assert scopes["g[\\'layer_1.sub_1.ffn_up.w_0\\']"] == \
        "paged_decoder/dense_ffn"
    assert scopes["g[\\'layer_0.sub_0.o_proj.w_0\\']"] == \
        "paged_decoder/attn_out"
    # a block without sub-blocks names no index
    single = _decoder(sub_blocks=1, zero_experts=0, router_bias=False,
                      dense_d_inner=0)
    pool_k, pool_v = single.init_pool(3)
    text = single.step.lower(
        _weights(single), pool_k, pool_v, np.zeros((2, NB), np.int32), z,
        z, z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    assert "/sub0" not in text and "moe_zero" not in text


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/longcat-flash-1chip.json's `block`, read as the
    benchmark's job reads it, builds the decoder at the published
    widths (shapes only: nothing is allocated), and the parameter and
    cache arithmetic the file and the issue state is the decoder's
    own."""
    m = _json("perf", "configs", "longcat-flash-1chip.json")
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.latent and spec.sub_blocks == 2
    assert (spec.router, spec.router_bias, spec.routed_scaling_factor,
            spec.norm_topk_prob) == ("softmax", True, 6, False)
    assert spec.held == (0, 16) and spec.n_experts == 512
    assert (spec.zero_experts, spec.experts_per_token) == (256, 12)
    assert spec.scale_q_lora and spec.scale_kv_lora
    assert spec.rope_of(lm_block.FULL) == {"rope_type": "default",
                                           "rope_theta": 10000000}
    assert m["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert m["published"] == {"num_layers": 28, "n_routed_experts": 512,
                              "vocab_size": 131072}
    assert set(m["assumed"]) >= {"shortcut", "zero_expert", "router",
                                 "latent_scales", "norm_placement",
                                 "rope_columns", "training", "weights"}
    # every published width, unchanged, under the source's own keys
    assert (m["hidden_size"], m["num_attention_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["ffn_hidden_size"],
            m["expert_ffn_hidden_size"], m["zero_expert_num"],
            m["moe_topk"], m["routed_scaling_factor"], m["rope_theta"],
            m["rms_norm_eps"], m["max_position_embeddings"]) == (
                6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 256, 12, 6,
                10000000, 1e-5, 131072)
    assert (m["mla_scale_q_lora"], m["mla_scale_kv_lora"],
            m["zero_expert_type"], m["attention_method"],
            m["attention_bias"]) == (True, True, "identity", "MLA", False)
    # the catalog's row, but for what `reduced` names
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"LongCat-Flash-Chat"' in line) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        assert row["source_url"] == m["source"]
        for key, value in row["config"].items():
            assert m[key] == (value if key not in m["reduced"]
                              else m[key]), key
            assert (key in m["reduced"]) == (m[key] != value), key
    assert m["num_hidden_layers"] == m["num_layers"] == 4
    assert m["num_experts"] == m["n_routed_experts"] == 16
    assert m["num_experts_per_tok"] == m["moe_topk"]
    assert m["num_routed_experts"] == m["published"]["n_routed_experts"]
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 256, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu",
        block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas:latent"
    assert (dec.table_layers, dec.kv_planes, dec.ring_layers,
            dec.moe_layers) == (8, 8, 0, 4)
    shapes = dec.state_shapes
    assert shapes["layer_0.sub_0.q_b_proj.w_0"] == (1536, 64 * 192)
    assert shapes["layer_0.sub_1.kv_a_proj.w_0"] == (6144, 576)
    assert shapes["layer_3.sub_0.kv_b_proj.w_0"] == (512, 64 * 256)
    assert shapes["layer_3.sub_1.o_proj.w_0"] == (64 * 128, 6144)
    assert shapes["layer_0.sub_1.ffn_gate.w_0"] == (6144, 12288)
    assert shapes["layer_1.experts_down.w_0"] == (16, 2048, 6144)
    assert shapes["layer_1.router.w_0"] == (6144, 768)
    assert shapes["layer_1.router_bias.b_0"] == (768,)
    assert shapes["lm_head.w_0"] == (6144, 16384)

    def params(prefix, but=()):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix) and not any(x in n for x in but))

    # the file's counts
    assert round(params("layer_0.sub_0.", ("ffn_",)) / 1e6, 2) == 90.58
    assert round(params("layer_0.sub_0.ffn_") / 1e6, 1) == 226.5
    assert round(params("layer_0.experts_") / 1e6, 1) == 604.0
    assert round(params("layer_0.", ("experts_",)) / 1e6, 1) == 638.9
    # 1242.82 M of matrices and 0.03 M of norm scales and bias
    assert 1242.8 < params("layer_0.") / 1e6 < 1242.9
    weights_gb = params("") * 2 / 1e9
    assert round(weights_gb, 2) == 10.35
    # ONE row a position a plane, 1280 B, on 8 planes
    assert dec.bytes_per_block // 16 == 8 * 1280 == 10240
    pool_gb = dec.bytes_per_block * 64 * 256 / 1e9
    assert round(pool_gb, 2) == 2.68
    assert 12.9 <= weights_gb + pool_gb <= 13.1          # held, of 16
    assert dec.attention_tiling == ((64, 8), None)
    assert dec.tick_counts(np.asarray([999] * 64), 64)["latent_rows"] == (
        8 * 64 * 1000)
    g = _weights(_decoder())
    ids = np.zeros(9, np.int32)
    assert set(m["compare"]["limits"]) <= set(
        REF.compare(g, CONFIG, ids, *REF.forward(g, CONFIG, ids)))
    assert set(m["compare"]["served_limits"]) <= set(
        REF.served(g, CONFIG, [(ids, 2)]))
    assert m["reference"] == "longcat_flash"
    # the rehearsal's twin describes a block too
    tiny = dict(m, **m["rehearse"])
    lm_block.BlockSpec(**dict(
        b["spec"], **{f: tiny[k] for f, k in b["from_keys"].items()}))


def test_the_accepted_cost_functions_count_this_block():
    """The accepted readers take heads and widths from the file's keys:
    a row attended by 64 heads, and an expert of 6144 x 2048."""
    m = _json("perf", "configs", "longcat-flash-1chip.json")
    cost = _load(os.path.join(ROOT, "perf", "latent_attention_cost.py"),
                 "latent_attention_cost")
    assert cost.row_ops(m["num_attention_heads"], m["kv_lora_rank"],
                        m["qk_rope_head_dim"]) == 2 * 64 * (576 + 512)
    call = cost.attention_call(8 * 64 * 1000, 64, 512, 64)
    assert call["bytes"] == 8 * 64 * 1000 * 1152
    assert call["flops"] / call["bytes"] == pytest.approx(120.9, abs=0.1)
    flops = _load(os.path.join(ROOT, "perf", "moe_flops.py"), "moe_flops")
    assert flops.expert_bytes(m["hidden_size"], m[m["block"]["d_inner"]]) \
        == 3 * 6144 * 2048 * 2


def test_traffic_file_is_agent64_under_the_bias_job():
    mine = _json("perf", "traffic", "agent64-bias.json")
    agent64 = _json("perf", "traffic", "agent64.json")
    assert mine["job"] == "serve_lm_latent_bias" != agent64["job"]
    assert mine["what"] != agent64["what"]
    assert set(mine) == set(agent64)
    for key in set(agent64) - {"job", "what"}:
        assert mine[key] == agent64[key], key
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"]
                if w["name"] == "longcat-flash-serve-agent64")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-1chip", "agent64-bias", 1)
    reports = {m["name"] for m in bench["end_to_end"]
               if "workloads" not in m or cell["name"] in m["workloads"]}
    assert reports == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}


def _perf_module(*path):
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        return common.load_module(os.path.join(ROOT, "perf", *path))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))


def test_the_jobs_fit_reaches_its_target():
    """perf/jobs/serve_lm_latent_bias.py `fit`: over router inputs that
    share a large common component (what seeded weights give: some
    columns popular, some starved) the sign rule finds a bias under
    which every column, routed and identity, takes nearly the same
    load: a third of the assignments identity at 2 columns routed to 1;
    the bias has the dtype asked for (the job asks for float32)."""
    job = _perf_module("jobs", "serve_lm_latent_bias.py")
    r = np.random.RandomState(4)
    inputs = jnp.asarray(r.normal(0, 1, (2048, D)) + 2.0 * r.normal(
        0, 1, (1, D)), jnp.float32)
    # logits of standard deviation 1.5, as 6144 columns at 0.02 give
    w = jnp.asarray(r.normal(0, 0.1, (D, E + Z)), jnp.float32)
    probs = jax.nn.softmax(inputs @ w, axis=-1)
    spec = _block()

    def loads(bias):
        _, chosen = lm_block.route(spec, inputs, w, bias)
        return np.bincount(np.asarray(chosen).reshape(-1),
                           minlength=E + Z) / (2048 * K / (E + Z))

    seeded = loads(jnp.zeros(E + Z))
    assert seeded.max() > 2.0 and seeded.min() < 0.3
    assert job.fit(probs, K, jnp.bfloat16).dtype == jnp.bfloat16
    bias = job.fit(probs, K, jnp.float32)
    assert bias.dtype == jnp.float32 and bias.shape == (E + Z,)
    after = loads(bias)
    assert after.max() < 1.1 and after.min() > 0.9, after
    assert after[E:].sum() / after.sum() == pytest.approx(1 / 3, abs=0.02)


def test_the_readers_read_the_identity_share_from_the_tick_spans():
    reader = _perf_module("metrics", "sched_moe_zero_share.py")
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "model step", "%", "itl_p95_ms", "program_span")
    zero = _perf_module("metrics", "serve_moe_zero_share.py")
    assert (zero.LAYER, zero.SOURCE, zero.SCOPE) == (
        "kernels", "device_trace", "paged_decoder/moe_zero")
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        for zeros, sent, extra in ((40, 120, {}), (10, 30, {}),
                                   (None, None, {"other": 1})):
            with tracing.span("serving.decode_tick") as sp:
                if zeros is not None:
                    sp.set_attr("moe_zero_assignments", zeros)
                    sp.set_attr("moe_assignments", sent)
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    run = types.SimpleNamespace(spans=spans)
    assert reader.compute(run) == pytest.approx(100.0 * 50 / 150)
    assert reader.compute(types.SimpleNamespace(spans=[])) is None
    assert zero.compute(types.SimpleNamespace(trace=None)) is None
