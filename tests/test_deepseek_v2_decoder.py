"""The DeepSeek-V2 block (a LATENT cache: one compressed row a position
for all heads, served in the absorbed form; a group-limited softmax
router whose weights take a factor and are not renormalised; a dense
layer before the sparse ones, a share of the routed experts beside the
shared ones) through `build_lm_paged_decoder` against the plain
EXPANDED reference `perf/reference/deepseek_v2.py`, at toy widths on
the CPU with seeded random float32 weights.

The toy keeps what makes the model: a query/key head (8 + 8) wider
than a value head (8), a row (32 + 8 = 40) that needs the pad to the
lane grid (128), layer 0 dense and two sparse layers, 16 routed experts
in 8 groups of which a token keeps 3 and takes 5 (more than the groups
kept: else the limit is the identity), 4 held (two whole groups: the
four-share test cuts 16 into four such), YaRN over an original context
(16) shorter than the sequences.  What is compared is LOGITS, never
tokens.
"""
import functools
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.kernels import paged_attention
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, L = 97, 48, 8, 3
QL, KVL, DN, DR, DV = 24, 32, 8, 8, 8   # ranks; nope, rope, value a head
E, HELD, FIRST, K = 16, 4, 4, 5         # routed, held here, from, a token
G, TG = 8, 3                            # groups, groups kept
F, FD, FS = 16, 40, 32                  # an expert, the dense layer, shared
BS, NB = 4, 10                          # 40 positions
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 16,
        "type": "yarn"}
CONFIG = {"num_attention_heads": H, "q_lora_rank": QL, "kv_lora_rank": KVL,
          "qk_nope_head_dim": DN, "qk_rope_head_dim": DR, "v_head_dim": DV,
          "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
          "first_k_dense_replace": 1, "num_hidden_layers": L, "n_group": G,
          "topk_group": TG, "num_experts_per_tok": K,
          "routed_scaling_factor": 16, "norm_topk_prob": False,
          "moe_intermediate_size": F, "first_local_expert": FIRST}
# float32 weights and pool: the same float32 sums in another order
# (absorbed against expanded, grouped matmul against a masked scan):
# measured 8e-7 to 1.3e-6
TOL_FP32 = 1e-4
# bf16 pool: the latent row rounded to 8 bits of mantissa on its way
# into the table; over three layers measured 5e-3 to 8e-3
TOL_BF16_POOL = 4e-2


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(ROOT, "perf", "reference", "deepseek_v2.py"),
            "ref_deepseek_v2")
ROPE = {"rope_type": "yarn", "rope_theta": 10000, "factor": 40,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": REF.table_gain(CONFIG)}


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="deepseek_v2", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, norm_eps=1e-6, n_experts=E,
        experts_per_token=K, norm_topk_prob=False, rope_parameters=ROPE,
        mlp_layer_types=[lm_block.DENSE] + [lm_block.SPARSE] * (L - 1),
        dense_d_inner=FD, experts_first=FIRST, experts_held=HELD,
        shared_d_inner=FS, router="softmax", routed_scaling_factor=16.0,
        n_group=G, topk_group=TG, q_lora_rank=QL, kv_lora_rank=KVL,
        qk_nope_head_dim=DN, qk_rope_head_dim=DR, v_head_dim=DV,
        attention_multiplier=REF.softmax_scale(CONFIG)), **over))


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
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router" in n else 0.1,
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
    assert pool_v == () and pool_k.shape[-1] == 128       # one array
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
        _, pool_k, pool_v, hit, held, here = dec.step(*args)
        assert pool_v == ()
        counted.append((np.asarray(hit), np.asarray(held), np.asarray(here),
                        np.asarray(r["experts"])[:, act]))
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if routing:
        return out, {k: np.concatenate([r[k] for r in routed], 1)
                     for k in routed[0]}, counted
    return out


SEQ = list(np.random.RandomState(7).randint(0, V, 37))   # over 9 blocks


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_latent_table_matches_the_expanded_reference(kv_dtype, tol):
    """37 positions (prompt, then decode: one position a step either
    way) through the dense layer and the two sparse ones, the ABSORBED
    step over the latent table against the reference's keys and values
    widened for every head."""
    dec = _decoder(kv_dtype)
    assert (dec.table_layers, dec.ring_layers, dec.moe_layers) == (3, 0, 2)
    # one row of 40 columns a position a layer, stored 128 wide
    assert dec.bytes_per_block == L * BS * 128 * (4 if kv_dtype == "fp32"
                                                  else 2)
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    assert routing["experts"].shape == (2, len(SEQ), K)
    out = REF.compare(g, CONFIG, np.asarray(SEQ, np.int32), got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["late_rms_err"] <= tol and out["router_rel_err"] <= 1e-4, out


def test_absorbed_equals_expanded_at_float32_to_rounding():
    dec = _decoder()
    g = _weights(dec, seed=4)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, np.asarray(SEQ, np.int32), got, routing)
    assert ok["logits_rel_err"] <= 1e-5 and ok["logits_rms_err"] <= 1e-5
    assert ok["router_rel_err"] <= 1e-5 and ok["routing_agree"] == 1.0


@pytest.mark.parametrize("what", ["below"] + list(REF.FAULTS))
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """What `compare` must tell apart at these widths: the whole model
    in bfloat16, and the six seeded faults, each by at least one of the
    numbers the cell bounds and far above the float32 decoder."""
    dec = _decoder()
    g = _weights(dec)
    ids = np.asarray(SEQ, np.int32)
    out = (REF.below(g, CONFIG, ids) if what == "below"
           else REF.compare(g, CONFIG, ids, *REF.forward(
               g, CONFIG, ids, fault=what)))
    by = {"no_group_limit": "router_rel_err",
          "renormalised": "router_rel_err"}.get(what, "logits_rms_err")
    assert out[by] > 100 * TOL_FP32, (what, out)
    if what == "no_group_limit":
        # the reference followed the system's experts, so only the
        # router's own number sees them
        assert out["logits_rms_err"] == 0.0
        limited = np.asarray(REF.forward(g, CONFIG, ids)[1]["experts"])
        free = np.asarray(REF.forward(g, CONFIG, ids,
                                      fault=what)[1]["experts"])
        assert len({e // (E // G) for e in limited[0, 0]}) <= TG
        assert not np.array_equal(limited, free)
    if what in ("plain_rope", "k_pe_unrotated"):
        # a position signal: the first position has nothing to turn
        moved = np.abs(np.asarray(REF.forward(g, CONFIG, ids, fault=what)[0])
                       - np.asarray(REF.forward(g, CONFIG, ids)[0])).max(-1)
        assert moved[0] == 0.0 and moved[1:].min() > 0.0


@pytest.mark.parametrize("where,moves", [("one_position", False),
                                         ("every_block_edge", True)])
def test_the_p99_error_skips_one_position_and_sees_every_block(where,
                                                               moves):
    """`logits_p99_err` is the number the cell bounds in place of the
    largest difference: an error at ONE of 320 positions moves the
    largest alone (a position the equations carry badly does that to
    any rounding), an error at the first position of every block of 16
    moves both."""
    g = _weights(_decoder())
    ids = np.random.RandomState(3).randint(0, V, 320).astype(np.int32)
    got, routing = REF.forward(g, CONFIG, ids)
    got = np.array(got, np.float32)
    at = [161] if where == "one_position" else list(range(0, 320, 16))
    got[at] += 0.05 * np.abs(got).max()
    out = REF.compare(g, CONFIG, ids, got, routing)
    assert out["logits_rel_err"] == pytest.approx(0.05, rel=1e-3)
    assert (out["logits_p99_err"] > 0.049) == moves, out
    assert out["logits_p99_err"] <= out["logits_rel_err"]


def test_the_derived_keys_are_the_references_own_derivations():
    """The served description takes scale, frequencies and gain from
    keys the configuration file DERIVES; the reference derives them
    from `rope_scaling` itself: the two agree, here and in the file."""
    np.testing.assert_allclose(
        lm_block.yarn_inv_freq(ROPE, DR), REF.inv_freq(CONFIG), rtol=1e-12)
    with open(os.path.join(ROOT, "perf", "configs",
                           "deepseek-v2-1chip.json")) as f:
        m = json.load(f)
    assert m["softmax_scale"] == pytest.approx(REF.softmax_scale(m),
                                               rel=1e-12)
    assert m["softmax_scale"] == pytest.approx(0.11472, abs=1e-5)
    assert m["rope_parameters"]["attention_factor"] == REF.table_gain(m) == 1
    np.testing.assert_allclose(
        lm_block.yarn_inv_freq(m["rope_parameters"], 64), REF.inv_freq(m),
        rtol=1e-12)
    assert REF.mscale(40, 0.707) == pytest.approx(1.26081, abs=1e-5)


def test_the_four_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The parts of one sparse layer's result that four shares give
    (each its 4 of the 16 routed experts, two whole groups, through
    `moe_ffn`), with the shared expert counted once, are the uncut
    reference's layer; three shares are not; and each share is the
    reference given the same share."""
    g = _weights(_decoder(), seed=2)
    r = np.random.RandomState(3)
    x = jnp.asarray(r.normal(0, 3, (11, D)), jnp.float32)
    whole = {n: jnp.asarray(r.normal(0, 0.1, s), jnp.float32)
             for n, s in (("gate", (E, D, F)), ("up", (E, D, F)),
                          ("down", (E, F, D)))}
    router = g["layer_1.router.w_0"]
    shared_w = {f"shared_{n}": g[f"layer_1.shared_{n}.w_0"]
                for n in ("gate", "up", "down")}
    own = jnp.full((11, K), -1, jnp.int32)

    def reference(experts, first):
        out, _ = REF._ffn(x, {"norm": jnp.ones(D), "router": router,
                              **experts, **shared_w}, own,
                          jnp.asarray(16.0), top_k=K, n_group=G,
                          topk_group=TG, first=first, eps=1e-6)
        return np.asarray(out) - np.asarray(x)

    normed = REF._rms(x, jnp.ones(D), 1e-6)
    shared = np.asarray(lm_block.swiglu(normed, *shared_w.values()))
    parts = []
    for first in range(0, E, HELD):
        cut = {n: w[first:first + HELD] for n, w in whole.items()}
        y, hit, (top_w, top_e) = lm_block.moe_ffn(
            _block(experts_first=first, experts_held=HELD), normed,
            router, *cut.values())
        parts.append(np.asarray(y))
        assert 0 <= int(hit) <= HELD
        # a token's experts come from at most 3 of the 8 groups
        assert max(len(set(row // (E // G))) for row in
                   np.asarray(top_e)) <= TG
        np.testing.assert_allclose(parts[-1] + shared,
                                   reference(cut, first), atol=5e-5)
    want = reference(whole, 0)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=5e-5)
    assert np.abs(sum(parts[:3]) + shared - want).max() > 1e-3


def _loop_route(probs, n_group, topk_group, k):
    """Group-limited choice by a plain loop: ties to the lower index."""
    e_n = len(probs)
    per = e_n // n_group
    scores = [max(probs[g * per:(g + 1) * per]) for g in range(n_group)]
    kept = sorted(range(n_group), key=lambda g: (-scores[g], g))[
        :topk_group]
    left = [(p if e // per in kept else 0.0) for e, p in enumerate(probs)]
    return sorted(range(e_n), key=lambda e: (-left[e], e))[:k]


def test_route_is_group_limited_with_ties_to_the_lower_index():
    """`lm_block.route` with 8 groups of which 3 are kept against a
    plain loop over softmax probabilities, ties of groups and of
    experts included; the weights are p x 16, not renormalised."""
    r = np.random.RandomState(5)
    m = jnp.asarray(r.normal(0, 1, (33, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.5, (D, E)), jnp.float32)
    top_w, top_e = lm_block.route(_block(), m, w)
    logits = np.asarray(m, np.float64) @ np.asarray(w, np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want_e = np.asarray([_loop_route(list(row), G, TG, K) for row in p])
    assert np.array_equal(np.asarray(top_e), want_e)
    np.testing.assert_allclose(
        top_w, 16.0 * np.take_along_axis(p, want_e, -1), rtol=1e-5)
    # the limit changed somebody's choice
    free_w, free_e = lm_block.route(_block(n_group=1, topk_group=1), m, w)
    assert not np.array_equal(np.asarray(free_e), want_e)
    # all scores equal: groups 0, 1, 2 and their lowest experts
    _, tied = lm_block.route(_block(), m, jnp.zeros((D, E)))
    assert np.array_equal(np.asarray(tied), np.tile(np.arange(K), (33, 1)))
    # two columns equal: the lower expert, and the lower group, first
    dup = np.asarray(w).copy()
    dup[:, 9] = dup[:, 2]
    _, e_dup = lm_block.route(_block(), m, jnp.asarray(dup))
    p_dup = np.exp(np.asarray(m, np.float64) @ dup)
    p_dup /= p_dup.sum(-1, keepdims=True)
    p_dup[:, 9] = p_dup[:, 2]
    assert np.array_equal(np.asarray(e_dup), np.asarray(
        [_loop_route(list(row), G, TG, K) for row in p_dup]))


def test_route_without_groups_is_bit_identical_to_the_plain_top_k():
    """`n_group` 1 takes the branch every other block takes: the same
    weights and experts, bit for bit, as a description without the
    fields, softmax and sigmoid."""
    import jax

    r = np.random.RandomState(6)
    m = jnp.asarray(r.normal(0, 1, (9, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3, (D, E)), jnp.float32)
    olmoe = lm_block.olmoe(n_experts=E, experts_per_token=K)
    probs = jax.nn.softmax(jnp.dot(m, w, precision="highest"), axis=-1)
    want_w, want_e = jax.lax.top_k(probs, K)
    got_w, got_e = lm_block.route(olmoe, m, w)
    assert olmoe.n_group == 1 == olmoe.topk_group
    assert np.array_equal(np.asarray(got_w), np.asarray(want_w))
    assert np.array_equal(np.asarray(got_e), np.asarray(want_e))
    scaled, scaled_e = lm_block.route(
        _block(n_group=1, topk_group=1), m, w)
    assert np.array_equal(np.asarray(scaled_e), np.asarray(want_e))
    assert np.array_equal(np.asarray(scaled), np.asarray(want_w * 16.0))


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["xla_gather", "pallas_interpreted"])
def test_lanes_out_of_step_bit_identical_to_the_same_sequence_alone(
        streamed, monkeypatch):
    """Three sequences of different lengths that start at different
    ticks in one four-lane step, the sequence in another lane and other
    blocks than alone: bit for bit the same logits, through the XLA
    gather and through the latent form of the Pallas kernel under the
    interpreter (whose logits are the gather's to rounding); the step's
    counts are the routing's own, of the live lanes alone."""
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
    for hit, held, here, experts in counted:
        on = (experts >= FIRST) & (experts < FIRST + HELD)
        assert np.array_equal(held, on.sum((1, 2)))
        assert np.array_equal(here, on.any(2).sum(1))
        assert hit.shape == held.shape == here.shape == (2,)
        assert (here <= held).all() and (hit <= HELD).all()
    assert sum(int(c[2].sum()) for c in counted) > 0
    if streamed:
        monkeypatch.undo()
        plain = _decoder()
        (want,) = _drive(plain, g, [seq], slots=4, lanes=[2])
        assert np.abs(alone - want).max() <= 2e-5 * np.abs(want).max()


def test_description_is_checked_and_laid_out():
    """What is not built is refused by name; the seven attention
    arrays have the published layout."""
    with pytest.raises(ValueError, match="groups are equal parts"):
        _block(n_group=3)
    with pytest.raises(ValueError, match="groups are equal parts"):
        _block(topk_group=9)
    with pytest.raises(NotImplementedError, match="group-limited choice"):
        _decoder(router="sigmoid")
    with pytest.raises(NotImplementedError, match="sigmoid router alone"):
        _decoder(norm_topk_prob=True)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        _decoder(qk_norm=True)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        _decoder(layer_types=[lm_block.SLIDING] * L, window=8)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        _decoder(qk_rope_head_dim=7)
    with pytest.raises(NotImplementedError, match="int8 pool"):
        _decoder(kv_dtype="int8")
    dec = _decoder()
    shapes = dec.state_shapes
    assert shapes["layer_0.q_a_proj.w_0"] == (D, QL)
    assert shapes["layer_0.q_a_norm.scale_0"] == (QL,)
    assert shapes["layer_0.q_b_proj.w_0"] == (QL, H * (DN + DR))
    assert shapes["layer_0.kv_a_proj.w_0"] == (D, KVL + DR)
    assert shapes["layer_0.kv_a_norm.scale_0"] == (KVL,)
    assert shapes["layer_0.kv_b_proj.w_0"] == (KVL, H * (DN + DV))
    assert shapes["layer_0.o_proj.w_0"] == (H * DV, D)
    assert "layer_0.q_proj.w_0" not in shapes
    assert shapes["layer_0.ffn_gate.w_0"] == (D, FD)
    assert shapes["layer_1.router.w_0"] == (D, E)
    assert "layer_1.router_bias.b_0" not in shapes
    assert shapes["layer_1.experts_gate.w_0"] == (HELD, D, F)
    assert shapes["layer_1.shared_down.w_0"] == (FS, D)
    assert dec.step_counters == ("moe_experts_hit", "moe_rows_held",
                                 "moe_tokens_here")
    # a router without groups counts no tokens here (K-EXAONE's step
    # is the program it was)
    plain = _decoder(n_group=1, topk_group=1)
    assert plain.step_counters == ("moe_experts_hit", "moe_rows_held")
    counts = dec.tick_counts(np.asarray([0, 5, 17]), 4)
    assert counts["latent_rows"] == L * (1 + 6 + 18)
    assert counts["kv_pages_table"] == 4 * L * NB
    assert "latent_rows" not in _decoder_opt().tick_counts(
        np.asarray([3]), 2)


def _decoder_opt():
    from paddle_tpu.core import framework as fw

    fw.reset_unique_names()
    return build_lm_paged_decoder(V, BS, NB, d_model=32, n_heads=4,
                                  n_layers=1, platform="cpu")[1]


def test_generation_server_serves_the_block_and_refuses_by_name():
    """Requests of several blocks through `GenerationServer`,
    continuously batched, give the tokens of the same request alone;
    the tick spans carry `latent_rows` and, with the tokens of the tick
    read, `moe_rows_held` and `moe_tokens_here`; a draft model and
    `step_window` are refused by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    assert set(dec.refuses) == {"draft_model"}
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
    assert ticks and all(a["moe_layers"] == 2 and a["latent_rows"] > 0
                         for a in ticks)
    read = [a for a in ticks if a["ahead"]]
    assert read and all(("moe_tokens_here" in a) == bool(a["ahead"])
                        for a in ticks)
    assert all(a["moe_tokens_here"] <= min(2 * 2, a["moe_rows_held"])
               for a in read)
    assert max(a["latent_rows"] for a in ticks) >= L * 2 * 20


def test_the_prefix_cache_works_on_the_latent_table():
    """A cached block holds every layer's latent rows, so a shared
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
    wrong = REF.served(g, CONFIG, requests, fault="k_pe_unrotated")
    assert wrong["served_argmax_agree"] < 0.9, wrong
    assert wrong["served_gap_rms"] > 1e-3


def test_scopes_name_the_latent_path_and_the_router():
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("latent_q", "latent_kv", "latent_absorb", "attention",
                 "kv_write", "attn_out", "dense_ffn", "moe_router",
                 "moe_dispatch", "moe_experts", "moe_combine",
                 "shared_expert", "rope"):
        assert f"paged_decoder/{part}" in text, part
    assert "paged_decoder/qkv" not in text
    scopes = dec.compiler_scopes
    assert scopes["g[\\'layer_0.q_b_proj.w_0\\']"] == \
        "paged_decoder/latent_q"
    assert scopes["g[\\'layer_1.kv_a_proj.w_0\\']"] == \
        "paged_decoder/latent_kv"
    assert scopes["g[\\'layer_1.kv_b_proj.w_0\\']"] == \
        "paged_decoder/latent_absorb"
    assert scopes["g[\\'layer_2.o_proj.w_0\\']"] == "paged_decoder/attn_out"


def _config_file():
    with open(os.path.join(ROOT, "perf", "configs",
                           "deepseek-v2-1chip.json")) as f:
        return json.load(f)


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/deepseek-v2-1chip.json's `block`, read as the
    benchmark's job reads it, builds the decoder at the published
    widths (shapes only: nothing is allocated), and the parameter and
    cache arithmetic the file states is the decoder's own."""
    m = _config_file()
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.latent and (spec.n_group, spec.topk_group) == (8, 3)
    assert (spec.router, spec.router_bias, spec.routed_scaling_factor,
            spec.norm_topk_prob) == ("softmax", False, 16, False)
    assert spec.held == (0, 40) and spec.n_experts == 160
    assert spec.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert m["published"] == {"num_hidden_layers": 60,
                              "n_routed_experts": 160, "vocab_size": 102400}
    assert set(m["assumed"]) >= {"rope_columns", "norm_placement", "k_pe",
                                 "training_losses", "weights"}
    # every published width, unchanged
    assert (m["hidden_size"], m["num_attention_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["intermediate_size"],
            m["moe_intermediate_size"], m["num_routed_experts"],
            m["n_group"], m["topk_group"], m["num_experts_per_tok"],
            m["routed_scaling_factor"], m["n_shared_experts"]) == (
                5120, 128, 1536, 512, 128, 64, 128, 12288, 1536, 160, 8, 3,
                6, 16, 2)
    assert m["rope_scaling"]["factor"] == 40
    assert m["rope_scaling"]["original_max_position_embeddings"] == 4096
    assert m["num_experts"] == m["n_routed_experts"] == 40
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 256, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu",
        block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas:latent"
    assert (dec.table_layers, dec.ring_layers, dec.moe_layers) == (5, 0, 4)
    shapes = dec.state_shapes
    assert shapes["layer_0.q_b_proj.w_0"] == (1536, 128 * 192)
    assert shapes["layer_0.kv_a_proj.w_0"] == (5120, 576)
    assert shapes["layer_0.kv_b_proj.w_0"] == (512, 128 * 256)
    assert shapes["layer_0.o_proj.w_0"] == (128 * 128, 5120)
    assert shapes["layer_0.ffn_gate.w_0"] == (5120, 12288)
    assert shapes["layer_1.experts_down.w_0"] == (40, 1536, 5120)
    assert shapes["layer_1.shared_gate.w_0"] == (5120, 3072)
    assert shapes["layer_1.router.w_0"] == (5120, 160)
    assert shapes["lm_head.w_0"] == (5120, 25600)

    def params(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix))

    assert round(params("layer_0.") / 1e6, 1) == 338.0   # the file's counts
    assert round(params("layer_1.") / 1e6, 1) == 1141.0
    weights_gb = params("") * 2 / 1e9
    assert round(weights_gb, 2) == 10.33
    # ONE row a position a layer, at most 1280 B
    assert dec.bytes_per_block // 16 == 5 * 1280
    pool_gb = dec.bytes_per_block * 64 * 256 / 1e9
    assert round(pool_gb, 2) == 1.68
    assert weights_gb + pool_gb >= 11.8                  # held, of 16
    assert dec.attention_tiling == ((64, 8), None)
    ids = np.zeros(9, np.int32)
    g = _weights(_decoder())
    assert set(m["compare"]["limits"]) <= set(
        REF.compare(g, CONFIG, ids, *REF.forward(g, CONFIG, ids)))
    assert set(m["compare"]["served_limits"]) <= set(
        REF.served(g, CONFIG, [(ids, 2)]))
    assert m["reference"] == "deepseek_v2"


def test_traffic_file_is_agent96_at_64_clients_under_the_latent_job():
    def load(name):
        with open(os.path.join(ROOT, "perf", "traffic", name)) as f:
            return json.load(f)

    mine, agent96 = load("agent64.json"), load("agent96.json")
    assert mine["job"] == "serve_lm_latent" != agent96["job"]
    assert (mine["clients"], mine["slots"]) == (64, 64)
    for key in set(agent96) - {"job", "what", "ramp", "clients", "slots"}:
        assert mine[key] == agent96[key], key


def _job():
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        return common.load_module(os.path.join(
            ROOT, "perf", "jobs", "serve_lm_latent.py"))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))


def test_the_job_makes_the_router_even_and_the_loads_with_it():
    """perf/jobs/serve_lm_latent.py `even`: over router inputs that
    share a large common component (what seeded weights give: some
    experts popular, some starved) the matrix without its component
    along the mean input gives every expert the same mean logit and
    far more even loads under the group-limited choice; the matrix
    keeps its dtype."""
    job = _job()
    r = np.random.RandomState(4)
    inputs = jnp.asarray(r.normal(0, 1, (2048, D)) + 3.0 * r.normal(
        0, 1, (1, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3, (D, E)), jnp.bfloat16)

    def loads(matrix):
        _, chosen = lm_block.route(_block(), inputs, matrix)
        return np.bincount(np.asarray(chosen).reshape(-1),
                           minlength=E) / (2048 * K / E)

    seeded = loads(w)
    assert seeded.max() > 2.0 and seeded.min() < 0.3
    fitted = job.even(w, inputs.mean(0))
    assert fitted.dtype == jnp.bfloat16 and fitted.shape == (D, E)
    mean_logit = np.asarray(inputs.mean(0)) @ np.asarray(fitted, np.float32)
    assert np.abs(mean_logit).max() < 0.05 * np.abs(
        np.asarray(inputs.mean(0)) @ np.asarray(w, np.float32)).max()
    after = loads(fitted)
    assert after.max() < 1.6 and after.min() > 0.5, after


def test_the_cost_file_counts_a_row_once_for_both_products():
    cost = _load(os.path.join(ROOT, "perf", "latent_attention_cost.py"),
                 "latent_attention_cost")
    assert cost.row_ops(128, 512, 64) == 2 * 128 * (576 + 512) == 278528
    assert cost.row_bytes(512, 64) == 1152
    call = cost.attention_call(1000, 128, 512, 64)
    assert call["flops"] / call["bytes"] == pytest.approx(241.8, abs=0.1)
