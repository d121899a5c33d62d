"""The K-EXAONE block (a dense layer before the sparse ones, a sigmoid
router with a choice bias and a scaling factor, the QK-norm per head on
grouped heads, RoPE on the sliding layers only, a share of the routed
experts beside a shared expert, ring beside table) through
`build_lm_paged_decoder` against the plain reference
`perf/reference/k_exaone.py`, at toy widths on the CPU with seeded
random float32 weights.

The toy keeps what makes the model: layer 0 dense and seven sparse
layers, the layer kinds of period 4 (L L L G), 8 query heads a K/V
head, `H * DH` (128) not `D` (48), 4 of 16 routed experts held (a
quarter: the eight-share test cuts 16 into eight pairs), a window (8
positions, 2 blocks) shorter than the sequences, so every comparison
runs past the ring's wrap.  What is compared is LOGITS, never tokens.
"""
import dataclasses
import functools
import importlib.util
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.kernels import grouped_matmul
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, HKV, DH, L = 97, 48, 16, 2, 8, 8
E, HELD, FIRST, K = 16, 4, 4, 3         # routed, held here, from, a token
F, FD, FS = 16, 40, 16                  # an expert, the dense layer, shared
BS, NB, WINDOW = 4, 10, 8               # 40 positions, a ring of 2 blocks
KINDS = ([lm_block.SLIDING] * 3 + [lm_block.FULL]) * 2
MLP = [lm_block.DENSE] + [lm_block.SPARSE] * 7
ROPE = {"rope_type": "default", "rope_theta": 500.0}
CONFIG = {"num_attention_heads": H, "num_key_value_heads": HKV,
          "head_dim": DH, "num_experts_per_tok": K, "rms_norm_eps": 1e-5,
          "norm_topk_prob": True, "num_hidden_layers": L,
          "layer_types": KINDS, "mlp_layer_types": MLP,
          "sliding_window": WINDOW, "rope_parameters": ROPE,
          "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
          "moe_intermediate_size": F, "num_routed_experts": E,
          "num_experts": HELD, "first_local_expert": FIRST}
# float32 weights and pool: the same float32 sums in another order
# (grouped matmul against a masked scan over the experts, ring and table
# against a banded mask over recomputed keys): measured 4e-7 to 8e-7
TOL_FP32 = 1e-4
# bf16 pool: K and V rounded to 8 bits of mantissa on their way into
# table and ring; over eight layers measured 2e-3 to 6e-3
TOL_BF16_POOL = 4e-2


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "k_exaone.py")
    spec = importlib.util.spec_from_file_location("ref_k_exaone", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="exaone_moe", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, qk_norm=True, qk_norm_per_head=True,
        norm_eps=1e-5, n_experts=E, experts_per_token=K,
        norm_topk_prob=True, n_kv_heads=HKV, d_head=DH, layer_types=KINDS,
        window=WINDOW, rope_parameters=ROPE,
        rope_layers=[lm_block.SLIDING], mlp_layer_types=MLP,
        dense_d_inner=FD, experts_first=FIRST, experts_held=HELD,
        shared_d_inner=FS, router="sigmoid", router_bias=True,
        routed_scaling_factor=2.5), **over))


def _decoder(kv_dtype="fp32", **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu", block=_block(**over))
    assert startup is None
    return dec


def _weights(dec, seed=0):
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router" in n else 0.1,
                     shape).astype(np.float32)
        g[n] = jnp.asarray(1.0 + w if ".scale_" in n else w)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, routing=False):
    """Teacher-force each of `seqs` through `step` in its own slot, the
    tables taken from a `PagedKVCache` and the rings from the lanes, as
    the server takes them; returns each sequence's [len, V] logits
    (and lane 0's routing stacked over positions, and what the steps
    counted: experts hit and rows held, a sparse layer)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    ring = dec.window_blocks_per_seq
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = dec.init_pool(
        1 + slots * NB, window_blocks=1 + slots * ring)
    tables = np.zeros((slots, NB), np.int32)
    rings = dec.slot_rings(slots)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed, counted = [[] for _ in seqs], [], []
    for pos in range(max(len(s) for s in seqs)):
        toks = np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane in zip(seqs, lanes):
            if pos < len(s):
                toks[lane], act[lane] = s[pos], True
        args = (g, pool_k, pool_v, (tables, rings),
                np.where(act, pos, 0).astype(np.int32), toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        routed.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                       for k, v in r.items()})
        _, pool_k, pool_v, hit, held = dec.step(*args)
        counted.append((np.asarray(hit), np.asarray(held),
                        np.asarray(r["experts"])[:, act]))
        for i, (s, lane) in enumerate(zip(seqs, lanes)):
            if pos < len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if routing:
        return out, {k: np.concatenate([r[k] for r in routed], 1)
                     for k in routed[0]}, counted
    return out


def _ref_logits(g, seq, **kw):
    return np.asarray(REF.forward(g, CONFIG, np.asarray(seq, np.int32),
                                  **kw)[0])


SEQ = list(np.random.RandomState(7).randint(0, V, 37))   # 4.6 windows


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_table_and_ring_match_reference_past_the_wrap(kv_dtype, tol):
    """37 positions (prompt, then decode: one position a step either
    way) through the dense layer, the seven sparse ones and both kinds
    of cache, the ring of 8 wrapping four times, against the
    reference's banded and causal masks over the whole sequence."""
    dec = _decoder(kv_dtype)
    assert dec.window_blocks_per_seq == WINDOW // BS and dec.moe_layers == 7
    assert (dec.table_layers, dec.ring_layers) == (2, 6)
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    assert routing["experts"].shape == (7, len(SEQ), K)   # sparse layers
    out = REF.compare(g, CONFIG, np.asarray(SEQ, np.int32), got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["past_window_rms_err"] <= tol
    assert abs(out["window_edge_share"]) <= max(10 * tol, 1e-2), out
    assert out["router_rel_err"] <= 1e-4, out


@pytest.mark.parametrize("what", ["below"] + list(REF.FAULTS))
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """What `compare` must tell apart at these widths: the whole model
    in bfloat16, and the five seeded faults (the full layers rotated,
    the choice bias in the weights, the scaling factor left out, a
    window of one key more, a softmax router), each by at least one of
    the numbers the cell bounds and far above the float32 decoder."""
    dec = _decoder()
    g = _weights(dec)
    ids = np.asarray(SEQ, np.int32)
    out = (REF.below(g, CONFIG, ids) if what == "below"
           else REF.compare(g, CONFIG, ids, *REF.forward(
               g, CONFIG, ids, fault=what)))
    by = {"below": "logits_rms_err", "full_rope": "logits_rms_err",
          "bias_in_weights": "router_rel_err",
          "no_scaling": "router_rel_err", "window_129": "window_edge_share",
          "softmax": "router_rel_err"}[what]
    limit = 0.5 if by == "window_edge_share" else 100 * TOL_FP32
    assert out[by] > limit, (what, out)
    if what == "window_129":
        # the reference's own twin: exactly the whole step, and nothing
        # before the window has been left behind
        assert out["window_edge_share"] == pytest.approx(1.0, abs=1e-6)
        moved = np.abs(_ref_logits(g, SEQ, fault=what)
                       - _ref_logits(g, SEQ)).max(-1)
        assert moved[:WINDOW].max() == 0.0 and moved[WINDOW:].min() > 0.0
    if what == "full_rope":
        # rotating the full layers changes every position but the first
        moved = np.abs(_ref_logits(g, SEQ, fault=what)
                       - _ref_logits(g, SEQ)).max(-1)
        assert moved[0] == 0.0 and moved[1:].min() > 0.0


def test_the_float32_decoder_reads_under_every_limit_the_faults_pass():
    dec = _decoder()
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, np.asarray(SEQ, np.int32), got, routing)
    assert ok["logits_rms_err"] <= TOL_FP32 and ok["router_rel_err"] <= 1e-4
    assert abs(ok["window_edge_share"]) <= 1e-3
    assert ok["routing_agree"] == 1.0


def test_the_eight_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The parts of one sparse layer's result that eight shares give
    (each its 2 of the 16 routed experts, through `moe_ffn`), with the
    shared expert counted once, are the uncut reference's layer; seven
    shares are not; and each share is the reference given the same
    share."""
    held = E // 8
    g = _weights(_decoder(), seed=2)
    r = np.random.RandomState(3)
    x = jnp.asarray(r.normal(0, 3, (11, D)), jnp.float32)
    whole = {n: jnp.asarray(r.normal(0, 0.1, s), jnp.float32)
             for n, s in (("gate", (E, D, F)), ("up", (E, D, F)),
                          ("down", (E, F, D)))}
    router, bias = g["layer_1.router.w_0"], g["layer_1.router_bias.b_0"]
    shared_w = {f"shared_{n}": g[f"layer_1.shared_{n}.w_0"]
                for n in ("gate", "up", "down")}
    own = jnp.full((11, K), -1, jnp.int32)

    def reference(experts, first):
        # x + (experts + shared) of RMSNorm(x) under a unit scale
        out, _ = REF._ffn(x, {"norm": jnp.ones(D), "router": router,
                              "bias": bias, **experts, **shared_w}, own,
                          jnp.asarray(2.5), top_k=K, first=first, eps=1e-5)
        return np.asarray(out) - np.asarray(x)

    normed = REF._rms(x, jnp.ones(D), 1e-5)
    shared = np.asarray(lm_block.swiglu(normed, *shared_w.values()))
    parts = []
    for first in range(0, E, held):
        cut = {n: w[first:first + held] for n, w in whole.items()}
        y, hit, (top_w, _) = lm_block.moe_ffn(
            _block(experts_first=first, experts_held=held), normed,
            router, *cut.values(), b_router=bias)
        parts.append(np.asarray(y))
        assert 0 <= int(hit) <= held
        # the router's weights are over all 16 (renormalised, times
        # 2.5), not shared out over the 2 held
        np.testing.assert_allclose(np.asarray(top_w).sum(-1), 2.5,
                                   atol=1e-5)
        np.testing.assert_allclose(parts[-1] + shared,
                                   reference(cut, first), atol=2e-5)
    want = reference(whole, 0)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    assert np.abs(sum(parts[:7]) + shared - want).max() > 1e-3


def test_router_is_sigmoid_scores_a_choice_bias_renormalised_and_scaled():
    """`lm_block.route` under `router: "sigmoid"`: the k largest of
    sigmoid(logits) + b, weighed by sigmoid(logits) alone, over their
    sum, times the factor; a tie goes to the lower index; the softmax
    router of the other blocks is untouched by the new fields."""
    r = np.random.RandomState(5)
    m = jnp.asarray(r.normal(0, 1, (9, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3, (D, E)), jnp.float32)
    b = jnp.asarray(r.normal(0, 0.2, E), jnp.float32)
    top_w, top_e = lm_block.route(_block(), m, w, b)
    s = 1.0 / (1.0 + np.exp(-np.asarray(m, np.float64) @ np.asarray(w)))
    want_e = np.argsort(-(s + np.asarray(b)), -1, kind="stable")[:, :K]
    assert np.array_equal(np.asarray(top_e), want_e)
    chosen = np.take_along_axis(s, want_e, -1)
    np.testing.assert_allclose(
        top_w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # the bias moved the choice somewhere, and never a weight
    plain_w, plain_e = lm_block.route(_block(router_bias=False), m, w)
    assert not np.array_equal(np.asarray(plain_e), want_e)
    # all scores equal: the lowest indices
    _, tied = lm_block.route(_block(), m, jnp.zeros((D, E)), jnp.zeros(E))
    assert np.array_equal(np.asarray(tied), np.tile(np.arange(K), (9, 1)))
    soft = lm_block.olmoe(n_experts=E, experts_per_token=K)
    soft_w, _ = lm_block.route(soft, m, w)
    assert float(np.asarray(soft_w).sum(-1).max()) < 1.0


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
def test_batched_slot_bit_identical_to_the_same_sequence_alone(
        kernel, monkeypatch):
    """Three sequences of different lengths in one call of the same
    four-lane step, the sequence in another lane and other table and
    ring blocks than alone: bit for bit the same logits, through
    `ragged_dot` and through the Pallas grouped matmul under the
    interpreter (three quarters of the rows sort past the last group:
    the held share); and the step's count of held rows is the routing's
    own, of the live lanes alone."""
    if kernel:
        monkeypatch.setattr(
            grouped_matmul, "select_grouped_matmul", functools.partial(
                grouped_matmul.select_grouped_matmul, interpret=True))
    dec = _decoder()
    g = _weights(dec, seed=3)
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,) = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    together, _, counted = _drive(dec, g, [others[0], SEQ, others[1]],
                                  slots=4, lanes=[3, 1, 0], routing=True)
    assert np.array_equal(together[1], alone)
    assert dec.expert_kernel == (grouped_matmul.NAME if kernel
                                 else "xla:not_tpu")
    for hit, held, experts in counted:
        here = (experts >= FIRST) & (experts < FIRST + HELD)
        assert np.array_equal(held, here.sum((1, 2)))      # live lanes'
        assert hit.shape == held.shape == (7,) and (hit <= HELD).all()
    assert sum(int(c[1].sum()) for c in counted) > 0


def test_description_is_hashable_from_json_and_checked():
    """The configuration's JSON lists become tuples; what is not built
    is refused by name."""
    spec = _block(mlp_layer_types=list(MLP),
                  rope_layers=json.loads(json.dumps([lm_block.SLIDING])))
    assert spec == _block() and hash(spec) == hash(_block())
    assert spec.ffn_of(0) == lm_block.DENSE == "dense"
    assert spec.ffn_of(7) == lm_block.SPARSE
    assert spec.rotated(lm_block.SLIDING) and not spec.rotated(lm_block.FULL)
    # one set of RoPE parameters for every kind it turns
    assert spec.rope_of(lm_block.SLIDING) == ROPE
    with pytest.raises(ValueError, match="mlp_layer_types: unknown"):
        _block(mlp_layer_types=["dense", "shared"])
    with pytest.raises(ValueError, match="router 'tanh'"):
        _block(router="tanh")
    with pytest.raises(ValueError, match="rope_layers"):
        _block(rope_layers=["mamba"])
    with pytest.raises(ValueError, match="8 mlp_layer_types, and a layer 8"):
        build_lm_paged_decoder(V, BS, NB, d_model=D, n_heads=H, n_layers=9,
                               d_inner=F, platform="cpu",
                               block=_block(layer_types=KINDS + KINDS[:1]))
    with pytest.raises(NotImplementedError, match="dense_d_inner"):
        _decoder(dense_d_inner=0)
    with pytest.raises(ValueError, match="qk_norm is off"):
        _decoder(qk_norm=False)
    with pytest.raises(NotImplementedError, match="qk_norm_per_head"):
        _decoder(qk_norm_per_head=False)
    with pytest.raises(NotImplementedError, match="sigmoid router alone"):
        _decoder(router="softmax")
    with pytest.raises(NotImplementedError, match="dense layers among"):
        _decoder(ffn="swiglu", n_experts=0, experts_held=0, experts_first=0,
                 shared_d_inner=0, qk_norm=False, qk_norm_per_head=False,
                 n_kv_heads=0, d_head=0, layer_types=(), window=0,
                 router="softmax", router_bias=False,
                 routed_scaling_factor=1.0)
    dec = _decoder()
    shapes = dec.state_shapes
    assert shapes["layer_0.ffn_gate.w_0"] == (D, FD)
    assert shapes["layer_0.ffn_down.w_0"] == (FD, D)
    assert "layer_0.router.w_0" not in shapes            # dense: none
    assert "layer_0.shared_gate.w_0" not in shapes
    assert shapes["layer_1.router.w_0"] == (D, E)
    assert shapes["layer_1.router_bias.b_0"] == (E,)
    assert shapes["layer_1.experts_gate.w_0"] == (HELD, D, F)
    assert shapes["layer_1.shared_down.w_0"] == (FS, D)
    assert shapes["layer_3.q_norm.scale_0"] == (DH,) == \
        shapes["layer_3.k_norm.scale_0"]
    assert dec.step_counters == ("moe_experts_hit", "moe_rows_held")


def test_generation_server_serves_the_block_and_counts_held_rows():
    """Requests longer than the window through `GenerationServer`,
    continuously batched, give the tokens of the same request alone;
    the tick spans carry `moe_layers` and, with the tokens of the tick
    read, `moe_experts_hit` and `moe_rows_held`; what a ring cannot
    serve stays refused by name: the prefix cache, a draft model (what
    a multi-token-prediction module would be), `step_window`."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    # the block's own word, which the server raises as it stands
    # (the prefix cache is served through snapshots of a lane's rings
    # since PR 65)
    assert set(dec.refuses) == {"draft_model"}
    assert all("sliding-window layers" in why
               for why in dec.refuses.values())
    assert dec.init_snapshots is not None
    GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place).close()
    with pytest.raises(ValueError, match="no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                         prefix_cache=False, draft_decoder=dec,
                         draft_states=g)
    pool_k, pool_v = dec.init_pool(3, window_blocks=3)
    z = np.zeros(1, np.int32)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(
            _weights(dec), pool_k, pool_v,
            (np.zeros((1, NB), np.int32), np.zeros((1, 2), np.int32)), z,
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
        assert srv.stats()["expert_kernel"] == "xla:not_tpu"
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(a["moe_layers"] == 7 for a in ticks)
    read = [a for a in ticks if a["ahead"]]
    assert read and all(("moe_rows_held" in a) == bool(a["ahead"])
                        for a in ticks)
    assert all(0 <= a["moe_rows_held"] <= 2 * K * 7
               and a["moe_experts_hit"] <= HELD * 7 for a in read)
    assert max(a["past_window"] for a in ticks) == 2


def test_served_tokens_are_judged_by_the_reference_alone():
    """`served` knows only the tokens a server delivered: greedy
    requests past the window agree with the reference's argmax, and a
    window of one key more reads the same tokens as disagreeing."""
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
    assert out["tokens"] == 72 and out["tokens_past_window"] > 40
    assert out["served_argmax_agree"] == 1.0 == \
        out["past_window_argmax_agree"]
    assert out["served_gap_rms"] == 0.0
    wider = REF.served(g, CONFIG, requests, fault="window_129")
    assert wider["past_window_argmax_agree"] < 0.9, wider
    assert wider["past_window_gap_rms"] > 1e-3


def test_scopes_name_the_dense_layer_the_router_and_the_kinds():
    """`paged_decoder/dense_ffn` (layer 0 alone), `qk_norm`, `rope`,
    `moe_router`, `shared_expert`, and `sliding` / `full` under
    `attention` in the step's compiled text."""
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3, window_blocks=3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v,
        (np.zeros((2, NB), np.int32), np.zeros((2, 2), np.int32)), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("dense_ffn", "qk_norm", "rope", "moe_router",
                 "moe_dispatch", "moe_experts", "moe_combine",
                 "shared_expert", "attention/sliding", "attention/full",
                 "kv_gather/sliding", "kv_gather/full"):
        assert f"paged_decoder/{part}" in text, part
    # the dense layer's weights' own slices count under its scope
    assert dec.compiler_scopes["g[\\'layer_0.ffn_up.w_0\\']"] == \
        "paged_decoder/dense_ffn"
    assert not any("layer_1.experts" in k for k in dec.compiler_scopes)


def _config_file():
    with open(os.path.join(ROOT, "perf", "configs",
                           "k-exaone-236b-a23b-1chip.json")) as f:
        return json.load(f)


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/k-exaone-236b-a23b-1chip.json's `block`, read as the
    benchmark's job reads it, builds the decoder at the published
    widths (shapes only: nothing is allocated), and the parameter and
    cache arithmetic the file states is the decoder's own."""
    m = _config_file()
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.layer_types == tuple(KINDS) and spec.window == 128
    assert spec.mlp_layer_types == tuple(MLP) and spec.dense_d_inner == 18432
    assert (spec.router, spec.router_bias, spec.routed_scaling_factor,
            spec.norm_topk_prob) == ("sigmoid", True, 2.5, True)
    assert spec.qk_norm and spec.qk_norm_per_head and not spec.post_norm
    assert spec.rope_layers == (lm_block.SLIDING,)
    assert spec.rope_of(lm_block.SLIDING)["rope_theta"] == 1000000
    assert spec.held == (0, 16) and spec.n_experts == 128
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size",
                            "num_nextn_predict_layers"]
    assert m["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "vocab_size": 153600,
                              "num_nextn_predict_layers": 1}
    assert set(m["assumed"]) >= {"norm_placement", "qk_norm", "rope_layers",
                                 "router_bias"}
    # every published width, unchanged
    assert (m["hidden_size"], m["num_attention_heads"], m["head_dim"],
            m["num_key_value_heads"], m["intermediate_size"],
            m["moe_intermediate_size"], m["num_routed_experts"],
            m["num_experts_per_tok"], m["sliding_window"]) == (
                6144, 64, 128, 8, 18432, 2048, 128, 8, 128)
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 64, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu",
        block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas"
    assert (dec.table_layers, dec.ring_layers, dec.moe_layers) == (2, 6, 7)
    shapes = dec.state_shapes
    assert shapes["layer_0.ffn_gate.w_0"] == (6144, 18432)
    assert shapes["layer_1.experts_down.w_0"] == (16, 2048, 6144)
    assert shapes["layer_1.router.w_0"] == (6144, 128)
    assert shapes["layer_7.q_proj.w_0"] == (6144, 8192)
    assert shapes["layer_7.v_proj.w_0"] == (6144, 1024)
    assert shapes["lm_head.w_0"] == (6144, 19200)

    def params(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix))

    assert round(params("layer_0.") / 1e6, 1) == 453.0   # the file's counts
    assert round(params("layer_1.") / 1e6, 1) == 755.8
    weights_gb = params("") * 2 / 1e9
    assert round(weights_gb, 2) == 11.96
    # 4096 B a position a layer; 2 full layers' table, 6 layers' rings
    assert dec.window_blocks_per_seq == 8
    assert dec.bytes_per_block == 2 * 4096 * 16
    assert dec.window_bytes_per_block == 6 * 4096 * 16
    table_gb = dec.bytes_per_block * 64 * 64 / 1e9
    rings_gb = dec.window_bytes_per_block * 64 * 8 / 1e9
    assert (round(table_gb, 2), round(rings_gb, 2)) == (0.54, 0.20)
    assert weights_gb + table_gb + rings_gb >= 12.0      # held, of 16
    ids = np.zeros(WINDOW + 3, np.int32)
    g = _weights(_decoder())
    assert set(m["compare"]["limits"]) <= set(
        REF.compare(g, CONFIG, ids, *REF.forward(g, CONFIG, ids)))
    served = REF.served(g, CONFIG, [(ids, 2)])
    assert set(m["compare"]["served_limits"]) <= set(served)


def test_traffic_file_is_chat64_under_the_ring_job():
    """perf/traffic/chat64-ring.json: chat64's parameters letter for
    letter, but the job (`serve_lm_ring` after a fit of the choice
    bias), its description and the rehearsal's table."""
    def load(name):
        with open(os.path.join(ROOT, "perf", "traffic", name)) as f:
            return json.load(f)

    ring, chat64 = load("chat64-ring.json"), load("chat64.json")
    assert ring["job"] == "serve_lm_balanced" != chat64["job"]
    for key in set(chat64) - {"job", "what", "rehearse"}:
        assert ring[key] == chat64[key], key
    assert {k: v for k, v in ring["rehearse"].items() if k != "lengths"} \
        == {k: v for k, v in chat64["rehearse"].items() if k != "lengths"}


def test_the_job_fits_the_choice_bias_until_the_loads_are_even():
    """perf/jobs/serve_lm_balanced.py `fit`: over router inputs that
    share a large common component (what seeded weights give: some
    experts popular, some starved) the sign rule ends with every
    expert's load within a seventh of the mean (the bias is kept in
    the weights' own dtype: at 0.1 a bfloat16 step is 5e-4), and moves
    no weight: the choice alone."""
    import sys

    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        job = common.load_module(os.path.join(
            ROOT, "perf", "jobs", "serve_lm_balanced.py"))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    r = np.random.RandomState(4)
    e_n, k = 32, 4
    inputs = jnp.asarray(r.normal(0, 1, (2048, D)) + 1.5 * r.normal(
        0, 1, (1, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3, (D, e_n)), jnp.float32)

    scores = 1.0 / (1.0 + np.exp(-np.asarray(inputs) @ np.asarray(w)))

    def loads(bias):
        chosen = np.argsort(-(scores + np.asarray(bias, np.float32)),
                            -1)[:, :k]
        return np.bincount(chosen.reshape(-1), minlength=e_n) / (
            2048 * k / e_n)

    seeded = loads(np.zeros(e_n))
    assert seeded.max() > 2.0 and seeded.min() < 0.3
    bias = job.fit(jnp.asarray(scores, jnp.float32), k, jnp.bfloat16)
    assert bias.dtype == jnp.bfloat16 and bias.shape == (e_n,)
    fitted = loads(bias)
    assert fitted.max() < 1.15 and fitted.min() > 0.85, fitted
