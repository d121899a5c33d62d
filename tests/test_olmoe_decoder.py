"""The OLMoE block (RMSNorm, RoPE, QK-norm, a dropless top-k SwiGLU
expert layer) through `build_lm_paged_decoder` and the paged cache,
against the plain reference `perf/reference/olmoe.py`, at toy widths
on the CPU with seeded random float32 weights.

What is compared is LOGITS, never tokens: with random weights the
largest logit changes on rounding.  `rel` is the largest |difference|
over the largest |reference logit|.
"""
import dataclasses
import functools
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.kernels import grouped_matmul
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, E, F, K, L = 97, 64, 4, 8, 32, 2, 2
BS, NB = 4, 6                                   # 24 positions
CONFIG = {"num_attention_heads": H, "num_experts_per_tok": K,
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
          "norm_topk_prob": False, "num_hidden_layers": L}

# float32 weights, float32 pool: the step and the reference compute
# the same float32 sums in another order (sorted grouped matmul against
# a dense masked einsum, cached K against recomputed K): measured 3e-7
# to 9e-7 over four seeds.
TOL_FP32 = 1e-4
# bf16 pool: K and V are rounded to 8 bits of mantissa (relative 2^-9)
# on their way into the cache and everything else is as above; over two
# layers that measured 1.9e-3 to 9.2e-3 over four seeds (the largest is
# a seed where the rounding tips a near-tie between the k-th and k+1-th
# expert at one position).  Renormalised top-k weights measure 2.9e-1.
TOL_BF16_POOL = 3e-2


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "olmoe.py")
    spec = importlib.util.spec_from_file_location("ref_olmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _decoder(kv_dtype="fp32", **block):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu",
        block=lm_block.olmoe(**dict(
            {"n_experts": E, "experts_per_token": K}, **block)))
    assert startup is None          # no training Program behind it
    return dec


def _weights(dec, seed=0, router=None):
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router" in n else 0.1,
                     shape).astype(np.float32)
        if ".scale_" in n:
            w = 1.0 + w
        if router is not None and "router" in n:
            w = np.full(shape, router, np.float32)
        g[n] = jnp.asarray(w)
    return g


def _tables(slots):
    """Slot s owns blocks 1 + s*NB ...: disjoint, block 0 is null."""
    return (1 + np.arange(slots * NB, dtype=np.int32)).reshape(slots, NB)


def _drive(dec, g, seqs, slots=None, lanes=None):
    """Teacher-force each of `seqs` (token lists, any lengths) through
    `step` in its own slot, all slots in the same calls; returns each
    sequence's [len, V] logits read by `step_logits` before the write,
    and the experts-hit counts of the last call."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    pool_k, pool_v = dec.init_pool(1 + slots * NB)
    tables = _tables(slots)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out = [[] for _ in seqs]
    hit = None
    for pos in range(max(len(s) for s in seqs)):
        toks = np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane in zip(seqs, lanes):
            if pos < len(s):
                toks[lane], act[lane] = s[pos], True
        args = (g, pool_k, pool_v, tables, np.full(slots, pos, np.int32),
                toks, zs, zt, act)
        lg = np.asarray(dec.step_logits(*args))
        _, pool_k, pool_v, hit = dec.step(*args)
        for i, (s, lane) in enumerate(zip(seqs, lanes)):
            if pos < len(s):
                out[i].append(lg[lane])
    return [np.stack(o) for o in out], np.asarray(hit)


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ref_logits(g, seq, **over):
    return np.asarray(REF.logits(g, dict(CONFIG, **over),
                                 np.asarray(seq, np.int32)))


SEQ = list(np.random.RandomState(7).randint(0, V, 21))


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_prompt_then_decode_through_cache_matches_reference(kv_dtype, tol):
    """21 positions (the server teacher-forces a prompt and decodes
    through the same `step`), each attending to the paged cache of the
    positions before it, against the reference's full causal forward."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), hit = _drive(dec, g, [SEQ])
    rel = _rel(got, _ref_logits(g, SEQ))
    assert rel <= tol, rel
    assert hit.shape == (L,) and hit.dtype == np.int32
    assert ((1 <= hit) & (hit <= K)).all()      # one token, top-K


def test_slots_at_different_positions_in_one_call():
    """RoPE turns each slot by ITS position: three sequences of other
    lengths share every call, a slot that ended goes inactive, and each
    still matches its own full forward."""
    dec = _decoder()
    g = _weights(dec, 1)
    r = np.random.RandomState(3)
    seqs = [list(r.randint(0, V, n)) for n in (5, 17, 11)]
    # staggered: slot i starts i*2 positions late, so at any call the
    # active slots sit at different cursors
    pool_k, pool_v = dec.init_pool(1 + 3 * NB)
    zs, zt = np.zeros(3, np.uint32), np.zeros(3, np.float32)
    got = [[] for _ in seqs]
    for tick in range(17 + 4):
        pos = np.array([tick, tick - 2, tick - 4], np.int32)
        act = np.array([0 <= p < len(s) for p, s in zip(pos, seqs)])
        pos = np.where(act, pos, 0).astype(np.int32)
        toks = np.array([s[p] if a else 0
                         for s, p, a in zip(seqs, pos, act)], np.int32)
        args = (g, pool_k, pool_v, _tables(3), pos, toks, zs, zt, act)
        lg = np.asarray(dec.step_logits(*args))
        _, pool_k, pool_v, _ = dec.step(*args)
        for i in range(3):
            if act[i]:
                got[i].append(lg[i])
    for s, o in zip(seqs, got):
        assert _rel(np.stack(o), _ref_logits(g, s)) <= TOL_FP32


def test_step_window_matches_step():
    """The multi-position step (speculative verify, chunked prefill)
    rotates W positions a slot and routes S*W tokens: its greedy
    predictions and the K/V it leaves are `step`'s, position by
    position."""
    dec = _decoder()
    g = _weights(dec, 2)
    r = np.random.RandomState(5)
    seqs = [list(r.randint(0, V, 12)) for _ in range(2)]
    tables = _tables(2)
    zs, zt = np.zeros(2, np.uint32), np.zeros(2, np.float32)
    act = np.ones(2, bool)
    pk1, pv1 = dec.init_pool(1 + 2 * NB)
    one = []
    for pos in range(12):
        nxt, pk1, pv1, _ = dec.step(
            g, pk1, pv1, tables, np.full(2, pos, np.int32),
            np.array([s[pos] for s in seqs], np.int32), zs, zt, act)
        one.append(np.asarray(nxt))
    one = np.stack(one, 1)                                  # [2, 12]
    pk2, pv2 = dec.init_pool(1 + 2 * NB)
    win = []
    for start in range(0, 12, 4):
        toks = np.array([s[start:start + 4] for s in seqs], np.int32)
        preds, pk2, pv2, hit = dec.step_window(
            g, pk2, pv2, tables, np.full(2, start, np.int32), toks, zs,
            zt, np.full(2, 4, np.int32))
        win.append(np.asarray(preds))
        assert np.asarray(hit).shape == (L,)
    assert (np.concatenate(win, 1) == one).all()
    np.testing.assert_allclose(np.asarray(pk2)[:, 1:], np.asarray(pk1)[:, 1:],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(pv2)[:, 1:], np.asarray(pv1)[:, 1:],
                               atol=1e-5)


def _through_the_kernel(monkeypatch):
    """From here to the test's end `select_grouped_matmul`, which a
    step calls when it is TRACED, asks for the Pallas interpreter: the
    entry point's own argument for tests."""
    monkeypatch.setattr(
        grouped_matmul, "select_grouped_matmul", functools.partial(
            grouped_matmul.select_grouped_matmul, interpret=True))


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
@pytest.mark.parametrize("router", [None, 0.0],
                         ids=["routed_apart", "all_to_the_same_experts"])
def test_batched_slot_bit_identical_to_the_same_sequence_alone(
        router, kernel, monkeypatch):
    """The dropless property: what a slot's token gets never depends on
    where the other slots' tokens went.  With a zero router every
    probability ties, `top_k` takes experts 0 and 1 for EVERY token of
    every slot, and a layer with a capacity would drop most of them;
    here the batched slot is bit for bit the slot alone, through
    `ragged_dot` and through the Pallas grouped matmul (whose row
    tiles the other slots' rows share)."""
    if kernel:
        _through_the_kernel(monkeypatch)
    dec = _decoder()
    g = _weights(dec, 4, router=router)
    r = np.random.RandomState(9)
    seqs = [list(r.randint(0, V, n)) for n in (9, 14, 6, 11)]
    batched, hit = _drive(dec, g, seqs)
    if router is not None:
        assert (hit == K).all()     # one group of experts took it all
    for lane, s in enumerate(seqs):
        (alone,), _ = _drive(dec, g, [s], slots=4, lanes=[lane])
        assert np.array_equal(alone, batched[lane])
        assert _rel(alone, _ref_logits(g, s)) <= TOL_FP32
    assert dec.expert_kernel == (grouped_matmul.NAME if kernel
                                 else "xla:not_tpu")


def test_every_token_to_one_expert_loses_nothing():
    """top-1 with a zero router: every assignment of every slot lands
    on expert 0 (a group of S rows, 7 empty groups) and each token still
    gets that expert's whole output."""
    dec = _decoder(experts_per_token=1)
    g = _weights(dec, 6, router=0.0)
    r = np.random.RandomState(11)
    seqs = [list(r.randint(0, V, 8)) for _ in range(4)]
    got, hit = _drive(dec, g, seqs)
    assert (hit == 1).all()
    for s, o in zip(seqs, got):
        assert _rel(o, _ref_logits(g, s, num_experts_per_tok=1)) <= TOL_FP32


def test_top_k_weights_are_not_renormalised():
    """`norm_topk_prob` false: the k largest probabilities weigh the
    experts as they are.  The description with renormalisation agrees
    with the renormalising reference and NOT with the published one, so
    this fails if the served block renormalises."""
    dec, dec_renorm = _decoder(), _decoder(norm_topk_prob=True)
    g = _weights(dec, 8)
    want = _ref_logits(g, SEQ)
    want_renorm = _ref_logits(g, SEQ, norm_topk_prob=True)
    (got,), _ = _drive(dec, g, [SEQ])
    (got_renorm,), _ = _drive(dec_renorm, g, [SEQ])
    assert _rel(got, want) <= TOL_FP32
    assert _rel(got_renorm, want_renorm) <= TOL_FP32
    assert _rel(got, want_renorm) > 100 * TOL_FP32


def _drive_routing(dec, g, seq):
    """`seq` alone through `step`, logits and routing of every position
    read by `step_routing`: what the benchmark's job hands `compare`."""
    pool_k, pool_v = dec.init_pool(1 + NB)
    zs, zt, act = np.zeros(1, np.uint32), np.zeros(1, np.float32), \
        np.ones(1, bool)
    got, routed = [], []
    for pos, tok in enumerate(seq):
        args = (g, pool_k, pool_v, _tables(1), np.full(1, pos, np.int32),
                np.full(1, tok, np.int32), zs, zt, act)
        lg, routing = dec.step_routing(*args)
        assert np.array_equal(np.asarray(lg),
                              np.asarray(dec.step_logits(*args)))
        got.append(np.asarray(lg))
        routed.append({k: np.asarray(v) for k, v in routing.items()})
        _, pool_k, pool_v, _ = dec.step(*args)
    return np.concatenate(got), {
        k: np.concatenate([r[k] for r in routed], 1) for k in routed[0]}


def _compare(dec, g, seq=None):
    seq = SEQ if seq is None else seq
    return REF.compare(g, CONFIG, np.asarray(seq, np.int32),
                       *_drive_routing(dec, g, seq))


def test_step_routing_is_what_the_reference_routes():
    """The routing the step reports ([layers, slots, ...]: the router's
    input, its weights, its experts) is the float32 router's on the
    same input, and the reference following those experts reproduces
    the logits: the comparison that decides `correct` in the OLMoE
    cell, at float32 where every number is at its floor."""
    dec = _decoder()
    g = _weights(dec, 4)
    got, routing = _drive_routing(dec, g, SEQ)
    assert routing["inputs"].shape == (L, len(SEQ), D)
    assert routing["weights"].shape == routing["experts"].shape == (
        L, len(SEQ), K)
    # float32 sums in another order: 1e-6 of a probability
    out = _compare(dec, g)
    assert out["finite"] and out["logits_rel_err"] <= TOL_FP32
    assert out["router_rel_err"] <= 1e-5
    assert out["routing_agree"] == 1.0
    # following its own choice is the free-running forward, bit for bit
    want, own = REF.forward(g, CONFIG, np.asarray(SEQ, np.int32))
    assert np.array_equal(np.asarray(REF.forward(
        g, CONFIG, np.asarray(SEQ, np.int32),
        follow=np.asarray(own["experts"]))[0]), np.asarray(want))
    assert np.array_equal(np.asarray(REF.logits(
        g, CONFIG, np.asarray(SEQ, np.int32))), np.asarray(want))


def test_reference_follows_a_swapped_expert_and_reports_it():
    """A near-tie that fell the other way is not an error: with the
    k-th expert of one position replaced by the k+1-th in what the
    system reports, the reference applies THAT expert (its logits move
    away from the free-running forward's), `routing_agree` counts the
    assignments it would have made otherwise, and `router_rel_err` shows how far from a tie it was."""
    dec = _decoder()
    g = _weights(dec, 4)
    ids = np.asarray(SEQ, np.int32)
    got, routing = _drive_routing(dec, g, SEQ)
    probs = np.asarray(REF._router(jnp.asarray(routing["inputs"][0]),
                                   g["layer_0.router.w_0"]))
    order = np.argsort(-probs[5])
    assert order[K - 1] == routing["experts"][0, 5, K - 1]
    swapped = {k: v.copy() for k, v in routing.items()}
    swapped["experts"][0, 5, K - 1] = order[K]
    swapped["weights"][0, 5, K - 1] = probs[5, order[K]]
    followed = np.asarray(REF.forward(g, CONFIG, ids,
                                      follow=swapped["experts"])[0])
    assert _rel(followed, got) > 100 * TOL_FP32
    out = REF.compare(g, CONFIG, ids, followed, swapped)
    assert out["logits_rel_err"] <= TOL_FP32
    # that assignment, and those the changed stream moves further down
    assert out["routing_agree"] <= 1 - 1 / (L * len(SEQ) * K)
    assert out["router_rel_err"] == pytest.approx(
        probs[5, order[K - 1]] / probs[5, order[K]] - 1, rel=1e-3)


@pytest.mark.parametrize("fault", ["bf16_router", "renormalised"])
def test_comparison_refuses_lower_precision_routing_and_renormalising(
        fault, monkeypatch):
    """What the OLMoE cell's limits rest on, at toy widths: routing
    through one bfloat16 pass leaves the logits within rounding of the
    reference that follows it, and is caught on the router's own input
    (1e-3 of a probability and more, against 1e-6); renormalised
    weights are caught there and in the logits."""
    if fault == "renormalised":
        dec = _decoder(norm_topk_prob=True)
    else:
        def bf16_route(spec, m, w_router, b_router=None, choice=None):
            bf = jnp.bfloat16
            probs = jax.nn.softmax(jnp.dot(m.astype(bf),
                                           w_router.astype(bf)), -1)
            top_w, top_e = jax.lax.top_k(probs, spec.experts_per_token)
            return top_w.astype(jnp.float32), top_e

        monkeypatch.setattr(lm_block, "route", bf16_route)
        dec = _decoder()
    out = _compare(dec, _weights(dec, 4))
    assert out["router_rel_err"] > 1e-3
    if fault == "renormalised":
        assert out["logits_rel_err"] > 100 * TOL_FP32


def test_below_is_refused_where_the_system_passes():
    """`below` (the equations wholly in bfloat16, judged as a system)
    reads worse than the float32 decoder on both judged numbers."""
    dec = _decoder()
    g = _weights(dec, 4)
    sound = _compare(dec, g)
    below = REF.below(g, CONFIG, np.asarray(SEQ, np.int32))
    assert below["logits_rel_err"] > 30 * sound["logits_rel_err"]
    assert below["router_rel_err"] > 1e-3 > 100 * sound["router_rel_err"]


def test_configuration_file_describes_the_block():
    """perf/configs/olmoe-1b-7b-1chip.json's `block`, read as the
    benchmark's job reads it, is `lm_block.olmoe()` at the published
    keys, and its `compare.limits` name numbers `compare` returns."""
    with open(os.path.join(ROOT, "perf", "configs",
                           "olmoe-1b-7b-1chip.json")) as f:
        m = json.load(f)
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec == lm_block.olmoe()
    assert (m[b["d_inner"]], m["num_experts"]) == (1024, 64)
    dec = _decoder()
    assert set(m["compare"]["limits"]) <= set(_compare(dec,
                                                       _weights(dec, 4)))


def test_rope_scores_depend_on_distance_only():
    spec = lm_block.olmoe()
    r = np.random.RandomState(0)
    q, k = (jnp.asarray(r.normal(size=(1, 2 * 16)), jnp.float32)
            for _ in range(2))

    def score(pq, pk):
        rq = lm_block.rope(q, *lm_block.rope_tables(
            spec, jnp.array([pq]), 16), 2)
        rk = lm_block.rope(k, *lm_block.rope_tables(
            spec, jnp.array([pk]), 16), 2)
        return float((rq * rk).sum())

    assert score(0, 0) == pytest.approx(float((q * k).sum()), rel=1e-6)
    assert score(7, 3) == pytest.approx(score(104, 100), rel=1e-4)
    assert abs(score(7, 3) - score(7, 5)) > 1e-3


def test_block_description_is_checked():
    with pytest.raises(TypeError, match="BlockSpec"):
        build_lm_paged_decoder(V, BS, NB, block="olmoe")
    with pytest.raises(NotImplementedError, match="OLMoE combination"):
        build_lm_paged_decoder(V, BS, NB, block=dataclasses.replace(
            lm_block.olmoe(), name="dense", ffn="relu"))
    assert build_lm_paged_decoder(V, BS, NB)[1].step_routing is None
    dec = _decoder()
    assert dec.step_counters == ("moe_experts_hit",)
    assert dec.state_shapes["layer_1.experts_down.w_0"] == (E, F, D)
    assert len(dec.state_names) == 4 + L * 12 - 1


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
def test_generation_server_serves_the_block_and_counts_experts(
        kernel, monkeypatch):
    """The normal path: GenerationServer over the same decoder, mixed
    admissions bit-identical to solo runs, and the span attributes
    this block brings (`moe_experts_hit`; `moe_kernel`, which with
    `stats()["expert_kernel"]` says what the expert layer runs as)."""
    if kernel:
        _through_the_kernel(monkeypatch)
    dec = _decoder()
    states = {n: np.asarray(v) for n, v in _weights(dec, 12).items()}
    r = np.random.RandomState(13)
    prompts = [list(r.randint(0, V, n)) for n in (3, 6, 2, 5, 4)]
    max_news = [6, 9, 12, 4, 8]

    def serve(together):
        srv = GenerationServer(dec, states, slots=3, kv_blocks=18,
                               place=fluid.CPUPlace())
        assert srv.stats()["expert_kernel"] == (
            grouped_matmul.NAME if kernel else "xla:not_tpu")
        try:
            if not together:
                return [srv.submit(p, m).result(timeout=60)
                        for p, m in zip(prompts, max_news)]
            streams = [srv.submit(p, m)
                       for p, m in zip(prompts, max_news)]
            return [s.result(timeout=60) for s in streams]
        finally:
            srv.close()

    tracing.clear()
    tracing.set_enabled(True)
    try:
        batched = serve(together=True)
        ticks = [s["attrs"] for s in tracing.finished_spans()
                 if s["name"] == "serving.decode_tick"]
    finally:
        tracing.set_enabled(False)
        tracing.clear()
    assert batched == serve(together=False)
    # the count comes back with the tokens, so it is on the span of
    # the iteration that READ a tick: every one that ran ahead
    assert ticks and all(("moe_experts_hit" in a) == bool(a["ahead"])
                         for a in ticks)
    assert any(a["ahead"] for a in ticks)
    assert all(L <= a["moe_experts_hit"] <= L * min(E, 3 * K)
               for a in ticks if a["ahead"])
    assert all(a["moe_kernel"] == int(kernel) for a in ticks)


def test_expert_layer_lowers_for_tpu_as_three_grouped_matmuls():
    """At the published widths (32 tokens, 64 experts of 2048 x 1024,
    bf16) the expert layer reaches the TPU lowering as grouped matmuls
    over the sorted rows: the Pallas kernel's two Mosaic calls (gate,
    up and the gated product in one, down in the other) where
    selection takes it, three `ragged_dot`s where it does not (the CPU
    every tier-1 test runs on); on neither path a [tokens, experts,
    capacity] one-hot or a float32 copy of an expert tensor."""
    spec = lm_block.olmoe()
    sds, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    args = (sds((32, 2048), jnp.float32), sds((2048, 64), bf),
            sds((64, 2048, 1024), bf), sds((64, 2048, 1024), bf),
            sds((64, 1024, 2048), bf))

    def lowered(platform):
        experts, reason = grouped_matmul.select_grouped_matmul(
            rows=32 * 8, d_model=2048, d_ff=1024, n_experts=64, dtype=bf,
            platform=platform)
        traced = jax.jit(lambda *a: lm_block.moe_ffn(
            spec, *a, experts=experts)[0]).trace(*args)
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        assert "tensor<64x2048x1024xf32>" not in text
        assert "tensor<64x1024x2048xf32>" not in text
        return reason, text, str(traced.jaxpr)

    reason, text, jaxpr = lowered("tpu")
    assert reason is None
    assert text.count("tpu_custom_call") == 2
    assert "ragged_dot" not in text and "ragged_dot" not in jaxpr
    # what a CPU build traces (lowered for the TPU all the same: the
    # CPU's own lowering expands the op into a loop over groups)
    reason, text, jaxpr = lowered("cpu")
    assert reason == "not_tpu"
    assert jaxpr.count("= ragged_dot_general[") == 3
    assert text.count('"chlo.ragged_dot"') == 3
    assert "tpu_custom_call" not in text


def test_compiler_made_op_names_resolve_to_the_owners_scope():
    """The TPU compiler renames `ragged_dot`'s calls and drops their
    scope, and prefetches a weight in slices named after the step's
    parameter; the decoder says which scope they belong to and
    `register_jitted` applies it to the table it reads."""
    from paddle_tpu import profiler

    scopes = _decoder().compiler_scopes
    assert lm_block.MOE_COMPILER_SCOPES.items() <= scopes.items()
    # and a weight's prefetch (the compiler's own slices of a step's
    # parameter) under the part that multiplies by it
    assert scopes["g[\\'layer_0.q_proj.w_0\\']"] == "paged_decoder/qkv"
    assert scopes["g[\\'layer_1.o_proj.w_0\\']"] == \
        "paged_decoder/attn_out"
    assert set(scopes.values()) == {
        "paged_decoder/moe_experts", "paged_decoder/moe_dispatch",
        "paged_decoder/qkv", "paged_decoder/attn_out",
        "paged_decoder/head"}
    f = jax.jit(lambda x: x + 1)
    try:
        profiler.register_jitted(
            "t.alias", f, jnp.ones(3),
            compiler_scopes={"jit(<lambda>)/add":
                             "paged_decoder/moe_experts"})
        table = profiler.hlo_scopes("t.alias")["t.alias"]
    finally:
        profiler.reset_profiler()
    assert "paged_decoder/moe_experts" in set(table.values())
    assert "jit(<lambda>)/add" not in set(table.values())


def test_expert_layer_operations_and_bytes_from_shapes():
    """perf/moe_flops.py at OLMoE's widths: the numbers the
    configuration file and `moe_experts_roofline` rest on."""
    path = os.path.join(ROOT, "perf", "moe_flops.py")
    spec = importlib.util.spec_from_file_location("moe_flops", path)
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    assert flops.expert_bytes(2048, 1024) == 12_582_912
    assert flops.moe_layer_params(2048, 1024, 64) == 402_784_256
    assert flops.expected_experts_hit(64, 8, 32) == pytest.approx(
        63.1, abs=0.05)              # 98.6% of 64
    call = flops.moe_experts_call(2048, 1024, 256, 63)
    assert call["bytes"] == 63 * 12_582_912
    assert call["flops"] == 2 * 256 * 3 * 2048 * 1024
    # bound by memory: the bytes' time is ten times the operations'
    assert call["bytes"] / 819e9 > 10 * call["flops"] / 197e12
