"""The Granite 4.0-H block (Mamba-2 layers with a recurrent state a lane
beside the paged table, attention without positions, a share of the
routed experts and a shared expert, four scalar multipliers, a tied
head) through `build_lm_paged_decoder` and `GenerationServer`, against
the plain reference `perf/reference/granite_hybrid.py`, at toy widths
on the CPU with seeded random float32 weights.

The toy keeps the structure: a period with both kinds of layer (m a m
m), 4 of 8 routed experts held, 3 a token, a shared expert, 4 query
heads over 2 K/V heads, a state of 4 heads x 16 x 8 and a convolution
of width 4.  What is compared is LOGITS, never tokens, except where a
server's streams are compared with themselves.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.resilience import FaultError, fault_injector
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, HKV, E, HELD, F, FS, K, L = 97, 32, 4, 2, 8, 4, 16, 24, 3, 4
SH, SP, SN, CONV = 4, 16, 8, 4                   # the SSM: H, P, N, width
BS, NB = 4, 8                                    # 32 positions
KINDS = ["mamba", "attention", "mamba", "mamba"]
CONFIG = {"hidden_size": D, "num_attention_heads": H,
          "num_key_value_heads": HKV, "num_experts_per_tok": K,
          "rms_norm_eps": 1e-5, "num_hidden_layers": L,
          "layer_types": KINDS, "vocab_size": V,
          "num_routed_experts": E, "num_local_experts": HELD,
          "first_local_expert": 0, "mamba_n_heads": SH, "mamba_d_head": SP,
          "mamba_d_state": SN, "mamba_d_conv": CONV, "mamba_n_groups": 1,
          "mamba_expand": 2, "embedding_multiplier": 12,
          "residual_multiplier": 0.22, "attention_multiplier": 0.2,
          "logits_scaling": 16}
# float32 weights, pool and state: the same float32 sums in another
# order (a recurrence a position against a scan, grouped matmuls
# against dense masked products): measured 3e-7 to 9e-7 over four seeds
TOL_FP32 = 1e-4


def _load(name, *parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_granite_hybrid", "perf", "reference", "granite_hybrid.py")


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="granitemoehybrid", norm="rms_norm", positions="none",
        ffn="moe_swiglu", bias=False, n_experts=E, experts_per_token=K,
        norm_topk_prob=True, n_kv_heads=HKV, layer_types=KINDS,
        experts_first=0, experts_held=HELD, shared_d_inner=FS,
        tied_head=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.2,
        logits_scaling=16.0, ssm_heads=SH, ssm_d_head=SP, ssm_d_state=SN,
        ssm_conv=CONV), **over))


def _decoder(kv_dtype="fp32", **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu", block=_block(**over))
    assert startup is None
    return dec


def _weights(dec, seed=0):
    """Seeded float32 weights of a size at which the state matters:
    decays from 0.999 to 0.2 a position, a convolution of PyTorch's
    default size, an embedding small enough that the tied head does
    not put a token's own logit above all others."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        if n.endswith("ssm_a_log.w_0"):
            w = np.log(r.uniform(1.0, 16.0, shape))
        elif n.endswith("ssm_dt.b_0"):
            dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), shape))
            w = dt + np.log(-np.expm1(-dt))
        elif n.endswith("ssm_conv.w_0"):
            w = r.uniform(-0.5, 0.5, shape)
        elif n == "tok_embedding.w_0":
            w = r.normal(0, 0.02, shape)
        else:
            w = r.normal(0, 0.3 if "router" in n else 0.1, shape)
            if ".scale_" in n or n.endswith("ssm_d.w_0"):
                w = 1.0 + w
        g[n] = jnp.asarray(w, jnp.float32)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, routing=False,
           pools=None, start=0):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them;
    returns each sequence's [len, V] logits (and lane 0's routing
    stacked over positions).  `pools` continues on pools an earlier
    drive left (-> the pools are returned too)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(1 + slots * NB, lanes=slots)
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed = [[] for _ in seqs], []
    for pos in range(start, max(len(s) for s in seqs)):
        toks = np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane in zip(seqs, lanes):
            if pos < len(s):
                toks[lane], act[lane] = s[pos], True
        args = (g, pool_k, pool_v, tables,
                np.where(act, pos, 0).astype(np.int32), toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        routed.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                       for k, v in r.items()})
        _, pool_k, pool_v, *_ = dec.step(*args)
        for i, (s, lane) in enumerate(zip(seqs, lanes)):
            if pos < len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if pools is not None:
        return out, (pool_k, pool_v)
    if routing:
        return out, {
            "state": np.stack([np.asarray(h)[lanes[0]] for h in pool_k[1]]),
            **{k: np.concatenate([r[k] for r in routed], 1)
               for k in routed[0]}}
    return out


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


SEQ = list(np.random.RandomState(7).randint(0, V, 29))


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", 2e-2)])
def test_prompt_then_decode_match_the_reference_at_every_position(
        kv_dtype, tol):
    """29 positions one a step through state, tail and table against
    the reference's scan and causal mask over the whole sequence."""
    dec = _decoder(kv_dtype)
    assert (dec.state_layers, dec.window_blocks_per_seq) == (3, 0)
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    ids = np.asarray(SEQ, np.int32)
    want = np.asarray(REF.forward(g, CONFIG, ids,
                                  follow=routing["experts"])[0])
    assert _rel(got, want) <= tol
    out = REF.compare(g, CONFIG, ids, got, routing)
    assert out["finite"] and out["late_rms_err"] <= tol
    assert out["router_rel_err"] <= 1e-4, out
    # Granite's own form of the router: the k largest LOGITS and a
    # softmax over those, against `route` under norm_topk_prob
    np.testing.assert_allclose(routing["weights"].sum(-1), 1.0, atol=1e-6)
    if kv_dtype != "fp32":
        return
    own = REF.forward(g, CONFIG, ids)[1]
    assert np.array_equal(np.asarray(own["experts"]), routing["experts"])
    np.testing.assert_allclose(routing["weights"], own["weights"],
                               atol=1e-6)


def test_the_shares_and_the_shared_expert_add_up_to_the_whole_layer():
    """The parts of one expert layer's result that the two shares give
    (each its 4 of the 8 routed experts, through `moe_ffn`), with the
    shared expert counted once, are the uncut reference's layer; a
    share alone is not; and each share is the reference given the same
    share."""
    g = _weights(_decoder(), seed=2)
    r = np.random.RandomState(3)
    x = jnp.asarray(r.normal(0, 3, (11, D)), jnp.float32)
    whole = {n: jnp.asarray(r.normal(0, 0.1, s), jnp.float32)
             for n, s in (("gate", (E, D, F)), ("up", (E, D, F)),
                          ("down", (E, F, D)))}
    router = g["layer_0.router.w_0"]
    shared_w = {f"shared_{n}": g[f"layer_0.shared_{n}.w_0"]
                for n in ("gate", "up", "down")}
    own = jnp.full((11, K), -1, jnp.int32)

    def reference(experts, first):
        # x + 1.0 * (moe + shared) of RMSNorm(x) under a unit scale
        out, _ = REF._ffn(x, {"norm": jnp.ones(D), "router": router,
                              **experts, **shared_w}, own,
                          jnp.asarray(1.0), top_k=K, first=first, eps=1e-5,
                          dtype=jnp.float32)
        return np.asarray(out) - np.asarray(x)

    normed = REF._rms(x, jnp.ones(D), 1e-5)
    shared = np.asarray(lm_block.swiglu(normed, *shared_w.values()))
    parts, hits = [], []
    for first in (0, HELD):
        cut = {n: w[first:first + HELD] for n, w in whole.items()}
        y, hit, (top_w, _) = lm_block.moe_ffn(
            _block(experts_first=first), normed, router, *cut.values())
        parts.append(np.asarray(y))
        hits.append(int(hit))
        # the router's weights are over all 8, not shared out over 4
        np.testing.assert_allclose(np.asarray(top_w).sum(-1), 1.0,
                                   atol=1e-6)
        np.testing.assert_allclose(parts[-1] + shared,
                                   reference(cut, first), atol=2e-5)
    want = reference(whole, 0)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=2e-5)
    assert np.abs(parts[0] + shared - want).max() > 1e-3
    assert 1 <= min(hits) and max(hits) <= HELD


def test_an_assignment_to_an_absent_expert_adds_nothing():
    """A token whose experts are all on the other chip gets the shared
    expert alone, and its weights are not shared out: the held share's
    weights sum to less than 1 wherever an expert is absent."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    m = jnp.asarray(np.abs(r.normal(0, 1, (9, D))), jnp.float32)
    # a router that sends every token to experts 5, 6, 7: none held
    router = np.zeros((D, E), np.float32)
    router[:, 5:] = 1.0
    y, hit, (top_w, top_e) = lm_block.moe_ffn(
        _block(), m, jnp.asarray(router),
        *(g[f"layer_0.experts_{n}.w_0"] for n in ("gate", "up", "down")))
    assert int(hit) == 0 and not np.asarray(y).any()
    assert (np.asarray(top_e) >= HELD).all()


def test_batched_lane_bit_identical_to_the_same_sequence_alone():
    """Three sequences of different lengths in one call of the same
    four-lane step, the sequence in another lane and other table
    blocks than alone: bit for bit the same logits (no capacity in
    the expert layer, no lane in the recurrence)."""
    dec = _decoder()
    g = _weights(dec, seed=3)
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,) = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    together = _drive(dec, g, [others[0], SEQ, others[1]], slots=4,
                      lanes=[3, 1, 0])
    assert np.array_equal(together[1], alone)


def test_a_lane_starts_from_zero_at_position_0_and_idle_lanes_keep_still():
    """A sequence run in a lane whose state, tail and table blocks
    still hold ANOTHER sequence's gives bit for bit what it gives on
    zero pools: position 0 resets the lane from the cursor alone.  A
    lane that is not active keeps its state and tail to the bit while
    its neighbour runs."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    first, second = (list(r.randint(0, V, n)) for n in (27, 13))

    def zero():
        return dec.init_pool(1 + 2 * NB, lanes=2)

    (fresh,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=zero())
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    state, tail = used[0][1][0], used[1][1][0]
    assert np.asarray(state)[1].any() and np.asarray(tail)[1].any()
    assert not np.asarray(state)[0].any() and not np.asarray(tail)[0].any()
    (again,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=used)
    assert np.array_equal(fresh, again) and np.isfinite(again).all()
    # lane 1 idle while lane 0 runs: what lane 1 holds does not move
    before = jax.tree_util.tree_map(lambda a: np.asarray(a)[1].copy(),
                                    (used[0][1], used[1][1]))
    _, moved = _drive(dec, g, [first[:9]], slots=2, lanes=[0], pools=used)
    after = jax.tree_util.tree_map(lambda a: np.asarray(a)[1],
                                   (moved[0][1], moved[1][1]))
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert np.array_equal(a, b)
    assert np.asarray(moved[0][1][0])[0].any()


def test_the_comparison_refuses_every_fault_and_one_precision_below():
    """What `compare` must tell apart at these widths: a state rounded
    to bfloat16, a lane that was not reset, `D * x` left out, 1 /
    sqrt(head size) for the attention multiplier, and the whole model
    in bfloat16 each read far above the float32 decoder, by the limits
    the configuration bounds."""
    dec = _decoder()
    g = _weights(dec)
    ids = np.asarray(SEQ, np.int32)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, ids, got, routing)
    assert ok["logits_rms_err"] <= TOL_FP32 >= ok["late_rms_err"]
    assert ok["state_rms_err"] <= TOL_FP32
    faults = REF.faults(g, CONFIG, ids)
    assert set(faults) == set(REF.FAULTS) == {
        "state_bf16", "no_reset", "no_d_skip", "attention_scale"}
    for name, out in faults.items():
        # a state rounded to bfloat16 shows in the state itself, 300
        # times the float32 decoder's; the logits hardly see it
        told = "scan_rel_err" if name == "state_bf16" else "logits_rms_err"
        assert out[told] > 20 * TOL_FP32, (name, out)
        assert out["router_rel_err"] <= 1e-4, (name, out)
    # the recurrence judged on its own inputs: a wrong scalar
    # elsewhere leaves it alone, a leak or a rounded state does not
    assert ok["scan_rel_err"] <= 1e-5
    for name in ("no_d_skip", "attention_scale"):
        assert faults[name]["scan_rel_err"] <= 1e-5, faults[name]
    assert faults["no_reset"]["scan_rel_err"] > 20 * TOL_FP32
    below = REF.below(g, CONFIG, ids)
    assert min(below["logits_rms_err"], below["state_rms_err"],
               below["scan_rel_err"]) > 20 * TOL_FP32
    with open(os.path.join(ROOT, "perf", "configs",
                           "granite-4.0-h-small-1chip.json")) as f:
        limits = json.load(f)["compare"]["limits"]
    assert set(limits) <= set(ok)
    # a leak decays: what the predecessor left moves the first
    # positions most
    free = np.asarray(REF.forward(g, CONFIG, ids)[0])
    leak = np.asarray(REF._forward_fault(g, CONFIG, ids, "no_reset")[0])
    moved = np.abs(leak - free).max(-1)
    assert moved[0] > 0.0 and moved[:8].mean() > moved[-8:].mean()


def test_generation_server_serves_a_state_a_lane():
    """Requests through `GenerationServer`, tick-ahead on, continuously
    batched: a sequence beside others, one admitted into a lane another
    has just left, and one cut off and admitted again, each give the
    tokens of the same request alone; the tick spans count lanes and
    resets; what a recurrent state cannot serve is refused by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    # the block's own word, which the server raises as it stands
    # the prefix cache is served through snapshots of the lane's state
    # since PR 59 (tests/test_solar_open2_decoder.py holds a hit to the
    # miss for this block too): the draft model alone is refused
    assert set(dec.refuses) == {"draft_model"}
    assert "a recurrent state or a convolution tail" in dec.refuses[
        "draft_model"]
    assert dec.snapshot_save is not None is not dec.snapshot_restore
    with pytest.raises(ValueError, match="a lane takes no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=16, place=place,
                         prefix_cache=False, draft_decoder=dec,
                         draft_states=g)
    prompts = [list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 5), (2, 11), (3, 3), (4, 7))]

    def ask(server, i):
        # sampled (the key is the request's seed and the position): at
        # these widths a greedy stream repeats one token, and a state
        # that leaked would not show in it
        return server.submit(prompts[i], 18, temperature=1.0, seed=40 + i)

    want = []
    for i in range(len(prompts)):
        solo = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                                prefix_cache=False)
        try:
            want.append(ask(solo, i).result(timeout=120))
        finally:
            solo.close()
    assert all(len(set(w)) > 9 for w in want)
    # one lane: every request after the first runs on the state its
    # predecessor left; and the same request again, after the others
    one = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                           prefix_cache=False)
    try:
        assert [ask(one, i).result(timeout=120)
                for i in range(len(prompts))] == want
        assert ask(one, 0).result(timeout=120) == want[0]
        assert one.stats()["state_bytes"] == dec.state_bytes_per_lane
    finally:
        one.close()
    spans = []
    tracing.add_span_listener(spans.append)
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                           prefix_cache=False)
    try:
        # four requests on two lanes: the third and fourth wait for a
        # lane and take over one a request has just left, its successor
        # dispatched a tick ahead of the read that ended it
        streams = [ask(srv, i) for i in range(len(prompts))]
        assert [s.result(timeout=120) for s in streams] == want
        # a request cut off mid-way (its lane evicted with the tick in
        # flight lost), then admitted again: from position 0, the same
        inj = fault_injector()
        inj.clear()
        try:
            inj.inject("serving.decode", "error", nth=15)
            cut = ask(srv, 1)
            with pytest.raises(FaultError):
                cut.result(timeout=120)
            assert 0 < len(cut.tokens_so_far()) < 18
        finally:
            inj.clear()
        assert ask(srv, 1).result(timeout=120) == want[1]
        stats = srv.stats()
        assert stats["state_bytes"] == 2 * dec.state_bytes_per_lane
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all({"state_lanes", "state_resets"} <= set(a)
                         for a in ticks)
    assert all(a["state_lanes"] == a["active"] for a in ticks)
    assert sum(a["state_resets"] for a in ticks) == 6    # one a request
    assert max(a["ahead"] for a in ticks) == 1


def test_description_is_checked_and_the_old_blocks_are_as_they_were():
    """`param_layout` refuses by name what nothing builds; `step_window`
    and a pool without lanes are refused; OLMoE's and Mellum's
    `state_shapes` are what they were."""
    with pytest.raises(NotImplementedError, match="positions 'rope' with"):
        _decoder(positions="rope")
    with pytest.raises(NotImplementedError, match="positions 'none' without"):
        _decoder(layer_types=["attention"] * L)
    with pytest.raises(NotImplementedError, match="beside sliding-window"):
        _decoder(layer_types=["mamba", "sliding_attention"] * 2, window=8)
    with pytest.raises(ValueError, match="Mamba layers need ssm_heads"):
        _decoder(ssm_d_state=0)
    with pytest.raises(ValueError, match="not among the 8 routed"):
        _block(experts_first=6)
    with pytest.raises(ValueError, match="unknown kind"):
        _block(layer_types=["mamba2"])
    dec = _decoder()
    assert _block().kind_of(1) == lm_block.FULL and _block().held == (0, 4)
    di, conv = SH * SP, SH * SP + 2 * SN
    shapes = dec.state_shapes
    assert shapes["layer_0.ssm_in_proj.w_0"] == (D, di + conv + SH)
    assert shapes["layer_0.ssm_conv.w_0"] == (CONV, conv)
    assert shapes["layer_0.ssm_out_proj.w_0"] == (di, D)
    assert shapes["layer_1.k_proj.w_0"] == (D, HKV * D // H)
    assert shapes["layer_1.experts_gate.w_0"] == (HELD, D, F)
    assert shapes["layer_1.router.w_0"] == (D, E)
    assert shapes["layer_3.shared_down.w_0"] == (FS, D)
    assert "lm_head.w_0" not in shapes and "layer_0.q_proj.w_0" not in shapes
    assert dec.state_bytes_per_lane == 4 * 3 * (
        SH * SP * SN + (CONV - 1) * conv)
    assert dec.bytes_per_block == 2 * 1 * BS * HKV * (D // H) * 4
    with pytest.raises(ValueError, match="needs lanes"):
        dec.init_pool(3)
    pool_k, pool_v = dec.init_pool(3, lanes=1)
    assert [a.shape for a in pool_k[1]] == [(1, SH, SP, SN)] * 3
    assert [a.shape for a in pool_v[1]] == [(1, CONV - 1, conv)] * 3
    z = np.zeros(1, np.int32)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(_weights(dec), pool_k, pool_v,
                        np.zeros((1, NB), np.int32), z,
                        np.zeros((1, 2), np.int32), z.astype(np.uint32),
                        z.astype(np.float32), z)
    # the blocks that were there: names, shapes and what they report
    _, olmoe = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=2, d_inner=F,
        platform="cpu", block=lm_block.olmoe(n_experts=E,
                                             experts_per_token=2))
    assert olmoe.state_shapes == {
        **{f"layer_{l}.{n}": s for l in range(2) for n, s in {
            "attn_norm.scale_0": (D,), "q_proj.w_0": (D, D),
            "k_proj.w_0": (D, D), "v_proj.w_0": (D, D),
            "o_proj.w_0": (D, D), "ffn_norm.scale_0": (D,),
            "router.w_0": (D, E), "experts_gate.w_0": (E, D, F),
            "experts_up.w_0": (E, D, F), "experts_down.w_0": (E, F, D),
            "q_norm.scale_0": (D,), "k_norm.scale_0": (D,)}.items()},
        "tok_embedding.w_0": (V, D), "final_norm.scale_0": (D,),
        "lm_head.w_0": (D, V)}
    assert (olmoe.state_layers, olmoe.state_bytes_per_lane) == (0, 0)
    assert olmoe.bytes_per_block == 2 * 2 * BS * D * 4
    assert not isinstance(olmoe.init_pool(3, lanes=4)[0], tuple)


def test_scopes_name_the_mixer_and_the_shared_expert():
    """The five `ssm_*` scopes and `shared_expert` in the step's
    compiled text, beside the ones a block with experts has."""
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3, lanes=2)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                 "ssm_out_proj", "shared_expert", "moe_experts",
                 "attention", "kv_gather"):
        assert f"paged_decoder/{part}" in text, part


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/granite-4.0-h-small-1chip.json's `block`, read as
    the benchmark's job reads it, builds the decoder at the published
    widths (shapes only: nothing is allocated), and the parameter,
    state and cache arithmetic the file states is the decoder's own."""
    with open(os.path.join(ROOT, "perf", "configs",
                           "granite-4.0-h-small-1chip.json")) as f:
        m = json.load(f)
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.layer_types == tuple(["mamba"] * 5 + ["attention"]
                                     + ["mamba"] * 4)
    assert spec.held == (0, 36) and spec.n_experts == 72
    assert spec.tied_head and spec.positions == "none"
    assert m["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert b["d_inner"] == "intermediate_size"
    assert m["mamba_expand"] * m["hidden_size"] == \
        m["mamba_n_heads"] * m["mamba_d_head"]
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 64, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu",
        block=spec)
    # the one attention layer in ten, `attention_multiplier` and all,
    # through the streaming kernel (`scale` is its argument)
    assert dec.kernels["paged_attention_decode"] == "pallas"
    assert (dec.table_layers, dec.ring_layers) == (1, 0)
    shapes = dec.state_shapes
    assert shapes["layer_0.ssm_in_proj.w_0"] == (4096, 16768)
    assert shapes["layer_0.ssm_conv.w_0"] == (4, 8448)
    assert shapes["layer_5.k_proj.w_0"] == (4096, 1024)
    assert shapes["layer_9.experts_down.w_0"] == (36, 768, 4096)
    assert shapes["layer_9.router.w_0"] == (4096, 72)
    assert shapes["layer_9.shared_gate.w_0"] == (4096, 1536)

    def count(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix))

    assert round(count("layer_0.") / 1e6, 1) == 461.2    # the file's counts
    assert round(count("layer_5.") / 1e6, 1) == 400.9
    assert round(count("layer_0.ssm_") / 1e6
                 + shapes["layer_0.mixer_norm.scale_0"][0] / 1e6, 1) == 102.3
    assert round(count("") * 2 / 1e9, 2) == 9.93         # GB in bfloat16
    assert round(dec.state_bytes_per_lane * 64 / 1e9, 2) == 2.47
    assert dec.state_layers == 9
    assert dec.bytes_per_block == 4096 * 16              # K and V a position
    assert round(dec.bytes_per_block * 64 * 64 / 1e9, 2) == 0.27
    ssm = _load("ssm_bytes", "perf", "ssm_bytes.py")
    assert ssm.lane_state_bytes(128, 64, 128) == 4194304
    assert ssm.scan_bytes(64, m["layer_types"], 128, 64, 128) == \
        2 * 64 * 9 * 4194304


@pytest.mark.parametrize("command", [
    ["perf/selfcheck.py"],
    ["perf/run_cell.py", "--workload", "granite-4.0-h-small-serve-chat64",
     "--seed", "3000000019", "--seconds", "3", "--trace", "1",
     "--rehearse"]], ids=["selfcheck", "rehearse"])
def test_the_benchmark_wires_and_rehearses_the_cell(command, tmp_path):
    """`perf/selfcheck.py` (every reader agrees with BENCHMARK.json)
    and the new cell's rehearsal at its files' toy sizes: `correct`
    true with both comparisons deciding it, the new span-sourced
    metrics in the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable] + command, cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    if command[0].endswith("selfcheck.py"):
        assert last == "selfcheck ok"
        return
    line = json.loads(last)
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert line["metrics"]["sched_state_reset_share"]["value"] > 0
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert notes["reference"]["ok"] and notes["served"]["ok"]
    assert notes["served"]["reused_lanes"] >= 1
    assert notes["state"]["layers"] == 2
