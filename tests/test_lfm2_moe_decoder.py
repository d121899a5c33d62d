"""The LFM2-MoE block (gated short convolutions whose tail belongs to a
lane beside grouped-query attention layers on the paged table, a dense
layer before sparse ones, a sigmoid router with a choice bias and 1e-6
in its renormalisation, a tied head) through `build_lm_paged_decoder`
and `GenerationServer`, against the plain reference
`perf/reference/lfm2_moe.py`, at toy widths on the CPU with seeded
random float32 weights.

The toy is the configuration file's `rehearse` overlay: conv attention
conv conv, the first layer dense, 8 experts of 16 (3 a token), 4 query
heads over 2 K/V heads of 8, 3 taps.  What is compared is LOGITS, never
tokens, except where a server's streams are compared with themselves.
"""
import hashlib
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-24b-a2b-serve-agent128"
BS, NB = 4, 16                                   # 64 positions
# float32 weights, pool and tail: the same float32 sums in another
# order (a tail a position against a convolution over the sequence,
# grouped matmuls against dense masked products): measured 2e-7 to 6e-7
TOL_FP32 = 1e-4
# bf16 pool: K and V rounded to 8 bits of mantissa on their way into
# the table, 1 layer of 4 attends: measured 1e-3 to 3e-3
TOL_BF16_POOL = 2e-2


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _load(name, *parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_lfm2_moe", "perf", "reference", "lfm2_moe.py")
FILE = _json("perf", "configs", "lfm2-24b-a2b-1chip.json")
CONFIG = dict(FILE, **FILE["rehearse"])
V, D, H, L = (CONFIG[k] for k in ("vocab_size", "hidden_size",
                                  "num_attention_heads",
                                  "num_hidden_layers"))
E, K, TAPS = (CONFIG[k] for k in ("num_experts", "num_experts_per_tok",
                                  "conv_L_cache"))
N_CONV = CONFIG["layer_types"].count("conv")


def _block(m=CONFIG, **over):
    """The description as the benchmark's job builds it: the file's
    `block`, literal fields and the source's own keys."""
    b = m["block"]
    return lm_block.BlockSpec(**dict(dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}),
        **over)), m[b["d_inner"]]


def _decoder(kv_dtype="fp32", **over):
    spec, d_inner = _block(**over)
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=d_inner,
        kv_dtype=kv_dtype, platform="cpu", block=spec)
    assert startup is None
    return dec


def _weights(dec, seed=0):
    """Seeded float32 weights of a size at which every part matters:
    matrices at sigma 0.1 (0.3 the router, whose product decides a
    choice), the taps of PyTorch's default size, a choice bias of 0.05
    beside sigmoids whose 3rd and 4th lie about that far apart."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        if n.endswith(".conv.w_0"):
            w = r.uniform(-1, 1, shape) / np.sqrt(shape[0])
        elif n.endswith("router_bias.b_0"):
            w = r.normal(0, 0.05, shape)
        else:
            w = r.normal(0, 0.3 if "router.w" in n else 0.1, shape)
            if ".scale_" in n:
                w = 1.0 + w
        g[n] = jnp.asarray(w, jnp.float32)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None,
           routing=False, pools=None):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); returns each
    sequence's [len, V] logits, then (with `routing`) lane `lanes[0]`'s
    routing stacked over its positions with its tails after the last
    one under "tails", then (with `pools`, which continues on pools an
    earlier drive left) the pools."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(1 + slots * NB, lanes=slots)
    # the table's planes are the attention layers' alone; a tail a conv
    # layer rides beside V and nothing beside K
    assert pool_k[0].shape[0] == L - N_CONV and pool_k[1] == ()
    assert [t.shape for t in pool_v[1]] == [(slots, TAPS - 1, D)] * N_CONV
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed = [[] for _ in seqs], []
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
        _, pool_k, pool_v, *counts = jax.block_until_ready(dec.step(*args))
        assert len(counts) == len(dec.step_counters) == 1
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    res = ([np.stack(o) for o in out],)
    if routing:
        res += ({"tails": np.stack([np.asarray(t)[lanes[0]]
                                    for t in pool_v[1]]),
                 **{k: np.concatenate([r[k] for r in routed], 1)
                    for k in routed[0]}},)
    if pools is not None:
        res += ((pool_k, pool_v),)
    return res[0] if len(res) == 1 else res


SEQ = list(np.random.RandomState(7).randint(0, V, 57))   # over 14 blocks
IDS = np.asarray(SEQ, np.int32)


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_prompt_then_decode_equals_the_references_full_forward(kv_dtype,
                                                               tol):
    """Every position of a sequence through the paged step (the prompt
    one position a tick, then decode: the step does not tell them
    apart), the conv layers' tails carried a lane, the attention layer
    through the table, against the reference's ONE forward pass over
    the sequence with the convolution over the whole of it."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["logits_rms_err"] <= tol >= out["late_rms_err"], out
    assert out["router_rel_err"] <= 1e-4, out
    # the tail is float32 whatever the pool is: the product of the two
    # gates, to a rounding of the matmul that made them
    assert out["tail_rms_err"] <= (TOL_FP32 if kv_dtype == "fp32"
                                   else tol), out
    if kv_dtype == "fp32":
        want = np.asarray(REF.logits(g, CONFIG, IDS))
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
        assert out["routing_agree"] == 1.0 == out["argmax_agree"]


def test_a_reused_lane_reads_as_a_fresh_one_and_an_idle_lane_keeps_still():
    """A sequence run in a lane whose tails and table blocks still hold
    ANOTHER sequence's gives bit for bit what it gives on zero pools:
    position 0 resets the lane from the cursor alone.  A lane that is
    not active keeps its tails to the bit while its neighbour runs."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    first, second = (list(r.randint(0, V, n)) for n in (27, 13))

    def zero():
        return dec.init_pool(1 + 2 * NB, lanes=2)

    (fresh,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=zero())
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    tails = [np.asarray(t) for t in used[1][1]]
    assert all(t[1].any() and not t[0].any() for t in tails)
    (again,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=used)
    assert np.array_equal(fresh, again) and np.isfinite(again).all()
    # without the reset the same lane reads otherwise: the toy's tails
    # are large enough to show
    leak = np.asarray(REF._forward_fault(
        g, CONFIG, np.asarray(second, np.int32), "no_reset")[0])
    assert np.abs(leak - fresh).max() > 100 * TOL_FP32 * np.abs(fresh).max()
    # lane 1 idle while lane 0 runs: what lane 1 holds does not move
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    before = [np.asarray(t)[1].copy() for t in used[1][1]]
    _, moved = _drive(dec, g, [first[:9]], slots=2, lanes=[0], pools=used)
    for a, t in zip(before, moved[1][1]):
        assert np.array_equal(a, np.asarray(t)[1])
        assert np.asarray(t)[0].any()


@pytest.mark.parametrize("zero_router", [False, True])
def test_a_sequence_among_others_is_bit_identical_to_itself_alone(
        zero_router):
    """Continuous batching: the same sequence beside two others out of
    step with it, in another lane and other table blocks than alone:
    bit for bit the same logits (no capacity in the expert layer, no
    lane in the convolution), even with a zero router (every token on
    the same experts: the fullest groups)."""
    dec = _decoder()
    g = _weights(dec, seed=3)
    if zero_router:
        g = {n: jnp.zeros_like(w) if "router" in n else w
             for n, w in g.items()}
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,) = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    together = _drive(dec, g, [others[0], SEQ, others[1]], slots=4,
                      lanes=[3, 1, 0], starts=[0, 2, 5])
    assert np.array_equal(together[1], alone)


# the toy's limits, between the decoder's readings (2e-7 to 6e-7, the
# router's 2e-7) and the least any fault or `below` reads
LIMITS = {"logits_rms_err": TOL_FP32, "late_rms_err": TOL_FP32,
          "tail_rms_err": TOL_FP32, "router_rel_err": TOL_FP32}


def _refused(out):
    return sorted(k for k, hi in LIMITS.items() if out[k] > hi)


def test_the_comparison_passes_the_decoder_by_every_limit():
    dec = _decoder()
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert _refused(out) == [], out
    assert set(FILE["compare"]["limits"]) <= set(out)
    assert REF.FAULTS == ("no_reset", "tail_shifted", "conv_silu",
                          "gates_exchanged", "bias_in_weights",
                          "no_renorm", "norm_after_rope")


@pytest.mark.parametrize("what", ("below",) + REF.FAULTS)
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """Each of the seven wrong models and the right one in bfloat16, as
    if it were the system, is refused by at least one limit, and by
    the limit that names what it broke."""
    g = _weights(_decoder())
    out = (REF.below(g, CONFIG, IDS) if what == "below"
           else REF.faults(g, CONFIG, IDS, which=(what,))[what])
    refused = _refused(out)
    assert refused, (what, out)
    by = {"below": "logits_rms_err", "no_reset": "tail_rms_err",
          "tail_shifted": "logits_rms_err", "conv_silu": "logits_rms_err",
          "gates_exchanged": "tail_rms_err",
          "bias_in_weights": "router_rel_err",
          "no_renorm": "router_rel_err",
          "norm_after_rope": "logits_rms_err"}[what]
    assert by in refused, (what, by, out)
    if what in ("tail_shifted", "conv_silu", "norm_after_rope"):
        # the router and the gates are right there: only the logits tell
        assert out["router_rel_err"] <= TOL_FP32, out
    if what in ("conv_silu", "norm_after_rope", "bias_in_weights",
                "no_renorm"):
        assert out["tail_rms_err"] > TOL_FP32    # downstream of layer 0
    if what == "no_reset":
        # a leak is nearest at the start: the first positions move most
        free = np.asarray(REF.forward(g, CONFIG, IDS)[0])
        leak = np.asarray(REF._forward_fault(g, CONFIG, IDS, what)[0])
        moved = np.abs(leak - free).max(-1)
        assert moved[0] > 0.0 and moved[:8].mean() > moved[-8:].mean()


def test_the_renormalisations_epsilon_is_computed_and_defaults_to_nothing():
    """`route` divides the chosen scores by their sum PLUS
    `norm_topk_eps`: at scores near 1e-3 the 1e-6 moves the weights by
    2.5e-4 of themselves, which float32 holds.  The field's default
    adds no operation: every other caller's lowered `route` is the text
    it was."""
    spec, _ = _block()
    assert spec.norm_topk_eps == 1e-6 == FILE["norm_topk_eps"]
    r = np.random.RandomState(0)
    # a constant column whose weight is -7: logits near -7, sigmoids
    # near 1e-3
    m7 = jnp.asarray(np.concatenate(
        [r.normal(0, 1, (9, D)), np.ones((9, 1))], 1), jnp.float32)
    w7 = jnp.asarray(np.concatenate(
        [r.normal(0, 0.02, (D, E)), np.full((1, E), -7.0)], 0), jnp.float32)
    b = jnp.zeros(E, jnp.float32)
    logits = np.asarray(m7, np.float64) @ np.asarray(w7, np.float64)
    top_w, top_e = lm_block.route(spec, m7, w7, b)
    s = 1.0 / (1.0 + np.exp(-logits))
    chosen = np.take_along_axis(s, np.asarray(top_e), -1)
    with_eps = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    without = chosen / chosen.sum(-1, keepdims=True)
    got = np.asarray(top_w, np.float64)
    assert np.abs(got / with_eps - 1).max() < 2e-6
    assert np.abs(got / without - 1).min() > 5e-5
    # the default: the parent's operations, to the letter
    plain = lm_block.BlockSpec(**dict(
        FILE["block"]["spec"], n_experts=E, experts_per_token=K,
        norm_topk_prob=True, router_bias=True))
    assert plain.norm_topk_eps == 0.0

    def parents_route(m, w, b):
        probs = jax.nn.sigmoid(jnp.dot(
            m, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        _, top_e = lm_block._largest(probs + b.astype(jnp.float32), K)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        return top_w / top_w.sum(-1, keepdims=True), top_e

    def body(fn):
        """The lowered text under the module's own name."""
        return jax.jit(fn).lower(m7, w7, b).as_text().split("\n", 1)[1]

    text = body(lambda m, w, b: lm_block.route(plain, m, w, b))
    assert text == body(parents_route)
    # and the description's own adds ONE operation: the sum plus 1e-6
    own = body(lambda m, w, b: lm_block.route(spec, m, w, b))
    assert own.count("stablehlo.add") == text.count("stablehlo.add") + 1
    with pytest.raises(ValueError, match="norm_topk_prob is off"):
        _block(norm_topk_prob=False)


def test_description_is_checked_and_laid_out():
    spec, d_inner = _block()
    assert (spec.conv_width, spec.router, spec.router_bias,
            spec.tied_head, spec.qk_norm_per_head) == (
                3, "sigmoid", True, True, True)
    assert [spec.kind_of(l) for l in range(L)] == CONFIG["layer_types"]
    assert [spec.ffn_of(l) for l in range(L)] == CONFIG["mlp_layer_types"]
    assert CONFIG["mlp_layer_types"] == (
        ["dense"] * CONFIG["num_dense_layers"]
        + ["sparse"] * (L - CONFIG["num_dense_layers"]))
    assert FILE["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    layout, shapes = lm_block.param_layout(spec, V, D, H, L, d_inner)
    f, fd, dkv = d_inner, CONFIG["intermediate_size"], 2 * (D // H)
    assert shapes["layer_0.conv_in_proj.w_0"] == (D, 3 * D)
    assert shapes["layer_0.conv.w_0"] == (TAPS, D)
    assert shapes["layer_0.conv_out_proj.w_0"] == (D, D)
    assert shapes["layer_0.operator_norm.scale_0"] == (D,)
    assert shapes["layer_0.ffn_gate.w_0"] == (D, fd)
    assert shapes["layer_1.k_proj.w_0"] == (D, dkv)
    assert shapes["layer_1.q_norm.scale_0"] == (D // H,)
    assert shapes["layer_1.router.w_0"] == (D, E)
    assert shapes["layer_1.router_bias.b_0"] == (E,)
    assert shapes["layer_2.experts_down.w_0"] == (E, f, D)
    # conv layers carry no attention arrays and no head norms, the
    # dense layer no router, and the head is the embedding
    assert not [n for n in shapes if n.startswith("layer_0.")
                and ("q_" in n or "k_" in n or "router" in n)]
    assert "lm_head.w_0" not in shapes and layout.head[0] == layout.tok
    with pytest.raises(ValueError, match="unknown kind"):
        _block(layer_types=["conv", "convolution"])
    with pytest.raises(ValueError, match="conv_width 0 with 'conv'"):
        _block(conv_width=0)
    with pytest.raises(ValueError, match="conv_width 3 without 'conv'"):
        _block(layer_types=["full_attention"] * L)
    for over in (dict(conv_width=1), dict(layer_types=["conv"] * L),
                 dict(layer_types=["conv", "sliding_attention",
                                   "full_attention", "conv"], window=4)):
        with pytest.raises(NotImplementedError,
                           match="gated short convolutions"):
            lm_block.param_layout(_block(**over)[0], V, D, H, L, d_inner)


def test_a_tail_a_lane_is_what_the_decoder_says_it_holds():
    """`state_layers`, `state_bytes_per_lane`, the table's planes and a
    tick's counts for a block with conv layers; an int8 pool and
    `step_window` are refused by name."""
    dec = _decoder("bf16")
    assert dec.state_layers == N_CONV == 3
    assert dec.state_bytes_per_lane == N_CONV * (TAPS - 1) * D * 4
    assert dec.table_layers == dec.kv_planes == L - N_CONV == 1
    # K and V of ONE layer: 2 K/V heads of 8 columns, bf16
    assert dec.bytes_per_block == 2 * 1 * BS * 16 * 2
    assert (dec.ring_layers, dec.index_planes, dec.moe_layers) == (0, 0, 3)
    assert dec.kernels["paged_attention_decode"] == "xla:not_tpu"
    counts = dec.tick_counts(np.asarray([0, 7, 0, 31]), 6)
    assert counts["state_lanes"] == 4 and counts["state_resets"] == 2
    assert counts["conv_layers"] == N_CONV
    assert counts["conv_tail_bytes"] == 2 * 4 * dec.state_bytes_per_lane
    assert counts["kv_pages_table"] == 6 * NB * 1 and counts["moe_layers"] == 3
    pool_k, pool_v = dec.init_pool(5, lanes=6)
    assert pool_k[1] == () and len(pool_v[1]) == N_CONV
    assert all(t.dtype == jnp.float32 and t.shape == (6, TAPS - 1, D)
               for t in pool_v[1])
    with pytest.raises(ValueError, match="needs lanes"):
        dec.init_pool(5)
    with pytest.raises(NotImplementedError, match="int8 pool beside conv"):
        _decoder("int8")
    z = np.zeros((2, 3), np.int32)
    with pytest.raises(NotImplementedError,
                       match="convolution tail is carried"):
        dec.step_window(
            {}, *dec.init_pool(3, lanes=2), np.zeros((2, NB), np.int32),
            z[:, 0], z, np.zeros(2, np.uint32), np.zeros(2, np.float32),
            z[:, 0])
    # a block without conv layers sets none of the counts
    plain = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=2, d_inner=16,
        platform="cpu", block=lm_block.olmoe(n_experts=E,
                                             experts_per_token=K))[1]
    assert not {"conv_layers", "conv_tail_bytes", "state_lanes"} & set(
        plain.tick_counts(np.asarray([3]), 2))
    assert plain.state_layers == 0 == plain.state_bytes_per_lane


def test_generation_server_serves_a_tail_a_lane_and_refuses_by_name():
    """Requests through `GenerationServer`, tick-ahead on, continuously
    batched: a sequence beside others and one admitted into a lane
    another has just left each give the tokens of the same request
    alone; the tick spans count lanes, resets and the tails' bytes;
    what a tail a lane cannot be served with is refused by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
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
        # sampled (the key is the request's seed and the position): a
        # greedy stream at these widths soon cycles, and a tail that
        # leaked would not show in it
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
    # one lane: every request after the first runs on the tails its
    # predecessor left
    one = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                           prefix_cache=False)
    try:
        assert [ask(one, i).result(timeout=120)
                for i in range(len(prompts))] == want
        assert one.stats()["state_bytes"] == dec.state_bytes_per_lane
    finally:
        one.close()
    spans = []
    tracing.add_span_listener(spans.append)
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                           prefix_cache=False)
    try:
        streams = [ask(srv, i) for i in range(len(prompts))]
        assert [s.result(timeout=120) for s in streams] == want
        stats = srv.stats()
        assert stats["state_bytes"] == 2 * dec.state_bytes_per_lane
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(t["conv_layers"] == N_CONV for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == len(prompts)
    assert all(t["conv_tail_bytes"]
               == 2 * t["state_lanes"] * dec.state_bytes_per_lane
               for t in ticks)


def test_scopes_name_the_three_parts_of_the_convolution():
    dec = _decoder()
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(9, lanes=2))
    i32 = sds((2,), np.int32)
    text = dec.step.lower(
        g, *pools, sds((2, NB), np.int32), i32, i32, sds((2,), np.uint32),
        sds((2,), np.float32), sds((2,), np.bool_)).as_text(debug_info=True)
    for part in ("conv_in_proj", "conv_gate", "conv_out_proj", "dense_ffn",
                 "moe_experts", "attention", "qk_norm", "rope"):
        assert f"paged_decoder/{part}" in text, part
    assert "ssm_" not in text
    assert dec.compiler_scopes["g[\\'layer_0.conv_in_proj.w_0\\']"] == (
        "paged_decoder/conv_in_proj")
    assert dec.compiler_scopes["g[\\'layer_3.conv_out_proj.w_0\\']"] == (
        "paged_decoder/conv_out_proj")


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/lfm2-24b-a2b-1chip.json's `block`, read as the
    benchmark's job reads it, lays out the decoder at the published
    widths; `cut.arithmetic` is recomputed from the shapes; every
    number of the catalog's row is there under its own key but for the
    keys listed as reduced."""
    m = FILE
    spec, d_inner = _block(m)
    _, shapes = lm_block.param_layout(
        spec, m["vocab_size"], m["hidden_size"], m["num_attention_heads"],
        m["num_hidden_layers"], d_inner)
    numbers = m["cut"]["arithmetic_numbers"]
    size = lambda n: int(np.prod(shapes[n]))
    near = lambda got, want: abs(got / 1e6 - want) <= 0.006 * max(want, 1)
    mixer = lambda l, names: sum(size(f"layer_{l}.{n}") for n in names)
    assert near(size("layer_1.experts_gate.w_0") * 3 / 64,
                numbers["expert_m"])
    assert near(mixer(1, ("experts_gate.w_0", "experts_up.w_0",
                          "experts_down.w_0")), numbers["experts_layer_m"])
    assert near(size("layer_1.router.w_0"), numbers["router_m"])
    assert near(mixer(1, ("q_proj.w_0", "k_proj.w_0", "v_proj.w_0",
                          "o_proj.w_0")), numbers["attention_m"])
    assert near(mixer(0, ("conv_in_proj.w_0", "conv.w_0",
                          "conv_out_proj.w_0")), numbers["conv_m"])
    assert near(mixer(0, ("ffn_gate.w_0", "ffn_up.w_0", "ffn_down.w_0")),
                numbers["dense_ffn_m"])
    assert near(size("tok_embedding.w_0"), numbers["embedding_m"])
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(total / 1e9 - numbers["here_b"]) < 0.001
    assert abs(2 * total / 1e9 - numbers["weights_gb"]) < 0.01
    whole = (38 * (numbers["experts_layer_m"] + numbers["router_m"])
             + 30 * numbers["conv_m"] + 10 * numbers["attention_m"]
             + 2 * numbers["dense_ffn_m"] + numbers["embedding_m"]) / 1e3
    assert abs(whole - numbers["model_b"]) < 0.01
    for said in (f'{numbers["here_b"]} B', f'{numbers["weights_gb"]} GB',
                 f'{numbers["pool_gb"]} GB', "4096 B a position",
                 f'{numbers["tail_bytes_a_lane"]} B'):
        assert said in m["cut"]["arithmetic"], said
    assert f'{numbers["model_b"]} B' in m["cut"]["deployment_arithmetic"]
    t = _json("perf", "traffic", "agent128.json")
    _, dec = build_lm_paged_decoder(
        64, t["block_size"], t["context"] // t["block_size"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=d_inner,
        kv_dtype=t["kv_dtype"], platform="cpu", block=spec)
    assert dec.bytes_per_block == numbers["cache_bytes_a_position"] * 16
    assert dec.state_bytes_per_lane == numbers["tail_bytes_a_lane"]
    assert (dec.state_layers, dec.table_layers, dec.moe_layers) == (7, 2, 8)
    pool = dec.bytes_per_block * t["slots"] * t["context"] // t["block_size"]
    assert abs(pool / 1e9 - numbers["pool_gb"]) < 0.01
    assert m["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert set(REF.FAULTS) <= set(m["assumed"]["faults"].split())
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "lfm2-24b-a2b-1chip")
    assert entry["reduced"] == m["reduced"]
    assert entry["source"] == m["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LFM2-24B-A2B")
    assert m["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)
               and m.get(k) != v}
    assert changed == set(m["reduced"]) == set(m["published"])
    assert all(m["published"][k] == row["config"][k] for k in changed)
    assert all(m[k] == v for k, v in row["config"].items()
               if k not in changed and k != "layer_types")
    # one leading dense layer (published layer 0), then published
    # layers 2 to 9: two whole periods
    assert m["layer_types"] == (row["config"]["layer_types"][:1]
                                + row["config"]["layer_types"][2:10])


def test_traffic_file_is_agent96s_table_at_128_lanes():
    t, src = (_json("perf", "traffic", n + ".json")
              for n in ("agent128", "agent96"))
    assert t["lengths"] == src["lengths"]
    table = t["lengths"]["table"]
    assert len(table) == 64 and max(p + o for p, o in table) == 3488
    assert (t["clients"], t["slots"], t["context"], t["block_size"],
            t["kv_dtype"], t["prefix_cache"], t["temperature"],
            t["max_queue"]) == (128, 128, 4096, 16, "bf16", False, 0.0, 256)
    assert (t["ramp_seconds"], t["stagger_seconds"], t["slice_seconds"],
            t["trace_delay_seconds"], t["trace_seconds"],
            t["correct_tokens"], t["served_requests"],
            t["served_tokens"]) == (80, 40, 5, 8, 4, 512, 6, 512)
    assert t["job"] == "serve_lm_conv"
    # the pool holds every lane's longest request: admission never waits
    assert t["slots"] * -(-3488 // 16) <= t["slots"] * t["context"] // 16
    cell = next(w for w in _json("BENCHMARK.json")["workloads"]
                if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-1chip", "agent128", 1)
    assert len(cell["why"]) <= 200


def test_the_bytes_and_the_two_readers_on_a_synthetic_run(monkeypatch):
    """`perf/short_conv_bytes.py` at the published widths, and the two
    new readers on a `Run` made by hand: tick spans with the conv
    counts, a scope table."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    import common
    from paddle_tpu import profiler

    cost = _load("short_conv_bytes", "perf", "short_conv_bytes.py")
    # in [2048, 6144] + out [2048, 2048] + taps [3, 2048], bf16
    assert cost.mixer_weight_bytes(2048, 3) == 2 * 16783360 == 33566720
    assert cost.lane_tail_bytes(2048, 3) == 16384
    kinds = FILE["layer_types"]
    a_lane = 2 * 16384 + 3 * 2048 * 4
    assert cost.mixer_bytes(1, 0, kinds, 2048, 3) == 7 * 33566720
    assert cost.mixer_bytes(2, 200, kinds, 2048, 3) == 7 * (
        2 * 33566720 + 200 * a_lane)
    assert cost.mixer_bytes(5, 9, ["full_attention"], 2048, 3) == 0.0
    readers = {n: common.load_module(os.path.join(
        ROOT, "perf", "metrics", n + ".py"))
        for n in ("serve_short_conv_share", "serve_short_conv_roofline")}
    ticks = [{"name": "serving.decode_tick", "ts": 10.0 + i, "dur": 0.5,
              "attrs": {"conv_layers": 7, "state_lanes": 100 + i,
                        "conv_tail_bytes": 1}} for i in range(4)]
    monkeypatch.setattr(tracing, "finished_spans", lambda: list(ticks))
    by_scope = {"paged_decoder/conv_in_proj": 5e-4,
                "paged_decoder/conv_gate": 1e-4,
                "paged_decoder/conv_out_proj": 2e-4,
                "paged_decoder/moe_experts": 32e-4}
    monkeypatch.setattr(
        profiler, "scope_seconds",
        lambda ops, label, inherited_only=False:
            {} if inherited_only else dict(by_scope))
    run = common.Run()
    run.trace = {"op_seconds": {}}
    run.notes["trace_slice_wall"] = (10.0, 12.0)    # ticks 0 and 1
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.cell = types.SimpleNamespace(config=FILE)
    got = {n: r.compute(run) for n, r in readers.items()}
    assert got["serve_short_conv_share"] == pytest.approx(20.0)
    assert got["serve_short_conv_roofline"] == pytest.approx(
        100 * 7 * (2 * 33566720 + 201 * a_lane) / 819e9 / 8e-4)
    assert 0 < got["serve_short_conv_roofline"] < 100
    # a program without the counts or the scopes (the parent's):
    # nothing, and no error
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        dict(s, attrs={"state_lanes": 5, "state_resets": 1})
        for s in ticks])
    assert readers["serve_short_conv_roofline"].compute(run) is None
    by_scope = {"paged_decoder/ssm_conv": 2e-4,
                "paged_decoder/moe_experts": 6e-4}
    monkeypatch.setattr(tracing, "finished_spans", lambda: list(ticks))
    assert {n: r.compute(run) for n, r in readers.items()} == dict.fromkeys(
        readers)
    run.trace = None
    assert {n: r.compute(run) for n, r in readers.items()} == dict.fromkeys(
        readers)
    bench = _json("BENCHMARK.json")
    specs = [m for m in bench["per_layer"] if m["name"] in readers]
    assert [m["name"] for m in specs] == list(readers)
    for spec in specs:
        mod = readers[spec["name"]]
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            spec["layer"], spec["unit"], spec["moves"], spec["source"])
        assert spec["workloads"] == [CELL]
    assert specs[-1]["better"] == "higher"


def test_the_job_makes_the_two_assumed_arrays_and_reads_the_tails():
    """`perf/jobs/serve_lm_conv.py`: the taps uniform in +-1/sqrt(3),
    the choice bias at its sigma, the head norms' scales log-uniform in
    1/2 to 2, everything else `serve_lm_ring`'s;
    the walk through the served step returns lane 0's tails; the share
    of choices the bias moved is counted on the router's own inputs."""
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        job = common.load_module(os.path.join(
            ROOT, "perf", "jobs", "serve_lm_conv.py"))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    dec = _decoder("bf16")
    g = job.make_weights(dec.state_shapes, 3000000019, jnp.float32)
    ring = job.state.ring.make_weights(dec.state_shapes, 3000000019,
                                       jnp.float32)
    special = [n for n in g if n.endswith((
        ".conv.w_0", "router_bias.b_0", "q_norm.scale_0", "k_norm.scale_0"))]
    assert len(special) == N_CONV + 3 + 2
    for n in g:
        assert (n in special) != np.array_equal(g[n], ring[n]), n
    taps = np.concatenate([np.asarray(g[n]).ravel() for n in special
                           if n.endswith(".conv.w_0")])
    assert np.abs(taps).max() <= 3 ** -0.5 < 1.1 * np.abs(taps).max()
    bias = np.concatenate([np.asarray(g[n]) for n in special
                           if n.endswith("b_0")])
    assert 0.5 * job.BIAS_SIGMA < bias.std() < 1.5 * job.BIAS_SIGMA
    assert str(job.BIAS_SIGMA) in FILE["assumed"]["expert_bias"]
    scales = np.concatenate([np.asarray(g[n]) for n in special
                             if n.endswith("norm.scale_0")])
    assert 0.5 <= scales.min() < 0.7 and 1.5 < scales.max() <= 2.0
    toks = IDS[:9]
    logits, routing = job.system_outputs(dec, g, toks, 3)
    assert logits.shape == (9, V)
    assert routing["tails"].shape == (N_CONV, TAPS - 1, D)
    assert routing["inputs"].shape == (3, 9, D)
    out = REF.compare(g, CONFIG, toks, logits, routing)
    assert out["tail_rms_err"] < TOL_BF16_POOL > out["logits_rms_err"]
    share = job.bias_moved_choice_share(g, routing, K)
    assert 0.0 <= share <= 1.0
    big = dict(g, **{n: 10 * g[n] for n in special if n.endswith("b_0")})
    assert job.bias_moved_choice_share(big, routing, K) > share


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """`perf/run_cell.py --rehearse`, traced, in a process of its own:
    the toy through the whole job (weights, the walk against the
    reference, the ramp, the window, the served requests against the
    reference) is `correct`, and the span-sourced metrics of the new
    block are in the line.  The two new metrics read a DEVICE trace,
    which the CPU has none of: the synthetic run above holds them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run_cell.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearse"], cwd=ROOT, env=env, timeout=600,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    notes, line = lines[-2]["notes"], lines[-1]
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert notes["reference"]["ok"] and notes["served"]["ok"]
    assert notes["served"]["reused_lanes"] >= 1
    assert "tail_rms_err" in notes["reference"]
    assert 0 <= notes["reference"]["bias_moved_choice_share"] <= 1
    assert notes["state"] == {
        "layers": N_CONV, "bytes_per_lane": N_CONV * (TAPS - 1) * D * 4,
        "bytes": 4 * N_CONV * (TAPS - 1) * D * 4}
    for name in ("sched_state_reset_share", "moe_held_experts_hit_share",
                 "sched_pool_wait_share", "tick_ms", "sched_build_ms"):
        assert name in line["metrics"], name
    assert line["metrics"]["sched_pool_wait_share"]["value"] == 0.0


# sha256 of the lowered served step (StableHLO text, no locations) of
# four other configurations' toys, taken at the parent commit of the PR
# that added conv layers: a description without them computes what it
# computed
# (a block with experts: taken again at PR 63, whose routing orders
# nothing: `tests/test_moe_routing.py` holds it to the results it had)
PARENTS_STEPS = {
    "granite-4.0-h-small-1chip":
        "253a1dfac12af82aae0c4a96fde88a25ce72655e5821ba4f294308b4bb447b37",
    "olmoe-1b-7b-1chip":
        "581e73cc9cf988400e7f3617e6d7daed41b3b7f4f9640d37a2a2c5183dad795b",
    "k-exaone-236b-a23b-1chip":
        "4ffbd3e82acdc257b7942dc29e8e4eb0490383b28ace53b1c7d480a6fa040522",
    "glm-5.2-1chip":
        "c2087f45f65f60cf050d6351ca811905c6c4db817d265cde6d714819933e5ca5",
}


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_other_toys_lowered_steps_are_the_parents_text(name):
    m = _json("perf", "configs", name + ".json")
    m.update(m["rehearse"])
    spec, d_inner = _block(m)
    slots, bs, nb = 2, 4, 4
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], bs, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=d_inner, kv_dtype="bf16", platform="cpu", block=spec)
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    ring = dec.window_blocks_per_seq
    pools = jax.eval_shape(lambda: dec.init_pool(
        slots * nb + 1, window_blocks=slots * ring + 1, lanes=slots))
    tables = sds((slots, nb), np.int32)
    if ring:
        tables = (tables, sds((slots, ring), np.int32))
    i32 = sds((slots,), np.int32)
    text = dec.step.lower(
        g, *pools, tables, i32, i32, sds((slots,), np.uint32),
        sds((slots,), np.float32), sds((slots,), np.bool_)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEPS[name]
