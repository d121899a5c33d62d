"""The Mellum 2 block (grouped-query heads wider than the model, sliding
and full layers 3:1, YaRN on the full layers, renormalised top-k
experts) through `build_lm_paged_decoder`, the paged table and the
ring of the sliding layers, against the plain reference
`perf/reference/mellum2.py`, at toy widths on the CPU with seeded
random float32 weights.

The toy keeps what makes the geometry: `H * DH` (64) is not `D` (48),
8 query heads share a K/V head, the layer kinds have period 4, and the
window (8 positions, 2 blocks) is shorter than the sequences, so every
comparison runs past the ring's first wrap.  What is compared is
LOGITS, never tokens.
"""
import functools
import importlib.util
import json
import math
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
V, D, H, HKV, DH, E, F, K, L = 97, 48, 16, 2, 4, 8, 16, 2, 8
BS, NB, WINDOW = 4, 8, 8                        # 32 positions, ring of 2
KINDS = [lm_block.SLIDING] * 3 + [lm_block.FULL]
ROPE = {"sliding_attention": {"rope_type": "default", "rope_theta": 500.0},
        "full_attention": {"rope_type": "yarn", "rope_theta": 500.0,
                           "factor": 4, "beta_fast": 4, "beta_slow": 1,
                           "original_max_position_embeddings": 16,
                           "attention_factor": 1.2}}
CONFIG = {"num_attention_heads": H, "num_key_value_heads": HKV,
          "head_dim": DH, "num_experts_per_tok": K, "rms_norm_eps": 1e-6,
          "norm_topk_prob": True, "num_hidden_layers": L,
          "layer_types": KINDS * 2, "sliding_window": WINDOW,
          "rope_parameters": ROPE}
# float32 weights and pool: the same float32 sums in another order
# (grouped matmul against a dense masked einsum, ring and table against
# a banded mask over recomputed keys): measured 4e-7 to 1.2e-6 over
# four seeds
TOL_FP32 = 1e-4
# bf16 pool: K and V rounded to 8 bits of mantissa on their way into
# table and ring; over eight layers measured 3e-3 to 1.1e-2
TOL_BF16_POOL = 4e-2


def _reference():
    path = os.path.join(ROOT, "perf", "reference", "mellum2.py")
    spec = importlib.util.spec_from_file_location("ref_mellum2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="mellum", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, norm_eps=1e-6, n_experts=E,
        experts_per_token=K, norm_topk_prob=True, n_kv_heads=HKV,
        d_head=DH, layer_types=KINDS * 2, window=WINDOW,
        rope_parameters=ROPE), **over))


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


def _drive(dec, g, seqs, slots=None, lanes=None, routing=False,
           pools=None):
    """Teacher-force each of `seqs` through `step` in its own slot, the
    tables taken from a `PagedKVCache` and the rings from the lanes, as
    the server takes them; returns each sequence's [len, V] logits
    (and lane 0's routing stacked over positions).  `pools` continues
    on pools an earlier drive left (-> the pools are returned too)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    ring = dec.window_blocks_per_seq
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(
        1 + slots * NB, window_blocks=1 + slots * ring)
    tables = np.zeros((slots, NB), np.int32)
    rings = dec.slot_rings(slots)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed = [[] for _ in seqs], []
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
        _, pool_k, pool_v, _ = dec.step(*args)
        for i, (s, lane) in enumerate(zip(seqs, lanes)):
            if pos < len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if pools is not None:
        return out, (pool_k, pool_v)
    if routing:
        return out, {k: np.concatenate([r[k] for r in routed], 1)
                     for k in routed[0]}
    return out


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _ref_logits(g, seq, **kw):
    return np.asarray(REF.forward(g, CONFIG, np.asarray(seq, np.int32),
                                  **kw)[0])


SEQ = list(np.random.RandomState(7).randint(0, V, 29))   # 3.6 windows


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_table_and_ring_match_reference_past_the_wrap(kv_dtype, tol):
    """29 positions through both kinds of cache (the ring of 8 wraps
    three times) against the reference's banded and causal masks over
    the whole sequence."""
    dec = _decoder(kv_dtype)
    assert dec.window_blocks_per_seq == WINDOW // BS and dec.window == WINDOW
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    # free-running where no near-tie falls the other way, else following
    rel = _rel(got, _ref_logits(g, SEQ, follow=routing["experts"]))
    assert rel <= tol, rel
    out = REF.compare(g, CONFIG, np.asarray(SEQ, np.int32), got, routing)
    assert out["finite"] and out["past_window_rms_err"] <= tol
    assert out["router_rel_err"] <= 1e-4, out


def test_the_comparison_refuses_a_wrong_mask_rope_and_precision():
    """What `compare` must tell apart at these widths: a full mask on
    the sliding layers, plain RoPE on the full layers, and the whole
    model in bfloat16 each read far above the float32 decoder."""
    dec = _decoder()
    g = _weights(dec)
    ids = np.asarray(SEQ, np.int32)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, ids, got, routing)
    assert ok["logits_rms_err"] <= TOL_FP32
    faults = REF.faults(g, CONFIG, ids)
    assert set(faults) == {"full_mask", "plain_rope"}
    for name, out in faults.items():
        assert out["logits_rms_err"] > 100 * TOL_FP32, (name, out)
    # a full mask differs only where the window has been left behind
    first = np.abs(_ref_logits(g, SEQ, fault="full_mask")
                   - _ref_logits(g, SEQ)).max(-1)
    assert first[:WINDOW].max() == 0.0 and first[WINDOW:].min() > 0.0
    assert REF.below(g, CONFIG, ids)["logits_rms_err"] > 20 * TOL_FP32


def test_reference_follows_where_told_and_takes_its_own_elsewhere():
    """`forward(follow=...)`: a negative row leaves a position its own
    experts, so following nothing is the free-running pass bit for
    bit, and following another choice at one position moves the
    logits from that position on and at none before it."""
    g = _weights(_decoder())
    free, routing = REF.forward(g, CONFIG, np.asarray(SEQ, np.int32))
    own = np.asarray(routing["experts"])
    nothing = np.full_like(own, -1)
    assert np.array_equal(_ref_logits(g, SEQ, follow=nothing), free)
    assert np.array_equal(_ref_logits(g, SEQ, follow=own), free)
    other = nothing.copy()
    other[0, 10] = (own[0, 10] + 1) % E      # layer 0, position 10
    moved = np.abs(_ref_logits(g, SEQ, follow=other) - free).max(-1)
    assert moved[:10].max() == 0.0 and moved[10:].min() > 0.0


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["ragged_dot", "pallas_interpreted"])
def test_batched_slot_bit_identical_to_the_same_sequence_alone(
        kernel, monkeypatch):
    """Three sequences of different lengths in one call of the same
    four-lane step, the sequence in another lane and other table and
    ring blocks than alone: bit for bit the same logits (no capacity
    in the expert layer, no slot in the mask, no order in the ring),
    through `ragged_dot` and through the Pallas grouped matmul under
    the interpreter (the step asks for the kernel when it is traced)."""
    if kernel:
        monkeypatch.setattr(
            grouped_matmul, "select_grouped_matmul", functools.partial(
                grouped_matmul.select_grouped_matmul, interpret=True))
    dec = _decoder()
    g = _weights(dec, seed=3)
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,) = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    together = _drive(dec, g, [others[0], SEQ, others[1]], slots=4,
                      lanes=[3, 1, 0])
    assert np.array_equal(together[1], alone)
    assert dec.expert_kernel == (grouped_matmul.NAME if kernel
                                 else "xla:not_tpu")


def test_yarn_table_is_the_published_equations():
    """`lm_block.yarn_inv_freq` against a direct transcription at the
    published sizes (d 128, theta 500000, factor 16, original 8192,
    beta 32 and 1), and `rope_tables` against cos and sin of it times
    the attention factor."""
    p = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
         "original_max_position_embeddings": 8192, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": 1.2772588722239782}
    d, theta = 128, 500000.0

    def c(r):
        return d * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))

    low, high = max(math.floor(c(32)), 0), min(math.ceil(c(1)), d - 1)
    assert (low, high) == (18, 35)
    want = []
    for i in range(d // 2):
        extrap = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(extrap / 16 * ramp + extrap * (1 - ramp))
    got = lm_block.yarn_inv_freq(p, d)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(REF.inv_freq(p, d), want, rtol=1e-12)
    assert got[18] == want[18] == theta ** (-36 / d)       # ramp 0
    assert got[35] == theta ** (-70 / d) / 16              # ramp 1
    spec = _block(rope_parameters={"full_attention": p,
                                   "sliding_attention": ROPE[
                                       "sliding_attention"]})
    pos = jnp.asarray([0, 1, 1023, 4095])
    cos, sin = lm_block.rope_tables(spec, pos, d, lm_block.FULL)
    ang = np.asarray(pos, np.float64)[:, None] * np.asarray(want)
    np.testing.assert_allclose(
        cos, np.cos(np.concatenate([ang, ang], -1)) * p["attention_factor"],
        atol=2e-3)      # float32 angles of up to 4095 radians
    assert float(cos[0, 0]) == pytest.approx(p["attention_factor"])
    cos_s, _ = lm_block.rope_tables(spec, pos, d, lm_block.SLIDING)
    assert float(cos_s[0, 0]) == 1.0


def test_top_k_weights_are_renormalised():
    dec = _decoder()
    g = _weights(dec)
    _, routing = _drive(dec, g, [SEQ[:5]], routing=True)
    np.testing.assert_allclose(routing["weights"].sum(-1), 1.0, atol=1e-6)
    plain = _decoder(norm_topk_prob=False)
    _, routing = _drive(plain, g, [SEQ[:5]], routing=True)
    assert (routing["weights"].sum(-1) < 0.99).all()


def test_description_is_hashable_from_json_and_checked():
    """The configuration's JSON list and dict become nested tuples; a
    geometry nothing builds is refused by name."""
    spec = _block(layer_types=list(KINDS * 2),
                  rope_parameters=json.loads(json.dumps(ROPE)))
    assert spec == _block() and hash(spec) == hash(_block())
    assert spec.rope_of(lm_block.FULL)["factor"] == 4
    assert spec.kind_of(3) == lm_block.FULL
    with pytest.raises(ValueError, match="unknown kind"):
        _block(layer_types=["chunked_attention"])
    with pytest.raises(ValueError, match="window >= 1"):
        _block(window=0)
    with pytest.raises(ValueError, match="do not share"):
        _decoder(n_kv_heads=3)
    with pytest.raises(ValueError, match="whole number"):
        _decoder(window=6)
    with pytest.raises(ValueError, match="4 layer_types, and a layer 4"):
        _decoder(layer_types=KINDS)
    with pytest.raises(NotImplementedError, match="qk_norm"):
        _decoder(qk_norm=True)
    with pytest.raises(NotImplementedError, match="rope_type 'llama3'"):
        _decoder(rope_parameters={
            "full_attention": {"rope_type": "llama3", "rope_theta": 1.0}})
    dec = _decoder()
    assert dec.state_shapes["layer_0.q_proj.w_0"] == (D, H * DH)
    assert dec.state_shapes["layer_0.k_proj.w_0"] == (D, HKV * DH)
    assert dec.state_shapes["layer_0.o_proj.w_0"] == (H * DH, D)
    assert dec.kernels == {"paged_attention_decode": "xla:not_tpu",
                           "paged_attention_window": "xla:not_tpu"}
    # K+V of a block over the layers that hold it
    assert dec.bytes_per_block == 2 * 2 * BS * HKV * DH * 4
    assert dec.window_bytes_per_block == 2 * 6 * BS * HKV * DH * 4


def test_int8_pool_and_step_window_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="int8 pool"):
        _decoder("int8")
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3, window_blocks=3)
    z = np.zeros(1, np.int32)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(
            _weights(dec), pool_k, pool_v,
            (np.zeros((1, NB), np.int32), np.zeros((1, 2), np.int32)), z,
            np.zeros((1, 2), np.int32), z.astype(np.uint32),
            z.astype(np.float32), z)
    with pytest.raises(ValueError, match="window_blocks"):
        dec.init_pool(3)
    # grouped heads without sliding layers keep int8 and step_window
    full = _decoder("int8", layer_types=(), window=0)
    assert full.window_blocks_per_seq == 0
    assert full.kernels["paged_attention_decode"] == "xla:not_tpu"


def test_a_lane_is_a_ring_and_a_reused_one_shows_no_stale_key():
    """`slot_rings` gives every lane its own blocks of the ring pool,
    none of them the null block; a sequence run in a lane whose ring
    (and table blocks) still hold ANOTHER sequence's keys, longer than
    itself and past the wrap, gives bit for bit what it gives on zero
    pools: the mask from the cursor shows only what it wrote."""
    dec = _decoder()
    rings = dec.slot_rings(3)
    assert rings.shape == (3, WINDOW // BS) and rings.dtype == np.int32
    assert sorted(rings.ravel()) == list(range(1, 1 + rings.size))
    g = _weights(dec)
    r = np.random.RandomState(5)
    first, second = (list(r.randint(0, V, n)) for n in (3 * WINDOW, 13))

    def zero():
        return dec.init_pool(1 + 2 * NB, window_blocks=1 + 2 * WINDOW // BS)

    (fresh,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=zero())
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    (again,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=used)
    assert np.array_equal(fresh, again)
    assert np.isfinite(again).all()


def test_generation_server_serves_both_kinds_of_state():
    """Requests longer than the window through `GenerationServer`,
    continuously batched, give the tokens of the same request on a
    one-slot server (whose second and third requests run on a ring
    their predecessor filled) and on two slots, where the third waits
    for a slot and reuses it; the tick spans carry the rows' counts;
    what a ring cannot serve is refused by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    # the block's own word, which the server raises as it stands
    # (the prefix cache is served through snapshots of a lane's rings
    # since PR 65: tests/test_dots3_note_decoder.py holds a hit to the
    # run without a cache on this toy)
    assert set(dec.refuses) == {"draft_model"}
    assert all("sliding-window layers" in why
               for why in dec.refuses.values())
    assert dec.init_snapshots is not None
    GenerationServer(dec, g, slots=2, kv_blocks=16, place=place).close()
    with pytest.raises(ValueError, match="no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=16, place=place,
                         prefix_cache=False, draft_decoder=dec,
                         draft_states=g)
    prompts = [list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 5), (2, 11), (3, 3))]
    solo = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                            prefix_cache=False)
    try:
        want = [solo.generate(p, 18) for p in prompts]
        assert solo.stats()["kv_window_blocks"] == 2
    finally:
        solo.close()
    two = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                           prefix_cache=False)
    try:
        streams = [two.submit(p, 18) for p in prompts]
        assert [s.result(timeout=120) for s in streams] == want
    finally:
        two.close()
    spans = []
    tracing.add_span_listener(spans.append)
    srv = GenerationServer(dec, g, slots=3, kv_blocks=3 * NB, place=place,
                           prefix_cache=False)
    try:
        streams = [srv.submit(p, 18) for p in prompts]
        assert [s.result(timeout=120) for s in streams] == want
        stats = srv.stats()
        assert stats["kv_window_blocks"] == 6
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
        assert stats["kv_bytes_resident"] == 0
        # a request that needs more table blocks than are free waits,
        # and one beyond the context is refused at submit
        with pytest.raises(ValueError, match="per-sequence capacity"):
            srv.submit(prompts[0], NB * BS)
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(
        {"past_window", "kv_rows_full", "kv_rows_win"} <= set(a)
        for a in ticks)
    assert max(a["past_window"] for a in ticks) == 3
    assert all(a["kv_rows_win"] <= a["kv_rows_full"]
               and a["kv_rows_win"] <= WINDOW * a["active"] for a in ticks)
    assert any(a["kv_rows_win"] < a["kv_rows_full"] for a in ticks)


def test_served_tokens_are_judged_by_the_reference_alone():
    """`served` knows only the tokens a server delivered: greedy
    requests past the window agree with the reference's argmax and lie
    nowhere below it, a token that was not its argmax shows as a gap,
    and the two faults read the same tokens as disagreeing, the full
    mask past the window alone."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB,
                           place=fluid.CPUPlace(), prefix_cache=False)
    try:
        prompts = [list(np.random.RandomState(s).randint(0, V, n))
                   for s, n in ((7, 4), (8, 9), (9, 6))]
        streams = [srv.submit(p, 20) for p in prompts]
        requests = [(np.asarray(p + s.result(timeout=120), np.int32),
                     len(p)) for p, s in zip(prompts, streams)]
    finally:
        srv.close()
    cfg = dict(CONFIG, vocab_size=V)
    out = REF.served(g, cfg, requests)
    assert out["tokens"] == 60 and out["tokens_past_window"] == \
        sum(len(ids) - 1 - max(start - 1, WINDOW)
            for ids, start in requests)
    assert out["served_argmax_agree"] == 1.0 == \
        out["past_window_argmax_agree"]
    assert out["served_gap_rms"] == 0.0 == out["past_window_gap_rms"]
    # one delivered token swapped for another: one disagreement
    ids, start = requests[0]
    wrong = ids.copy()
    wrong[-1] = (wrong[-1] + 1) % V
    one = REF.served(g, cfg, [(wrong, start)])
    assert one["served_argmax_agree"] == 1.0 - 1.0 / 20
    assert one["served_gap_rms"] > 0.0
    full = REF.served(g, cfg, requests, fault="full_mask")
    rope = REF.served(g, cfg, requests, fault="plain_rope")
    assert full["past_window_argmax_agree"] < 0.9, full
    assert rope["served_argmax_agree"] < 0.9, rope
    assert full["past_window_gap_rms"] > 1e-3 < rope["served_gap_rms"]


def test_scopes_tell_ring_from_table_and_sum_under_the_old_names():
    """`paged_decoder/kv_gather/{sliding,full}` and
    `paged_decoder/attention/{sliding,full}` in the step's compiled
    text; OPT's step has neither sub-scope."""
    import jax

    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3, window_blocks=3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v,
        (np.zeros((2, NB), np.int32), np.zeros((2, 2), np.int32)), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("kv_gather", "attention"):
        for kind in ("sliding", "full"):
            assert f"paged_decoder/{part}/{kind}" in text, (part, kind)
    assert jax.default_backend() == "cpu"


def _config_file():
    with open(os.path.join(ROOT, "perf", "configs",
                           "mellum2-12b-a2.5b-1chip.json")) as f:
        return json.load(f)


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/mellum2-12b-a2.5b-1chip.json's `block`, read as the
    benchmark's job reads it, builds the decoder at the published
    widths (shapes only: nothing is allocated), and the parameter and
    cache arithmetic the file states is the decoder's own."""
    m = _config_file()
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.layer_types == tuple(KINDS * 2) and spec.window == 1024
    assert spec.norm_topk_prob and not spec.qk_norm
    assert spec.rope_of(lm_block.FULL)["rope_type"] == "yarn"
    assert m["reduced"] == ["num_hidden_layers"] and b["d_inner"] == \
        "moe_intermediate_size"
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 256, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu",
        block=spec)
    # table and ring both through the streaming kernel
    assert dec.kernels["paged_attention_decode"] == "pallas"
    assert (dec.table_layers, dec.ring_layers) == (2, 6)
    shapes = dec.state_shapes
    assert shapes["layer_0.q_proj.w_0"] == (2304, 4096)
    assert shapes["layer_0.v_proj.w_0"] == (2304, 512)
    assert shapes["layer_7.experts_down.w_0"] == (64, 896, 2304)
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("layer_0."))
    assert round(layer / 1e6, 1) == 417.7               # the file's count
    assert round(sum(int(np.prod(s)) for s in shapes.values()) * 2 / 1e9,
                 2) == 7.59                             # GB in bfloat16
    # 2048 B a position a layer; 2 full layers' table, 6 layers' rings
    assert dec.window_blocks_per_seq == 64
    assert dec.bytes_per_block == 2 * 2048 * 16
    assert dec.window_bytes_per_block == 6 * 2048 * 16
    table_gb = dec.bytes_per_block * 96 * 256 / 1e9
    rings_gb = dec.window_bytes_per_block * 96 * 64 / 1e9
    assert (round(table_gb, 2), round(rings_gb, 2)) == (1.61, 1.21)
    ids = np.zeros(3, np.int32)
    toy = dict(CONFIG)
    g = _weights(_decoder())
    assert set(m["compare"]["limits"]) <= set(
        REF.compare(g, dict(toy, sliding_window=2), ids,
                    *REF.forward(g, dict(toy, sliding_window=2), ids)))


def test_attention_bytes_from_shapes():
    """perf/attention_bytes.py at the cell's widths: what
    `serve_attention_roofline` divides by."""
    path = os.path.join(ROOT, "perf", "attention_bytes.py")
    spec = importlib.util.spec_from_file_location("attention_bytes", path)
    ab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ab)
    assert ab.kv_row_bytes(4, 128) == 1024
    kinds = _config_file()["layer_types"]
    # one slot at cursor 2999: 3000 rows on 2 layers, 1024 on 6, K and V
    assert ab.kv_read_bytes(3000, 1024, kinds, 4, 128) == \
        2 * 1024 * (2 * 3000 + 6 * 1024)
    # before the window both kinds read the same rows
    assert ab.kv_read_bytes(10, 10, kinds, 4, 128) == 2 * 1024 * 8 * 10
