"""The Falcon-H1 block (lm_block's fourteenth description: EVERY layer a
Mamba-2 mixer with groups of B and C AND grouped-query attention under
RoPE, both reading the layer's ONE normed input, a dense SwiGLU, an
untied head, the family's muP multipliers) through
`build_lm_paged_decoder` and `GenerationServer` against the plain
reference `perf/reference/falcon_h1.py`, at toy widths on the CPU with
seeded random float32 weights.

The toy keeps what makes the model: two groups of B and C under four
heads, five query heads' worth of grouping (4 over 2 K/V heads), every
one of the eleven multipliers at a value of its own (so that a swap of
two shows), three layers that each own a plane of the lanes' states, of
their tails AND of the K/V table.  What is compared is LOGITS, states,
tails and cache ROWS; streams are compared with streams.
"""
import dataclasses
import hashlib
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
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "falcon-h1-34b-serve-docqa64"
FILE = ("perf", "configs", "falcon-h1-34b-1chip.json")
V, D, H, KV, DH, L, F = 97, 32, 4, 2, 8, 3, 48
SH, SP, SN, SG, SW = 4, 8, 16, 2, 4         # heads, head, state, groups, taps
BS, NB = 4, 16                              # 64 positions
CONFIG = {
    "hidden_size": D, "num_attention_heads": H, "num_key_value_heads": KV,
    "head_dim": DH, "intermediate_size": F, "vocab_size": V,
    "num_hidden_layers": L, "rms_norm_eps": 1e-5, "rope_theta": 1e11,
    "mamba_n_heads": SH, "mamba_d_head": SP, "mamba_d_ssm": SH * SP,
    "mamba_d_state": SN, "mamba_n_groups": SG, "mamba_d_conv": SW,
    "ssm_multipliers": [0.7, 0.9, 0.6, 1.2, 0.8],
    "ssm_in_multiplier": 0.5, "ssm_out_multiplier": 0.7,
    "attention_in_multiplier": 1.0, "attention_out_multiplier": 0.4,
    "key_multiplier": 0.3, "mlp_multipliers": [0.6, 0.25],
    "embedding_multiplier": 2.0, "lm_head_multiplier": 0.5,
    "tie_word_embeddings": False, "layer_types": ["mamba"] * L}
STATE, TAIL = (SH, SP, SN), (SW - 1, SH * SP + 2 * SG * SN)
TOL_FP32 = 1e-4
# the toy's limits, between the decoder's readings (1e-6) and what must
# fail (all-bfloat16 from 3e-3, every fault more on the limit that
# names what it broke)
LIMITS = {"logits_rel_err": 1e-3, "logits_rms_err": 1e-3,
          "late_rms_err": 1e-3, "state_rms_err": 1e-3,
          "tail_rms_err": 1e-3, "scan_rel_err": 1e-4, "kv_rms_err": 1e-3}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_falcon_h1", "perf", "reference", "falcon_h1.py")


def _file_block(m):
    b = m["block"]
    return lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()})), \
        m[b["d_inner"]]


def _block(m=CONFIG, **over):
    """The description as the configuration FILE makes it: its literal
    fields and its `from_keys` over the toy's keys."""
    b = _json(*FILE)["block"]
    return lm_block.BlockSpec(**dict(dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}), **over))


def _decoder(kv_dtype="fp32", m=CONFIG, nb=NB, platform="cpu", **over):
    startup, dec = build_lm_paged_decoder(
        m["vocab_size"], BS, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m["intermediate_size"], kv_dtype=kv_dtype,
        platform=platform, block=_block(m, **over))
    assert startup is None
    return dec


def _weights(dec, seed=0):
    """Seeded float32 weights at which every part matters: matrices at
    sigma 0.1 (q, k, the mixer's input and the gate's three to five
    times that: peaked scores, a state that adds to `D x`, a SiLU off its
    linear part), the taps uniform in +-1/2, decays and step sizes as
    Mamba-2 draws them."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        if n.endswith("ssm_conv.w_0"):
            w = r.uniform(-0.5, 0.5, shape)
        elif n.endswith("ssm_a_log.w_0"):
            w = np.log(r.uniform(1.0, 16.0, shape))
        elif n.endswith("ssm_dt.b_0"):
            dt = r.uniform(1e-3, 1e-1, shape)
            w = dt + np.log(-np.expm1(-dt))
        else:
            w = r.normal(0, 0.1, shape) * (
                5.0 if n.endswith(("ffn_gate.w_0", "q_proj.w_0",
                                   "k_proj.w_0")) else
                3.0 if n.endswith("ssm_in_proj.w_0") else 1.0)
            if ".scale_" in n or n.endswith("ssm_d.w_0"):
                w = 1.0 + w
        g[n] = jnp.asarray(w, jnp.float32)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None, left=False):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); -> each sequence's
    [len, V] logits and, with `left`, what lane `lanes[0]`'s walk left:
    `ssm_inputs` over its positions, its states and tails of every layer
    and its table rows of every layer; and of its LAST position the
    states that position found (`state_before`, read from the lanes'
    pool) and what `step_routing`, the program that reports the inputs,
    says each layer's recurrence left (`ssm_states`)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = dec.init_pool(1 + slots * NB, lanes=slots)
    # EVERY layer is in both pools
    assert pool_k[0].shape[0] == L == pool_v[0].shape[0]
    assert [s.shape for s in pool_k[1]] == [(slots,) + STATE] * L
    assert [t.shape for t in pool_v[1]] == [(slots,) + TAIL] * L
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, given, last = [[] for _ in seqs], [], {}
    end = starts[0] + len(seqs[0]) - 1
    for tick in range(max(t + len(s) for s, t in zip(seqs, starts))):
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane, t0 in zip(seqs, lanes, starts):
            if t0 <= tick < t0 + len(s):
                toks[lane], pos[lane], act[lane] = s[tick - t0], tick - t0, \
                    True
        args = (g, pool_k, pool_v, tables, pos, toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        assert set(r) == {"ssm_inputs", "ssm_states"}   # nothing is routed
        lg = np.asarray(lg)
        if act[lanes[0]]:
            given.append(np.asarray(r["ssm_inputs"])[:, lanes[0]])
        if tick == end:
            last = {"state_before": np.stack(
                [np.asarray(h)[lanes[0]] for h in pool_k[1]]),
                "ssm_states": np.asarray(r["ssm_states"])[:, lanes[0]]}
        _, pool_k, pool_v, *counts = jax.block_until_ready(dec.step(*args))
        assert not counts and dec.step_counters == ()
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    logits = [np.stack(o) for o in out]
    if not left:
        return logits
    n = len(seqs[0])
    blocks = tables[lanes[0], :-(-n // BS)]

    def rows(pool):
        return np.asarray(pool[0][:, blocks], np.float32).reshape(
            L, -1, KV * DH)[:, :n]

    return logits, {
        "state": np.stack([np.asarray(s)[lanes[0]] for s in pool_k[1]]),
        "tails": np.stack([np.asarray(t)[lanes[0]] for t in pool_v[1]]),
        "k_rows": rows(pool_k), "v_rows": rows(pool_v),
        "ssm_inputs": np.stack(given, 1), **last}


SEQ = list(np.random.RandomState(7).randint(0, V, 57))   # over 14 blocks
IDS = np.asarray(SEQ, np.int32)


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", 4e-2)])
def test_prompt_then_decode_equals_the_references_full_forward(kv_dtype,
                                                               tol):
    """Every position of a sequence through the paged step (a lane's
    states and tails AND the table on every layer) against the
    reference's ONE forward pass over the sequence: logits, states,
    tails, the recurrence's inputs and the table's rows."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), left = _drive(dec, g, [SEQ], slots=2, left=True)
    want, own = REF.forward(g, CONFIG, IDS)
    assert np.abs(got - want).max() / np.abs(want).max() < tol
    for name in ("state", "tails", "state_before", "ssm_inputs",
                 "ssm_states", "k_rows", "v_rows"):
        a, b = left[name], np.asarray(own[name])
        assert a.shape == b.shape, name
        assert np.abs(a - b).max() / np.abs(b).max() < tol, name
    out = REF.compare(g, CONFIG, IDS, got, left)
    assert out["finite"] and out["argmax_agree"] > 0.9
    if kv_dtype == "fp32":
        assert not _refused(out), out


def test_a_reused_lane_reads_as_a_fresh_one_and_an_idle_lane_keeps_still():
    """A lane that held another sequence starts the next from zero
    states and tails (its cursor is 0), whatever its table rows held;
    a lane with no sequence keeps states and tails bit for bit."""
    dec = _decoder()
    g = _weights(dec)
    other = list(np.random.RandomState(9).randint(0, V, 23))
    (alone,) = _drive(dec, g, [SEQ[:20]], slots=2)
    pool_k, pool_v = dec.init_pool(1 + 2 * NB, lanes=2)
    tables = np.zeros((2, NB), np.int32)
    tables[0, :6] = 1 + np.arange(6)
    zs, zt = np.zeros(2, np.uint32), np.zeros(2, np.float32)
    act = np.array([True, False])
    held = None
    for seq in (other, SEQ[:20]):
        got = []
        for pos, tok in enumerate(seq):
            args = (g, pool_k, pool_v, tables, np.array([pos, 0], np.int32),
                    np.array([tok, 0], np.int32), zs, zt, act)
            got.append(np.asarray(dec.step_logits(*args))[0])
            _, pool_k, pool_v = dec.step(*args)
            idle = [np.asarray(t)[1] for t in pool_k[1] + pool_v[1]]
            assert held is None or all(
                (a == b).all() for a, b in zip(idle, held))
            held = idle
    assert (np.stack(got) == alone).all()
    assert all((h == 0).all() for h in held)


def test_a_sequence_among_others_is_bit_identical_to_itself_alone():
    """Lanes out of step, in other lanes and other blocks: a sequence's
    logits are those of the same sequence beside idle lanes, bit for
    bit (compared at the SAME lane count: the CPU's gemm tiles by
    batch)."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    others = [list(r.randint(0, V, n)) for n in (31, 12)]
    (alone,) = _drive(dec, g, [SEQ[:40]], slots=3, lanes=[1])
    among = _drive(dec, g, [SEQ[:40]] + others, lanes=[1, 0, 2],
                   starts=[3, 0, 7])
    assert (among[0] == alone).all()


def _refused(out, limits=LIMITS):
    return sorted(k for k, hi in limits.items() if out[k] > hi)


def test_the_comparison_passes_the_decoder_by_every_limit():
    dec = _decoder()
    g = _weights(dec, 1)
    (got,), left = _drive(dec, g, [SEQ], slots=2, left=True)
    out = REF.compare(g, CONFIG, IDS, got, left)
    assert out["finite"] and not _refused(out), out
    assert out["scan_rel_err"] < 1e-6 and out["logits_rms_err"] < 1e-4
    assert len(out["scan_rel_err_by_layer"]) == L


# the limit that names what each reading broke (it may pass others)
REFUSED_BY = {
    "below": "logits_rms_err", "state_bf16": "scan_rel_err",
    "no_reset": "logits_rel_err", "wrong_snapshot": "state_rms_err",
    "shifted_blocks": "kv_rms_err", "no_key_multiplier": "kv_rms_err",
    "rope_theta_1e4": "kv_rms_err", "no_rope": "kv_rms_err"}


@pytest.mark.parametrize("what", ("below",) + REF.FAULTS)
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """The same equations wholly in bfloat16, and each reading of a key
    or of a snapshot that the configuration's `assumed` rules out, are
    each refused by a limit, the one that names what they broke where
    the table above says one; and the logits' limits refuse every one
    that changes an equation."""
    g = _weights(_decoder(), 1)
    out = (REF.below(g, CONFIG, IDS) if what == "below"
           else REF.faults(g, CONFIG, IDS, which=(what,))[what])
    refused = _refused(out)
    assert refused, (what, out)
    assert REFUSED_BY.get(what, "logits_rms_err") in refused, (what, out)
    if what in ("state_bf16", "below"):
        # which no reading of the logits tells from float32 rounding at
        # the served widths: the recurrence on its own inputs does, on
        # EVERY layer
        assert min(out["scan_rel_err_by_layer"]) > 10 * LIMITS[
            "scan_rel_err"]


def test_served_reads_a_servers_tokens_and_refuses_the_snapshot_faults():
    """`served` on requests decoded greedily from the reference's own
    logits reads 1.0 and 0.0; on the same tokens a state restored from
    the wrong document, blocks one block off and each mixer left out
    read worse, from the first delivered tokens on."""
    g = _weights(_decoder(), 1)
    r = np.random.RandomState(11)
    requests = []
    for n in (37, 41):
        ids = list(r.randint(0, V, n))
        for _ in range(12):
            ids.append(int(REF.forward(
                g, CONFIG, np.asarray(ids, np.int32),
                logits_from=len(ids) - 1)[0][0].argmax()))
        requests.append((np.asarray(ids, np.int32), n))
    right = REF.served(g, CONFIG, requests, pad_to=64)
    assert right["served_argmax_agree"] == 1.0
    assert right["served_gap_rms"] == 0.0 and right["tokens"] == 24
    for fault in ("wrong_snapshot", "shifted_blocks", "no_mamba",
                  "no_attention"):
        # (the snapshot faults at each prompt's last position: the
        # toy's states forget in a dozen positions)
        wrong = REF.served(g, CONFIG, requests, pad_to=64, fault=fault,
                           cuts=[start - 1 for _, start in requests])
        assert wrong["early_gap_rms"] > 1e-3, (fault, wrong)


def test_two_groups_of_one_b_and_c_are_the_one_group_mixer():
    """A mixer of two groups whose B and C columns are copies of one
    group's gives the one-group mixer's state, tail rows and recurrence
    inputs (its gated norm alone differs: by group); and the one-group
    description lowers to what Granite's lowered to (the pinned sha256
    below)."""
    one = lm_block.BlockSpec(
        name="granitemoehybrid", norm="rms_norm", positions="none",
        ffn="moe_swiglu", bias=False, ssm_heads=SH, ssm_d_head=SP,
        ssm_d_state=SN, ssm_conv=SW)
    two = _block()
    r = np.random.RandomState(2)
    di, s_n = SH * SP, 3
    w_z, w_x, w_b, w_c, w_dt = (r.normal(0, 0.3, (D, n))
                                for n in (di, di, SN, SN, SH))
    conv_x, conv_b, conv_c = (r.uniform(-0.5, 0.5, (SW, n))
                              for n in (di, SN, SN))
    bias_x, bias_b, bias_c = (r.normal(0, 0.1, n) for n in (di, SN, SN))
    rest = {"ssm_dt": r.normal(-3, 1, SH), "ssm_a_log": np.log(
        r.uniform(1, 16, SH)), "ssm_d": r.normal(1, 0.1, SH),
        "ssm_gate_norm": r.normal(1, 0.1, di),
        "ssm_out": r.normal(0, 0.1, (di, D))}

    def params(groups):
        cat = np.concatenate
        p = dict(rest, ssm_in=cat([w_z, w_x] + [w_b] * groups
                                  + [w_c] * groups + [w_dt], 1),
                 ssm_conv=(cat([conv_x] + [conv_b] * groups
                               + [conv_c] * groups, 1),
                           cat([bias_x] + [bias_b] * groups
                               + [bias_c] * groups)))
        return {k: (tuple(jnp.asarray(a, jnp.float32) for a in v)
                    if isinstance(v, tuple) else jnp.asarray(v, jnp.float32))
                for k, v in p.items()}

    plain = dataclasses.replace(two, ssm_multipliers=())
    state = jnp.asarray(r.normal(0, 1, (s_n,) + STATE), jnp.float32)
    u = jnp.asarray(r.normal(0, 1, (s_n, D)), jnp.float32)
    fresh, live = jnp.array([False, True, False]), jnp.array(
        [True, True, False])
    outs = []
    for spec, groups in ((one, 1), (plain, 2)):
        width = di + 2 * groups * SN
        tail = jnp.ones((s_n, SW - 1, width), jnp.float32)
        outs.append(lm_block.mamba2_step(spec, u, state, tail, fresh, live,
                                         params(groups)))
    (_, h1, t1, g1), (_, h2, t2, g2) = outs
    assert (np.asarray(h1) == np.asarray(h2)).all()
    assert (np.asarray(t1)[..., :di + SN] == np.asarray(t2)[
        ..., :di + SN]).all()
    assert (np.asarray(g1)[:, :di + SN] == np.asarray(g2)[:, :di + SN]).all()
    assert (np.asarray(g1)[:, -SH:] == np.asarray(g2)[:, -SH:]).all()


def _serve(dec, g, prefix, asks, *, slots=3, snapshots=None, blocks=96,
           temperature=1.0):
    """`asks`: (prompt, new tokens) in order, each awaited before the
    next (so that a later one finds what an earlier one cached); ->
    (their streams, the server's stats)."""
    srv = GenerationServer(dec, g, slots=slots, kv_blocks=blocks,
                           place=fluid.CPUPlace(), prefix_cache=prefix,
                           state_snapshots=snapshots)
    try:
        out = [srv.submit(p, n, temperature=temperature,
                          seed=50 + i).result(timeout=120)
               for i, (p, n) in enumerate(asks)]
        return out, srv.stats()
    finally:
        srv.close()


@pytest.mark.parametrize("temperature", [0.0, 1.0],
                         ids=["greedy", "sampled"])
def test_a_hit_restores_every_layers_state_beside_shared_blocks(temperature):
    """A document built through `submit(document, 1)`, then requests
    that are the document and a question: with the prefix cache on each
    maps EVERY layer's shared K/V blocks and restores EVERY layer's
    state and tail from one snapshot; its stream is, token for token,
    that of the same request on a server without a cache, which ran
    every position."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(3)
    doc = list(r.randint(0, V, 6 * BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, V, n)), 9)
                         for n in (5, 7, 2)]
    hit, stats = _serve(dec, g, True, asks, temperature=temperature)
    miss, plain = _serve(dec, g, False, asks, temperature=temperature)
    assert hit == miss
    assert temperature == 0.0 or all(len(set(s)) > 4 for s in hit[1:])
    assert "state_snapshots_saved" not in plain
    assert stats["state_snapshots_restored"] == 3
    assert stats["state_snapshots_saved"] == 3
    assert stats["prefix_blocks_cut"] == 0
    assert stats["prefix_hits"] == 3 * 6
    # a snapshot holds every layer's state and tail, a block every
    # layer's K and V rows
    per = 4 * L * (SH * SP * SN + (SW - 1) * TAIL[1])
    assert dec.state_bytes_per_lane == per and dec.state_layers == L
    assert stats["state_snapshot_pool_bytes"] == 3 * per
    assert dec.bytes_per_block == 2 * L * BS * KV * DH * 4
    assert dec.table_layers == L


def test_served_greedy_requests_agree_with_the_references_logits():
    """Prefill then decode through `GenerationServer`, with the prefix
    cache on and off, a lane reused after another request: every
    delivered token is the argmax of the reference's LOGITS at its
    position (`served`), for the hit, the miss and the reused lane."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 1).items()}
    r = np.random.RandomState(8)
    doc = list(r.randint(0, V, 5 * BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, V, n)), 10)
                         for n in (6, 3, 9)]
    for prefix in (True, False):
        # ONE slot: every request but the first finds a lane another used
        streams, stats = _serve(dec, g, prefix, asks, slots=1,
                                temperature=0.0)
        requests = [(np.asarray(list(p) + list(s), np.int32), len(p))
                    for (p, _), s in zip(asks[1:], streams[1:])]
        out = REF.served(g, CONFIG, requests, pad_to=64)
        assert out["served_argmax_agree"] == 1.0, (prefix, out)
        assert out["tokens"] == 30
        assert bool(stats.get("prefix_hits")) == prefix


def test_spans_and_counts_of_a_tick_with_two_caches():
    """`serving.decode_tick` carries BOTH caches' counts of one tick:
    the lanes with a state, the table's rows under the cursors and its
    pages; and the bytes a tick must move count every layer's state,
    tail and rows."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    doc = list(np.random.RandomState(2).randint(0, V, 4 * BS))
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        _serve(dec, g, True, [(doc, 1), (doc + [1, 2, 3, 4, 5], 4)])
    finally:
        tracing.remove_span_listener(spans.append)
        tracing.finished_spans()
    names = [s["name"] for s in spans]
    assert names.count("generation.phase.snapshot_save") == 2
    assert names.count("generation.phase.snapshot_restore") == 1
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"
             and "state_lanes" in s["attrs"]]
    per, row = dec.state_bytes_per_lane, dec.bytes_per_block // BS
    page = dec.bytes_per_block // L
    assert ticks and all(
        t["kv_rows_win"] == 0 and t["kv_rows_full"] >= t["state_lanes"]
        and t["step_bytes_cache"] == (
            t["kv_pages_read"] * page + 2 * t["state_lanes"] * per
            + t["state_lanes"] * row) for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == 1    # the document


def test_the_bytes_of_a_tick_at_the_published_widths():
    """`tick_counts` of the cell's decoder (built for a TPU, where the
    kernel reads the pages under the cursors): `step_bytes_cache` is 2 x
    `state_lanes` x 5 x 4.256 MB + the pages under the cursors x 32 KiB
    a plane + the rows written x 10 240 B; `step_bytes_weights` every
    array but the embedding."""
    m = _json(*FILE)
    spec, d_inner = _file_block(m)
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], 16, 432, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=5, d_inner=d_inner,
        kv_dtype="bf16", platform="tpu", block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas"
    assert set(dec.refuses) == {"draft_model"}
    dec.weight_itemsize = 2
    cursors = np.array([4869, 3071, 6783, 0, 15, 16])
    counts = dec.tick_counts(cursors, 64)
    lane = 32 * 128 * 256 * 4 + 3 * 5120 * 4             # 4.256 MB
    assert dec.state_bytes_per_lane == 5 * lane == 21278720
    assert dec.bytes_per_block == 16 * 10240
    pages = int((-(-(cursors + 1) // 16)).sum()) + (64 - 6)
    assert counts["kv_pages_read"] == 5 * pages
    assert counts["state_lanes"] == 6 and counts["state_resets"] == 1
    assert counts["kv_rows_full"] == int((cursors + 1).sum())
    assert counts["step_bytes_cache"] == (
        2 * 6 * 5 * lane + 5 * pages * 2 * 16 * 512 * 2 + 6 * 10240)
    params = sum(int(np.prod(s)) for s in dec.state_shapes.values())
    assert counts["step_bytes_weights"] == 2 * (params - 261120 * 5120)
    assert counts["expert_bytes"] == 0


def test_the_cut_is_param_layouts_arithmetic():
    """430.12 M a layer, 4.825 B here, 33.64 B whole, from the shapes
    `param_layout` lays out at the published widths."""
    m = _json(*FILE)
    spec, d_inner = _file_block(m)

    def count(layers):
        _, shapes = lm_block.param_layout(
            spec, m["vocab_size"], m["hidden_size"],
            m["num_attention_heads"], layers, d_inner)
        return shapes, sum(int(np.prod(s)) for s in shapes.values())

    shapes, here = count(m["num_hidden_layers"])
    layer = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("layer_0."))
    mamba = sum(int(np.prod(s)) for n, s in shapes.items()
                if n.startswith("layer_0.ssm_"))
    assert shapes["layer_0.ssm_in_proj.w_0"] == (5120, 9248)
    assert shapes["layer_0.ssm_conv.w_0"] == (4, 5120)
    assert shapes["layer_0.k_proj.w_0"] == (5120, 512)
    assert shapes["layer_0.q_proj.w_0"] == (5120, 2560)
    assert shapes["lm_head.w_0"] == (5120, 261120)
    assert round(mamba / 1e6, 2) == 68.35
    assert round(layer / 1e6, 2) == 430.12
    assert round(here / 1e9, 3) == 4.824 or round(here / 1e9, 3) == 4.825
    whole = 72 * layer + here - 5 * layer
    assert round(whole / 1e9, 2) == 33.64
    assert m["published"]["num_hidden_layers"] == 72


def test_configuration_file_is_the_catalogs_row_and_its_derived_keys():
    """Every number of the catalog's `config` under the same key but
    `num_hidden_layers` (in `reduced`); each `derived` key held to the
    source's; the description made from the file's own keys."""
    m = _json(*FILE)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    assert m["source"] == row["source_url"]
    assert m["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in m["reduced"]:
            assert m[key] == value, key
    assert m["num_hidden_layers"] == 5
    # derived: a Mamba entry a layer
    assert m["layer_types"] == ["mamba"] * m["num_hidden_layers"]
    assert set(m["derived"]) - {"what", "intermediate_size"} == {
        "layer_types"}
    assert m["mamba_n_heads"] * m["mamba_d_head"] == m["mamba_d_ssm"]
    spec, d_inner = _file_block(m)
    assert spec.parallel_attention and d_inner == 21504
    assert (spec.ssm_groups, spec.ssm_d_state,
            spec.ssm_heads * spec.ssm_d_head) == (2, 256, m["mamba_d_ssm"])
    assert spec.ssm_multipliers == tuple(row["config"]["ssm_multipliers"])
    assert spec.mlp_multipliers == tuple(row["config"]["mlp_multipliers"])
    assert spec.rope_of("mamba")["rope_theta"] == 1e11
    assert not spec.tied_head and spec.rotated("mamba")
    # every fault and note the file's `assumed` names is the reference's
    text = json.dumps(m["assumed"])
    named = {f for f in REF.FAULTS if f"`{f}`" in text}
    assert named >= set(REF.FAULTS) - {
        "no_mamba", "no_attention", "no_d_skip", "no_reset",
        "wrong_snapshot", "shifted_blocks"}
    # the limits stand between the readings
    for limits in (m["compare"]["limits"], m["compare"]["served_limits"]):
        assert set(limits) <= set(m["compare"]["readings"])
    # the toy's twin of the sizes
    toy = dict(m, **m["rehearse"])
    assert toy["layer_types"] == ["mamba"] * toy["num_hidden_layers"]
    assert toy["mamba_n_heads"] * toy["mamba_d_head"] == toy["mamba_d_ssm"]


def test_traffic_file_is_docqa64_state_but_for_job_and_what():
    """`docqa64-state.json` letter for letter but for `job` and `what`:
    docqa64's 16 literal document lengths and its literal table of 64
    (question, answer) pairs, neither pre-declared fallback taken."""
    a = _json("perf", "traffic", "docqa64-state.json")
    b = _json("perf", "traffic", "docqa64-parallel.json")
    assert list(a) == list(b)
    assert {k for k in a if a[k] != b[k]} == {"job", "what"}
    assert b["job"] == "serve_lm_docqa_parallel"
    assert not b["documents"]["fallback_taken"]
    assert (b["context"], b["state_snapshots"]) == (6912, 32)
    longest = max(b["documents"]["lengths"]) + max(
        q + n for q, n in b["lengths"]["table"])
    assert longest <= 6784 <= b["context"] - b["block_size"]
    assert b["served"]["document_lengths"][1] + max(
        q + n for q, n in b["lengths"]["table"]) <= b["served"]["padded"]


# points of the space `param_layout` STILL refuses beside a parallel
# layer, and the new fields without one
STILL_REFUSED = [
    dict(layer_types=["mamba", "attention", "mamba"]),
    dict(layer_types=["mamba", "sliding_attention", "mamba"], window=8),
    dict(positions="none"), dict(rope_layers=["full_attention"]),
    dict(ffn="moe_swiglu", n_experts=8, experts_per_token=2),
    dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
         v_head_dim=8),
    dict(tied_head=True), dict(qk_norm=True, qk_norm_per_head=True),
    dict(residual_multiplier=0.5), dict(attention_multiplier=0.1),
    dict(logits_scaling=2.0), dict(ssm_groups=3), dict(attention_gate=True),
    dict(passes=2), dict(parallel_attention=False),
    dict(parallel_attention=False, positions="none", ssm_groups=1,
         ssm_multipliers=(), mlp_multipliers=(),
         ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
         attention_out_multiplier=1.0, key_multiplier=1.0,
         embedding_multiplier=1.0),        # lm_head_multiplier is left
]


@pytest.mark.parametrize("over", STILL_REFUSED,
                         ids=lambda o: "-".join(sorted(o))[:60])
def test_param_layout_still_refuses(over):
    with pytest.raises((NotImplementedError, ValueError)):
        lm_block.param_layout(_block(**over), V, D, H, L, F)


def test_what_cannot_be_served_is_refused_by_name():
    """A draft model, `step_window` and an int8 pool stay refused; the
    multiplier lists have their lengths."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec).items()}
    assert set(dec.refuses) == {"draft_model"}
    assert "recurrent state" in dec.refuses["draft_model"]
    with pytest.raises(ValueError, match="a lane takes no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=32,
                         place=fluid.CPUPlace(), draft_decoder=dec,
                         draft_states=g)
    pool_k, pool_v = dec.init_pool(9, lanes=2)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(g, pool_k, pool_v, np.zeros((2, NB), np.int32),
                        np.zeros(2, np.int32), np.zeros((2, 2), np.int32),
                        np.zeros(2, np.uint32), np.zeros(2, np.float32),
                        np.ones(2, np.int32))
    with pytest.raises(NotImplementedError, match="int8"):
        _decoder("int8")
    with pytest.raises(ValueError, match="five"):
        _block(ssm_multipliers=[1.0, 2.0])
    with pytest.raises(ValueError, match="two"):
        _block(mlp_multipliers=[1.0])


def test_scopes_name_both_mixers_under_one_layer():
    """The Mamba mixer's five scopes and the attention's, all in one
    lowered step; the dense SwiGLU under the dense layers' name; the two
    snapshot copies under theirs."""
    dec = _decoder()
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(9, lanes=2))
    i32 = sds((2,), np.int32)
    text = dec.step.lower(
        g, *pools, sds((2, NB), np.int32), i32, i32, sds((2,), np.uint32),
        sds((2,), np.float32), sds((2,), np.bool_)).as_text(debug_info=True)
    for part in ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
                 "ssm_out_proj", "qkv", "rope", "kv_write", "attention",
                 "attn_out", "dense_ffn", "head", "embed"):
        assert f"paged_decoder/{part}" in text, part
    assert "paged_decoder/mlp" not in text
    assert dec.compiler_scopes["g[\\'layer_0.ffn_up.w_0\\']"] == \
        "paged_decoder/dense_ffn"
    assert dec.compiler_scopes["g[\\'layer_1.ssm_in_proj.w_0\\']"] == \
        "paged_decoder/ssm_in_proj"
    snaps = jax.eval_shape(lambda: dec.init_snapshots(2))
    scalar = sds((), np.int32)
    assert "state_snapshot_save" in dec.snapshot_save.lower(
        snaps, *pools, scalar, scalar).as_text(debug_info=True)
    assert "state_snapshot_restore" in dec.snapshot_restore.lower(
        *pools, snaps, scalar, scalar).as_text(debug_info=True)


def test_benchmark_lists_the_cell_and_its_readers():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-1chip", "docqa64-parallel", 1)
    (config,) = [c for c in bench["configs"]
                 if c["name"] == "falcon-h1-34b-1chip"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert bench["workloads"][-1] is cell and bench["configs"][-1] is config
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", ())}
    for name in ("serve_tokens_per_s", "itl_p95_ms", "serve_ssm_share",
                 "serve_ssm_scan_roofline", "serve_attention_share",
                 "serve_attention_roofline", "serve_dense_ffn_share",
                 "serve_step_bytes_roofline", "sched_step_cache_bytes_share",
                 "sched_state_reset_share", "serve_state_snapshot_share",
                 "sched_snapshot_restore_share", "sched_prefix_hit_share",
                 "serve_ttft_p50_ms", "decode_kernel_pallas",
                 "serve_hbm_peak_gb", "serve_device_idle_share"):
        assert name in mine, name
    assert not [n for n in mine if n.startswith(("serve_moe", "sched_moe",
                                                 "moe_"))]
    assert "serve_shared_expert_share" not in mine


def test_the_job_draws_the_assumed_arrays(monkeypatch):
    """The job's `make_weights` at the toy's shapes: a sigma an array as
    the file's `weights.sigma` gives it, Mamba-2's own draws, the same
    seed the same arrays."""
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        job = common.load_module(os.path.join(
            ROOT, "perf", "jobs", "serve_lm_docqa_parallel.py"))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    m = _json(*FILE)
    monkeypatch.setitem(job._cell, "cell", type("C", (), {"config": m})())
    shapes = {"layer_0.ssm_in_proj.w_0": (256, 128),
              "layer_0.q_proj.w_0": (256, 64),
              "layer_0.v_proj.w_0": (256, 64),
              "layer_0.ssm_conv.w_0": (4, 96), "layer_0.ssm_dt.b_0": (64,),
              "layer_0.ssm_a_log.w_0": (64,), "layer_0.ssm_d.w_0": (64,),
              "layer_0.mixer_norm.scale_0": (256,),
              "tok_embedding.w_0": (128, 256)}
    g = {n: np.asarray(w, np.float32)
         for n, w in job.make_weights(shapes, 7, jnp.float32).items()}
    again = job.make_weights(shapes, 7, jnp.float32)
    assert all((g[n] == np.asarray(again[n])).all() for n in g)
    sigma = m["weights"]["sigma"]
    assert abs(g["layer_0.ssm_in_proj.w_0"].std()
               / sigma["ssm_in_proj.w_0"] - 1) < 0.05
    assert abs(g["layer_0.q_proj.w_0"].std() / sigma["q_proj.w_0"] - 1) < 0.05
    assert abs(g["layer_0.v_proj.w_0"].std() / sigma["default"] - 1) < 0.05
    assert abs(g["layer_0.mixer_norm.scale_0"].mean() - 1) < 0.01
    assert np.abs(g["layer_0.ssm_conv.w_0"]).max() <= 0.5
    a = np.exp(g["layer_0.ssm_a_log.w_0"])
    assert 1.0 <= a.min() and a.max() <= 16.0
    dt = np.log1p(np.exp(g["layer_0.ssm_dt.b_0"]))
    assert 0.9e-3 < dt.min() and dt.max() < 0.11
    assert abs(g["layer_0.ssm_d.w_0"].mean() - 1) < 0.02


def test_the_cell_rehearses_on_the_cpu_and_selfcheck_passes(tmp_path):
    """The cell end to end on the CPU at the files' tiny sizes: both
    comparisons pass, every request of the load starts from a restored
    snapshot, and the span-sourced readers are in the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run_cell.py"),
         "--workload", CELL, "--seed", "6900000123", "--seconds", "4",
         "--trace", "1", "--rehearse"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.strip().splitlines()
             if l.startswith("{")]
    line = lines[-1]
    assert line["correct"] and line["failed"] == 0, out.stdout[-3000:]
    for name in ("sched_prefix_hit_share", "sched_snapshot_restore_share",
                 "sched_state_reset_share", "serve_step_bytes_roofline",
                 "sched_step_cache_bytes_share", "tick_ms",
                 "serve_ttft_p50_ms"):
        assert name in line["metrics"], name
    assert line["metrics"]["sched_snapshot_restore_share"]["value"] == 100.0
    notes = lines[0]["notes"]
    assert set(notes["reference"]["limits"]) <= set(notes["reference"])
    assert notes["reference"]["scan_rel_err"] < 1e-5
    assert notes["snapshots"]["prefix_blocks_cut"] == 0
    check = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "selfcheck.py")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=400)
    assert check.returncode == 0, check.stdout[-2000:]


# sha256 of the lowered served step (StableHLO text, no locations) of
# the twelve other configurations' toys and of OPT's, taken at the
# parent commit of the PR that built the parallel layer: a description
# without the new fields computes what it computed
PARENTS_STEPS = {
    "deepseek-v2-1chip":
        "9c3830f8c2a1b9138e20f455378fb171060f6d4315ba634aa4ee74bd4b592eae",
    "dots3-note-prev-1chip":
        "b5edf0364eb57278ea9b83e4d4105a81e51896dd2b091dda7d43b6191b13cb99",
    "glm-5.2-1chip":
        "c2087f45f65f60cf050d6351ca811905c6c4db817d265cde6d714819933e5ca5",
    "granite-4.0-h-small-1chip":
        "253a1dfac12af82aae0c4a96fde88a25ce72655e5821ba4f294308b4bb447b37",
    "k-exaone-236b-a23b-1chip":
        "4ffbd3e82acdc257b7942dc29e8e4eb0490383b28ace53b1c7d480a6fa040522",
    "lfm2-24b-a2b-1chip":
        "1eb9e2f2533b44ba25115b9015bd3bca06580000c971b2cdee7f87dec1a4031f",
    "ling-3.0-flash-1chip":
        "6b6bc0aefb7fc26abce38769220c9e82ec5b76f394118cc59ee899dc7708eaf6",
    "longcat-flash-1chip":
        "9701d7e40fd04fc70b38db89e5fa74c0ff1fd7824acb46fb0678af10329f0ecc",
    "mellum2-12b-a2.5b-1chip":
        "212478f1abda7105caa4cdbcca0cbf132f2b82a36c59fa52d9f7f3ec136f15c1",
    "olmoe-1b-7b-1chip":
        "581e73cc9cf988400e7f3617e6d7daed41b3b7f4f9640d37a2a2c5183dad795b",
    "ouro-2.6b":
        "f36a5c45128a6cc5bdc417a7c0750a5bf83734afcdd4d1b341bd85d1b96a531e",
    "solar-open2-250b-1chip":
        "5f635bdc363ce11324dd4a658685153cfd808418ff428b25bede464595b9a1ea",
    "opt":
        "fb9bbcf3d2ea93bed424cb86096f4850910f35c01db035ee4d362a9a6ab6f30a",
}


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_other_toys_lowered_steps_are_the_parents_text(name):
    slots, bs, nb = 2, 4, 4
    if name == "opt":
        _, dec = build_lm_paged_decoder(
            97, bs, nb, d_model=32, n_heads=4, n_layers=2, d_inner=64,
            kv_dtype="bf16", platform="cpu")
    else:
        m = _json("perf", "configs", name + ".json")
        m.update(m["rehearse"])
        spec, d_inner = _file_block(m)
        _, dec = build_lm_paged_decoder(
            m["vocab_size"], bs, nb, d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"],
            n_layers=m["num_hidden_layers"], d_inner=d_inner,
            kv_dtype="bf16", platform="cpu", block=spec)
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
