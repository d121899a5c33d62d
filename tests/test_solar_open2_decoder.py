"""The Solar-Open2 block (gated delta-rule layers whose matrix state and
tail belong to a lane beside ONE gated no-position attention layer on
the paged table, a softmax router over held experts, a shared expert,
an untied head) through `build_lm_paged_decoder`, `PagedKVCache` and
`GenerationServer` WITH THE PREFIX CACHE ON (a hit restores a snapshot
of the lane's state), against the plain reference
`perf/reference/solar_open2.py`, at toy widths on the CPU with seeded
random float32 weights.

The toy is the configuration file's `rehearse` overlay: G K K K, 16
experts of 16 routed over and 4 held (3 a token), 4 query heads over 2
K/V heads of 8, 4 delta-rule heads of 8, 4 taps, gates of rank 6.  What
is compared is LOGITS, never tokens, except where a server's streams
are compared with themselves.
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
CELL = "solar-open2-250b-serve-docqa64"
BS, NB = 4, 24                                   # 96 positions
# float32 weights, pool, state and tail: the same float32 sums in
# another order (a state a position against a scan over the sequence,
# grouped matmuls against dense masked products): measured 3e-7 to 2e-6
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


REF = _load("ref_solar_open2", "perf", "reference", "solar_open2.py")
FILE = _json("perf", "configs", "solar-open2-250b-1chip.json")
CONFIG = dict(FILE, **FILE["rehearse"])
V, D, H, L = (CONFIG[k] for k in ("vocab_size", "hidden_size",
                                  "num_attention_heads",
                                  "num_hidden_layers"))
E, HELD, K = (CONFIG[k] for k in ("num_routed_experts", "n_routed_experts",
                                  "num_experts_per_tok"))
LIN = CONFIG["linear_attn_config"]
DH, DK, TAPS = LIN["num_heads"], LIN["head_dim"], LIN["short_conv_kernel_size"]
N_DELTA = CONFIG["layer_types"].count("delta_rule")
STATE, TAIL = (DH, DK, DK), (TAPS - 1, 3 * DH * DK)


def _block(m=CONFIG, **over):
    """The description as the benchmark's job builds it: the file's
    `block`, literal fields and the source's own keys."""
    b = m["block"]
    return lm_block.BlockSpec(**dict(dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}),
        **over)), m[b["d_inner"]]


def _decoder(kv_dtype="fp32", m=CONFIG, nb=NB, **over):
    spec, d_inner = _block(m, **over)
    startup, dec = build_lm_paged_decoder(
        m["vocab_size"], BS, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=d_inner, kv_dtype=kv_dtype, platform="cpu", block=spec)
    assert startup is None
    return dec


def _weights(dec, seed=0):
    """Seeded float32 weights of a size at which every part matters:
    matrices at sigma 0.1 (0.3 the router, whose product decides a
    choice), the taps uniform in +-1/2, decays and step sizes as the
    released layer draws them."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        if n.endswith(("delta_conv.w_0", ".conv.w_0", "ssm_conv.w_0")):
            w = r.uniform(-0.5, 0.5, shape)
        elif n.endswith(("delta_a_log.w_0", "ssm_a_log.w_0")):
            w = np.log(r.uniform(1.0, 16.0, shape))
        elif n.endswith(("delta_dt.b_0", "ssm_dt.b_0")):
            dt = r.uniform(1e-3, 1e-1, shape)
            w = dt + np.log(-np.expm1(-dt))
        else:
            w = r.normal(0, 0.3 if "router.w" in n else 0.1, shape)
            if ".scale_" in n or n.endswith("ssm_d.w_0"):
                w = 1.0 + w
        g[n] = jnp.asarray(w, jnp.float32)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None,
           routing=False, pools=None):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); returns each
    sequence's [len, V] logits, then (with `routing`) lane `lanes[0]`'s
    routing stacked over its positions with its states and tails after
    the last one under "state" and "tails", then (with `pools`, which
    continues on pools an earlier drive left) the pools."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(1 + slots * NB, lanes=slots)
    # the table's one plane is the attention layer's; a state a delta
    # layer rides beside K and its tail beside V
    assert pool_k[0].shape[0] == L - N_DELTA == pool_v[0].shape[0]
    assert [s.shape for s in pool_k[1]] == [(slots,) + STATE] * N_DELTA
    assert [t.shape for t in pool_v[1]] == [(slots,) + TAIL] * N_DELTA
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
        assert len(counts) == len(dec.step_counters) == 2
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    res = ([np.stack(o) for o in out],)
    if routing:
        res += ({"state": np.stack([np.asarray(s)[lanes[0]]
                                    for s in pool_k[1]]),
                 "tails": np.stack([np.asarray(t)[lanes[0]]
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
    apart), the delta layers' states and tails carried a lane, the
    attention layer through the table, against the reference's ONE
    forward pass: a loop over positions on a state from zeros, the
    convolutions over the whole sequence."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["logits_rms_err"] <= tol >= out["late_rms_err"], out
    assert out["cut_rms_err"] <= tol and out["router_rel_err"] <= 1e-4, out
    # layers 1 to 3 stand behind the attention layer: a bf16 pool moves
    # their states and tails by what it moves the stream
    assert out["state_rms_err"] <= tol >= out["tail_rms_err"], out
    if kv_dtype == "fp32":
        want = np.asarray(REF.logits(g, CONFIG, IDS))
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
        assert out["routing_agree"] == 1.0 == out["argmax_agree"]


def test_a_reused_lane_reads_as_a_fresh_one_and_an_idle_lane_keeps_still():
    """A sequence run in a lane whose states, tails and table blocks
    still hold ANOTHER sequence's gives bit for bit what it gives on
    zero pools: position 0 resets the lane from the cursor alone.  A
    lane that is not active keeps state and tails to the bit while its
    neighbour runs."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    first, second = (list(r.randint(0, V, n)) for n in (27, 13))

    def zero():
        return dec.init_pool(1 + 2 * NB, lanes=2)

    (fresh,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=zero())
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    held = [np.asarray(x) for x in used[0][1] + used[1][1]]
    assert all(x[1].any() and not x[0].any() for x in held)
    (again,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=used)
    assert np.array_equal(fresh, again) and np.isfinite(again).all()
    # lane 1 idle while lane 0 runs: what lane 1 holds does not move
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    before = [np.asarray(x)[1].copy() for x in used[0][1] + used[1][1]]
    _, moved = _drive(dec, g, [first[:9]], slots=2, lanes=[0], pools=used)
    for a, x in zip(before, moved[0][1] + moved[1][1]):
        assert np.array_equal(a, np.asarray(x)[1])
        assert np.asarray(x)[0].any()


@pytest.mark.parametrize("zero_router", [False, True])
def test_a_sequence_among_others_is_bit_identical_to_itself_alone(
        zero_router):
    """Continuous batching: the same sequence beside two others out of
    step with it, in another lane and other table blocks than alone:
    bit for bit the same logits (no capacity in the expert layer, no
    lane in the recurrence), even with a zero router (every token on
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


# the toy's limits, between the decoder's readings (3e-7 to 2e-6) and
# the least any fault or `below` reads
LIMITS = {"logits_rms_err": TOL_FP32, "late_rms_err": TOL_FP32,
          "cut_rms_err": TOL_FP32, "state_rms_err": TOL_FP32,
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
    assert len(REF.FAULTS) == 13 == len(set(REF.FAULTS))
    assert all(f in FILE["assumed"]["faults"] for f in REF.FAULTS)
    served = REF.served(g, CONFIG, [(IDS, 40)], pad_to=64)
    assert set(FILE["compare"]["served_limits"]) <= set(served)


@pytest.mark.parametrize("what", ("below",) + REF.FAULTS)
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """Each of the thirteen wrong models and the right one in bfloat16,
    as if it were the system, is refused by at least one limit, and by
    the limit that names what it broke."""
    g = _weights(_decoder())
    out = (REF.below(g, CONFIG, IDS) if what == "below"
           else REF.faults(g, CONFIG, IDS, which=(what,))[what])
    refused = _refused(out)
    assert refused, (what, out)
    by = {"below": "state_rms_err", "decay_after_update": "state_rms_err",
          "beta_no_2": "state_rms_err", "decay_per_head": "state_rms_err",
          "no_l2norm": "state_rms_err", "gate_silu": "logits_rms_err",
          "no_conv_silu": "state_rms_err", "tail_shifted": "logits_rms_err",
          "hit_no_restore": "cut_rms_err", "snapshot_late": "cut_rms_err",
          "snapshot_no_tails": "cut_rms_err",
          "rope_on_attention": "logits_rms_err",
          "no_attention_gate": "logits_rms_err",
          "no_renorm": "router_rel_err"}[what]
    assert by in refused, (what, by, out)
    if what in REF.SNAPSHOT_FAULTS:
        # nothing moves before the cut, everything at it
        free = np.asarray(REF.forward(g, CONFIG, IDS)[0])
        hurt = np.asarray(REF.forward(g, CONFIG, IDS, fault=what)[0])
        cut = REF.cut_of(len(IDS))
        assert np.array_equal(free[:cut - 1], hurt[:cut - 1])
        assert np.abs(free[cut] - hurt[cut]).max() > 100 * TOL_FP32 * (
            np.abs(free).max())
    if what in ("rope_on_attention", "no_attention_gate"):
        # the router is right there: only what follows layer 0 tells
        assert out["router_rel_err"] <= TOL_FP32, out
    if what == "snapshot_no_tails":
        # the state is kept: only three rows of history are lost
        assert out["cut_rms_err"] > 10 * out["late_rms_err"] or (
            out["late_rms_err"] > TOL_FP32)


def test_served_refuses_the_snapshot_faults_where_they_start():
    """`served` on a request whose tokens are the reference's own
    greedy choice: 1.0 and no gap for the model itself, less for a lane
    that started from zeros at the cut or whose snapshot was a position
    late (the first tokens after the cut tell)."""
    g = _weights(_decoder(), seed=2)
    prompt = IDS[:40]
    ids = list(prompt)
    for _ in range(12):
        ids.append(int(np.argmax(REF.forward(
            g, CONFIG, np.asarray(ids, np.int32),
            logits_from=len(ids) - 1)[0][-1])))
    req = [(np.asarray(ids, np.int32), len(prompt))]
    ok = REF.served(g, CONFIG, req, pad_to=64, cuts=[36])
    assert ok["served_argmax_agree"] == 1.0 and ok["served_gap_rms"] == 0.0
    assert ok["tokens"] == 12 == ok["tokens_early"]
    for fault in ("hit_no_restore", "snapshot_late"):
        bad = REF.served(g, CONFIG, req, pad_to=64, cuts=[36], fault=fault)
        assert bad["served_gap_rms"] > 0.0, (fault, bad)


def test_the_eight_shares_and_the_shared_expert_are_the_uncut_layer():
    """The guide's test of a share: the parts that the (here four)
    chips of a stage compute of ONE expert layer, each from its own
    quarter of the experts, with the shared expert (which every chip
    computes alike) counted once, add up to what the uncut reference
    gives for the whole layer."""
    r = np.random.RandomState(9)
    x = jnp.asarray(r.normal(0, 1, (11, D)), jnp.float32)
    f = CONFIG["moe_intermediate_size"]
    p = {"norm": 1 + r.normal(0, 0.1, D), "router": r.normal(0, 0.3, (D, E)),
         "gate": r.normal(0, 0.1, (E, D, f)),
         "up": r.normal(0, 0.1, (E, D, f)),
         "down": r.normal(0, 0.1, (E, f, D)),
         "shared_gate": r.normal(0, 0.1, (D, f)),
         "shared_up": r.normal(0, 0.1, (D, f)),
         "shared_down": r.normal(0, 0.1, (f, D))}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    own = jnp.full((11, K), -1, jnp.int32)
    one = jnp.asarray(1.0, jnp.float32)
    kw = dict(top_k=K, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        whole, routing = REF._moe(x, p, own, one, first=0, **kw)
        nothing = dict(p, **{k: jnp.zeros_like(p[k]) for k in (
            "shared_gate", "shared_up", "shared_down")})
        shared = REF._moe(x, dict(p, **{k: p[k][:0] for k in (
            "gate", "up", "down")}), own, one, first=0, **kw)[0] - x
        parts = []
        for first in range(0, E, HELD):
            share = dict(nothing, **{k: p[k][first:first + HELD]
                                     for k in ("gate", "up", "down")})
            got, r_ = REF._moe(x, share, own, one, first=first, **kw)
            assert np.array_equal(r_["experts"], routing["experts"])
            parts.append(got - x)
    total = x + sum(parts) + shared
    assert np.abs(total - whole).max() <= 1e-5 * np.abs(whole).max()
    assert all(np.abs(part).max() > 0 for part in parts)
    # and the served layer is such a share: `lm_block.moe_ffn` over the
    # experts `first` onward gives the reference's part
    spec, _ = _block()
    m = REF._rms(x, p["norm"], 1e-5)
    first = 2 * HELD
    mine = lm_block.moe_ffn(
        lm_block.BlockSpec(**dict(spec.__dict__, experts_first=first)), m,
        p["router"], *(p[k][first:first + HELD]
                       for k in ("gate", "up", "down")))[0]
    assert np.abs(mine - parts[2]).max() <= 1e-5 * np.abs(parts[2]).max()


# -- the prefix cache over a lane's state ----------------------------------
def _serve(dec, g, prefix, asks, *, slots=3, snapshots=None, blocks=96):
    """`asks`: (prompt, new tokens) in order, each awaited before the
    next (so that a later one finds what an earlier one cached); ->
    (their sampled streams, the server's stats)."""
    srv = GenerationServer(dec, g, slots=slots, kv_blocks=blocks,
                           place=fluid.CPUPlace(), prefix_cache=prefix,
                           state_snapshots=snapshots)
    try:
        # sampled (the key is the request's seed and the position): a
        # greedy stream at these widths soon cycles
        out = [srv.submit(p, n, temperature=1.0, seed=50 + i).result(
            timeout=120) for i, (p, n) in enumerate(asks)]
        return out, srv.stats()
    finally:
        srv.close()


def _toy(name):
    """Another configuration's toy with a lane state: its decoder and
    seeded weights."""
    m = _json("perf", "configs", name + ".json")
    m.update(m["rehearse"])
    dec = _decoder(m=m)
    return dec, {n: np.asarray(w) for n, w in _weights(dec, 4).items()}, m


@pytest.mark.parametrize("name", [
    "solar-open2-250b-1chip", "granite-4.0-h-small-1chip",
    "lfm2-24b-a2b-1chip"])
def test_a_hit_through_a_snapshot_equals_the_miss_bit_for_bit(name):
    """A document built through `submit(document, 1)`, then requests
    that are the document and a question: with the prefix cache on each
    starts from the document's cached blocks and a RESTORED snapshot of
    the lane's state; its stream is, token for token, that of the same
    request on a server without a cache, which ran every position (a
    sampled stream: one wrong logit anywhere changes it).  For every
    block whose lanes keep something: a matrix state and a tail, an SSM
    state and a tail, a tail alone."""
    dec, g, m = _toy(name)
    r = np.random.RandomState(3)
    v = m["vocab_size"]
    doc = list(r.randint(0, v, 6 * BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, v, n)), 9)
                         for n in (5, 7, 2)]
    hit, stats = _serve(dec, g, True, asks)
    miss, plain = _serve(dec, g, False, asks)
    assert hit == miss and all(len(set(s)) > 4 for s in hit[1:])
    assert "state_snapshots_saved" not in plain
    # the document's snapshot, restored three times; the document's own
    # save and two requests' (at 28 and 28, other blocks; the third's
    # boundary is the document's, where one is held already)
    assert stats["state_snapshots_restored"] == 3
    assert stats["state_snapshots_saved"] == 3
    assert stats["state_snapshot_bytes"] == 6 * dec.state_bytes_per_lane
    assert stats["prefix_blocks_cut"] == 0
    assert stats["prefix_hits"] == 3 * 6
    assert stats["state_snapshot_pool_bytes"] == (
        3 * dec.state_bytes_per_lane)


def test_a_hit_is_cut_back_to_the_last_block_with_a_snapshot():
    """A prompt that shares MORE full blocks with an earlier one than
    the earlier one's snapshot covers (it went on where the other's
    question did): the blocks hit by hash past the snapshot are cut,
    counted and run again; the stream is the miss's."""
    dec, g, _ = _toy("solar-open2-250b-1chip")
    r = np.random.RandomState(8)
    doc = list(r.randint(0, V, 4 * BS))
    longer = doc + list(r.randint(0, V, 2 * BS + 1))   # snapshot at 24
    asks = [(doc, 1), (longer, 3), (longer[:5 * BS + 2], 6),
            (longer + [3, 4], 6)]
    hit, stats = _serve(dec, g, True, asks)
    assert hit == _serve(dec, g, False, asks)[0]
    # the third shares 5 blocks with the second, whose snapshots lie at
    # 16 (the document's) and 24: cut back to 16, 1 block cut; the
    # fourth ends in the block at 24 and takes it whole
    assert stats["prefix_blocks_cut"] == 1
    assert stats["state_snapshots_restored"] == 3
    assert stats["prefix_hits"] == 4 + 4 + 6


def test_a_whole_prompt_hit_runs_its_last_position_once():
    """The same prompt again: with a lane state a hit may not reach the
    last prompt position (the step that produces the first token would
    run it a second time on a state that has seen it), so it is cut to
    a snapshot before it, here to the document's."""
    dec, g, _ = _toy("solar-open2-250b-1chip")
    r = np.random.RandomState(6)
    doc = list(r.randint(0, V, 5 * BS))
    aligned = doc + list(r.randint(0, V, BS))          # 6 blocks exactly
    asks = [(doc, 1), (aligned, 5), (aligned, 5), (doc, 4)]
    hit, stats = _serve(dec, g, True, asks)
    assert hit == _serve(dec, g, False, asks)[0]
    # second: 5 of 6 blocks; third: all 6 hit by hash, cut to 5 (the
    # snapshot at 24 would leave nothing to run); fourth: the document
    # whole, 5 blocks by hash, none usable
    assert stats["prefix_blocks_cut"] == 1 + 5
    assert stats["state_snapshots_restored"] == 2


def test_an_evicted_snapshot_shortens_the_next_hit_and_breaks_nothing():
    """Two snapshot rows: the documents' snapshots turn over in the LRU
    under the requests' own, a later request on the first document
    finds its blocks and no snapshot, runs from position 0, and its
    stream is still the miss's."""
    dec, g, _ = _toy("solar-open2-250b-1chip")
    r = np.random.RandomState(12)
    docs = [list(r.randint(0, V, 4 * BS)) for _ in range(3)]
    asks = [(d, 1) for d in docs] + [(docs[0] + [5, 6, 7], 6),
                                     (docs[2] + [8, 9], 6)]
    hit, stats = _serve(dec, g, True, asks, snapshots=2)
    assert hit == _serve(dec, g, False, asks)[0]
    assert stats["state_snapshots_evicted"] >= 1
    assert stats["state_snapshots"] <= 2
    # the first document lost its snapshot to the third's: 4 blocks cut
    assert stats["prefix_blocks_cut"] == 4
    assert stats["state_snapshots_restored"] == 1
    assert stats["kv_blocks_cached"] >= 12       # the blocks stay cached


def test_snapshot_ids_on_cached_blocks_the_cache_alone():
    """`PagedKVCache(state_snapshots=)` without a device: a hit needs a
    snapshot and ends at one, `reserve_snapshot` evicts the least
    recently used, `commit_prefix` hangs a row on the boundary's block,
    `flush_prefix` drops every snapshot, a cache without snapshots hits
    as ever."""
    toks = list(range(40))
    plain = PagedKVCache(32, 4, 16, prefix_cache=True)
    plain.allocate_prefix("a", 41, prompt_tokens=toks)
    plain.commit_prefix("a", 40)
    assert plain.allocate_prefix("b", 41, prompt_tokens=toks)[1] == 40
    assert plain.hit_snapshot("b") == (None, 0)
    assert "state_snapshots_saved" not in plain.prefix_stats()

    cache = PagedKVCache(32, 4, 16, prefix_cache=True, state_snapshots=2,
                         snapshot_bytes=10)
    assert cache.allocate_prefix("a", 41, prompt_tokens=toks,
                                 cached_upto=39)[1] == 0
    assert cache.reserve_snapshot("a", 24) == 0
    assert cache.reserve_snapshot("a", 28) is None      # one a prompt
    cache.commit_prefix("a", 20)
    assert cache.prefix_stats()["state_snapshots"] == 0
    cache.commit_prefix("a", 40)
    stats = cache.prefix_stats()
    assert stats["state_snapshots"] == 1 == stats["state_snapshots_saved"]
    # ten blocks hit by hash; the snapshot is on the sixth
    assert cache.can_admit(41, prompt_tokens=toks, cached_upto=39)
    table, cached = cache.allocate_prefix("b", 41, prompt_tokens=toks,
                                          cached_upto=39)
    assert cached == 24 and cache.hit_snapshot("b") == (0, 4)
    assert cache.hit_snapshot("b") == (None, 0)          # asked once
    assert list(table[:6]) == list(cache._owned["a"][:6])
    assert not set(table[6:11]) & set(cache._owned["a"])
    # a hit may not pass `cached_upto`
    assert cache.allocate_prefix("c", 25, prompt_tokens=toks[:24],
                                 cached_upto=23)[1] == 0
    assert cache.hit_snapshot("c") == (None, 6)
    # b's own snapshot goes on the block cached under its boundary's
    # key, which is a's tenth (equal tokens, the first committed)
    assert cache.reserve_snapshot("b", 40) == 1
    cache.commit_prefix("b", 40)
    assert cache.prefix_stats()["state_snapshots"] == 2
    cache.release("b")
    assert cache.prefix_stats()["state_snapshots"] == 2
    # no row free: the least recently used of those NO admission has hit
    # goes (the tenth block's: the sixth's is older, and b hit it), and
    # its block stays cached
    assert cache.reserve_snapshot("c", 24) == 1
    assert cache.prefix_stats()["state_snapshots_evicted"] == 1
    cache.release("c")                # never committed: the row is free
    other = list(range(100, 124))
    cache.allocate_prefix("d", 25, prompt_tokens=other, cached_upto=23)
    assert cache.reserve_snapshot("d", 24) == 1
    cache.commit_prefix("d", 24)
    assert cache.allocate_prefix("e", 30, prompt_tokens=other + [1] * 4,
                                 cached_upto=27)[1] == 24
    assert cache.hit_snapshot("e") == (1, 0)
    # every snapshot has been hit: the least recently used of all goes
    assert cache.reserve_snapshot("e", 28) == 0      # a's sixth's
    stats = cache.prefix_stats()
    assert stats["state_snapshots_evicted"] == 2
    assert stats["state_snapshots"] == 1 and stats["kv_blocks_cached"] == 16
    assert stats["state_snapshot_bytes"] == 10 * (
        stats["state_snapshots_saved"] + stats["state_snapshots_restored"])
    assert cache.allocate_prefix("f", 41, prompt_tokens=toks,
                                 cached_upto=39)[1] == 0  # shorter, right
    assert cache.hit_snapshot("f") == (None, 10)
    cache.flush_prefix()
    stats = cache.prefix_stats()
    assert stats["state_snapshots"] == 0 == stats["kv_blocks_cached"]
    assert sorted(cache._snap_free) == [0, 1]


def test_flush_prefix_drops_every_snapshot_of_a_server():
    """A hot swap flushes the prefix cache, snapshots and all: the same
    request afterwards hits nothing and gives the tokens of a server
    that never cached."""
    dec, g, _ = _toy("solar-open2-250b-1chip")
    doc = list(np.random.RandomState(1).randint(0, V, 5 * BS))
    srv = GenerationServer(dec, g, slots=2, kv_blocks=64,
                           place=fluid.CPUPlace(), prefix_cache=True)
    try:
        srv.submit(doc, 1).result(timeout=120)
        first = srv.submit(doc + [1, 2, 3], 6, temperature=1.0,
                           seed=7).result(timeout=120)
        # the document's: the request's boundary is the document's end
        assert srv.stats()["state_snapshots"] == 1
        srv.swap_states(g)
        stats = srv.stats()
        assert stats["state_snapshots"] == 0 == stats["kv_blocks_cached"]
        again = srv.submit(doc + [1, 2, 3], 6, temperature=1.0,
                           seed=7).result(timeout=120)
        assert again == first
        assert srv.stats()["state_snapshots_restored"] == 1
        assert srv.stats()["recompiles_after_warmup"] == 0
    finally:
        srv.close()


def test_spans_and_counts_of_a_served_snapshot():
    """`serving.request` carries what its admission restored and its
    prompt saved; `serving.decode_tick` the delta layers' counts; the
    two copies run under host spans of their own inside the iteration
    clock; a server without the prefix cache dispatches neither."""
    dec, g, _ = _toy("solar-open2-250b-1chip")
    doc = list(np.random.RandomState(2).randint(0, V, 4 * BS))
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        _serve(dec, g, True, [(doc, 1), (doc + [1, 2, 3, 4, 5], 4)])
        mark = len(spans)
        _serve(dec, g, False, [(doc, 1), (doc + [1, 2, 3, 4, 5], 4)])
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    names = [s["name"] for s in spans[:mark]]
    assert names.count("generation.phase.snapshot_save") == 2
    assert names.count("generation.phase.snapshot_restore") == 1
    assert not [s for s in spans[mark:] if "snapshot" in s["name"]]
    first, second = [s["attrs"] for s in spans[:mark]
                     if s["name"] == "serving.request"]
    per = dec.state_bytes_per_lane
    assert (first["state_snapshots_saved"], first["state_snapshots_restored"],
            first["state_snapshot_bytes"], first["prefix_blocks_cut"]) == (
                1, 0, per, 0)
    assert (second["state_snapshots_saved"],
            second["state_snapshots_restored"],
            second["state_snapshot_bytes"], second["prefix_hit_tokens"]) == (
                1, 1, 2 * per, 4 * BS)
    plain = [s["attrs"] for s in spans[mark:]
             if s["name"] == "serving.request"]
    assert all("state_snapshots_saved" not in a for a in plain)
    ticks = [s["attrs"] for s in spans[:mark]
             if s["name"] == "serving.decode_tick" and "state_lanes" in
             s["attrs"]]
    assert ticks and all(
        t["delta_layers"] == N_DELTA
        and t["state_bytes"] == 2 * t["state_lanes"] * per for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == 1    # the document


def test_description_is_checked_and_what_cannot_be_served_is_refused():
    """A lane holds what the decoder says; a draft model, `step_window`
    and an int8 pool are refused by name; the description's points that
    are not built are refused by `param_layout`."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    per = 4 * N_DELTA * (DH * DK * DK + (TAPS - 1) * 3 * DH * DK)
    assert (dec.state_layers, dec.state_bytes_per_lane) == (N_DELTA, per)
    assert dec.table_layers == 1 and dec.bytes_per_block == (
        2 * BS * CONFIG["num_key_value_heads"] * CONFIG["head_dim"] * 4)
    assert set(dec.refuses) == {"draft_model"}
    with pytest.raises(ValueError, match="a lane takes no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=16,
                         place=fluid.CPUPlace(), draft_decoder=dec,
                         draft_states=g)
    pools = dec.init_pool(5, lanes=2)
    z = np.zeros((2, 2), np.int32)
    with pytest.raises(NotImplementedError, match="step_window runs a "
                       "window of positions"):
        dec.step_window(g, *pools, np.zeros((2, NB), np.int32), z[:, 0], z,
                        z[:, 0].astype(np.uint32),
                        z[:, 0].astype(np.float32), z[:, 0])
    with pytest.raises(NotImplementedError, match="an int8 pool beside "
                       "convolution tails or delta-rule states"):
        _decoder("int8")
    with pytest.raises(ValueError, match="needs lanes"):
        dec.init_pool(5)
    with pytest.raises(ValueError, match="unknown kind"):
        _block(layer_types=["full_attention", "delta"])
    with pytest.raises(ValueError, match="delta_heads 0 with 'delta_rule'"):
        _block(delta_heads=0)
    for over, why in ((dict(positions="rope"), "positions 'rope' with"),
                      (dict(delta_gate_rank=-1), "delta_gate_rank"),
                      (dict(qk_norm=True), "QK-norm"),
                      (dict(layer_types=["delta_rule"] * 4), "among "
                       "full-attention layers")):
        with pytest.raises(NotImplementedError, match=why):
            _decoder(**over)
    spec, _ = _block()
    with pytest.raises(NotImplementedError, match="attention_gate"):
        lm_block.param_layout(
            lm_block.olmoe(n_experts=8, experts_per_token=2).__class__(
                **dict(lm_block.olmoe(8, 2).__dict__, attention_gate=True)),
            V, D, H, 2, 16)
    # what a plain block is: no lane state, no snapshot programs
    _, plain = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=2, d_inner=16,
        block=lm_block.olmoe(8, 2), platform="cpu")
    assert plain.snapshot_save is None is plain.init_snapshots
    assert spec.rotated(lm_block.DELTA) is False


def test_scopes_name_the_parts_of_the_mixer_the_gate_and_the_copies():
    dec = _decoder()
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(9, lanes=2))
    i32 = sds((2,), np.int32)
    text = dec.step.lower(
        g, *pools, sds((2, NB), np.int32), i32, i32, sds((2,), np.uint32),
        sds((2,), np.float32), sds((2,), np.bool_)).as_text(debug_info=True)
    for part in ("delta_in_proj", "delta_conv", "delta_gates", "delta_rule",
                 "delta_gate_norm", "delta_out_proj", "attention_gate"):
        assert f"paged_decoder/{part}" in text, part
    scopes = dec.compiler_scopes
    assert scopes["g[\\'layer_1.delta_in_proj.w_0\\']"] == (
        "paged_decoder/delta_in_proj")
    assert scopes["g[\\'layer_0.attn_gate.w_0\\']"] == (
        "paged_decoder/attention_gate")
    snaps = jax.eval_shape(lambda: dec.init_snapshots(3))
    i = sds((), np.int32)
    assert "state_snapshot_save" in dec.snapshot_save.lower(
        snaps, *pools, i, i).as_text(debug_info=True)
    assert "state_snapshot_restore" in dec.snapshot_restore.lower(
        *pools, snaps, i, i).as_text(debug_info=True)
    counts = dec.tick_counts(np.array([0, 7, 30]), 4)
    assert (counts["delta_layers"], counts["state_lanes"],
            counts["state_resets"]) == (N_DELTA, 3, 1)
    assert counts["state_bytes"] == 6 * dec.state_bytes_per_lane
    assert "conv_layers" not in counts


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """Every published width under the source's own keys; the derived
    keys are what they repeat; `cut.arithmetic_numbers` recomputed from
    the keys (250.29 B whole, 3.308 B held)."""
    line = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if "Solar-Open2-250B" in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in line:
        assert FILE["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in FILE["reduced"]:
                assert FILE[key] == value, key
    m = FILE
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert m["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 320,
                              "vocab_size": 196608}
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"]) == (
                4096, 64, 8, 128, 1280, 8)
    lin = m["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (64, 128, 4) == (
                m["linear_num_heads"], m["linear_head_dim"],
                m["linear_conv_kernel_size"])
    for cfg in (m, CONFIG):
        l_ = cfg["linear_attn_config"]
        assert (cfg["linear_num_heads"], cfg["linear_head_dim"]) == (
            l_["num_heads"], l_["head_dim"])
        assert cfg["layer_types"] == [
            "full_attention" if l in cfg["gqa_layers"] else "delta_rule"
            for l in range(cfg["num_hidden_layers"])]
        assert cfg["num_experts"] == cfg["n_routed_experts"]
        assert cfg["shared_intermediate_size"] == (
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
    assert m["num_routed_experts"] == m["published"]["n_routed_experts"]
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (4, 40, 24576)
    assert m["n_routed_experts"] * 8 == 320 and m["vocab_size"] * 8 == 196608
    assert "96 v5e chips" in m["deployment"] and "EIGHT" in m["deployment"]
    spec, d_inner = _block(m)
    assert (spec.held, spec.n_experts, d_inner) == ((0, 40), 320, 1280)
    assert (spec.delta_heads, spec.delta_d_head, spec.delta_conv,
            spec.delta_gate_rank, spec.delta_neg_eigval,
            spec.attention_gate, spec.positions, spec.tied_head) == (
                64, 128, 4, 128, True, True, "none", False)
    _, shapes = lm_block.param_layout(spec, m["vocab_size"], 4096, 64, 4,
                                      d_inner)
    here = sum(int(np.prod(s)) for s in shapes.values())
    per_layer = {l: sum(int(np.prod(s)) for n, s in shapes.items()
                        if n.startswith(f"layer_{l}.")) for l in range(4)}
    experts_here = 40 * 3 * 4096 * 1280
    absent = 280 * 3 * 4096 * 1280
    k_layer, g_layer = per_layer[1] - experts_here, per_layer[0] - experts_here
    whole = (36 * (per_layer[1] + absent) + 12 * (per_layer[0] + absent)
             + 2 * 196608 * 4096 + 4096)
    num = m["cut"]["arithmetic_numbers"]
    assert round(whole / 1e9, 2) == num["model_b"] == 250.29
    assert round(here / 1e9, 3) == num["here_b"] == 3.308
    assert round(2 * here / 1e9, 2) == num["weights_gb"] == 6.62
    assert (round(k_layer / 1e6, 1), round(g_layer / 1e6, 1)) == (
        num["k_layer_m"], num["g_layer_m"]) == (154.8, 126.1)
    dec = _decoder("bf16", m=dict(m, vocab_size=128), nb=4)
    assert dec.state_bytes_per_lane == num["state_bytes_a_lane"] == 13467648
    assert dec.bytes_per_block == BS * num["cache_bytes_a_position"]
    for text in ("250.29 B", "3.308 B", "6.62 GB", "13.47 MB", "0.86 GB",
                 "0.43 GB", "0.60 GB"):
        assert text in m["cut"]["arithmetic"], text
    # every limit of the two comparisons is a number the reference gives
    # and has its readings beside it
    for group in ("limits", "served_limits"):
        assert m["compare"][group]
        for name in m["compare"][group]:
            assert name in m["compare"]["readings"], name


def test_traffic_file_is_docqa64_but_for_three_keys():
    ours = _json("perf", "traffic", "docqa64-state.json")
    theirs = _json("perf", "traffic", "docqa64.json")
    assert ours.pop("state_snapshots") == 32
    assert ours.pop("job") == "serve_lm_docqa_state" != theirs.pop("job")
    assert ours.pop("what") != theirs.pop("what")
    assert ours == theirs and ours["prefix_cache"] is True
    bench = _json("BENCHMARK.json")
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert (cell[0]["config"], cell[0]["traffic"]) == (
        FILE["name"], "docqa64-state")
    (entry,) = [c for c in bench["configs"] if c["name"] == FILE["name"]]
    assert entry["reduced"] == FILE["reduced"]


def test_the_bytes_and_the_four_readers_on_a_synthetic_run(monkeypatch):
    """`perf/delta_rule_bytes.py` at the published widths, and the four
    new readers on a `Run` made by hand: tick and request spans with the
    new counts, scope tables of the step and of the two copies."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    import common
    from paddle_tpu import profiler

    cost = _load("delta_rule_bytes", "perf", "delta_rule_bytes.py")
    assert cost.lane_state_bytes(64, 128) == 4194304
    assert cost.lane_tail_bytes(64, 128, 4) == 294912
    assert cost.lane_row_bytes(64, 128) == 196608
    a_lane = 2 * 4194304 + 2 * 294912 + 196608
    kinds = FILE["layer_types"]
    assert cost.rule_bytes(200, kinds, 64, 128, 4) == 3 * 200 * a_lane
    assert cost.rule_bytes(9, ["full_attention"], 64, 128, 4) == 0.0
    # a lane's state and tails are what the decoder says a lane holds
    assert 3 * (4194304 + 294912) == FILE["cut"]["arithmetic_numbers"][
        "state_bytes_a_lane"]
    names = ("serve_delta_rule_share", "serve_delta_rule_roofline",
             "serve_state_snapshot_share", "sched_snapshot_restore_share")
    readers = {n: common.load_module(os.path.join(
        ROOT, "perf", "metrics", n + ".py")) for n in names}
    spans = [{"name": "serving.decode_tick", "ts": 10.0 + i, "dur": 0.5,
              "attrs": {"delta_layers": 3, "state_lanes": 60 + i,
                        "state_bytes": 1}} for i in range(4)]
    spans += [{"name": "serving.request", "ts": 10.0 + i, "dur": 0.4,
               "attrs": {"state_snapshots_restored": int(i != 2),
                         "state_snapshots_saved": 1}} for i in range(4)]
    monkeypatch.setattr(tracing, "finished_spans", lambda: list(spans))
    by_scope = {"paged_decoder/delta_in_proj": 5e-3,
                "paged_decoder/delta_conv": 2e-3,
                "paged_decoder/delta_gates": 1e-3,
                "paged_decoder/delta_rule": 12e-3,
                "paged_decoder/delta_gate_norm": 1e-3,
                "paged_decoder/delta_out_proj": 4e-3,
                "paged_decoder/moe_experts": 75e-3}
    tables = {"paged_decoder.step": {"fusion.1": "paged_decoder/delta_rule",
                                     "copy.2": "paged_decoder/head"},
              "paged_decoder.snapshot_save": {
                  "dynamic-update-slice.5": "state_snapshot_save",
                  "copy.2": "state_snapshot_save"},
              "paged_decoder.snapshot_restore": {
                  "dynamic-update-slice.9": "state_snapshot_restore",
                  "parameter.1": ""}}
    monkeypatch.setattr(
        profiler, "scope_seconds",
        lambda ops, label, inherited_only=False:
            {} if inherited_only else dict(by_scope))
    monkeypatch.setattr(
        profiler, "hlo_scopes",
        lambda label=None: {label: tables[label]} if label in tables else {})
    run = common.Run()
    run.trace = {"op_seconds": {"fusion.1": 12e-4, "copy.2": 1e-4,
                                "dynamic-update-slice.5": 3e-4,
                                "dynamic-update-slice.9": 2e-4,
                                "parameter.1": 1e-4}}
    run.notes["trace_slice_wall"] = (10.0, 12.0)    # ticks 0 and 1
    run.spans = [{"ts": 9.0, "dur": 0.5}, {"ts": 13.0, "dur": 0.4}]
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.cell = types.SimpleNamespace(config=FILE)
    got = {n: r.compute(run) for n, r in readers.items()}
    assert got["serve_delta_rule_share"] == pytest.approx(25.0)
    assert got["serve_delta_rule_roofline"] == pytest.approx(
        100 * 3 * 121 * a_lane / 819e9 / 16e-3)
    assert 0 < got["serve_delta_rule_roofline"] < 100
    # `copy.2` is the step's name too: left to the step
    assert got["serve_state_snapshot_share"] == pytest.approx(
        100 * 5e-4 / 100e-3)
    assert got["sched_snapshot_restore_share"] == pytest.approx(75.0)
    # a program without the counts, the scopes or the programs (the
    # parent's): nothing, and no error
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        dict(s, attrs={"state_lanes": 5, "state_resets": 1}) for s in spans])
    by_scope = {"paged_decoder/ssm_conv": 2e-4,
                "paged_decoder/moe_experts": 6e-4}
    tables = {"paged_decoder.step": tables["paged_decoder.step"]}
    assert {n: r.compute(run) for n, r in readers.items()} == dict.fromkeys(
        readers)
    run.trace = None
    run.spans = []
    assert {n: r.compute(run) for n, r in readers.items()} == dict.fromkeys(
        readers)
    bench = _json("BENCHMARK.json")
    specs = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in specs] == list(names)
    for spec in specs:
        mod = readers[spec["name"]]
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            spec["layer"], spec["unit"], spec["moves"], spec["source"])
        # (a later cell with delta-rule layers lists itself after)
        assert spec["workloads"][0] == CELL
    assert [m["better"] for m in specs] == ["lower", "higher", "lower",
                                            "higher"]


def test_the_job_makes_the_assumed_arrays_and_reads_states_and_tails():
    """`perf/jobs/serve_lm_docqa_state.py`: the taps uniform in +-1/2,
    A_log = log U(1, 16), dt's bias the inverse softplus of U(0.001,
    0.1), everything else `serve_lm_docqa`'s; the walk through the
    served step returns lane 0's states and tails; the fit makes the
    router matrices even over a walk whose pools hold the lanes."""
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
        job = common.load_module(os.path.join(
            ROOT, "perf", "jobs", "serve_lm_docqa_state.py"))
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    dec = _decoder("bf16")
    g = job.make_weights(dec.state_shapes, 3000000019, jnp.float32)
    base = job._make_weights(dec.state_shapes, 3000000019, jnp.float32)
    special = [n for n in g if n.endswith((
        "delta_conv.w_0", "delta_a_log.w_0", "delta_dt.b_0"))]
    assert len(special) == 3 * N_DELTA
    for n in g:
        assert (n in special) != np.array_equal(g[n], base[n]), n
    taps = np.concatenate([np.asarray(g[n]).ravel() for n in special
                           if n.endswith("delta_conv.w_0")])
    assert np.abs(taps).max() <= 0.5 < 1.1 * np.abs(taps).max()
    a = np.exp(np.concatenate([np.asarray(g[n]) for n in special
                               if n.endswith("a_log.w_0")]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    dt = np.log1p(np.exp(np.concatenate([
        np.asarray(g[n], np.float64) for n in special
        if n.endswith("dt.b_0")])))
    assert 0.99e-3 <= dt.min() < 0.02 and 0.08 < dt.max() <= 0.1001
    toks = IDS[:9]
    logits, routing = job.system_outputs(dec, g, toks, 3)
    assert logits.shape == (9, V)
    assert routing["state"].shape == (N_DELTA,) + STATE
    assert routing["tails"].shape == (N_DELTA,) + TAIL
    assert routing["inputs"].shape == (L, 9, D)
    out = REF.compare(g, CONFIG, toks, logits, routing)
    assert out["state_rms_err"] < TOL_BF16_POOL > out["logits_rms_err"]
    cell = types.SimpleNamespace(config=CONFIG, seed=5,
                                 traffic={"slots": 3})
    before = {n: np.asarray(w) for n, w in g.items()
              if n.endswith("router.w_0")}
    fitted = job.balance(cell, dec, g, 24)
    assert fitted["layers"] == L and fitted["passes"] == 3
    assert all(not np.array_equal(before[n], g[n]) for n in before)
    assert job.latent.walk is not job.walk          # put back


def test_the_cell_rehearses_on_the_cpu(tmp_path):
    """`perf/run_cell.py --rehearse`, traced, in a process of its own:
    the toy through the whole job (weights, the fit, the walk against
    the reference, the documents' build, the ramp, the window, the
    served requests against the reference) is `correct`, every request
    of the load restored a snapshot, and the span-sourced metrics of the
    new block are in the line.  Three of the four new metrics read a
    DEVICE trace, which the CPU has none of: the synthetic run above
    holds them."""
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
    assert {"state_rms_err", "tail_rms_err", "cut_rms_err"} <= set(
        notes["reference"])
    assert notes["server"]["recompiles_after_warmup"] == 0
    per = 4 * N_DELTA * (DH * DK * DK + (TAPS - 1) * 3 * DH * DK)
    assert notes["state"] == {
        "layers": N_DELTA, "bytes_per_lane": per, "bytes": 4 * per,
        "snapshot_pool_bytes": 32 * per}
    snaps = notes["snapshots"]
    # (the close may cut each of the 4 clients' last request before its
    # admission)
    assert snaps["state_snapshots_restored"] >= (
        snaps["requests_started"] - 4) > 0
    assert snaps["prefix_blocks_cut"] == 0
    for name in ("sched_snapshot_restore_share", "sched_prefix_hit_share",
                 "sched_state_reset_share", "moe_held_experts_hit_share",
                 "sched_moe_rows_held_share", "sched_pool_wait_share",
                 "tick_ms", "sched_build_ms"):
        assert name in line["metrics"], name
    assert line["metrics"]["sched_snapshot_restore_share"]["value"] == 100.0
    assert line["metrics"]["sched_host_unattributed_share"]["value"] < 25.0


# sha256 of the lowered served step (StableHLO text, no locations) of
# the nine other configurations' toys, taken at the parent commit of
# the PR that added delta-rule layers and snapshots: a description
# without them computes what it computed
# (a block with experts: taken again at PR 63, whose routing orders
# nothing: `tests/test_moe_routing.py` holds it to the results it had)
PARENTS_STEPS = {
    "deepseek-v2-1chip":
        "9c3830f8c2a1b9138e20f455378fb171060f6d4315ba634aa4ee74bd4b592eae",
    "glm-5.2-1chip":
        "c2087f45f65f60cf050d6351ca811905c6c4db817d265cde6d714819933e5ca5",
    "granite-4.0-h-small-1chip":
        "253a1dfac12af82aae0c4a96fde88a25ce72655e5821ba4f294308b4bb447b37",
    "k-exaone-236b-a23b-1chip":
        "4ffbd3e82acdc257b7942dc29e8e4eb0490383b28ace53b1c7d480a6fa040522",
    "lfm2-24b-a2b-1chip":
        "1eb9e2f2533b44ba25115b9015bd3bca06580000c971b2cdee7f87dec1a4031f",
    "longcat-flash-1chip":
        "9701d7e40fd04fc70b38db89e5fa74c0ff1fd7824acb46fb0678af10329f0ecc",
    "mellum2-12b-a2.5b-1chip":
        "212478f1abda7105caa4cdbcca0cbf132f2b82a36c59fa52d9f7f3ec136f15c1",
    "olmoe-1b-7b-1chip":
        "581e73cc9cf988400e7f3617e6d7daed41b3b7f4f9640d37a2a2c5183dad795b",
    "ouro-2.6b":
        "f36a5c45128a6cc5bdc417a7c0750a5bf83734afcdd4d1b341bd85d1b96a531e",
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
