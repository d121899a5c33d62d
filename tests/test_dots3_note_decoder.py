"""The dots3-note-prev block (lm_block's thirteenth description: latent
RINGS beside a selected latent table: sliding layers that are latent
attention at sizes of their own over a ring a lane, full layers under a
lightning indexer on the table, a sigmoid scalar a head on both,
K-EXAONE's sigmoid router over held experts and a shared expert) through
`build_lm_paged_decoder` and `GenerationServer` against the plain
EXPANDED reference `perf/reference/dots3_note.py`, at toy widths on the
CPU with seeded random float32 weights.

The toy keeps what makes the model: the published first five layers
(full, full, sliding x 3; dense, then four sparse), sliding layers with
OTHER heads (4 of 8), another latent rank (48 of 32), another unrotated
key part (12 of 8) and another theta, a window (10) that is NO whole
number of blocks (4: a ring of 3 blocks under a mask of the last 10
rows), `index_topk` 8 against sequences of 57 positions, 16 routed
experts of which 4 are held.  What is compared is LOGITS, cache ROWS and
sampled streams, never greedy tokens.
"""
import functools
import hashlib
import importlib.util
import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.kernels import (paged_attention, paged_index_scores,
                                select_rows)
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "dots3-note-prev-serve-docqa64"
V, D, H, L = 97, 48, 8, 5
QL, KVL, DN, DR, DV = 24, 32, 8, 8, 8     # the full layers' latent sizes
SH, SQL, SKVL, SDN, SDR, SDV = 4, 16, 48, 12, 8, 8    # the sliding layers'
HI, DI, TOPK = 4, 16, 8                 # index heads, their size, rows kept
E, HELD, FIRST, K = 16, 4, 4, 3         # routed, held, from, k
F, FD = 16, 48                          # an expert, a dense FFN
BS, NB, WINDOW = 4, 16, 10              # 64 positions; a ring of 3 blocks
NW = -(-WINDOW // BS)
KINDS = ["full_attention"] * 2 + ["sliding_attention"] * 3
MLPS = ["dense"] + ["sparse"] * 4
CONFIG = {"hidden_size": D, "num_attention_heads": H, "q_lora_rank": QL,
          "kv_lora_rank": KVL, "qk_nope_head_dim": DN,
          "qk_rope_head_dim": DR, "v_head_dim": DV, "rope_theta": 8e4,
          "swa_num_attention_heads": SH, "swa_q_lora_rank": SQL,
          "swa_kv_lora_rank": SKVL, "swa_qk_nope_head_dim": SDN,
          "swa_qk_rope_head_dim": SDR, "swa_v_head_dim": SDV,
          "swa_rope_theta": 50.0, "sliding_window_size": WINDOW,
          "layer_types": KINDS, "apply_mla_qkv_lora_rescale": True,
          "rms_norm_eps": 1e-5, "index_n_heads": HI, "index_head_dim": DI,
          "index_topk": TOPK, "first_k_dense_replace": 1,
          "num_hidden_layers": L, "num_experts_per_tok": K,
          "norm_topk_prob": True, "routed_scaling_factor": 1.0,
          "moe_intermediate_size": F, "first_local_expert": FIRST}
# float32 weights and pool: the same float32 sums in another order
TOL_FP32 = 1e-4
# bf16 pool: latent rows, ring rows and index keys rounded to 8 bits
TOL_BF16_POOL = 4e-2
# the toy's limits, between the decoder's readings and what must fail
LIMITS = {"logits_rms_err": 1e-3, "late_rms_err": 1e-3,
          "index_rel_err": 1e-4, "selection_gap": 1e-6,
          "router_rel_err": 1e-4, "latent_rms_err": 1e-4,
          "ring_rms_err": 1e-4, "window_edge_share": 0.5,
          "window_short_share": 0.5}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


REF = _load(os.path.join(ROOT, "perf", "reference", "dots3_note.py"),
            "ref_dots3_note")


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="dots3_note", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, norm_eps=1e-5,
        rope_parameters={
            "full_attention": {"rope_type": "default", "rope_theta": 8e4},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 50.0}},
        layer_types=KINDS, window=WINDOW,
        n_experts=E, experts_per_token=K, norm_topk_prob=True,
        mlp_layer_types=MLPS, dense_d_inner=FD, experts_first=FIRST,
        experts_held=HELD, shared_d_inner=F, router="sigmoid",
        router_bias=True, routed_scaling_factor=1.0, q_lora_rank=QL,
        kv_lora_rank=KVL, qk_nope_head_dim=DN, qk_rope_head_dim=DR,
        v_head_dim=DV, scale_q_lora=True, scale_kv_lora=True,
        sliding_n_heads=SH, sliding_q_lora_rank=SQL,
        sliding_kv_lora_rank=SKVL, sliding_qk_nope_head_dim=SDN,
        sliding_qk_rope_head_dim=SDR, sliding_v_head_dim=SDV,
        attention_gate=True, attention_gate_per_head=True,
        index_n_heads=HI, index_head_dim=DI, index_topk=TOPK), **over))


def _decoder(kv_dtype="fp32", platform="cpu", **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform=platform, block=_block(**over))
    assert startup is None
    return dec


def _interpreted(monkeypatch, chunk_bytes=2 * BS * 128 * 4, tile_rows=4):
    """Both selections of the latent kernel (the table's under the
    indexer's selection, the ring's under the window's mask) under the
    interpreter, through a whole decoder: pages in several chunks."""
    monkeypatch.setattr(paged_attention, "_CHUNK_BYTES", chunk_bytes)
    monkeypatch.setattr(paged_attention, "_TILE_ROWS", tile_rows)
    monkeypatch.setattr(
        paged_attention, "select_paged_attention", functools.partial(
            paged_attention.select_paged_attention, interpret=True))


def _weights(dec, seed=0):
    """Matrices at sigma 0.1 (0.3 where a product decides a CHOICE: the
    router and the indexer), a choice bias at 0.1."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router.w" in n or "indexer" in n else 0.1,
                     shape).astype(np.float32)
        g[n] = jnp.asarray(1.0 + w if ".scale_" in n else w)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None, pools=None,
           nb=NB):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables from a `PagedKVCache`, the rings `slot_rings`', lane i
    starting at tick `starts[i]`; -> (each sequence's [len, V] logits,
    the first sequence's routing stacked over its positions with its
    table's rows "latent_rows" and its rings "ring_rows" after the last
    position, the pools)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * nb, BS, nb)
    pool_k, pool_v = pools or dec.init_pool(
        1 + slots * nb, window_blocks=1 + slots * NW)
    tables = np.zeros((slots, nb), np.int32)
    rings = dec.slot_rings(slots)
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
        args = (g, pool_k, pool_v, (tables, rings), pos, toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        if act[lanes[0]]:
            routed.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                           for k, v in r.items()})
        _, pool_k, pool_v, *counts = jax.block_until_ready(dec.step(*args))
        assert len(counts) == len(dec.step_counters)
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    routing = {k: np.concatenate([r[k] for r in routed], 1)
               for k in routed[0]}
    lane = lanes[0]
    routing["latent_rows"] = np.asarray(
        pool_k[0][:, tables[lane]], np.float32).reshape(
            pool_k[0].shape[0], nb * BS, -1)
    routing["ring_rows"] = np.asarray(
        pool_k[1][:, rings[lane]], np.float32).reshape(
            pool_k[1].shape[0], NW * BS, -1)
    return out, routing, (pool_k, pool_v)


SEQ = list(np.random.RandomState(7).randint(0, V, 57))   # over 14 blocks
IDS = np.asarray(SEQ, np.int32)


# -- the step against the reference ----------------------------------------
@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_table_rings_and_index_planes_match_the_expanded_reference(
        kv_dtype, tol):
    """57 positions (prompt, then decode: one position a step either
    way) through the five layers: the absorbed step over the selected
    rows of two latent planes, the last 10 rows of three rings of 12
    (four wraps) and two index-key planes against the reference's
    expanded attention under a selection mask and a band mask."""
    dec = _decoder(kv_dtype)
    assert (dec.table_layers, dec.ring_layers, dec.index_planes,
            dec.moe_layers, dec.window, dec.window_blocks_per_seq) == (
                2, 3, 2, 4, WINDOW, NW)
    elem = 4 if kv_dtype == "fp32" else 2
    # rows on the 128-lane grid: 32 + 8 and 48 + 8 columns, 128 each
    assert dec.bytes_per_block == (2 * 128 + 2 * DI) * BS * elem
    assert dec.window_bytes_per_block == 3 * 128 * BS * elem
    assert dec.state_bytes_per_lane == NW * dec.window_bytes_per_block
    g = _weights(dec)
    (got,), routing, (pool_k, pool_v) = _drive(dec, g, [SEQ])
    assert pool_k[0].shape[0] == 2 and pool_k[1].shape[0] == 3
    assert pool_v[0].shape[0] == 2 and pool_v[1] == ()
    assert routing["selected"].shape == (2, len(SEQ), NB * BS)
    assert (routing["selected"].sum(-1)
            == np.minimum(np.arange(len(SEQ)) + 1, TOPK)).all()
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["late_rms_err"] <= tol and out["router_rel_err"] <= 1e-4, out
    assert out["selection_gap"] == 0.0 and out["late_from"] == TOPK, out
    assert out["ring_rows_written"] == NW * BS, out
    assert abs(out["window_edge_share"]) < 0.1, out
    assert abs(out["window_short_share"]) < 0.1, out
    if kv_dtype == "fp32":
        assert out["index_rel_err"] <= 1e-5, out
        assert out["latent_rms_err"] <= 1e-5, out
        assert out["ring_rms_err"] <= 1e-5, out
    else:
        assert out["latent_rms_err"] <= 1e-2 >= out["ring_rms_err"], out


def test_the_comparison_passes_the_decoder_by_every_limit():
    dec = _decoder()
    g = _weights(dec, seed=4)
    (got,), routing, _ = _drive(dec, g, [SEQ])
    ok = REF.compare(g, CONFIG, IDS, got, routing)
    assert ok["finite"] and all(ok[k] <= hi for k, hi in LIMITS.items()), ok
    assert ok["own_rms_err"] <= 1e-5 and ok["routing_agree"] == 1.0
    assert ok["selection_agree"] == 1.0


# the limit(s) that name what each fault broke
REFUSED_BY = {
    "below": ("logits_rms_err", "ring_rms_err", "latent_rms_err"),
    "window_minus_1": ("window_short_share",),
    "window_plus_1": ("window_edge_share",),
    "sliding_at_full_theta": ("ring_rms_err",),
    "full_at_sliding_theta": ("latent_rms_err",),
    "sliding_rank_full": ("ring_rms_err",),
    "sliding_scale_full": ("logits_rms_err",),
    "no_q_rescale": ("logits_rms_err",),
    "no_kv_rescale": ("latent_rms_err", "ring_rms_err"),
    "rescale_swapped": ("latent_rms_err",),
    "no_gate_full": ("logits_rms_err",),
    "no_gate_sliding": ("logits_rms_err",),
    "gate_elementwise": ("logits_rms_err",),
    "sliding_selects": ("logits_rms_err",),
    "full_shared": ("selection_gap",),
    "dense_attention": ("selection_gap",),
    "key_unnormed": ("index_rel_err",),
    "no_index_rope": ("index_rel_err",),
    "no_head_weights": ("index_rel_err",),
    "no_relu": ("index_rel_err",),
    "bias_in_weight": ("router_rel_err",),
    "not_renormalised": ("router_rel_err",),
    "dense_as_sparse": ("logits_rms_err",),
    "ring_wrong_document": ("ring_rms_err",),
    "ring_shifted_block": ("ring_rms_err",),
}


def test_every_fault_of_the_reference_is_named_here():
    assert set(REFUSED_BY) == set(REF.FAULTS) | {"below"}


@pytest.mark.parametrize("what", sorted(REFUSED_BY))
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """`below` (the same equations wholly in bfloat16) and each of the
    reference's faults, as if it were the system, is refused by the
    limit that names what it broke."""
    dec = _decoder()
    g = _weights(dec, seed=4)
    # a restored prefix that the ring still holds rows of at the end
    ids = IDS[:30]
    bad = (REF.below(g, CONFIG, ids, block=BS) if what == "below" else
           REF.faults(g, CONFIG, ids, block=BS, which=(what,))[what])
    if what in REF.RING_FAULTS:
        # the last cursor's ring holds positions 18 to 29: restore to 24
        bad = REF.compare(g, CONFIG, ids, *REF.as_system(
            CONFIG, ids, REF.forward(g, CONFIG, ids, fault=what,
                                     restored=24, block=BS), BS))
    for name in REFUSED_BY[what]:
        assert not bad[name] <= LIMITS[name], (what, name, bad)


@pytest.mark.parametrize("what", REF.RING_FAULTS + ("below", "no_gate_full",
                                                    "dense_attention"))
def test_served_refuses_a_wrong_restore_and_lower_precision(what):
    """`served` (tokens only: what the cell's check after the window
    uses) reads 1.0 on what the reference itself decodes greedily and
    less under a ring restored wrongly, all-bfloat16 and two faults."""
    dec = _decoder()
    g = _weights(dec, seed=4)
    r = np.random.RandomState(5)
    requests = []
    for start in (34, 41):
        ids = list(r.randint(0, V, start))
        for _ in range(16):
            ids.append(int(np.argmax(np.asarray(
                REF.forward(g, CONFIG, np.asarray(ids + [0] * (
                    64 - len(ids))), logits_from=len(ids) - 1)[0])[0])))
        requests.append((np.asarray(ids, np.int32), start))
    ok = REF.served(g, CONFIG, requests, pad_to=64, block=BS)
    assert ok["served_argmax_agree"] == 1.0 and ok["tokens"] == 32, ok
    kw = (dict(dtype=jnp.bfloat16) if what == "below"
          else dict(fault=what))
    bad = REF.served(g, CONFIG, requests, pad_to=64, block=BS, **kw)
    assert bad["served_argmax_agree"] < 0.95, (what, bad)


def test_a_window_of_whole_blocks_and_no_indexer_match_the_reference():
    """The neighbours of the point: a latent ring exactly one window
    long (no mask: `paged_attention_ring` without `masked`), and a
    latent ring beside a latent table WITHOUT an indexer (the
    reference's `dense_attention` computes that model)."""
    whole = _decoder(window=12)
    assert whole.window_blocks_per_seq == 3
    g = _weights(whole, seed=2)
    (got,), routing, _ = _drive(whole, g, [SEQ])
    out = REF.compare(g, dict(CONFIG, sliding_window_size=12), IDS, got,
                      routing)
    assert out["logits_rms_err"] <= 1e-5 >= out["ring_rms_err"], out
    dense = _decoder(index_n_heads=0, index_head_dim=0, index_topk=0)
    assert dense.index_planes == 0 and "lightning_indexer" not in (
        dense.kernels)
    g = _weights(dense, seed=2)
    pools = dense.init_pool(1 + NB, window_blocks=1 + NW)
    assert pools[1] == ((), ())
    tables = np.zeros((1, NB), np.int32)
    tables[0] = 1 + np.arange(NB)
    got = []
    for pos, tok in enumerate(SEQ):
        args = (g, *pools, (tables, dense.slot_rings(1)),
                np.asarray([pos], np.int32), np.asarray([tok], np.int32),
                np.zeros(1, np.uint32), np.zeros(1, np.float32),
                np.ones(1, bool))
        got.append(np.asarray(dense.step_logits(*args))[0])
        _, *pools = dense.step(*args)[:3]
    full = {n: w for n, w in _weights(_decoder(), seed=2).items()
            if "indexer" in n}
    want = np.asarray(REF.forward(dict(g, **full), CONFIG, IDS,
                                  fault="dense_attention")[0])
    assert np.abs(np.stack(got) - want).max() <= 1e-5 * np.abs(want).max()


def test_lanes_out_of_step_are_bit_identical_to_the_sequence_alone():
    """Three sequences in lanes 2, 0 and 1 of a four-lane step, started
    at ticks 0, 5 and 11: each one's logits are, bit for bit, those of
    the sequence alone in the same lane count."""
    dec = _decoder()
    g = _weights(dec, seed=1)
    r = np.random.RandomState(2)
    seqs = [list(r.randint(0, V, n)) for n in (31, 23, 17)]
    together, _, _ = _drive(dec, g, seqs, slots=4, lanes=[2, 0, 1],
                            starts=[0, 5, 11])
    for seq, lane, got in zip(seqs, [2, 0, 1], together):
        (alone,), _, _ = _drive(dec, g, [seq], slots=4, lanes=[lane])
        assert np.array_equal(got, alone)


def test_a_lane_whose_rings_hold_a_predecessor_reads_as_a_fresh_one():
    """A sequence in a lane whose rings (and table blocks) still hold a
    longer predecessor's rows is bit-identical to itself on zero pools:
    the cursor's mask and the window's hide what it did not write."""
    dec = _decoder()
    g = _weights(dec, seed=3)
    r = np.random.RandomState(4)
    first, second = (list(r.randint(0, V, n)) for n in (49, 21))
    _, _, pools = _drive(dec, g, [first])
    (fresh,), _, _ = _drive(dec, g, [second])
    (reused,), _, _ = _drive(dec, g, [second], pools=pools)
    assert np.array_equal(fresh, reused)


def test_both_kernels_in_the_interpreter_equal_the_gather_path(monkeypatch):
    """The table's kernel under the selection and the ring's under the
    window's mask (each selected at its own kind's geometry), in the
    Pallas interpreter through a whole decoder, against the gather path:
    a cursor before the ring's wrap, at it and two wraps past it."""
    plain = _decoder()
    g = _weights(plain, seed=6)
    (want,), _, _ = _drive(plain, g, [SEQ[:30]])
    _interpreted(monkeypatch)
    dec = _decoder()
    assert dec.kernels["paged_attention_decode"] == "pallas:latent"
    assert dec.kernels["paged_attention_ring"] == (
        "pallas:latent:masked_pages")
    assert dec.attention_tiling[1] is not None
    (got,), _, _ = _drive(dec, g, [SEQ[:30]])
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_the_selection_kernel_in_the_interpreter_selects_what_the_passes_do(
        monkeypatch):
    """`kernels/select_rows.py` under the interpreter through a whole
    decoder whose table holds 128 rows (whole 128-lane tiles; the toy's
    64 are refused by name): both full layers' selections and the
    logits are the passes' bit for bit, with the rings two wraps past
    their first, and the path is named once a step is traced, on and
    off the interpreter."""
    def decoder(nb):
        return build_lm_paged_decoder(
            V, BS, nb, d_model=D, n_heads=H, n_layers=L, d_inner=F,
            kv_dtype="fp32", platform="cpu", block=_block())[1]

    plain = decoder(32)
    assert "index_selection" not in plain.kernels   # a step names it
    g = _weights(plain, seed=6)
    seqs, drive = [SEQ[:30], SEQ[3:12]], dict(slots=3, lanes=[0, 2],
                                              starts=[0, 2], nb=32)
    (want, _), routed_x, _ = _drive(plain, g, seqs, **drive)
    assert plain.kernels["index_selection"] == "passes:not_tpu"
    assert plain.tick_counts(np.array([3]), 2)["select_kernel"] == 0
    monkeypatch.setattr(
        select_rows, "select_index_selection", functools.partial(
            select_rows.select_index_selection, interpret=True))
    small = _decoder()
    _drive(small, g, [SEQ[:3]])
    assert small.kernels["index_selection"] == "passes:lane_misaligned"
    dec = decoder(32)
    (got, _), routed_k, _ = _drive(dec, g, seqs, **drive)
    assert dec.kernels["index_selection"] == "pallas:select_rows"
    assert dec.tick_counts(np.array([3]), 2)["select_kernel"] == 1
    assert routed_k["selected"].shape[0] == 2       # both full layers
    assert np.array_equal(routed_x["selected"], routed_k["selected"])
    assert routed_k["selected"].sum(-1).max() == TOPK
    assert np.array_equal(want, got)


# -- the expert layer's share ------------------------------------------------
def test_the_eight_shares_and_the_shared_expert_are_the_uncut_layer():
    """The guide's test of a share: the parts that the eight chips of a
    stage compute of ONE expert layer, each from its own eighth of the
    experts (experts 0 to 1, 2 to 3, ...), with the shared expert (which
    every chip computes alike) counted once, add up to what the uncut
    reference gives for the whole layer."""
    r = np.random.RandomState(9)
    x = jnp.asarray(r.normal(0, 1, (11, D)), jnp.float32)
    p = {"router": r.normal(0, 0.3, (D, E)), "bias": r.normal(0, 0.1, E),
         "gate": r.normal(0, 0.1, (E, D, F)),
         "up": r.normal(0, 0.1, (E, D, F)),
         "down": r.normal(0, 0.1, (E, F, D)),
         "shared_gate": r.normal(0, 0.1, (D, F)),
         "shared_up": r.normal(0, 0.1, (D, F)),
         "shared_down": r.normal(0, 0.1, (F, D))}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    own = jnp.full((11, K), -1, jnp.int32)
    scaling = jnp.asarray(1.0, jnp.float32)
    held = E // 8
    with jax.default_matmul_precision("highest"):
        whole, routing = REF._moe(x, p, own, scaling, top_k=K, first=0)
        nothing = dict(p, **{k: jnp.zeros_like(p[k]) for k in (
            "shared_gate", "shared_up", "shared_down")})
        shared = REF._moe(x, dict(p, **{k: p[k][:0] for k in (
            "gate", "up", "down")}), own, scaling, top_k=K, first=0)[0]
        parts = []
        for first in range(0, E, held):
            share = dict(nothing, **{k: p[k][first:first + held]
                                     for k in ("gate", "up", "down")})
            got, r_ = REF._moe(x, share, own, scaling, top_k=K, first=first)
            assert np.array_equal(r_["experts"], routing["experts"])
            parts.append(got)
    assert len(parts) == 8
    total = sum(parts) + shared
    assert np.abs(total - whole).max() <= 1e-5 * np.abs(whole).max()
    # and the served layer is such a share: `lm_block.moe_ffn` over the
    # experts `first` onward gives the reference's part
    first = 3 * held
    mine = lm_block.moe_ffn(
        lm_block.BlockSpec(**dict(_block().__dict__, experts_first=first,
                                  experts_held=held)), x,
        p["router"], *(p[k][first:first + held]
                       for k in ("gate", "up", "down")),
        b_router=p["bias"])[0]
    assert np.abs(mine - parts[3]).max() <= 1e-5 * np.abs(parts[3]).max()


# -- the prefix cache over rings and a selected table -----------------------
def _serve(dec, g, prefix, asks, *, slots=3, snapshots=None, blocks=96):
    """`asks`: (prompt, new tokens) in order, each awaited before the
    next (so that a later one finds what an earlier one cached); ->
    (their sampled streams, the server's stats)."""
    srv = GenerationServer(dec, g, slots=slots, kv_blocks=blocks,
                           place=fluid.CPUPlace(), prefix_cache=prefix,
                           state_snapshots=snapshots)
    try:
        out = [srv.submit(p, n, temperature=1.0, seed=50 + i).result(
            timeout=120) for i, (p, n) in enumerate(asks)]
        return out, srv.stats()
    finally:
        srv.close()


@pytest.mark.parametrize("doc_blocks", [2, 3, 7],
                         ids=["before_the_wrap", "at_the_wrap",
                              "after_the_wrap"])
def test_a_hit_restores_the_rings_and_reads_the_shared_blocks(doc_blocks):
    """A document built through `submit(document, 1)`, then requests
    that are the document and a question: with the prefix cache on each
    starts from a RESTORED snapshot of the lane's three rings AND
    attends, on the full layers, over the document's latent rows and
    index keys in the blocks the document's request wrote; its stream
    is, token for token, that of the same request on a server without a
    cache.  The hit ends before the ring's first wrap (8 of 12 rows
    written), at it (12) and well after (28: the ring has wrapped
    twice)."""
    dec = _decoder()
    assert set(dec.refuses) == {"draft_model"}
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(3)
    doc = list(r.randint(0, V, doc_blocks * BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, V, n)), 9)
                         for n in (3, 6, 5)]
    hit, stats = _serve(dec, g, True, asks)
    miss, plain = _serve(dec, g, False, asks)
    assert hit == miss
    assert stats["state_snapshots_saved"] >= 1
    assert stats["state_snapshots_restored"] == 3
    assert stats["prefix_hits"] >= 3
    assert plain["prefix_hits"] == 0
    assert stats["state_snapshot_pool_bytes"] == 3 * dec.state_bytes_per_lane


@pytest.mark.parametrize("prefix", [False, True], ids=["miss", "hit"])
def test_the_server_delivers_the_references_tokens(prefix):
    """Prefill, then decode through `GenerationServer` at temperature 0,
    with every lane live, against the reference's full forward pass over
    each delivered sequence (`served`: every delivered token is the
    reference's argmax at the position that sampled it): without a cache,
    and with the requests' shared document found in the prefix cache and
    their rings restored from its snapshot."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(6)
    doc = list(r.randint(0, V, 5 * BS))
    prompts = [doc + list(r.randint(0, V, n)) for n in (3, 6, 5)]
    srv = GenerationServer(dec, g, slots=3, kv_blocks=96,
                           place=fluid.CPUPlace(), prefix_cache=prefix)
    try:
        srv.submit(doc, 1).result(timeout=120)
        streams = [srv.submit(p, 20) for p in prompts]
        out = [s.result(timeout=120) for s in streams]
        stats = srv.stats()
    finally:
        srv.close()
    assert (stats["prefix_hits"] > 0) == prefix
    assert dec.kernels["index_selection"] == "passes:not_tpu"
    requests = [(np.asarray(p + list(o), np.int32), len(p))
                for p, o in zip(prompts, out)]
    got = REF.served({n: jnp.asarray(w) for n, w in g.items()}, CONFIG,
                     requests, pad_to=64, block=BS)
    assert got["served_argmax_agree"] == 1.0 and got["tokens"] == 60, got


def test_a_reused_lane_equals_a_fresh_one_through_the_server():
    """One slot, two requests one after the other: the second, in the
    lane the first left its rings in, streams what it streams alone."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(8)
    a, b = (list(r.randint(0, V, n)) for n in (37, 14))
    for prefix in (False, True):
        both, _ = _serve(dec, g, prefix, [(a, 12), (b, 12)], slots=1)
        srv = GenerationServer(dec, g, slots=1, kv_blocks=96,
                               place=fluid.CPUPlace(), prefix_cache=prefix)
        try:
            alone = srv.submit(b, 12, temperature=1.0, seed=51).result(
                timeout=120)
        finally:
            srv.close()
        assert both[1] == alone


def _mellum(window=8):
    spec = lm_block.BlockSpec(
        name="mellum", norm="rms_norm", positions="rope", ffn="moe_swiglu",
        bias=False, n_experts=8, experts_per_token=2, norm_topk_prob=True,
        n_kv_heads=2, d_head=4,
        layer_types=["sliding_attention"] * 3 + ["full_attention"],
        window=window,
        rope_parameters={"rope_type": "default", "rope_theta": 500.0})
    _, dec = build_lm_paged_decoder(V, 4, 12, d_model=48, n_heads=16,
                                    n_layers=4, d_inner=16, block=spec,
                                    platform="cpu")
    return dec


def test_a_ring_of_k_and_v_heads_is_served_under_the_prefix_cache():
    """Mellum's toy (rings of K and V heads beside a K/V table), which
    refused `prefix_cache=True` by name until rings had snapshots: a hit
    restores the K ring and the V ring of the three sliding layers and
    equals the run without a cache."""
    dec = _mellum()
    assert set(dec.refuses) == {"draft_model"}
    assert dec.init_snapshots is not None and dec.state_layers == 0
    assert dec.state_bytes_per_lane == 2 * dec.window_bytes_per_block
    assert "paged_attention_ring" not in dec.kernels
    r = np.random.RandomState(1)
    g = {n: (1.0 + r.normal(0, 0.1, s) if ".scale_" in n
             else r.normal(0, 0.1, s)).astype(np.float32)
         for n, s in sorted(dec.state_shapes.items())}
    doc = list(r.randint(0, V, 5 * 4))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, V, n)), 9)
                         for n in (3, 6)]
    hit, stats = _serve(dec, g, True, asks, blocks=64)
    miss, _ = _serve(dec, g, False, asks, blocks=64)
    assert hit == miss
    assert stats["state_snapshots_restored"] == 2
    with pytest.raises(ValueError, match="whole number of 4-position"):
        _mellum(window=10)


# -- what a tick, a block, a ring and a snapshot hold -----------------------
def test_counts_of_a_tick_name_table_rings_and_selection_together():
    dec = _decoder("bf16")
    cursors = np.asarray([0, 5, 9, 10, 40], np.int64)
    counts = dec.tick_counts(cursors, 8)
    rows = cursors + 1
    assert counts["latent_rows"] == 2 * rows.sum()
    assert counts["kv_rows_win"] == np.minimum(rows, WINDOW).sum()
    assert counts["past_window"] == 2
    assert counts["kv_rows_indexed"] == 2 * rows.sum()
    assert counts["kv_rows_selected"] == 2 * np.minimum(rows, TOPK).sum()
    assert dec.index_planes == 2 and counts["moe_layers"] == 4
    assert "index_planes" not in counts     # the field has no reader
    # three rings of rows 128 wide in bfloat16, 12 rows at most
    assert counts["ring_bytes"] == 3 * 128 * 2 * np.minimum(
        rows, NW * BS).sum()
    assert counts["kv_pages_table"] == 8 * (2 * NB + 3 * NW)
    snaps = dec.init_snapshots(5)
    assert snaps[0].shape == (5, 3, NW, BS, 128) and snaps[1] == ()
    assert snaps[0].dtype == jnp.bfloat16
    pool_k, pool_v = dec.init_pool(9, window_blocks=1 + 4 * NW)
    ring = pool_k[1].at[:, 1 + 2 * NW:1 + 3 * NW].set(1.0)
    snaps = dec.snapshot_save(snaps, (pool_k[0], ring), pool_v,
                              np.int32(2), np.int32(4))
    assert float(snaps[0][4].min()) == 1.0 and float(snaps[0][:4].max()) == 0
    back_k, back_v = dec.snapshot_restore(pool_k, pool_v, snaps,
                                          np.int32(1), np.int32(4))
    assert float(back_k[1][:, 1 + NW:1 + 2 * NW].min()) == 1.0
    assert float(back_k[1][:, 1 + 2 * NW:].max()) == 0.0
    assert back_v[1] == () and back_k[0].shape == pool_k[0].shape


def test_scopes_name_the_kinds_and_the_selection():
    """The latent parts and the indexer's lie under their layer's kind,
    the selected read under `attention/selected/full` (what
    `serve_sparse_attention_roofline` looks for) and the ring's under
    `attention/sliding` (what `serve_window_layers_share` and the new
    reader look for)."""
    dec = _decoder()
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(
        2 * NB + 1, window_blocks=2 * NW + 1))
    i32 = sds((2,), np.int32)
    text = dec.step.lower(
        g, *pools, (sds((2, NB), np.int32), sds((2, NW), np.int32)), i32,
        i32, sds((2,), np.uint32), sds((2,), np.float32),
        sds((2,), np.bool_)).as_text(debug_info=True)
    for scope in ("paged_decoder/latent_q/full", "paged_decoder/latent_q/"
                  "sliding", "paged_decoder/latent_kv/sliding",
                  "paged_decoder/latent_absorb/full",
                  "paged_decoder/attention/selected/full",
                  "paged_decoder/attention/sliding",
                  "paged_decoder/attention_head_gate/sliding",
                  "paged_decoder/attn_out/full",
                  "paged_decoder/indexer_scores/full",
                  "paged_decoder/indexer_topk/full",
                  "paged_decoder/dense_ffn", "paged_decoder/moe_experts"):
        assert scope in text, scope
    assert "paged_decoder/indexer_q/sliding" not in text
    assert "paged_decoder/attention/selected/sliding" not in text


# -- what is STILL refused ----------------------------------------------------
STILL_REFUSED = {
    "kv_ring_beside_a_latent_table": (
        dict(n_kv_heads=2, d_head=8), "ring of K and V heads"),
    "mamba_beside_a_latent_ring": (
        dict(layer_types=["full_attention", "mamba"] + KINDS[2:],
             ssm_heads=2, ssm_d_head=8, ssm_d_state=4, ssm_conv=3),
        "Mamba"),
    "conv_beside_a_latent_ring": (
        dict(layer_types=["full_attention", "conv"] + KINDS[2:],
             conv_width=3), "short convolutions"),
    "delta_rule_beside_a_latent_ring": (
        dict(layer_types=["full_attention", "delta_rule"] + KINDS[2:],
             delta_heads=2, delta_d_head=8, delta_conv=3),
        "delta-rule layers"),
    "a_selection_shared_across_a_sliding_layer": (
        dict(indexer_types=["full", "shared", "none", "none", "none"]),
        "SHARED across a sliding layer"),
    "a_selection_on_a_sliding_layer": (
        dict(indexer_types=["full", "full", "full", "none", "none"]),
        "a selection on a sliding layer"),
    "an_elementwise_gate_on_a_latent_layer": (
        dict(attention_gate_per_head=False), "elementwise gate on a latent"),
    "a_sliding_query_without_a_rank_under_rescale": (
        dict(q_lora_rank=0, sliding_q_lora_rank=0, index_topk=0,
             index_n_heads=0, index_head_dim=0), "query of ONE matrix"),
    "an_odd_sliding_rope_width": (
        dict(sliding_qk_rope_head_dim=7), "even qk_rope_head_dim"),
    "no_full_layer_beside_the_rings": (
        dict(layer_types=["sliding_attention"] * 5, index_topk=0,
             index_n_heads=0, index_head_dim=0), "latent cache"),
}


@pytest.mark.parametrize("name", sorted(STILL_REFUSED))
def test_each_narrowed_refusal_still_refuses_what_is_not_built(name):
    over, why = STILL_REFUSED[name]
    with pytest.raises(NotImplementedError, match=why):
        lm_block.param_layout(_block(**over), V, D, H, L, F)


@pytest.mark.parametrize("what,why", [
    ("int8", "int8 pool"), ("draft", "takes no draft model"),
    ("step_window", "step_window")])
def test_the_served_block_still_refuses_by_name(what, why):
    if what == "int8":
        with pytest.raises(NotImplementedError, match=why):
            _decoder("int8")
        return
    dec = _decoder()
    if what == "draft":
        assert why in dec.refuses["draft_model"]
        g = {n: np.asarray(w) for n, w in _weights(dec).items()}
        with pytest.raises(ValueError, match=why):
            GenerationServer(dec, g, slots=2, kv_blocks=32,
                             place=fluid.CPUPlace(), draft_decoder=dec,
                             draft_states=g)
        return
    pools = dec.init_pool(1 + NB, window_blocks=1 + NW)
    with pytest.raises(NotImplementedError, match=why):
        dec.step_window(_weights(dec), *pools,
                        (np.zeros((1, NB), np.int32), dec.slot_rings(1)),
                        np.zeros(1, np.int32), np.zeros((1, 2), np.int32),
                        np.zeros(1, np.uint32), np.zeros(1, np.float32),
                        np.ones(1, np.int32))


@pytest.mark.parametrize("over,why", [
    (dict(sliding_n_heads=4, layer_types=[], window=0, index_topk=0,
          index_n_heads=0, index_head_dim=0), "sliding layers' own"),
    (dict(sliding_kv_lora_rank=-1), "sliding layers' own"),
    (dict(indexer_types=["full", "none", "none", "none", "none"],
          layer_types=[], window=0, sliding_n_heads=0,
          sliding_q_lora_rank=0, sliding_kv_lora_rank=0,
          sliding_qk_nope_head_dim=0, sliding_qk_rope_head_dim=0,
          sliding_v_head_dim=0), "a sliding layer's"),
])
def test_a_description_that_contradicts_itself_is_a_value_error(over, why):
    with pytest.raises(ValueError, match=why):
        spec = _block(**over)
        lm_block.param_layout(spec, V, D, H, L, F)


# -- the configuration, the cell and the readers -----------------------------
FILE = _json("perf", "configs", "dots3-note-prev-1chip.json")


def _file_block(m):
    b = m["block"]
    fields = dict(b["spec"], **{f: m[k] for f, k in b["from_keys"].items()})
    return lm_block.BlockSpec(**fields), m[b["d_inner"]]


def test_the_kernels_are_selected_for_a_tpu_at_the_cells_geometry():
    """Built for "tpu" at the published widths (no array is made): the
    three kernels named apart, and what a block, a ring and a lane
    hold."""
    spec, d_inner = _file_block(FILE)
    _, dec = build_lm_paged_decoder(
        FILE["vocab_size"], 16, 432, d_model=FILE["hidden_size"],
        n_heads=FILE["num_attention_heads"],
        n_layers=FILE["num_hidden_layers"], d_inner=d_inner,
        kv_dtype="bf16", platform="tpu", block=spec)
    assert dec.kernels == {
        "paged_attention_decode": "pallas:latent",
        "paged_attention_window": "xla:window_rows",
        "paged_attention_selected": "pallas:latent:masked_pages",
        "lightning_indexer": "pallas:paged_scores",
        "paged_attention_ring": "pallas:latent:masked_pages"}
    numbers = FILE["cut"]["arithmetic_numbers"]
    assert dec.bytes_per_block == 16 * numbers["cache_bytes_a_position"]
    assert (dec.window, dec.window_blocks_per_seq) == (513, 33)
    assert dec.state_bytes_per_lane == numbers["ring_bytes_a_lane"]
    assert (dec.table_layers, dec.ring_layers, dec.index_planes,
            dec.moe_layers) == (2, 3, 2, 4)
    # (since PR 66: a cap of 64 pages over the table, the ring whole)
    assert dec.attention_tiling == ((64, 8), (33, 8))


def test_configuration_file_holds_the_catalogs_keys_and_its_arithmetic():
    """Every published width under the source's own keys; the derived
    keys are what they repeat; `cut.arithmetic_numbers` recomputed from
    `param_layout`'s shapes (279.6 B whole, 4.087 B held)."""
    rows = [json.loads(l) for l in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    for row in (r for r in rows if r["name"] == "dots3-note-prev"):
        assert FILE["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in FILE["reduced"] and key != "layer_types":
                assert FILE[key] == value, key
        assert FILE["layer_types"] == row["config"]["layer_types"][:5]
        assert FILE["published"] == {k: row["config"][k]
                                     for k in FILE["reduced"]}
    m = FILE
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (5, 32, 19008)
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == m["name"]]
    assert entry["reduced"] == m["reduced"]
    assert entry["source"] == m["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        m["name"], "docqa64-ring", 1)
    # the file's rules of form on what this configuration added: a line
    # of 1 to 200 printable characters, a name of at most 64
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for name in (entry["name"], cell["name"], cell["traffic"]):
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name), name
    # the derived keys are what the source's own keys give, in the toy's
    # overlay too
    for cfg in (m, dict(m, **m["rehearse"])):
        n = cfg["num_hidden_layers"]
        assert cfg["mlp_layer_types"] == [
            "dense" if l < cfg["first_k_dense_replace"] else "sparse"
            for l in range(n)]
        assert cfg["num_experts"] == cfg["n_routed_experts"]
        assert cfg["first_local_expert"] == 0
        assert cfg["shared_intermediate_size"] == (
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
        assert cfg["rope_parameters"] == {
            "full_attention": {"rope_type": "default",
                               "rope_theta": cfg["rope_theta"]},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg["swa_rope_theta"]}}
        assert cfg["n_group"] == cfg["topk_group"] == 1
    assert m["num_routed_experts"] == m["published"]["n_routed_experts"]
    # the arithmetic, from the shapes `param_layout` gives
    spec, d_inner = _file_block(m)
    numbers = m["cut"]["arithmetic_numbers"]

    def shapes_of(spec, vocab, layers):
        return lm_block.param_layout(spec, vocab, m["hidden_size"],
                                     m["num_attention_heads"], layers,
                                     d_inner)[1]

    here = shapes_of(spec, m["vocab_size"], 5)

    def millions(pred, shapes=here):
        return sum(math.prod(s) for n, s in shapes.items() if pred(n)) / 1e6

    def mixer(layer):
        return lambda n: n.startswith(f"layer_{layer}.") and any(
            k in n for k in ("attn_", "q_a", "q_b", "kv_", "o_proj"))

    assert round(millions(mixer(1)), 2) == numbers["full_attention_m"]
    assert round(millions(lambda n: n.startswith("layer_1.indexer")),
                 2) == numbers["indexer_m"]
    assert round(millions(mixer(2)), 2) == numbers["sliding_attention_m"]
    assert round(millions(lambda n: n.startswith("layer_0.ffn_")
                          and "norm" not in n), 2) == numbers["dense_ffn_m"]
    assert round(millions(lambda n: n.startswith("layer_2.router.")),
                 2) == numbers["router_m"]
    assert round(millions(lambda n: n.startswith("layer_2.experts"))
                 / m["n_routed_experts"], 2) == numbers["expert_m"]
    assert round(millions(lambda n: n == "lm_head.w_0"), 1) == numbers[
        "vocabulary_m"]
    assert round(millions(lambda n: True) / 1e3, 3) == numbers["here_b"]
    assert round(2 * millions(lambda n: True) / 1e3, 2) == numbers[
        "weights_gb"]
    # the whole model: 46 layers, every expert, the whole vocabulary
    kinds = ["full_attention"] + ["full_attention", "sliding_attention",
                                  "sliding_attention",
                                  "sliding_attention"] * 11 + [
        "full_attention"]
    whole = lm_block.BlockSpec(**dict(
        spec.__dict__, experts_held=0, layer_types=tuple(kinds),
        mlp_layer_types=("dense",) + ("sparse",) * 45))
    model = shapes_of(whole, m["published"]["vocab_size"], 46)
    assert round(millions(lambda n: True, model) / 1e3, 1) == numbers[
        "model_b"]
    assert round(2 * millions(lambda n: n.startswith("layer_2."), model)
                 / 1e3, 1) == numbers["sparse_layer_whole_gb"]
    assert numbers["ring_bytes_a_lane"] == 3 * 33 * 16 * 1152 * 2
    assert numbers["cache_bytes_a_position"] == 2 * 640 * 2 + 2 * 128 * 2
    # the traffic file is docqa64's, but for what names the job and the
    # snapshots
    mine, theirs = (_json("perf", "traffic", name + ".json")
                    for name in ("docqa64-ring", "docqa64"))
    assert mine.pop("job") == "serve_lm_docqa_ring"
    assert mine.pop("state_snapshots") == 32
    for key in ("job", "what"):
        mine.pop(key, None), theirs.pop(key, None)
    assert mine == theirs


def test_the_new_reader_and_the_cells_lists_agree_with_the_benchmark():
    bench = _json("BENCHMARK.json")
    (last,) = [m for m in bench["per_layer"]
               if m["name"] == "serve_latent_ring_roofline"]
    assert last["workloads"] == [CELL]
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        reader = _load(os.path.join(ROOT, "perf", "metrics",
                                    "serve_latent_ring_roofline.py"),
                       "reader_latent_ring")
        assert (reader.UNIT, reader.MOVES, reader.SOURCE, reader.LAYER) == (
            last["unit"], last["moves"], last["source"], last["layer"])
        # nothing to read without a trace: the line leaves the metric out
        run = type("Run", (), {"trace": None, "notes": {}})()
        assert reader.compute(run) is None
        cost = _load(os.path.join(ROOT, "perf", "latent_ring_cost.py"),
                     "latent_ring_cost")
        assert cost.stored_row_bytes(1024, 64, 2) == 2304
        assert cost.ring_call(10, 1024, 64, 2)["bytes"] == 23040.0
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", ())}
    for name in ("serve_tokens_per_s", "itl_p95_ms", "ttft_p95_ms",
                 "ttft_p50_ms", "serve_queue_wait_p95_ms",
                 "serve_sparse_attention_roofline", "serve_indexer_roofline",
                 "serve_moe_experts_roofline", "serve_window_layers_share",
                 "sched_past_window_share", "sched_prefix_hit_share",
                 "sched_snapshot_restore_share",
                 "serve_state_snapshot_share", "serve_dense_ffn_share",
                 "serve_hbm_peak_gb", "serve_device_idle_share"):
        assert name in mine, name
    for name in ("serve_attention_roofline", "serve_full_layers_share",
                 "serve_latent_attention_roofline"):
        assert name not in mine, name


def test_rehearsal_of_the_cell_prints_the_readers():
    """The cell end to end on the CPU at the files' tiny sizes: both
    comparisons pass, every request of the load starts from a restored
    ring, and the span-sourced readers are in the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   os.environ.get("TMPDIR", "/tmp"), "dots3_rehearsal_cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run_cell.py"),
         "--workload", CELL, "--seed", "6500000123", "--seconds", "3",
         "--trace", "1", "--rehearse"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=400)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0, out.stdout[-3000:]
    for name in ("sched_prefix_hit_share", "sched_snapshot_restore_share",
                 "sched_past_window_share", "sched_kv_rows_selected_share",
                 "moe_held_experts_hit_share", "sched_moe_rows_held_share",
                 "tick_ms", "ttft_p50_ms", "serve_queue_wait_p95_ms"):
        assert name in line["metrics"], name     # the cell reports ttft


# sha256 of the lowered served step (StableHLO text, no locations) of
# the eleven other configurations' toys, taken at the parent commit of
# the PR that put latent rings beside a selected latent table: a
# description without the new fields computes what it computed
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
}


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_other_toys_lowered_steps_are_the_parents_text(name):
    m = _json("perf", "configs", name + ".json")
    m.update(m["rehearse"])
    spec, d_inner = _file_block(m)
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
