"""The GLM-5.2 block (latent attention under a LIGHTNING INDEXER: an index
key a position in a plane of its own on the latent rows' block table,
`index_topk` rows selected inside attention, the selection SHARED by the
layers after a selecting one; K-EXAONE's sigmoid router over held
experts and a shared expert) through `build_lm_paged_decoder` against
the plain EXPANDED reference `perf/reference/glm_dsa.py`, at toy widths
on the CPU with seeded random float32 weights.

The toy keeps what makes the model: the published pattern of five
layers (dense, then four sparse; full, shared x 3, full), a value head
(16) wider than a key's unrotated part (8), a row (32 + 8 = 40) that
needs the pad to the lane grid, `index_topk` 8 against sequences of 48
to 64 positions (five sixths of the rows dropped), 4 index heads of 16
with RoPE on their first 8 columns, 16 routed experts of which 4 are
held.  What is compared is LOGITS, never tokens.
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
from paddle_tpu.kernels import (paged_attention, paged_index_scores,
                                select_rows)
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, D, H, L = 97, 48, 8, 5
QL, KVL, DN, DR, DV = 24, 32, 8, 8, 16  # ranks; nope, rope, value a head
HI, DI, TOPK = 4, 16, 8                 # index heads, their size, rows kept
E, HELD, FIRST, K = 16, 4, 4, 3         # routed, held, from, k
F, FD = 16, 40                          # an expert, a dense FFN
BS, NB = 4, 16                          # 64 positions
INDEXERS = ["full", "shared", "shared", "shared", "full"]
MLPS = ["dense", "sparse", "sparse", "sparse", "sparse"]
CONFIG = {"num_attention_heads": H, "hidden_size": D, "q_lora_rank": QL,
          "kv_lora_rank": KVL, "qk_nope_head_dim": DN,
          "qk_rope_head_dim": DR, "v_head_dim": DV, "rms_norm_eps": 1e-5,
          "rope_parameters": {"rope_type": "default", "rope_theta": 8e6},
          "index_n_heads": HI, "index_head_dim": DI, "index_topk": TOPK,
          "indexer_types": INDEXERS, "mlp_layer_types": MLPS,
          "num_hidden_layers": L, "num_experts_per_tok": K,
          "norm_topk_prob": True, "routed_scaling_factor": 2.5,
          "moe_intermediate_size": F, "first_local_expert": FIRST}
# float32 weights and pool: the same float32 sums in another order
# (absorbed against expanded, grouped matmul against a masked scan):
# measured 1e-6 to 3e-6
TOL_FP32 = 1e-4
# bf16 pool: latent rows AND index keys rounded to 8 bits of mantissa on
# their way into the table (a rounded index key can swap a row at the
# 8th score: the reference follows the decoder's selection): measured
# 3e-3 to 8e-3
TOL_BF16_POOL = 4e-2
# the toy's limits, between the decoder's readings and `below`'s
LIMITS = {"logits_rms_err": 1e-3, "late_rms_err": 1e-3,
          "index_rel_err": 1e-4, "selection_gap": 1e-6,
          "router_rel_err": 1e-4}


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(os.path.join(ROOT, "perf", "reference", "glm_dsa.py"),
            "ref_glm_dsa")


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="glm_moe_dsa", norm="rms_norm", positions="rope",
        ffn="moe_swiglu", bias=False, norm_eps=1e-5,
        rope_parameters={"rope_type": "default", "rope_theta": 8e6},
        n_experts=E, experts_per_token=K, norm_topk_prob=True,
        mlp_layer_types=MLPS, dense_d_inner=FD, experts_first=FIRST,
        experts_held=HELD, shared_d_inner=F, router="sigmoid",
        router_bias=True, routed_scaling_factor=2.5, q_lora_rank=QL,
        kv_lora_rank=KVL, qk_nope_head_dim=DN, qk_rope_head_dim=DR,
        v_head_dim=DV, index_n_heads=HI, index_head_dim=DI,
        index_topk=TOPK, indexer_types=INDEXERS), **over))


def _decoder(kv_dtype="fp32", **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu", block=_block(**over))
    assert startup is None
    return dec


def _interpreted(monkeypatch, chunk_bytes=2 * BS * 128 * 4, tile_rows=4):
    """The latent form of the Pallas kernel under a selection, under
    the interpreter, through a whole decoder: pages in several chunks,
    some with no selected row."""
    monkeypatch.setattr(paged_attention, "_CHUNK_BYTES", chunk_bytes)
    monkeypatch.setattr(paged_attention, "_TILE_ROWS", tile_rows)
    monkeypatch.setattr(
        paged_attention, "select_paged_attention", functools.partial(
            paged_attention.select_paged_attention, interpret=True))


def _indexer_interpreted(monkeypatch, pages=4, tile_rows=BS):
    """The index-score kernel under the interpreter through a whole
    decoder: the table's 16 pages of 4 float32 keys in four chunks."""
    monkeypatch.setattr(paged_index_scores, "_CHUNK_BYTES",
                        pages * BS * DI * 4)
    monkeypatch.setattr(paged_index_scores, "_TILE_ROWS", tile_rows)
    monkeypatch.setattr(
        paged_index_scores, "select_index_scores", functools.partial(
            paged_index_scores.select_index_scores, interpret=True))


def _weights(dec, seed=0):
    """Matrices at sigma 0.1 (0.3 where a product decides a CHOICE: the
    router and the indexer), a choice bias at 0.1 beside sigmoids near a
    half, so that it moves the choice of most tokens."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        w = r.normal(0, 0.3 if "router.w" in n or "indexer" in n else 0.1,
                     shape).astype(np.float32)
        g[n] = jnp.asarray(1.0 + w if ".scale_" in n else w)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None,
           routing=False, nb=NB):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); returns each
    sequence's [len, V] logits (and lane 0's routing stacked over its
    positions, and the pools)."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * nb, BS, nb)
    pool_k, pool_v = dec.init_pool(1 + slots * nb)
    # the latent rows a plane a layer, the index keys a plane a
    # selecting layer, on the same blocks
    assert pool_k.shape[0] == L and pool_k.shape[-1] == 128
    assert pool_v.shape == (2,) + pool_k.shape[1:3] + (DI,)
    tables = np.zeros((slots, nb), np.int32)
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
        assert len(counts) == len(dec.step_counters)
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    out = [np.stack(o) for o in out]
    if routing:
        return out, {k: np.concatenate([r[k] for r in routed], 1)
                     for k in routed[0]}, (pool_k, pool_v, tables)
    return out


SEQ = list(np.random.RandomState(7).randint(0, V, 57))   # over 14 blocks
IDS = np.asarray(SEQ, np.int32)


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_table_and_index_planes_match_the_expanded_reference(kv_dtype, tol):
    """57 positions (prompt, then decode: one position a step either
    way) through the five layers: the ABSORBED step over the selected
    rows of five latent planes and two index-key planes against the
    reference's expanded attention under a mask, 49 of 57 rows dropped
    at the end."""
    dec = _decoder(kv_dtype)
    assert (dec.table_layers, dec.kv_planes, dec.index_planes,
            dec.moe_layers, dec.ring_layers) == (5, 5, 2, 4, 0)
    elem = 4 if kv_dtype == "fp32" else 2
    assert dec.bytes_per_block == (5 * 128 + 2 * DI) * BS * elem
    g = _weights(dec)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    assert routing["selected"].shape == (2, len(SEQ), NB * BS)
    assert routing["index_scores"].shape == (2, len(SEQ), NB * BS)
    assert routing["index_inputs"].shape == (2, len(SEQ), D)
    assert routing["index_latents"].shape == (2, len(SEQ), QL)
    assert (routing["selected"].sum(-1)
            == np.minimum(np.arange(len(SEQ)) + 1, TOPK)).all()
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["late_rms_err"] <= tol and out["router_rel_err"] <= 1e-4, out
    assert out["selection_gap"] == 0.0 and out["late_from"] == TOPK, out
    assert 0.7 < out["rows_dropped_share"] < 0.8, out
    if kv_dtype == "fp32":
        assert out["index_rel_err"] <= 1e-5, out
        assert out["selection_agree"] == 1.0, out


def test_absorbed_equals_expanded_at_float32_to_rounding():
    dec = _decoder()
    g = _weights(dec, seed=4)
    (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
    ok = REF.compare(g, CONFIG, IDS, got, routing)
    assert ok["logits_rel_err"] <= 1e-5 and ok["logits_rms_err"] <= 1e-5
    assert ok["own_rms_err"] <= 1e-5 and ok["index_rel_err"] <= 1e-5
    assert ok["router_rel_err"] <= 1e-5 and ok["routing_agree"] == 1.0
    assert ok["selection_agree"] == 1.0 and ok["selection_gap"] == 0.0


def test_the_kernel_under_a_selection_equals_the_gather_path(monkeypatch):
    """The Pallas latent kernel given the selection as a row mask, in
    the interpreter, chunks of two pages (most hold no selected row: the
    running maximum stays at minus infinity there), beside a lane with
    no sequence and a lane out of step."""
    dec_x = _decoder()
    _interpreted(monkeypatch)
    dec_k = _decoder()
    assert dec_k.kernels["paged_attention_selected"] == \
        "pallas:latent:masked_pages"
    assert dec_x.kernels["paged_attention_selected"] == \
        "xla:not_tpu:masked_gather"
    g = _weights(dec_x, seed=2)
    seqs = [SEQ[:29], SEQ[5:23]]
    want = _drive(dec_x, g, seqs, slots=3, lanes=[0, 2], starts=[0, 4])
    got = _drive(dec_k, g, seqs, slots=3, lanes=[0, 2], starts=[0, 4])
    for a, b in zip(want, got):
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max()


@pytest.mark.parametrize("attention_too", [False, True],
                         ids=["indexer", "indexer_and_attention"])
def test_the_index_score_kernel_holds_the_references_limits(
        attention_too, monkeypatch):
    """`step_routing`'s `index_scores` and the selection taken from
    them, through the streaming kernel in the interpreter (beside a
    lane with no sequence and a lane out of step), against the
    reference under the toy's limits, and against the gather path's
    own: the same products, the heads' float32 sum in another order.
    The interpreter takes a second a tick, so the sequence is the
    shortest that still meets what the stream has to get right: 22
    rows are six pages, which a chunk of THREE pages (no power of two:
    its waits take two sizes, and its issue group of three is one copy,
    a cache's blocks being a run) crosses once, with a last chunk the
    cursor fills page by page, and 14 rows more than `index_topk`
    keeps."""
    dec_x = _decoder()
    _indexer_interpreted(monkeypatch, pages=3)
    if attention_too:
        _interpreted(monkeypatch, chunk_bytes=3 * BS * 128 * 4)
    dec_k = _decoder()
    assert dec_k.kernels["lightning_indexer"] == "pallas:paged_scores"
    assert dec_x.kernels["lightning_indexer"] == \
        "xla:not_tpu:table_gather"
    if attention_too:
        assert dec_k.attention_tiling[0][0] == 3
    g = _weights(dec_x, seed=1)
    seqs = [SEQ[:22], SEQ[5:16]]
    drive = dict(slots=3, lanes=[0, 2], starts=[0, 4], routing=True)
    (want, _), routed_x, _ = _drive(dec_x, g, seqs, **drive)
    (got, _), routed_k, _ = _drive(dec_k, g, seqs, **drive)
    ok = REF.compare(g, CONFIG, IDS[:22], got, routed_k)
    assert ok["index_rel_err"] <= LIMITS["index_rel_err"], ok
    assert ok["selection_gap"] <= LIMITS["selection_gap"], ok
    assert all(ok[k] <= v for k, v in LIMITS.items()), ok
    a, b = routed_x["index_scores"], routed_k["index_scores"]
    assert np.array_equal(np.isneginf(a), np.isneginf(b))
    seen = np.isfinite(a)
    assert np.abs(a[seen] - b[seen]).max() <= 1e-5 * np.abs(a[seen]).max()
    assert np.array_equal(routed_x["selected"], routed_k["selected"])
    assert np.abs(want - got).max() <= 2e-5 * np.abs(want).max()


@pytest.mark.parametrize("platform,key,kv_dtype,page,reads", [
    ("cpu", DI, "fp32", BS, "xla:not_tpu:table_gather"),
    # the toy's key of 16 columns is not on the lane grid
    ("tpu", DI, "fp32", BS, "xla:lane_misaligned:table_gather"),
    ("tpu", 128, "bf16", BS, "xla:sublane_misaligned:table_gather"),
    ("tpu", 128, "bf16", 16, "pallas:paged_scores"),
    ("tpu", 128, "fp32", 8, "pallas:paged_scores"),
])
def test_the_indexers_kernel_follows_platform_and_geometry(
        platform, key, kv_dtype, page, reads):
    """Nothing but what the code can see chooses: the platform the
    decoder is built for, the key's width, the pool's dtype and the
    page's rows."""
    _, dec = build_lm_paged_decoder(
        V, page, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype=kv_dtype, platform=platform,
        block=_block(index_head_dim=key))
    assert dec.kernels["lightning_indexer"] == reads
    counts = dec.tick_counts(np.array([3, 40]), 4)
    assert ("index_dma_ops" in counts) == reads.startswith("pallas")


def test_the_ticks_index_pages_are_what_the_cursors_say(monkeypatch):
    """Cursors 3 and 40 in pages of 4 on four lanes, two selecting
    layers: the gather reads the lanes' whole tables; the kernel a page
    and 11 pages and one for each idle lane, a start a page and a wait
    for each set bit of a chunk's pages (chunks of 4: 4, 4, 3)."""
    cursors = np.array([3, 40])
    counts = _decoder().tick_counts(cursors, 4)
    assert (counts["index_pages_read"], counts["index_pages_table"]) == (
        2 * 4 * NB, 2 * 4 * NB)
    assert "index_dma_ops" not in counts
    _indexer_interpreted(monkeypatch)
    counts = _decoder().tick_counts(cursors, 4)
    assert (counts["index_pages_read"], counts["index_pages_table"],
            counts["index_dma_ops"]) == (
                2 * (1 + 11 + 2), 2 * 4 * NB,
                2 * ((1 + 1) + (11 + 1 + 1 + 2) + 2 * (1 + 1)))
    # one chunk for the table: 11 pages are waited for in three sizes
    _indexer_interpreted(monkeypatch, pages=NB)
    assert _decoder().tick_counts(cursors, 4)["index_dma_ops"] == \
        2 * ((1 + 1) + (11 + 3) + 2 * (1 + 1))
    # a windowed tick is refused for this block, and a lane at the
    # table's end reads all of it
    full = _decoder().tick_counts(np.array([NB * BS - 1] * 4), 4)
    assert full["index_pages_read"] == full["index_pages_table"]


def _walk(table, n_pages, pages, unroll):
    """DMA starts and waits a pool of a kernel that copies a lane's
    first `n_pages` pages in chunks of `pages`, walking its table page
    by page: `unroll` entries at a time, ONE start where they are
    consecutive ascending ids and `unroll` where not, a start for each
    page of a chunk past its last whole group, a wait for each set bit
    of a chunk's pages."""
    unroll, ops = min(unroll, pages), 0
    for first in range(0, n_pages, pages):
        copied = min(pages, n_pages - first)
        for g in range(copied // unroll):
            ids = table[first + g * unroll:first + (g + 1) * unroll]
            ops += 1 if all(b == ids[0] + j for j, b in enumerate(ids)) \
                else unroll
        ops += copied % unroll + bin(copied).count("1")
    return ops


@pytest.mark.parametrize("given", [True, False], ids=["tables", "none"])
def test_the_ticks_dma_ops_are_a_walk_of_the_tables(monkeypatch, given):
    """`kv_dma_ops` and `index_dma_ops` count what the two kernels'
    issue loop does over the lanes' tables (`decoder.starts_saved`, made
    once a table, looked up at the tick's cursors): a run, a shuffled
    table, a shared prefix then fresh blocks and an idle lane, in
    chunks of 5 latent pages (groups of 5) and of 6 index pages; with
    no table given every page counts a start, as before PR 56."""
    _interpreted(monkeypatch, chunk_bytes=5 * BS * 128 * 4)
    _indexer_interpreted(monkeypatch, pages=6)
    dec = _decoder()
    (pages, _), _ = dec.attention_tiling
    assert pages == 5 and paged_index_scores._ISSUE_UNROLL == 16
    r = np.random.RandomState(0)
    tables = np.zeros((4, NB), np.int32)
    tables[0] = 1 + np.arange(NB)
    tables[1] = 1 + NB + r.permutation(NB)
    tables[2, :7], tables[2, 7:] = tables[0, :7], 40 + np.arange(NB - 7)
    cursors = np.array([NB * BS - 1, 45, 58])
    saved = dec.starts_saved(tables)
    assert sorted(saved) == ["index", "table"]
    assert saved["table"][:, -1].tolist() == [4 * (NB // 5), 0, 4 * 2, 0]
    counts = dec.tick_counts(
        cursors, 4, saved={k: v[:3] for k, v in saved.items()}
        if given else None)
    if not given:
        tables = tables[:, ::-1]        # no run anywhere
    n_pages = cursors // BS + 1
    planes, index_planes = dec.kv_planes, dec.index_planes
    assert (planes, index_planes) == (L, 2)
    # the idle lane's page: a start and a wait
    assert counts["kv_dma_ops"] == planes * (2 + sum(
        _walk(tables[lane].tolist(), n, 5, 8)
        for lane, n in enumerate(n_pages)))
    assert counts["index_dma_ops"] == index_planes * (2 + sum(
        _walk(tables[lane].tolist(), n, 6, 16)
        for lane, n in enumerate(n_pages)))
    plain = dec.tick_counts(cursors, 4)
    assert (counts == plain) == (not given)
    assert all(counts[k] <= v for k, v in plain.items())


def test_fewer_rows_than_index_topk_is_dense_attention_exactly():
    """While a lane holds no more rows than `index_topk` the selection
    is every row under the cursor: the logits are those of the SAME
    weights served with no row ever dropped (`index_topk` the whole
    table), bit for bit, and differ once a row is dropped."""
    dec, all_rows = _decoder(), _decoder(index_topk=NB * BS)
    g = _weights(dec, seed=3)
    (a,), (b,) = _drive(dec, g, [SEQ]), _drive(all_rows, g, [SEQ])
    assert np.array_equal(a[:TOPK], b[:TOPK])
    assert np.abs(a[TOPK:] - b[TOPK:]).max() > 1e-3
    # and the dense reading is the reference's with nothing selected out
    want = REF.forward(g, dict(CONFIG, index_topk=NB * BS), IDS)[0]
    assert np.abs(b - np.asarray(want)).max() <= 1e-5 * np.abs(b).max()


def test_shared_layers_attend_the_earlier_full_layers_rows_and_no_others():
    """After a walk, every latent row that the selecting layer before a
    layer did NOT select for the next position is overwritten with
    large numbers (layers 0 to 3: outside layer 0's selection; layer 4:
    outside its own), and the index keys are left alone: the logits do
    not move.  Overwriting one SELECTED row of a shared layer does."""
    dec = _decoder()
    g = _weights(dec, seed=6)
    _, _, (pool_k, pool_v, tables) = _drive(dec, g, [SEQ[:-1]], routing=True)
    pos = len(SEQ) - 1
    args = (tables, np.full(1, pos, np.int32),
            np.asarray(SEQ[-1:], np.int32), np.zeros(1, np.uint32),
            np.zeros(1, np.float32), np.ones(1, bool))
    want, routing = dec.step_routing(g, pool_k, pool_v, *args)
    chosen = np.asarray(routing["selected"])[:, 0, :pos + 1]    # [2, rows]
    assert (chosen.sum(-1) == TOPK).all() and (chosen[0] != chosen[1]).any()
    rows = np.arange(pos)               # the cursor's own row is rewritten
    blk, off = np.asarray(tables)[0, rows // BS], rows % BS
    spoiled = pool_k
    for layer in range(L):
        out_ = ~chosen[0 if layer < 4 else 1][:pos]
        spoiled = spoiled.at[layer, blk[out_], off[out_]].set(1e4)
    got = dec.step_logits(g, spoiled, pool_v, *args)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # a row layer 0 selected and layer 4 did not, in a SHARED layer
    only0 = np.flatnonzero(chosen[0][:pos] & ~chosen[1][:pos])
    assert len(only0)
    r = only0[0]
    moved = dec.step_logits(
        g, pool_k.at[2, blk[r], off[r]].set(1e4), pool_v, *args)
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-3


@pytest.mark.parametrize("what", ("below",) + REF.FAULTS)
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """The toy's limits pass the decoder and refuse the same equations
    in bfloat16 and each of the nine faults, each by at least one
    number."""
    dec = _decoder()
    g = _weights(dec, seed=1)
    if what == "below":
        (got,), routing, _ = _drive(dec, g, [SEQ], routing=True)
        ok = REF.compare(g, CONFIG, IDS, got, routing)
        assert all(ok[k] <= v for k, v in LIMITS.items()), ok
        bad = REF.below(g, CONFIG, IDS)
    else:
        bad = REF.compare(g, CONFIG, IDS,
                          *REF.forward(g, CONFIG, IDS, fault=what))
    refused = [k for k, v in LIMITS.items() if not bad[k] <= v]
    assert refused, (what, bad)
    by = {"dense_attention": "selection_gap", "topk_future": "selection_gap",
          "no_relu": "index_rel_err", "no_head_weights": "index_rel_err",
          "key_unnormed": "index_rel_err", "no_index_rope": "index_rel_err",
          "shared_later": "late_rms_err",
          "not_renormalised": "router_rel_err",
          "bias_in_weight": "router_rel_err"}
    if what in by:
        assert by[what] in refused, (what, refused)


def _moe_arrays(seed, n_routed):
    r = np.random.RandomState(seed)
    u = jnp.asarray(r.normal(0, 1, (24, D)).astype(np.float32))
    whole = {"gate": r.normal(0, 0.1, (n_routed, D, F)),
             "up": r.normal(0, 0.1, (n_routed, D, F)),
             "down": r.normal(0, 0.1, (n_routed, F, D))}
    shared = {"shared_gate": r.normal(0, 0.1, (D, F)),
              "shared_up": r.normal(0, 0.1, (D, F)),
              "shared_down": r.normal(0, 0.1, (F, D))}
    as_f32 = functools.partial(jax.tree_util.tree_map,
                               lambda w: jnp.asarray(w, jnp.float32))
    return (u, as_f32(whole), as_f32(shared),
            jnp.asarray(r.normal(0, 0.3, (D, n_routed)), jnp.float32),
            jnp.asarray(r.normal(0, 0.1, (n_routed,)), jnp.float32))


def test_the_16_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The routed parts that 16 shares give (each its 2 of 32 routed
    experts, through `moe_ffn`), with the shared expert (which every
    chip computes alike) counted ONCE, are the uncut reference's sparse
    layer; 15 shares are not; each share is the reference given the
    same share."""
    n_routed, held = 32, 2
    u, whole, shared, router, bias = _moe_arrays(3, n_routed)
    own = jnp.full((u.shape[0], K), -1, jnp.int32)

    def reference(experts, first):
        out, _ = REF._moe(u, {"router": router, "bias": bias, **experts,
                              **shared}, own, jnp.asarray(2.5), top_k=K,
                          first=first)
        return np.asarray(out)

    everyone = np.asarray(lm_block.swiglu(u, *(
        shared[n] for n in ("shared_gate", "shared_up", "shared_down"))))
    assert np.abs(everyone).max() > 1e-2
    parts = []
    for first in range(0, n_routed, held):
        cut = {n: w[first:first + held] for n, w in whole.items()}
        y, hit, _ = lm_block.moe_ffn(
            _block(n_experts=n_routed, experts_first=first,
                   experts_held=held), u, router, cut["gate"], cut["up"],
            cut["down"], b_router=bias)
        assert 0 <= int(hit) <= held
        np.testing.assert_allclose(np.asarray(y) + everyone,
                                   reference(cut, first), atol=5e-5)
        parts.append(np.asarray(y))
    want = reference(whole, 0)
    np.testing.assert_allclose(sum(parts) + everyone, want, atol=5e-5)
    assert np.abs(sum(parts[:15]) + everyone - want).max() > 1e-3
    # counted on every chip, the shared expert would be there 16 times
    assert np.abs(sum(parts) + 16 * everyone - want).max() > 1e-1


@pytest.mark.parametrize("interpreted", [False, True])
def test_lanes_out_of_step_bit_identical_to_the_same_sequence_alone(
        interpreted, monkeypatch):
    """A sequence beside two others that started at other ticks, in
    another lane and other blocks, reads the logits it reads alone (at
    the same lane count: the CPU's gemm tiles by batch).  Interpreted
    (a second a tick), 14 rows: four pages in chunks of THREE (no power
    of two; a cache's blocks are a run, so a chunk's group is one
    copy), the second chunk entered two rows deep and six rows past
    `index_topk`."""
    if interpreted:
        _interpreted(monkeypatch, chunk_bytes=3 * BS * 128 * 4)
    dec = _decoder()
    g = _weights(dec, seed=8)
    n = 14 if interpreted else len(SEQ)
    others = [list(np.random.RandomState(s).randint(0, V, m))
              for s, m in ((11, 13), (12, n - 4))]
    (alone,) = _drive(dec, g, [SEQ[:n]], slots=3, lanes=[1])
    together = _drive(dec, g, [SEQ[:n]] + others, slots=3, lanes=[1, 0, 2],
                      starts=[3, 0, 5])
    assert np.array_equal(alone, together[0])


def test_description_is_checked_and_laid_out():
    dec = _decoder()
    shapes = dec.state_shapes
    for l, kind in enumerate(INDEXERS):
        has = f"layer_{l}.indexer_q.w_0" in shapes
        assert has == (kind == "full"), l
    assert shapes["layer_0.indexer_q.w_0"] == (QL, HI * DI)
    assert shapes["layer_4.indexer_k.w_0"] == (D, DI)
    assert shapes["layer_4.indexer_k_norm.shift_0"] == (DI,)
    assert shapes["layer_0.indexer_w.w_0"] == (D, HI)
    assert shapes["layer_0.kv_b_proj.w_0"] == (KVL, H * (DN + DV))
    assert shapes["layer_0.o_proj.w_0"] == (H * DV, D)
    assert "layer_0.router.w_0" not in shapes           # the dense layer
    assert shapes["layer_1.router.w_0"] == (D, E)
    assert shapes["layer_1.experts_gate.w_0"] == (HELD, D, F)
    spec = _block()
    assert spec.sparse and spec.latent
    assert [spec.indexer_of(l) for l in range(L)] == INDEXERS
    with pytest.raises(NotImplementedError, match="lightning indexer"):
        _block(kv_lora_rank=0, q_lora_rank=0)
    with pytest.raises(NotImplementedError, match="starts 'full'"):
        _block(indexer_types=["shared", "full"])
    with pytest.raises(ValueError, match="indexer_types"):
        _block(indexer_types=["full", "windowed"])
    # "none" is a sliding layer's kind (lm_block's thirteenth
    # description), and this block has no sliding layer
    with pytest.raises(ValueError, match="a sliding layer's"):
        _decoder(indexer_types=["full", "none", "none", "none", "full"])
    with pytest.raises(ValueError, match="index_topk is 0"):
        _block(index_topk=0)
    with pytest.raises(ValueError, match="indexer_types, and a layer"):
        _decoder(indexer_types=["full", "shared"])
    with pytest.raises(NotImplementedError, match="int8"):
        _decoder("int8")


def test_select_rows_is_the_k_largest_with_ties_to_the_lower_row():
    # a shape is a compile of the search and of the reference's top-k:
    # seven (a row, fewer rows than k, k of 1, the toy's 8), each met
    # with ties, with all-zero scores and with masked rows
    shapes = [(1, 4), (5, 11), (12, 3), (16, 7), (24, 11), (29, 8), (39, 1)]
    r = np.random.RandomState(0)
    for trial in range(60):
        rows, k = shapes[trial % len(shapes)]
        scores = r.normal(size=(3, rows)).astype(np.float32)
        if trial % 3 == 0:
            scores = np.round(scores)           # ties
        if trial % 5 == 0:
            scores[:] = 0.0
        valid = r.rand(3, rows) < 0.7
        got = np.asarray(lm_block.select_rows(
            jnp.asarray(scores), jnp.asarray(valid), k))
        ref = np.asarray(REF.top_rows(jnp.asarray(scores),
                                      jnp.asarray(valid), k))
        for b in range(3):
            order = sorted(np.flatnonzero(valid[b]),
                           key=lambda i: (-scores[b, i], i))
            want = np.zeros(rows, bool)
            want[order[:k]] = True
            assert (got[b] == want).all() and (ref[b] == want).all(), trial


def _served(dec, g, prompts, n_new, **kw):
    srv = GenerationServer(dec, g, place=fluid.CPUPlace(), **kw)
    try:
        streams = [srv.submit(p, n_new) for p in prompts]
        return [s.result(timeout=300) for s in streams], srv.stats()
    finally:
        srv.close()


def test_generation_server_serves_the_block_and_refuses_by_name():
    """Requests through `GenerationServer`, continuously batched, give
    the tokens of the same request alone; the tick spans carry the
    indexer's counts; a draft model and `step_window` are refused by
    name, the prefix cache is not."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    assert set(dec.refuses) == {"draft_model"}
    assert "lightning indexer" in dec.refuses["draft_model"]
    with pytest.raises(ValueError, match="no draft model"):
        GenerationServer(dec, g, slots=2, kv_blocks=2 * NB,
                         place=fluid.CPUPlace(), draft_decoder=dec,
                         draft_states=g)
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(1, np.int32)
    with pytest.raises(NotImplementedError, match="step_window"):
        dec.step_window(_weights(dec), pool_k, pool_v,
                        np.zeros((1, NB), np.int32), z,
                        np.zeros((1, 2), np.int32), z.astype(np.uint32),
                        z.astype(np.float32), z)
    prompts = [list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 5), (2, 23), (3, 3))]
    want = [_served(dec, g, [p], 30, slots=1, kv_blocks=NB,
                    prefix_cache=False)[0][0] for p in prompts]
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        got, stats = _served(dec, g, prompts, 30, slots=2,
                             kv_blocks=2 * NB, prefix_cache=True)
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    assert got == want and stats["decode_kernel"] == "xla:not_tpu"
    assert dec.kernels["index_selection"] == "passes:not_tpu"
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(
        "index_planes" not in a and a["moe_layers"] == 4
        and a["select_kernel"] == 0
        and a["kv_rows_indexed"] * 5 == a["latent_rows"] * 2
        and 0 < a["kv_rows_selected"] <= a["latent_rows"] for a in ticks)
    assert any(a["kv_rows_selected"] < a["latent_rows"] for a in ticks)
    # two lanes past the eighth row: 8 rows a lane a plane
    assert max(a["kv_rows_selected"] for a in ticks) == 2 * L * TOPK
    done = [s["attrs"] for s in spans if s["name"] == "serving.request"]
    assert len(done) == 3 and all(
        a["prefix_hit_tokens"] == a["cached_tokens"] <= a["prompt_tokens"]
        for a in done)


def test_the_selection_kernel_in_the_interpreter_selects_what_the_passes_do(
        monkeypatch):
    """`kernels/select_rows.py` under the interpreter through a whole
    decoder, on a table of 128 rows (the kernel takes whole 128-lane
    tiles: the toy's 64 rows are refused by name even there): the path
    is named once a step is traced, the selection and the logits are
    the passes' bit for bit over lanes out of step (an idle lane sees
    row 0), and a server's tick spans carry `select_kernel` 1."""
    def decoder(nb):
        return build_lm_paged_decoder(
            V, BS, nb, d_model=D, n_heads=H, n_layers=L, d_inner=F,
            kv_dtype="fp32", platform="cpu", block=_block())[1]

    plain = decoder(32)
    assert "index_selection" not in plain.kernels   # a step names it
    assert "select_kernel" not in plain.tick_counts(np.array([3]), 2)
    g = _weights(plain, seed=2)
    seqs = [SEQ[:22], SEQ[5:16]]
    drive = dict(slots=3, lanes=[0, 2], starts=[0, 4], routing=True, nb=32)
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
    assert np.array_equal(routed_x["selected"], routed_k["selected"])
    assert routed_k["selected"].sum(-1).max() == TOPK
    assert np.array_equal(want, got)
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        served, _ = _served(dec, {n: np.asarray(v) for n, v in g.items()},
                            [SEQ[:11]], 4, slots=2, kv_blocks=64)
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(a["select_kernel"] == 1 for a in ticks)
    assert len(served[0]) == 4


def test_a_prefix_hit_reads_latent_rows_and_index_keys_from_shared_blocks():
    """A cached block holds every plane's latent rows AND both index
    planes' keys: requests that share a long prefix (past `index_topk`:
    their first own position already selects among cached index keys)
    give the tokens of the unshared run, and the later ones start at the
    prefix's end."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec, seed=5).items()}
    prefix = list(np.random.RandomState(9).randint(0, V, 7 * BS))
    prompts = [prefix + list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 3), (2, 6), (3, 2))]
    out, spans = {}, []
    for cached in (False, True):
        if cached:
            tracing.add_span_listener(spans.append)
        try:
            srv = GenerationServer(dec, g, slots=2, kv_blocks=3 * NB,
                                   place=fluid.CPUPlace(),
                                   prefix_cache=cached)
            try:
                out[cached] = [srv.generate(p, 12) for p in prompts]
                hits = srv.stats()["prefix_hits"]
            finally:
                srv.close()
        finally:
            tracing.remove_span_listener(spans.append)
            # (a listener sees a tick before its account is made: a reader's)
            tracing.finished_spans()
    assert out[True] == out[False] and hits >= 2 * 7
    done = [s["attrs"] for s in spans if s["name"] == "serving.request"]
    assert sorted(a["prefix_hit_tokens"] for a in done) == [0, 28, 28]


def test_a_hit_gives_the_logits_of_the_same_request_prefilled():
    """The decoder alone: request B walked from position 0 in lane 1,
    against B started at the prefix's end on a table whose first seven
    blocks are the ones request A (lane 0) filled: the same logits bit
    for bit, at every position past the prefix."""
    dec = _decoder()
    g = _weights(dec, seed=5)
    r = np.random.RandomState(9)
    prefix = list(r.randint(0, V, 7 * BS))
    a, b = (prefix + list(r.randint(0, V, n)) for n in (5, 9))
    zs, zt = np.zeros(2, np.uint32), np.zeros(2, np.float32)

    def walk(tables, start_b):
        pool_k, pool_v = dec.init_pool(1 + 2 * NB)
        out = []
        for tick in range(len(a) + len(b) - start_b):
            in_a = tick < len(a)
            pos_b = start_b + tick - len(a)
            pos = np.asarray([tick if in_a else 0, max(pos_b, 0)], np.int32)
            act = np.asarray([in_a, not in_a])
            toks = np.asarray([a[tick] if in_a else 0,
                               b[pos[1]]], np.int32)
            args = (g, pool_k, pool_v, tables, pos, toks, zs, zt, act)
            if not in_a:
                out.append(np.asarray(dec.step_logits(*args))[1])
            _, pool_k, pool_v, *_ = dec.step(*args)
        return np.stack(out)

    own = np.zeros((2, NB), np.int32)
    own[0, :8], own[1, :10] = 1 + np.arange(8), 9 + np.arange(10)
    shared = own.copy()
    shared[1, :7] = own[0, :7]
    whole = walk(own, 0)
    hit = walk(shared, len(prefix))
    assert np.array_equal(whole[len(prefix):], hit)


def test_served_tokens_are_judged_by_the_reference_alone():
    """`served` knows only the tokens a server delivered: greedy
    requests agree with the reference's argmax, requests of other
    lengths share one padded forward, and a fault reads the same tokens
    as disagreeing."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    prompts = [list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((7, 14), (8, 29), (9, 21))]
    got, _ = _served(dec, g, prompts, 24, slots=2, kv_blocks=2 * NB,
                     prefix_cache=False)
    requests = [(np.asarray(p + t, np.int32), len(p))
                for p, t in zip(prompts, got)]
    out = REF.served(g, CONFIG, requests)
    assert out["tokens"] == 72 and 0.6 < out["rows_dropped_share"] < 0.85
    assert out["served_argmax_agree"] == 1.0 and out["served_gap_rms"] == 0.0
    wrong = REF.served(g, CONFIG, requests, fault="dense_attention")
    assert wrong["served_argmax_agree"] < 0.9, wrong
    assert wrong["served_gap_rms"] > 1e-3


def test_scopes_name_the_indexer_and_the_selected_rows_attention():
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("indexer_q", "indexer_k", "indexer_scores", "indexer_topk",
                 "attention/selected", "latent_q", "latent_kv",
                 "latent_absorb", "attn_out", "dense_ffn", "moe_router",
                 "moe_experts", "shared_expert", "kv_write", "head"):
        assert f"paged_decoder/{part}" in text, part
    scopes = dec.compiler_scopes
    assert scopes["g[\\'layer_0.indexer_q.w_0\\']"] == \
        "paged_decoder/indexer_q"
    assert scopes["g[\\'layer_4.indexer_k.w_0\\']"] == \
        "paged_decoder/indexer_k"
    assert scopes["g[\\'layer_0.indexer_w.w_0\\']"] == \
        "paged_decoder/indexer_q"
    assert "g[\\'layer_1.indexer_q.w_0\\']" not in scopes
    assert scopes["g[\\'layer_2.kv_b_proj.w_0\\']"] == \
        "paged_decoder/latent_absorb"
    counts = dec.tick_counts(np.array([3, 40]), 4)
    assert (dec.index_planes, counts["kv_rows_indexed"],
            counts["kv_rows_selected"], counts["latent_rows"]) == (
                2, 2 * 45, 5 * (4 + TOPK), 5 * 45)
    # a latent block without an indexer names none of it
    plain = _decoder(index_n_heads=0, index_head_dim=0, index_topk=0,
                     indexer_types=[])
    assert "paged_attention_selected" not in plain.kernels
    assert "lightning_indexer" not in plain.kernels
    counts = plain.tick_counts(np.array([3]), 2)
    assert "kv_rows_selected" not in counts
    assert not {"index_pages_read", "index_pages_table",
                "index_dma_ops"} & set(counts)
    assert plain.init_pool(3)[1] == () and plain.index_planes == 0


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/glm-5.2-1chip.json's `block`, read as the
    benchmark's job reads it, lays out the decoder at the published
    widths; its cut's arithmetic is recomputed from the shapes; every
    number of the catalog's row is there under its own key but for the
    keys listed as reduced."""
    m = _json("perf", "configs", "glm-5.2-1chip.json")
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert spec.sparse and spec.held == (0, 16) and spec.n_experts == 256
    assert [spec.indexer_of(l) for l in range(5)] == INDEXERS
    assert [spec.ffn_of(l) for l in range(5)] == MLPS
    _, shapes = lm_block.param_layout(
        spec, m["vocab_size"], m["hidden_size"], m["num_attention_heads"],
        m["num_hidden_layers"], m[b["d_inner"]])
    count = lambda pre: sum(int(np.prod(s)) for n, s in shapes.items()
                            if n.startswith(pre))
    attention = sum(int(np.prod(shapes[f"layer_1.{n}"])) for n in (
        "q_a_proj.w_0", "q_b_proj.w_0", "kv_a_proj.w_0", "kv_b_proj.w_0",
        "o_proj.w_0"))
    indexer = sum(int(np.prod(s)) for n, s in shapes.items()
                  if n.startswith("layer_0.indexer"))
    numbers = m["cut"]["arithmetic_numbers"]
    near = lambda got, want: abs(got / 1e6 - want) <= 0.06
    assert near(attention, numbers["attention_m"])
    assert near(indexer, numbers["indexer_m"])
    assert near(count("layer_0."), numbers["dense_layer_m"])
    assert near(count("layer_1."), numbers["sparse_layer_m"])
    assert near(count("layer_4.") - count("layer_1."), numbers["indexer_m"])
    assert near(count("tok_embedding") + count("lm_head"),
                numbers["embedding_and_head_m"])
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert abs(2 * total / 1e9 - numbers["weights_gb"]) < 0.01
    assert f'{numbers["weights_gb"]} GB' in m["cut"]["arithmetic"]
    t = _json("perf", "traffic", "docqa64.json")
    _, dec = build_lm_paged_decoder(
        64, t["block_size"], t["context"] // t["block_size"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=5, d_inner=m[b["d_inner"]], kv_dtype=t["kv_dtype"],
        platform="cpu", block=spec)
    assert dec.bytes_per_block == numbers["cache_bytes_a_position"] * 16
    assert 1.0e9 < dec.bytes_per_block * t["pool_blocks"] < 1.05e9
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "GLM-5.2")
    assert m["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)
               and m.get(k) != v}
    assert changed == set(m["reduced"]) == set(m["published"])
    assert all(m["published"][k] == row["config"][k] for k in changed)
    assert m["indexer_types"] == row["config"]["indexer_types"][2:7]
    assert m["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7]
    assert m["rope_parameters"] == row["config"]["rope_parameters"]
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "glm-5.2-1chip")
    assert entry["reduced"] == m["reduced"]
    assert entry["source"] == m["source"]
    assert set(REF.FAULTS) <= set(" ".join(
        m["assumed"].values()).replace("`", " ").replace(",", " ").split())


def test_traffic_file_tables_are_the_quantiles_they_say():
    from statistics import NormalDist

    t = _json("perf", "traffic", "docqa64.json")
    inv = NormalDist().inv_cdf

    def quantiles(median, sigma, n, lo, hi, grid=1):
        return [int(round(min(max(median * np.exp(
            sigma * inv((i + 0.5) / n)), lo), hi) / grid)) * grid
            for i in range(n)]

    docs = t["documents"]["lengths"]
    assert docs == quantiles(4608, 0.25, 16, 3072, 6144, 16)
    assert not t["documents"]["fallback_taken"]
    table = [tuple(r) for r in t["lengths"]["table"]]
    questions = quantiles(64, 0.4, 64, 32, 128)
    answers = quantiles(256, 0.4, 64, 128, 512)
    assert sorted(table) == sorted(
        (questions[i], answers[(37 * i) % 64]) for i in range(64))
    size = t["lengths"]["block"]
    sums = [(sum(q for q, _ in table[i:i + size]),
             sum(a for _, a in table[i:i + size]))
            for i in range(0, 64, size)]
    assert max(q for q, _ in sums) <= 1.12 * min(q for q, _ in sums)
    assert max(a for _, a in sums) <= 1.08 * min(a for _, a in sums)
    assert (t["clients"], t["slots"], t["block_size"], t["context"],
            t["pool_blocks"], t["prefix_cache"], t["kv_dtype"],
            t["temperature"], t["eos_id"], t["ramp_seconds"]) == (
                64, 64, 16, 6912, 9216, True, "bf16", 0.0, None, 20)
    assert max(docs) + 128 + 512 <= t["context"]
    assert t["job"] == "serve_lm_docqa" and t["correct_tokens"] == 2560
    # the cached documents and 64 requests' own blocks fit the pool
    own = -(-(128 + 512) // 16) + 1
    assert sum(docs) // 16 + 64 * own <= t["pool_blocks"]
    cell = next(w for w in _json("BENCHMARK.json")["workloads"]
                if w["name"] == "glm-5.2-serve-docqa64")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5.2-1chip", "docqa64", 1)


def test_the_cost_functions_and_the_readers_on_a_synthetic_run(monkeypatch):
    """`perf/sparse_attention_cost.py` at the published widths, and the
    six new readers on a `Run` made by hand: tick spans with the
    indexer's counts, request spans with prefix hits, a scope table."""
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        import common
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    cost = _load(os.path.join(ROOT, "perf", "sparse_attention_cost.py"),
                 "sparse_attention_cost")
    assert cost.stored_row_bytes(512, 64) == 1280
    assert cost.attention_call(10, 512, 64) == {"bytes": 12800.0}
    assert cost.indexer_call(10, 32, 128) == {"bytes": 2560.0,
                                              "flops": 81920.0}
    metrics = os.path.join(ROOT, "perf", "metrics")
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    readers = {n: common.load_module(os.path.join(metrics, n + ".py"))
               for n in ("serve_sparse_attention_roofline",
                         "serve_indexer_roofline", "serve_indexer_share",
                         "serve_index_topk_share",
                         "sched_kv_rows_selected_share",
                         "sched_prefix_hit_share")}
    ticks = [{"name": "serving.decode_tick", "ts": 10.0 + i, "dur": 0.5,
              "attrs": {"latent_rows": 1000, "kv_rows_selected": 400,
                        "kv_rows_indexed": 400}} for i in range(4)]
    done = [{"name": "serving.request", "ts": 10.5, "dur": 2.0,
             "attrs": {"prompt_tokens": 500, "prefix_hit_tokens": 480}},
            {"name": "serving.request", "ts": 11.0, "dur": 1.0,
             "attrs": {"prompt_tokens": 300, "prefix_hit_tokens": 288}}]
    from paddle_tpu import profiler

    monkeypatch.setattr(tracing, "finished_spans", lambda: ticks + done)
    by_scope = {"paged_decoder/attention/selected": 2e-6,
                "paged_decoder/indexer_scores": 1.5e-6,
                "paged_decoder/indexer_topk": 0.5e-6,
                "paged_decoder/dense_ffn": 6e-6}
    monkeypatch.setattr(
        profiler, "scope_seconds",
        lambda ops, label, inherited_only=False:
            {} if inherited_only else dict(by_scope))
    run = common.Run()
    run.spans = [{"name": "x", "ts": 9.0, "dur": 0.1},
                 {"name": "x", "ts": 14.0, "dur": 0.1}]
    run.trace = {"op_seconds": {}}
    run.notes["trace_slice_wall"] = (10.0, 12.0)    # ticks 0 and 1
    run.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    run.cell = types.SimpleNamespace(
        config=_json("perf", "configs", "glm-5.2-1chip.json"),
        traffic=_json("perf", "traffic", "docqa64.json"))
    got = {n: r.compute(run) for n, r in readers.items()}
    assert got["sched_kv_rows_selected_share"] == pytest.approx(40.0)
    assert got["sched_prefix_hit_share"] == pytest.approx(96.0)
    assert got["serve_indexer_share"] == pytest.approx(20.0)
    assert got["serve_index_topk_share"] == pytest.approx(5.0)
    assert got["serve_sparse_attention_roofline"] == pytest.approx(
        100 * 800 * 1280 / 819e9 / 2e-6)
    assert got["serve_indexer_roofline"] == pytest.approx(
        100 * 800 * 256 / 819e9 / 2e-6)
    assert all(0 < v < 100 for v in got.values())
    # a program without the counts or the scopes: nothing, and no error
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        dict(s, attrs={"latent_rows": 1000, "prompt_tokens": 5})
        for s in ticks + done])
    by_scope = {"paged_decoder/attention": 2e-6,
                "paged_decoder/dense_ffn": 6e-6}
    assert {n: r.compute(run) for n, r in readers.items()} == dict.fromkeys(
        readers)
    bench = _json("BENCHMARK.json")
    for spec in bench["per_layer"]:
        if spec["name"] in readers:
            mod = readers[spec["name"]]
            assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
                spec["layer"], spec["unit"], spec["moves"], spec["source"])
            # the prefix cache's reader is also the cell's that PR 59
            # added (a lane state under snapshots)
            # (and the cell PR 65 added: latent rings beside a selected
            # table, whose full layers have the indexer; and, for the
            # prefix cache's reader, the cell PR 69 added: a state AND a
            # table plane a layer)
            hits = spec["name"] == "sched_prefix_hit_share"
            assert spec["workloads"] == ["glm-5.2-serve-docqa64"] + (
                ["solar-open2-250b-serve-docqa64"] if hits else []) + [
                    "dots3-note-prev-serve-docqa64"] + (
                ["falcon-h1-34b-serve-docqa64"] if hits else [])


def test_the_index_dma_ops_reader_on_a_synthetic_run(monkeypatch):
    """`sched_index_dma_ops_per_page` on a `Run` made by hand: the
    window's ticks that carry the index kernel's DMA count, over the
    pages they read; ticks outside it, and those of a program without
    the count (the gather path, a parent before PR 54), left out."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    import common

    reader = common.load_module(os.path.join(
        ROOT, "perf", "metrics", "sched_index_dma_ops_per_page.py"))

    def tick(ts, **attrs):
        return {"name": "serving.decode_tick", "ts": ts, "dur": 0.5,
                "attrs": attrs}

    ticks = [tick(5.0, index_pages_read=100, index_dma_ops=900),
             tick(10.0, index_pages_read=600, index_dma_ops=90),
             tick(11.0, index_pages_read=400, index_dma_ops=60),
             tick(12.0, index_pages_read=5000, index_pages_table=5000),
             tick(13.0, kv_pages_read=5, kv_dma_ops=10),
             tick(20.0, index_pages_read=100, index_dma_ops=900)]
    monkeypatch.setattr(tracing, "finished_spans", lambda: ticks)
    run = common.Run()
    run.spans = [{"name": "x", "ts": 9.0, "dur": 0.1},
                 {"name": "x", "ts": 14.0, "dur": 0.1}]
    assert reader.compute(run) == pytest.approx(0.15)
    monkeypatch.setattr(tracing, "finished_spans", lambda: ticks[3:5])
    assert reader.compute(run) is None
    run.spans = []
    assert reader.compute(run) is None
    spec, = (m for m in _json("BENCHMARK.json")["per_layer"]
             if m["name"] == "sched_index_dma_ops_per_page")
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        spec["layer"], spec["unit"], spec["moves"], spec["source"])
    assert spec["better"] == "lower"
    assert spec["workloads"] == ["glm-5.2-serve-docqa64",
                                 "dots3-note-prev-serve-docqa64"]


def test_the_index_pages_reader_on_a_synthetic_run(monkeypatch):
    """`sched_index_pages_read_share` on a `Run` made by hand: the
    window's ticks with the indexer's page counts, those outside it and
    those of a program without the counts left out."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    import common

    reader = common.load_module(os.path.join(
        ROOT, "perf", "metrics", "sched_index_pages_read_share.py"))

    def tick(ts, **attrs):
        return {"name": "serving.decode_tick", "ts": ts, "dur": 0.5,
                "attrs": attrs}

    ticks = [tick(5.0, index_pages_read=1, index_pages_table=1000),
             tick(10.0, index_pages_read=600, index_pages_table=1000),
             tick(11.0, index_pages_read=800, index_pages_table=1000),
             tick(12.0, kv_pages_read=5, kv_pages_table=10),
             {"name": "serving.request", "ts": 10.0, "dur": 1.0,
              "attrs": {"index_pages_read": 7, "index_pages_table": 7}},
             tick(20.0, index_pages_read=1, index_pages_table=1000)]
    monkeypatch.setattr(tracing, "finished_spans", lambda: ticks)
    run = common.Run()
    run.spans = [{"name": "x", "ts": 9.0, "dur": 0.1},
                 {"name": "x", "ts": 14.0, "dur": 0.1}]
    assert reader.compute(run) == pytest.approx(70.0)
    # a program that sets no such attribute (the parent's), and a run
    # with no span store: nothing, and no error
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        tick(10.0, kv_pages_read=5, kv_pages_table=10,
             kv_rows_indexed=400)])
    assert reader.compute(run) is None
    run.spans = []
    assert reader.compute(run) is None
    spec, = (m for m in _json("BENCHMARK.json")["per_layer"]
             if m["name"] == "sched_index_pages_read_share")
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        spec["layer"], spec["unit"], spec["moves"], spec["source"])
    assert spec["better"] == "lower"
    assert spec["workloads"] == ["glm-5.2-serve-docqa64",
                                 "dots3-note-prev-serve-docqa64"]
