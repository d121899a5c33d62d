"""PR 67: a traced tick's account is made by whoever reads its span
(`generation._TickAccount`, `tracing.Span.defer`), and holds the bytes
the tick must move (`decoder.tick_counts`: `step_bytes_weights`,
`step_bytes_cache`, `expert_bytes`), over the thirteen served blocks'
toys: each configuration's file under its own `rehearse` overlay, as
the benchmark's jobs build them."""
import importlib.util
import math
import os
import types

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving import generation

import test_paged_attention as paged

_TOYS = paged._TOYS + ["solar-open2-250b-1chip", "ling-3.0-flash-1chip",
                       "dots3-note-prev-1chip"]
_BYTES = ("step_bytes_weights", "step_bytes_cache", "expert_bytes")
# the scheduler's own attributes, eager on the span
_EAGER = {"active", "prefill", "kv_used", "kv_total", "kv_wait", "ahead"}
SLOTS, BS, NB = 3, 4, 6


@pytest.fixture(autouse=True)
def _fresh():
    def reset():
        tracing.set_enabled(False)
        del tracing._listeners[:]
        tracing.clear()

    reset()
    yield
    reset()


def _served_toy(name):
    """-> (decoder, states): the toy of configuration `name` with every
    paged kernel it can run SELECTED (so that `tick_counts` walks the
    page streams and `starts_saved` has rows), and a `step` that runs
    none of it: zeros for the tokens and for each of `step_counters`.
    The account is what is under test, not the step."""
    dec, _ = paged._toy_decoder(name, BS, NB)
    assert dec.attention_tiling is not None

    def step(states, pool_k, pool_v, tables, positions, tokens, seeds,
             temps, active):
        return (np.zeros(len(positions), np.int32), pool_k, pool_v,
                *(np.full(max(dec.moe_layers, 1), 2, np.int32)
                  for _ in dec.step_counters))

    dec.step = step
    dec.weight_itemsize = 2         # what a traced bfloat16 step notes
    states = {n: np.zeros(shape, np.float32)
              for n, shape in dec.state_shapes.items()}
    return dec, states


@pytest.mark.parametrize("name", _TOYS)
def test_a_read_span_holds_what_tick_counts_says_eagerly(name):
    """The attributes a reader finds on a live server's
    `serving.decode_tick` spans are, tick for tick and name for name,
    what `decoder.tick_counts` returns when called EAGERLY, as each tick
    is built, on the same cursors with `starts_saved`'s rows of the
    lanes' tables as they then stood; though the reader comes after
    the last request has gone, with every slot given twice over and
    `_tables` rewritten by each admission and eviction in between."""
    dec, states = _served_toy(name)
    srv = GenerationServer(dec, states, slots=SLOTS, kv_blocks=SLOTS * NB,
                           place=fluid.CPUPlace(), prefix_cache=False)
    assert not hasattr(srv, "_saved") and not hasattr(srv, "_saved_stale")
    rings = (np.asarray(dec.slot_rings(SLOTS)) if dec.ring_layers
             else None)
    want, tick = [], srv._tick

    def eager(seqs):
        lanes = np.array(sorted(s.slot for s in seqs))
        cur = np.array([srv._active[i].cur for i in lanes], np.int32)
        saved = dec.starts_saved(
            srv._tables[lanes].copy(),
            None if rings is None else rings[lanes])
        want.append(dec.tick_counts(cur, SLOTS, saved=saved))
        return tick(seqs)

    srv._tick = eager
    requests = [([3, 1, 4, 1, 5], 9), ([2, 7], 6), ([6], 3),
                ([1, 8, 2, 8, 1, 8, 2], 11), ([4, 4], 2), ([5, 9, 2], 7),
                ([3], 13)]
    made_by = []
    tracing.set_enabled(True)
    try:
        for s in [srv.submit(p, m) for p, m in requests]:
            s.result(timeout=120)
        assert not srv._tables.any()            # every row rewritten
        pending = [s for s in tracing._spans
                   if s["name"] == "serving.decode_tick"]
        # nothing of the decoder's on a record nobody has read
        assert pending and all(
            set(s["attrs"]) <= _EAGER | set(dec.step_counters)
            and "deferred" in s for s in pending)
        counts = dec.tick_counts
        dec.tick_counts = lambda *a, **k: (
            made_by.append(generation.threading.get_ident()),
            counts(*a, **k))[1]
        ticks = [s for s in tracing.finished_spans()
                 if s["name"] == "serving.decode_tick"]
    finally:
        srv.close()
    assert len(ticks) == len(want) >= 20
    # made by this thread, the reader's, not the scheduler's
    assert set(made_by) == {generation.threading.get_ident()}
    assert {s["tid"] for s in ticks}.isdisjoint(made_by)
    own = _EAGER | set(dec.step_counters)
    for span, counted in zip(ticks, want):
        assert {k: v for k, v in span["attrs"].items()
                if k not in own} == counted
        assert set(_BYTES) <= set(counted)
        assert all(type(v) is int for v in span["attrs"].values())
    assert "index_planes" not in ticks[0]["attrs"]
    assert (tracing.dropped_deferred(), tracing.failed_deferred()) == (0, 0)
    assert any(a["attrs"]["prefill"] for a in ticks)
    assert len({a["attrs"]["active"] for a in ticks}) > 1


def _config(name):
    import json

    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "configs", name + ".json")) as f:
        m = json.load(f)
    m.update(m["rehearse"])
    return m


@pytest.mark.parametrize("name", _TOYS)
def test_the_three_byte_counts_are_sums_over_the_blocks_shapes(name):
    """`step_bytes_weights`, `step_bytes_cache` and `expert_bytes`
    against sums written out from the block's shapes by NAME (the
    decoder walks its layout): every parameter but the routed experts'
    three stacks, the embedding (unless the head is the table) and a
    position table, a looped stack's layers once a pass; the pages the
    cursors reach at each pool's own page, the lanes' float32 states
    and tails twice, a row a lane a plane; one expert's slice of the
    three stacks.  And nothing before a step is traced, nor on a
    `step_window` tick."""
    dec, _ = paged._toy_decoder(name, BS, NB)
    m = _config(name)
    cursors = np.array([0, 5, 17], np.int32)
    assert dec.weight_itemsize is None
    assert not set(_BYTES) & set(dec.tick_counts(cursors, 4))
    dec.weight_itemsize = 2
    got = dec.tick_counts(cursors, 4)
    assert not set(_BYTES) & set(dec.tick_counts(cursors, 4, windowed=True))
    shapes = dec.state_shapes
    tied = "lm_head.w_0" not in shapes and name != "opt-1.3b"
    routed = [n for n in shapes if ".experts_" in n]
    if name == "opt-1.3b":
        # OPT's names are the training Program's: the two tables are
        # its only parameters with a row a token and a row a position
        rows_of = [n for n, s in shapes.items()
                   if len(s) == 2 and s[1] == m["hidden_size"]
                   and s[0] in (m["vocab_size"], dec.max_len)]
        assert len(rows_of) == 2
    else:
        rows_of = [] if tied else ["tok_embedding.w_0"]
    in_stack = [n for n in shapes if n.startswith("layer_")
                and n not in routed]
    outside = [n for n in shapes
               if n not in routed + rows_of + in_stack]
    elems = (dec.passes * sum(math.prod(shapes[n]) for n in in_stack)
             + sum(math.prod(shapes[n]) for n in outside))
    assert got["step_bytes_weights"] == 2 * elems
    if name != "opt-1.3b":      # (whose names say no layer; one pass)
        assert {n.split(".")[0] for n in outside} <= {
            "final_norm", "lm_head", "exit_gate", "tok_embedding"}
    if routed:
        one = sorted(n for n in routed
                     if n.startswith(routed[0].split(".")[0] + "."))
        assert len(one) == 3
        assert got["expert_bytes"] == 2 * sum(
            math.prod(shapes[n][1:]) for n in one)
        assert got["expert_bytes"] > 0
    else:
        assert got["expert_bytes"] == 0

    # the cache's: fp32 pools here (4 bytes an element)
    latent = dec.kernels["paged_attention_decode"].endswith("latent")
    index_page = 0
    if dec.index_planes:
        index_page = BS * 4 * m["index_head_dim"]
    table_page = (dec.bytes_per_block
                  - dec.index_planes * index_page) // dec.table_layers
    ring_page = (dec.window_bytes_per_block // dec.ring_layers
                 if dec.ring_layers else 0)
    rows = cursors.astype(np.int64) + 1
    pages = -(-rows // BS)
    ring_rows = np.minimum(rows, dec.window_blocks_per_seq * BS)
    # a page a plane for the one idle lane of the four
    want = table_page * dec.table_layers * (pages.sum() + 1)
    if dec.ring_layers and latent:
        assert got["ring_bytes"] == (
            ring_rows.sum() * dec.window_bytes_per_block // BS)
        want += got["ring_bytes"]
    elif dec.ring_layers:
        want += ring_page * dec.ring_layers * (
            (-(-ring_rows // BS)).sum() + 1)
    want += index_page * dec.index_planes * (pages.sum() + 1)
    if dec.state_layers and not dec.ring_layers:
        moved = 2 * 3 * dec.state_bytes_per_lane
        assert moved == got.get("state_bytes", got.get(
            "conv_tail_bytes", moved))
        want += moved
    want += 3 * (dec.bytes_per_block + dec.window_bytes_per_block) // BS
    assert got["step_bytes_cache"] == want
    assert got["kv_pages_read"] == (
        dec.table_layers * (pages.sum() + 1) + (
            dec.ring_layers * ((-(-ring_rows // BS)).sum() + 1)))
    assert all(type(got[k]) is int for k in _BYTES)


def test_the_byte_counts_at_hand_written_sizes():
    """OPT's toy by hand: 2 layers at width 32 and FFN 128 (the four
    attention matrices and biases, two LayerNorms, the FFN's two
    matrices and biases), the final LayerNorm and a head of 29 with its
    bias; bf16 weights; K and V pages of 4 rows of 32 floats on 2
    planes."""
    dec, _ = paged._decoder()
    assert (dec.n_layers, dec.d_model, dec.vocab_size) == (2, 32, 29)
    d, f, v = 32, 128, 29
    layer = 4 * (d * d + d) + 2 * 2 * d + (d * f + f) + (f * d + d)
    dec.weight_itemsize = 2
    got = dec.tick_counts(np.array([0, 9], np.int32), 3)
    assert got["step_bytes_weights"] == 2 * (
        2 * layer + 2 * d + d * v + v)
    # gathered: every page of 3 lanes x 4 blocks x 2 layers, K and V;
    # and the two live lanes' rows on both layers
    page = 2 * 4 * d * 4
    assert got["step_bytes_cache"] == (
        got["kv_pages_table"] * page + 2 * 2 * (2 * d * 4))
    assert got["kv_pages_table"] == got["kv_pages_read"] == 24
    assert got["expert_bytes"] == 0


def _reader(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_reader_" + name, path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader


def test_the_two_readers_sum_the_windows_ticks(monkeypatch):
    """`serve_step_bytes_roofline`: the three sums of every tick span of
    the window but the first, `expert_bytes` times the span's
    `moe_experts_hit`, over the peak times the wall from the first
    span's end to the last's; `sched_step_cache_bytes_share`: the cache's
    part of the same sum.  Nothing where a tick lacks the three (the
    parent), an account was dropped or failed, or the ring dropped."""
    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf")
    monkeypatch.syspath_prepend(perf)
    roofline = _reader("serve_step_bytes_roofline")
    share = _reader("sched_step_cache_bytes_share")
    ticks = [{"name": "serving.decode_tick", "ts": 10.0 + 0.5 * i,
              "dur": 0.5,
              "attrs": {"step_bytes_weights": 300, "step_bytes_cache": 100,
                        "expert_bytes": 10, "moe_experts_hit": 10}}
             for i in range(5)]
    del ticks[2]["attrs"]["moe_experts_hit"]    # a tick that read none
    other = {"name": "generation.phase.build", "ts": 10.0, "dur": 0.1,
             "attrs": {}}
    monkeypatch.setattr(tracing, "finished_spans",
                        lambda: [other] + ticks)
    run = types.SimpleNamespace(spans=ticks,
                                peaks={"hbm_bytes_per_s": 1000.0})
    # four ticks in 2 s: 4 x 400 + 3 x 100 bytes of 2000
    assert roofline.compute(run) == pytest.approx(100.0 * 1900 / 2000)
    assert share.compute(run) == pytest.approx(100.0 * 400 / 1900)
    for reader, layer, better in ((roofline, "device", "higher"),
                                  (share, "serving.kv_cache", "lower")):
        assert (reader.LAYER, reader.UNIT, reader.MOVES,
                reader.SOURCE) == (layer, "%", "itl_p95_ms",
                                   "program_span")
    for broken in ("dropped_spans", "dropped_deferred", "failed_deferred"):
        with monkeypatch.context() as mp:
            mp.setattr(tracing, broken, lambda: 1)
            assert roofline.compute(run) is None
            assert share.compute(run) is None
    # a program without the accounts (the parent): nothing, no raise
    with monkeypatch.context() as mp:
        mp.delattr(tracing, "dropped_deferred")
        assert roofline.compute(run) is None and share.compute(run) is None
    del ticks[3]["attrs"]["step_bytes_cache"]
    assert roofline.compute(run) is None and share.compute(run) is None
    empty = types.SimpleNamespace(spans=[], peaks=run.peaks)
    assert roofline.compute(empty) is None and share.compute(empty) is None


def test_an_untraced_tick_captures_nothing(monkeypatch):
    """With no span live `_tick` makes no closure and keeps no row: the
    account's maker is never asked, no span is deferred to, and the
    server holds neither `_saved` nor `_saved_stale`."""
    assert not (tracing.enabled() or tracing._listeners)
    asked = []
    monkeypatch.setattr(generation._TickAccount, "of",
                        lambda self, *a, **k: asked.append(a))
    monkeypatch.setattr(tracing.Span, "defer",
                        lambda self, fn: asked.append(fn))
    dec, states = paged._decoder()
    srv = GenerationServer(dec, states, slots=2, kv_blocks=8,
                           place=fluid.CPUPlace())
    try:
        for _ in range(3):
            srv.submit([3, 1, 4], 8).result(timeout=60)
        assert srv.stats()["ticks"] >= 25
        assert not hasattr(srv, "_saved")
        assert not hasattr(srv, "_saved_stale")
        # and the tick in hand holds its rows alone
        assert generation._Tick.__slots__ == ("rows", "nxt", "counts",
                                              "tokens")
    finally:
        srv.close()
    assert asked == []
    # a request's table is kept once, at admission, traced or not
    assert "table" in generation._Seq.__slots__
