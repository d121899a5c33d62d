"""`kernels/select_rows.py` (a lightning indexer's selection as one
Pallas call: a block of lanes' keys in VMEM for all 32 counts) in the
Pallas interpreter on the CPU against `lm_block.select_rows`, whose
mask it must give BIT FOR BIT: the k valid rows of largest score, every
valid row where there are k or fewer, a tie at the k-th score to the
lower row, -0.0 as +0.0, an invalid row under every score.

What the interpreter cannot show (that Mosaic takes the kernel at the
cells' shape, that the compiled step holds no loop under the scope) is
`tests/test_kernels_lower_tpu.py`'s; through a whole decoder it runs in
`tests/test_glm_dsa_decoder.py` and `tests/test_dots3_note_decoder.py`.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels import select_rows
from paddle_tpu.models import lm_block
from paddle_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ["glm-5.2-serve-docqa64", "dots3-note-prev-serve-docqa64"]


def _normal(lanes, rows, seed=0):
    return np.random.RandomState(seed).normal(
        size=(lanes, rows)).astype(np.float32)


def _quarters(lanes, rows, seed=0):
    """Scores on a grid of quarters: every lane's k-th score is shared
    by dozens of rows."""
    return np.round(_normal(lanes, rows, seed) * 4) / 4


def _tie_over_an_edge():
    """Lane 0: three rows above, then SIX rows at the k-th score (k 6:
    three of them are taken) of which two lie before the edge of the
    first 128-lane tile and four after it; lane 1: the tie spans rows
    127, 128 and 256 with room for two."""
    s = -np.abs(_normal(8, 384, 3)) - 1.0
    s[0, [5, 60, 200]] = 2.0
    s[0, [100, 127, 128, 129, 255, 300]] = 1.0
    s[1, [0, 1, 2, 3]] = 2.0
    s[1, [127, 128, 256]] = 1.0
    return s


def _signed_zeros():
    """Zeros of both signs at the k-th score, the negative ones on the
    LOWER rows: they tie with the positive ones and win."""
    s = -np.abs(_normal(8, 256, 4)) - 1.0
    s[:, 10:20] = -0.0
    s[:, 130:140] = 0.0
    s[:, 200] = 1.0
    return s


def _infinities():
    s = _normal(8, 256, 5)
    s[:, [3, 129, 250]] = np.inf
    s[:, [0, 128, 255]] = -np.inf
    s[1, :] = -np.inf                   # a lane of nothing but -inf
    s[2, :] = np.inf
    return s


def _docqa_cursors(lanes, rows):
    r = np.random.RandomState(0)
    return np.minimum(r.randint(rows * 4 // 9, rows * 8 // 9, lanes)
                      + r.randint(32, 640, lanes), rows - 1)


# name: (scores [lanes, rows], cursors [lanes] or None (seeded, an idle
# lane at 0 and a full lane among them), k)
CASES = {
    "distinct_scores": (_normal(8, 256), None, 40),
    "ties_at_the_kth_score": (_quarters(8, 384), None, 50),
    "a_tie_across_a_tile_edge": (_tie_over_an_edge(), [383] * 8, 6),
    "fewer_valid_rows_than_k": (_normal(8, 256, 1), [0, 1, 7, 38, 39, 40,
                                                   41, 255], 40),
    "one_valid_row_a_lane": (_quarters(8, 128, 2), [0] * 8, 8),
    "signed_zeros": (_signed_zeros(), [255] * 8, 16),
    "infinities_among_the_valid": (_infinities(), None, 3),
    "all_scores_equal": (np.full((8, 256), 0.5, np.float32), None, 100),
    "all_scores_minus_zero": (np.full((8, 128), -0.0, np.float32), None, 9),
    "k_1": (_quarters(16, 256, 6), None, 1),
    "k_is_the_rows": (_quarters(8, 256, 7), None, 256),
    "k_past_the_rows": (_normal(8, 128, 8), None, 300),
    "three_blocks_of_lanes": (_quarters(24, 256, 9), None, 30),
    "two_blocks_of_64_lanes": (_quarters(128, 128, 10), None, 20),
    "lanes_off_the_sublane_grid": (_quarters(3, 256, 11), None, 40),
    "rows_past_the_cursor_are_not_numbers": (
        np.where(np.arange(256)[None, :] > 100, np.nan,
                 _quarters(8, 256, 12)).astype(np.float32), [100] * 8, 30),
    # the two cells' table and k at toy lanes, docqa64's cursors
    "the_cells_shape": (_normal(8, 6912, 13), _docqa_cursors(8, 6912), 2048),
    "the_cells_shape_with_ties": (_quarters(8, 6912, 14),
                                  _docqa_cursors(8, 6912), 2048),
    # cursors near twice k: the k-th score is a zero, of both signs
    "the_cells_shape_with_zeros_at_the_kth_score": (
        _quarters(8, 6912, 15), [3083, 3500, 4000, 4096, 4200, 5000, 6000,
                                 6325], 2048),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernels_mask_is_select_rows_mask(name):
    scores, cursors, k = CASES[name]
    lanes, rows = scores.shape
    if cursors is None:
        cursors = np.random.RandomState(1).randint(0, rows, lanes)
        cursors[0], cursors[-1] = rows - 1, 0
    cursors = np.asarray(cursors, np.int32)
    kernel, why = select_rows.select_index_selection(
        rows=rows, lanes=lanes, k=k, platform="cpu", interpret=True)
    assert kernel is not None, why
    assert kernel.name == "pallas:select_rows"
    valid = np.arange(rows)[None, :] <= cursors[:, None]
    want = np.asarray(lm_block.select_rows(
        jnp.asarray(np.where(valid, scores, -np.inf)), jnp.asarray(valid),
        k))
    got = np.asarray(kernel.select(jnp.asarray(scores),
                                   jnp.asarray(cursors)))
    assert got.dtype == np.bool_ and got.shape == want.shape
    assert np.array_equal(got, want)
    # and it is what the words say: k rows a lane or every valid one,
    # none of them invalid, none under a row left out
    assert np.array_equal(got.sum(-1), np.minimum(k, valid.sum(-1)))
    assert not (got & ~valid).any()
    if not np.isnan(scores[valid]).any():
        for lane in range(lanes):
            left = valid[lane] & ~got[lane]
            if left.any() and got[lane].any():
                assert scores[lane][got[lane]].min() >= \
                    scores[lane][left].max()


def test_zeros_of_both_signs_tie_as_the_words_and_the_eager_lines_say():
    """Where a lane's k-th score is a zero, -0.0 and +0.0 tie and the
    lower rows win: `select_rows`' words, what its EAGER call computes
    (the parametrised test's reference) and what the plain references'
    `scores == kth` does.  Under `jax.jit` XLA takes the lines' `x +
    0.0` for x, on the CPU and on the chip, so the jitted lines rank
    -0.0 under +0.0 there (PERF.md section 7, "From PR 68"); the
    kernel's keys are made of integers and tie them jitted or not."""
    scores, cursors, k = CASES["the_cells_shape_with_zeros_at_the_kth_score"]
    kernel, _ = select_rows.select_index_selection(
        rows=6912, lanes=8, k=k, platform="cpu", interpret=True)
    got = np.asarray(kernel.select(jnp.asarray(scores),
                                   jnp.asarray(cursors, jnp.int32)))
    kth = np.array([scores[lane][got[lane]].min() for lane in range(8)])
    at_zero = np.flatnonzero(kth == 0.0)
    assert at_zero.size                 # the case bites
    for lane in at_zero:
        zeros = np.flatnonzero((scores[lane] == 0.0)
                               & (np.arange(6912) <= cursors[lane]))
        took = got[lane][zeros]
        assert np.signbit(scores[lane][zeros[took]]).any()  # a -0.0 won
        assert not took[np.argmin(took):].any()     # the lowest, no gap


def test_the_ties_on_a_tile_edge_go_to_the_lower_rows():
    """The case spelled out: rows 100, 127 and 128 of the six at the
    k-th score are taken, 129, 255 and 300 are not."""
    scores, cursors, k = CASES["a_tie_across_a_tile_edge"]
    kernel, _ = select_rows.select_index_selection(
        rows=384, lanes=8, k=k, platform="cpu", interpret=True)
    got = np.asarray(kernel.select(jnp.asarray(scores),
                                   jnp.asarray(cursors, jnp.int32)))
    assert list(np.flatnonzero(got[0])) == [5, 60, 100, 127, 128, 200]
    assert list(np.flatnonzero(got[1])) == [0, 1, 2, 3, 127, 128]


@pytest.mark.parametrize("over, reason", [
    ({}, None),
    ({"platform": "cpu"}, "not_tpu"),
    ({"platform": "cpu", "interpret": True}, None),
    ({"rows": 6912 + 16}, "lane_misaligned"),
    ({"rows": 64, "platform": "cpu", "interpret": True}, "lane_misaligned"),
    ({"lanes": 60}, "sublane_misaligned"),
    ({"lanes": 3, "platform": "cpu", "interpret": True}, None),
    ({"rows": 1 << 17}, "scores_exceed_vmem"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_selection_is_a_function_of_shapes_and_the_platform(over,
                                                                reason):
    """The cells' shape is taken for a TPU, its 64 lanes one block;
    each refusal is named."""
    args = dict(dict(rows=6912, lanes=64, k=2048, platform="tpu"), **over)
    kernel, why = select_rows.select_index_selection(**args)
    assert why == reason and (kernel is None) == (reason is not None)
    assert select_rows.index_selection_supports(**args) == reason
    if not over:
        assert kernel.lanes_block == 64
        assert select_rows._vmem_bytes(64, 6912) <= 10 * 1024 * 1024


def test_the_reader_of_the_counter_on_a_synthetic_run(monkeypatch):
    """`sched_select_kernel_share` on a `Run` made by hand: the mean of
    `select_kernel` over the window's tick spans; nothing, and no error,
    from a program that sets no such attribute (the parent's) or keeps
    no spans; its entry is the benchmark's and lists the two selecting
    cells."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perf"))
    import common

    reader = common.load_module(os.path.join(
        ROOT, "perf", "metrics", "sched_select_kernel_share.py"))
    spans = [{"name": "serving.decode_tick", "ts": 10.0 + i, "dur": 0.5,
              "attrs": {"kv_rows_indexed": 9, "select_kernel": int(i != 1)}}
             for i in range(4)]
    spans.append({"name": "serving.request", "ts": 11.0, "dur": 0.4,
                  "attrs": {}})
    monkeypatch.setattr(tracing, "finished_spans", lambda: list(spans))
    run = common.Run()
    run.spans = [{"ts": 9.0, "dur": 0.5}, {"ts": 13.0, "dur": 0.6}]
    assert reader.compute(run) == pytest.approx(75.0)
    run.spans = [{"ts": 9.0, "dur": 0.5}, {"ts": 11.0, "dur": 0.6}]
    assert reader.compute(run) == pytest.approx(50.0)   # ticks 0 and 1
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        dict(s, attrs={"kv_rows_indexed": 9, "moe_kernel": 1})
        for s in spans])
    assert reader.compute(run) is None
    run.spans = []
    assert reader.compute(run) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1] == {
        "name": "sched_select_kernel_share", "unit": reader.UNIT,
        "better": "higher", "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": CELLS}
    assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "kernels", "itl_p95_ms", "program_span")


def test_kernel_pace_rehearses_the_cells_selection():
    """`tools/kernel_pace.py --shape glm-5.2-select,dots3-note-prev-select
    --rehearse --check`: a toy of the cells' selection walks the whole
    kernel in the interpreter and gives `select_rows`' mask; the real
    geometry is read from the cells' own files; the removal leaves the
    module as it was; off a TPU the tool gives a time for nothing."""
    path = os.path.join(ROOT, "tools", "kernel_pace.py")
    spec = importlib.util.spec_from_file_location("kernel_pace", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    for name, cell in zip(("glm-5.2-select", "dots3-note-prev-select"),
                          CELLS):
        assert tool.SHAPES[name] == {"kernel": "select", "cell": cell}
        lanes, rows, k, cursors = tool.select_cell(cell)
        assert (lanes, rows, k) == (64, 6912, 2048)
        assert cursors.shape == (64,) and 3072 <= cursors.min() \
            and cursors.max() < 6912
    assert tool.SELECT_VARIANTS == ("whole", "xla", "no_counts")
    res = tool.main(["--shape", "glm-5.2-select", "--rehearse", "--check"])
    assert res["rehearsal"] and res["check"] == [[0, 0], [0, 0]]
    assert set(res) >= {"whole", "xla"} and "no_counts" not in res
    assert select_rows._PASSES == 32
    with pytest.raises(SystemExit, match="no TPU here"):
        tool.main(["--shape", "glm-5.2-select"])
