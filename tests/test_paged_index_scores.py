"""The lightning indexer's score kernel (`kernels/paged_index_scores.py`)
under the Pallas interpreter on the CPU against the XLA gather of the
whole table (`_indexer`'s three lines), and its selector's refusals.

What the kernel defines is the rows UNDER a lane's length; what it
leaves past them (a window's tail, a chunk never reached) is made minus
infinity by the caller's mask, as here.  The property: under that mask
the two paths' scores agree to float32 rounding (the same products, the
32-term sum in another order) and `lm_block.select_rows` picks the same
rows from both.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_index_scores as pis
from paddle_tpu.models import lm_block

BS, NB, H, D = 8, 12, 4, 128            # 96 rows a table
PAGES, TILE = 4, 2                      # three chunks of four pages
ROWS = NB * BS
# a cursor (rows scored are cursor + 1) at: the first row, the second,
# a page's last row, a page's first row, a chunk's last row, a chunk's
# first row, the row before the table's last, the table's last
CURSORS = {"first_row": 0, "second_row": 1, "page_last_row": BS - 1,
           "page_first_row": BS, "chunk_last_row": PAGES * BS - 1,
           "chunk_first_row": PAGES * BS, "next_to_last": ROWS - 2,
           "whole_table": ROWS - 1}
TOPK = 8


def _case(dtype, cursors, active=None, shared=0, seed=0):
    """Pools, tables and queries for lanes at `cursors`: every lane's
    blocks its own but its first `shared` (lane 0's: a prefix hit), a
    lane that is not `active` on the null block 0 all along."""
    r = np.random.RandomState(seed)
    s_n = len(cursors)
    active = np.ones(s_n, bool) if active is None else np.asarray(active)
    pool = jnp.asarray(r.randn(2, s_n * NB + 1, BS, D) * 0.5, dtype)
    tables = 1 + np.arange(s_n * NB, dtype=np.int32).reshape(s_n, NB)
    tables[:, :shared] = tables[0, :shared]
    tables[~active] = 0
    q = jnp.asarray(r.randn(s_n, H, D) * 0.3, jnp.float32)
    w = jnp.asarray(r.randn(s_n, H), jnp.float32)
    cur = np.where(active, np.asarray(cursors), 0)
    return pool, jnp.asarray(tables), q, w, jnp.asarray(cur, jnp.int32)


def _gather(q, w, pool, tables, plane):
    """`_indexer`'s XLA path: the whole table in logical order."""
    keys = pool[plane, tables].reshape(q.shape[0], ROWS, D)
    dots = jax.lax.dot_general(
        q.astype(keys.dtype), keys, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return (jax.nn.relu(dots) * w[:, :, None]).sum(axis=1)


def _both(pool, tables, q, w, cur, plane=1, pages=PAGES, tile=TILE):
    """(kernel, gather) scores under the caller's mask, and the mask."""
    valid = jnp.arange(ROWS)[None, :] <= cur[:, None]
    got = pis.paged_index_scores(q, w, pool, tables, cur + 1, plane,
                                 pages=pages, tile=tile, interpret=True)
    assert got.shape == (len(cur), ROWS) and got.dtype == jnp.float32
    want = _gather(q, w, pool, tables, plane)
    return (np.asarray(jnp.where(valid, got, -jnp.inf)),
            np.asarray(jnp.where(valid, want, -jnp.inf)), valid)


def _agree(got, want, valid, k=TOPK):
    seen = np.asarray(valid)
    assert np.array_equal(np.isneginf(got), ~seen)
    scale = np.abs(want[seen]).max()
    assert np.abs(got[seen] - want[seen]).max() <= 1e-5 * scale
    assert np.array_equal(
        np.asarray(lm_block.select_rows(jnp.asarray(got), valid, k)),
        np.asarray(lm_block.select_rows(jnp.asarray(want), valid, k)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("edge", sorted(CURSORS))
def test_scores_equal_the_gather_at_a_ragged_cursor(edge, dtype):
    """A lane at the edge between two lanes at other cursors: the lane
    before it leaves its last chunk in the other buffer, the lane after
    it starts from the buffer this one leaves."""
    cursors = [37, CURSORS[edge], 70, CURSORS[edge], 5]
    _agree(*_both(*_case(dtype, cursors)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("idle", [(0,), (4,), (1, 2), (0, 1, 2, 3, 4)],
                         ids=["first", "last", "middle", "all"])
def test_an_idle_lane_reads_the_null_block_and_disturbs_no_other(idle,
                                                                 dtype):
    active = [i not in idle for i in range(5)]
    case = _case(dtype, [40, 95, 8, 63, 17], active=active)
    got, want, valid = _both(*case)
    _agree(got, want, valid)
    # an idle lane scores row 0 of block 0 and nothing else
    for lane in idle:
        assert np.isfinite(got[lane]).sum() == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("shared", [1, PAGES, PAGES + 1, NB])
def test_lanes_that_share_blocks_score_them_each_for_itself(shared,
                                                            dtype):
    """A prefix hit: the lanes' first `shared` pages are lane 0's
    blocks (a page, a chunk, a chunk and a page, the whole table)."""
    pool, tables, q, w, cur = _case(dtype, [95, 50, 95, 33],
                                    shared=shared)
    got, want, valid = _both(pool, tables, q, w, cur)
    _agree(got, want, valid)
    # the same keys under another lane's queries: not the same scores
    assert not np.array_equal(got[0, :BS], got[2, :BS])


@pytest.mark.parametrize("pages,tile", [(NB, 1), (NB, NB), (5, 1), (1, 1),
                                        (8, 4)])
def test_scores_hold_whatever_the_chunk_and_the_tile(pages, tile):
    """One chunk for the table, chunks that do not divide it (the last
    one's rows past the table are cut off), a page a chunk."""
    _agree(*_both(*_case(jnp.bfloat16, [0, 95, 39, 40, 64, 8]),
                  pages=pages, tile=tile))


@pytest.mark.parametrize("plane", [0, 1])
def test_the_plane_is_a_traced_scalar_and_names_the_pool_read(plane):
    pool, tables, q, w, cur = _case(jnp.bfloat16, [20, 90, 55])
    got, want, valid = _both(pool, tables, q, w, cur,
                             plane=jnp.asarray(plane, jnp.int32))
    _agree(got, want, valid)
    other, _, _ = _both(pool, tables, q, w, cur, plane=1 - plane)
    assert not np.array_equal(got, other)


def test_rows_past_the_length_are_the_callers_to_mask():
    """The kernel's own words on them: nothing.  Rows of a page the
    length reaches are scored whole (the page was copied); the rest may
    be anything, and the caller's `where` is what defines them."""
    pool, tables, q, w, cur = _case(jnp.float32, [10, 40])
    raw = np.asarray(pis.paged_index_scores(
        q, w, pool, tables, cur + 1, 0, pages=PAGES, tile=TILE,
        interpret=True))
    want = np.asarray(_gather(q, w, pool, tables, 0))
    for lane, c in enumerate(np.asarray(cur)):
        reached = -(-(c + 1) // BS) * BS
        np.testing.assert_allclose(raw[lane, :reached],
                                   want[lane, :reached], rtol=1e-5,
                                   atol=1e-5)


# The issue loop's groups (`paged_attention.start_pages`, PR 56): a
# table of 43 pages in chunks of 20, so a chunk is a group of
# `_ISSUE_UNROLL` (16) table entries and four pages that go one by one.
RUN_NB, RUN_PAGES = 43, 20


def _run_tables(kind, s_n):
    """[s_n, RUN_NB] int32 over the lanes' own blocks (lane s's: 1 + s
    * RUN_NB on), by name."""
    group = min(pis._ISSUE_UNROLL, RUN_PAGES)
    up = 1 + np.arange(s_n * RUN_NB, dtype=np.int32).reshape(s_n, RUN_NB)
    r = np.random.RandomState(11)
    if kind == "descending":
        return up[:, ::-1].copy()
    if kind == "shuffled":
        return 1 + r.permutation(s_n * RUN_NB).astype(np.int32).reshape(
            s_n, RUN_NB)
    if kind == "broken_inside_a_group":
        # two neighbours swapped in the first chunk's group
        for lane in range(s_n):
            at = (5 * lane) % (group - 1)
            up[lane, [at, at + 1]] = up[lane, [at + 1, at]]
    if kind == "run_across_a_chunks_edge":
        # no run up to three pages before the first chunk's end
        for lane in range(s_n):
            up[lane, :RUN_PAGES - 3] = r.permutation(up[lane, :RUN_PAGES - 3])
    if kind == "prefix_then_fresh":
        # lane 0's first pages under every lane, then its own run
        up[:, :group - 5] = up[0, :group - 5]
    if kind == "idle_ring_of_block_0":
        up[1] = 0
    return up


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("kind", [
    "ascending", "descending", "shuffled", "broken_inside_a_group",
    "run_across_a_chunks_edge", "prefix_then_fresh", "idle_ring_of_block_0",
    "ends_inside_a_group"])
def test_a_run_of_pages_is_one_copy_and_the_same_scores(kind, dtype):
    """Whatever a table names (runs up or down, no run, a run broken
    inside a group, one across a chunk's edge, a shared prefix then
    fresh blocks, an idle lane's block 0 throughout, lengths that end
    inside a group) the scores under the cursors equal the gather's,
    AND equal, bit for bit, the kernel's own on the same pages behind a
    shuffled table (a copy a page)."""
    from paddle_tpu.kernels import paged_attention

    group = min(pis._ISSUE_UNROLL, RUN_PAGES)
    # pages a lane holds: the whole table, a chunk to its last page,
    # ends inside the second chunk's group and among the pages after it
    n_pages = [RUN_NB, RUN_PAGES, RUN_PAGES + 7, 2 * RUN_PAGES - 2]
    if kind == "ends_inside_a_group":
        n_pages = [3, group - 1, RUN_PAGES + 2, RUN_PAGES + group - 1]
    s_n = len(n_pages)
    tables = _run_tables(kind, s_n)
    active = tables.any(axis=1)
    cur = np.where(active, np.asarray(n_pages) * BS - 1 - np.arange(s_n), 0)
    saved = paged_attention.starts_saved(
        tables, RUN_PAGES, pis._ISSUE_UNROLL)[:, -1] // (group - 1)
    assert {"ascending": (saved == 2).all(), "descending": not saved.any(),
            "shuffled": not saved.any()}.get(kind, saved.any())
    r = np.random.RandomState(3)
    pool = jnp.asarray(r.randn(2, s_n * RUN_NB + 1, BS, D) * 0.5, dtype)
    q = jnp.asarray(r.randn(s_n, H, D) * 0.3, jnp.float32)
    w = jnp.asarray(r.randn(s_n, H), jnp.float32)
    valid = np.arange(RUN_NB * BS)[None, :] <= cur[:, None]

    def scores(pool, tables):
        return np.where(valid, np.asarray(pis.paged_index_scores(
            q, w, pool, jnp.asarray(tables), jnp.asarray(cur + 1, jnp.int32),
            1, pages=RUN_PAGES, tile=TILE, interpret=True)), -np.inf)

    got = scores(pool, tables)
    keys = pool[1, tables].reshape(s_n, RUN_NB * BS, D)
    dots = jax.lax.dot_general(
        q.astype(keys.dtype), keys, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    want = np.where(valid, np.asarray(
        (jax.nn.relu(dots) * w[:, :, None]).sum(axis=1)), -np.inf)
    _agree(got, want, jnp.asarray(valid))
    # block b's page in block behind[b], the table renamed
    behind = np.concatenate(
        [[0], 1 + r.permutation(s_n * RUN_NB)]).astype(np.int32)
    assert not paged_attention.starts_saved(
        behind[tables], RUN_PAGES, pis._ISSUE_UNROLL)[:, -1].any()
    np.testing.assert_array_equal(
        got, scores(pool[:, np.argsort(behind)], behind[tables]))


# The cut (`paged_attention.chunk_cut`, PR 66): a table of 75 pages
# under a cap of 48, three groups of 16 entries (a tile of 8 rows a
# page is 16 pages too), so a lane past the cap is cut at a stride of
# 32 or 48: 49 are 32 + 17, 65 are 48 + 17, 75 are 48 + 27
CUT_NB, CUT_PAGES, CUT_TILE = 75, 48, 16
CUT_LANES = {"a_page": [1, 75, 1], "a_stride": [47, 48, 49],
             "past_a_stride": [49, 33, 65], "the_table": [75, 64, 75],
             "mixed": [65, 1, 48, 75, 49, 96 // 2]}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("lanes", sorted(CUT_LANES))
def test_a_lane_past_the_cap_is_scored_in_equal_chunks(lanes, dtype):
    """Lanes of a page, one short of the cap, the cap, one over (32 +
    17), 48 + 17 and the whole table (48 + 27) in one call, each
    started by the lane before it: a lane's chunks lie a stride apart
    in its table and a buffer apart in the kernel's output, and the
    scores under the cursors equal the gather's, row for row."""
    from paddle_tpu.kernels import paged_attention

    group = paged_attention.cut_group(CUT_PAGES, CUT_TILE,
                                      pis._ISSUE_UNROLL)
    assert group == pis._ISSUE_UNROLL == 16
    assert [tuple(map(int, paged_attention.chunk_cut(n, CUT_PAGES, group)))
            for n in (48, 49, 65, 75)] == [(48, 1), (32, 2), (48, 2),
                                           (48, 2)]
    n_pages = CUT_LANES[lanes]
    s_n = len(n_pages)
    r = np.random.RandomState(5)
    pool = jnp.asarray(r.randn(2, s_n * CUT_NB + 1, BS, D) * 0.5, dtype)
    # ascending blocks, every other lane's shuffled: runs and no run
    tables = 1 + np.arange(s_n * CUT_NB, dtype=np.int32).reshape(
        s_n, CUT_NB)
    for lane in range(1, s_n, 2):
        tables[lane] = r.permutation(tables[lane])
    q = jnp.asarray(r.randn(s_n, H, D) * 0.3, jnp.float32)
    w = jnp.asarray(r.randn(s_n, H), jnp.float32)
    # the cursor ends inside its last page
    cur = np.asarray(n_pages) * BS - 1 - np.arange(s_n) % BS
    valid = np.arange(CUT_NB * BS)[None, :] <= cur[:, None]
    got = np.where(valid, np.asarray(pis.paged_index_scores(
        q, w, pool, jnp.asarray(tables), jnp.asarray(cur + 1, jnp.int32),
        1, pages=CUT_PAGES, tile=CUT_TILE, interpret=True)), -np.inf)
    keys = pool[1, tables].reshape(s_n, CUT_NB * BS, D)
    dots = jax.lax.dot_general(
        q.astype(keys.dtype), keys, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    want = np.where(valid, np.asarray(
        (jax.nn.relu(dots) * w[:, :, None]).sum(axis=1)), -np.inf)
    _agree(got, want, jnp.asarray(valid))


@pytest.mark.parametrize("kw,reason", [
    (dict(platform="cpu"), "not_tpu"),
    (dict(platform="gpu"), "not_tpu"),
    (dict(platform="tpu", kv_dtype="int8"), "kv_dtype"),
    (dict(platform="tpu", index_head_dim=64), "lane_misaligned"),
    (dict(platform="tpu", index_head_dim=192), "lane_misaligned"),
    (dict(platform="tpu", block_size=8), "sublane_misaligned"),
    (dict(platform="tpu", block_size=4, kv_dtype="fp32"),
     "sublane_misaligned"),
])
def test_the_selector_refuses_by_geometry_dtype_and_platform(kw, reason):
    geometry = dict(index_head_dim=128, block_size=16, kv_dtype="bf16")
    kern, why = pis.select_index_scores(**{**geometry, **kw})
    assert kern is None and why == reason


@pytest.mark.parametrize("kw,table_pages,tiling", [
    # the cell's plane: 432 pages of 4 KiB in one chunk
    (dict(platform="tpu"), 432, (432, 8)),
    # a short table is one chunk, a long one a cap of 2 MiB, under
    # which a lane is cut in equal chunks by its own length
    (dict(platform="tpu"), 64, (64, 8)),
    (dict(platform="tpu"), 1024, (512, 8)),
    (dict(platform="tpu"), 1200, (512, 8)),
    (dict(platform="tpu", kv_dtype="fp32", block_size=8), 432, (432, 16)),
    # off the TPU only the interpreter, at any geometry
    (dict(platform="cpu", interpret=True, index_head_dim=16,
          block_size=4), 16, (16, 16)),
])
def test_the_selector_returns_the_kernel_and_its_tiling(kw, table_pages,
                                                        tiling):
    geometry = dict(index_head_dim=128, block_size=16, kv_dtype="bf16")
    kern, why = pis.select_index_scores(**{**geometry, **kw})
    assert why is None and kern.tiling(table_pages) == tiling


def test_the_selected_kernel_is_the_jitted_call_at_its_tiling():
    kern, _ = pis.select_index_scores(
        index_head_dim=D, block_size=BS, kv_dtype="bf16", platform="cpu",
        interpret=True)
    pool, tables, q, w, cur = _case(jnp.bfloat16, [3, 95, 48])
    valid = jnp.arange(ROWS)[None, :] <= cur[:, None]
    got = jnp.where(valid, kern(q, w, pool, tables, cur + 1, 1), -jnp.inf)
    want = jnp.where(valid, _gather(q, w, pool, tables, 1), -jnp.inf)
    _agree(np.asarray(got), np.asarray(want), valid)


def test_kernel_pace_rehearses_the_cells_index_planes(tmp_path):
    """`tools/kernel_pace.py --shape glm-5.2-serve-docqa64-indexer
    --rehearse --check`: the cell's planes cut to a toy walk the whole
    kernel in the interpreter and its scores are the gather's under the
    cursors; off a TPU the tool gives a time for nothing else."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "kernel_pace.py")
    spec = importlib.util.spec_from_file_location("kernel_pace", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    shape = "glm-5.2-serve-docqa64-indexer"
    out = tmp_path / "pace.json"
    res = tool.main(["--shape", shape, "--rehearse", "--check",
                     "--out", str(out)])
    assert res == json.loads(out.read_text())
    assert res["rehearsal"] and res["whole"] > 0 and res["check"] < 1e-5
    assert res["pages"] * 16 >= res["rows"] > 0
    # the cell's own cursors: 3 k to 7 k rows of a table of 6912
    lengths = tool.index_lengths(tool.SHAPES[shape])
    assert 3072 <= lengths.min() and lengths.max() <= 6912
    assert 4700 < lengths.mean() < 5200
    with pytest.raises(SystemExit, match="no TPU"):
        tool.run(shape)
