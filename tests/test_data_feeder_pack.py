"""DataFeeder's dense branch: a column is packed by copying each row into
its place in one array (`data_feeder._pack_dense`), into a destination
the prefetch pipeline passes or into a new array the caller owns.  The
oracle is what the branch did before: `np.asarray(col, dtype=dtype)` and
the reshape of flat rows to the declared shape.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.framework import reset_unique_names
from paddle_tpu.core.types import np_dtype
from paddle_tpu.data_feeder import DataFeeder


def _feeder(*slots):
    """DataFeeder over one `layers.data` a slot: (name, shape, dtype)."""
    reset_unique_names()
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        feed_list = [fluid.layers.data(name=n, shape=list(shape), dtype=dt)
                     for n, shape, dt in slots]
    return DataFeeder(feed_list, fluid.CPUPlace())


def _asarray_oracle(col, var):
    arr = np.asarray(col, dtype=np_dtype(var.dtype))
    if len(var.shape) > arr.ndim:
        arr = arr.reshape((len(col),) + tuple(
            d if d > 0 else -1 for d in var.shape[1:]))
    return arr


_R = np.random.RandomState(3)

# name -> (declared shape, dtype, the column's rows)
_COLUMNS = {
    "ndarray_rows": ([3, 4], "float32",
                     [_R.rand(3, 4).astype(np.float32) for _ in range(5)]),
    "nested_list_rows": ([2, 3], "float32",
                         [_R.rand(2, 3).tolist() for _ in range(4)]),
    "tuple_rows": ([3], "float32", [(1.0, 2.5, -3.0), (0.0, 4.0, 9.5)]),
    "scalar_labels": ([1], "int64", [3, 1, 4, 1, 5, 9]),
    "numpy_scalar_labels": ([1], "int64",
                            [np.int64(v) for v in (2, 7, 1, 8)]),
    "labels_of_shape_1": ([1], "int64",
                          [np.asarray([v], np.int64) for v in (2, 7, 1)]),
    "float64_rows_into_float32": ([5], "float32",
                                  [_R.rand(5) for _ in range(4)]),
    "int32_rows_into_int64": ([2], "int64",
                              [np.asarray([i, -i], np.int32)
                               for i in range(4)]),
    "float_rows_into_int64": ([2], "int64",
                              [np.asarray([1.9, -2.9]),
                               np.asarray([0.5, 7.0])]),
    "python_int_lists_into_float32": ([3], "float32",
                                      [[1, 2, 3], [16777217, 5, 6]]),
    "flat_rows_declared_chw": ([3, 4, 4], "float32",
                               [_R.rand(48).astype(np.float32)
                                for _ in range(3)]),
    "flat_list_rows_declared_hw": ([2, 2], "float32",
                                   [[1.0, 2.0, 3.0, 4.0],
                                    [5.0, 6.0, 7.0, 8.0]]),
    "batch_of_one_row": ([3, 2], "float32",
                         [_R.rand(3, 2).astype(np.float32)]),
    "rows_that_are_views": ([4], "float32",
                            list(_R.rand(6, 8).astype(np.float32)[:, ::2])),
}


@pytest.mark.parametrize("case", sorted(_COLUMNS))
def test_packed_column_equals_asarray(case):
    shape, dtype, col = _COLUMNS[case]
    feeder = _feeder(("x", shape, dtype))
    var = feeder.feed_list[0]
    want = _asarray_oracle(col, var)
    got = feeder.feed([(v,) for v in col])["x"]
    assert isinstance(got, np.ndarray)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    # into a destination of the batch's shape: the same values, in it
    dest = np.full(want.shape, 99, want.dtype)
    into = feeder.feed([(v,) for v in col], out={"x": dest})["x"]
    assert into is dest
    np.testing.assert_array_equal(dest, want)


_UNEQUAL = {
    # np.copyto would BROADCAST the second row over a (3,) slot
    "shape_1_into_shape_3": [np.ones(3, np.float32),
                             np.ones(1, np.float32)],
    "shape_3_then_shape_2": [np.ones(3, np.float32),
                             np.ones(2, np.float32)],
    "scalar_into_shape_3": [np.ones(3, np.float32), 2.0],
    "ragged_lists": [[1.0, 2.0, 3.0], [1.0, 2.0]],
    "same_size_other_shape": [np.ones((2, 3), np.float32),
                              np.ones((3, 2), np.float32)],
}


@pytest.mark.parametrize("with_destination", [False, True])
@pytest.mark.parametrize("case", sorted(_UNEQUAL))
def test_rows_of_unequal_shape_raise(case, with_destination):
    col = _UNEQUAL[case]
    with pytest.raises(ValueError):
        np.asarray(col, dtype=np.float32)  # what the branch did before
    feeder = _feeder(("x", list(np.shape(col[0])), "float32"))
    out = {"x": np.zeros((2,) + np.shape(col[0]), np.float32)} \
        if with_destination else None
    with pytest.raises(ValueError):
        feeder.feed([(v,) for v in col], out=out)


def _batch(n, seed=0):
    r = np.random.RandomState(seed)
    return [(r.rand(3, 2).astype(np.float32), int(r.randint(10)))
            for _ in range(n)]


def _xy_feeder():
    return _feeder(("x", [3, 2], "float32"), ("y", [1], "int64"))


@pytest.mark.parametrize("misfit", [
    "fewer_rows", "more_rows", "other_row_shape", "other_dtype",
    "not_contiguous", "read_only"])
def test_destination_that_does_not_fit_is_left_unwritten(misfit):
    dest = {"fewer_rows": np.full((3, 3, 2), 7, np.float32),
            "more_rows": np.full((5, 3, 2), 7, np.float32),
            "other_row_shape": np.full((4, 2, 3), 7, np.float32),
            "other_dtype": np.full((4, 3, 2), 7, np.float64),
            "not_contiguous": np.full((4, 3, 4), 7, np.float32)[:, :, ::2],
            "read_only": np.full((4, 3, 2), 7, np.float32)}[misfit]
    if misfit == "read_only":
        dest.flags.writeable = False
    rows = _batch(4)
    got = _xy_feeder().feed(rows, out={"x": dest})["x"]
    assert got is not dest and not np.shares_memory(got, dest)
    assert (dest == 7).all(), "a destination that does not fit was written"
    np.testing.assert_array_equal(got, np.asarray([r[0] for r in rows]))


def test_fitting_destination_is_the_array_returned():
    feeder, rows = _xy_feeder(), _batch(4)
    first = feeder.feed(rows)
    # the arrays of one batch are the destinations of the next, as the
    # pipeline passes them; a name with no entry gets a new array
    again = feeder.feed(_batch(4, seed=1), out={"x": first["x"]})
    assert again["x"] is first["x"]
    assert np.shares_memory(again["x"], first["x"])
    assert not np.shares_memory(again["y"], first["y"])
    np.testing.assert_array_equal(
        again["x"], np.asarray([r[0] for r in _batch(4, seed=1)]))
    # the short last batch of a pass: new arrays, the old ones untouched
    kept = first["x"].copy()
    short = feeder.feed(_batch(3, seed=2), out=first)
    assert short["x"].shape == (3, 3, 2) and short["y"].shape == (3, 1)
    assert not np.shares_memory(short["x"], first["x"])
    np.testing.assert_array_equal(first["x"], kept)


def test_direct_feed_returns_arrays_the_caller_owns():
    """No destination: every call allocates, and the rows are copied
    before `feed` returns (a reader may reuse its row memory)."""
    feeder = _xy_feeder()
    image = np.full((3, 2), 5.0, np.float32)
    rows = [(image, i) for i in range(4)]  # the reader's one row array
    a = feeder.feed(rows)
    image[...] = -1.0  # the reader's memory moves on
    b = feeder.feed(rows)
    assert (a["x"] == 5.0).all() and (b["x"] == -1.0).all()
    assert not np.shares_memory(a["x"], b["x"])
    assert not np.shares_memory(a["x"], image)
    assert a["y"].dtype == np.int64 and a["y"].shape == (4, 1)
    np.testing.assert_array_equal(a["y"].reshape(-1), np.arange(4))


def test_empty_batch_keeps_its_old_answer():
    feeder = _feeder(("x", [], "float32"))
    got = feeder.feed([])["x"]
    assert got.shape == (0,) and got.dtype == np.float32


def test_lod_slots_take_no_destination():
    reset_unique_names()
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        words = fluid.layers.data(name="w", shape=[1], dtype="int64",
                                  lod_level=1)
    feeder = DataFeeder([words], fluid.CPUPlace())
    rows = [([1, 2, 3],), ([4, 5],)]
    plain = feeder.feed(rows)["w"]
    dest = np.full((5, 1), 7, np.int64)
    given = feeder.feed(rows, out={"w": dest})["w"]
    assert (dest == 7).all()
    np.testing.assert_array_equal(np.asarray(given.data),
                                  np.asarray(plain.data))
    assert given.lod == plain.lod
