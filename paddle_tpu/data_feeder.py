"""DataFeeder: python minibatch -> feed dict of arrays / LoDTensors.

Reference: /root/reference/python/paddle/v2/fluid/data_feeder.py:1-115
(DataToLoDTensorConverter).
"""
from __future__ import annotations

import math

import numpy as np

from .core.framework import Variable
from .core.lod import LoDTensor, lod_from_seq_lens
from .core.types import np_dtype

__all__ = ["DataFeeder"]


def _allocate(shape, dtype):
    """A packed column's own memory (a seam: tests/test_async_feed.py
    substitutes an allocator with a chosen alignment)."""
    return np.empty(shape, dtype)


def _as_declared(arr, shape):
    """rows carried flat features: view them under the declared shape"""
    if shape is not None and len(shape) > arr.ndim:
        return arr.reshape((arr.shape[0],) + tuple(
            d if d > 0 else -1 for d in shape[1:]))
    return arr


def _pack_dense(col, dtype, shape, dest=None):
    """`np.asarray(col, dtype=dtype)` under the declared `shape`, built
    by copying each row into its place in ONE array: `dest` where it has
    the dtype and the shape the batch comes to (then `dest` itself is
    returned), else a new array, and a `dest` that does not fit is left
    unwritten.  `np.copyto` lets go of the GIL while it copies a row,
    and into memory already touched it costs no page faults: a tenth of
    `np.asarray`'s time for 256 rows of 602 KB (PERF.md, PR 37).  Rows
    whose shapes differ raise ValueError as `np.asarray` does; they are
    compared here because `copyto` would broadcast a (1,) row into a
    (3,) slot."""
    if not col:
        return _as_declared(np.asarray(col, dtype=dtype), shape)
    row_shape = np.shape(col[0])
    packed = (len(col),) + row_shape
    fits = (dest is not None and dest.dtype == dtype
            and dest.size == math.prod(packed)
            and dest.flags.c_contiguous and dest.flags.writeable
            and _as_declared(dest.reshape(packed), shape).shape == dest.shape)
    out = dest if fits else _as_declared(_allocate(packed, dtype), shape)
    slots = out.reshape(packed)  # contiguous: a view, never a copy
    for i, row in enumerate(col):
        if not isinstance(row, np.ndarray):
            row = np.asarray(row, dtype=dtype)
        if row.shape != row_shape:
            raise ValueError(
                f"rows of one feed slot differ in shape: row 0 is "
                f"{row_shape}, row {i} is {row.shape}")
        np.copyto(slots[i, ...], row, casting="unsafe")
    return out


class DataFeeder:
    def __init__(self, feed_list, place=None, program=None):
        self.feed_list = feed_list
        self.place = place

    def feed(self, iterable, out=None):
        """iterable of rows; each row has one slot value per feed var.
        lod_level==0 slots are stacked dense; lod_level==1 slots are lists of
        variable-length sequences, packed flat + offset table (LoD).

        The rows are COPIED before this returns (a reader may reuse its
        row memory), into arrays the caller owns.  `out` is the
        prefetch pipeline's side of that (reader/pipeline.py), not an
        option for callers: {feed name: an array this feeder returned
        for an earlier batch}, whose memory the pipeline says may be
        written again.  A dense slot is packed into its entry where
        that still fits the batch and comes back as that very array;
        where it does not (a short last batch) the entry is left alone
        and a new array comes back.

        Emits a `feed.pack` profiler event: under `Trainer.train` it
        runs on the prefetch worker's thread (reader/pipeline.py),
        beside the device's step; in a hand-written serial loop it is
        host time the device sits idle."""
        from . import profiler

        with profiler.record_event("feed.pack"):
            return self._feed(iterable, out or {})

    def _feed(self, iterable, dests):
        rows = list(iterable)
        out = {}
        for i, var in enumerate(self.feed_list):
            name = var.name if isinstance(var, Variable) else str(var)
            dtype = np_dtype(var.dtype if isinstance(var, Variable)
                             else "float32")
            lod_level = getattr(var, "lod_level", 0)
            col = [r[i] for r in rows]
            if lod_level == 0:
                out[name] = _pack_dense(col, dtype,
                                        getattr(var, "shape", None),
                                        dests.get(name))
            elif lod_level == 1:
                seqs = [np.asarray(s, dtype=dtype) for s in col]
                seq_lens = [len(s) for s in seqs]
                flat = (np.concatenate(seqs, axis=0) if seqs
                        else np.zeros((0,), dtype=dtype))
                if flat.ndim == 1:
                    flat = flat.reshape(-1, 1)
                out[name] = LoDTensor(flat, [lod_from_seq_lens(seq_lens)])
            else:  # nested sequences: col is list of list of sequences
                outer_lens, inner, flat_parts = [], [], []
                for doc in col:
                    outer_lens.append(len(doc))
                    for s in doc:
                        s = np.asarray(s, dtype=dtype)
                        inner.append(len(s))
                        flat_parts.append(s)
                flat = (np.concatenate(flat_parts, axis=0) if flat_parts
                        else np.zeros((0,), dtype=dtype))
                if flat.ndim == 1:
                    flat = flat.reshape(-1, 1)
                # paddle LoD convention: level-k offsets index into level-k+1
                # entries (rows for the last level)
                inner_offsets = lod_from_seq_lens(inner)
                outer_offsets = lod_from_seq_lens(outer_lens)
                out[name] = LoDTensor(flat, [outer_offsets, inner_offsets])
        return out
