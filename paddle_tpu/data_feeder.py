"""DataFeeder: python minibatch -> feed dict of arrays / LoDTensors.

Reference: /root/reference/python/paddle/v2/fluid/data_feeder.py:1-115
(DataToLoDTensorConverter).
"""
from __future__ import annotations

import numpy as np

from .core.framework import Variable
from .core.lod import LoDTensor, lod_from_seq_lens
from .core.types import np_dtype

__all__ = ["DataFeeder"]


class DataFeeder:
    def __init__(self, feed_list, place=None, program=None):
        self.feed_list = feed_list
        self.place = place

    def feed(self, iterable):
        """iterable of rows; each row has one slot value per feed var.
        lod_level==0 slots are stacked dense; lod_level==1 slots are lists of
        variable-length sequences, packed flat + offset table (LoD).

        Emits a `feed.pack` profiler event: under `Trainer.train` it
        runs on the prefetch worker's thread (reader/pipeline.py),
        beside the device's step; in a hand-written serial loop it is
        host time the device sits idle."""
        from . import profiler

        with profiler.record_event("feed.pack"):
            return self._feed(iterable)

    def _feed(self, iterable):
        rows = list(iterable)
        out = {}
        for i, var in enumerate(self.feed_list):
            name = var.name if isinstance(var, Variable) else str(var)
            dtype = np_dtype(var.dtype if isinstance(var, Variable)
                             else "float32")
            lod_level = getattr(var, "lod_level", 0)
            col = [r[i] for r in rows]
            if lod_level == 0:
                arr = np.asarray(col, dtype=dtype)
                shape = getattr(var, "shape", None)
                if shape is not None and len(shape) > arr.ndim:
                    # rows carried flat features: reshape to declared shape
                    arr = arr.reshape((len(rows),) + tuple(
                        d if d > 0 else -1 for d in shape[1:]))
                out[name] = arr
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
