"""Device-mesh utilities — the TPU answer to device enumeration and
process-group setup.

Replaces (SURVEY.md §2.5/§5.8): `get_places_op`
(/root/reference/paddle/fluid/operators/get_places_op.cc), NCCL communicator
init (operators/nccl_op.cc ncclInit), pserver endpoint lists
(distribute_transpiler.py pserver_endpoints) and etcd membership
(go/pserver/etcd_client.go).  On TPU, membership is the jax distributed
coordination service and topology is a `jax.sharding.Mesh` whose axes map
onto ICI; DCN-spanning meshes put the slowest-varying axis across hosts.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_mesh", "get_places", "data_sharding", "replicated",
           "init_distributed", "PartitionSpec", "NamedSharding",
           "shard_map"]


# Every shard_map in this package goes through this spelling: the bodies
# mix manual and auto axes plus masked psums, which the varying-manual-
# axes checker rejects, so it is off for all of them.  `axis_names` (a
# set) names the MANUAL axes — values inside the body have a local view
# of them and collectives may reference them; axes left out stay in
# GSPMD-auto mode.  Omitted = every mesh axis manual.
shard_map = functools.partial(jax.shard_map, check_vma=False)


def get_places(device_count: Optional[int] = None):
    """Device list (reference get_places_op / fluid.layers.get_places)."""
    devs = jax.devices()
    return devs[:device_count] if device_count else devs


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None
              ) -> Mesh:
    """Build a named mesh, e.g. make_mesh({'dp': 2, 'tp': 4}).

    Axis order follows dict order: earlier axes vary slowest — put the
    inter-host (DCN) axis first, ICI axes last, so collectives on the
    fast-varying axes ride ICI neighbors."""
    names = tuple(axes.keys())
    shape = tuple(axes.values())
    n = int(np.prod(shape))
    devs = list(devices if devices is not None else jax.devices())[:n]
    if len(devs) < n:
        raise ValueError(f"mesh needs {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs).reshape(shape), names)


def data_sharding(mesh: Mesh, batch_axis: str = "dp") -> NamedSharding:
    """Shard dim-0 (batch) over `batch_axis`, replicate the rest."""
    return NamedSharding(mesh, PartitionSpec(batch_axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: int = 1, process_id: int = 0):
    """Multi-host bring-up (replaces etcd registration + gRPC endpoints):
    wires this process into the jax coordination service.  No-op for
    single-process runs.

    Arguments default from the PADDLE_TPU_{COORDINATOR,NUM_PROCESSES,
    PROCESS_ID} env vars set by tools/launch.py --coordinator mode."""
    import os
    if coordinator_address is None:
        coordinator_address = os.environ.get("PADDLE_TPU_COORDINATOR")
        if coordinator_address is not None:
            num_processes = int(
                os.environ.get("PADDLE_TPU_NUM_PROCESSES", "1"))
            process_id = int(os.environ.get("PADDLE_TPU_PROCESS_ID", "0"))
    if num_processes <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def count_collectives(hlo_text: str) -> dict:
    """Counts of cross-device collective instructions in module text —
    the one shared digest behind ParallelExecutor.compiled_collectives
    and composite.collective_counts.  Optimized-HLO instruction forms:
    `<name> = <type> <op>(`; async pairs appear as
    <op>-start(/<op>-done( and count once.  `<op>(` never matches operand
    references (those are `%<op>.N`).  The traced (StableHLO) module
    spells the same ops `stablehlo.all_reduce` etc.; both count."""
    import re

    out = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        n = len(re.findall(
            rf"{op}(?:-start)?\(|stablehlo\.{op.replace('-', '_')}\b",
            hlo_text))
        if n:
            out[op] = n
    return out


# dtype token -> bytes/element for HLO result shapes (collective_bytes)
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_HLO_SHAPE_RE = r"(?:pred|[suf]\d+|bf16|c\d+)\[[\d,]*\]"


def collective_bytes(hlo_text: str) -> dict:
    """Payload BYTES of cross-device collective instructions in
    optimized HLO text: per collective type, the summed element bytes of
    every instruction's result shape(s) — tuple-shaped and async
    (`-start`) forms included.  This is the measured side of the static
    `analysis.cost_model.estimate_comm` volume (same logical-payload
    convention: an all-reduce's result shape IS its operand shape)."""
    import re

    def shape_bytes(tok: str) -> int:
        dtype, dims = tok.split("[", 1)
        dims = dims.rstrip("]")
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        return n * _HLO_DTYPE_BYTES.get(dtype, 4)

    out = {}
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all"):
        total = 0
        # `%name = <shape> op(` and `%name = (<shape>, <shape>) op(`
        for m in re.finditer(
                rf"=\s*(\(?(?:{_HLO_SHAPE_RE}(?:\{{[\d,]*\}})?"
                rf"(?:,\s*)?)+\)?)\s*{op}((?:-start)?)\(", hlo_text):
            toks = re.findall(_HLO_SHAPE_RE, m.group(1))
            if m.group(2) and len(toks) > 1:
                # async `-start` result is a tuple of (operand, result
                # [, context scalars]) — the logical payload is the
                # RESULT shape only (for all-reduce/permute operand and
                # result are identical; summing both would double-count
                # vs the sync form).  Drop scalar context tokens (the
                # u32[] pair some backends append to permute-start)
                # BEFORE picking the result, or the payload reads as
                # 4 bytes
                tensors = [t for t in toks if "[]" not in t]
                toks = (tensors or toks)[-1:]
            for tok in toks:
                total += shape_bytes(tok)
        if total:
            out[op] = total
    return out
