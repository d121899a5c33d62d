#!/usr/bin/env python
"""Calibrate XLA's "bytes accessed" against measured HBM time.

`hbm_util` (harness.roofline_fields) divides XLA's cost-analysis byte
count by measured time x the chip's peak bandwidth.  Two questions
decide whether that number is an instrument or noise:

1. **Is the COUNT right?**  Checked statically (no timing involved):
   for streaming kernels whose traffic is known analytically (copy,
   axpy), XLA's count must equal ground truth.  It does, exactly
   (`count_ratio = 1.0` below).  For FUSED model steps the count
   over-reads (a buffer consumed by two fusions counts twice): the
   seq2seq transformer step measures hbm_util ~1.43 at a
   sync-validated step time, bounding the over-count at ~1.43x — the
   origin of the plausibility band `hbm_util <= 1.5`
   (harness.HBM_UTIL_BOUND).

2. **Is the TIME right?**  This script calibrates on the ResNet-50
   bs256 training step rather than on pure-bandwidth microkernels (whose
   timings were not trusted when the band was set) — a config whose
   wall-clock was independently
   reproduced with synchronous per-step probes, whose arithmetic
   intensity (~82 FLOP/B) sits 3x below the v5e ridge point, and whose
   XLA count matched hand analysis within a few percent.  The achieved
   fraction of datasheet bandwidth on that step is the empirical
   "speed of light" for fused real models on this chip.

Run on the real chip: python benchmark/calibrate_hbm.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from harness import (HBM_UTIL_BOUND, chip_specs, plausibility,
                     roofline_from_cost, time_program_scan)


def count_exactness():
    """XLA bytes-accessed vs analytic ground truth on unfused streaming
    kernels — a pure cost-analysis check, no device timing involved."""
    import jax
    import jax.numpy as jnp

    n = 16 * 1024 * 1024  # 64 MB f32
    x = jnp.zeros((n,), jnp.float32)
    rows = []
    for name, fn, analytic in (
            ("copy", lambda v: v + 1.0, 2 * 4 * n),
            ("axpy", lambda v: 0.5 * v + 0.25, 2 * 4 * n),
            ("sum", lambda v: jnp.sum(v), 4 * n)):
        cost = jax.jit(fn).lower(x).compile().cost_analysis() or {}
        got = cost.get("bytes accessed", 0.0)
        rows.append({"case": name,
                     "analytic_mb": round(analytic / 1e6, 1),
                     "xla_mb": round(got / 1e6, 1),
                     "count_ratio": round(got / analytic, 3)})
    return rows


def measured_band():
    """ResNet-50 bs256 amp step via the scan instrument: the achieved
    fraction of datasheet HBM bandwidth on a sync-validated,
    memory-bound real model."""
    import paddle_tpu as fluid

    import bench  # noqa: E402  (repo-root bench.py, on path via line 38)

    fluid.amp.enable_bf16()
    main_p, startup, avg = bench.build_resnet50_train(256, "bfloat16")
    r = np.random.RandomState(0)
    from paddle_tpu.core.types import np_dtype
    feeds = {
        "img": r.rand(256, 3, 224, 224).astype(np_dtype("bfloat16")),
        "label": r.randint(0, 1000, (256, 1)).astype(np.int32),
    }
    ms, cost = time_program_scan(main_p, startup, feeds, avg.name,
                                 outer_iters=3, k_inner=4,
                                 with_cost=True)
    fields = roofline_from_cost(ms, cost)
    ok, reason = plausibility(fields, ms)
    return {
        "model": "resnet50_bs256_amp_train",
        "ms_per_step": round(ms, 2),
        "hbm_gb_per_step": fields.get("hbm_gb_per_step"),
        "achieved_bw_frac_of_peak": fields.get("hbm_util"),
        "valid": ok, **({"invalid_reason": reason} if not ok else {}),
    }


def main():
    kind, peak, hbm = chip_specs()
    if hbm is None:
        raise SystemExit(f"no HBM spec for device {kind!r} — run on TPU")
    band = measured_band()
    out = {
        "device": kind,
        "hbm_peak_gb_s": hbm / 1e9,
        "count_exactness": count_exactness(),
        "measured": band,
        "fused_overcount_bound": 1.43,  # seq2seq step, sync-validated
        "acceptance_band": f"hbm_util <= {HBM_UTIL_BOUND} is plausible "
                           "(fused over-count allowance); beyond it is "
                           "a timing artifact (harness.plausibility, "
                           "benches exit non-zero)",
        "valid": band["valid"],
    }
    print(json.dumps(out))
    if not out["valid"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
