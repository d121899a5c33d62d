#!/usr/bin/env python3
"""Check the benchmark's own arithmetic and wiring, on the CPU, touching
no device:

    JAX_PLATFORMS=cpu python perf/selfcheck.py

  * perf/reduce_trace.py on the capture recorded on a v5e under
    perf/testdata/: busy and idle time, time per op, the device clock's
    lag, and the attribution of idle gaps to host spans;
  * every cell of BENCHMARK.json resolves by name: configuration,
    traffic mix, job, reference, and one reader per per-layer metric
    whose `LAYER`, `UNIT`, `MOVES` and `SOURCE` are what BENCHMARK.json
    says, each moving an end-to-end metric that its cells report;
  * perf/flops.py against numbers known from the papers;
  * the serving mix's table: every seed offers the same lengths.
Exit code 0 and a last line `selfcheck ok` when all of it holds.
"""
from __future__ import annotations

import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
sys.path.insert(0, ROOT)
sys.path.insert(0, PERF_DIR)

import common  # noqa: E402
import flops  # noqa: E402
import reduce_trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def near(got, want, rel=1e-6):
    return abs(got - want) <= rel * max(abs(want), 1e-30)


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print("ok  ", what)


def check_reduce_trace():
    """The capture: 4 x (two calls of a 2048^3 bf16 matmul+tanh, then a
    10 ms host sleep), annotated `perf.step` / `perf.host_sleep`, on one
    v5e chip.  Numbers below were read from the file by hand (the
    events of `XLA Ops`, summed) when it was recorded in PR 23."""
    path = os.path.join(PERF_DIR, "testdata", "v5e_probe.xplane.pb")
    tr = reduce_trace.Trace.from_file(path)
    check(tr.chips() == [0], "one device plane, /device:TPU:0")
    check(len(tr.device_ops[0]) == 24 and len(tr.modules[0]) == 8,
          "24 device ops in 8 program runs")
    check(near(tr.shift_ns, 1606189.0),
          "the device clock lags the host by 1.606 ms")
    r = reduce_trace.reduce(path)
    check(near(r["busy_s"], 0.000812934, 1e-4),
          "device busy 0.813 ms (8 runs of ~0.1 ms)")
    check(near(r["window_s"], 0.035433818, 1e-4), "window 35.4 ms")
    ops = r["op_seconds"]
    check(near(ops["convolution_tanh_fusion"], 0.000732486, 1e-4)
          and near(ops["copy-done"], 8.0342e-05, 1e-3),
          "per-op time: the fusion 0.732 ms, copy-done 0.080 ms")
    idle = r["idle_by_span"]
    check(near(sum(idle.values()), r["window_s"] - r["busy_s"], 1e-6),
          "idle gaps add up to window minus busy")
    check(near(idle["perf.host_sleep"], 0.032349589, 1e-3)
          and idle["perf.host_sleep"] > 10 * idle["perf.step"],
          "the idle time goes to perf.host_sleep (32.3 ms), not perf.step")
    share = 1 - r["busy_s"] / r["window_s"]
    check(near(share, 0.97706, 1e-4), "idle share 97.7%")
    check(r["breakdown"]["device_ops"][0][0] == "convolution_tanh_fusion"
          and r["breakdown"]["idle_gaps"][0][0] == "perf.host_sleep",
          "breakdown lists the longest first")
    check(r["collective_s"] == 0, "no collective on one chip")
    # the interval arithmetic by itself
    check(reduce_trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
          and reduce_trace.gaps([(0, 3), (5, 6)], 0, 8) == [(3, 5), (6, 8)],
          "union and gaps of intervals")
    check(reduce_trace.COLLECTIVE.search("all-reduce-done.3")
          and reduce_trace.COLLECTIVE.search("all-reduce.17")
          and not reduce_trace.COLLECTIVE.search("fusion.3"),
          "collective ops are told by name")


def check_wiring():
    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    check("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1,
          "setup_s is an end-to-end metric with a bound of at most 0.1")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        check(len(set(names)) == len(names)
              and all(NAME.match(n) for n in names),
              f"{group}: names are unique and well formed")
    for m in bench["end_to_end"]:
        check(0 < m["bound"] <= 0.1 and m["source"] in (
            "host_clock", "device_trace"), f"bound and source of {m['name']}")
    files = [c["file"] for c in bench["configs"]]
    check(len(set(files)) == len(files), "one file per configuration")
    used = {w["config"] for w in bench["workloads"]}
    check(used == {c["name"] for c in bench["configs"]},
          "every configuration is used by a cell")
    for w in bench["workloads"]:
        cell = common.Cell(bench, w["name"], seed=0, seconds=1, trace=True,
                           rehearse=False, t_process_start=0.0)
        check(callable(getattr(cell.job(), "run", None)),
              f"{w['name']}: job {cell.traffic['job']!r} has run(cell)")
        cell.reference()
        mine = {m["name"] for m in cell.end_to_end}
        check("setup_s" in mine and len(mine) >= 2 and cell.per_layer,
              f"{w['name']}: setup_s, another end-to-end metric and a "
              "per-layer metric")
        check(len(w["why"]) <= 200 and w["chips"] in (1, 4),
              f"{w['name']}: why fits 200 characters, chips 1 or 4")
        for spec in cell.per_layer:
            mod = common.load_module(os.path.join(
                PERF_DIR, "metrics", spec["name"] + ".py"))
            same = (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
                spec["layer"], spec["unit"], spec["moves"], spec["source"])
            if not (same and callable(mod.compute)
                    and spec["moves"] in mine):
                raise AssertionError(
                    f"{w['name']}: per-layer metric {spec['name']!r} does "
                    "not agree with its reader, or moves a metric the cell "
                    "does not report")
        print("ok  ", f"{w['name']}: {len(cell.per_layer)} per-layer "
              "readers agree with BENCHMARK.json")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    check(four <= max(1, len(bench["workloads"]) // 4),
          "at most a quarter of the cells (and one always) ask for 4 chips")
    for kind in common.load_json(os.path.join(
            PERF_DIR, "peaks.json"))["devices"].values():
        check(kind["bf16_flops_per_s"] > 0 and kind["hbm_bytes_per_s"] > 0,
              "a row of perf/peaks.json")


def check_flops():
    check(near(flops.resnet_forward_macs(50, 224, 1000), 3.858e9, 1e-3),
          "ResNet-50 forward: 3.86 G multiply-adds (He et al. give 3.8e9)")
    d, f = 2048, 8192
    check(flops.lm_layer_params(d, f) == 12 * d * d,
          "a decoder block holds 12 d^2 weights at ffn = 4d")
    per_tok = flops.lm_train_flops_per_token(d, f, 24, 50272, 2048)
    check(near(per_tok, 6 * (24 * 12 * d * d + d * 50272)
               + 6 * 24 * 2048 * d, 1e-12),
          "LM training: 6 per weight plus causal attention")
    tick = flops.lm_decode_tick(d, f, 24, 50272, 32, 512)
    check(near(tick["weight_bytes"], 2.62e9, 1e-2),
          "a decode tick reads 2.62 GB of bf16 weights (3.2 ms of HBM)")


def check_serving_table():
    serve = common.load_module(os.path.join(PERF_DIR, "jobs",
                                            "serve_closed.py"))
    lengths = common.load_json(os.path.join(
        PERF_DIR, "traffic", "closed32.json"))["lengths"]
    table = [tuple(r) for r in lengths["table"]]
    check(len(table) == 64 and len(table) % lengths["block"] == 0,
          "closed32: a literal table of 64 pairs in blocks of 8")
    check(all(16 <= p <= 256 and 16 <= o <= 128 for p, o in table)
          and max(p + o - 1 for p, o in table) <= 512,
          "closed32: lengths inside their clips and the context")
    a = serve.permuted_table(lengths, 1)
    b = serve.permuted_table(lengths, 3000000019)
    check(sorted(a) == sorted(b) == sorted(table) and a != b,
          "closed32: seeds permute the table and never change a length")
    size = lengths["block"]
    sums = {tuple(sorted(a[i:i + size])) for i in range(0, 64, size)}
    check(sums == {tuple(sorted(table[i:i + size]))
                   for i in range(0, 64, size)},
          "closed32: a permutation keeps the balanced blocks whole")
    check(serve.permuted_table(lengths, 7) == serve.permuted_table(lengths, 7),
          "closed32: the same seed gives the same order")


def main() -> int:
    check_reduce_trace()
    check_wiring()
    check_flops()
    check_serving_table()
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
