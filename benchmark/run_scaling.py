"""Data-parallel scaling efficiency — the BASELINE.json headline's third
metric ("1→64 chip scaling eff"); the reference's analogue tables are the
4×K40m speedups (benchmark/README.md:70-84, e.g. AlexNet 3.85×/4 GPUs) and
the k8s trainer-count scaling grid (benchmark/cluster/vgg16/README.md:43-48,
60-93% efficiency at 20-100 trainers).

Per device-count N: jit one ResNet training step over a {"dp": N} mesh
(ParallelExecutor — same psum-over-ICI path `dryrun_multichip` validates),
batch = N × per-device batch, report images/sec and efficiency vs N=1.

With real multi-chip hardware this measures ICI scaling directly.  With a
single chip / CPU, pass `--virtual` to respawn per-N subprocesses with
`--xla_force_host_platform_device_count=N` (validates the SPMD path and
measures collective+partitioning overhead; physical cores are shared, so
virtual "efficiency" is a lower bound, not an ICI measurement).

Usage: python benchmark/run_scaling.py [--devices 1,2,4,8] [--virtual]
       [--batch-per-dev 64] [--iters 10] [--depth 50] [--img 32]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def run_single(n, batch_per_dev, iters, depth, img, overlap="off"):
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.models.resnet import resnet_cifar10, resnet_imagenet

    batch = n * batch_per_dev
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        data = fluid.layers.data(name="img", shape=[3, img, img],
                                 dtype="bfloat16")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if img <= 64:
            predict = resnet_cifar10(data, class_dim=10, depth=min(depth, 32))
        else:
            predict = resnet_imagenet(data, class_dim=1000, depth=depth)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg = fluid.layers.mean(cost)
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg)

    if overlap != "off":
        # the MAINLINE multichip path (docs/performance.md "Multichip
        # sharding"): spmd transpile + bucketed-psum grad overlap, so
        # scaling rounds measure the transpiler, not a hand-built
        # executor.  ResNet's training-mode batch_norm makes 'auto'
        # stand down to the GSPMD step — the record says why.
        t = fluid.ShardingTranspiler()
        # shard_optimizer_states=False: the direct ParallelExecutor arm
        # below runs without ZeRO-1, and an A/B between the arms must
        # not attribute ZeRO's placement collectives to the overlap path
        t.transpile(program=main, startup_program=startup,
                    mesh={"dp": n}, overlap=overlap,
                    shard_optimizer_states=False)
        pe = t.build_executor(["img", "label"], [avg])
    else:
        pe = parallel.ParallelExecutor(main, ["img", "label"], [avg],
                                       mesh={"dp": n},
                                       startup_program=startup)
    r = np.random.RandomState(0)
    feed = {"img": r.rand(batch, 3, img, img).astype("float32")
            .astype("bfloat16"),
            "label": r.randint(0, 10, (batch, 1)).astype(np.int32)}
    out = pe.run(feed)          # compile + warmup
    jax.block_until_ready(out[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = pe.run(feed)
    jax.block_until_ready(out[0])
    ms = (time.perf_counter() - t0) / iters * 1000
    out = {"devices": n, "batch": batch, "ms_per_batch": round(ms, 2),
           "images_per_sec": round(batch / ms * 1000, 1)}
    if overlap != "off":
        out["overlap"] = dict(pe.overlap_info)
    if jax.default_backend() != "tpu":
        # the communication structure is meaningful even when virtual
        # throughput is not: dp-N must show grad all-reduces (and only
        # those), pinned per N from the compiled HLO.  Skipped on real
        # chips: compiled_collectives lowers+compiles a second copy of
        # the step (minutes of compile for a structure that is identical
        # to the CPU lowering's).
        out["collectives"] = pe.compiled_collectives(feed)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--batch-per-dev", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--depth", type=int, default=32)
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--virtual", action="store_true",
                    help="respawn per-N with virtual CPU devices")
    ap.add_argument("--overlap", default="off",
                    choices=["off", "auto"],
                    help="'auto': run the mainline spmd-transpiler "
                         "path; the bucketed compute/collective "
                         "overlap engages where eligible and the "
                         "record carries overlap_info (ResNet's "
                         "training-mode batch_norm makes it stand "
                         "down with the reason recorded)")
    ap.add_argument("--single", type=int, default=0,
                    help="(internal) run one N in this process")
    a = ap.parse_args()

    if a.single:
        print(json.dumps(run_single(a.single, a.batch_per_dev, a.iters,
                                    a.depth, a.img, a.overlap)))
        return

    counts = [int(x) for x in a.devices.split(",")]
    results = []
    for n in counts:
        if a.virtual:
            env = dict(os.environ,
                       JAX_PLATFORMS="cpu",
                       XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                                  f" --xla_force_host_platform_device_count={n}"))
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--single", str(n),
                 "--batch-per-dev", str(a.batch_per_dev),
                 "--iters", str(a.iters), "--depth", str(a.depth),
                 "--img", str(a.img), "--overlap", a.overlap],
                env=env, capture_output=True, text=True, check=True)
            results.append(json.loads(out.stdout.strip().splitlines()[-1]))
        else:
            import jax

            if n > len(jax.devices()):
                print(json.dumps({"devices": n,
                                  "skipped": "not enough devices"}))
                continue
            results.append(run_single(n, a.batch_per_dev, a.iters,
                                      a.depth, a.img, a.overlap))
    if results:
        base = results[0]["images_per_sec"] / results[0]["devices"]
        for rec in results:
            rec["scaling_efficiency"] = round(
                rec["images_per_sec"] / (rec["devices"] * base), 3)
            print(json.dumps(rec))


if __name__ == "__main__":
    main()
