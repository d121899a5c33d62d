#!/usr/bin/env python
"""Cluster-VGG16 protocol cells (reference benchmark/cluster/vgg16):
CIFAR-shape vgg16_bn_drop samples/s.

The reference's published cells are 20-trainer/10-pserver k8s pods
(190-258 samples/s at bs 32-256) plus a single-node single-thread row
(15.4-16.8 samples/s).  One chip + one host cannot reproduce the pod
grid; this script fills what is honest here:

  * default          — single-process samples/s on the current backend
                       (pin to one CPU core via
                       `taskset -c 0` + XLA_FLAGS=--xla_cpu_multi_thread_eigen=false
                       to compare against the single-thread row)
  * --cluster P T    — a REAL local pserver cluster (P pservers x T
                       trainer subprocesses over the TCP transport,
                       DistributeTranspiler) reporting aggregate
                       samples/s — the protocol at laptop scale, not a
                       pod-grid claim.

Prints one JSON line per measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def build(batch):
    import paddle_tpu as fluid
    from paddle_tpu.models.vgg import vgg16_bn_drop

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="pixel", shape=[3, 32, 32],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        predict = vgg16_bn_drop(img, class_dim=10)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        opt_ops, params_grads = fluid.SGD(
            learning_rate=0.01).minimize(loss)
    return main, startup, loss, opt_ops, params_grads


def run_single(batch, iters):
    import numpy as np

    sys.path.insert(0, HERE)
    from harness import time_program

    main, startup, loss, _, _ = build(batch)
    r = np.random.RandomState(0)
    feeds = {"pixel": r.rand(batch, 3, 32, 32).astype(np.float32),
             "label": r.randint(0, 10, (batch, 1)).astype(np.int32)}
    ms = time_program(main, startup, feeds, loss.name, iters)
    print(json.dumps({
        "bench": "cluster_vgg16", "mode": "single", "batch": batch,
        "ms_per_batch": round(ms, 2),
        "samples_per_sec": round(batch / ms * 1000, 2),
        "ref_single_thread_samples_per_sec":
            {32: 15.44, 64: 16.32, 128: 16.74, 256: 16.79}.get(batch),
    }))


def run_trainer_role(batch, iters):
    """Body for one cluster role process (env-var convention)."""
    import numpy as np

    import paddle_tpu as fluid

    role = os.environ["TRAINING_ROLE"]
    trainers = int(os.environ["PADDLE_INIT_NUM_GRADIENT_SERVERS"])
    main, startup, loss, opt_ops, params_grads = build(batch)
    with fluid.program_guard(main, startup):
        t = fluid.DistributeTranspiler()
        t.transpile(optimize_ops=opt_ops, params_grads=params_grads,
                    trainers=trainers, pservers=os.environ["PSERVERS"])
    exe = fluid.Executor(fluid.CPUPlace())
    if role == "PSERVER":
        ep = os.environ["SERVER_ENDPOINT"]
        exe.run(t.get_startup_program(ep))
        exe.run(t.get_pserver_program(ep))
        return
    exe.run(startup)
    prog = t.get_trainer_program()
    r = np.random.RandomState(0)
    feeds = {"pixel": r.rand(batch, 3, 32, 32).astype(np.float32),
             "label": r.randint(0, 10, (batch, 1)).astype(np.int32)}
    exe.run(prog, feed=feeds, fetch_list=[loss])  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        exe.run(prog, feed=feeds, fetch_list=[loss])
    dt = time.perf_counter() - t0
    print(json.dumps({"role_samples_per_sec":
                      round(batch * iters / dt, 2)}), flush=True)


def run_cluster(batch, iters, n_pservers, n_trainers):
    import threading

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from launch import launch_pserver_cluster

    # child processes rebuild env from os.environ (launch.py); APPEND to
    # XLA_FLAGS — clobbering would silently drop operator-set flags like
    # --xla_cpu_multi_thread_eigen=false and invalidate the measurement
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=1"
                               ).strip()
    procs = launch_pserver_cluster(
        os.path.abspath(__file__),
        ["--role-body", "--batch", str(batch), "--iters", str(iters)],
        n_pservers, n_trainers,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    total = 0.0
    ok = True
    try:
        # drain every trainer pipe CONCURRENTLY: sync-SGD trainers move in
        # lock-step through the pserver barrier, so one trainer blocked on
        # a full unread pipe would stall the whole cluster
        outs = {}

        def drain(p):
            outs[p] = p.communicate(timeout=1800)[0]

        threads = [threading.Thread(target=drain, args=(p,), daemon=True)
                   for role, p in procs if role == "trainer"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1800)
        for role, p in procs:
            if role != "trainer":
                continue
            m = re.search(r'\{"role_samples_per_sec": ([0-9.]+)\}',
                          outs.get(p) or "")
            if m:
                total += float(m.group(1))
            else:
                ok = False
    finally:
        for role, p in procs:
            if p.poll() is None:
                p.terminate()
    print(json.dumps({
        "bench": "cluster_vgg16", "mode": "pserver_cluster",
        "pservers": n_pservers, "trainers": n_trainers, "batch": batch,
        "aggregate_samples_per_sec": round(total, 2), "ok": ok,
        "note": "local-host protocol run (TCP pserver transport); the "
                "reference's 20-trainer k8s cells are not reproducible "
                "on one host",
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cluster", nargs=2, type=int, metavar=("P", "T"))
    ap.add_argument("--role-body", action="store_true")
    args = ap.parse_args()
    if args.role_body:
        run_trainer_role(args.batch, args.iters)
    elif args.cluster:
        run_cluster(args.batch, args.iters, *args.cluster)
    else:
        run_single(args.batch, args.iters)


if __name__ == "__main__":
    main()
