"""Benchmark entry — prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Headline: ResNet-50 ImageNet-shape training throughput (images/sec), 1
chip, measured in the CONVERGENCE-VALID config — bf16 compute under amp
(f32 master weights; batch-norm statistics always accumulate f32
in-register, see ops/norm.py).  Baseline: the reference's best published
ResNet-50 training number, 84.08 img/s (2x Xeon 6148, MKL-DNN, bs=256;
BASELINE.md — the reference has no GPU ResNet-50 number in-tree).

The JSON also carries the honesty block (VERDICT r1 #1/#2):
  * tflops / mfu — achieved model FLOP/s vs chip bf16 peak, with the
    per-step XLA cost analysis taken from the SINGLE-STEP optimized
    module (harness.step_cost_analysis), not the whole scan program;
  * hbm_gb_per_step — peak live HBM of the optimized step module
    (memory_analysis: args + outputs + temps − donated aliases), a
    number that must fit the chip; hbm_traffic_gb / hbm_util — the
    XLA-counted traffic and achieved bandwidth vs the chip's HBM peak.
    ResNet-50 bs256 is MEMORY-bound on TPU (arithmetic intensity ~37
    FLOP/byte vs the v5e ridge point of ~240), so hbm_util ~1.0 means
    the chip is saturated even though mfu sits near the ~0.16 roofline
    ceiling for this model+batch;
  * compile_seconds — XLA compile wall time of the measured executable
    (the persistent compilation cache is pre-warmed across rounds:
    BENCH_COMPILE_CACHE=0 opts out);
  * convergence — a timed CIFAR-10 ResNet-20 run in the SAME numeric
    config (amp bf16) trained to a fixed accuracy, so the measured mode
    is demonstrably one that learns (reference --job=time + book-test
    discipline).  BENCH_CONVERGENCE=0 skips it.

Knobs: BENCH_BATCH, BENCH_ITERS, BENCH_DTYPE, BENCH_LAYOUT,
BENCH_REMAT=1 (rematerialized residual blocks), BENCH_MEMOPT=1 (arm
the memory_optimize flag: feed-buffer donation + dead-var freeing in
the executor legs), BENCH_STEP_ANALYSIS=0 (skip the single-step
cost/memory analysis compile), BENCH_COMPILE_CACHE=0 (no persistent
compile cache pre-warm), BENCH_AMP=0 (pure-bf16 mode, reported as the
secondary number in benchmark/README.md), BENCH_CONVERGENCE=0,
BENCH_PREFETCH=N (input
pipeline microbench: serial vs prefetch-depth-N + lazy-fetch steps/s
with the host-blocked fraction of each loop; BENCH_PREFETCH_ITERS
steps), BENCH_COMM=1 (pserver comm microbench: per-var serial wire
path vs bucketed+concurrent CommPool over 2 in-process pservers x 64
small grads, with a byte-identical final-params check), BENCH_SERVING=1
(generation serving microbench: the scheduler/optimization ablation
ladder — static batch, continuous, +prefix caching, +speculative
decoding, both — under the shared-prefix mixed-length open-loop load
generator, benchmark/run_serving.py, with tokens/s, p50/p99, shed
rate, KV-pool utilization, prefix hit rate, draft accept rate, the
KV-quantization residency table, and a Prometheus dump at
BENCH_SERVING_PROM if set.  Knobs: BENCH_SERVING_PREFIX_POOL/
_PREFIX_LEN/_PREFIX_HIT shape the shared-prefix workload,
BENCH_SERVING_SPEC_K sets the draft length, BENCH_SERVING_SPEC=0 /
BENCH_SERVING_QUANT=0 / BENCH_SERVING_KERNELS=0 skip those sections),
BENCH_KERNELS=1 (serving-kernel microbench: each fused Pallas kernel —
paged-attention decode fp32+int8, MoE gate+dispatch, fused bucket
update — vs its XLA oracle path, best-of-BENCH_KERNELS_TRIALS
throughput plus the kernel-backed static bytes-moved rows; off-TPU the
Pallas legs run interpret mode, so the CPU numbers demonstrate the
path, the bytes delta is the TPU argument), BENCH_SERVING_RAMP=1
(open-loop load ramp against a LIVE autoscaling fleet — router +
autoscaler + `cli serve` replicas from a warm-start model dir: rate
ramps up then down, reporting per-phase tokens/s and p99, the scaling
timeline, zero-failed accounting, and new-replica warm-start stats;
knobs BENCH_SERVING_RAMP_PEAK/_PHASE_S/_MAX).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))

import numpy as np

BASELINE_RESNET50_IMG_S = 84.08
BATCH = int(os.environ.get("BENCH_BATCH", "256"))
IMG = 224
DTYPE = os.environ.get("BENCH_DTYPE", "bfloat16")
ITERS = int(os.environ.get("BENCH_ITERS", "20"))
# amp (f32 master weights + bf16 compute) is the DEFAULT: the headline
# number must be a config somebody should actually train in (VERDICT r1
# weak #2); BENCH_AMP=0 measures the pure-bf16 path
AMP = os.environ.get("BENCH_AMP", "1").lower() in ("1", "true", "yes",
                                                   "on")
# NCHW measured faster end-to-end than NHWC on v5e with the affine BN
# (2535 vs 2359 img/s; XLA's layout assignment already places batch in
# the vector lanes where C < 128, see benchmark/README.md)
LAYOUT = os.environ.get("BENCH_LAYOUT", "NCHW").upper()
# BENCH_REMAT=1: rematerialize every residual block (jax.checkpoint) —
# the bytes-for-FLOPs trade for this memory-bound model (defaults to
# the framework `remat` flag, env PADDLE_TPU_REMAT)
REMAT = os.environ.get(
    "BENCH_REMAT",
    os.environ.get("PADDLE_TPU_REMAT", "0")).lower() in ("1", "true",
                                                         "yes", "on")
# BENCH_MEMOPT=1 arms the memory_optimize flag for the convergence/book
# legs (feed-buffer donation + dead-var freeing in the executors); the
# scan-timed headline always runs the donation plan via the harness
MEMOPT = os.environ.get(
    "BENCH_MEMOPT",
    os.environ.get("PADDLE_TPU_MEMORY_OPTIMIZE", "0")).lower() in (
        "1", "true", "yes", "on")
# ResNet-50 fwd at 224x224 is ~4.1 GMACs = ~8.2 GFLOPs (2*MACs — the MFU
# convention); train ~= 3x fwd.  Cross-check: XLA's own cost analysis
# counts 22.5 GFLOP/img for the whole train step
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 8.2e9


def build_resnet50_train(batch, dtype):
    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import resnet_imagenet

    img_shape = ([IMG, IMG, 3] if LAYOUT == "NHWC" else [3, IMG, IMG])
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=img_shape, dtype=dtype)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        predict = resnet_imagenet(img, class_dim=1000, depth=50,
                                  data_format=LAYOUT, remat=REMAT)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg_cost = fluid.layers.mean(cost)
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg_cost)
    return main, startup, avg_cost


def run_convergence(target_acc=0.85, max_seconds=None, batch=128):
    """CIFAR-10 ResNet-20 trained in the SAME numeric config as the
    headline (amp/pure-bf16 per BENCH_AMP) until test accuracy >=
    target_acc; returns a compact result dict with wall-clock.  Uses the
    real corpus when cached, the deterministic synthetic fallback
    offline (dataset/common.py policy) — the point is that the measured
    numeric mode LEARNS, not the dataset.

    BOTH executables (train step, test eval) are compiled BEFORE the
    clock starts — r2's driver run burned its whole 120 s budget on
    compiles and recorded steps=2, best_acc=0.0.  The training
    budget (BENCH_CONV_SECONDS, default 180) is pure post-compile
    wall-clock."""
    import paddle_tpu as fluid
    from paddle_tpu import dataset, reader
    from paddle_tpu.core.types import np_dtype
    from paddle_tpu.models.resnet import resnet_cifar10

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 32, 32], dtype=DTYPE)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        predict = resnet_cifar10(img, class_dim=10, depth=20)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg = fluid.layers.mean(cost)
        acc = fluid.layers.accuracy(input=predict, label=label)
        # clone BEFORE minimize: the test program must not carry the
        # optimizer ops (they would train on the test batch)
        test_prog = main.clone(for_test=True)
        fluid.Momentum(learning_rate=0.01, momentum=0.9).minimize(avg)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    def batches(rd):
        for b in reader.batch(rd, batch, drop_last=True)():
            imgs = np.stack([np.asarray(s[0], np_dtype(DTYPE))
                             .reshape(3, 32, 32) for s in b])
            lbls = np.asarray([s[1] for s in b], np.int64)[:, None]
            yield {"img": imgs, "label": lbls}

    if max_seconds is None:
        max_seconds = float(os.environ.get("BENCH_CONV_SECONDS", "180"))
    train_rd = dataset.cifar.train10()
    test_feed = next(batches(dataset.cifar.test10()))
    # precompile both executables, then re-run startup so the timed run
    # starts from a FRESH init (the executor folds a per-run step counter
    # into the RNG key, so these are new random weights, not a bit-exact
    # restore — the benchmark only needs an untrained start)
    t_c = time.perf_counter()
    exe.run(main, feed=next(batches(train_rd)), fetch_list=[avg],
            scope=scope)
    exe.run(test_prog, feed=test_feed, fetch_list=[acc], scope=scope)
    exe.run(startup, scope=scope)
    compile_seconds = time.perf_counter() - t_c
    t0 = time.perf_counter()
    steps = 0
    best = 0.0
    reached = False
    while time.perf_counter() - t0 < max_seconds and not reached:
        for feed in batches(train_rd):
            exe.run(main, feed=feed, fetch_list=[avg], scope=scope)
            steps += 1
            if steps % 20 == 0:
                a, = exe.run(test_prog, feed=test_feed, fetch_list=[acc],
                             scope=scope)
                best = max(best, float(np.asarray(a).reshape(-1)[0]))
                if best >= target_acc:
                    reached = True
                    break
            if time.perf_counter() - t0 >= max_seconds:
                break
    return {"model": "resnet20_cifar10", "target_acc": target_acc,
            "best_acc": round(best, 4), "reached": reached,
            "steps": steps,
            "seconds": round(time.perf_counter() - t0, 1),
            "compile_seconds": round(compile_seconds, 1)}


def run_prefetch_bench(depth, steps=None):
    """Input-pipeline microbench (BENCH_PREFETCH=N): one pass of a
    host-bound training loop measured serial, then with the prefetch
    pipeline (reader/pipeline.py) + lazy fetches.  Reports steps/s and
    samples/s for both modes and each loop's host-blocked fraction —
    serial blocks in feed packing (timed inline), the prefetched loop
    only in queue waits (PrefetchIterator.wait_s) — so the JSON shows
    both the speedup AND where the remaining stall is."""
    import paddle_tpu as fluid
    from paddle_tpu import reader as rdr
    from paddle_tpu.data_feeder import DataFeeder
    from paddle_tpu.reader.pipeline import prefetch_feeder

    steps = steps or int(os.environ.get("BENCH_PREFETCH_ITERS", "40"))
    bs, dim = 128, 256
    place = fluid.TPUPlace()

    def build():
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            h = fluid.layers.fc(input=x, size=512, act="relu")
            h = fluid.layers.fc(input=h, size=512, act="relu")
            p = fluid.layers.fc(input=h, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=p, label=y))
            fluid.SGD(learning_rate=0.01).minimize(loss)
        return main_p, startup, loss, [x, y]

    def sample_reader():
        # chunked numpy generate + normalize: real host work standing in
        # for decode/augment, sized so the serial loop is host-BOUND —
        # the regime the pipeline exists for (on a compute-bound loop
        # BENCH_PREFETCH correctly reports speedup ~1.0).  Work is done
        # in batch-size chunks like a real decoder: large numpy ops
        # release the GIL, so the worker thread genuinely overlaps the
        # consumer's dispatch (per-sample tiny-op python loops would
        # serialize on the GIL and measure contention, not the pipeline)
        r = np.random.RandomState(0)
        for _ in range(steps):
            v = r.standard_normal((bs, 12, dim)).astype(np.float32)
            v = (v - v.mean(axis=1, keepdims=True)) \
                / (v.std(axis=1, keepdims=True) + 1e-6)
            x = v.mean(axis=1)
            y = r.rand(bs, 1).astype(np.float32)
            for i in range(bs):
                yield (x[i], y[i])

    batches = rdr.batch(sample_reader, bs, drop_last=True)

    def measure(prefetch_depth):
        main_p, startup, loss, feed_vars = build()
        exe = fluid.Executor(place)
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        feeder = DataFeeder(feed_vars, place)
        warm = feeder.feed(next(iter(batches())))
        exe.run(main_p, feed=warm, fetch_list=[loss], scope=scope)
        misses_warm = exe.cache_stats()["misses"]
        host_blocked = 0.0
        t0 = time.perf_counter()
        if prefetch_depth == 0:
            it = iter(batches())
            while True:
                f0 = time.perf_counter()  # reader + pack both block here
                b = next(it, None)
                if b is None:
                    break
                feed = feeder.feed(b)
                host_blocked += time.perf_counter() - f0
                exe.run(main_p, feed=feed, fetch_list=[loss], scope=scope)
        else:
            it = prefetch_feeder(batches, feeder, place,
                                 depth=prefetch_depth)()
            fence_s = 0.0
            last = None
            for i, feed in enumerate(it):
                last, = exe.run(main_p, feed=feed, fetch_list=[loss],
                                scope=scope, return_numpy=False)
                if (i + 1) % 8 == 0:  # periodic fence (sync_every_n=8)
                    f0 = time.perf_counter()
                    np.asarray(last)
                    fence_s += time.perf_counter() - f0
            f0 = time.perf_counter()
            np.asarray(last)  # final fence: count finished work only
            fence_s += time.perf_counter() - f0
            # blocked = input starvation (queue waits) + fetch fences —
            # the two stalls the prefetched loop can still suffer
            host_blocked = it.wait_s + fence_s
        wall = time.perf_counter() - t0
        recompiles = exe.cache_stats()["misses"] - misses_warm
        return {"steps_per_sec": round(steps / wall, 2),
                "samples_per_sec": round(steps * bs / wall, 1),
                "host_blocked_fraction": round(host_blocked / wall, 4),
                "recompiles_after_warmup": recompiles}

    serial = measure(0)
    prefetched = measure(depth)
    return {"depth": depth, "steps": steps, "batch": bs,
            "serial": serial, "prefetch": prefetched,
            "speedup": round(prefetched["steps_per_sec"]
                             / serial["steps_per_sec"], 3)}


def run_comm_bench(n_grads=64, dim=16, rounds=4, pservers=2, trials=3):
    """Pserver comm microbench (BENCH_COMM=1): one trainer, `pservers`
    in-process VariableServers, `n_grads` small grads per sync round.
    Baseline = the pre-bucketing wire path (one SEND frame per var,
    endpoints visited serially, per-var GETs); fused = parallel/comm's
    CommPool (arrival-order SEND_BATCH buckets, concurrent endpoints,
    one batched GET per endpoint).  Walls are best-of-`trials` over the
    post-warmup rounds — round 0 absorbs the optimize-program compile on
    both sides — and the dict also reports whether both paths left the
    pservers with byte-identical parameters (they must)."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import comm
    from paddle_tpu.parallel.pserver import VariableClient, VariableServer

    names = [f"bw{i}" for i in range(n_grads)]
    owner = {n: i % pservers for i, n in enumerate(names)}
    rng = np.random.RandomState(7)
    grads = [{n: rng.rand(dim).astype(np.float32) for n in names}
             for _ in range(rounds + 1)]  # +1: untimed warmup round

    def build_servers():
        servers = []
        for s in range(pservers):
            scope = fluid.Scope()
            prog = fluid.Program()
            with fluid.program_guard(prog, fluid.Program()):
                blk = prog.global_block()
                blk.create_var(name="lr", shape=[1], dtype="float32",
                               persistable=True)
                for n in names:
                    if owner[n] != s:
                        continue
                    blk.create_var(name=n, shape=[dim], dtype="float32",
                                   persistable=True)
                    blk.create_var(name=n + "@GRAD", shape=[dim],
                                   dtype="float32", persistable=True)
                    blk.append_op("sgd",
                                  {"Param": [n], "Grad": [n + "@GRAD"],
                                   "LearningRate": ["lr"]},
                                  {"ParamOut": [n]}, {})
            scope.set_var("lr", np.asarray([0.1], np.float32))
            for n in names:
                if owner[n] == s:
                    scope.set_var(n, np.ones(dim, np.float32))
            srv = VariableServer(prog, scope,
                                 fluid.Executor(fluid.CPUPlace()),
                                 fan_in=1)
            srv.serve(0)
            servers.append(srv)
        return servers, [f"127.0.0.1:{s.port}" for s in servers]

    def run_serial(eps):
        clients = {ep: VariableClient(ep, client_id="bench-serial")
                   for ep in eps}

        def one_round(r):
            for n in names:
                clients[eps[owner[n]]].send_var(n + "@GRAD", grads[r][n])
            for ep in eps:
                clients[ep].send_batch_barrier()
            for n in names:
                clients[eps[owner[n]]].get_var(n)

        one_round(0)  # warmup: optimize-program compile on the servers
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            one_round(r)
        wall = time.perf_counter() - t0
        params = {n: np.asarray(clients[eps[owner[n]]].get_var(n))
                  for n in names}
        for c in clients.values():
            c.close()
        return wall, params

    def run_fused(eps):
        pool = comm.CommPool()

        def one_round(r):
            pool.send_round(
                [(eps[owner[n]], n + "@GRAD", grads[r][n])
                 for n in names],
                [(eps[owner[n]], n) for n in names])

        one_round(0)
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            one_round(r)
        wall = time.perf_counter() - t0
        vals = pool.send_round([], [(eps[owner[n]], n) for n in names])
        params = {n: np.asarray(v) for n, v in zip(names, vals)}
        pool.close()
        return wall, params

    best = {"serial": float("inf"), "fused": float("inf")}
    params_serial = params_fused = None
    for _ in range(trials):
        for mode, runner in (("serial", run_serial), ("fused", run_fused)):
            servers, eps = build_servers()
            try:
                wall, params = runner(eps)
            finally:
                for s in servers:
                    s.stop()
            best[mode] = min(best[mode], wall)
            if mode == "serial":
                params_serial = params
            else:
                params_fused = params
    identical = all(params_serial[n].tobytes() == params_fused[n].tobytes()
                    for n in names)
    return {"n_grads": n_grads, "dim": dim, "rounds": rounds,
            "pservers": pservers,
            "serial_seconds": round(best["serial"], 4),
            "fused_seconds": round(best["fused"], 4),
            "speedup": round(best["serial"] / best["fused"], 3),
            "params_identical": identical}


# serving-kernel microbench decoders are cached at module level: both
# trials AND any later bench section reuse the same compiled step —
# no per-row rebuilds (the PR 8 compile-budget discipline)
_KERNEL_DECODERS = {}


def run_kernels_bench(trials=None, ticks=None):
    """Serving-kernel microbench (BENCH_KERNELS=1): each fused Pallas
    kernel against the XLA oracle path it replaces — paged-attention
    decode (fp32 + quantized int8 KV), fused MoE gate+dispatch, fused
    per-bucket optimizer update.  Rows are best-of-`trials` measured
    throughput plus the kernel-backed static bytes-moved from
    analysis/cost_model.py (what each path charges the roofline).

    Off-TPU the Pallas rows run in interpret mode, so measured CPU
    throughput favors XLA by construction — those rows demonstrate the
    PATH and its numerics; the bytes-moved delta is the TPU argument
    (docs/performance.md "Serving kernels")."""
    import jax
    import jax.numpy as jnp

    from run_serving import VOCAB, _build_decoder, _build_kernel_decoder
    from paddle_tpu.analysis.cost_model import serving_kernel_cost
    from paddle_tpu.kernels import (build_fused_bucket_update,
                                    build_moe_gate_dispatch,
                                    interpret_mode,
                                    moe_dispatch_supports)
    from paddle_tpu.parallel.moe import moe_gate

    # the "pallas" rows run what the platform runs: Mosaic on a TPU,
    # the interpreter elsewhere (never the interpreter under a
    # kernel's name on a chip)
    platform = jax.default_backend()
    interpret = interpret_mode(platform)

    trials = trials or int(os.environ.get("BENCH_KERNELS_TRIALS", "2"))
    ticks = ticks or int(os.environ.get("BENCH_KERNELS_TICKS", "8"))
    d_model, n_heads, n_layers, bs, nb, slots = 128, 4, 2, 8, 12, 4
    rng = np.random.RandomState(0)

    def best_rate(fn, units):
        b = 0.0
        for _ in range(trials):
            t0 = time.perf_counter()
            fn()
            b = max(b, units / (time.perf_counter() - t0))
        return round(b, 1)

    # -- paged-attention decode: full decode tick, gather vs fused ----
    att = {}
    for kv_dtype in ("fp32", "int8"):
        spec = dict(d_model=d_model, n_layers=n_layers,
                    n_heads=n_heads, vocab_size=VOCAB, block_size=bs,
                    max_blocks_per_seq=nb, kv_dtype=kv_dtype)
        row = {}
        for label, build in (("xla", _build_decoder),
                             ("pallas", _build_kernel_decoder)):
            key = (label, kv_dtype)
            if key not in _KERNEL_DECODERS:
                _KERNEL_DECODERS[key] = build(
                    d_model, n_layers, n_heads, bs, nb,
                    kv_dtype=kv_dtype, platform=platform)
            dec, states = _KERNEL_DECODERS[key]
            sj = {k: jnp.asarray(v) for k, v in states.items()}
            tables = jnp.zeros((slots, nb), jnp.int32)
            positions = jnp.full((slots,), bs * nb // 2, jnp.int32)
            zi = jnp.zeros((slots,), jnp.int32)
            temps = jnp.zeros((slots,), jnp.float32)
            act = jnp.ones((slots,), bool)

            def run(dec=dec, sj=sj):
                # pools re-initialized per trial: step() donates them
                pk, pv = dec.init_pool(nb)
                for _ in range(ticks):
                    toks, pk, pv = dec.step(sj, pk, pv, tables,
                                            positions, zi, zi, temps,
                                            act)
                jax.block_until_ready(toks)

            run()  # warmup: compile outside the timed trials
            est = serving_kernel_cost(
                "paged_decode_step", spec, slots=slots,
                context=bs * nb // 2, kv_dtype=kv_dtype, backend=label)
            row[label] = {
                "tokens_per_sec": best_rate(run, slots * ticks),
                "est_bytes_per_tick": est["bytes"],
                "kernel": dec.kernels.get("paged_attention_decode")}
        row["bytes_ratio_pallas_vs_xla"] = round(
            row["pallas"]["est_bytes_per_tick"]
            / row["xla"]["est_bytes_per_tick"], 3)
        att[kv_dtype] = row
    out = {"paged_attention_decode": att}

    # -- fused MoE gate+dispatch vs the oracle op chain ---------------
    T, D, E, C, top_k = 64, 64, 4, 24, 2
    x = jnp.asarray(rng.standard_normal((T, D)).astype(np.float32))
    gw = jnp.asarray(rng.standard_normal((D, E)).astype(np.float32))

    @jax.jit
    def moe_oracle(x, gw):
        dispatch, combine, aux = moe_gate(x, gw, E, C, top_k=top_k)
        expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                               dispatch).astype(x.dtype)
        return expert_in, combine, aux

    moe_geometry = dict(tokens=T, d_model=D, num_experts=E, capacity=C,
                        top_k=top_k)
    moe_refused = moe_dispatch_supports(platform=platform,
                                        **moe_geometry)
    moe_iters = 4 * ticks
    est = serving_kernel_cost(
        "moe_gate_dispatch", {"d_model": D, "n_heads": 1,
                              "n_layers": 1, "vocab_size": VOCAB},
        tokens=T, num_experts=E, capacity=C, top_k=top_k)

    def run_moe(fn):
        def go():
            for _ in range(moe_iters):
                r = fn(x, gw)
            jax.block_until_ready(r)
        go()  # warmup
        return best_rate(go, T * moe_iters)

    if moe_refused:
        moe_pallas = {"fallback": f"xla:{moe_refused}"}
    else:
        moe_pallas = {"tokens_per_sec": run_moe(jax.jit(
            build_moe_gate_dispatch(interpret=interpret,
                                    **moe_geometry)))}
    out["moe_gate_dispatch"] = {
        "xla": {"tokens_per_sec": run_moe(moe_oracle)},
        "pallas": moe_pallas,
        "est_bytes": est["bytes"],
        "routing_bytes_avoided": est["routing_bytes_avoided"],
        "tokens": T, "num_experts": E, "capacity": C, "top_k": top_k}

    # -- fused bucket update vs the per-parameter chain ---------------
    n_params, per = 16, 4096
    numel = n_params * per
    parts = [jnp.asarray(rng.standard_normal(per).astype(np.float32))
             for _ in range(n_params)]
    gparts = [jnp.asarray(rng.standard_normal(per).astype(np.float32))
              for _ in range(n_params)]
    lr = jnp.float32(0.01)

    @jax.jit
    def chain(ps, gs, lr):
        return [p - lr * g for p, g in zip(ps, gs)]

    upd = build_fused_bucket_update(numel=numel, interpret=interpret)

    @jax.jit
    def fused_upd(ps, gs, lr):
        return upd(jnp.concatenate(ps), jnp.concatenate(gs), lr)

    upd_iters = 8 * ticks

    def run_upd(fn):
        def go():
            for _ in range(upd_iters):
                r = fn(parts, gparts, lr)
            jax.block_until_ready(r)
        go()  # warmup
        return best_rate(go, numel * upd_iters)

    est = serving_kernel_cost("fused_bucket_update", {}, numel=numel,
                              n_params=n_params)
    out["fused_bucket_update"] = {
        "xla_chain": {"elems_per_sec": run_upd(chain)},
        "pallas": {"elems_per_sec": run_upd(fused_upd)},
        "est_bytes": est["bytes"],
        "launches_replaced": est["launches_replaced"],
        "numel": numel, "n_params": n_params}
    return out


def main():
    import paddle_tpu as fluid
    from harness import gated_time_program
    from paddle_tpu.core.compile_cache import compile_cache_dir

    # one compile cache for every bench section and round (the
    # harness jits outside any Executor, so arm it here)
    compile_cache_dir()

    if AMP:
        fluid.amp.enable_bf16()
    if MEMOPT:
        from paddle_tpu.core.flags import set_flags
        set_flags({"memory_optimize": True})
    main_p, startup, avg = build_resnet50_train(BATCH, DTYPE)

    r = np.random.RandomState(0)
    from paddle_tpu.core.types import np_dtype

    img_shape = ((BATCH, IMG, IMG, 3) if LAYOUT == "NHWC"
                 else (BATCH, 3, IMG, IMG))
    feeds = {
        "img": r.rand(*img_shape).astype(np_dtype(DTYPE)),
        "label": r.randint(0, 1000, (BATCH, 1)).astype(np.int32),
    }
    # harness.gated_time_program: K real steps inside one executable
    # (replay-immune scan instrument) + the roofline plausibility gate —
    # an implausible number is published as valid:false and exits 1,
    # never as a silent headline
    step_analysis = os.environ.get(
        "BENCH_STEP_ANALYSIS", "1").lower() not in ("0", "false", "no",
                                                    "off")
    ms, cost, fields = gated_time_program(
        main_p, startup, feeds, avg.name, ITERS,
        model_flops_per_step=RESNET50_TRAIN_FLOPS_PER_IMG * BATCH,
        step_analysis=step_analysis)
    img_per_sec = BATCH / ms * 1000
    out = {
        "metric": "resnet50_train_images_per_sec",
        "value": round(img_per_sec, 2),
        "unit": "images/s",
        "vs_baseline": round(img_per_sec / BASELINE_RESNET50_IMG_S, 3),
        "batch": BATCH,
        "amp": AMP,
        "layout": LAYOUT,
        "remat": REMAT,
        "memory_optimize": MEMOPT,
        "ms_per_step": round(ms, 2),
    }
    out.update(fields)
    prefetch_depth = int(os.environ.get("BENCH_PREFETCH", "0"))
    if prefetch_depth > 0:
        out["prefetch_pipeline"] = run_prefetch_bench(prefetch_depth)
    if os.environ.get("BENCH_COMM", "0").lower() in ("1", "true", "yes",
                                                     "on"):
        out["comm"] = run_comm_bench()
    if os.environ.get("BENCH_SERVING", "0").lower() in ("1", "true",
                                                        "yes", "on"):
        from run_serving import run_serving_bench
        env = os.environ.get
        out["serving"] = run_serving_bench(
            prom_out=env("BENCH_SERVING_PROM", ""),
            prefix_pool=int(env("BENCH_SERVING_PREFIX_POOL", "3")),
            prefix_len=int(env("BENCH_SERVING_PREFIX_LEN", "24")),
            prefix_hit=float(env("BENCH_SERVING_PREFIX_HIT", "0.75")),
            spec_k=int(env("BENCH_SERVING_SPEC_K", "4")),
            with_spec=env("BENCH_SERVING_SPEC", "1").lower() not in (
                "0", "false", "no", "off"),
            with_quant=env("BENCH_SERVING_QUANT", "1").lower() not in (
                "0", "false", "no", "off"),
            with_kernels=env("BENCH_SERVING_KERNELS",
                             "1").lower() not in ("0", "false", "no",
                                                  "off"))
    if os.environ.get("BENCH_KERNELS", "0").lower() in ("1", "true",
                                                        "yes", "on"):
        out["kernels"] = run_kernels_bench()
    if os.environ.get("BENCH_SERVING_RAMP", "0").lower() in (
            "1", "true", "yes", "on"):
        from run_serving import run_fleet_ramp_bench
        env = os.environ.get
        out["serving_ramp"] = run_fleet_ramp_bench(
            peak_rps=float(env("BENCH_SERVING_RAMP_PEAK", "24")),
            phase_s=float(env("BENCH_SERVING_RAMP_PHASE_S", "6")),
            max_replicas=int(env("BENCH_SERVING_RAMP_MAX", "3")))
    if os.environ.get("BENCH_CONVERGENCE", "1").lower() not in (
            "0", "false", "no", "off"):
        conv = run_convergence()
        out["convergence"] = conv
        if not conv["reached"]:
            out["valid"] = False
            out.setdefault("invalid_reason",
                           "convergence target not reached in budget")
    # book acceptance matrix (benchmark/run_book.py): the 8 reference
    # book models trained to their thresholds in this same numeric mode
    # (~2 min incl. compiles; measured reach times are all <= 21 s, the
    # 45 s/model cap is 2x margin).  Reported, not validity-gating —
    # the headline's validity stays with its own roofline + convergence
    # gates.  BENCH_BOOK=0 skips; BOOK_MATRIX_r04.json is the committed
    # reference artifact.
    if (os.environ.get("BENCH_BOOK", "1").lower() in ("1", "true", "yes",
                                                      "on")
            and out.get("valid", True)):
        # skipped when the headline already failed its gates: the matrix
        # would delay the nonzero exit by ~2 min without changing it
        os.environ.setdefault("BOOK_SECONDS", "45")
        amp_was = fluid.amp.is_bf16_enabled()
        try:
            from run_book import run_matrix
            out["book_matrix"] = run_matrix()
        except Exception as e:  # a matrix crash must not destroy the
            out["book_matrix"] = {  # headline artifact — record it,
                "error": f"{type(e).__name__}: {e}"}  # and exit 1 below
        finally:  # run_matrix flips the process-global amp flag
            (fluid.amp.enable_bf16 if amp_was
             else fluid.amp.disable_bf16)()
    print(json.dumps(out))
    if not out["valid"] or "error" in out.get("book_matrix", {}):
        sys.exit(1)


if __name__ == "__main__":
    main()
