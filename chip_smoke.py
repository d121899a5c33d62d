#!/usr/bin/env python
"""Does the system still start on the chip?  One process, one command:

    python chip_smoke.py                     # full width; needs a TPU
    python chip_smoke.py --legs serve_lm     # a subset of the legs
    python chip_smoke.py --rehearsal         # tiny sizes, any backend

It drives the main paths once through the entry points a user calls —
`trainer.Trainer`, `Executor.run`, `save_generation_model` ->
`server_from_model_dir` -> `ReplicaServer` over the wire,
`ParallelExecutor` — at the full width of configurations the repo
publishes (depth is what they publish too; weights are random, from the
programs' seeded initializers; inputs are seeded and synthetic, so no
network is needed).  Every leg prints ONE JSON line naming the device
it ran on, its compile seconds, a steady step/request time and the
kernel backend that actually ran; the last line of stdout is
`{"ok": true, "device": {...}}` and the exit code is 0 only if every
leg passed.  Without a TPU the no-argument run prints no result and
exits 2: nothing here can complete on the CPU by default.

Times printed here are SMOKE OBSERVATIONS (a handful of steps, compile
included where it says so) — not benchmark results.

`--rehearsal` is for debugging this script in a sandbox without a chip:
same code, toy sizes, whatever backend JAX finds; every line it prints
carries `"rehearsal": true`.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

EXIT_NO_TPU = 2
EXIT_NO_REPO = 3

# flash kernel vs the XLA composition, bf16 operands: forward and
# gradients relative to the reference's largest magnitude (the same
# band the r4/r5 attention bench gated on; measured ~1e-2 on v5e)
FLASH_REL_TOL = 4e-2
# one decode step's logits, Pallas paged attention vs the XLA gather
# path on identical pools, relative to the largest |logit|: Mosaic's
# f32 MXU passes are not XLA's default-precision einsum, so this is a
# tolerance, not bit-identity (kernel-level difference measured ~6e-3
# of the context's magnitude on v5e)
DECODE_LOGIT_REL_TOL = 2e-2


# ---------------------------------------------------------------------------
# sizes: the published widths, and the toy rehearsal
# ---------------------------------------------------------------------------

FULL = dict(
    # BASELINE north star / the r4 headline: ResNet-50 224x224 bs256 amp
    resnet=dict(depth=50, img=224, classes=1000, batch=256, steps=5),
    # the long-context ridge row: d768 x 8 layers, 6 heads x 128, seq 8192
    lm=dict(vocab=30000, d_model=768, n_layers=8, n_heads=6, seq=8192,
            batch=1, steps=3),
    # the MoE-round LM width as a decoder: d1024 x 6 layers, 8 heads x 128
    serve=dict(vocab=30000, d_model=1024, n_layers=6, n_heads=8,
               block_size=16, max_blocks=32, slots=8, kv_blocks=256,
               prompts=(16, 32, 48, 64, 96, 128, 192, 256), max_new=32,
               fill=40),
    flash_check=dict(seq=2048, heads=6, d_head=128),
)
REHEARSAL = dict(
    resnet=dict(depth=8, img=32, classes=10, batch=8, steps=3),
    lm=dict(vocab=64, d_model=32, n_layers=2, n_heads=2, seq=64,
            batch=1, steps=3),
    serve=dict(vocab=64, d_model=32, n_layers=2, n_heads=2,
               block_size=4, max_blocks=8, slots=4, kv_blocks=32,
               prompts=(3, 5, 8, 12), max_new=6, fill=10),
    flash_check=dict(seq=256, heads=2, d_head=16),
)


class Smoke:
    """What every leg needs: the sizes, the place, the device identity
    stamped on each line, and the pass/fail ledger."""

    def __init__(self, rehearsal: bool):
        import jax

        import paddle_tpu as fluid

        self.rehearsal = rehearsal
        self.sizes = REHEARSAL if rehearsal else FULL
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.on_tpu = dev.platform == "tpu"
        self.place = fluid.TPUPlace() if self.on_tpu else fluid.CPUPlace()
        self.failed = []

    def emit(self, leg: str, ok: bool, **fields):
        line = {"leg": leg, "ok": ok,
                "platform": self.device["platform"],
                "device_kind": self.device["kind"],
                "device_count": self.device["count"]}
        if self.rehearsal:
            line["rehearsal"] = True
        line.update(fields)
        print(json.dumps(line), flush=True)
        if not ok:
            self.failed.append(leg)

    def run_leg(self, name: str, fn):
        from paddle_tpu.core.executor import xla_compile_counts

        c0 = xla_compile_counts()
        t0 = time.perf_counter()
        try:
            fields = fn(self)
            ok = True
        except Exception as e:
            traceback.print_exc()
            fields = {"error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        c1 = xla_compile_counts()
        fields["leg_seconds"] = round(time.perf_counter() - t0, 2)
        fields["compile_cache"] = {
            k: int(c1[k] - c0[k])
            for k in ("compiles", "cache_hits", "cache_misses")}
        self.emit(name, ok, **fields)
        gc.collect()


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def _steady(times):
    """Mean of the step times after the first (which holds the compile)."""
    rest = times[1:] or times
    return round(sum(rest) / len(rest), 4)


def _mosaic_calls(dump_dir) -> int:
    """Mosaic custom calls in the largest module JAX handed to the
    compiler while `jax_dump_ir_to` pointed at `dump_dir` — i.e. in the
    step the Executor's own jit compiled."""
    best = 0
    for name in os.listdir(dump_dir):
        with open(os.path.join(dump_dir, name), errors="replace") as f:
            best = max(best, f.read().count("tpu_custom_call"))
    return best


# ---------------------------------------------------------------------------
# leg: ResNet-50 through fluid.Trainer
# ---------------------------------------------------------------------------

def _build_resnet(fluid, cfg):
    from paddle_tpu.models.resnet import resnet_cifar10, resnet_imagenet

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img",
                                shape=[3, cfg["img"], cfg["img"]],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if cfg["depth"] >= 50:
            predict = resnet_imagenet(img, class_dim=cfg["classes"],
                                      depth=cfg["depth"])
        else:
            predict = resnet_cifar10(img, class_dim=cfg["classes"],
                                     depth=cfg["depth"])
        avg = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg)
    return main, startup, img, label, avg


def _image_batches(cfg, steps, seed):
    """`steps` distinct seeded batches, made up front so that no step
    time below contains their synthesis."""
    import numpy as np

    r = np.random.RandomState(seed)
    shape = (cfg["batch"], 3, cfg["img"], cfg["img"])
    return [(r.rand(*shape).astype(np.float32),
             r.randint(0, cfg["classes"],
                       (cfg["batch"], 1)).astype(np.int64))
            for _ in range(steps)]


def leg_train_resnet50(smoke: Smoke):
    import paddle_tpu as fluid
    from paddle_tpu import trainer as trainer_mod
    from paddle_tpu.core import framework as fw

    cfg = smoke.sizes["resnet"]
    fw.reset_unique_names()
    fluid.amp.enable_bf16()
    try:
        with fluid.scope_guard(fluid.Scope()):
            main, startup, img, label, avg = _build_resnet(fluid, cfg)
            trainer = trainer_mod.Trainer(avg, place=smoke.place,
                                          feed_list=[img, label],
                                          main_program=main,
                                          startup_program=startup)

            batches = _image_batches(cfg, cfg["steps"], seed=0)

            def reader():
                for x, y in batches:
                    yield list(zip(x, y))

            losses, stamps = [], [time.perf_counter()]

            def on_event(ev):
                if isinstance(ev, trainer_mod.EndIteration):
                    losses.append(float(ev.cost))
                    stamps.append(time.perf_counter())

            trainer.train(1, reader, event_handler=on_event)
            times = [b - a for a, b in zip(stamps, stamps[1:])]
            stats = trainer.exe.cache_stats()
            scope = fluid.global_scope()
            params = [p.name for p in main.global_block().all_parameters()]
            homes = {d for n in params
                     for d in scope.find_var(n).devices()}
            trainer.exe.close()
    finally:
        fluid.amp.disable_bf16()
    check(len(losses) == cfg["steps"], f"ran {len(losses)} steps")
    check(all(math.isfinite(v) for v in losses), f"loss {losses}")
    # random init: the first loss sits near ln(classes)
    check(abs(losses[0] - math.log(cfg["classes"]))
          < 0.5 * math.log(cfg["classes"]), f"first loss {losses[0]}")
    check(homes == {smoke.place.jax_device()},
          f"parameters live on {homes}")
    check(stats["recompiles_after_warmup"] == 0, stats)
    return {"model": f"resnet{cfg['depth']}", "batch": cfg["batch"],
            "amp": "bf16", "steps": len(losses),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "params": len(params),
            "compile_s": round(stats["compile_s"], 2),
            # host-side row packing and the H2D copy of each batch are
            # inside this time: it is the Trainer loop's, not the chip's
            "steady_step_s": _steady(times),
            "recompiles_after_warmup": stats["recompiles_after_warmup"],
            "kernel_backend": "xla"}


# ---------------------------------------------------------------------------
# leg: long-context LM through Executor.run — the flash kernels
# ---------------------------------------------------------------------------

def _build_lm(fluid, cfg):
    from paddle_tpu.models.transformer import transformer_lm

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[cfg["seq"]],
                                dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[cfg["seq"], 1],
                                dtype="int64")
        logits = transformer_lm(
            ids, cfg["vocab"], d_model=cfg["d_model"],
            n_heads=cfg["n_heads"], n_layers=cfg["n_layers"],
            max_len=cfg["seq"], dropout_rate=0.0, return_logits=True)
        cost = fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, shape=[-1, cfg["vocab"]]),
            fluid.layers.reshape(lbl, shape=[-1, 1]))
        avg = fluid.layers.mean(cost)
        fluid.Momentum(learning_rate=0.01, momentum=0.9).minimize(avg)
    return main, startup, avg


def leg_train_lm_flash(smoke: Smoke):
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw

    cfg = smoke.sizes["lm"]
    fw.reset_unique_names()
    fluid.amp.enable_bf16()
    dump = tempfile.mkdtemp(prefix="chip_smoke_ir_")
    try:
        main, startup, avg = _build_lm(fluid, cfg)
        scope = fluid.Scope()
        exe = fluid.Executor(smoke.place)
        exe.run(startup, scope=scope)
        r = np.random.RandomState(1)
        losses, times = [], []
        for step in range(cfg["steps"]):
            feed = {"ids": r.randint(0, cfg["vocab"],
                                     (cfg["batch"], cfg["seq"])
                                     ).astype(np.int32),
                    "lbl": r.randint(0, cfg["vocab"],
                                     (cfg["batch"], cfg["seq"], 1)
                                     ).astype(np.int32)}
            if step == 0:
                # the module the Executor's jit hands to the compiler
                jax.config.update("jax_dump_ir_to", dump)
            t0 = time.perf_counter()
            try:
                loss, = exe.run(main, feed=feed, fetch_list=[avg],
                                scope=scope)
            finally:
                jax.config.update("jax_dump_ir_to", None)
            times.append(time.perf_counter() - t0)
            losses.append(float(np.asarray(loss).ravel()[0]))
        stats = exe.cache_stats()
        mosaic = _mosaic_calls(dump)
        exe.close()
    finally:
        fluid.amp.disable_bf16()
        shutil.rmtree(dump, ignore_errors=True)
    check(all(math.isfinite(v) for v in losses), f"loss {losses}")
    check(abs(losses[0] - math.log(cfg["vocab"]))
          < 0.2 * math.log(cfg["vocab"]), f"first loss {losses[0]}")
    check(stats["recompiles_after_warmup"] == 0, stats)
    if smoke.on_tpu:
        # the forward kernel and the fused backward kernel (a dq and a
        # dk/dv kernel past its VMEM budget) per attention layer —
        # without this the XLA composition could have run in silence
        check(mosaic >= 2 * cfg["n_layers"],
              f"{mosaic} Mosaic calls in the compiled step, want "
              f">= {2 * cfg['n_layers']}")
    return {"model": "transformer_lm", "d_model": cfg["d_model"],
            "n_layers": cfg["n_layers"], "seq": cfg["seq"],
            "batch": cfg["batch"], "amp": "bf16", "steps": len(losses),
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "compile_s": round(stats["compile_s"], 2),
            "steady_step_s": _steady(times),
            "mosaic_calls_in_step": mosaic,
            "kernel_backend": ("pallas:flash_attention" if mosaic
                               else "xla")}


# ---------------------------------------------------------------------------
# leg: the flash kernel against the XLA composition, on the device
# ---------------------------------------------------------------------------

def leg_kernels_numerics(smoke: Smoke):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import (flash_attention,
                                    flash_attention_reference)

    platform = smoke.device["platform"]
    interp = platform != "tpu"    # a rehearsal: the Pallas interpreter
    out = {}

    # flash fwd + bwd vs the XLA composition
    fc = smoke.sizes["flash_check"]
    r = np.random.RandomState(2)
    q, k, v = (jnp.asarray(
        r.randn(1, fc["seq"], fc["heads"], fc["d_head"]) * 0.5,
        jnp.bfloat16) for _ in range(3))

    def grads_of(attend):
        def loss(q, k, v):
            return jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    val_p, g_p = grads_of(lambda q, k, v: flash_attention(
        q, k, v, causal=True, min_seq_k=0, interpret=interp,
        platform=platform))(q, k, v)
    val_x, g_x = grads_of(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True))(q, k, v)

    def rel(a, b):
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))

    flash_rel = max([rel(val_p, val_x)]
                    + [rel(a, b) for a, b in zip(g_p, g_x)])
    check(flash_rel <= FLASH_REL_TOL,
          f"flash vs XLA rel err {flash_rel} > {FLASH_REL_TOL}")
    out["flash_attention"] = {"rel_err": round(flash_rel, 5),
                              "tol": FLASH_REL_TOL,
                              "backend": "pallas"}

    out["kernel_backend"] = "pallas" + (":interpret" if interp else "")
    return out


# ---------------------------------------------------------------------------
# leg: the generation server, over the wire
# ---------------------------------------------------------------------------

def _serve_once(smoke: Smoke, kv_dtype: str, states, workdir):
    """save -> server_from_model_dir -> ReplicaServer -> N streamed
    requests over TCP; returns (fields, spec)."""
    import numpy as np

    from paddle_tpu.serving import (ReplicaServer, save_generation_model,
                                    server_from_model_dir)
    from paddle_tpu.serving.replica import replica_call, replica_stream

    cfg = smoke.sizes["serve"]
    spec = {"vocab_size": cfg["vocab"], "d_model": cfg["d_model"],
            "n_heads": cfg["n_heads"], "n_layers": cfg["n_layers"],
            "block_size": cfg["block_size"],
            "max_blocks_per_seq": cfg["max_blocks"],
            "slots": cfg["slots"], "kv_blocks": cfg["kv_blocks"],
            "kv_dtype": kv_dtype}
    model_dir = os.path.join(workdir, f"model_{kv_dtype}")
    save_generation_model(model_dir, states, spec)
    server = server_from_model_dir(model_dir, place=smoke.place)
    rep = ReplicaServer(server, port=0)
    r = np.random.RandomState(3)
    prompts = [[int(t) for t in r.randint(0, cfg["vocab"], n)]
               for n in cfg["prompts"]]
    results = [None] * len(prompts)

    def ask(i):
        t0 = time.perf_counter()
        try:
            toks = list(replica_stream(
                rep.addr, {"op": "generate", "prompt": prompts[i],
                           "max_new": cfg["max_new"], "seed": i},
                timeout_s=600))
            results[i] = (toks, time.perf_counter() - t0)
        except Exception as e:   # surfaces below as a failed stream
            results[i] = e

    try:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        wall = time.perf_counter() - t0
        check(not any(t.is_alive() for t in threads),
              "a request is still running")
        stats = replica_call(rep.addr, {"op": "stats"})["stats"]
    finally:
        rep.close()
        server.close()
    for i, res in enumerate(results):
        check(not isinstance(res, Exception), f"stream {i}: {res!r}")
        toks = res[0]
        check(len(toks) == cfg["max_new"]
              and all(0 <= t < cfg["vocab"] for t in toks),
              f"stream {i} returned {len(toks)} tokens")
    check(stats["requests"] == len(prompts), stats)
    check(stats["shed"] == 0 and stats["deadline_expired"] == 0, stats)
    check(stats["generated_tokens"] == len(prompts) * cfg["max_new"],
          stats)
    check(stats["kv_blocks_free"] == stats["kv_blocks_total"], stats)
    check(stats["recompiles_after_warmup"] == 0, stats)
    if smoke.on_tpu:
        # on a TPU the predicate accepts this pool at fp32, so anything
        # but the kernel means selection lost it; an int8 pool it
        # refuses by name, and the gather path serves
        check(stats["decode_kernel"] == (
            "xla:kv_dtype" if kv_dtype == "int8" else "pallas"),
            stats["decode_kernel"])
    lat = sorted(res[1] for res in results)
    return {"decode_kernel": stats["decode_kernel"],
            "requests": len(prompts), "ticks": stats["ticks"],
            "warmup_s": stats["warmup_s"],
            "compile_s": stats["compile_seconds"],
            "warm_start": stats["warm_start"],
            "request_s_median": round(lat[len(lat) // 2], 3),
            "request_s_max": round(lat[-1], 3),
            "wall_s": round(wall, 3),
            "tick_s": round(wall / max(stats["ticks"], 1), 5)}, spec


def _decode_logit_gate(smoke: Smoke, kv_dtype: str, states, spec):
    """One decode step's logits through the decoder the server runs
    (Pallas on a TPU) against the XLA gather path, on identical pools
    filled by `fill` teacher-forced positions per slot."""
    import jax
    import numpy as np

    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    cfg = smoke.sizes["serve"]

    def build(platform):
        # the kernel is selected from the platform the decoder is built
        # for: "cpu" gives the XLA gather path, on whatever device the
        # step is then given
        fw.reset_unique_names()
        return build_lm_paged_decoder(
            cfg["vocab"], cfg["block_size"], cfg["max_blocks"],
            d_model=cfg["d_model"], n_heads=cfg["n_heads"],
            n_layers=cfg["n_layers"], kv_dtype=kv_dtype,
            platform=platform)[1]

    dec_run, dec_xla = build(smoke.device["platform"]), build("cpu")
    chosen = dec_run.kernels["paged_attention_decode"]
    if chosen != "pallas":
        return {"skipped": chosen}
    dev = smoke.place.jax_device()
    g = {n: jax.device_put(np.asarray(states[n]), dev)
         for n in dec_xla.state_names}
    s_n, nb = cfg["slots"], cfg["max_blocks"]
    pool_k, pool_v = dec_xla.init_pool(s_n * nb + 1, dev)
    tables = (1 + np.arange(s_n * nb, dtype=np.int32)).reshape(s_n, nb)
    r = np.random.RandomState(4)
    zs = np.zeros(s_n, np.uint32)
    zt = np.zeros(s_n, np.float32)
    act = np.ones(s_n, bool)
    for pos in range(cfg["fill"]):
        _, pool_k, pool_v = dec_xla.step(
            g, pool_k, pool_v, tables, np.full(s_n, pos, np.int32),
            r.randint(0, cfg["vocab"], s_n).astype(np.int32), zs, zt, act)
    args = (g, pool_k, pool_v, tables,
            np.full(s_n, cfg["fill"], np.int32),
            r.randint(0, cfg["vocab"], s_n).astype(np.int32), zs, zt, act)
    want = np.asarray(dec_xla.step_logits(*args))
    got = np.asarray(dec_run.step_logits(*args))
    check(got.shape == (s_n, cfg["vocab"]) and np.isfinite(got).all(),
          f"logits {got.shape}")
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    check(rel <= DECODE_LOGIT_REL_TOL,
          f"{kv_dtype} decode logits: Pallas vs XLA rel err {rel} > "
          f"{DECODE_LOGIT_REL_TOL}")
    return {"rel_err": round(rel, 6), "tol": DECODE_LOGIT_REL_TOL,
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1)))}


def leg_serve_lm(smoke: Smoke):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    cfg = smoke.sizes["serve"]
    fw.reset_unique_names()
    startup, dec = build_lm_paged_decoder(
        cfg["vocab"], cfg["block_size"], cfg["max_blocks"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_layers=cfg["n_layers"], platform=smoke.device["platform"])
    scope = fluid.Scope()
    fluid.Executor(smoke.place).run(startup, scope=scope)
    states = {n: np.asarray(scope.find_var(n)) for n in dec.state_names}
    del scope
    workdir = tempfile.mkdtemp(prefix="chip_smoke_serve_")
    out = {"model": "transformer_lm_decoder", "d_model": cfg["d_model"],
           "n_layers": cfg["n_layers"], "slots": cfg["slots"],
           "context": cfg["block_size"] * cfg["max_blocks"]}
    try:
        for kv_dtype in ("fp32", "int8"):
            fields, spec = _serve_once(smoke, kv_dtype, states, workdir)
            gc.collect()
            fields["logits_vs_xla"] = _decode_logit_gate(
                smoke, kv_dtype, states, spec)
            out[kv_dtype] = fields
            gc.collect()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    out["kernel_backend"] = {k: out[k]["decode_kernel"]
                             for k in ("fp32", "int8")}
    return out


# ---------------------------------------------------------------------------
# leg: data parallelism over four chips
# ---------------------------------------------------------------------------

def leg_train_dp4(smoke: Smoke):
    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.core import framework as fw

    n_dev = smoke.device["count"]
    if n_dev < 4:
        return {"skipped": f"{n_dev} device" + ("s" if n_dev > 1 else "")}
    cfg = smoke.sizes["resnet"]
    fw.reset_unique_names()
    fluid.amp.enable_bf16()
    try:
        main, startup, img, label, avg = _build_resnet(fluid, cfg)
        pe = parallel.ParallelExecutor(
            main, ["img", "label"], [avg], mesh={"dp": 4},
            startup_program=startup)
        losses, times = [], []
        feed = None
        for x, y in _image_batches(cfg, 3, seed=5):
            feed = {"img": x, "label": y.astype(np.int32)}
            t0 = time.perf_counter()
            out, = pe.run(feed, return_numpy=False)
            loss_devices = out.devices()
            losses.append(float(np.asarray(out).ravel()[0]))
            times.append(time.perf_counter() - t0)
        params = [p.name for p in main.global_block().all_parameters()]
        state_homes = {
            n: {s.device for s in
                pe.state(n, return_numpy=False).addressable_shards}
            for n in params}
        sharding = parallel.data_sharding(pe.mesh, pe.batch_axis)
        feed_homes = {
            n: {s.device for s in
                jax.device_put(v, sharding).addressable_shards}
            for n, v in feed.items()}
        collectives = pe.compiled_collectives(feed)
        pe.close()
    finally:
        fluid.amp.disable_bf16()
    check(all(math.isfinite(v) for v in losses), f"loss {losses}")
    for homes in (state_homes, feed_homes):
        bad = {n: len(d) for n, d in homes.items() if len(d) != 4}
        check(not bad, f"not on four devices: {bad}")
    check(len(loss_devices) == 4, f"loss on {loss_devices}")
    check(collectives.get("all-reduce", 0) >= 1, collectives)
    return {"model": f"resnet{cfg['depth']}", "batch": cfg["batch"],
            "per_chip_batch": cfg["batch"] // 4, "mesh": {"dp": 4},
            "steps": len(losses), "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "states_on_4_devices": len(state_homes),
            "feeds_on_4_devices": len(feed_homes),
            "collectives": collectives,
            "first_step_s": round(times[0], 2),
            "steady_step_s": _steady(times),
            "kernel_backend": "xla"}


LEGS = {
    "train_resnet50": leg_train_resnet50,
    "train_lm_flash": leg_train_lm_flash,
    "kernels_numerics": leg_kernels_numerics,
    "serve_lm": leg_serve_lm,
    "train_dp4": leg_train_dp4,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of: " + ", ".join(LEGS))
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy sizes on whatever backend JAX finds; "
                    "every line says it is a rehearsal")
    args = ap.parse_args(argv)
    legs = [s for s in args.legs.split(",") if s]
    unknown = [s for s in legs if s not in LEGS]
    if unknown:
        ap.error(f"unknown leg(s) {unknown}")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"chip_smoke: JAX found no TPU (jax.devices()[0] is {dev!r},"
              f" platform {dev.platform!r}); this run needs the chip",
              file=sys.stderr)
        return EXIT_NO_TPU
    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "paddle_tpu")):
        print(f"chip_smoke: {here} holds no paddle_tpu package; run it "
              "from the root of a checkout", file=sys.stderr)
        return EXIT_NO_REPO
    sys.path.insert(0, here)

    from paddle_tpu.core.compile_cache import compile_cache_dir

    smoke = Smoke(args.rehearsal)
    print(json.dumps({"chip_smoke": "start", "legs": legs,
                      "rehearsal": args.rehearsal,
                      "device": smoke.device,
                      "compile_cache_dir": compile_cache_dir()}),
          flush=True)
    for name in legs:
        smoke.run_leg(name, LEGS[name])
    final = {"ok": not smoke.failed, "device": smoke.device}
    if smoke.failed:
        final["failed"] = smoke.failed
    if args.rehearsal:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0 if not smoke.failed else 1


if __name__ == "__main__":
    sys.exit(main())
