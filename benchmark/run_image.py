#!/usr/bin/env python
"""Image-model training benchmark (reference benchmark/paddle/image/run.sh
`paddle train --job=time`; published tables benchmark/README.md:33-95).

Prints one JSON line per (model, batch) with ms/batch and images/sec.

    python benchmark/run_image.py --model alexnet --batch 128
    python benchmark/run_image.py --all            # the reference table grid
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from harness import gated_time_program

SPECS = {
    # name -> (input HxW, reference 1xK40m ms/batch table keyed by batch,
    #          from the reference benchmark/README.md:33-95)
    "alexnet": (227, {64: 195.0, 128: 334.0, 256: 602.0, 512: 1629.0}),
    "googlenet": (224, {64: 613.0, 128: 1149.0, 256: 2348.0}),
    "smallnet": (32, {64: 10.5, 128: 18.2, 256: 33.1, 512: 63.0}),
    "resnet50": (224, {}),
    "vgg19": (224, {}),
}

# reference CPU-inference img/s (2x Xeon Gold 6148, MKL-DNN) keyed by
# batch — benchmark/IntelOptimizedPaddle.md:71-107 via BASELINE.md
INFER_REF = {
    "vgg19": {1: 75.07, 2: 88.64, 4: 82.58, 8: 92.29, 16: 96.75},
    "resnet50": {1: 107.83, 2: 148.84, 4: 177.78, 8: 189.35, 16: 217.69},
    "googlenet": {1: 175.10, 2: 272.92, 4: 450.70, 8: 512.00, 16: 600.94},
    "alexnet": {1: 442.91, 2: 656.41, 4: 719.10, 8: 847.68, 16: 850.51},
}


def build(model, img, dtype):
    import paddle_tpu as fluid
    from paddle_tpu import models

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        data = fluid.layers.data(name="img", shape=[3, img, img],
                                 dtype=dtype)
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if model == "alexnet":
            predict = models.alexnet(data, class_dim=1000)
        elif model == "googlenet":
            predict = models.googlenet(data, class_dim=1000)
        elif model == "smallnet":
            predict = models.smallnet_mnist_cifar(data, class_dim=10)
        elif model == "resnet50":
            predict = models.resnet_imagenet(data, class_dim=1000, depth=50)
        elif model == "vgg19":
            predict = models.vgg(data, class_dim=1000, depth=19)
        else:
            raise SystemExit(f"unknown model {model}")
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg = fluid.layers.mean(cost)
        fluid.Momentum(learning_rate=0.01, momentum=0.9).minimize(avg)
    return main, startup, avg, predict


def run_one(model, batch, iters, dtype):
    from paddle_tpu.core.types import np_dtype

    img, ref_table = SPECS[model]
    classes = 10 if model == "smallnet" else 1000
    main, startup, avg, _ = build(model, img, dtype)
    r = np.random.RandomState(0)
    feeds = {
        "img": r.rand(batch, 3, img, img).astype(np_dtype(dtype)),
        "label": r.randint(0, classes, (batch, 1)).astype(np.int32),
    }
    ms, cost, fields = gated_time_program(main, startup, feeds, avg.name,
                                          iters)
    ref = ref_table.get(batch)
    out = {
        "model": model, "batch": batch,
        "ms_per_batch": round(ms, 2),
        "images_per_sec": round(batch / ms * 1000, 1),
        "ref_k40m_ms_per_batch": ref,
        "speedup_vs_ref": round(ref / ms, 2) if ref else None,
    }
    out.update(fields)
    print(json.dumps(out))
    if not fields["valid"]:
        sys.exit(1)


def infer_one(model, batch, iters, dtype):
    """Inference img/s (is_test program, no optimizer) — the
    IntelOptimizedPaddle.md CPU-inference table's axis.  Timing uses a
    distinct input per iteration, an async chain and one final block
    (docs/design/perf.md)."""
    import time

    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn
    from paddle_tpu.core.types import np_dtype

    img, _ = SPECS[model]
    main_p, startup, _, predict = build(model, img, dtype)
    from paddle_tpu.io import prune

    pred_name = predict.name
    # forward slice only (drop loss + optimizer ops), is_test semantics
    infer_prog = prune(main_p, [predict], for_test=True)
    fn = program_to_fn(infer_prog, ["img"], [pred_name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: jax.device_put(np.asarray(scope.find_var(n)))
              for n in fn.state_in_names}
    key = jax.random.key(0)
    jfn = jax.jit(lambda feeds, states: fn(feeds, states, key)[0])
    r = np.random.RandomState(0)
    # iters+1 buffers: [0] is warmup-only — the timed loop never
    # repeats an (executable, inputs) pair (states are not donated
    # here)
    variants = [jax.device_put(r.rand(batch, 3, img, img)
                               .astype(np_dtype(dtype)))
                for _ in range(iters + 1)]
    jax.block_until_ready(variants)
    # call the AOT executable directly — a resident server holds exactly
    # this handle; the jit python dispatch layer costs ~0.5 ms/call extra
    # at bs-1 (serving.py design)
    compiled = jfn.lower({"img": variants[0]}, states).compile()
    out = compiled({"img": variants[0]}, states)
    jax.block_until_ready(out)
    outs = []
    t0 = time.perf_counter()
    for v in variants[1:]:
        outs.append(compiled({"img": v}, states))
    jax.block_until_ready(outs)
    ms = (time.perf_counter() - t0) / iters * 1000
    ref = INFER_REF.get(model, {}).get(batch)
    print(json.dumps({
        "model": model, "batch": batch, "mode": "inference",
        "ms_per_batch": round(ms, 3),
        "images_per_sec": round(batch / ms * 1000, 1),
        "ref_xeon_img_s": ref,
        "vs_ref": round(batch / ms * 1000 / ref, 2) if ref else None,
    }))


def serve_one(model, dtype, n_requests=256, floor=False):
    """Resident-server serving numbers (paddle_tpu/serving.py): sustained
    bs-1 request throughput under concurrency (dynamic batching — the
    production serving configuration), single-stream latency, and with
    `floor` the on-device/dispatch-overhead decomposition for the bs-1
    cell (a K-fwd-fused dispatch isolates device time from transport)."""
    import time

    import jax
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn
    from paddle_tpu.core.types import np_dtype
    from paddle_tpu.io import prune
    from paddle_tpu.serving import InferenceServer

    img, _ = SPECS[model]
    main_p, startup, _, predict = build(model, img, dtype)
    infer_prog = prune(main_p, [predict], for_test=True)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)

    server = InferenceServer(infer_prog, "img", predict, scope,
                             buckets=(1, 2, 4, 8, 16), window_ms=0.3)
    r = np.random.RandomState(0)
    # disjoint request pools: warmup / single-stream / throughput never
    # share contents, so no timed phase re-dispatches anything the
    # transport has already seen (content-keyed replays bias low)
    n_ss = 30
    pool = [r.rand(1, 3, img, img).astype(np_dtype(dtype))
            for _ in range(3 + n_ss + n_requests)]
    warm, ss, reqs = pool[:3], pool[3:3 + n_ss], pool[3 + n_ss:]

    # single-stream latency: one outstanding request at a time
    for q in warm:
        server.submit(q).result()  # warm every path
    t0 = time.perf_counter()
    for q in ss:
        np.asarray(server.submit(q).result())
    single_ms = (time.perf_counter() - t0) / n_ss * 1000

    # sustained throughput: all requests in flight (distinct contents —
    # transport-cache-proof), clock stops when the LAST result lands
    t0 = time.perf_counter()
    futs = [server.submit(q) for q in reqs]
    outs = [f.result() for f in futs]
    jax.block_until_ready(outs)
    wall = time.perf_counter() - t0
    stats = server.stats()
    server.close()

    out = {
        "model": model, "mode": "serving", "requests": n_requests,
        "single_stream_ms": round(single_ms, 3),
        "single_stream_img_s": round(1000 / single_ms, 1),
        "throughput_img_s": round(n_requests / wall, 1),
        "dispatches": stats["dispatches"],
        "ref_xeon_bs1_img_s": INFER_REF.get(model, {}).get(1),
    }
    ref = out["ref_xeon_bs1_img_s"]
    if ref:
        out["vs_ref_bs1"] = round(out["throughput_img_s"] / ref, 2)

    if floor:
        # K forwards fused in one dispatch: wall/K bounds the true
        # on-device time per bs-1 forward; the rest of the single-stream
        # latency is per-dispatch transport overhead
        K = 8
        fn = program_to_fn(infer_prog, ["img"], [predict.name])
        states = {n: jax.device_put(np.asarray(scope.find_var(n)))
                  for n in fn.state_in_names}
        key = jax.random.key(0)

        def multi(feeds, states):
            import jax.numpy as jnp
            outs = []
            for i in range(K):
                x = feeds["img"] + jnp.asarray(i, feeds["img"].dtype) \
                    * 1e-3
                outs.append(fn({"img": x}, states, key)[0][predict.name])
            return jnp.stack(outs).sum(0)

        # 41 staged buffers: [0] warmup-only, [1:] timed once each
        vs = [jax.device_put(q) for q in reqs[:41]]
        comp = jax.jit(multi).lower({"img": vs[0]}, states).compile()
        o = comp({"img": vs[0]}, states)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        outs = [comp({"img": v}, states) for v in vs[1:]]
        jax.block_until_ready(outs)
        fused_ms = (time.perf_counter() - t0) / (len(vs) - 1) * 1000
        out["on_device_ms_per_fwd"] = round(fused_ms / K, 3)
        out["dispatch_overhead_ms"] = round(
            single_ms - fused_ms / K, 3)
        # the chip-side lower bound for serving bs-1 requests: device
        # time per forward with the per-dispatch host cost amortized
        out["on_chip_bs1_img_s_bound"] = round(1000 / (fused_ms / K), 1)
    print(json.dumps(out))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet", choices=sorted(SPECS))
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--all", action="store_true",
                    help="reference table grid (README.md:33-95)")
    ap.add_argument("--infer", action="store_true",
                    help="inference mode (no optimizer, is_test)")
    ap.add_argument("--serve", action="store_true",
                    help="resident-server serving numbers (dynamic "
                         "batching; paddle_tpu/serving.py)")
    ap.add_argument("--floor", action="store_true",
                    help="with --serve: also measure the on-device vs "
                         "dispatch-overhead decomposition (extra compile)")
    args = ap.parse_args()
    if args.serve:
        models = (("alexnet", "googlenet", "resnet50", "vgg19")
                  if args.all else (args.model,))
        for model in models:
            serve_one(model, args.dtype, floor=args.floor)
    elif args.all and args.infer:
        for model in ("alexnet", "googlenet", "resnet50", "vgg19"):
            for batch in (1, 2, 4, 8, 16):
                infer_one(model, batch, max(args.iters, 20), args.dtype)
    elif args.all:
        for model in ("alexnet", "googlenet", "smallnet"):
            for batch in sorted(SPECS[model][1]):
                run_one(model, batch, args.iters, args.dtype)
    elif args.infer:
        infer_one(args.model, args.batch, args.iters, args.dtype)
    else:
        run_one(args.model, args.batch, args.iters, args.dtype)


if __name__ == "__main__":
    main()
