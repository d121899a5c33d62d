"""Train a small CNN, export it, and serve it with the resident
InferenceServer — the deployment loop for vision models: per-bucket AOT
executables, dynamic request batching (numerics-identical to
one-request-at-a-time), transfer/compute overlap (docs/design/serving.md;
the reference's analogue is the capi resident process,
gradient_machine.cpp).

Run:  JAX_PLATFORMS=cpu python examples/serve_image_classifier.py
"""
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.io import prune
from paddle_tpu.serving import InferenceServer

C, H, W, CLS = 3, 32, 32, 10


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[C, H, W],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        conv = fluid.layers.conv2d(input=img, num_filters=16,
                                   filter_size=3, act="relu")
        pool = fluid.layers.pool2d(input=conv, pool_size=2, pool_stride=2)
        predict = fluid.layers.fc(input=pool, size=CLS, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        fluid.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    return main, startup, predict, loss


def main():
    main_p, startup, predict, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    # quick training pass on synthetic class templates so the served
    # model actually predicts something
    r = np.random.RandomState(0)
    templates = r.rand(CLS, C, H, W).astype(np.float32)
    for step in range(30):
        lbl = r.randint(0, CLS, (64, 1))
        img = (templates[lbl[:, 0]]
               + 0.1 * r.randn(64, C, H, W)).astype(np.float32)
        lv, = exe.run(main_p, feed={"img": img, "label": lbl},
                      fetch_list=[loss], scope=scope)
        if step % 10 == 0:
            print(f"train step {step}: loss {float(np.asarray(lv)[0]):.3f}")

    infer_prog = prune(main_p, [predict], for_test=True)
    server = InferenceServer(infer_prog, "img", predict, scope,
                             place=fluid.CPUPlace(),
                             buckets=(1, 2, 4, 8), window_ms=2.0)
    try:
        # concurrent clients: each submits one image and checks the
        # argmax; the server coalesces them into few dispatches
        n, hits = 64, []

        def client(i):
            lbl = i % CLS
            img = templates[lbl] + 0.1 * np.random.RandomState(i) \
                .randn(C, H, W).astype(np.float32)
            probs = np.asarray(server.submit(img).result())[0]
            hits.append(int(np.argmax(probs)) == lbl)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = server.stats()
        print(f"served {stats['requests']} requests in "
              f"{stats['dispatches']} dispatches "
              f"(aggregation {stats['requests'] / stats['dispatches']:.1f}x), "
              f"accuracy {np.mean(hits):.2f}")
    finally:
        server.close()


if __name__ == "__main__":
    main()
