"""Multi-process sharded-checkpoint worker (tests/test_multiprocess_spmd.py).

Launched by tools/launch.py --coordinator with N processes: trains a
dp-sharded classifier for STEPS_BEFORE steps on a GLOBAL device mesh
spanning the processes, then writes a sharded checkpoint — each process
saving only its addressable shards, process 0 publishing the
{uuid, md5, timestamp} meta (parallel/checkpoint.py; the reference
pserver's per-shard snapshot discipline, go/pserver/service.go:120-203).
The test then restores the snapshot in a SINGLE-process run on a
different mesh and checks the continued training matches the
uninterrupted serial oracle.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import parallel

FEATS, CLS, HIDDEN = 16, 4, 32
STEPS_BEFORE = 5


def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLS)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    return main, startup, loss


def batches(n):
    r = np.random.RandomState(17)
    return [(r.randn(32, FEATS).astype(np.float32),
             r.randint(0, CLS, (32, 1)).astype(np.int64))
            for _ in range(n)]


def main():
    ckpt_dir = sys.argv[1]
    parallel.init_distributed()
    n_dev = len(jax.devices())
    assert jax.process_count() > 1, "run via tools/launch.py --coordinator"
    main_p, startup, loss = build()
    pe = parallel.ParallelExecutor(
        main_p, ["x", "y"], [loss], mesh={"dp": n_dev},
        startup_program=startup, shard_optimizer_states=True)
    for x, y in batches(STEPS_BEFORE):
        out = pe.run({"x": x, "y": y})
    uuid = pe.save_checkpoint(ckpt_dir)
    print(f"proc {jax.process_index()}/{jax.process_count()}: trained "
          f"{STEPS_BEFORE} steps on dp-{n_dev}, saved shard of "
          f"checkpoint {uuid[:8]} OK, loss={float(np.asarray(out[0]))}")


if __name__ == "__main__":
    main()
