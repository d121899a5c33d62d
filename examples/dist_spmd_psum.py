"""Multi-process SPMD worker: cross-process mesh + dp training step.

Run under tools/launch.py --coordinator mode (one process per "host"):
each process contributes its local CPU devices to one GLOBAL mesh, then

  1. a shard_map psum reduces across the process boundary (the DCN/ICI
     collective path the single-process virtual mesh cannot test), and
  2. a real paddle_tpu program (fit-a-line + SGD) trains one step with
     the batch sharded over the global dp axis — XLA inserts the
     cross-process grad psum — and the updated params are checked
     against a local numpy reference of the FULL global batch.

Exit code 0 on every process = pass (tests/test_multiprocess_spmd.py).
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def main():
    from paddle_tpu.parallel import mesh as pmesh

    pmesh.init_distributed()
    nproc = jax.process_count()
    pid = jax.process_index()
    assert nproc >= 2, f"expected a multi-process run, got {nproc}"

    devs = np.array(jax.devices())
    n = devs.size
    mesh = Mesh(devs, ("dp",))

    # ---- 1. raw cross-process psum ---------------------------------------
    sharding = NamedSharding(mesh, P("dp"))
    gshape = (n, 4)

    def cb(idx):
        rows = np.arange(gshape[0], dtype=np.float32)[idx[0]]
        return rows.reshape(-1, 1) * np.ones((1, 4), np.float32)

    arr = jax.make_array_from_callback(gshape, sharding, cb)

    summed = jax.jit(jax.shard_map(
        lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P("dp")))(arr)
    expect = float(sum(range(n)))
    for shard in summed.addressable_shards:
        np.testing.assert_allclose(np.asarray(shard.data), expect)
    print(f"[p{pid}] psum across {nproc} processes / {n} devices OK",
          flush=True)

    # ---- 2. dp-sharded train step of a real program -----------------------
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    LR, BATCH, DIM = 0.1, 4 * n, 3
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name="x", shape=[DIM], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1,
                               param_attr=fluid.ParamAttr(name="w"),
                               bias_attr=fluid.ParamAttr(name="b"))
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.SGD(learning_rate=LR).minimize(loss)

    fn = program_to_fn(main_p, ["x", "y"], [loss.name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {k: np.asarray(scope.find_var(k)) for k in fn.state_in_names}

    r = np.random.RandomState(0)  # same on every process
    xs = r.rand(BATCH, DIM).astype(np.float32)
    ys = (xs @ np.array([1.0, -2.0, 0.5], np.float32))[:, None]

    batch_shard = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())
    feeds = {
        "x": jax.make_array_from_callback(
            xs.shape, batch_shard, lambda idx: xs[idx]),
        "y": jax.make_array_from_callback(
            ys.shape, batch_shard, lambda idx: ys[idx]),
    }
    dev_states = {k: jax.device_put(v, repl) for k, v in states.items()}

    step = jax.jit(fn, in_shardings=(
        {"x": batch_shard, "y": batch_shard},
        {k: repl for k in dev_states}, None))
    fetches, new_states = step(feeds, dev_states, jax.random.key(0))

    # numpy reference over the FULL global batch
    w = states["w"]
    b = states["b"]
    pred_np = xs @ w + b
    gw = 2 * xs.T @ (pred_np - ys) / BATCH
    gb = 2 * np.sum(pred_np - ys, axis=0) / BATCH
    np.testing.assert_allclose(
        np.asarray(jax.device_get(new_states["w"])), w - LR * gw,
        rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(new_states["b"])), b - LR * gb,
        rtol=2e-5)
    print(f"[p{pid}] dp train step (global batch {BATCH}) matches the "
          "full-batch numpy reference OK", flush=True)


if __name__ == "__main__":
    main()
