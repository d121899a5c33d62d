"""Real 2-process jax.distributed SPMD run (VERDICT r1 #8).

The 8-virtual-device conftest mesh cannot test the PROCESS coordination
path (jax.distributed.initialize, cross-process collectives, global
arrays assembled from per-process shards).  This launches two actual
processes through tools/launch.py --coordinator mode — the closest
honest approximation to multi-host DCN this single-host environment
allows — and each worker asserts a cross-process psum and a dp-sharded
program train step against a full-batch numpy reference.
"""
import pytest

pytestmark = pytest.mark.slow

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_spmd_psum_and_dp_step():
    port = _free_port()
    worker = os.path.join(REPO, "examples", "dist_spmd_psum.py")
    launcher = os.path.join(REPO, "tools", "launch.py")
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            # 2 devices per process -> a 4-device global dp mesh
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
        )
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(subprocess.Popen(
            [sys.executable, launcher,
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(pid),
             worker],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    joined = "\n".join(outs)
    assert "psum across 2 processes / 4 devices OK" in joined
    assert joined.count("matches the full-batch numpy reference OK") == 2


def test_two_process_sharded_checkpoint_restores_on_one_process(tmp_path):
    """VERDICT r4 next #4: a 2-process dp-4 SPMD run saves a sharded
    checkpoint (each process writes its own shards, process 0 publishes
    the meta), the run dies, and a SINGLE-process dp-4 run restores it
    and continues to numerics matching the uninterrupted serial run."""
    import numpy as np

    port = _free_port()
    worker = os.path.join(REPO, "examples", "dist_ckpt_worker.py")
    launcher = os.path.join(REPO, "tools", "launch.py")
    ckpt = str(tmp_path / "ckpt")
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
        )
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(subprocess.Popen(
            [sys.executable, launcher,
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(pid),
             worker, ckpt],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    assert all("saved shard of checkpoint" in o for o in outs)
    # exactly one complete snapshot with 2 shard files + meta
    import glob
    shard_files = glob.glob(os.path.join(
        ckpt, "checkpoint_*", "sharded_states.p*_of_2.npz"))
    assert len(shard_files) == 2, shard_files

    # restore in THIS (single) process on a 4-virtual-device mesh and
    # continue; compare to the uninterrupted 10-step serial oracle
    import paddle_tpu as fluid
    from paddle_tpu import parallel
    from paddle_tpu.core.framework import reset_unique_names
    sys.path.insert(0, os.path.join(REPO, "examples"))
    import dist_ckpt_worker as W

    total = 10
    reset_unique_names()
    m, s, loss = W.build()
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    exe.run(s, scope=sc)
    for x, y in W.batches(total):
        exe.run(m, feed={"x": x, "y": y}, fetch_list=[loss], scope=sc)
    params = [p.name for p in m.global_block().all_parameters()]
    serial = {n: np.asarray(sc.find_var(n)) for n in params}

    reset_unique_names()
    m2, s2, loss2 = W.build()
    pe = parallel.ParallelExecutor(
        m2, ["x", "y"], [loss2], mesh={"dp": 4}, startup_program=s2,
        shard_optimizer_states=True)
    meta = pe.restore_checkpoint(ckpt)
    assert meta is not None and meta["trainer_args"]["n_processes"] == 2
    assert pe._step == W.STEPS_BEFORE
    for x, y in W.batches(total)[W.STEPS_BEFORE:]:
        pe.run({"x": x, "y": y})
    delta = max(float(np.abs(pe.state(n) - serial[n]).max())
                for n in params)
    assert delta < 1e-4, delta
