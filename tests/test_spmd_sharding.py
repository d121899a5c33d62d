"""Mainline multichip sharding (ROADMAP item 2): GSPMD-style Program
annotations lowered through `ShardingTranspiler` /
`DistributeTranspiler.transpile(mode="spmd")` onto the proven strategy
executors, with compute/collective overlap.

Oracle discipline (the MULTICHIP dryrun contract): a user Program
annotated via `layers.shard` / `data(sharding=...)` and run through the
MAINLINE transpiler on the 8-device virtual mesh must match

  * the serial Executor in trained parameters (strategy equivalence),
  * the hand-built `parallel/composite.py` step in loss trajectory and
    in the pipeline/all-to-all collective structure of the optimized
    HLO,

and the bucketed-psum overlap must be visible STRUCTURALLY (all-reduce
count == bucket count + 1 loss pmean), not just by wall clock.
Diagnostics of the `sharding-consistency` pass are golden-tested.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import parallel
from paddle_tpu.core.flags import get_flag, set_flags
from paddle_tpu.core.framework import Program, reset_unique_names
from paddle_tpu.parallel.spmd import propagate_sharding

FEATS, CLS, HIDDEN, STEPS = 16, 4, 32, 6


# ---------------------------------------------------------------------------
# annotation surface + serialization
# ---------------------------------------------------------------------------


def test_sharding_annotation_roundtrip():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32",
                              sharding=("dp", None))
        h = fluid.layers.fc(input=x, size=HIDDEN)
        fluid.layers.shard(h, (None, ("tp", "dp")))
        fluid.layers.set_program_mesh({"dp": 4, "tp": 2})
    assert x.sharding == ("dp", None)
    assert h.sharding == (None, ("tp", "dp"))
    # op-level dist_attr rider mirrors the annotation
    assert h.op.dist_attr["sharding"][h.name] == [None, ["tp", "dp"]]

    clone = Program.from_dict(main.to_dict())
    blk = clone.global_block()
    assert blk.vars["x"].sharding == ("dp", None)
    assert blk.vars[h.name].sharding == (None, ("tp", "dp"))
    assert clone.mesh_axes == {"dp": 4, "tp": 2}


def test_shard_rejects_contradiction():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=HIDDEN)
        fluid.layers.shard(h, (None, "tp"))
        with pytest.raises(ValueError, match="contradictory"):
            fluid.layers.shard(h, ("tp", None))


# ---------------------------------------------------------------------------
# propagation: the Megatron alternation from one activation annotation
# ---------------------------------------------------------------------------


def _annotated_mlp(annotate=True, second_spec=None):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        if annotate:
            fluid.layers.shard(h, (None, "tp"))
        h2 = fluid.layers.fc(input=h, size=HIDDEN, act="relu")
        if second_spec is not None:
            fluid.layers.shard(h2, second_spec)
        logits = fluid.layers.fc(input=h2, size=CLS)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    params = [p.name for p in main.global_block().all_parameters()]
    return main, startup, loss, params


def test_propagation_derives_megatron_split():
    reset_unique_names()
    main, _, _, _ = _annotated_mlp()
    plan = propagate_sharding(main, {"dp": 4, "tp": 2})
    # one activation annotation -> column w + sharded bias + row w, one
    # pending psum on the row matmul, nothing else invented
    assert plan.param_specs == {"fc_0.w_0": (None, "tp"),
                               "fc_0.b_0": ("tp",),
                               "fc_1.w_0": ("tp", None)}
    assert list(plan.reduce_ops.values()) == [("tp",)]
    assert plan.model_axes == ("tp",)
    assert plan.feed_specs == {"x": ("dp",), "y": ("dp",)}
    assert not plan.findings


# ---------------------------------------------------------------------------
# sharding-consistency pass: golden diagnostics
# ---------------------------------------------------------------------------


def _diags(program, **kw):
    return [d for d in program.verify(level=None,
                                      passes=["sharding-consistency"],
                                      **kw)]


def test_consistency_rank_and_duplicate_axis_errors():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32",
                              sharding=("dp", None, "tp"))  # rank 2 var
        h = fluid.layers.fc(input=x, size=HIDDEN)
        fluid.layers.shard(h, ("tp", "tp"))  # duplicate axis
    ds = _diags(main)
    msgs = [d.message for d in ds if d.severity == "error"]
    assert any("3 entries but the variable is rank 2" in m for m in msgs), ds
    assert any("more than once" in m for m in msgs), ds


def test_consistency_unknown_axis_and_divisibility():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=30)   # 30 % 4 != 0
        fluid.layers.shard(h, (None, "mp"))
        fluid.layers.set_program_mesh({"dp": 2, "tp": 4})
    ds = _diags(main)
    assert any(d.severity == "error" and "undeclared mesh axis" in
               d.message for d in ds), ds

    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=30)
        fluid.layers.shard(h, (None, "tp"))
        fluid.layers.set_program_mesh({"dp": 2, "tp": 4})
    ds = _diags(main2)
    assert any(d.severity == "warning" and "not divisible" in d.message
               for d in ds), ds


def test_consistency_contradictory_contraction_error():
    """First fc column-split over 'tp', but the second weight is
    hand-annotated to contract over 'dp' — one contraction, two axes."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=HIDDEN)
        fluid.layers.shard(h, (None, "tp"))
        h2 = fluid.layers.fc(input=h, size=CLS)
        fluid.layers.shard("fc_1.w_0", ("dp", None))
        del h2
    ds = _diags(main)
    assert any(d.severity == "error" and
               "contradictory specs for one contraction" in d.message
               for d in ds), ds
    # and the transpiler refuses the same program at build time
    t = fluid.ShardingTranspiler()
    with pytest.raises(ValueError, match="inconsistent"):
        t.transpile(program=main, startup_program=startup,
                    mesh={"dp": 4, "tp": 2})


def test_consistency_resharding_hotspot_warning():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        a = fluid.layers.data(name="a", shape=[FEATS], dtype="float32",
                              sharding=("dp", "tp"))
        b = fluid.layers.data(name="b", shape=[FEATS], dtype="float32",
                              sharding=("dp", None))
        c = a + b
        del c
    ds = _diags(main)
    assert any(d.severity == "warning" and "resharding hotspot"
               in d.message for d in ds), ds


def test_unannotated_program_skips_pass():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        fluid.layers.fc(input=x, size=HIDDEN)
    assert _diags(main) == []


# ---------------------------------------------------------------------------
# strategy equivalence through the mainline transpiler (8 virtual devices)
# ---------------------------------------------------------------------------


def _batches(dims=1, n=STEPS):
    r = np.random.RandomState(7)
    return [(r.randn(32, FEATS).astype(np.float32),
             r.randint(0, CLS, (32, 1)).astype(np.int64))
            for _ in range(n)]


def _train_serial(build):
    reset_unique_names()
    main, startup, loss, params = build()
    exe = fluid.Executor(fluid.CPUPlace())
    sc = fluid.Scope()
    exe.run(startup, scope=sc)
    losses = []
    for x, y in _batches():
        out = exe.run(main, feed={"x": x, "y": y}, fetch_list=[loss],
                      scope=sc)
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    return {n: np.asarray(sc.find_var(n)) for n in params}, losses


def test_spmd_overlap_matches_serial_and_buckets_structurally():
    """The dp-8 overlapped step: identical training to serial (tolerance
    = strategy equivalence), and the step as TRACED issues exactly
    bucket count + 1 all-reduces (the loss pmean) — the overlap is
    asserted from collective structure, not wall clock.  The optimized
    HLO is only bounded: XLA's all-reduce combiner may merge neighbours
    (it does on the CPU backend), but never adds one."""
    build = lambda: _annotated_mlp(annotate=False)
    serial_params, serial_losses = _train_serial(build)

    reset_unique_names()
    main, startup, loss, params = build()
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="bucketed", shard_optimizer_states=False)
    pe = t.build_executor(["x", "y"], [loss])
    assert pe.overlap_info["mode"] == "bucketed"
    losses = []
    for x, y in _batches():
        out = pe.run({"x": x, "y": y})
        losses.append(float(np.asarray(out[0]).ravel()[0]))
    for n in params:
        np.testing.assert_allclose(pe.state(n), serial_params[n],
                                   rtol=2e-4, atol=1e-5, err_msg=n)
    np.testing.assert_allclose(losses, serial_losses, rtol=1e-4,
                               atol=1e-6)
    x, y = _batches()[0]
    want = pe.overlap_info["buckets"] + 1
    traced = pe.compiled_collectives({"x": x, "y": y}, optimized=False)
    assert traced.get("all-reduce", 0) == want, (traced, pe.overlap_info)
    cc = pe.compiled_collectives({"x": x, "y": y})
    assert 1 <= cc.get("all-reduce", 0) <= want, (cc, pe.overlap_info)


def test_overlap_bucket_cap_shapes_the_allreduce_count():
    """overlap_bucket_bytes=0 puts every gradient in its own bucket —
    the traced all-reduce count moves with the knob (6 grads -> 7 ARs;
    the combiner bounds what survives optimization)."""
    prev = get_flag("overlap_bucket_bytes")
    set_flags({"overlap_bucket_bytes": 0})
    try:
        reset_unique_names()
        main, startup, loss, _ = _annotated_mlp(annotate=False)
        t = fluid.ShardingTranspiler()
        t.transpile(program=main, startup_program=startup,
                    mesh={"dp": 8}, overlap="bucketed",
                    shard_optimizer_states=False)
        pe = t.build_executor(["x", "y"], [loss])
        assert pe.overlap_info["buckets"] == pe.overlap_info["grads"]
        x, y = _batches(n=1)[0]
        want = pe.overlap_info["grads"] + 1
        traced = pe.compiled_collectives({"x": x, "y": y},
                                         optimized=False)
        assert traced.get("all-reduce", 0) == want, traced
        cc = pe.compiled_collectives({"x": x, "y": y})
        assert 1 <= cc.get("all-reduce", 0) <= want, cc
    finally:
        set_flags({"overlap_bucket_bytes": prev})


def test_shard_rejects_bare_string_spec():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        with pytest.raises(ValueError, match="bare string"):
            fluid.layers.shard(x, "dp")


def _clipped_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=HIDDEN, act="relu")
        logits = fluid.layers.fc(input=h, size=CLS)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        # a tight global-norm clip that actually fires on these grads
        from paddle_tpu.clip import set_gradient_clip

        set_gradient_clip(fluid.GradientClipByGlobalNorm(clip_norm=0.05))
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    params = [p.name for p in main.global_block().all_parameters()]
    return main, startup, loss, params


def test_overlap_runs_grad_clip_on_reduced_grads():
    """Global-norm clip must see the REDUCED full-batch gradients: the
    clip/regularizer ops belong to the update section (outside the
    per-shard map), so clipped training under overlap equals serial."""
    serial_params, _ = _train_serial(_clipped_mlp)

    reset_unique_names()
    main, startup, loss, params = _clipped_mlp()
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="bucketed", shard_optimizer_states=False)
    pe = t.build_executor(["x", "y"], [loss])
    assert pe.overlap_info["mode"] == "bucketed"
    for x, y in _batches():
        pe.run({"x": x, "y": y})
    for n in params:
        np.testing.assert_allclose(pe.state(n), serial_params[n],
                                   rtol=2e-4, atol=1e-5, err_msg=n)


def test_overlap_requires_mean_loss():
    """A sum-reduced loss would make the pmean grad combination wrong
    by a factor of dp — the eligibility analysis must refuse it."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        y = fluid.layers.data(name="y", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=FEATS)
        loss = fluid.layers.reduce_sum(
            fluid.layers.square_error_cost(h, y))
        fluid.SGD(learning_rate=0.01).minimize(loss)
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="bucketed")
    with pytest.raises(ValueError, match="mean"):
        t.build_executor(["x", "y"], [loss])


def test_overlap_stands_down_for_explicit_param_shardings():
    """Explicit param_shardings must gate the overlap exactly like
    annotation-derived placements (the manual-dp shard_map would
    silently gather a tp-split weight)."""
    from jax.sharding import PartitionSpec as P

    reset_unique_names()
    main, startup, loss, _ = _annotated_mlp(annotate=False)
    pe = parallel.ParallelExecutor(
        main, ["x", "y"], [loss], mesh={"dp": 4, "tp": 2},
        startup_program=startup,
        param_shardings={"fc_1.w_0": P(None, "tp")}, overlap="auto")
    assert pe.overlap_info["mode"] == "off"
    assert "param_shardings" in pe.overlap_info["reason"]


def test_propagation_batch_spec_survives_layer_norm():
    """A batch-only ('dp',) spec must pass through normalization
    layers unchanged (only a spec that reaches the feature dim has its
    feature entry cleared)."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        h = fluid.layers.fc(input=x, size=HIDDEN)
        ln = fluid.layers.layer_norm(h)
        h2 = fluid.layers.fc(input=ln, size=HIDDEN)
        fluid.layers.shard(h2, (None, "tp"))
    plan = propagate_sharding(main, {"dp": 4, "tp": 2})
    assert plan.var_specs[ln.name] == ("dp",)
    # downstream Megatron inference still fired past the layer_norm
    assert plan.param_specs.get("fc_1.w_0") == (None, "tp")


def test_overlap_stands_down_for_empty_feed_spec():
    """sharding=() (fully replicated) on a batch feed must stand the
    overlap down with a reason, not crash the eligibility analysis."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32",
                              sharding=())
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=HIDDEN)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(h, y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="auto")
    pe = t.build_executor(["x", "y"], [loss])
    assert pe.overlap_info["mode"] == "off"
    assert "batch axis" in pe.overlap_info["reason"]


def test_overlap_requires_training_program():
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        out = fluid.layers.fc(input=x, size=CLS)
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="bucketed")
    with pytest.raises(ValueError, match="optimizer ops"):
        t.build_executor(["x"], [out])


def test_spmd_tp_matches_serial_with_megatron_placement():
    """dp4 x tp2 via ONE activation annotation: params land under the
    derived Megatron NamedShardings, training matches serial, and
    overlap='auto' records why it stood down (GSPMD keeps tp sharded)."""
    build = lambda: _annotated_mlp(annotate=True)
    serial_params, _ = _train_serial(build)

    reset_unique_names()
    main, startup, loss, params = build()
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup,
                mesh={"dp": 4, "tp": 2}, overlap="auto")
    pe = t.build_executor(["x", "y"], [loss])
    assert pe.overlap_info["mode"] == "off"
    assert "model-parallel" in pe.overlap_info["reason"]
    from jax.sharding import PartitionSpec as P

    assert pe._state_shardings["fc_0.w_0"].spec == P(None, "tp")
    assert pe._state_shardings["fc_1.w_0"].spec == P("tp", None)
    for x, y in _batches():
        pe.run({"x": x, "y": y})
    for n in params:
        np.testing.assert_allclose(pe.state(n), serial_params[n],
                                   rtol=2e-4, atol=1e-5, err_msg=n)
    x, y = _batches(n=1)[0]
    cc = pe.compiled_collectives({"x": x, "y": y})
    assert cc.get("all-reduce", 0) >= 1, cc


# ---------------------------------------------------------------------------
# the composite.py oracle: loss + collective structure (dp2 x pp2 x tp2)
# ---------------------------------------------------------------------------


class _ArrayInit(fluid.initializer.Initializer):
    def __init__(self, arr):
        self.arr = np.asarray(arr, np.float32)

    def __call__(self, var, block):
        block.append_op(
            "assign_value", {}, {"Out": [var.name]},
            {"shape": list(self.arr.shape), "dtype": "float32",
             "values": self.arr.flatten().tolist()})


def test_mainline_transpiler_matches_composite_oracle():
    """The ROADMAP item-2 acceptance: an annotated user Program through
    the MAINLINE `ShardingTranspiler` on 8 simulated devices
    (dp2 x pp2 x tp2, GPipe microbatching, Momentum + ZeRO-1) tracks
    `make_composite_step`'s loss trajectory within the dryrun's
    strategy-equivalence tolerance, and reproduces its pipeline
    collective structure exactly (collective-permute / all-to-all
    counts).  all-reduce/all-gather totals are placement-dependent
    (the oracle shards optimizer state over dp AND tp; this jax's
    shard_map gathers GSPMD-auto axes — see parallel/mesh.py), so for
    them the pin is presence, not count."""
    from paddle_tpu.parallel.composite import (collective_counts,
                                               make_composite_step)
    from paddle_tpu.parallel.mesh import make_mesh

    DIM, HID, PP, N_MICRO, LR, MU, SEED = 8, 16, 2, 4, 0.05, 0.9, 0
    mesh_axes = {"dp": 2, "pp": PP, "tp": 2}
    mesh = make_mesh(mesh_axes)

    step_fn, params, velocity = make_composite_step(
        mesh, dim=DIM, hidden=HID, n_micro=N_MICRO, lr=LR, mu=MU,
        seed=SEED)
    dim, hid = params[0].shape[1], params[0].shape[2]
    r = np.random.RandomState(3)
    batches = [(r.randn(1, 32, dim).astype(np.float32),
                r.randn(1, 32, dim).astype(np.float32))
               for _ in range(STEPS)]
    oracle_losses = []
    for xs, ys in batches:
        params, velocity, loss = step_fn(params, velocity, xs, ys)
        oracle_losses.append(float(loss))
    cc_oracle = collective_counts(step_fn, params, velocity,
                                  batches[0][0], batches[0][1])

    # the SAME model as a fluid Program: staged trunk via
    # pipeline_stage, identical inits via assign_value, same optimizer
    rw = np.random.RandomState(SEED)
    stage_inits = [((rw.randn(dim, hid) * 0.3).astype(np.float32),
                    np.zeros((hid,), np.float32),
                    (rw.randn(hid, dim) * 0.3).astype(np.float32),
                    np.zeros((dim,), np.float32)) for _ in range(PP)]
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        yv = fluid.layers.data(name="y", shape=[dim], dtype="float32")
        h = x
        for s in range(PP):
            w1, b1, w2, b2 = stage_inits[s]
            with fluid.pipeline_stage(s):
                u = fluid.layers.fc(
                    input=h, size=hid, act="tanh",
                    param_attr=fluid.ParamAttr(
                        initializer=_ArrayInit(w1)),
                    bias_attr=fluid.ParamAttr(
                        initializer=_ArrayInit(b1)))
                h = fluid.layers.fc(
                    input=u, size=dim,
                    param_attr=fluid.ParamAttr(
                        initializer=_ArrayInit(w2)),
                    bias_attr=fluid.ParamAttr(
                        initializer=_ArrayInit(b2)))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(h, yv))
        fluid.Momentum(learning_rate=LR, momentum=MU).minimize(loss)

    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh=mesh_axes)
    pe = t.build_executor(["x", "y"], [loss], n_micro=N_MICRO,
                          shard_optimizer_states=True)
    assert type(pe).__name__ == "PipelineExecutor"
    # the transpiler handed the pp program the tp axis: Megatron split
    # derived structurally (w1 column, w2 row)
    specs = {tuple(s) for s in pe.tp_param_specs.values()}
    assert (None, "tp") in specs and ("tp", None) in specs

    dsl_losses = []
    for xs, ys in batches:
        out = pe.run({"x": xs[0], "y": ys[0]})
        dsl_losses.append(float(np.asarray(out[0]).ravel()[0]))
    np.testing.assert_allclose(dsl_losses, oracle_losses, rtol=1e-5,
                               atol=1e-6)

    cc_dsl = pe.compiled_collectives({"x": batches[0][0][0],
                                      "y": batches[0][1][0]})
    assert cc_dsl.get("collective-permute") == \
        cc_oracle.get("collective-permute"), (cc_dsl, cc_oracle)
    assert cc_dsl.get("all-to-all", 0) == cc_oracle.get("all-to-all", 0), \
        (cc_dsl, cc_oracle)
    assert cc_dsl.get("all-reduce", 0) >= 1 and \
        cc_oracle.get("all-reduce", 0) >= 1, (cc_dsl, cc_oracle)


# ---------------------------------------------------------------------------
# annotated feeds
# ---------------------------------------------------------------------------


def test_replicated_feed_annotation_is_honored():
    """A feed annotated fully-replicated (e.g. a shared table) keeps
    its spec instead of the batch-over-dp default."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[FEATS], dtype="float32")
        tbl = fluid.layers.data(name="tbl", shape=[FEATS],
                                dtype="float32", append_batch_size=False,
                                sharding=(None,))
        # tbl: [FEATS] static -> broadcastable add against batch rows
        h = fluid.layers.fc(input=x, size=FEATS)
        out = h + tbl
        loss = fluid.layers.mean(out)
        fluid.SGD(learning_rate=0.1).minimize(loss)
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8})
    pe = t.build_executor(["x", "tbl"], [loss])
    from jax.sharding import PartitionSpec as P

    assert pe._feed_shardings["tbl"].spec == P(None)
    assert pe._feed_shardings["x"].spec == P("dp")  # batch default
    r = np.random.RandomState(0)
    out = pe.run({"x": r.randn(16, FEATS).astype(np.float32),
                  "tbl": r.randn(FEATS).astype(np.float32)})
    assert np.isfinite(out[0]).all()
