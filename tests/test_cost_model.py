"""Static cost analyzer (paddle_tpu.analysis.cost_model + the
collective-safety pass): golden per-op costs, liveness-backed peak-HBM,
comm volume pinned EXACTLY against HLO-counted all-reduce bytes on the
dp8 overlap program, collective-safety deadlock goldens (including a
seeded cross-rank ordering bug the pre-existing passes miss), the
book-matrix roofline verdict reproduction (MOE_r05 / BENCH_r04
measurements, no XLA invoked), the estimated-vs-measured calibration
band, `cli analyze`/`cli verify --json`, generation-model-dir analysis,
and the tools/lint.py locked-IO rule."""
import importlib.util
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis
from paddle_tpu.analysis import cost_model
from paddle_tpu.core.framework import reset_unique_names

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RIDGE = cost_model.ridge_point()  # TPU v5 lite, the bench chip


def _find(diags, pass_id, severity=None):
    return [d for d in diags if d.pass_id == pass_id
            and (severity is None or d.severity == severity)]


# ---------------------------------------------------------------------------
# golden per-op costs
# ---------------------------------------------------------------------------


def test_mul_cost_is_exact_2mkn():
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[32, 64], dtype="float32")
    b.create_var(name="w", shape=[64, 128], dtype="float32")
    op = b.append_op("mul", {"X": ["x"], "Y": ["w"]}, {"Out": ["o"]})
    c = analysis.estimate_op(op, b)
    assert c.kind == "matmul"
    assert c.flops == 2 * 32 * 64 * 128
    assert c.bytes == (32 * 64 + 64 * 128 + 32 * 128) * 4


def test_batch_dim_substitution():
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[-1, 64], dtype="float32")
    b.create_var(name="w", shape=[64, 16], dtype="float32")
    op = b.append_op("mul", {"X": ["x"], "Y": ["w"]}, {"Out": ["o"]})
    assert analysis.estimate_op(op, b, batch_size=8).flops \
        == 2 * 8 * 64 * 16
    assert analysis.estimate_op(op, b, batch_size=128).flops \
        == 2 * 128 * 64 * 16


def test_grad_op_costs_track_forward():
    """The generic '<t>_grad' desc costs 2x the forward for dense
    classes (dX and dY are each a GEMM of the forward's size)."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=16, bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    b = main.global_block()
    costs = {op.type: analysis.estimate_op(op, b, batch_size=32)
             for op in b.ops if op.type in ("mul", "mul_grad")}
    assert costs["mul"].flops == 2 * 32 * 64 * 16
    assert costs["mul_grad"].flops == 2 * costs["mul"].flops
    assert costs["mul_grad"].kind == "matmul"


def test_unknown_op_is_reported_never_zero():
    from paddle_tpu.core.registry import register_op, register_op_cost

    @register_op("cost_model_test_op", inputs=("X",), outputs=("Out",))
    def _lower(ctx, ins, attrs):  # pragma: no cover - never executed
        return {"Out": ins["X"][0]}

    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4, 4], dtype="float32")
    b.append_op("cost_model_test_op", {"X": ["x"]}, {"Out": ["o"]})
    est = analysis.estimate_program(p)
    assert est.unknown_types == {"cost_model_test_op": 1}
    assert est.roofline()["unknown_ops"] == 1
    # the cost-model pass surfaces the coverage gap as a diagnostic
    ds = _find(p.verify(level=None), "cost-model")
    assert any("no cost metadata" in d.message
               and "cost_model_test_op" in d.message for d in ds), ds
    # registering metadata closes the gap
    register_op_cost("cost_model_test_op", kind="elementwise")
    est2 = analysis.estimate_program(p)
    assert not est2.unknown_types
    assert est2.total_flops > 0


def test_explicitly_registered_grad_ops_inherit_forward_kind():
    """dropout_grad (and split/merge_lod_tensor_grad) have their OWN
    registry entries, so get_op_info never falls back to the forward op
    — the kind lookup must, or every dropout training program trips the
    max_unknown_ops=0 budget floor."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.dropout(fluid.layers.fc(input=x, size=8),
                                 dropout_prob=0.5)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(input=h, size=1), y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    est = analysis.estimate_program(main, fetch_names=[loss.name])
    assert not est.unknown_types, est.unknown_types


def test_int8_kv_bytes_match_the_real_decoder_accounting():
    """The serving cost entries use the decoder's own int8 accounting
    (one f32 scale per (layer, block)), not a flat surcharge — the
    analyze report's bytes_per_block must equal
    `build_lm_paged_decoder(...).bytes_per_block` for every kv_dtype."""
    spec = {"vocab_size": 50, "d_model": 256, "n_heads": 4,
            "n_layers": 2, "block_size": 16, "max_blocks_per_seq": 4}
    for kd, want in (("fp32", 2 * 2 * 16 * 256 * 4),
                     ("bf16", 2 * 2 * 16 * 256 * 2),
                     ("int8", 2 * 2 * (16 * 256 + 4))):
        rep = analysis.analyze_generation_spec(spec, kv_dtype=kd)
        assert rep["bytes_per_block"] == want, (kd, rep["bytes_per_block"])
    # int8 residency lever is the documented ~4x, not 3.2x
    fp32 = analysis.analyze_generation_spec(spec, kv_dtype="fp32")
    int8 = analysis.analyze_generation_spec(spec, kv_dtype="int8")
    assert fp32["bytes_per_block"] / int8["bytes_per_block"] > 3.9


def test_flash_attention_never_counts_score_matrix():
    """The fused-attention byte model is q/k/v/out only — no Sq x Sk
    materialization (the Pallas-tier HBM argument, statically)."""
    p = fluid.Program()
    b = p.global_block()
    B, S, H, D = 2, 128, 4, 16
    for n in ("q", "k", "v"):
        b.create_var(name=n, shape=[B, S, H, D], dtype="float32")
    op = b.append_op("flash_attention",
                     {"Q": ["q"], "K": ["k"], "V": ["v"]},
                     {"Out": ["o"]}, {"causal": True})
    c = analysis.estimate_op(op, b)
    assert c.kind == "attention"
    assert c.flops == 4 * B * H * S * S * D * 0.5  # causal halves
    assert c.bytes == 4 * B * S * H * D * 4        # qkv + out, NOT S*S


def test_flash_attention_grad_is_priced_with_a_lowering_of_its_own():
    """`flash_attention_grad` is a registered op (it runs the kernel's
    backward on the forward's saved statistics), so the generic
    '<t>_grad' pricing never sees it: its own estimator must give the
    forward's operations x 2.5, and the program no unknown op."""
    B, S, H, D = 2, 128, 4, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[S, H, D], dtype="float32")
        q.stop_gradient = False
        out = fluid.layers.flash_attention(q, q, q, causal=True)
        loss = fluid.layers.mean(out)
        fluid.backward.calc_gradient(loss, [q])
    b = main.global_block()
    fwd = next(op for op in b.ops if op.type == "flash_attention")
    grad = next(op for op in b.ops if op.type == "flash_attention_grad")
    cf = analysis.estimate_op(fwd, b, batch_size=B)
    cg = analysis.estimate_op(grad, b, batch_size=B)
    assert cf.flops == 4 * B * H * S * S * D * 0.5
    assert cg.kind == "attention" and cg.flops == 2.5 * cf.flops
    assert "grad x2.5" in cg.note
    assert cg.bytes > cf.bytes      # q, k, v, out, d_out in; three out
    est = analysis.estimate_program(main, batch_size=B)
    assert not est.unknown_types, est.unknown_types


# ---------------------------------------------------------------------------
# static peak HBM (liveness + donation)
# ---------------------------------------------------------------------------


def test_peak_hbm_reflects_dead_var_freeing():
    """A chain of same-size temporaries peaks at ~2 live buffers under
    the liveness walk; holding everything to the end (no freeing) costs
    the whole chain — the plan_dead_frees effect, statically."""
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[1024], dtype="float32")
    prev = "x"
    for i in range(6):
        b.append_op("relu", {"X": [prev]}, {"Out": [f"t{i}"]})
        prev = f"t{i}"
    peak = analysis.estimate_peak_hbm(p, feed_names=["x"])
    buf = 1024 * 4
    # at any op: the input + output of that op live (2 buffers)
    assert peak["peak_temp_bytes"] == 2 * buf
    assert peak["no_free_peak_bytes"] == 7 * buf  # x + 6 temps
    assert peak["peak_bytes"] < peak["no_free_peak_bytes"]


def test_peak_hbm_fetched_var_survives_the_step():
    """A fetch target cannot be freed at its last use — the donation
    plan's rule, reflected statically."""
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[1024], dtype="float32")
    b.append_op("relu", {"X": ["x"]}, {"Out": ["early"]})
    for i in range(4):
        b.append_op("relu", {"X": ["early" if i == 0 else f"t{i-1}"]},
                    {"Out": [f"t{i}"]})
    free = analysis.estimate_peak_hbm(p, feed_names=["x"])
    held = analysis.estimate_peak_hbm(p, feed_names=["x"],
                                      fetch_names=["early"])
    assert held["peak_temp_bytes"] == free["peak_temp_bytes"] + 1024 * 4


def test_peak_hbm_counts_persistables_once():
    """Read-write state is donated by the executors (plan_donation
    .states), so params count one copy, not old+new."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=32, bias_attr=False)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    peak = analysis.estimate_peak_hbm(main, feed_names=["x", "y"],
                                      fetch_names=[loss.name])
    w_bytes = 64 * 32 * 4
    # one copy of the weight (+ small optimizer scalars like the lr),
    # NOT old+new
    assert w_bytes <= peak["persistable_bytes"] < 2 * w_bytes


# ---------------------------------------------------------------------------
# comm volume: static estimate == HLO-counted all-reduce bytes
# ---------------------------------------------------------------------------


def _dp_mlp():
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        h2 = fluid.layers.fc(input=h, size=32, act="relu")
        p = fluid.layers.fc(input=h2, size=4, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p, label=y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_comm_volume_matches_hlo_allreduce_bytes_exactly():
    """The acceptance pin: the static gradient-sync volume on the PR 9
    dp8 overlap program equals the summed all-reduce payload bytes of
    the optimized HLO, byte for byte (grad buckets + the loss pmean).
    Runs on the 8 virtual CPU devices conftest always configures."""
    import jax

    from paddle_tpu.parallel.mesh import collective_bytes

    main, startup, loss = _dp_mlp()
    t = fluid.ShardingTranspiler()
    t.transpile(program=main, startup_program=startup, mesh={"dp": 8},
                overlap="bucketed", shard_optimizer_states=False)
    pe = t.build_executor(["x", "y"], [loss])
    assert pe.overlap_info["mode"] == "bucketed"

    r = np.random.RandomState(7)
    feed = {"x": r.randn(32, 16).astype(np.float32),
            "y": r.randint(0, 4, (32, 1)).astype(np.int64)}
    feeds = {
        n: jax.ShapeDtypeStruct(
            np.asarray(v).shape, np.asarray(v).dtype,
            sharding=pe._feed_shardings.get(n, pe._data_sharding))
        for n, v in feed.items()}
    txt = pe._jit_step.lower(feeds, pe._states,
                             jax.random.key(pe._seed)).compile().as_text()
    measured = collective_bytes(txt)["all-reduce"]

    est = analysis.estimate_comm(main, fetch_names=[loss.name])
    static = est.by_axis()["dp"]["all_reduce"]
    assert static == measured, (est.rows, measured)
    # and the components are what the lowering says they are: every
    # trainable param's grad bytes + the f32[1] loss pmean
    grad_bytes = sum(
        int(np.prod(v.shape)) * 4
        for v in main.global_block().all_parameters())
    assert static == grad_bytes + 4


def test_comm_volume_row_parallel_psum_and_reshard():
    """Sharding annotations quantify: a row-split second matmul emits a
    psum over 'tp' of its output bytes (SpmdPlan.reduce_ops), and a
    feature-sharded operand hitting a full-feature op is a quantified
    reshard row (the previously qualitative hotspot warning)."""
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, bias_attr=False)
        fluid.layers.shard(h, (None, "tp"))     # column-split fc1
        out = fluid.layers.fc(input=h, size=8, bias_attr=False)
        fluid.layers.set_program_mesh({"dp": 2, "tp": 2})
    est = analysis.estimate_comm(main, batch_size=32)
    axes = est.by_axis()
    # fc2 infers the row split and contracts locally with one psum of
    # its [32, 8] f32 output
    assert axes["tp"]["all_reduce"] == 32 * 8 * 4, est.rows
    del out

    # a full-feature op on a feature-sharded input quantifies the gather
    reset_unique_names()
    m2, s2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(m2, s2):
        x2 = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h2 = fluid.layers.fc(input=x2, size=32, bias_attr=False)
        fluid.layers.shard(h2, (None, "tp"))
        fluid.layers.softmax_with_cross_entropy(
            h2, fluid.layers.data(name="lbl", shape=[1], dtype="int64"))
        fluid.layers.set_program_mesh({"tp": 2})
    est2 = analysis.estimate_comm(m2, batch_size=32)
    reshard = est2.by_axis().get("tp", {}).get("reshard", 0)
    assert reshard == 32 * 32 * 4, est2.rows


def test_comm_volume_pass_emits_info_rows():
    main, startup, loss = _dp_mlp()
    main.mesh_axes = {"dp": 8}
    ds = _find(main.verify(level=None, fetch_names=[loss.name]),
               "comm-volume")
    assert any("comm volume over 'dp'" in d.message
               and "all_reduce" in d.message for d in ds), ds


# ---------------------------------------------------------------------------
# collective-safety goldens
# ---------------------------------------------------------------------------


def test_collective_safety_cross_rank_ordering_mismatch():
    """The seeded deadlock the ACCEPTANCE names: two pipeline stages
    issue the same ring's collectives in different orders.  Every
    pre-existing pass runs clean on this program — only
    collective-safety catches it."""
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4, 4], dtype="float32")
    with fluid.pipeline_stage(0):
        b.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["a0"]},
                    {"ring_id": "dp"})
        b.append_op("c_allreduce_max", {"X": ["x"]}, {"Out": ["a1"]},
                    {"ring_id": "dp"})
    with fluid.pipeline_stage(1):
        b.append_op("c_allreduce_max", {"X": ["x"]}, {"Out": ["b0"]},
                    {"ring_id": "dp"})
        b.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["b1"]},
                    {"ring_id": "dp"})
    diags = p.verify(level=None)
    d, = _find(diags, "collective-safety", "error")
    assert "ordering mismatch" in d.message and "'dp'" in d.message
    # the pre-existing verifier passes this program clean at error level
    old = [x for x in diags
           if x.pass_id != "collective-safety" and x.severity == "error"]
    assert not old, old


def test_collective_safety_stage_imbalance():
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4], dtype="float32")
    with fluid.pipeline_stage(0):
        b.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["a"]},
                    {"ring_id": "dp"})
    with fluid.pipeline_stage(1):
        b.append_op("relu", {"X": ["x"]}, {"Out": ["r"]})
    d, = _find(p.verify(level=None), "collective-safety", "error")
    assert "imbalance" in d.message


def test_collective_safety_stage_axis_ring_reuse():
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4], dtype="float32")
    with fluid.pipeline_stage(0):
        b.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["a"]},
                    {"ring_id": "pp"})
    d, = _find(p.verify(level=None), "collective-safety", "error")
    assert "reuses ring 'pp'" in d.message
    # the schedule's own hop primitive is exempt
    p2 = fluid.Program()
    b2 = p2.global_block()
    b2.create_var(name="x", shape=[4], dtype="float32")
    with fluid.pipeline_stage(0):
        b2.append_op("c_ppermute", {"X": ["x"]}, {"Out": ["h"]},
                     {"ring_id": "pp"})
    assert not _find(p2.verify(level=None), "collective-safety",
                     "error")


def test_collective_safety_branch_and_loop_sub_blocks():
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4], dtype="float32")
    sub = p.create_block()
    sub.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["y"]},
                  {"ring_id": "dp"})
    p._current_block_idx = 0
    b.append_op("conditional_block", {"X": ["x"]}, {"Out": ["y"]},
                {"sub_block": {"__block__": 1}})
    d = _find(p.verify(level=None), "collective-safety", "error")
    assert d and "different branches" in d[0].message

    p2 = fluid.Program()
    b2 = p2.global_block()
    b2.create_var(name="x", shape=[4], dtype="float32")
    sub2 = p2.create_block()
    sub2.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["y"]},
                   {"ring_id": "dp"})
    p2._current_block_idx = 0
    b2.append_op("while", {"X": ["x"]}, {"Out": ["y"]},
                 {"sub_block": {"__block__": 1}})
    w = _find(p2.verify(level=None), "collective-safety", "warning")
    assert w and "trip count" in w[0].message


def test_collective_safety_clean_spmd_program():
    """Identical per-stage sequences + unstaged collectives: clean."""
    p = fluid.Program()
    b = p.global_block()
    b.create_var(name="x", shape=[4], dtype="float32")
    b.append_op("c_allreduce_sum", {"X": ["x"]}, {"Out": ["g"]},
                {"ring_id": "dp"})  # unstaged: all ranks, uniform
    for s in (0, 1):
        with fluid.pipeline_stage(s):
            b.append_op("c_allreduce_sum", {"X": ["x"]},
                        {"Out": [f"o{s}"]}, {"ring_id": "dp"})
    assert not _find(p.verify(level=None), "collective-safety")


# ---------------------------------------------------------------------------
# book-matrix verdict reproduction (no XLA)
# ---------------------------------------------------------------------------


def _build_moe_lm(batch, seq, vocab, d_model, n_heads, n_layers, experts,
                 top_k, capacity_factor, aux_weight=0.01):
    """The r5 MoE LM bench's program (MOE_r05.json)."""
    from paddle_tpu import nets
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models.transformer import _pre_ln, _proj

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq], dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[seq, 1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[vocab, d_model],
            param_attr={"initializer": NormalInitializer(0.0, 0.02)})
        pos = fluid.layers.create_parameter(
            shape=[seq, d_model], dtype=emb.dtype,
            default_initializer=NormalInitializer(0.0, 0.02))
        x = fluid.layers.elementwise_add(emb, pos, axis=1)
        aux_total = None
        for _ in range(n_layers):
            ln_x = _pre_ln(x)
            q = _proj(ln_x, d_model)
            k = _proj(ln_x, d_model)
            v = _proj(ln_x, d_model)
            att = nets.scaled_dot_product_attention(
                q, k, v, num_heads=n_heads, causal=True)
            x = fluid.layers.elementwise_add(x, _proj(att, d_model))
            f, aux = fluid.layers.moe_ffn(
                _pre_ln(x), num_experts=experts, top_k=top_k,
                capacity_factor=capacity_factor)
            x = fluid.layers.elementwise_add(x, f)
            aux_total = (aux if aux_total is None
                         else fluid.layers.elementwise_add(aux_total, aux))
        x = _pre_ln(x)
        logits = fluid.layers.fc(input=x, size=vocab, num_flatten_dims=2)
        cost = fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, shape=[-1, vocab]),
            fluid.layers.reshape(lbl, shape=[-1, 1]))
        avg = fluid.layers.mean(cost)
        aux_mean = fluid.layers.scale(aux_total,
                                      scale=aux_weight / n_layers)
        loss = fluid.layers.elementwise_add(avg, aux_mean)
        fluid.Momentum(learning_rate=0.01, momentum=0.9).minimize(loss)
    return main, startup, loss


def test_book_matrix_roofline_verdicts_without_xla():
    """`cli analyze`'s estimator reproduces the committed bench
    verdicts statically: the MoE LM bench config (MOE_r05.json: AI
    125.5 vs ridge 240.5, floor_frac 0.863 -> memory-bound) and the
    resnet-50 headline (BENCH_r04: mfu 0.317, hbm_util 0.92 ->
    memory-bound) both flag memory-bound, with static FLOPs inside 2x
    of the XLA-counted per-step FLOPs — and the MOE_r05
    capacity-factor sweep's floor_frac ordering (0.863 > 0.819 > 0.793
    > 0.766 for cf 1.0 < 1.25 < 1.5 < 2.0) is preserved as strictly
    INCREASING static AI (lower AI == deeper under the HBM roof).
    Program builds only — no jit, no XLA compile."""
    # the MOE_r05 measured rows (committed artifact): cf -> floor_frac
    measured_floor_frac = {1.0: 0.863, 1.25: 0.819, 1.5: 0.793,
                           2.0: 0.766}
    moe_measured_flops = 5.93e12  # 88.66 TFLOP/s * 66.86 ms (cf 1.0)

    ais = {}
    for cf in (1.0, 1.25, 1.5, 2.0):
        reset_unique_names()
        main, _, loss = _build_moe_lm(8, 512, 30000, 1024, 8, 6, 8, 2,
                                     cf)
        est = analysis.estimate_program(main, batch_size=8,
                                        fetch_names=[loss.name])
        roof = est.roofline()
        assert not est.unknown_types, est.unknown_types
        ais[cf] = roof["ai_flop_per_byte"]
        if cf == 1.0:
            assert roof["bound"] == "memory", roof
            assert roof["ai_flop_per_byte"] < RIDGE
            ratio = est.total_flops / moe_measured_flops
            assert 0.5 < ratio < 2.0, ratio
    # floor_frac strictly decreasing over cf == static AI strictly
    # increasing over cf: the ordering is preserved
    cfs = sorted(measured_floor_frac)
    assert [ais[c] for c in cfs] == sorted(ais[c] for c in cfs)
    assert ([measured_floor_frac[c] for c in cfs]
            == sorted((measured_floor_frac[c] for c in cfs),
                      reverse=True))

    # resnet-50 imagenet headline config (BENCH_r04.json)
    from paddle_tpu.models.resnet import resnet_imagenet

    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        predict = resnet_imagenet(img, class_dim=1000, depth=50)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        avg = fluid.layers.mean(cost)
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(avg)
    est = analysis.estimate_program(main, batch_size=256,
                                    fetch_names=[avg.name])
    roof = est.roofline()
    assert not est.unknown_types, est.unknown_types
    assert roof["bound"] == "memory", roof
    # analytic convention: 24.6 GFLOP/img train (BENCH_r04.json), bs 256
    ratio = est.total_flops / (24.6e9 * 256)
    assert 0.5 < ratio < 2.0, ratio


# ---------------------------------------------------------------------------
# estimated-vs-measured calibration band (the ONE compiling test)
# ---------------------------------------------------------------------------


def _peak_bytes(mem) -> float:
    """Approximate peak live HBM of one step from the OPTIMIZED module's
    memory analysis (`compiled.memory_analysis()`): arguments + outputs
    + temporaries, minus the aliased (donated) overlap counted in both
    arguments and outputs.  This is the physically-meaningful per-step
    HBM number — `bytes accessed` (cost analysis) is TRAFFIC, which
    over-counts fusion re-reads and was read as "76 GB per step" on a
    16 GB chip."""
    if mem is None:
        return 0.0
    return float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def _step_cost_analysis(main, startup, feeds, fetch_name):
    """(cost, memory, compile_s) of ONE compiled training step — the
    per-step accounting module.  The scan timer's cost analysis counts
    its while-body once, but the scan module's MEMORY analysis includes
    the whole k-step batch stack; this compiles the single-step program
    with the executor's donation plan applied (feeds + rw states ride
    donate_argnums), so FLOPs, bytes accessed, and peak footprint all
    describe exactly one step of the executable users run."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    fn = program_to_fn(main, list(feeds.keys()), [fetch_name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: jax.device_put(np.asarray(scope.find_var(n)))
              for n in fn.state_in_names}
    key = jax.random.key(0)

    def step(fd, st):
        fetches, new = fn(fd, st, key)
        return fetches[fetch_name], new

    donate = ((0, 1) if set(feeds.keys()) <= fn.donation_plan.feeds
              else (1,))
    # device_put through the pytree: LoDTensor wrappers (registered
    # nodes) keep their LoD — sequence ops need it at trace time
    dev_feeds = jax.device_put(dict(feeds))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=donate) \
        .lower(dev_feeds, states).compile()
    compile_s = time.perf_counter() - t0
    return (dict(compiled.cost_analysis()), compiled.memory_analysis(),
            compile_s)


def _static_vs_measured(main, startup, feeds, fetch_name,
                       batch_size=None):
    """Calibration row for the static cost model: the compile-free
    estimate (`paddle_tpu.analysis.estimate_program`) next to the
    XLA-measured per-step accounting (`_step_cost_analysis`), with the
    ratios that bound the model's error.

    Conventions differ by design — the static model counts per-op
    traffic (every op boundary), XLA's `bytes accessed` counts per-FUSION
    traffic, and XLA's flop count includes pointwise work the static
    class constants only approximate — so the honest contract is a
    RATIO BAND, not equality: the test below pins
    `flops_ratio` and `bytes_ratio` (estimated / measured) inside a
    documented tolerance on the fast book subset, which is what makes
    the analyzer's verdicts trustworthy without a compile."""
    from paddle_tpu import analysis

    # batch for -1-dim substitution: explicit wins; else dim 0 of the
    # first feed that FEEDS a -1-leading-dim var (a replicated table or
    # scalar feed must not masquerade as the batch)
    batch = batch_size or 0
    blk = main.global_block()
    if not batch:
        for name, v in feeds.items():
            arr = np.asarray(getattr(v, "data", v))
            var = blk.vars.get(name)
            if (arr.ndim and var is not None and var.shape
                    and var.shape[0] == -1):
                batch = int(arr.shape[0])
                break
    batch = batch or 1  # reported below = actually used
    est = analysis.estimate_program(main, batch_size=batch,
                                    feed_names=list(feeds.keys()),
                                    fetch_names=[fetch_name])
    cost, mem, compile_s = _step_cost_analysis(main, startup, feeds,
                                               fetch_name)
    out = {
        "batch": batch,
        "est_flops": est.total_flops,
        "xla_flops": float((cost or {}).get("flops", 0.0)),
        "est_bytes": est.total_bytes,
        "xla_bytes": float((cost or {}).get("bytes accessed", 0.0)),
        "est_peak_bytes": est.peak_hbm["peak_bytes"],
        "xla_peak_bytes": _peak_bytes(mem),
        "unknown_ops": sum(est.unknown_types.values()),
        "analysis_compile_seconds": round(compile_s, 2),
    }
    for k in ("flops", "bytes", "peak_bytes"):
        meas = out[f"xla_{k}"]
        out[f"{k}_ratio"] = (round(out[f"est_{k}"] / meas, 3)
                             if meas else None)
    return out


def test_static_vs_measured_within_documented_band():
    """The calibration pin: on the fast book subset the static model's
    flops land within [0.5, 2.5]x of XLA's per-step count, traffic
    within [0.4, 3]x of `bytes accessed`, peak HBM within [0.3, 3]x of
    the memory analysis — the documented tolerance that makes the
    compile-free verdicts trustworthy.  (The bands are wide by design:
    the static model counts per-OP traffic, XLA per-FUSION — see the
    cost_model module docstring.  Measured on this harness: flops
    1.15-1.45x, bytes 0.82-1.32x.)"""
    r = np.random.RandomState(0)

    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[13], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.SGD(learning_rate=0.01).minimize(loss)
    feeds = {"x": r.rand(32, 13).astype(np.float32),
             "y": r.rand(32, 1).astype(np.float32)}
    rows = [_static_vs_measured(main, startup, feeds, loss.name)]

    reset_unique_names()
    m2, s2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(m2, s2):
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        lab = fluid.layers.data(name="label", shape=[1], dtype="int64")
        c1 = fluid.nets.simple_img_conv_pool(
            input=img, filter_size=5, num_filters=8, pool_size=2,
            pool_stride=2, act="relu")
        pred2 = fluid.layers.fc(input=c1, size=10, act="softmax")
        loss2 = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred2, label=lab))
        fluid.SGD(learning_rate=0.01).minimize(loss2)
    feeds2 = {"img": r.rand(16, 1, 28, 28).astype(np.float32),
              "label": r.randint(0, 10, (16, 1)).astype(np.int64)}
    rows.append(_static_vs_measured(m2, s2, feeds2, loss2.name))

    for row in rows:
        assert row["unknown_ops"] == 0, row
        assert 0.5 < row["flops_ratio"] < 2.5, row
        assert 0.4 < row["bytes_ratio"] < 3.0, row
        assert 0.3 < row["peak_bytes_ratio"] < 3.0, row


# ---------------------------------------------------------------------------
# cli analyze / verify --json / budget gate
# ---------------------------------------------------------------------------

_CONFIG = """\
import paddle_tpu as fluid

def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        out = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(out, y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    return main, startup
"""


def _write_config(tmp_path):
    cfg = tmp_path / "cfg.py"
    cfg.write_text(_CONFIG)
    return str(cfg)


def test_cli_verify_json(tmp_path, capsys):
    from paddle_tpu.cli import cmd_verify

    reset_unique_names()
    rc = cmd_verify(["--json", _write_config(tmp_path)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["failed"] is False
    assert out["programs"], out
    diags = out["programs"][0]["diagnostics"]
    # structured shape: severity/pass/location/hint per record
    for d in diags:
        assert {"pass", "severity", "message", "location",
                "hint"} <= set(d)
        assert "block" in d["location"]


def test_cli_analyze_json_and_budget_gate(tmp_path, capsys):
    from paddle_tpu.cli import cmd_analyze

    cfg = _write_config(tmp_path)

    reset_unique_names()
    rc = cmd_analyze(["--json", cfg])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and not out["violations"]
    progs = [p for p in out["programs"] if p["kind"] == "program"]
    assert progs
    roof = progs[0]["roofline"]
    assert {"est_flops", "est_hbm_traffic_gb", "est_peak_hbm_gb",
            "ai_flop_per_byte", "ridge_flop_per_byte",
            "bound"} <= set(roof)

    # within-budget: clean exit
    ok_budget = tmp_path / "ok.json"
    ok_budget.write_text(json.dumps({
        "defaults": {"max_unknown_ops": 0},
        "models": {"cfg.py": {"max_flops_g": 1.0,
                              "max_hbm_traffic_gb": 1.0}}}))
    reset_unique_names()
    assert cmd_analyze([cfg, "--budget", str(ok_budget)]) == 0
    capsys.readouterr()

    # over-budget: non-zero exit naming the violation
    bad_budget = tmp_path / "bad.json"
    bad_budget.write_text(json.dumps({
        "models": {"cfg.py": {"max_flops_g": 1e-9}}}))
    reset_unique_names()
    assert cmd_analyze([cfg, "--budget", str(bad_budget)]) == 1
    assert "BUDGET VIOLATION" in capsys.readouterr().out


def test_budget_gate_fails_loud_not_silent(tmp_path, capsys):
    """Review hardening: a budgeted target that yields nothing
    analyzable (config rot, total metadata loss) is a VIOLATION, and a
    budget entry pointed at a generation dir reports unsupported
    instead of silently passing."""
    from paddle_tpu.cli import cmd_analyze
    from paddle_tpu.serving import save_generation_model

    empty_cfg = tmp_path / "empty.py"
    empty_cfg.write_text(
        "import paddle_tpu as fluid\n"
        "def build():\n"
        "    return fluid.Program(), fluid.Program()\n")
    gen = tmp_path / "gen"
    save_generation_model(
        str(gen), {"w": np.zeros((2, 2), np.float32)},
        {"vocab_size": 10, "d_model": 8, "n_heads": 2, "n_layers": 1})
    budget = tmp_path / "b.json"
    budget.write_text(json.dumps({
        "models": {"empty.py": {"max_flops_g": 1.0},
                   "gen": {"max_flops_g": 1.0}}}))
    rc = cmd_analyze([str(empty_cfg), str(gen),
                      "--budget", str(budget)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "no analyzable program" in out
    assert "generation model dirs are not supported" in out


def test_budget_coverage_floor_is_target_wide(tmp_path, capsys):
    """max_unknown_ops gates EVERY program a target builds, not just
    the max-FLOPs headline — a startup-program op losing its metadata
    must fail the gate too."""
    from paddle_tpu.cli import cmd_analyze
    from paddle_tpu.core.registry import register_op

    @register_op("cost_gate_test_op", inputs=("X",), outputs=("Out",))
    def _lower(ctx, ins, attrs):  # pragma: no cover - never executed
        return {"Out": ins["X"][0]}

    cfg = tmp_path / "cfg.py"
    cfg.write_text(_CONFIG.replace(
        "    return main, startup",
        "    aux = fluid.Program()\n"
        "    b = aux.global_block()\n"
        "    b.create_var(name='z', shape=[4], dtype='float32')\n"
        "    b.append_op('cost_gate_test_op', {'X': ['z']},"
        " {'Out': ['o']})\n"
        "    return main, startup, aux"))
    budget = tmp_path / "b.json"
    budget.write_text(json.dumps({
        "models": {"cfg.py": {"max_flops_g": 1.0,
                              "max_unknown_ops": 0}}}))
    reset_unique_names()
    rc = cmd_analyze([str(cfg), "--budget", str(budget)])
    out = capsys.readouterr().out
    assert rc == 1 and "cost_gate_test_op" in out, out


def test_generation_analysis_honors_device():
    spec = {"vocab_size": 100, "d_model": 32, "n_heads": 2,
            "n_layers": 2, "block_size": 4, "max_blocks_per_seq": 8}
    v5e = analysis.analyze_generation_spec(spec)["kernels"][0]
    v4 = analysis.analyze_generation_spec(
        spec, device="TPU v4")["kernels"][0]
    assert v5e["ridge_flop_per_byte"] == round(
        cost_model.ridge_point("TPU v5 lite"), 1)
    assert v4["ridge_flop_per_byte"] == round(
        cost_model.ridge_point("TPU v4"), 1)


def test_lint_ignores_sends_defined_not_executed_under_lock():
    """A lambda/def body built under the lock runs after release —
    rule 4 must not descend into it."""
    import ast as _ast

    lint = _load_lint()
    src = (
        "class C:\n"
        "    def f(self, buf):\n"
        "        with self._lock:\n"
        "            self._flush = lambda: self._sock.sendall(buf)\n"
        "            def later():\n"
        "                return self._sock.recv(4)\n"
        "            self._later = later\n")
    assert list(lint.check_locked_io(_ast.parse(src), "x.py",
                                     src.splitlines())) == []


def test_collective_bytes_counts_async_start_once():
    """An async `-start` pair's (operand, result) tuple counts the
    payload ONCE — same convention as the sync form."""
    from paddle_tpu.parallel.mesh import collective_bytes

    sync = ("  %ar = f32[1024]{0} all-reduce(f32[1024]{0} %p), "
            "replica_groups={{0,1}}\n")
    asy = ("  %ars = (f32[1024]{0}, f32[1024]{0}) "
           "all-reduce-start(f32[1024]{0} %p), replica_groups={{0,1}}\n"
           "  %ard = f32[1024]{0} all-reduce-done(%ars)\n")
    assert collective_bytes(sync) == {"all-reduce": 4096}
    assert collective_bytes(asy) == {"all-reduce": 4096}
    # permute-start's trailing u32[] context scalars are not the payload
    perm = ("  %cps = (f32[128]{0}, f32[128]{0}, u32[], u32[]) "
            "collective-permute-start(f32[128]{0} %p)\n")
    assert collective_bytes(perm) == {"collective-permute": 512}


def test_lint_lock_names_are_token_matched():
    """`seconds` is not a condition variable: rule 4's lock detection
    matches name tokens, not substrings."""
    import ast as _ast

    lint = _load_lint()
    src = (
        "class C:\n"
        "    def f(self, data):\n"
        "        with self.track_seconds():\n"
        "            self._sock.sendall(data)\n"
        "    def g(self, data):\n"
        "        with self._cond:\n"
        "            self._sock.sendall(data)\n")
    hits = list(lint.check_locked_io(_ast.parse(src), "x.py",
                                     src.splitlines()))
    assert len(hits) == 1 and hits[0][1] == 7  # only the _cond body


def test_check_budget_verdict_and_coverage():
    report = {"roofline": {"est_flops": 2e9, "est_hbm_traffic_gb": 0.5,
                           "est_peak_hbm_gb": 0.1, "bound": "compute",
                           "unknown_ops": 2,
                           "unknown_types": ["weird_op"]},
              "comm": {"dp": {"all_reduce": 4e9}}}
    v = analysis.check_budget(report, {
        "max_flops_g": 1.0, "bound": "memory", "max_unknown_ops": 0,
        "max_comm_gb": {"dp": 1.0}})
    text = "\n".join(v)
    assert "flops" in text and "verdict changed" in text
    assert "unknown-cost ops" in text and "comm[dp]" in text
    assert not analysis.check_budget(report, {"max_flops_g": 3.0,
                                              "bound": "compute"})


def test_cli_analyze_generation_model_dir(tmp_path, capsys):
    """`cli analyze` on a save_generation_model dir: the serving-kernel
    cost entries answer without building a decoder, and the
    step_window row shows the speculative-decoding AI lever (more
    flops per parameter read)."""
    from paddle_tpu.cli import cmd_analyze
    from paddle_tpu.serving import save_generation_model

    d = tmp_path / "genmodel"
    spec = {"vocab_size": 100, "d_model": 32, "n_heads": 2,
            "n_layers": 2, "block_size": 4, "max_blocks_per_seq": 8,
            "slots": 4, "kv_dtype": "int8", "spec_k": 2}
    save_generation_model(str(d), {"w": np.zeros((2, 2), np.float32)},
                          spec)
    rc = cmd_analyze([str(d)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "generation model dir" in out
    assert "paged_decode_step" in out and "memory-bound" in out

    step = analysis.serving_kernel_cost("paged_decode_step", spec,
                                        slots=4, kv_dtype="int8")
    window = analysis.serving_kernel_cost("paged_decode_step", spec,
                                          slots=4, kv_dtype="int8",
                                          window=3)
    assert window["ai_flop_per_byte"] > step["ai_flop_per_byte"]
    assert step["bound"] == "memory"
    gather = analysis.serving_kernel_cost("paged_attention_gather",
                                          spec, slots=4, context=16)
    assert gather["bytes"] > 0 and "shapes" in gather
    # the gather reads every page of every table whatever the cursor;
    # the streaming kernel the pages the cursor has reached
    kernel = analysis.serving_kernel_cost("paged_attention_decode",
                                          spec, slots=4, context=16)
    assert gather["pages_read"] == 4 * 2 * 8
    assert kernel["pages_read"] == 4 * 2 * 4
    assert gather["bytes"] == 3 * gather["pool_bytes"]
    assert kernel["pool_bytes"] == gather["pool_bytes"] / 2
    # beside its pages the kernel moves a query and a result at the
    # heads' own width: [slots, d_model] in the pool's dtype read, and
    # float32 written and read, a layer (n_heads times as much when the
    # block-diagonal operand was built outside it)
    assert kernel["query_result_bytes"] == 4 * 2 * 32 * (4 + 2 * 4)
    assert kernel["bytes"] == (kernel["pool_bytes"]
                               + kernel["query_result_bytes"])
    assert kernel["shapes"]["query"] == "[4, 32]"
    assert analysis.serving_kernel_cost(
        "paged_attention_gather", spec, slots=4,
        context=1)["bytes"] == gather["bytes"]


# ---------------------------------------------------------------------------
# tools/lint.py rule 4: no blocking send/recv under a lock
# ---------------------------------------------------------------------------


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "repo_lint", os.path.join(REPO, "tools", "lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_LOCKED_IO_BAD = """\
import threading

class C:
    def __init__(self, sock):
        self._lock = threading.Lock()
        self._sock = sock

    def bad(self, data):
        with self._lock:
            self._sock.sendall(data)
            return self._sock.recv(4)
"""

_LOCKED_IO_ALLOWED = """\
import threading

class C:
    def __init__(self, sock):
        self._conn_lock = threading.Lock()   # per-endpoint worker
        self._lock = threading.Lock()
        self._sock = sock

    def per_endpoint(self, data):
        with self._conn_lock:
            self._sock.sendall(data)

    def annotated(self, data):
        with self._lock:  # lint: send-under-lock-ok (single-owner)
            self._sock.sendall(data)

    def io_outside(self, data):
        with self._lock:
            payload = bytes(data)
        self._sock.sendall(payload)
"""


def test_lint_flags_send_under_lock(tmp_path):
    lint = _load_lint()
    bad = tmp_path / "parallel" / "mod.py"
    bad.parent.mkdir()
    bad.write_text(_LOCKED_IO_BAD)
    hits = list(lint.check_locked_io(
        __import__("ast").parse(_LOCKED_IO_BAD), str(bad),
        _LOCKED_IO_BAD.splitlines()))
    assert len(hits) == 2  # sendall + recv
    assert all("convoys" in h[2] for h in hits)


def test_lint_allowlists_per_endpoint_worker(tmp_path):
    lint = _load_lint()
    hits = list(lint.check_locked_io(
        __import__("ast").parse(_LOCKED_IO_ALLOWED), "x.py",
        _LOCKED_IO_ALLOWED.splitlines()))
    assert hits == []


def test_lint_repo_is_clean_under_locked_io_rule():
    """parallel/, cloud/, serving/ hold no blocking wire call under a
    lock (the PR 7/8 review hardening moved them all out); rule 4 keeps
    it that way."""
    import ast as _ast

    lint = _load_lint()
    hits = []
    for sub in ("parallel", "cloud", "serving"):
        base = os.path.join(REPO, "paddle_tpu", sub)
        for path in lint.iter_py_files([base]):
            with open(path) as f:
                src = f.read()
            hits.extend(lint.check_locked_io(
                _ast.parse(src), path, src.splitlines()))
    assert hits == [], hits


# ---------------------------------------------------------------------------
# pass hygiene: the cost passes stay quiet where they should
# ---------------------------------------------------------------------------


def test_cost_passes_never_error_on_clean_programs():
    """cost-model/comm-volume diagnostics are info-only (the budget
    gate, not the verifier, is the failure surface) — an armed
    PADDLE_TPU_VERIFY=error run must not start failing on estimates."""
    main, startup, loss = _dp_mlp()
    for prog in (main, startup):
        for pid in ("cost-model", "comm-volume"):
            ds = _find(prog.verify(level=None), pid)
            assert all(d.severity == "info" for d in ds), (pid, ds)
