"""The gated delta rule's Pallas kernel (`kernels/delta_rule.py`) in the
Pallas interpreter on the CPU against `lm_block.delta_rule`'s
`jax.numpy` lines, which it replaces in a served step where
`select_delta_rule` returns it and which stay where it refuses: random
float32 states at a toy's heads and at the published 64 heads of 128,
the `fresh` / `live` contract, the selection, and a toy of 2 heads of
128 served through `GenerationServer` with the kernel in.  Whether
Mosaic takes the kernel is `tests/test_kernels_lower_tpu.py`'s.
"""
import functools
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import test_solar_open2_decoder as solar
from paddle_tpu.kernels import delta_rule
from paddle_tpu.models import lm_block
from paddle_tpu.observability import tracing

# float32 sums over 128 keys in another order, on values of order 1:
# measured 2e-7 to 5e-7
TOL = 2e-6


def _arguments(lanes, heads, d_head, neg_eigval, seed=0):
    """state, q, k, v, g, beta as `delta_rule_step` hands them on: q
    and k of unit length a head (q times d_head ** -0.5), g a log decay
    a key channel, beta in (0, 1), doubled under `delta_neg_eigval`."""
    r = np.random.RandomState(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    rows = (lanes, heads, d_head)
    state = r.randn(lanes, heads, d_head, d_head)
    q, k, v = unit(r.randn(*rows)) * d_head ** -0.5, unit(
        r.randn(*rows)), r.randn(*rows)
    g = -np.log1p(np.exp(r.randn(*rows)))
    beta = (2.0 if neg_eigval else 1.0) / (1.0 + np.exp(-r.randn(
        lanes, heads)))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (state, q, k, v, g, beta))


def _kernel(lanes, heads, d_head=128):
    kern, why = delta_rule.select_delta_rule(
        lanes=lanes, heads=heads, d_head=d_head, platform="cpu",
        interpret=True)
    assert kern is not None and why is None
    assert kern.name == delta_rule.NAME
    return kern


def _flags(lanes, fresh=(), dead=()):
    fresh_, live = np.zeros(lanes, bool), np.ones(lanes, bool)
    fresh_[list(fresh)], live[list(dead)] = True, False
    return jnp.asarray(fresh_), jnp.asarray(live)


def _bits(x):
    return np.asarray(x).view(np.int32)


@pytest.mark.parametrize("neg_eigval", [False, True])
@pytest.mark.parametrize("lanes,heads", [(3, 4), (2, 64)])
def test_the_kernel_equals_the_lines(lanes, heads, neg_eigval):
    """State and o, every lane live and none fresh, at a toy's heads
    and at the published 64 heads of 128."""
    args = _arguments(lanes, heads, 128, neg_eigval)
    flags = _flags(lanes)
    want_state, want_o = lm_block.delta_rule(*args, *flags)
    state, o = jax.block_until_ready(
        _kernel(lanes, heads).rule(*args, *flags))
    assert state.dtype == o.dtype == jnp.float32
    np.testing.assert_allclose(state, want_state, rtol=0, atol=TOL)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=TOL)
    assert float(jnp.abs(want_o).max()) > 0.1       # not a test of zeros


@pytest.mark.parametrize("held", ["noise", "nan", "inf"])
def test_a_fresh_lane_starts_from_zeros_whatever_its_row_held(held):
    """Lane 1 is fresh: its state after the position is the rule over a
    zero matrix, bit for bit the same whether its row held noise, NaN
    or infinities (a branch reads no tile there; a product with zero
    would carry a NaN over)."""
    lanes, heads = 3, 4
    state, *rest = _arguments(lanes, heads, 128, True, seed=1)
    fill = {"noise": 7.0, "nan": np.nan, "inf": np.inf}[held]
    flags = _flags(lanes, fresh=[1])
    rule = _kernel(lanes, heads).rule
    got_state, got_o = jax.block_until_ready(
        rule(state.at[1].set(fill), *rest, *flags))
    zero_state, zero_o = jax.block_until_ready(
        rule(state.at[1].set(0.0), *rest, *_flags(lanes)))
    want_state, want_o = lm_block.delta_rule(
        state.at[1].set(fill), *rest, *flags)
    assert np.isfinite(np.asarray(got_state)).all()
    # the other lanes as ever, the fresh one as from a zero row
    np.testing.assert_allclose(got_state, want_state, rtol=0, atol=TOL)
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=TOL)
    np.testing.assert_allclose(got_state[1], zero_state[1], rtol=0,
                               atol=TOL)
    np.testing.assert_allclose(got_o[1], zero_o[1], rtol=0, atol=TOL)
    # a fresh lane's new state is the outer product k (beta v)^T alone
    _, k, v, _, beta = rest
    np.testing.assert_allclose(
        got_state[1], k[1][..., None] * (beta[1][..., None] * v[1])[
            :, None, :], rtol=0, atol=TOL)


@pytest.mark.parametrize("fresh_too", [False, True])
def test_a_lane_that_is_not_live_keeps_its_row_bit_for_bit(fresh_too):
    """Lanes 0 and 2 are not live (lane 2 with NaN in its row, and at
    cursor 0 under `fresh_too`): their rows come back bit for bit, the
    live lane's as the lines give it."""
    lanes, heads = 3, 8
    state, *rest = _arguments(lanes, heads, 128, True, seed=2)
    state = state.at[2, 3, 5].set(np.nan)
    flags = _flags(lanes, fresh=[2] if fresh_too else [], dead=[0, 2])
    got_state, got_o = jax.block_until_ready(
        _kernel(lanes, heads).rule(state, *rest, *flags))
    want_state, want_o = lm_block.delta_rule(state, *rest, *flags)
    for lane in (0, 2):
        assert np.array_equal(_bits(got_state[lane]), _bits(state[lane]))
    np.testing.assert_allclose(got_state[1], want_state[1], rtol=0,
                               atol=TOL)
    # o is the lines' for every lane that holds numbers
    np.testing.assert_allclose(got_o[:2], want_o[:2], rtol=0, atol=TOL)


@pytest.mark.parametrize("shape,platform,reason", [
    (dict(lanes=64, heads=64, d_head=128), "cpu", "not_tpu"),
    (dict(lanes=64, heads=64, d_head=128), "gpu", "not_tpu"),
    (dict(lanes=2, heads=4, d_head=8), "tpu", "lane_misaligned"),
    (dict(lanes=2, heads=4, d_head=192), "tpu", "lane_misaligned"),
    (dict(lanes=2, heads=1, d_head=1024), "tpu", "vmem"),
])
def test_selection_refuses_with_a_reason(shape, platform, reason):
    assert delta_rule.select_delta_rule(**shape, platform=platform) == (
        None, reason)
    assert delta_rule.delta_rule_supports(
        **shape, platform=platform) == reason


@pytest.mark.parametrize("heads,block", [(64, 32), (4, 4), (24, 24),
                                         (96, 32), (6, 6), (100, None)])
def test_a_heads_block_divides_the_heads_and_fits_the_budget(heads, block):
    """The most heads a step that divide the heads, keep the rows'
    blocks whole sublane tiles (or take every head) and fit the budget
    under double buffering."""
    kern, why = delta_rule.select_delta_rule(
        lanes=2, heads=heads, d_head=128, platform="tpu")
    if block is None:
        # 100 heads: its divisors that fit are no whole sublane tiles
        assert (kern, why) == (None, "vmem")
        return
    assert kern.heads_block == block and kern.grid == (2, heads // block)
    assert delta_rule._vmem_bytes(block, 128) <= (
        delta_rule._VMEM_BLOCK_BUDGET)


# sha256 of the rehearsal toy's lowered served step (4 delta heads of
# 8: `select_delta_rule` refuses), taken at this PR's parent commit
# (a block with experts: taken again at PR 63, whose routing orders
# nothing: `tests/test_moe_routing.py` holds it to the results it had)
PARENTS_STEP = (
    "5f635bdc363ce11324dd4a658685153cfd808418ff428b25bede464595b9a1ea")


def _lowered_step(dec, slots=2, nb=4):
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(slots * nb + 1,
                                                 lanes=slots))
    i32 = sds((slots,), np.int32)
    return dec.step.lower(
        g, *pools, sds((slots, nb), np.int32), i32, i32,
        sds((slots,), np.uint32), sds((slots,), np.float32),
        sds((slots,), np.bool_))


def test_the_refused_step_is_the_parents_text():
    """The rehearsal toy's heads are 8 wide: refused (here for the
    platform first), and the step it then lowers to is the parent's,
    letter for letter."""
    spec, d_inner = solar._block()
    m = solar.CONFIG
    _, dec = solar.build_lm_paged_decoder(
        m["vocab_size"], 4, 4, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=d_inner, kv_dtype="bf16", platform="cpu", block=spec)
    assert dec.delta_kernel is None             # chosen when a step is traced
    assert "delta_kernel" not in dec.tick_counts(np.array([3]), 2)
    text = _lowered_step(dec).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEP
    assert dec.delta_kernel == "xla:not_tpu"
    assert dec.tick_counts(np.array([3]), 2)["delta_kernel"] == 0


@pytest.fixture
def interpreted(monkeypatch):
    """From here to the test's end `select_delta_rule`, which a step's
    trace calls with the decoder's platform, hands back the kernel in
    the Pallas interpreter."""
    monkeypatch.setattr(
        delta_rule, "select_delta_rule", functools.partial(
            delta_rule.select_delta_rule, interpret=True))


# the rehearsal toy at 2 delta heads of 128: the narrowest head the
# kernel takes
WIDE = dict(delta_heads=2, delta_d_head=128)


def _wide_toy():
    dec = solar._decoder(**WIDE)
    return dec, {n: np.asarray(w)
                 for n, w in solar._weights(dec, 4).items()}


def _driven(seqs, starts):
    """Each of `seqs`'s logits through the wide toy's `step_logits`,
    lanes out of step, and its decoder and delta states after."""
    dec, g = _wide_toy()
    logits, pools = solar._drive(
        dec, g, seqs, starts=starts,
        pools=dec.init_pool(1 + len(seqs) * solar.NB, lanes=len(seqs)))
    return dec, logits, pools[0][1]


def test_a_step_with_the_kernel_in_equals_the_lines_step(monkeypatch):
    """Logits of sequences driven through `step_logits`, lanes out of
    step (fresh lanes and idle lanes in one tick), with the kernel in
    against the same decoder on the `jax.numpy` lines; the states after
    the last position too."""
    r = np.random.RandomState(5)
    seqs = [list(r.randint(0, solar.V, n)) for n in (9, 13, 6)]
    # what `_drive` checks the pools' shapes against
    monkeypatch.setattr(solar, "STATE", (2, 128, 128))
    monkeypatch.setattr(solar, "TAIL", (solar.TAPS - 1, 3 * 2 * 128))
    plain, want, want_states = _driven(seqs, [0, 2, 5])
    assert plain.delta_kernel == "xla:not_tpu"
    monkeypatch.setattr(
        delta_rule, "select_delta_rule", functools.partial(
            delta_rule.select_delta_rule, interpret=True))
    dec, got, states = _driven(seqs, [0, 2, 5])
    assert dec.delta_kernel == delta_rule.NAME
    assert dec.tick_counts(np.array([3]), 3)["delta_kernel"] == 1
    for a, b in zip(got + list(states), want + list(want_states)):
        np.testing.assert_allclose(a, b, rtol=0, atol=solar.TOL_FP32)


def test_served_with_the_kernel_in_a_hit_equals_the_miss_and_the_lines(
        interpreted, monkeypatch):
    """The toy at 2 heads of 128 through `GenerationServer` with the
    kernel (in the interpreter) in its step: requests that HIT a
    document's prefix and restore a snapshot of the lane's state give,
    token for token, the sampled streams of the same requests on a
    server without a cache, and both are the streams of the
    `jax.numpy` path; the tick spans carry `delta_kernel` 1 and the
    server reports the kernel's name."""
    dec, g = _wide_toy()
    r = np.random.RandomState(3)
    doc = list(r.randint(0, solar.V, 3 * solar.BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, solar.V, n)), 6)
                         for n in (5, 2)]
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        hit, stats = solar._serve(dec, g, True, asks)
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    miss, _ = solar._serve(dec, g, False, asks)
    assert dec.delta_kernel == delta_rule.NAME
    assert stats["delta_kernel"] == delta_rule.NAME
    assert stats["state_snapshots_restored"] == 2
    ticks = [s["attrs"] for s in spans
             if s["name"] == "serving.decode_tick"]
    assert ticks and all(t["delta_kernel"] == 1 for t in ticks)
    monkeypatch.undo()                        # the lines from here on
    plain, _ = _wide_toy()
    lines, plain_stats = solar._serve(plain, g, True, asks)
    assert plain_stats["delta_kernel"] == "xla:not_tpu"
    assert hit == miss == lines
    assert all(len(set(s)) > 3 for s in hit[1:])


def test_the_reader_of_the_counter_on_a_synthetic_run(monkeypatch):
    """`sched_delta_kernel_share` on a `Run` made by hand: the mean of
    `delta_kernel` over the window's tick spans; nothing, and no error,
    from a program that sets no such attribute (the parent's) or keeps
    no spans; and its entry is the benchmark's last."""
    monkeypatch.syspath_prepend(os.path.join(solar.ROOT, "perf"))
    import common

    reader = common.load_module(os.path.join(
        solar.ROOT, "perf", "metrics", "sched_delta_kernel_share.py"))
    spans = [{"name": "serving.decode_tick", "ts": 10.0 + i, "dur": 0.5,
              "attrs": {"delta_layers": 3, "delta_kernel": int(i != 1)}}
             for i in range(4)]
    spans.append({"name": "serving.request", "ts": 11.0, "dur": 0.4,
                  "attrs": {}})
    monkeypatch.setattr(tracing, "finished_spans", lambda: list(spans))
    run = common.Run()
    run.spans = [{"ts": 9.0, "dur": 0.5}, {"ts": 13.0, "dur": 0.6}]
    assert reader.compute(run) == pytest.approx(75.0)
    run.spans = [{"ts": 9.0, "dur": 0.5}, {"ts": 11.0, "dur": 0.6}]
    assert reader.compute(run) == pytest.approx(50.0)   # ticks 0 and 1
    monkeypatch.setattr(tracing, "finished_spans", lambda: [
        dict(s, attrs={"delta_layers": 3, "moe_kernel": 1}) for s in spans])
    assert reader.compute(run) is None
    run.spans = []
    assert reader.compute(run) is None
    spec, = (m for m in solar._json("BENCHMARK.json")["per_layer"]
             if m["name"] == "sched_delta_kernel_share")
    # (a later cell with delta-rule layers lists itself after Solar's)
    assert spec.pop("workloads")[0] == solar.CELL
    assert spec == {
        "name": "sched_delta_kernel_share", "unit": reader.UNIT,
        "better": "higher", "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES}
    assert (reader.LAYER, reader.MOVES, reader.SOURCE) == (
        "kernels", "itl_p95_ms", "program_span")


def test_kernel_pace_rehearses_the_cells_states(tmp_path):
    """`tools/kernel_pace.py --shape solar-open2-250b-serve-docqa64-delta
    --rehearse --check`: the cell's states cut to a toy walk the whole
    kernel in the interpreter (a fresh lane and an idle lane among
    them) and give the `jax.numpy` lines' state and o; its removals
    leave the module as it was; off a TPU the tool gives a time for
    nothing else."""
    import importlib.util
    import json

    path = os.path.join(solar.ROOT, "tools", "kernel_pace.py")
    spec = importlib.util.spec_from_file_location("kernel_pace", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    shape = "solar-open2-250b-serve-docqa64-delta"
    assert tool.SHAPES[shape] == dict(kernel="delta", slots=64, heads=64,
                                      d_head=128)
    out = tmp_path / "pace.json"
    res = tool.main(["--shape", shape, "--rehearse", "--check",
                     "--heads-blocks", "4", "--out", str(out)])
    assert res == json.loads(out.read_text())
    assert res["rehearsal"] and res["hb4.whole"] > 0 and res["xla"] > 0
    assert res["hb4.check"] < 1e-5
    assert res["state_bytes"] == 4 * 3 * 4 * 128 * 128
    head, block = delta_rule._head, delta_rule._state_block
    for variant in tool.DELTA_VARIANTS:
        with tool.delta_removed(variant, delta_rule):
            assert (delta_rule._head is head) == (
                variant != "no_arithmetic")
            assert (delta_rule._state_block is block) == (
                variant != "no_copies")
        assert (delta_rule._head, delta_rule._state_block) == (head, block)
    with pytest.raises(SystemExit, match="no TPU"):
        tool.run(shape)
