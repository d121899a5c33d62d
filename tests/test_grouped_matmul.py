"""The served expert layer's Pallas grouped matmul and its selection
(paddle_tpu/kernels/grouped_matmul.py).  Everything here runs on a
CPU: the kernel under the Pallas interpreter, texts through
`lower(lowering_platforms=("tpu",))`.

Pins three contracts:

  * the kernel is an IMPLEMENTATION swap for `lm_block.moe_ffn`'s
    three `jax.lax.ragged_dot`s over the same sorted rows, whatever
    the group sizes, and a row's result depends on no other row, on no
    group size and on no tile boundary;
  * which of the two runs is a function of the shapes, the weights'
    dtype and the platform, and of nothing else: every cell of the
    benchmark with experts selects the kernel on a TPU, read from its
    own files, with an expert's whole matrix as one block wherever
    that fits and tiles of its rows (K) where it does not;
  * set-up: the kernel's bodies are traced once a process and lowered
    once a program however many layers call them, and building a
    decoder compiles and runs nothing.
"""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.executor import xla_compile_counts
from paddle_tpu.kernels import grouped_matmul
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# reduced widths that keep the multiples of 128 the TPU selection asks
D, F = 256, 128


def _operands(sizes, dtype, seed=0, d=D, f=F):
    e_n, rows = len(sizes), int(np.sum(sizes))
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.normal(0, 1, (rows, d)), dtype),
            jnp.asarray(r.normal(0, 0.1, (e_n, d, f)), dtype),
            jnp.asarray(r.normal(0, 0.1, (e_n, d, f)), dtype),
            jnp.asarray(r.normal(0, 0.1, (e_n, f, d)), dtype))


def _kernel(rows, e_n, dtype, d=D, f=F):
    kern, reason = grouped_matmul.select_grouped_matmul(
        rows=rows, d_model=d, d_ff=f, n_experts=e_n, dtype=dtype,
        platform="cpu", interpret=True)
    assert reason is None and kern.name == grouped_matmul.NAME
    return kern


def _through_kernel(x, w_gate, w_up, w_down, sizes):
    kern = _kernel(x.shape[0], len(sizes), x.dtype, x.shape[1],
                   w_gate.shape[-1])
    plan = kern.plan(jnp.asarray(sizes, jnp.int32))
    act = kern.gate_up(x, w_gate, w_up, plan)
    return act, kern.down(act, w_down, plan)


def _through_ragged_dot(x, w_gate, w_up, w_down, sizes):
    """`moe_ffn`'s fallback, line for line."""
    sizes, f32 = jnp.asarray(sizes, jnp.int32), jnp.float32
    gate = jax.lax.ragged_dot(x, w_gate, sizes, preferred_element_type=f32)
    up = jax.lax.ragged_dot(x, w_up, sizes, preferred_element_type=f32)
    act = (jax.nn.silu(gate) * up).astype(w_down.dtype)
    return act, jax.lax.ragged_dot(act, w_down, sizes,
                                   preferred_element_type=f32)


def _uniform(rows, e_n, seed):
    return np.bincount(np.random.RandomState(seed).randint(0, e_n, rows),
                       minlength=e_n)


GROUPS = {
    "all_rows_to_one_expert": [0, 0, 40, 0, 0, 0],
    "every_expert_one_row": [1] * 16,
    "empty_experts_first": [0, 0, 0, 5, 9, 3],
    "empty_experts_last": [7, 2, 8, 0, 0, 0],
    "empty_experts_in_runs": [4, 0, 0, 6, 0, 0, 0, 1, 0, 5],
    # tiles of 16 (36 rows): the first group ends inside the second
    # tile, the third spans the second and third
    "a_group_straddles_row_tiles": [20, 0, 13, 3],
    "fewer_rows_than_a_tile": [2, 0, 1, 3],
    "rows_256_over_64_experts": _uniform(256, 64, 1),
    "rows_768_over_64_experts": _uniform(768, 64, 2),
}


@pytest.mark.parametrize("sizes", list(GROUPS.values()), ids=list(GROUPS))
def test_kernel_is_ragged_dot_over_the_same_sorted_rows(sizes):
    """bf16 operands, float32 accumulation: the gated product within
    one bf16 ulp of `ragged_dot`'s (the CPU's two matmuls sum K in
    different orders, so a value at a rounding boundary may fall on
    either side; a product that cancelled to 1e-5 carries the sums'
    absolute error instead), the down product within what one such
    ulp moves."""
    ops = _operands(sizes, jnp.bfloat16)
    act, out = _through_kernel(*ops, sizes)
    want_act, want = _through_ragged_dot(*ops, sizes)
    assert act.dtype == jnp.bfloat16 and out.dtype == jnp.float32
    a, wa = (np.asarray(v, np.float32) for v in (act, want_act))
    larger = np.maximum(np.maximum(np.abs(a), np.abs(wa)), 1e-30)
    ulp = 2.0 ** (np.floor(np.log2(larger)) - 7)
    assert np.all(np.abs(a - wa) <= np.maximum(ulp, 1e-6))
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(out) - np.asarray(want)).max() <= scale * 2 ** -8


def test_float32_operands_match_ragged_dot():
    """The interpreter takes the toy float32 weights the decoder tests
    use (a TPU build refuses them: `weights_dtype`)."""
    sizes = [3, 0, 2, 1, 0, 6]
    ops = _operands(sizes, jnp.float32, d=64, f=32)
    act, out = _through_kernel(*ops, sizes)
    want_act, want = _through_ragged_dot(*ops, sizes)
    np.testing.assert_allclose(act, want_act, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_a_rows_result_depends_on_no_other_row_group_or_tile():
    """Expert 2's five rows alone at the top of a tile, and the same
    rows behind 27 rows of other experts, where the group straddles a
    tile boundary and shares both tiles: bit for bit the same."""
    x, w_gate, w_up, w_down = _operands([40, 0, 0], jnp.bfloat16, seed=3)
    mine = x[:5]
    alone = [0, 0, 5]
    crowded = [20, 7, 5, 8]
    w4 = [jnp.concatenate([w, w[:1]]) for w in (w_gate, w_up, w_down)]
    rows = jnp.concatenate([x[5:32], mine, x[32:40]])
    _, out_alone = _through_kernel(mine, w_gate, w_up, w_down, alone)
    _, out_crowded = _through_kernel(rows, *w4, crowded)
    assert np.array_equal(np.asarray(out_alone),
                          np.asarray(out_crowded)[27:32])


def test_work_items_visit_each_expert_with_rows_once_a_tile():
    """The plan: a group has one item for every tile it has rows in,
    groups in order; an expert with no rows has none; items past the
    last repeat its indices (no block moves)."""
    kern = _kernel(36, 4, jnp.bfloat16)
    assert kern.row_tile == 32
    group, tile, offsets, total = (np.asarray(v) for v in kern.plan(
        jnp.asarray([20, 0, 13, 3], jnp.int32)))
    assert offsets.tolist() == [0, 20, 20, 33, 36]
    assert total.tolist() == [4]
    assert group.tolist() == [0, 2, 2, 3, 3]      # 2 tiles + 4 - 1
    assert tile.tolist() == [0, 0, 1, 1, 1]


def _cell_shapes(workload):
    def load(*parts):
        with open(os.path.join(ROOT, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = load("perf", "configs", w["config"] + ".json")
    t = load("perf", "traffic", w["traffic"] + ".json")
    return dict(rows=int(t["slots"]) * m["num_experts_per_tok"],
                d_model=m["hidden_size"], d_ff=m[m["block"]["d_inner"]],
                n_experts=m["num_experts"],
                dtype={"bfloat16": jnp.bfloat16}[m["dtype"]])


OLMOE, MELLUM, GRANITE, K_EXAONE = (
    "olmoe-1b-7b-serve-chat32", "mellum2-12b-a2.5b-serve-agent96",
    "granite-4.0-h-small-serve-chat64", "k-exaone-236b-a23b-serve-chat64")


@pytest.mark.parametrize("shapes,platform,interpret,want", [
    # 256 rows, 64 experts of 2048 x 1024, bf16
    (OLMOE, "tpu", False, None),
    # 768 rows, 64 experts of 2304 x 896, bf16
    (MELLUM, "tpu", False, None),
    # off a TPU there is nothing to compile the kernel with...
    (OLMOE, "cpu", False, "not_tpu"),
    # ...unless a test asks for the Pallas interpreter
    (OLMOE, "cpu", True, None),
    # the operands are the weights as the state dict holds them
    (dict(dtype=jnp.float32), "tpu", False, "weights_dtype"),
    # Mosaic's lane grid
    (dict(d_ff=1000), "tpu", False, "width_misaligned"),
    (dict(d_model=1000), "tpu", False, "width_misaligned"),
    # 8192 x 4096 twice over is past VMEM whole, and goes in tiles
    (dict(d_model=8192, d_ff=4096), "tpu", False, None),
    # not even one lane tile of 131072 rows fits
    (dict(d_model=131072), "tpu", False, "vmem"),
    # a speculative window's rows are a shape like any other
    (dict(rows=32 * 5 * 8), "tpu", False, None),
], ids=["olmoe-tpu", "mellum2-tpu", "olmoe-cpu", "olmoe-cpu-interpret",
        "float32-weights", "expert-width-1000", "model-width-1000",
        "tiled", "past-vmem", "window-rows"])
def test_selection_follows_shapes_dtype_and_platform(shapes, platform,
                                                     interpret, want):
    """The benchmark's own shapes, read from the cells' files."""
    if isinstance(shapes, str):
        shapes = _cell_shapes(shapes)
    else:
        shapes = dict(_cell_shapes(OLMOE), **shapes)
    kern, reason = grouped_matmul.select_grouped_matmul(
        platform=platform, interpret=interpret, **shapes)
    assert reason == want
    assert (kern is None) == (want is not None)
    assert grouped_matmul.grouped_matmul_supports(
        platform=platform, interpret=interpret, **shapes) == want


@pytest.mark.parametrize("workload,tiles", [
    # an expert's whole matrix is one block, as before there were tiles
    (OLMOE, (2048, 1024)), (MELLUM, (2304, 896)), (GRANITE, (4096, 768)),
    # 6144 x 2048: gate and up in three tiles of 2048 rows, down in two
    # of 1024
    (K_EXAONE, (2048, 1024))])
def test_k_tiles_are_the_whole_matrix_wherever_it_fits(workload, tiles):
    """The three cells that had experts before K tiles keep the block
    they had (the whole matrix: their compiled steps hold the Pallas
    calls they held), and the wide experts get the largest equal split
    of their rows that fits the kernel's VMEM."""
    s = _cell_shapes(workload)
    kern, reason = grouped_matmul.select_grouped_matmul(platform="tpu", **s)
    assert reason is None and kern.k_tiles == tiles
    assert kern.row_tile == 128
    whole = tiles == (s["d_model"], s["d_ff"])
    assert whole == (workload != K_EXAONE)
    item = jnp.dtype(s["dtype"]).itemsize
    assert grouped_matmul._call_vmem_bytes(
        128, tiles[0], s["d_ff"], 2, item, item, accumulate=not whole) \
        <= grouped_matmul._VMEM_LIMIT_BYTES
    if not whole:
        # the next larger split (halves) does not fit
        assert grouped_matmul._call_vmem_bytes(
            128, s["d_model"] // 2, s["d_ff"], 2, item, item,
            accumulate=True) > grouped_matmul._VMEM_LIMIT_BYTES


@pytest.mark.parametrize("sizes", [
    # 128 sorted rows of which 16 are in a group: seven eighths sort
    # past the last group, as on a chip that holds an eighth of the
    # experts
    [3, 0, 5, 1, 0, 2, 4, 1],
    # a group that straddles the two row tiles, and rows past the end
    [40, 0, 30, 3, 0, 0, 9, 0],
    [0, 0, 0, 0, 0, 0, 0, 128],
], ids=["seven_eighths_past_the_last_group", "straddles_row_tiles",
        "all_rows_to_the_last_expert"])
def test_k_tiled_kernel_is_ragged_dot_over_the_same_sorted_rows(
        sizes, monkeypatch):
    """Under a VMEM limit that a toy expert's whole matrix does not
    fit, gate and up go in four K tiles and down in two (the grid
    gains the tiles as its inner axis, the partial products a float32
    accumulator): the rows that are in a group equal `ragged_dot`'s as
    the whole-matrix kernel's do (the sum over K in another order);
    rows past the last group are whatever was there (`moe_ffn` masks
    them)."""
    monkeypatch.setattr(grouped_matmul, "_VMEM_LIMIT_BYTES", 1500 * 1024)
    d, f, rows = 512, 512, 128
    x, w_gate, w_up, w_down = _operands([rows], jnp.bfloat16, d=d, f=f)
    w_gate, w_up, w_down = (jnp.concatenate([w] * len(sizes)) * (
        1.0 + jnp.arange(len(sizes), dtype=w.dtype)[:, None, None] / 8)
        for w in (w_gate, w_up, w_down))
    kern = _kernel(rows, len(sizes), jnp.bfloat16, d, f)
    assert kern.row_tile == 64 and kern.k_tiles == (128, 256)
    plan = kern.plan(jnp.asarray(sizes, jnp.int32))
    # the static bound on the items is past the groups that have rows:
    # the padded items must move no block (they repeat the last one's)
    assert int(plan[3][0]) < plan[0].shape[0]
    act = kern.gate_up(x, w_gate, w_up, plan)
    out = kern.down(act, w_down, plan)
    want_act, want = _through_ragged_dot(x, w_gate, w_up, w_down, sizes)
    n = int(np.sum(sizes))
    a, wa = (np.asarray(v, np.float32)[:n] for v in (act, want_act))
    larger = np.maximum(np.maximum(np.abs(a), np.abs(wa)), 1e-30)
    ulp = 2.0 ** (np.floor(np.log2(larger)) - 7)
    assert np.all(np.abs(a - wa) <= np.maximum(ulp, 1e-6))
    scale = float(np.abs(np.asarray(want)[:n]).max())
    assert np.abs(np.asarray(out)[:n] - np.asarray(want)[:n]).max() \
        <= scale * 2 ** -8
    # the whole-matrix kernel over the same operands: within the same
    # ulp (one sum over K against four partial sums)
    monkeypatch.undo()
    whole = _kernel(rows, len(sizes), jnp.bfloat16, d, f)
    assert whole.k_tiles == (d, f)
    a1 = np.asarray(whole.gate_up(x, w_gate, w_up, plan), np.float32)[:n]
    assert np.all(np.abs(a1 - a) <= np.maximum(ulp, 1e-6))


def test_items_past_the_last_repeat_its_last_k_tile():
    """`_k_index`, which every block's index map of a K-tiled call
    goes through: an item with rows walks its expert's K tiles in
    turn, a padded item addresses the last item's FINAL tile at every
    step of its walk, so no block moves for it."""
    total = jnp.asarray([3], jnp.int32)
    seen = [[int(grouped_matmul._k_index(w, j, total, 4))
             for j in range(4)] for w in range(5)]
    assert seen == [[0, 1, 2, 3]] * 3 + [[3, 3, 3, 3]] * 2


@pytest.mark.parametrize("workload", [OLMOE, MELLUM, K_EXAONE])
def test_kernel_lowers_for_tpu_at_the_cells_widths(workload):
    """Both calls reach the TPU lowering as Mosaic custom calls at the
    published widths, on the weights as the state dict holds them."""
    s = _cell_shapes(workload)
    kern, _ = grouped_matmul.select_grouped_matmul(platform="tpu", **s)
    sds, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    e_n, d, f = s["n_experts"], s["d_model"], s["d_ff"]

    def experts(x, w_gate, w_up, w_down, sizes):
        plan = kern.plan(sizes)
        return kern.down(kern.gate_up(x, w_gate, w_up, plan), w_down, plan)

    text = jax.jit(experts).trace(
        sds((s["rows"], d), bf), sds((e_n, d, f), bf), sds((e_n, d, f), bf),
        sds((e_n, f, d), bf), sds((e_n,), jnp.int32)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert f"tensor<{e_n}x{d}x{f}xf32>" not in text


def _routing_decoder(n_layers, d_model=128, d_inner=128, n_experts=4):
    return build_lm_paged_decoder(
        61, 4, 4, d_model=d_model, n_heads=2, n_layers=n_layers,
        d_inner=d_inner, kv_dtype="bf16", platform="tpu",
        block=lm_block.olmoe(n_experts=n_experts, experts_per_token=2))[1]


def _step_args(dec, slots):
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    g = {n: sds(s, jnp.bfloat16) for n, s in dec.state_shapes.items()}
    pool = sds((dec.n_layers, 1 + slots * dec.max_blocks_per_seq,
                dec.block_size, dec.d_model), jnp.bfloat16)
    return (g, pool, pool, sds((slots, dec.max_blocks_per_seq), i32),
            sds((slots,), i32), sds((slots,), i32),
            sds((slots,), jnp.uint32), sds((slots,), jnp.float32),
            sds((slots,), jnp.bool_))


def _mosaic_modules(text):
    """The distinct serialized Mosaic bodies in a lowered text."""
    return set(re.findall(r'body\\22: \\22([^\\"]+)', text))


def test_kernel_is_traced_once_a_process_and_lowered_once_a_program(
        monkeypatch):
    """The set-up guard: a step is unrolled over its layers, and an
    inline `pallas_call` is traced and lowered to Mosaic at every call
    site in every process (three programs before a replica's window:
    36 layers' worth on the OLMoE cell, and no compile cache keeps
    it).  Behind the module-level `jax.jit`, four layers call the
    kernel's Python body twice in all (the gated call and down), a
    second program in the same process not at all, and the lowered
    text holds two Mosaic modules."""
    calls = []
    body = grouped_matmul._kernel

    def counted(*args, **kwargs):
        calls.append(kwargs["gated"])
        return body(*args, **kwargs)

    monkeypatch.setattr(grouped_matmul, "_kernel", counted)
    # widths no other test of this process traces the kernel at
    dec = _routing_decoder(4, d_model=384, d_inner=256, n_experts=6)
    args = _step_args(dec, slots=24)
    text = dec.step.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert dec.expert_kernel == grouped_matmul.NAME
    assert sorted(calls) == [False, True]
    assert text.count("tpu_custom_call") == 2       # one function each
    assert len(_mosaic_modules(text)) == 2
    assert "ragged_dot" not in text
    routing = dec.step_routing.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(calls) == [False, True]           # nothing new
    assert _mosaic_modules(routing) == _mosaic_modules(text)


@pytest.mark.parametrize("n_experts,choice,calls", [
    (8, "pallas_router_choice", 3),
    # six experts are no multiple of a sublane tile
    (6, "passes:sublane_misaligned", 2)])
def test_a_step_for_a_tpu_routes_without_a_sort(n_experts, choice, calls):
    """What says the mechanism engages: `decoder.router_choice` names
    the Pallas call (one function more in the lowered step, however
    many layers call it) or the passes and why, and the step's text
    holds neither a `top_k` nor a sort either way."""
    dec = _routing_decoder(3, d_model=256, d_inner=128, n_experts=n_experts)
    assert dec.router_choice is None        # chosen when a step is traced
    text = dec.step.trace(*_step_args(dec, slots=16)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert dec.router_choice == choice
    assert text.count("tpu_custom_call") == calls
    assert "top_k" not in text and "stablehlo.sort" not in text


@pytest.mark.parametrize("n_layers", [2, 8])
def test_building_a_decoder_compiles_and_runs_nothing(n_layers):
    """What the kernel needs (group offsets, work items) is computed
    inside the step: no eager `jax.numpy` at the build, whose programs
    compile in under a second and so are kept by no compile cache."""
    before = xla_compile_counts()
    dec = _routing_decoder(n_layers)
    assert xla_compile_counts() == before
    assert dec.expert_kernel is None        # chosen when a step is traced
