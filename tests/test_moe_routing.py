"""The expert layer routes without a sort, and nothing else about it
changed: `lm_block.route` chooses by maximum passes (`_largest`) and
`lm_block.moe_ffn` orders a tick's assignments by counting
(`_by_expert`).

Contract: over the ten serving cells' router descriptions, at their
real (E, k, `n_group`, `topk_group`, `group_score`, bias,
`zero_experts`) and cut to toy widths, the chosen experts, their ORDER
and their weights are bit for bit what the form with `jax.lax.top_k`
gives (`route_top_k` below: the lines `route` held before), on random
scores, on rows of equal scores and on scores tied at the k-th place;
`kernels.router_choice`'s Pallas call, run in the interpreter, the same
through `route(..., choice=)`, and refused off a TPU and at a width
that is no multiple of a sublane tile; `_largest` is `top_k` on rows
that hold `-inf`, as `route`'s `left`
does outside the kept groups, down to rows with fewer than k finite
scores; `_by_expert`'s places are the stable `argsort`'s and its sizes
the scatter-add's, with absent experts and with no row held at all.
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import router_choice
from paddle_tpu.models import lm_block

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the cells whose block has experts (BENCHMARK.json, `workloads`)
CELLS = ("olmoe-1b-7b-serve-chat32", "mellum2-12b-a2.5b-serve-agent96",
         "granite-4.0-h-small-serve-chat64",
         "k-exaone-236b-a23b-serve-chat64", "deepseek-v2-serve-agent64",
         "longcat-flash-serve-agent64", "glm-5.2-serve-docqa64",
         "lfm2-24b-a2b-serve-agent128", "solar-open2-250b-serve-docqa64",
         "ling-3.0-flash-serve-agent128")


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def cell_router(workload):
    """(the block description, the rows of a tick, the model's width) of
    a serving cell, read from the cell's own files as its job reads
    them (`perf/jobs/serve_lm_closed.block_of`)."""
    w = next(w for w in _load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = _load("perf", "configs", w["config"] + ".json")
    t = _load("perf", "traffic", w["traffic"] + ".json")
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    return spec, int(t["slots"]), int(m["hidden_size"])


@dataclasses.dataclass(frozen=True)
class ToyRouter:
    """What `route` reads of a block description, at a width of six
    experts a group."""
    n_experts: int
    zero_experts: int
    experts_per_token: int
    router: str
    n_group: int
    topk_group: int
    group_score: str
    norm_topk_prob: bool
    norm_topk_eps: float
    routed_scaling_factor: float

    @classmethod
    def of(cls, spec):
        zero = 6 * spec.n_group if spec.zero_experts else 0
        fields = {f.name: getattr(spec, f.name)
                  for f in dataclasses.fields(cls)}
        return cls(**dict(fields, n_experts=12 * spec.n_group - zero,
                          zero_experts=zero,
                          experts_per_token=min(spec.experts_per_token, 5)))


def route_top_k(spec, m, w_router, b_router=None):
    """`lm_block.route` as it chose until PR 63: every choice a
    `jax.lax.top_k`.  The reference the passes are held to."""
    logits = jnp.dot(m, w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if spec.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if spec.n_group > 1:
        top2 = spec.group_score == "top2_sum"
        by = probs + b_router.astype(jnp.float32) if top2 else probs
        grouped = by.reshape(probs.shape[:-1] + (spec.n_group, -1))
        _, kept = jax.lax.top_k(
            jax.lax.top_k(grouped, 2)[0].sum(-1) if top2
            else grouped.max(-1), spec.topk_group)
        keep = (kept[..., None] == jnp.arange(spec.n_group)).any(-2)
        left = jnp.where(keep[..., None], grouped,
                         -jnp.inf if top2 else 0.0).reshape(probs.shape)
        top_w, top_e = jax.lax.top_k(left, spec.experts_per_token)
        if top2:
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    elif b_router is None:
        top_w, top_e = jax.lax.top_k(probs, spec.experts_per_token)
    else:
        _, top_e = jax.lax.top_k(probs + b_router.astype(jnp.float32),
                                 spec.experts_per_token)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    if spec.norm_topk_prob:
        total = top_w.sum(-1, keepdims=True)
        top_w = top_w / (total + spec.norm_topk_eps
                         if spec.norm_topk_eps else total)
    if spec.routed_scaling_factor != 1.0:
        top_w = top_w * spec.routed_scaling_factor
    return top_w, top_e


def router_inputs(spec, bias: bool, scores: str, rows: int, seed: int):
    """(m, w_router, b_router) whose scores are of the kind `scores`:
    `random`; `equal` (every token a zero row: all its experts score
    alike); `tied` (one input column, and a router column and its bias
    one of two values: half of the experts share the best score, so
    the k-th place is tied in every row)."""
    rng = np.random.RandomState(seed)
    width = spec.n_experts + spec.zero_experts
    if scores == "tied":
        m = rng.uniform(0.5, 2.0, (rows, 1))
        w = (rng.permutation(width) < (width + 1) // 2)[None, :]
        b = 0.25 * w[0]
    else:
        m = rng.randn(rows, 16) * (scores != "equal")
        w = rng.randn(16, width)
        b = 0.1 * rng.randn(width) * (scores != "equal")
    f32 = jnp.float32
    return (jnp.asarray(m, f32), jnp.asarray(w, f32),
            jnp.asarray(b, f32) if bias else None)


@pytest.mark.parametrize("scores", ["random", "equal", "tied"])
@pytest.mark.parametrize("width", ["real", "toy"])
@pytest.mark.parametrize("cell", CELLS)
def test_route_chooses_what_top_k_chose(cell, width, scores):
    """Experts, order and weights bit for bit the `top_k` form's."""
    spec, rows, _ = cell_router(cell)
    bias = spec.router_bias
    if width == "toy":
        spec, rows = ToyRouter.of(spec), 7
    args = router_inputs(spec, bias, scores, min(rows, 16),
                         seed=len(cell) + len(scores))
    got_w, got_e = jax.jit(functools.partial(lm_block.route, spec))(*args)
    want_w, want_e = jax.jit(functools.partial(route_top_k, spec))(*args)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    assert got_e.dtype == want_e.dtype and got_w.dtype == want_w.dtype
    if scores == "tied":
        # the case is what it says: more than k experts share the best
        assert np.asarray(args[1]).sum() > spec.experts_per_token


def choice_kernel(spec, rows, **where):
    return router_choice.select_router_choice(
        rows=rows, width=spec.n_experts + spec.zero_experts,
        k=spec.experts_per_token, n_group=spec.n_group,
        topk_group=spec.topk_group, group_score=spec.group_score, **where)


@pytest.mark.parametrize("scores", ["random", "equal", "tied"])
@pytest.mark.parametrize("cell,width", [(c, "real") for c in CELLS] + [
    ("deepseek-v2-serve-agent64", "toy"),
    ("ling-3.0-flash-serve-agent128", "toy")])
def test_choice_kernel_chooses_what_top_k_chose(cell, width, scores):
    """The Pallas call (the group limit and the k passes in one launch,
    here in the interpreter) through `route`: experts, order and
    weights bit for bit the `top_k` form's."""
    spec, rows, _ = cell_router(cell)
    bias = spec.router_bias
    if width == "toy":
        spec = ToyRouter.of(spec)
    rows = min(rows, 9)
    kernel, refused = choice_kernel(spec, rows, platform="cpu",
                                     interpret=True)
    assert refused is None and kernel.name == router_choice.NAME
    args = router_inputs(spec, bias, scores, rows,
                         seed=len(cell) + len(scores))
    got_w, got_e = jax.jit(functools.partial(
        lm_block.route, spec, choice=kernel))(*args)
    want_w, want_e = jax.jit(functools.partial(route_top_k, spec))(*args)
    np.testing.assert_array_equal(np.asarray(got_e), np.asarray(want_e))
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    assert got_e.dtype == want_e.dtype and got_w.dtype == want_w.dtype


@pytest.mark.parametrize("cell,rows,toy,platform,want", [
    ("ling-3.0-flash-serve-agent128", 128, False, "tpu", None),
    ("ling-3.0-flash-serve-agent128", 128, False, "cpu", "not_tpu"),
    # twelve experts: no multiple of a sublane tile
    ("olmoe-1b-7b-serve-chat32", 32, True, "tpu", "sublane_misaligned"),
    # scores that would not sit in VMEM beside their masks
    ("longcat-flash-serve-agent64", 4096, False, "tpu",
     "scores_exceed_vmem")])
def test_choice_kernel_selection(cell, rows, toy, platform, want):
    """The kernel where the shapes and the platform allow, else None and
    the reason `_largest`'s passes run (`decoder.router_choice`)."""
    spec = cell_router(cell)[0]
    kernel, refused = choice_kernel(ToyRouter.of(spec) if toy else spec,
                                     rows, platform=platform)
    assert refused == want and (kernel is None) == (want is not None)


# (leading shape, row, k): the ten cells' choices over their experts,
# Ling's two best of a group and its four groups of eight, a toy
LARGEST = [((32,), 64, 8), ((64,), 72, 10), ((64,), 128, 8),
           ((64,), 160, 6), ((64,), 768, 12), ((64,), 256, 8),
           ((128,), 64, 4), ((64,), 320, 8), ((128,), 512, 8),
           ((128, 8), 64, 2), ((128,), 8, 4), ((3, 2), 7, 7)]


@pytest.mark.parametrize("kind", ["random", "equal", "tied", "left",
                                  "few_finite"])
@pytest.mark.parametrize("lead,row,k", LARGEST)
def test_largest_is_top_k(lead, row, k, kind):
    """Values and indices `top_k`'s, a tie to the lower index, on rows
    that hold `-inf`: `left` (half of every row, as outside the kept
    groups) and `few_finite` (all but k - 1 scores: a position is
    taken once even where what is left is `-inf` too)."""
    rng = np.random.RandomState(row + k)
    x = rng.randn(*lead, row).astype(np.float32)
    if kind == "equal":
        x[:] = 0.25
    elif kind == "tied":
        x = rng.randint(0, 3, x.shape).astype(np.float32)
    elif kind == "left":
        x[..., rng.permutation(row)[: row // 2]] = -np.inf
    elif kind == "few_finite":
        x[..., rng.permutation(row)[k - 1:]] = -np.inf
    got = jax.jit(functools.partial(lm_block._largest, k=k))(x)
    want = jax.lax.top_k(x, k)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# (assignments, held experts): a toy, OLMoE's, Granite's, LongCat's and
# Ling's ticks
ORDERS = [(21, 5), (256, 64), (640, 36), (768, 16), (1024, 128)]


@pytest.mark.parametrize("kind", ["all_held", "some_absent", "none_held",
                                  "one_expert"])
@pytest.mark.parametrize("n,e_n", ORDERS)
def test_by_expert_is_the_stable_argsort(n, e_n, kind):
    """Every assignment's place, the order and the group sizes are what
    `argsort(stable=True)`, its inverting scatter and the scatter-add
    gave; an absent expert's assignments (`e_n`) stand past the last
    group in the order they came and are counted nowhere."""
    rng = np.random.RandomState(n + e_n)
    flat_e = rng.randint(0, e_n, n)
    if kind == "some_absent":
        flat_e[rng.rand(n) < 0.6] = e_n
    elif kind == "none_held":
        flat_e[:] = e_n
    elif kind == "one_expert":
        flat_e[:] = e_n - 1
    flat_e = jnp.asarray(flat_e, jnp.int32)
    order, place, sizes = jax.jit(
        functools.partial(lm_block._by_expert, e_n=e_n))(flat_e)
    want = jnp.argsort(flat_e, stable=True)
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(place),
        np.asarray(jnp.zeros_like(want).at[want].set(jnp.arange(n))))
    np.testing.assert_array_equal(
        np.asarray(sizes),
        np.asarray(jnp.zeros(e_n, jnp.int32).at[flat_e].add(
            1, mode="drop")))
    assert order.dtype == place.dtype == sizes.dtype == jnp.int32
