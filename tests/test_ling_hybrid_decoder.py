"""The Ling-3.0-flash block (gated delta-rule layers whose matrix state
and tail belong to a lane BESIDE one latent attention layer on the paged
table, in one period K K K K K M; full-rank bounded gates; a direct
latent query; a sigmoid scalar a head on the latent layer; dense layers
before sparse ones; a group-limited sigmoid router with a choice bias
whose groups score by the sum of their two best; a clamp a layer on the
SwiGLU inputs) through `build_lm_paged_decoder`, `PagedKVCache` and
`GenerationServer` with the prefix cache ON (a hit restores a snapshot
of the lane's state AND finds the document's latent rows in the shared
blocks), against the plain reference `perf/reference/ling_hybrid.py`, at
toy widths on the CPU with seeded random float32 weights.

The toy is the configuration file's `rehearse` overlay: 32 experts of 16
in 8 groups (4 kept, 5 a token), 8 held, 4 heads, delta heads of 8, a
latent of 32 with 8 rotated columns, 4 taps.  What is compared is
LOGITS, never tokens, except where a server's streams are compared with
themselves.
"""
import hashlib
import importlib.util
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models import lm_block
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import tracing
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.kv_cache import PagedKVCache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ling-3.0-flash-serve-agent128"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BS, NB = 4, 24                                   # 96 positions
# float32 weights, pool, state and tail: the same float32 sums in
# another order (a state a position against a scan over the sequence,
# the absorbed latent against the expanded one, grouped matmuls against
# dense masked products)
TOL_FP32 = 1e-4
# bf16 pool: the latent row rounded to 8 bits of mantissa on its way
# into the table, 1 layer of 6 attends (the last: no state stands
# behind it)
TOL_BF16_POOL = 2e-2


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _load(name, *parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_ling_hybrid", "perf", "reference", "ling_hybrid.py")
FILE = _json("perf", "configs", "ling-3.0-flash-1chip.json")
CONFIG = dict(FILE, **FILE["rehearse"])
V, D, H, L = (CONFIG[k] for k in ("vocab_size", "hidden_size",
                                  "num_attention_heads",
                                  "num_hidden_layers"))
E, HELD, K = (CONFIG[k] for k in ("num_routed_experts", "num_experts",
                                  "num_experts_per_tok"))
DK, TAPS = CONFIG["head_dim"], CONFIG["short_conv_kernel_size"]
N_DELTA = CONFIG["layer_types"].count("delta_rule")
ROW = CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"]
STATE, TAIL = (H, DK, DK), (TAPS - 1, 3 * H * DK)


def _block(m=CONFIG, **over):
    """The description as the benchmark's job builds it: the file's
    `block`, literal fields and the source's own keys."""
    b = m["block"]
    return lm_block.BlockSpec(**dict(dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}),
        **over)), m[b["d_inner"]]


def _decoder(kv_dtype="fp32", m=CONFIG, nb=NB, **over):
    spec, d_inner = _block(m, **over)
    startup, dec = build_lm_paged_decoder(
        m["vocab_size"], BS, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=d_inner, kv_dtype=kv_dtype, platform="cpu", block=spec)
    assert startup is None
    return dec


def _weights(dec, seed=0, floor=-5.0):
    """Seeded float32 weights of a size at which every part matters:
    matrices at sigma 0.1 (0.3 the router, whose product decides a
    choice), a choice bias at sigma 0.1 (it moves a fifth of the
    choices), the taps uniform in +-1/2, A_log and the decay's bias as
    the benchmark's job draws them (a decay a step in 0.9 to 0.999 at a
    zero projection)."""
    r = np.random.RandomState(seed)
    g = {}
    for n, shape in sorted(dec.state_shapes.items()):
        if n.endswith(("delta_conv.w_0", ".conv.w_0", "ssm_conv.w_0")):
            w = r.uniform(-0.5, 0.5, shape)
        elif n.endswith(("delta_a_log.w_0", "ssm_a_log.w_0")):
            w = np.log(r.uniform(1.0, 4.0, shape))
        elif n.endswith("delta_dt.b_0") and floor:
            w = np.zeros(shape)          # solved below, from A_log
        elif n.endswith(("delta_dt.b_0", "ssm_dt.b_0")):
            dt = r.uniform(1e-3, 1e-1, shape)
            w = dt + np.log(-np.expm1(-dt))
        else:
            w = r.normal(0, 0.3 if "router.w" in n else 0.1, shape)
            if ".scale_" in n or n.endswith("ssm_d.w_0"):
                w = 1.0 + w
        g[n] = jnp.asarray(w, jnp.float32)
    for n in [n for n in g if n.endswith("delta_dt.b_0") and floor]:
        a = np.exp(np.asarray(g[n.replace("delta_dt.b_0",
                                          "delta_a_log.w_0")]))
        share = np.log(r.uniform(
            0.9, 0.999, (len(a), g[n].shape[0] // len(a)))) / floor
        g[n] = jnp.asarray((np.log(share / (1 - share)) / a[:, None])
                           .reshape(-1), jnp.float32)
    return g


def _drive(dec, g, seqs, slots=None, lanes=None, starts=None,
           routing=False, pools=None):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them, lane i
    starting at tick `starts[i]` (lanes out of step); returns each
    sequence's [len, V] logits, then (with `routing`) lane `lanes[0]`'s
    routing stacked over its positions with its states and tails after
    the last one under "state" and "tails" and the latent rows its
    blocks hold under "latent", then (with `pools`, which continues on
    pools an earlier drive left) the pools."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    starts = starts or [0] * len(seqs)
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(1 + slots * NB, lanes=slots)
    # the table's one plane is the latent layer's, and nothing stands
    # where a V pool would; a state a delta layer rides beside it and
    # its tail beside that nothing
    assert pool_k[0].shape[0] == L - N_DELTA and pool_v[0] == ()
    assert [s.shape for s in pool_k[1]] == [(slots,) + STATE] * N_DELTA
    assert [t.shape for t in pool_v[1]] == [(slots,) + TAIL] * N_DELTA
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, routed = [[] for _ in seqs], []
    for tick in range(max(t + len(s) for s, t in zip(seqs, starts))):
        toks, pos = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane, t0 in zip(seqs, lanes, starts):
            if t0 <= tick < t0 + len(s):
                toks[lane], pos[lane], act[lane] = s[tick - t0], tick - t0, \
                    True
        args = (g, pool_k, pool_v, tables, pos, toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        if act[lanes[0]]:
            routed.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                           for k, v in r.items()})
        _, pool_k, pool_v, *counts = jax.block_until_ready(dec.step(*args))
        assert len(counts) == len(dec.step_counters) == 3
        for i, (s, lane, t0) in enumerate(zip(seqs, lanes, starts)):
            if t0 <= tick < t0 + len(s):
                out[i].append(lg[lane])
    res = ([np.stack(o) for o in out],)
    if routing:
        n = len(seqs[0])
        rows = np.asarray(pool_k[0], np.float32)[:, tables[lanes[0]]]
        res += ({"state": np.stack([np.asarray(s)[lanes[0]]
                                    for s in pool_k[1]]),
                 "tails": np.stack([np.asarray(t)[lanes[0]]
                                    for t in pool_v[1]]),
                 "latent": rows.reshape(rows.shape[0], -1,
                                        rows.shape[-1])[:, :n, :ROW],
                 **{k: np.concatenate([r[k] for r in routed], 1)
                    for k in routed[0]}},)
    if pools is not None:
        res += ((pool_k, pool_v),)
    return res[0] if len(res) == 1 else res


SEQ = list(np.random.RandomState(7).randint(0, V, 57))   # over 14 blocks
IDS = np.asarray(SEQ, np.int32)


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", TOL_BF16_POOL)])
def test_prompt_then_decode_equals_the_references_full_forward(kv_dtype,
                                                               tol):
    """Every position of a sequence through the paged step (the prompt
    one position a tick, then decode: the step does not tell them
    apart), the delta layers' states and tails carried a lane, the
    latent layer ABSORBED over the table's rows, against the reference's
    ONE forward pass: a loop over positions on a state from zeros, the
    convolutions over the whole sequence, the latent EXPANDED to keys
    and values a head."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert out["finite"] and out["logits_rel_err"] <= tol, out
    assert out["logits_rms_err"] <= tol >= out["late_rms_err"], out
    assert out["router_rel_err"] <= 1e-4, out
    # the five delta layers stand BEFORE the latent layer: a bf16 pool
    # moves neither their states nor their tails
    assert out["state_rms_err"] <= TOL_FP32 >= out["tail_rms_err"], out
    assert out["latent_rms_err"] <= tol, out
    if kv_dtype == "fp32":
        want = np.asarray(REF.logits(g, CONFIG, IDS))
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
        assert out["routing_agree"] == 1.0 == out["argmax_agree"]


def test_a_reused_lane_reads_as_a_fresh_one_and_an_idle_lane_keeps_still():
    """A sequence run in a lane whose states, tails and table blocks
    still hold ANOTHER sequence's gives bit for bit what it gives on
    zero pools: position 0 resets the lane from the cursor alone, and
    the cursor's mask hides a predecessor's latent rows.  A lane that is
    not active keeps state and tails to the bit while its neighbour
    runs."""
    dec = _decoder()
    g = _weights(dec)
    r = np.random.RandomState(5)
    first, second = (list(r.randint(0, V, n)) for n in (27, 13))

    def zero():
        return dec.init_pool(1 + 2 * NB, lanes=2)

    (fresh,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=zero())
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    held = [np.asarray(x) for x in used[0][1] + used[1][1]]
    assert all(x[1].any() and not x[0].any() for x in held)
    assert np.asarray(used[0][0]).any()
    (again,), _ = _drive(dec, g, [second], slots=2, lanes=[1], pools=used)
    assert np.array_equal(fresh, again) and np.isfinite(again).all()
    # lane 1 idle while lane 0 runs: what lane 1 holds does not move
    _, used = _drive(dec, g, [first], slots=2, lanes=[1], pools=zero())
    before = [np.asarray(x)[1].copy() for x in used[0][1] + used[1][1]]
    _, moved = _drive(dec, g, [first[:9]], slots=2, lanes=[0], pools=used)
    for a, x in zip(before, moved[0][1] + moved[1][1]):
        assert np.array_equal(a, np.asarray(x)[1])
        assert np.asarray(x)[0].any()


@pytest.mark.parametrize("zero_router", [False, True])
def test_a_sequence_among_others_is_bit_identical_to_itself_alone(
        zero_router):
    """Continuous batching: the same sequence beside two others out of
    step with it, in another lane and other table blocks than alone:
    bit for bit the same logits (no capacity in the expert layer, no
    lane in the recurrence), even with a zero router (every token on
    the same experts: the fullest groups, every tie to the lower group
    and expert)."""
    dec = _decoder()
    g = _weights(dec, seed=3)
    if zero_router:
        g = {n: jnp.zeros_like(w) if "router" in n else w
             for n, w in g.items()}
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,) = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    together = _drive(dec, g, [others[0], SEQ, others[1]], slots=4,
                      lanes=[3, 1, 0], starts=[0, 2, 5])
    assert np.array_equal(together[1], alone)


# the toy's limits, between the decoder's readings and the least any
# fault or `below` reads
LIMITS = {"logits_rms_err": TOL_FP32, "late_rms_err": TOL_FP32,
          "state_rms_err": TOL_FP32, "tail_rms_err": TOL_FP32,
          "latent_rms_err": TOL_FP32, "router_rel_err": TOL_FP32}


def _refused(out):
    return sorted(k for k, hi in LIMITS.items() if not out[k] <= hi)


def test_the_comparison_passes_the_decoder_by_every_limit():
    dec = _decoder()
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ], routing=True)
    out = REF.compare(g, CONFIG, IDS, got, routing)
    assert _refused(out) == [], out
    assert set(FILE["compare"]["limits"]) <= set(out)
    assert len(REF.FAULTS) == 21 == len(set(REF.FAULTS))
    assert all(f in FILE["assumed"]["faults"] for f in REF.FAULTS)
    served = REF.served(g, CONFIG, [(IDS, 40)], length=64)
    assert set(FILE["compare"]["served_limits"]) <= set(served)
    assert served["tokens"] == len(IDS) - 40
    # every `assumed` entry that names a fault names one the reference has
    named = {w.strip("`,.;()") for text in FILE["assumed"].values()
             for w in text.split() if w.startswith("`") and "_" in w}
    assert set(REF.FAULTS) <= named


# the limit that names what each wrong model broke
REFUSED_BY = {
    "below": "state_rms_err", "softplus_decay": "state_rms_err",
    "bound_minus_1": "state_rms_err", "beta_times_2": "state_rms_err",
    "decay_per_head": "state_rms_err", "no_l2norm": "state_rms_err",
    "no_output_gate": "logits_rms_err", "no_head_gate": "logits_rms_err",
    "gate_elementwise": "logits_rms_err", "rope_on_delta": "state_rms_err",
    "no_rope": "latent_rms_err", "scale_nope": "logits_rms_err",
    "no_latent_norm": "latent_rms_err", "group_max": "router_rel_err",
    "no_group_limit": "router_rel_err",
    "bias_in_weights": "router_rel_err", "no_scaling": "router_rel_err",
    "no_renorm": "router_rel_err", "dense_as_sparse": "logits_rms_err",
    "tail_shifted": "logits_rms_err", "lane_not_reset": "state_rms_err",
    "latent_first": "logits_rms_err"}


@pytest.mark.parametrize("what", ("below",) + REF.FAULTS)
def test_the_comparison_refuses_lower_precision_and_every_fault(what):
    """Each of the twenty-one wrong models and the right one in
    bfloat16, as if it were the system, is refused by at least one
    limit, and by the limit that names what it broke."""
    g = _weights(_decoder())
    out = (REF.below(g, CONFIG, IDS) if what == "below"
           else REF.faults(g, CONFIG, IDS, which=(what,))[what])
    refused = _refused(out)
    assert refused, (what, out)
    assert REFUSED_BY[what] in refused, (what, REFUSED_BY[what], out)
    if what in ("no_head_gate", "gate_elementwise", "scale_nope"):
        # the last layer's mixer: no state stands behind it and the
        # rows it caches are right
        assert out["state_rms_err"] <= TOL_FP32 >= out["latent_rms_err"]


def _route_by_loop(s, b, k, n_group, topk_group, renorm, scaling):
    """`lm_block.route` under sigmoid + bias + "top2_sum", a token at a
    time in numpy: ties to the lower group and the lower expert."""
    weights, experts = [], []
    for row in s:
        c = row + b
        size = len(c) // n_group
        group = [sum(sorted(c[j * size:(j + 1) * size])[-2:])
                 for j in range(n_group)]
        kept = sorted(range(n_group), key=lambda j: (-group[j], j))[
            :topk_group]
        pool = [e for j in kept for e in range(j * size, (j + 1) * size)]
        take = sorted(pool, key=lambda e: (-c[e], e))[:k]
        w = np.asarray([row[e] for e in take])
        weights.append(w / w.sum() * scaling if renorm else w * scaling)
        experts.append(take)
    return np.asarray(weights), np.asarray(experts)


@pytest.mark.parametrize("case", ["seeded", "tied_groups", "tied_experts",
                                  "large_bias"])
def test_route_under_a_group_limit_equals_a_loop_in_numpy(case):
    """`route` with `router: "sigmoid"`, `router_bias` and `group_score:
    "top2_sum"`: the groups' scores are the sums of their two largest
    scores + bias, the choice is among the kept groups' experts by
    score + bias, the weights are the scores alone; a tie goes to the
    lower group and to the lower expert."""
    spec, _ = _block()
    r = np.random.RandomState(4)
    m = jnp.asarray(r.normal(0, 1, (23, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3, (D, E)), jnp.float32)
    b = r.normal(0, 0.1, E)
    if case == "tied_groups":
        # every expert scores alike: groups 0 to 3, experts 0 to 4
        w, b = jnp.zeros_like(w), np.zeros(E)
    elif case == "tied_experts":
        # groups told apart by the bias alone, their experts tied
        w, b = jnp.zeros_like(w), np.repeat(r.permutation(8) / 8.0, E // 8)
    elif case == "large_bias":
        b = r.normal(0, 2.0, E)           # the choice is the bias's
    top_w, top_e = lm_block.route(spec, m, w, jnp.asarray(b, jnp.float32))
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(m @ w), np.float64)
    want_w, want_e = _route_by_loop(
        s, b, K, CONFIG["n_group"], CONFIG["topk_group"], True,
        CONFIG["routed_scaling_factor"])
    assert np.array_equal(np.asarray(top_e), want_e)
    assert np.abs(np.asarray(top_w) - want_w).max() <= 1e-6
    if case == "tied_groups":
        assert np.array_equal(np.asarray(top_e)[0], np.arange(K))
    # the reference's own router agrees
    own = REF.choose(jnp.asarray(s, jnp.float32),
                     jnp.asarray(b, jnp.float32), top_k=K,
                     n_group=CONFIG["n_group"],
                     topk_group=CONFIG["topk_group"])
    assert np.array_equal(np.asarray(own), want_e)
    # the limit binds: without it other experts are chosen somewhere
    if case in ("seeded", "large_bias"):
        free = np.asarray(REF.choose(
            jnp.asarray(s, jnp.float32), jnp.asarray(b, jnp.float32),
            top_k=K, n_group=8, topk_group=4, fault="no_group_limit"))
        assert not np.array_equal(free, want_e)


@pytest.mark.parametrize("bias_sigma", [0.0, 0.1, 2.0])
def test_the_jobs_fit_counts_the_routers_own_choice(bias_sigma):
    """`perf/jobs/serve_lm_hybrid.py` fits the choice bias on the loads
    of `chosen`, argmax passes in place of `top_k`: the mask it counts
    is the set `lm_block.route` picks, ties to the lower index."""
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        job = _load("job_serve_lm_hybrid", "perf", "jobs",
                    "serve_lm_hybrid.py")
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))
    spec, _ = _block()
    r = np.random.RandomState(6)
    m = jnp.asarray(r.normal(0, 1, (37, D)), jnp.float32)
    w = jnp.asarray(r.normal(0, 0.3 if bias_sigma else 0.0, (D, E)),
                    jnp.float32)
    b = jnp.asarray(r.normal(0, bias_sigma, E), jnp.float32)
    _, top_e = lm_block.route(spec, m, w, b)
    want = np.zeros((37, E), bool)
    np.put_along_axis(want, np.asarray(top_e), True, -1)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(m @ w)
    got = np.asarray(job.chosen(scores, b, K, CONFIG["n_group"],
                                CONFIG["topk_group"]))
    assert np.array_equal(got, want) and got.sum(-1).tolist() == [K] * 37
    mask = np.asarray(job.largest(jnp.asarray([[1., 3., 3., 2.]]), 2))
    assert mask.tolist() == [[False, True, True, False]]
    mask = np.asarray(job.largest(jnp.asarray([[2., 2., 2., 2.]]), 2))
    assert mask.tolist() == [[True, True, False, False]]


@pytest.mark.parametrize("which", ["experts", "shared", "both"])
def test_a_clamp_on_one_layer_equals_the_references(which):
    """A non-zero SwiGLU limit on ONE sparse layer (the cut's six
    entries are 0): the experts' gate input min(., L), their up input
    clip(., -L, L), the shared expert's the same under its own list;
    against the reference, which reads the configuration's two lists.
    The limit binds: the unclamped logits differ."""
    limits = {"expert_swiglu_limit_list": [0, 0, 0, 0.05, 0, 0],
              "share_expert_swiglu_limit_list": [0, 0, 0, 0, 0.04, 0]}
    if which != "both":
        limits[{"experts": "share_expert_swiglu_limit_list",
                "shared": "expert_swiglu_limit_list"}[which]] = [0] * 6
    m = dict(CONFIG, **limits)
    dec = _decoder(m=m)
    g = _weights(dec)
    (got,), routing = _drive(dec, g, [SEQ[:21]], routing=True)
    ids = IDS[:21]
    out = REF.compare(g, m, ids, got, routing)
    assert _refused(out) == [], out
    free = REF.compare(g, CONFIG, ids, got, routing)
    assert free["logits_rms_err"] > 10 * TOL_FP32, free
    assert dec.expert_kernel == "xla:not_tpu"
    assert dec.router_choice == "passes:not_tpu"


def test_a_clamped_layer_takes_the_ragged_dots_on_a_tpu():
    """The grouped matmul's gated product is inside its call: a layer
    with a limit on its routed experts keeps the `ragged_dot`s and says
    so (`decoder.expert_kernel`); with every limit 0 the lowered step
    is the step of a description without the lists."""
    spec, d_inner = _block()
    sds = jax.ShapeDtypeStruct

    def lowered(spec):
        _, dec = build_lm_paged_decoder(
            V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=d_inner,
            platform="cpu", block=spec)
        g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
        pools = jax.eval_shape(lambda: dec.init_pool(9, lanes=2))
        i32 = sds((2,), np.int32)
        return dec, dec.step.lower(
            g, *pools, sds((2, NB), np.int32), i32, i32,
            sds((2,), np.uint32), sds((2,), np.float32),
            sds((2,), np.bool_)).as_text()

    bare = lm_block.BlockSpec(**dict(spec.__dict__, expert_swiglu_limits=(),
                                     shared_swiglu_limits=()))
    assert lowered(spec)[1] == lowered(bare)[1]
    clamped = lm_block.BlockSpec(**dict(
        spec.__dict__, expert_swiglu_limits=(0, 0, 0, 0, 0, 4.0)))
    dec, text = lowered(clamped)
    assert text != lowered(bare)[1] and "4.000000e+00" in text
    assert spec.swiglu_limits_of(5) == (0.0, 0.0)
    assert clamped.swiglu_limits_of(5) == (4.0, 0.0)
    assert clamped.swiglu_limits_of(41) == (0.0, 7.0)   # the shared list
    assert clamped.swiglu_limits_of(42) == (0.0, 0.0)   # past both
    # the selection is the kernel module's; the builder overrules it for
    # a clamped layer alone
    from paddle_tpu.kernels import grouped_matmul
    seen = []
    real = grouped_matmul.select_grouped_matmul

    def spy(**kw):
        seen.append(kw)
        return real(**kw)

    grouped_matmul.select_grouped_matmul = spy
    try:
        dec, _ = lowered(clamped)
    finally:
        grouped_matmul.select_grouped_matmul = real
    assert len(seen) == 4 and dec.expert_kernel == "xla:swiglu_limit"


def test_the_four_shares_and_the_shared_expert_are_the_uncut_layer():
    """The guide's test of a share: the parts that the four chips of a
    stage compute of ONE expert layer, each from its own quarter of the
    experts (two of the router's eight groups: experts 0 to 7, 8 to 15,
    ...), with the shared expert (which every chip computes alike)
    counted once, add up to what the uncut reference gives for the whole
    layer."""
    r = np.random.RandomState(9)
    x = jnp.asarray(r.normal(0, 1, (11, D)), jnp.float32)
    f = CONFIG["moe_intermediate_size"]
    p = {"norm": 1 + r.normal(0, 0.1, D), "router": r.normal(0, 0.3, (D, E)),
         "bias": r.normal(0, 0.1, E),
         "gate": r.normal(0, 0.1, (E, D, f)),
         "up": r.normal(0, 0.1, (E, D, f)),
         "down": r.normal(0, 0.1, (E, f, D)),
         "shared_gate": r.normal(0, 0.1, (D, f)),
         "shared_up": r.normal(0, 0.1, (D, f)),
         "shared_down": r.normal(0, 0.1, (f, D))}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    own = jnp.full((11, K), -1, jnp.int32)
    scaling = jnp.asarray(CONFIG["routed_scaling_factor"], jnp.float32)
    kw = dict(top_k=K, n_group=CONFIG["n_group"],
              topk_group=CONFIG["topk_group"], eps=1e-6, renorm=True,
              limit=0.0, shared_limit=0.0)
    with jax.default_matmul_precision("highest"):
        whole, routing = REF._moe(x, p, own, scaling, first=0, **kw)
        nothing = dict(p, **{k: jnp.zeros_like(p[k]) for k in (
            "shared_gate", "shared_up", "shared_down")})
        shared = REF._moe(x, dict(p, **{k: p[k][:0] for k in (
            "gate", "up", "down")}), own, scaling, first=0, **kw)[0] - x
        parts = []
        for first in range(0, E, HELD):
            share = dict(nothing, **{k: p[k][first:first + HELD]
                                     for k in ("gate", "up", "down")})
            got, r_ = REF._moe(x, share, own, scaling, first=first, **kw)
            assert np.array_equal(r_["experts"], routing["experts"])
            parts.append(got - x)
    assert len(parts) == 4
    total = x + sum(parts) + shared
    assert np.abs(total - whole).max() <= 1e-5 * np.abs(whole).max()
    assert all(np.abs(part).max() > 0 for part in parts)
    # a token reaches at most the chips of its four kept groups
    groups = np.asarray(routing["experts"]) // (E // CONFIG["n_group"])
    assert max(len(set(row)) for row in groups) <= CONFIG["topk_group"]
    # and the served layer is such a share: `lm_block.moe_ffn` over the
    # experts `first` onward gives the reference's part
    spec, _ = _block()
    m = REF._rms(x, p["norm"], 1e-6)
    first = 2 * HELD
    mine = lm_block.moe_ffn(
        lm_block.BlockSpec(**dict(spec.__dict__, experts_first=first)), m,
        p["router"], *(p[k][first:first + HELD]
                       for k in ("gate", "up", "down")),
        b_router=p["bias"])[0]
    assert np.abs(mine - parts[2]).max() <= 1e-5 * np.abs(parts[2]).max()


# -- the prefix cache over a lane's state AND a latent table ---------------
def _serve(dec, g, prefix, asks, *, slots=3, snapshots=None, blocks=96):
    """`asks`: (prompt, new tokens) in order, each awaited before the
    next (so that a later one finds what an earlier one cached); ->
    (their sampled streams, the server's stats)."""
    srv = GenerationServer(dec, g, slots=slots, kv_blocks=blocks,
                           place=fluid.CPUPlace(), prefix_cache=prefix,
                           state_snapshots=snapshots)
    try:
        # sampled (the key is the request's seed and the position): a
        # greedy stream at these widths soon cycles
        out = [srv.submit(p, n, temperature=1.0, seed=50 + i).result(
            timeout=120) for i, (p, n) in enumerate(asks)]
        return out, srv.stats()
    finally:
        srv.close()


def test_a_hit_restores_a_snapshot_and_reads_the_shared_latent_blocks():
    """A document built through `submit(document, 1)`, then requests
    that are the document and a question: with the prefix cache on each
    starts from a RESTORED snapshot of the lane's five states and tails
    AND attends, on the latent layer, over the document's rows in the
    blocks the document's request wrote (shared, not copied); its
    stream is, token for token, that of the same request on a server
    without a cache, which ran every position (a sampled stream: one
    wrong logit anywhere changes it)."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(3)
    doc = list(r.randint(0, V, 6 * BS))
    asks = [(doc, 1)] + [(doc + list(r.randint(0, V, n)), 9)
                         for n in (5, 7, 2)]
    hit, stats = _serve(dec, g, True, asks)
    miss, plain = _serve(dec, g, False, asks)
    assert hit == miss and all(len(set(s)) > 4 for s in hit[1:])
    assert "state_snapshots_saved" not in plain
    assert stats["state_snapshots_restored"] == 3
    assert stats["state_snapshots_saved"] == 3
    assert stats["state_snapshot_bytes"] == 6 * dec.state_bytes_per_lane
    assert stats["prefix_blocks_cut"] == 0
    # the document's six blocks of latent rows, found three times
    assert stats["prefix_hits"] == 3 * 6
    # wrong rows in the shared blocks WOULD show: a hit whose document
    # differs in its last token gives another stream
    other = [(doc[:-1] + [(doc[-1] + 1) % V], 1)] + [
        (doc[:-1] + [(doc[-1] + 1) % V] + a[0][len(doc):], a[1])
        for a in asks[1:]]
    assert _serve(dec, g, True, other)[0][1:] != hit[1:]


def test_a_hit_is_cut_back_to_a_snapshot_though_the_table_holds_more():
    """A prompt that shares MORE full blocks with an earlier one than
    the earlier one's snapshot covers: the latent table could serve
    them all, the lanes' states cannot, so the blocks past the snapshot
    are cut, counted and run again; the stream is the miss's."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    r = np.random.RandomState(8)
    doc = list(r.randint(0, V, 4 * BS))
    longer = doc + list(r.randint(0, V, 2 * BS + 1))   # snapshot at 24
    asks = [(doc, 1), (longer, 3), (longer[:5 * BS + 2], 6),
            (longer + [3, 4], 6)]
    hit, stats = _serve(dec, g, True, asks)
    assert hit == _serve(dec, g, False, asks)[0]
    assert stats["prefix_blocks_cut"] == 1
    assert stats["state_snapshots_restored"] == 3
    assert stats["prefix_hits"] == 4 + 4 + 6


def test_spans_and_counts_of_a_tick_name_lanes_and_table_together():
    """`serving.decode_tick` carries the latent table's rows AND the
    delta layers' counts on ONE tick, and the step's three counters."""
    dec = _decoder()
    g = {n: np.asarray(w) for n, w in _weights(dec, 4).items()}
    doc = list(np.random.RandomState(2).randint(0, V, 4 * BS))
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        _serve(dec, g, True, [(doc, 1), (doc + [1, 2, 3, 4, 5], 4)])
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
    ticks = [s["attrs"] for s in spans
             if s["name"] == "serving.decode_tick"
             and "state_lanes" in s["attrs"]]
    per = dec.state_bytes_per_lane
    assert ticks and all(
        t["delta_layers"] == N_DELTA and t["latent_rows"] > 0
        and t["state_bytes"] == 2 * t["state_lanes"] * per
        and t["moe_layers"] == 4 and t["delta_kernel"] == 0
        for t in ticks)
    assert sum(t["state_resets"] for t in ticks) == 1    # the document
    read = [t for t in ticks if "moe_experts_hit" in t]
    assert read and all("moe_rows_held" in t and "moe_tokens_here" in t
                        for t in read)
    counts = dec.tick_counts(np.array([0, 7, 30]), 4)
    assert (counts["delta_layers"], counts["state_lanes"],
            counts["state_resets"], counts["latent_rows"]) == (
                N_DELTA, 3, 1, 1 + 8 + 31)
    assert counts["state_bytes"] == 6 * per
    assert "conv_layers" not in counts and "kv_planes" not in counts


def test_what_a_lane_a_block_and_a_snapshot_hold_and_what_is_refused():
    """A lane holds what the decoder says, a block one latent plane, a
    snapshot the lane's states and tails and nothing of the table; a
    draft model is refused for BOTH reasons, `step_window` and an int8
    pool by name."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    per = 4 * N_DELTA * (H * DK * DK + (TAPS - 1) * 3 * H * DK)
    assert (dec.state_layers, dec.state_bytes_per_lane) == (N_DELTA, per)
    assert dec.table_layers == 1 == dec.kv_planes
    assert dec.bytes_per_block == BS * 128 * 4      # one row, 128 lanes
    assert _decoder("bf16").bytes_per_block == BS * 128 * 2
    snaps = dec.init_snapshots(3)
    assert [len(part) for part in snaps] == [N_DELTA, N_DELTA]
    assert sum(x.nbytes for part in snaps for x in part) == 3 * per
    pools = dec.init_pool(5, lanes=2)
    pool_k, pool_v = dec.snapshot_restore(*pools, snaps, np.int32(1),
                                          np.int32(0))
    assert pool_v[0] == () and pool_k[0].shape == pools[0][0].shape
    assert set(dec.refuses) == {"draft_model"}
    assert "a lane takes no draft model" in dec.refuses["draft_model"]
    assert "a latent cache takes no draft model" in dec.refuses[
        "draft_model"]
    with pytest.raises(ValueError, match="a latent cache takes no draft"):
        GenerationServer(dec, g, slots=2, kv_blocks=16,
                         place=fluid.CPUPlace(), draft_decoder=dec,
                         draft_states=g)
    z = np.zeros((2, 2), np.int32)
    with pytest.raises(NotImplementedError, match="step_window runs a "
                       "window of positions"):
        dec.step_window(g, *pools, np.zeros((2, NB), np.int32), z[:, 0], z,
                        z[:, 0].astype(np.uint32),
                        z[:, 0].astype(np.float32), z[:, 0])
    with pytest.raises(NotImplementedError, match="int8 pool"):
        _decoder("int8")
    with pytest.raises(ValueError, match="needs lanes"):
        dec.init_pool(5)
    assert dec.kernels["paged_attention_decode"] == "xla:not_tpu"
    spec, _ = _block()
    assert spec.rotated(lm_block.DELTA) is False
    assert spec.rotated(lm_block.FULL) is True


# what `param_layout` STILL refuses beside delta-rule layers and beside a
# latent, each by the words of the refusal that names it
STILL_REFUSED = {
    "a_ring_beside_delta": (
        dict(layer_types=["delta_rule"] * 4 + ["sliding_attention",
                                               "full_attention"],
             window=8), "no Mamba or conv layers, ring"),
    "mamba_beside_delta": (
        dict(layer_types=["delta_rule"] * 4 + ["mamba", "full_attention"],
             ssm_heads=2, ssm_d_head=4, ssm_d_state=4, ssm_conv=4),
        "no Mamba or conv"),
    "conv_beside_delta": (
        dict(layer_types=["delta_rule"] * 4 + ["conv", "full_attention"],
             conv_width=3), "no Mamba or conv"),
    "qk_norm_beside_delta": (dict(qk_norm=True, qk_norm_per_head=True),
                             "QK-norm"),
    "an_indexer_beside_delta": (
        dict(q_lora_rank=8, index_topk=4, index_n_heads=2,
             index_head_dim=8), "lightning indexer"),
    "no_attention_beside_delta": (
        dict(layer_types=["delta_rule"] * 6), "among full-attention layers"),
    "a_negative_gate_rank": (dict(delta_gate_rank=-1), "delta_gate_rank"),
    "no_positions_on_the_latent": (dict(positions="none"),
                                   "positions 'none' without"),
    "an_elementwise_gate_on_a_latent": (
        dict(attention_gate_per_head=False), "a scalar a head"),
    "grouped_heads_beside_a_latent": (dict(n_kv_heads=2),
                                      "grouped K/V heads"),
    "a_head_size_beside_a_latent": (dict(d_head=8), "d_head beside it"),
    "an_odd_rotated_part": (dict(qk_rope_head_dim=7), "even qk_rope"),
    "scale_q_lora_without_a_low_rank_query": (
        dict(scale_q_lora=True), "q_lora_rank 0 is a query of ONE"),
    "a_group_limit_on_a_softmax_router_with_a_bias": (
        dict(router="softmax", norm_topk_prob=False),
        "group-limited choice"),
    "a_group_limit_on_a_sigmoid_router_without_a_bias": (
        dict(router_bias=False), "group-limited choice"),
    "the_largest_score_under_a_sigmoid_router": (
        dict(group_score="max"), "group-limited choice"),
    "dense_layers_without_a_width": (dict(dense_d_inner=0),
                                     "need dense_d_inner"),
}


@pytest.mark.parametrize("name", sorted(STILL_REFUSED))
def test_each_narrowed_refusal_still_refuses_what_is_not_built(name):
    over, why = STILL_REFUSED[name]
    with pytest.raises(NotImplementedError, match=why):
        _decoder(**over)


@pytest.mark.parametrize("over,why", [
    (dict(group_score="top3"), "group_score"),
    (dict(group_score="top2_sum", n_group=1, topk_group=1), "group_score"),
    (dict(delta_gate_floor=1.0), "lower bound is negative"),
    (dict(attention_gate=False), "attention_gate is off"),
    (dict(expert_swiglu_limits=[0, -1]), "0 .no clamp. or positive"),
    (dict(layer_types=["full_attention"] * 6), "delta_heads 4 without"),
])
def test_a_description_that_contradicts_itself_is_a_value_error(over, why):
    with pytest.raises(ValueError, match=why):
        _block(**over)


def test_a_solar_like_block_keeps_its_refusals_and_gains_full_rank_gates():
    """The eleventh description's points beside the twelfth's: RoPE on a
    K/V table beside delta-rule layers and a gate a head there stay
    refused; full-rank gates (`delta_gate_rank` 0) and a bounded decay
    are the delta rule's own and build beside a K/V table too."""
    solar = _json("perf", "configs", "solar-open2-250b-1chip.json")
    solar.update(solar["rehearse"])
    for over, why in ((dict(positions="rope"), "positions 'rope' with"),
                      (dict(attention_gate_per_head=True), "a scalar a head"),
                      (dict(kv_lora_rank=32, qk_nope_head_dim=8,
                            qk_rope_head_dim=8, v_head_dim=8,
                            attention_gate=False, positions="rope"),
                       "grouped K/V heads")):
        with pytest.raises(NotImplementedError, match=why):
            _decoder(m=solar, **over)
    dec = _decoder(m=solar, delta_gate_rank=0, delta_gate_floor=-5.0)
    assert "layer_1.delta_decay.w_0" in dec.state_shapes
    assert "layer_1.delta_gate.b_0" not in dec.state_shapes
    assert "layer_1.delta_decay_a.w_0" not in dec.state_shapes


def test_scopes_name_the_head_gate_the_group_choice_and_the_mixers():
    dec = _decoder()
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    pools = jax.eval_shape(lambda: dec.init_pool(9, lanes=2))
    i32 = sds((2,), np.int32)
    text = dec.step.lower(
        g, *pools, sds((2, NB), np.int32), i32, i32, sds((2,), np.uint32),
        sds((2,), np.float32), sds((2,), np.bool_)).as_text(debug_info=True)
    for part in ("delta_in_proj", "delta_conv", "delta_gates", "delta_rule",
                 "delta_gate_norm", "delta_out_proj", "latent_q",
                 "latent_kv", "latent_absorb", "attention",
                 "attention_head_gate", "dense_ffn", "shared_expert",
                 "moe_router/moe_group_choice"):
        assert f"paged_decoder/{part}" in text, part
    assert "paged_decoder/attention_gate" not in text
    scopes = dec.compiler_scopes
    assert scopes["g[\\'layer_1.delta_decay.w_0\\']"] == (
        "paged_decoder/delta_gates")
    assert scopes["g[\\'layer_1.delta_gate.w_0\\']"] == (
        "paged_decoder/delta_gate_norm")
    assert scopes["g[\\'layer_5.attn_gate.w_0\\']"] == (
        "paged_decoder/attention_head_gate")
    assert scopes["g[\\'layer_5.q_proj.w_0\\']"] == "paged_decoder/latent_q"
    assert scopes["g[\\'layer_0.ffn_gate.w_0\\']"] == (
        "paged_decoder/dense_ffn")


def test_the_kernels_are_selected_for_a_tpu_at_the_cells_geometry():
    """Built for "tpu" at the published widths (no array is made): the
    latent form of the paged kernel at 32 heads over a row of 640, and
    what a lane, a block and the pool hold."""
    spec, d_inner = _block(FILE)
    _, dec = build_lm_paged_decoder(
        FILE["vocab_size"], 16, 256, d_model=FILE["hidden_size"],
        n_heads=FILE["num_attention_heads"],
        n_layers=FILE["num_hidden_layers"], d_inner=d_inner,
        kv_dtype="bf16", platform="tpu", block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas:latent"
    numbers = FILE["cut"]["arithmetic_numbers"]
    assert dec.bytes_per_block == 16 * numbers["cache_bytes_a_position"]
    assert dec.state_bytes_per_lane == numbers["state_bytes_a_lane"]
    from paddle_tpu.kernels import delta_rule, grouped_matmul
    kern, why = delta_rule.select_delta_rule(
        lanes=128, heads=32, d_head=128, platform="tpu")
    assert kern is not None and kern.name == "pallas_delta_rule", why
    kern, why = grouped_matmul.select_grouped_matmul(
        rows=128 * 8, d_model=2560, d_ff=768, n_experts=128,
        dtype=jnp.bfloat16, platform="tpu")
    assert kern is not None, why


def test_configuration_file_holds_the_catalogs_keys_and_its_arithmetic():
    """Every published width under the source's own keys; the derived
    keys are what they repeat; `cut.arithmetic_numbers` recomputed from
    `param_layout`'s shapes (124.41 B whole, 3.692 B held)."""
    rows = [json.loads(l) for l in open(CATALOG)] if os.path.exists(
        CATALOG) else []
    for row in (r for r in rows if r["name"] == "Ling-3.0-flash"):
        assert FILE["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in FILE["reduced"]:
                assert FILE[key] == value, key
        assert FILE["published"] == {k: row["config"][k]
                                     for k in FILE["reduced"]}
    m = FILE
    assert m["reduced"] == ["num_hidden_layers", "num_experts",
                            "vocab_size", "num_nextn_predict_layers"]
    assert (m["num_hidden_layers"], m["num_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"]) == (6, 128, 39296, 0)
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == m["name"]]
    assert entry["reduced"] == m["reduced"]
    assert entry["source"] == m["source"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        m["name"], "agent128-hybrid", 1)
    # the derived keys, for the toy's overlay too
    for cfg in (m, CONFIG):
        n = cfg["num_hidden_layers"]
        assert cfg["layer_types"] == [
            "full_attention" if (l + 1) % cfg["layer_group_size"] == 0
            else "delta_rule" for l in range(n)]
        assert cfg["mlp_layer_types"] == [
            "dense" if l < cfg["first_k_dense_replace"] else "sparse"
            for l in range(n)]
        lin = cfg["linear_attn_config"]
        assert (lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"]) == (
                    cfg["num_attention_heads"], cfg["head_dim"],
                    cfg["short_conv_kernel_size"])
        assert cfg["kda_gate_rank"] == 0 and cfg["no_kda_lora"]
        assert cfg["first_local_expert"] == 0
        assert REF.latent_layers(cfg) == [
            l for l, k in enumerate(cfg["layer_types"])
            if k == "full_attention"]
    assert m["num_routed_experts"] == m["published"]["num_experts"] == 512
    assert m["qk_rope_head_dim"] == m["rotary_dim"] == (
        m["partial_rotary_factor"] * m["head_dim"])
    assert m["q_lora_rank"] is None and m["block"]["spec"]["q_lora_rank"] == 0
    # the arithmetic, from the shapes `param_layout` gives
    spec, d_inner = _block(m)
    numbers = m["cut"]["arithmetic_numbers"]

    def shapes_of(spec, vocab, layers):
        return lm_block.param_layout(spec, vocab, m["hidden_size"],
                                     m["num_attention_heads"], layers,
                                     d_inner)[1]

    here = shapes_of(spec, m["vocab_size"], 6)

    def millions(pred, shapes=here):
        return sum(math.prod(s) for n, s in shapes.items() if pred(n)) / 1e6

    assert round(millions(lambda n: n.startswith("layer_0.")
                          and "ffn_" not in n), 2) == numbers[
        "delta_mixer_m"]
    assert round(millions(lambda n: n.startswith("layer_5.") and any(
        k in n for k in ("attn_", "q_proj", "kv_", "o_proj"))), 2) == (
        numbers["latent_mixer_m"])
    assert round(millions(lambda n: n.startswith("layer_0.ffn_")
                          and "norm" not in n), 2) == numbers["dense_ffn_m"]
    assert round(millions(lambda n: n.startswith("layer_2.router")), 2) == (
        numbers["router_m"])
    assert round(millions(lambda n: n.startswith("layer_2.shared")), 2) == (
        numbers["shared_expert_m"])
    assert round(millions(lambda n: n.startswith("layer_2.experts"))
                 / m["num_experts"], 3) == numbers["expert_m"]
    assert round(millions(lambda n: n == "lm_head.w_0"), 1) == numbers[
        "vocabulary_m"]
    assert round(millions(lambda n: True) / 1e3, 3) == numbers["here_b"]
    assert round(2 * millions(lambda n: True) / 1e3, 2) == numbers[
        "weights_gb"]
    # the whole model: 42 layers, every expert, the whole vocabulary
    whole = lm_block.BlockSpec(**dict(
        spec.__dict__, experts_held=0,
        layer_types=tuple(("delta_rule",) * 5 + ("full_attention",)) * 7,
        mlp_layer_types=("dense",) * 2 + ("sparse",) * 40))
    model = shapes_of(whole, m["published"]["vocab_size"], 42)
    assert round(millions(lambda n: True, model) / 1e3, 2) == numbers[
        "model_b"]
    assert round(2 * millions(lambda n: n.startswith("layer_2."), model)
                 / 1e3, 2) == numbers["sparse_layer_whole_gb"]
    per_lane = 4 * 5 * (32 * 128 * 128 + 3 * 3 * 32 * 128)
    assert numbers["state_bytes_a_lane"] == per_lane
    assert round(128 * per_lane / 1e9, 2) == numbers["state_gb_128_lanes"]
    assert round(32768 * 16 * numbers["cache_bytes_a_position"] / 1e9,
                 2) == numbers["pool_gb"]
    # the traffic file is agent128's, but for what names the job
    mine, theirs = (_json("perf", "traffic", name + ".json")
                    for name in ("agent128-hybrid", "agent128"))
    assert mine.pop("job") == "serve_lm_hybrid"
    for key in ("job", "what", "pool"):
        mine.pop(key, None), theirs.pop(key, None)
    assert mine == theirs


def test_the_new_reader_and_the_cells_lists_agree_with_the_benchmark():
    bench = _json("BENCHMARK.json")
    (last,) = [m for m in bench["per_layer"]
               if m["name"] == "serve_delta_gates_share"]
    # (the list's last entry until PR 65, PR 66, PR 67 and PR 68
    # appended theirs)
    assert [m["name"] for m in bench["per_layer"]].index(
        "serve_delta_gates_share") == bench["per_layer"].index(last)
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "serve_latent_ring_roofline", "sched_kv_copy_covered_share",
        "serve_step_bytes_roofline", "sched_step_cache_bytes_share",
        "sched_select_kernel_share"]
    assert last["workloads"] == [CELL, "solar-open2-250b-serve-docqa64"]
    reader = _load("reader_delta_gates", "perf", "metrics",
                   "serve_delta_gates_share.py")
    assert (reader.UNIT, reader.MOVES, reader.SOURCE, reader.LAYER) == (
        last["unit"], last["moves"], last["source"], last["layer"])
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", ())}
    for name in ("serve_tokens_per_s", "itl_p95_ms",
                 "serve_delta_rule_roofline", "sched_delta_kernel_share",
                 "serve_latent_attention_roofline",
                 "serve_moe_experts_roofline", "sched_moe_tokens_here_share",
                 "sched_state_reset_share", "serve_dense_ffn_share",
                 "serve_hbm_peak_gb", "serve_device_idle_share"):
        assert name in mine, name
    assert "serve_attention_roofline" not in mine
    # nothing to read without a trace: the line leaves the metric out
    sys.path.insert(0, os.path.join(ROOT, "perf"))
    try:
        run = type("Run", (), {"trace": None, "notes": {}})()
        assert reader.compute(run) is None
    finally:
        sys.path.remove(os.path.join(ROOT, "perf"))


def test_rehearsal_of_the_cell_prints_the_readers():
    """The cell end to end on the CPU at the files' tiny sizes: both
    comparisons pass, the fit evens the loads, and the span-sourced
    readers of lanes, table and router are in the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   os.environ.get("TMPDIR", "/tmp"), "ling_rehearsal_cache"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perf", "run_cell.py"),
         "--workload", CELL, "--seed", "6200000123", "--seconds", "3",
         "--trace", "1", "--rehearse"], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0, line
    for name in ("sched_state_reset_share", "moe_held_experts_hit_share",
                 "sched_moe_rows_held_share", "sched_moe_tokens_here_share",
                 "sched_pool_wait_share", "sched_delta_kernel_share",
                 "tick_ms", "sched_build_ms"):
        assert name in line["metrics"], name
    for name in ("ttft_p50_ms", "serve_queue_wait_p95_ms"):
        assert name in line["metrics"], name     # the cell reports ttft


# sha256 of the lowered served step (StableHLO text, no locations) of
# the ten other configurations' toys, taken at the parent commit of
# the PR that put delta-rule lanes beside a latent table: a description
# without the new fields computes what it computed
# (a block with experts: taken again at PR 63, whose routing orders
# nothing: `tests/test_moe_routing.py` holds it to the results it had)
PARENTS_STEPS = {
    "deepseek-v2-1chip":
        "9c3830f8c2a1b9138e20f455378fb171060f6d4315ba634aa4ee74bd4b592eae",
    "glm-5.2-1chip":
        "c2087f45f65f60cf050d6351ca811905c6c4db817d265cde6d714819933e5ca5",
    "granite-4.0-h-small-1chip":
        "253a1dfac12af82aae0c4a96fde88a25ce72655e5821ba4f294308b4bb447b37",
    "k-exaone-236b-a23b-1chip":
        "4ffbd3e82acdc257b7942dc29e8e4eb0490383b28ace53b1c7d480a6fa040522",
    "lfm2-24b-a2b-1chip":
        "1eb9e2f2533b44ba25115b9015bd3bca06580000c971b2cdee7f87dec1a4031f",
    "longcat-flash-1chip":
        "9701d7e40fd04fc70b38db89e5fa74c0ff1fd7824acb46fb0678af10329f0ecc",
    "mellum2-12b-a2.5b-1chip":
        "212478f1abda7105caa4cdbcca0cbf132f2b82a36c59fa52d9f7f3ec136f15c1",
    "olmoe-1b-7b-1chip":
        "581e73cc9cf988400e7f3617e6d7daed41b3b7f4f9640d37a2a2c5183dad795b",
    "ouro-2.6b":
        "f36a5c45128a6cc5bdc417a7c0750a5bf83734afcdd4d1b341bd85d1b96a531e",
    "solar-open2-250b-1chip":
        "5f635bdc363ce11324dd4a658685153cfd808418ff428b25bede464595b9a1ea",
}


@pytest.mark.parametrize("name", sorted(PARENTS_STEPS))
def test_the_other_toys_lowered_steps_are_the_parents_text(name):
    m = _json("perf", "configs", name + ".json")
    m.update(m["rehearse"])
    spec, d_inner = _block(m)
    slots, bs, nb = 2, 4, 4
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], bs, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=d_inner, kv_dtype="bf16", platform="cpu", block=spec)
    sds = jax.ShapeDtypeStruct
    g = {n: sds(s, np.float32) for n, s in dec.state_shapes.items()}
    ring = dec.window_blocks_per_seq
    pools = jax.eval_shape(lambda: dec.init_pool(
        slots * nb + 1, window_blocks=slots * ring + 1, lanes=slots))
    tables = sds((slots, nb), np.int32)
    if ring:
        tables = (tables, sds((slots, ring), np.int32))
    i32 = sds((slots,), np.int32)
    text = dec.step.lower(
        g, *pools, tables, i32, i32, sds((slots,), np.uint32),
        sds((slots,), np.float32), sds((slots,), np.bool_)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_STEPS[name]
