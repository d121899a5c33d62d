"""The paged-attention decode kernel and its selection
(paddle_tpu/kernels/paged_attention.py).

Pins two contracts:

  * the kernel is an IMPLEMENTATION swap, never a semantics change:
    greedy decode through the Pallas path (the interpreter on the CPU)
    is bit-identical to the XLA gather path for fp32/bf16/int8 KV,
    speculative verify rides the same kernel through step_window, and
    sampled streams match;
  * which of the two runs is a function of the decoder's geometry and
    the platform it is built for, and of nothing else: a refused
    geometry returns None with its reason, `decoder.kernels` and
    `GenerationServer.stats()` carry it, and the benchmark's own
    geometries select what PERF.md section 7 says they do.
"""
import contextlib
import functools
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.core.framework as fw
from paddle_tpu.kernels import paged_attention
from paddle_tpu.serving import GenerationServer

V = 29

_DECODERS = {}


@contextlib.contextmanager
def _interpreted():
    """Inside this context `build_lm_paged_decoder`'s one call of
    `select_paged_attention` asks for the Pallas interpreter: the entry
    point's own argument for tests."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "select_paged_attention",
                   functools.partial(
                       paged_attention.select_paged_attention,
                       interpret=True))
        yield


def _decoder(kv_dtype=None, interpret=False, block_size=4, max_blocks=4,
             d_model=32, n_heads=2, n_layers=2):
    """Build (or reuse) a paged decoder on the CPU, `interpret` under
    `_interpreted()`.  Every variant of one geometry shares the SAME
    parameter values (the fp32 XLA entry is built first: reset unique
    names make the param set reproducible across builds), so a
    comparison swaps the attention path, never the model."""
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    geo = (block_size, max_blocks, d_model, n_heads, n_layers)
    key = (kv_dtype, interpret) + geo
    base = (None, False) + geo
    if key not in _DECODERS:
        if key != base and base not in _DECODERS:
            _decoder(block_size=block_size, max_blocks=max_blocks,
                     d_model=d_model, n_heads=n_heads,
                     n_layers=n_layers)
        with _interpreted() if interpret else contextlib.nullcontext():
            fw.reset_unique_names()
            startup, dec = build_lm_paged_decoder(
                V, block_size, max_blocks, d_model=d_model,
                n_heads=n_heads, n_layers=n_layers, kv_dtype=kv_dtype,
                platform="cpu")
        if key != base:
            states = _DECODERS[base][1]
        else:
            scope = fluid.Scope()
            fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
            states = {n: np.asarray(scope.find_var(n))
                      for n in dec.state_names}
        _DECODERS[key] = (dec, states)
    return _DECODERS[key]


def _serve(dec, states, prompts, max_news, **kw):
    """The PR 8 staggered mixed-length harness: first wave mid-decode
    when the second arrives, early finishers evicted under load."""
    srv = GenerationServer(dec, states, slots=3, kv_blocks=12,
                           place=fluid.CPUPlace(), **kw)
    try:
        first = [srv.submit(p, m)
                 for p, m in zip(prompts[:3], max_news[:3])]
        while srv.stats()["generated_tokens"] == 0:
            time.sleep(0.002)
        rest = [srv.submit(p, m)
                for p, m in zip(prompts[3:], max_news[3:])]
        out = [s.result(timeout=120) for s in first + rest]
        stats = srv.stats()
    finally:
        srv.close()
    return out, stats


# ---------------------------------------------------------------------------
# paged-attention decode: bit-identity vs the XLA oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", [None, "bf16", "int8"])
def test_greedy_decode_bit_identical_pallas_vs_xla(kv_dtype):
    """Greedy decode through the fused kernel (interpret mode on CPU)
    produces the oracle's exact token streams — same einsum forms, same
    softmax, fused dequant included — under staggered mixed-length
    serving."""
    dec_x, states = _decoder(kv_dtype=kv_dtype)
    dec_p, _ = _decoder(kv_dtype=kv_dtype, interpret=True)
    assert dec_x.kernels["paged_attention_decode"] == "xla:not_tpu"
    assert dec_p.kernels["paged_attention_decode"] == "pallas"

    r = np.random.RandomState(2)
    prompts = [list(r.randint(0, V, n)) for n in (3, 6, 2, 5, 4)]
    max_news = [6, 9, 12, 4, 8]
    want, _ = _serve(dec_x, states, prompts, max_news)
    got, st = _serve(dec_p, states, prompts, max_news)
    assert got == want
    assert st["decode_kernel"] == "pallas"
    assert all(len(o) == m for o, m in zip(got, max_news))


def test_spec_verify_rides_the_same_kernel():
    """step_window (speculative verify: spec_k+1 positions per slot in
    one dispatch) uses the same kernel via its multi-position variant —
    accepted streams stay bit-identical to the plain XLA server."""
    dec_x, states = _decoder()
    dec_p, _ = _decoder(interpret=True)
    draft, dstates = _decoder(d_model=16, n_heads=2, n_layers=1)

    r = np.random.RandomState(3)
    prompts = [list(r.randint(0, V, n)) for n in (3, 5, 2, 6)]
    max_news = [6, 8, 10, 5]
    want, _ = _serve(dec_x, states, prompts, max_news)
    got, st = _serve(dec_p, states, prompts, max_news,
                     draft_decoder=draft, draft_states=dstates,
                     spec_k=3)
    assert got == want
    assert st["draft_proposed"] > 0
    assert st["decode_kernel"] == "pallas"


def test_sampled_decode_identical_through_kernel():
    """The (seed, position) PRNG rides on top of the kernel's logits:
    sampled streams match the oracle server's exactly."""
    dec_x, states = _decoder()
    dec_p, _ = _decoder(interpret=True)
    outs = []
    for dec in (dec_x, dec_p):
        srv = GenerationServer(dec, states, slots=2, kv_blocks=8,
                               place=fluid.CPUPlace())
        try:
            outs.append(srv.submit([3, 1, 4], 6, temperature=0.7,
                                   seed=11).result(timeout=120))
        finally:
            srv.close()
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# selection: a function of geometry and platform alone
# ---------------------------------------------------------------------------

def _cell_geometry(workload):
    """The decoder geometry a serving cell of BENCHMARK.json builds
    (perf/jobs/serve_closed.py), read from the cell's own files."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(repo, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = load("perf", "configs", w["config"] + ".json")
    t = load("perf", "traffic", w["traffic"] + ".json")
    geometry = dict(d_model=m["hidden_size"],
                    n_heads=m["num_attention_heads"],
                    block_size=int(t["block_size"]),
                    max_blocks_per_seq=(int(t["context"])
                                        // int(t["block_size"])),
                    kv_dtype=t["kv_dtype"])
    if "num_key_value_heads" in m:
        # a configuration that states its K/V geometry: what the
        # decoder derives from the block description (a head is the
        # model's width over its query heads where no key says other)
        d_head = m.get("head_dim", m["hidden_size"]
                       // m["num_attention_heads"])
        geometry.update(
            d_head=d_head, kv_width=m["num_key_value_heads"] * d_head,
            ringed="sliding_attention" in m.get("layer_types", ()))
    return geometry


def _cell_expert_shapes(workload):
    """What a serving cell's step asks `select_grouped_matmul` when it
    is traced: the slots' assignments, the widths and the experts HELD,
    read from the cell's own files."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(repo, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = load("perf", "configs", w["config"] + ".json")
    t = load("perf", "traffic", w["traffic"] + ".json")
    held = m[m["block"]["from_keys"].get("experts_held")
             or m["block"]["from_keys"]["n_experts"]]
    return dict(rows=int(t["slots"]) * m["num_experts_per_tok"],
                d_model=m["hidden_size"], d_ff=m[m["block"]["d_inner"]],
                n_experts=held, dtype=m["dtype"])


CHIP_SMOKE = dict(d_model=1024, n_heads=8, block_size=16,
                  max_blocks_per_seq=32)


@pytest.mark.parametrize("geometry,platform,interpret,want", [
    # d2048, 32 heads of 64, 32 blocks of 16, bf16
    ("opt-1.3b-serve-closed32", "tpu", False, "head_dim_misaligned"),
    # d2048, 16 heads of 128, 64 blocks of 16 (context 1024), bf16
    ("olmoe-1b-7b-serve-chat32", "tpu", False, "vmem_scratch"),
    # d2304, 32 query heads of 128 over 4 K/V heads (rows of 512),
    # sliding layers on a ring: not the kernel's geometry
    ("mellum2-12b-a2.5b-serve-agent96", "tpu", False, "kv_geometry"),
    # d4096, 32 query heads of 128 over 8 K/V heads (rows of 1024) on
    # its one attention layer in ten, context 1024: not its geometry
    ("granite-4.0-h-small-serve-chat64", "tpu", False, "kv_geometry"),
    # each part of that geometry alone is refused too
    (dict(CHIP_SMOKE, kv_dtype="bf16", kv_width=256), "tpu", False,
     "kv_geometry"),
    (dict(CHIP_SMOKE, kv_dtype="bf16", d_head=256), "tpu", False,
     "kv_geometry"),
    (dict(CHIP_SMOKE, kv_dtype="bf16", ringed=True), "tpu", False,
     "kv_geometry"),
    # stated and plain (a pool row IS d_model): the kernel
    (dict(CHIP_SMOKE, kv_dtype="bf16", kv_width=1024, d_head=128), "tpu",
     False, None),
    # chip_smoke.py --legs serve_lm: heads of 128, context 512
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "tpu", False, None),
    (dict(CHIP_SMOKE, kv_dtype="int8"), "tpu", False, None),
    # off a TPU there is nothing to compile the kernel with...
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "cpu", False, "not_tpu"),
    # ...unless a test asks for the Pallas interpreter
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "cpu", True, None),
], ids=["opt-1.3b-tpu", "olmoe-1b-7b-1chip-tpu", "mellum2-1chip-tpu",
        "granite-4.0-h-small-1chip-tpu", "grouped-kv-tpu", "wide-heads-tpu", "ring-tpu", "stated-mha-tpu",
        "chip_smoke-fp32-tpu",
        "chip_smoke-int8-tpu", "chip_smoke-cpu",
        "chip_smoke-cpu-interpret"])
def test_selection_follows_geometry_and_platform(geometry, platform,
                                                 interpret, want):
    """The benchmark's own geometries: this is the test PERF.md section
    7 cites for "no cell exercises the Pallas paged kernel"."""
    if isinstance(geometry, str):
        geometry = _cell_geometry(geometry)
    kern, reason = paged_attention.select_paged_attention(
        platform=platform, interpret=interpret, **geometry)
    assert reason == want
    assert (kern is None) == (want is not None)
    assert paged_attention.paged_attention_supports(
        platform=platform, interpret=interpret, **geometry) == want


@pytest.mark.parametrize("workload,held,rows", [
    ("olmoe-1b-7b-serve-chat32", 64, 256),
    ("mellum2-12b-a2.5b-serve-agent96", 64, 768),
    # one chip's 36 of the 72 experts the router routes 640 rows over
    ("granite-4.0-h-small-serve-chat64", 36, 640)])
def test_expert_kernel_selection_follows_the_cells_shapes(workload, held,
                                                          rows):
    """The cells with experts, from their own files: every one runs the
    Pallas grouped matmul on a TPU (what `sched_moe_kernel_share` reads
    as 100) and `ragged_dot` off one."""
    from paddle_tpu.kernels import grouped_matmul

    shapes = _cell_expert_shapes(workload)
    assert (shapes["n_experts"], shapes["rows"]) == (held, rows)
    kern, reason = grouped_matmul.select_grouped_matmul(
        platform="tpu", **shapes)
    assert reason is None and kern.name == grouped_matmul.NAME
    assert grouped_matmul.select_grouped_matmul(
        platform="cpu", **shapes) == (None, "not_tpu")


def test_unsupported_shape_is_refused_with_its_reason():
    """A refused geometry never crashes a build: the decoder runs the
    XLA gather path, and `decoder.kernels` and the server's stats say
    why."""
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    # 2 * (64*512) * 64 * 4B = 16 MiB of VMEM scratch: over budget
    geometry = dict(d_model=64, n_heads=2, block_size=64,
                    max_blocks_per_seq=512, kv_dtype="fp32")
    assert paged_attention.select_paged_attention(
        platform="cpu", interpret=True, **geometry) == \
        (None, "vmem_scratch")
    with _interpreted():
        fw.reset_unique_names()
        _, dec = build_lm_paged_decoder(
            V, 64, 512, d_model=64, n_heads=2, n_layers=1,
            platform="cpu")
    assert dec.kernels == {"paged_attention_decode": "xla:vmem_scratch"}
    dec_x, states = _decoder()
    srv = GenerationServer(dec_x, states, slots=2, kv_blocks=8,
                           place=fluid.CPUPlace())
    try:
        assert srv.stats()["decode_kernel"] == "xla:not_tpu"
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# analyzer: the rows reflect what runs
# ---------------------------------------------------------------------------


def test_analyze_rows_follow_the_platform(monkeypatch):
    import jax

    from paddle_tpu import analysis

    # heads of 128 and 8-row blocks: a geometry the TPU accepts
    spec = {"vocab_size": V, "d_model": 256, "n_heads": 2,
            "n_layers": 2, "block_size": 8, "max_blocks_per_seq": 4,
            "kv_dtype": "int8"}
    rep = analysis.analyze_generation_spec(spec, slots=4)
    assert rep["kernels"][0]["backend"] == "xla"
    assert all(r["kernel"] != "paged_attention_decode"
               for r in rep["kernels"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rep = analysis.analyze_generation_spec(spec, slots=4)
    assert rep["kernels"][0]["backend"] == "pallas"
    fused = [r for r in rep["kernels"]
             if r["kernel"] == "paged_attention_decode"]
    assert fused and fused[0]["fused_dequant"]
    # the fused path deletes the gather path's logical-order copy
    gather = [r for r in rep["kernels"]
              if r["kernel"] == "paged_attention_gather"][0]
    assert fused[0]["bytes"] < gather["bytes"]
    # a geometry the TPU refuses keeps the XLA rows there too
    rep = analysis.analyze_generation_spec(
        dict(spec, d_model=32), slots=4)
    assert rep["kernels"][0]["backend"] == "xla"
