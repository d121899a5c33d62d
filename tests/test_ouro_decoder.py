"""The Ouro block (one stack of layers run `passes` times a token over
the same weights, a K/V plane for every (pass, layer) pair, a dense
SwiGLU FFN, norms on each sub-block's output, the final norm and an
exit gate after every pass) through `build_lm_paged_decoder` and
`GenerationServer`, against the plain reference
`perf/reference/ouro.py`, at toy widths on the CPU with seeded random
float32 weights.

The toy keeps the structure: 3 layers, 4 passes (12 planes), 4 heads of
8, an FFN of 48.  What is compared is LOGITS, every pass's x_t and the
gates, never tokens, except where a server's streams are compared with
themselves.
"""
import importlib.util
import json
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
V, D, H, F, L, T = 97, 32, 4, 48, 3, 4
BS, NB = 4, 8                                    # 32 positions
CONFIG = {"hidden_size": D, "num_attention_heads": H,
          "num_hidden_layers": L, "total_ut_steps": T,
          "rms_norm_eps": 1e-6, "rope_theta": 1e6, "vocab_size": V,
          "early_exit_threshold": 1}
# float32 weights and pool: the same float32 sums in another order (a
# position a step through planes against one causal pass): measured
# 1.5e-6 to 2.6e-6 over four seeds
TOL_FP32 = 1e-4
# The limits' toy-width twins, for float32 weights and a bfloat16
# pool: the decoder reads 1.1e-2, 7.8e-3, 5.7e-3, 7.5e-3, 2.8e-3; the
# nearest fault (all bfloat16) 1.1e-1, 8.3e-2, 6.4e-2, 8.1e-2, 3.2e-2
TOY_LIMITS = {"logits_rel_err": 0.04, "logits_rms_err": 0.03,
              "late_rms_err": 0.03, "pass_rms_err": 0.03,
              "gate_abs_err": 0.012}


def _load(name, *parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("ref_ouro", "perf", "reference", "ouro.py")


def _block(**over):
    return lm_block.BlockSpec(**dict(dict(
        name="ouro", norm="rms_norm", positions="rope", ffn="swiglu",
        bias=False, norm_eps=1e-6, rope_theta=1e6, passes=T,
        post_norm=True, exit_gate=True), **over))


def _decoder(kv_dtype="fp32", n_layers=L, **over):
    startup, dec = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=n_layers, d_inner=F,
        kv_dtype=kv_dtype, platform="cpu", block=_block(**over))
    assert startup is None
    return dec


def _weights(dec, seed=0):
    r = np.random.RandomState(seed)
    return {n: jnp.asarray(r.normal(0, 0.1, shape)
                           + (1.0 if ".scale_" in n else 0.0), jnp.float32)
            for n, shape in sorted(dec.state_shapes.items())}


def _drive(dec, g, seqs, slots=None, lanes=None, pools=None):
    """Teacher-force each of `seqs` through `step` in its own lane, the
    tables taken from a `PagedKVCache` as the server takes them;
    -> (each sequence's [len, V] logits, lane `lanes[0]`'s loop: every
    pass's x_t and gate stacked over positions, the pools as left).
    `pools` continues on pools an earlier drive left."""
    slots = slots or len(seqs)
    lanes = lanes if lanes is not None else list(range(len(seqs)))
    cache = PagedKVCache(slots * NB, BS, NB)
    pool_k, pool_v = pools or dec.init_pool(1 + slots * NB)
    tables = np.zeros((slots, NB), np.int32)
    for s, lane in zip(seqs, lanes):
        tables[lane] = cache.allocate(lane, len(s))
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    out, loops = [[] for _ in seqs], []
    for pos in range(max(len(s) for s in seqs)):
        toks = np.zeros(slots, np.int32)
        act = np.zeros(slots, bool)
        for s, lane in zip(seqs, lanes):
            if pos < len(s):
                toks[lane], act[lane] = s[pos], True
        args = (g, pool_k, pool_v, tables,
                np.where(act, pos, 0).astype(np.int32), toks, zs, zt, act)
        lg, r = dec.step_routing(*args)
        lg = np.asarray(lg)
        loops.append({k: np.asarray(v)[:, lanes[0]:lanes[0] + 1]
                      for k, v in r.items()})
        _, pool_k, pool_v, _ = dec.step(*args)
        for i, (s, lane) in enumerate(zip(seqs, lanes)):
            if pos < len(s):
                out[i].append(lg[lane])
    return ([np.stack(o) for o in out],
            {k: np.concatenate([r[k] for r in loops], 1)
             for k in loops[0]}, (pool_k, pool_v))


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


SEQ = list(np.random.RandomState(7).randint(0, V, 29))


@pytest.mark.parametrize("kv_dtype,tol", [("fp32", TOL_FP32),
                                          ("bf16", None)])
def test_prompt_then_decode_match_the_reference_at_every_position(
        kv_dtype, tol):
    """A position a step through the planes against the reference's
    one causal pass: the logits, x_t of EVERY pass and every gate.  A
    float32 pool to rounding; a bfloat16 pool inside the toy limits."""
    dec = _decoder(kv_dtype)
    g = _weights(dec)
    (got,), loop, _ = _drive(dec, g, [SEQ], slots=3)
    out = REF.compare(g, CONFIG, SEQ, got, loop)
    assert out["finite"] and len(out["pass_rms_errs"]) == T
    for name, most in TOY_LIMITS.items():
        assert out[name] <= (tol or most), (name, out[name])
    want, ref = REF.forward(g, CONFIG, SEQ)
    if tol:
        assert _rel(got, np.asarray(want)) < tol
        assert _rel(loop["passes"], np.asarray(ref["passes"])) < tol
        assert np.abs(loop["gates"] - np.asarray(ref["gates"])).max() < tol


def test_batched_lane_bit_identical_to_the_same_sequence_alone():
    """Continuous batching's guarantee, in one call of the same
    four-lane step: what a lane computes does not depend on its
    neighbours, the blocks it holds or the lane it is in."""
    dec = _decoder()
    g = _weights(dec, seed=3)
    others = [list(np.random.RandomState(s).randint(0, V, n))
              for s, n in ((11, 17), (12, 26))]
    (alone,), loop_alone, _ = _drive(dec, g, [SEQ], slots=4, lanes=[2])
    (beside, _, _), loop_beside, _ = _drive(
        dec, g, [SEQ, others[0], others[1]], slots=4, lanes=[1, 3, 0])
    assert np.array_equal(alone, beside)
    assert np.array_equal(loop_alone["passes"], loop_beside["passes"])
    assert np.array_equal(loop_alone["gates"], loop_beside["gates"])


def test_a_lane_and_its_blocks_reused_by_a_second_request():
    """A second sequence in the lane AND the blocks the first left
    full, in all 12 planes: it sees nothing of them."""
    dec = _decoder()
    g = _weights(dec)
    first = list(np.random.RandomState(3).randint(0, V, 32))
    _, _, pools = _drive(dec, g, [first])
    assert float(jnp.abs(pools[0][:, 1:]).min(axis=(1, 2, 3)).min()) > 0
    (got,), loop, _ = _drive(dec, g, [SEQ], pools=pools)
    want, ref = REF.forward(g, CONFIG, SEQ)
    assert _rel(got, np.asarray(want)) < TOL_FP32
    assert _rel(loop["passes"], np.asarray(ref["passes"])) < TOL_FP32


def test_the_comparison_refuses_every_fault_and_one_precision_below():
    """The reference's five faults and its all-bfloat16 reading, each
    held against the toy limits as if it were the system: every one is
    refused, and `pass_rms_errs` shows WHERE (a shared plane at the
    first pass, a pass left out at the last alone)."""
    dec = _decoder("bf16")
    g = _weights(dec)
    (got,), loop, _ = _drive(dec, g, [SEQ])
    system = REF.compare(g, CONFIG, SEQ, got, loop)
    assert all(system[k] <= most for k, most in TOY_LIMITS.items())
    readings = dict(REF.faults(g, CONFIG, SEQ),
                    below=REF.below(g, CONFIG, SEQ))
    assert set(readings) == set(REF.FAULTS) | {"below"}
    for fault, out in readings.items():
        refused = [k for k, most in TOY_LIMITS.items() if out[k] > most]
        assert refused, (fault, out)
    shared = readings["shared_planes"]["pass_rms_errs"]
    assert shared[0] > TOY_LIMITS["pass_rms_err"]
    three = readings["three_passes"]["pass_rms_errs"]
    assert three[:3] == [0.0, 0.0, 0.0] and three[3] > 0.1
    unnormed = readings["no_final_norm"]["pass_rms_errs"]
    assert unnormed[0] == 0.0 and unnormed[1] > 0.1


def test_one_pass_without_the_new_norms_is_the_shared_block():
    """`passes` 1 with the output norms and the gate off runs the path
    every other block runs (no scan): its numbers are those of the
    OLMoE-like block with ONE expert, one a token (a router over one
    expert weighs it 1), on the same matrices."""
    plain = _decoder(passes=1, post_norm=False, exit_gate=False)
    assert plain.step_routing is None and plain.step_counters == ()
    assert plain.passes == 1 and plain.kv_planes == L
    g = _weights(plain)
    _, moe = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
        kv_dtype="fp32", platform="cpu", block=lm_block.BlockSpec(
            name="olmoe-like", norm="rms_norm", positions="rope",
            ffn="moe_swiglu", bias=False, norm_eps=1e-6, rope_theta=1e6,
            n_experts=1, experts_per_token=1))
    gm = {}
    for n, shape in moe.state_shapes.items():
        dense = n.replace("experts_", "ffn_")
        gm[n] = (jnp.zeros(shape, jnp.float32) if "router" in n
                 else g[dense].reshape(shape))
    pool = plain.init_pool(1 + NB)
    tables = 1 + np.arange(NB, dtype=np.int32)[None]
    z = np.zeros(1, np.uint32), np.zeros(1, np.float32), np.ones(1, bool)
    pools_a, pools_b = pool, moe.init_pool(1 + NB)
    for pos, tok in enumerate(SEQ[:12]):
        at = np.array([pos], np.int32), np.array([tok], np.int32)
        a = plain.step_logits(g, *pools_a, tables, *at, *z)
        b = moe.step_logits(gm, *pools_b, tables, *at, *z)
        assert _rel(np.asarray(a), np.asarray(b)) < 1e-5
        _, *pools_a = plain.step(g, *pools_a, tables, *at, *z)
        _, *pools_b, _ = moe.step(gm, *pools_b, tables, *at, *z)


def test_description_is_checked_and_the_pool_has_a_plane_a_pass_and_layer():
    """What nothing builds is refused by name; the pool's shape and
    `bytes_per_block` count `passes x layers` planes; the other blocks'
    shapes are what they were."""
    with pytest.raises(NotImplementedError, match="adaptive exit"):
        _block(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="passes 0"):
        _block(passes=0)
    with pytest.raises(NotImplementedError, match="dense SwiGLU FFN is"):
        _decoder(qk_norm=True)
    with pytest.raises(NotImplementedError, match="dense SwiGLU FFN is"):
        _decoder(layer_types=["sliding_attention"] * L, window=8)
    with pytest.raises(NotImplementedError, match="built for the dense"):
        build_lm_paged_decoder(
            V, BS, NB, d_model=D, n_heads=H, n_layers=L, d_inner=F,
            platform="cpu", block=lm_block.olmoe(4, 2).__class__(
                **dict(lm_block.olmoe(4, 2).__dict__, passes=2)))
    with pytest.raises(NotImplementedError, match="int8 pool under a"):
        _decoder("int8")
    dec = _decoder("bf16")
    assert (dec.passes, dec.kv_planes, dec.table_layers) == (T, T * L, T * L)
    assert dec.bytes_per_block == 2 * T * L * BS * D * 2
    pool_k, pool_v = dec.init_pool(5)
    assert pool_k.shape == pool_v.shape == (T * L, 5, BS, D)
    assert pool_k.dtype == jnp.bfloat16
    assert dec.state_shapes["layer_2.ffn_gate.w_0"] == (D, F)
    assert dec.state_shapes["layer_0.attn_post_norm.scale_0"] == (D,)
    assert dec.state_shapes["exit_gate.w_0"] == (D, 1)
    assert dec.step_counters == ("exit_gate_open",)
    z = np.zeros(2, np.int32)
    with pytest.raises(NotImplementedError, match="step_window is not "
                       "built for a looped stack"):
        dec.step_window(_weights(dec), pool_k, pool_v,
                        np.zeros((2, NB), np.int32), z,
                        np.zeros((2, 3), np.int32), z.astype(np.uint32),
                        z.astype(np.float32), z)
    _, olmoe = build_lm_paged_decoder(
        V, BS, NB, d_model=D, n_heads=H, n_layers=2, d_inner=F,
        platform="cpu", block=lm_block.olmoe(4, 2))
    assert olmoe.bytes_per_block == 2 * 2 * BS * D * 4
    assert (olmoe.passes, olmoe.kv_planes) == (1, 2)
    assert "layer_0.experts_gate.w_0" in olmoe.state_shapes
    assert "exit_gate.w_0" not in olmoe.state_shapes


def _equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _equations(inner)
    return n


def test_the_step_holds_one_stack_body_however_many_passes():
    """The program of `step` does not grow with `passes`: one scan over
    the pass around ONE stack's equations; with the layers it does."""
    def count(**kw):
        dec = _decoder(**kw)
        pool_k, pool_v = dec.init_pool(3)
        z = np.zeros(2, np.int32)
        jaxpr = jax.make_jaxpr(dec.step)(
            _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32),
            z, z, z.astype(np.uint32), z.astype(np.float32),
            np.zeros(2, bool))
        text = str(jaxpr)
        return _equations(jaxpr.jaxpr), text.count("scan["), text

    two, scans, _ = count(passes=2)
    four, _, text = count(passes=4)
    eight, _, _ = count(passes=8)
    assert two == four == eight and scans >= 1
    assert "length=4" in text
    deeper, _, _ = count(passes=4, n_layers=2 * L)
    assert deeper > 1.7 * four


def test_generation_server_serves_a_looped_stack():
    """Requests through `GenerationServer`, tick-ahead on: beside
    others, in a lane and blocks another has left, and through the
    PREFIX CACHE (a block id is common to all planes: a hit finds every
    pass's K/V), each gives the tokens of the same request alone; the
    tick spans carry the loop's counts; a draft model is refused."""
    dec = _decoder()
    g = {n: np.asarray(v) for n, v in _weights(dec).items()}
    place = fluid.CPUPlace()
    # the block's own word: a draft model alone, the prefix cache works
    assert set(dec.refuses) == {"draft_model"}
    with pytest.raises(ValueError, match="looped stack takes no draft"):
        GenerationServer(dec, g, slots=2, kv_blocks=16, place=place,
                         prefix_cache=False, draft_decoder=dec,
                         draft_states=g)
    shared = list(np.random.RandomState(9).randint(0, V, 9))
    prompts = [shared + list(np.random.RandomState(s).randint(0, V, n))
               for s, n in ((1, 2), (2, 5), (3, 1), (4, 3))]

    def ask(server, i):
        return server.submit(prompts[i], 14, temperature=1.0, seed=40 + i)

    want = []
    for i in range(len(prompts)):
        solo = GenerationServer(dec, g, slots=1, kv_blocks=NB, place=place,
                                prefix_cache=False)
        try:
            want.append(ask(solo, i).result(timeout=120))
        finally:
            solo.close()
    assert all(len(set(w)) > 6 for w in want)
    spans = []
    tracing.add_span_listener(spans.append)
    srv = GenerationServer(dec, g, slots=2, kv_blocks=2 * NB, place=place,
                           prefix_cache=True)
    try:
        streams = [ask(srv, i) for i in range(len(prompts))]
        assert [s.result(timeout=120) for s in streams] == want
        assert ask(srv, 1).result(timeout=120) == want[1]
        stats = srv.stats()
        assert stats["prefix_hits"] >= 2
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
    finally:
        tracing.remove_span_listener(spans.append)
        # (a listener sees a tick before its account is made: a reader's)
        tracing.finished_spans()
        srv.close()
    ticks = [s["attrs"] for s in spans if s["name"] == "serving.decode_tick"]
    assert ticks and all(a["loop_passes"] == T and a["kv_planes"] == T * L
                         and a["kv_wait"] == 0 for a in ticks)
    assert all(a["kv_pages_table"] == 2 * NB * T * L for a in ticks)
    assert any("exit_gate_open" in a for a in ticks)


def test_scopes_name_the_loops_norms_and_the_gate():
    """`loop_norm` and `exit_gate` in the step's compiled text, beside
    the scopes every block has."""
    dec = _decoder()
    pool_k, pool_v = dec.init_pool(3)
    z = np.zeros(2, np.int32)
    text = dec.step.lower(
        _weights(dec), pool_k, pool_v, np.zeros((2, NB), np.int32), z, z,
        z.astype(np.uint32), z.astype(np.float32),
        np.zeros(2, bool)).compile().as_text()
    for part in ("loop_norm", "exit_gate", "qkv", "kv_write", "attention",
                 "attn_out", "mlp", "head"):
        assert f"paged_decoder/{part}" in text, part
    assert dec.compiler_scopes["g[\\'layer_1.ffn_up.w_0\\']"] == \
        "paged_decoder/mlp"


def test_scope_tables_name_a_loops_weights_and_leave_the_loop_out():
    """In a loop's body a weight is an element of the body's parameter
    tuple, which has no metadata: the compiler's slices of it take the
    scope of the matmul that waits for them (their CONSUMER), and the
    `while` itself, whose seconds are its body's over again, is left
    out of `scope_seconds`."""
    from paddle_tpu import profiler

    mlp = ("jit(step)/paged_decoder/while/body/closed_call/paged_decoder/"
           "mlp/dot_general")
    text = f"""
  %gte.1 = bf16[8,8] get-tuple-element(%arg_tuple.0), index=3
  %slice-start.1 = (bf16[8,8], bf16[4,8], s32[]) slice-start(%gte.1)
  %slice-done.1 = bf16[4,8] slice-done(%slice-start.1)
  %fusion.2 = f32[2,8] fusion(%x.1, %slice-done.1), kind=kOutput, metadata={{op_name="{mlp}"}}
  %while.4 = (s32[], f32[2,8]) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={{op_name="jit(step)/paged_decoder/while"}}
"""
    table, inherited = profiler._scope_tables(text)
    assert table["slice-done.1"] == table["slice-start.1"] == mlp
    assert {"slice-done.1", "slice-start.1"} <= inherited
    assert "fusion.2" not in inherited
    assert table["while.4"] == profiler.LOOP_SCOPE
    profiler._register_hlo_text("test.loop_scopes", lambda: text)
    seconds = profiler.scope_seconds(
        {"while.4": 1.0, "fusion.2": 0.5, "slice-done.1": 0.25,
         "copy.9": 0.125}, "test.loop_scopes")
    assert seconds == {mlp: 0.75, "": 0.125}


def test_configuration_file_describes_the_block_and_its_arithmetic():
    """perf/configs/ouro-2.6b.json's `block`, read as the benchmark's
    job reads it, builds the decoder at the published widths (shapes
    only: nothing is allocated), nothing is reduced, and the parameter
    and cache arithmetic the file states is the decoder's own."""
    with open(os.path.join(ROOT, "perf", "configs", "ouro-2.6b.json")) as f:
        m = json.load(f)
    with open(os.path.join(ROOT, "perf", "traffic", "chat12.json")) as f:
        t = json.load(f)
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    assert m["reduced"] == [] and b["d_inner"] == "intermediate_size"
    assert (spec.passes, spec.post_norm, spec.exit_gate) == (4, True, True)
    assert spec.rope_theta == 1e6 and spec.norm_eps == 1e-6
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], t["block_size"], t["context"] // t["block_size"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=m[b["d_inner"]],
        kv_dtype=t["kv_dtype"], platform="tpu", block=spec)
    assert dec.kernels["paged_attention_decode"] == "pallas"
    assert (dec.passes, dec.kv_planes) == (4, 192)
    shapes = dec.state_shapes

    def count(prefix):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(prefix))

    assert round(count("layer_0.") / 1e6, 2) == 51.39    # the file's counts
    assert round(count("layer_") / 1e9, 3) == 2.467
    assert round(count("") / 1e9, 3) == 2.668
    assert round(count("") * 2 / 1e9, 2) == 5.34         # GB in bfloat16
    assert dec.bytes_per_block == 1572864 * 16           # 1.5 MiB a position
    assert round(dec.bytes_per_block * (t["kv_blocks"] + 1) / 1e9, 2) == 7.27
    assert t["kv_blocks"] < t["slots"] * t["context"] // t["block_size"]
    # chat32's literal table, unchanged, served in its stored order
    with open(os.path.join(ROOT, "perf", "traffic", "chat32.json")) as f:
        assert t["lengths"]["table"] == json.load(f)["lengths"]["table"]
    assert "order" in t and "permute" not in t
    loop = _load("loop_bytes", "perf", "loop_bytes.py")
    assert loop.stack_weight_bytes(m) == 2 * count("layer_")
    assert loop.page_bytes(m, t["block_size"]) == 2 * 16 * 2048 * 2


@pytest.mark.parametrize("command", [
    ["perf/selfcheck.py"],
    ["perf/run_cell.py", "--workload", "ouro-2.6b-serve-chat12",
     "--seed", "3000000019", "--seconds", "3", "--trace", "1",
     "--rehearse"]], ids=["selfcheck", "rehearse"])
def test_the_benchmark_wires_and_rehearses_the_cell(command, tmp_path):
    """`perf/selfcheck.py` (every reader agrees with BENCHMARK.json)
    and the new cell's rehearsal at its files' toy sizes: `correct`
    true with both comparisons deciding it, the pool read from the
    traffic file, the span-sourced readers in the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable] + command, cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = out.stdout.strip().splitlines()[-1]
    if command[0].endswith("selfcheck.py"):
        assert last == "selfcheck ok"
        return
    line = json.loads(last)
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert line["metrics"]["sched_loop_passes"]["value"] == 2
    assert line["metrics"]["sched_pool_wait_share"]["value"] == 0
    notes = json.loads(out.stdout.strip().splitlines()[-2])["notes"]
    assert notes["reference"]["ok"] and notes["served"]["ok"]
    assert notes["pool"]["kv_blocks"] < notes["pool"]["slots_x_context"]
