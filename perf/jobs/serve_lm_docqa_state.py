"""Job `serve_lm_docqa_state`: `serve_lm_docqa`'s document sessions for a
block whose lanes keep a recurrent STATE and a convolution tail
(`BlockSpec.layer_types` "delta_rule") under the PREFIX CACHE, whose
hits start a lane from a SNAPSHOT of that state
(`GenerationServer(state_snapshots=)`).  `serve_lm_docqa.run` is what
runs: the documents built in set-up, `DocLoad`, the ramp, the window
and its accounting, `check_served` (delivered tokens of requests on two
documents against the reference, every one of them served from a
restored snapshot), `warm_served` and the comparison before the window
(`serve_lm_ring.check_against_reference` under `serve_lm_latent
.warm_reference`) are imported UNEDITED, as is `serve_lm_state`'s
`hbm_marks` (what the chip holds at each mark of set-up: the reference,
not the server, may set the process's peak).  `serve_lm_balanced` is
NOT imported: its `fit` makes a CHOICE BIAS, which this block's softmax
router does not have, so the evenness comes from `serve_lm_latent
.even`, the fit for a router without one, through `serve_lm_docqa`'s
own import of that module.

Replaced in those modules before `serve_lm_docqa.run` runs, because
they name what this block does not have or lack what it needs:

  `serve_lm_docqa.make_weights` (its lines 78 to 118)  kept, and over
        its arrays the draws the configuration's `assumed` names, as
        `serve_lm_state.make_weights` (its lines 85 to 104) draws a
        Mamba mixer's: `delta_a_log` = log U(1, 16), `delta_dt` =
        softplus^-1 of U(0.001, 0.1), `delta_conv` uniform in +-1/2.
  `serve_lm_latent.system_outputs` (its lines 108 to 139, which
        `serve_lm_docqa.run` hands to the comparison)  there the pools
        are made without lanes; here `serve_lm_state.system_outputs`'
        walk (its lines 108 to 140: pools with `lanes`, lane 0 from
        position 0) on the ONE pool shape of the walks before the
        window, and lane 0's TAILS read beside its states after the
        walk, for the reference's `tail_rms_err`.
  `serve_lm_docqa.balance` (its lines 129 to 170)  there a sigmoid
        router's choice bias is fitted; here `serve_lm_latent.balance`'s
        passes (its lines 184 to 206) over `walk`, which is
        `serve_lm_latent.walk` (its lines 142 to 181) with the pools
        made with `lanes`: each router matrix loses its component
        along the mean router input (`serve_lm_latent.even`).
  `serve_lm_docqa.build_server` (its lines 193 to 241)  there the
        server is made without snapshots; here the same lines with
        `state_snapshots` from the traffic file.

After the run the notes gain the cache's snapshot counts, and `correct`
also needs every admitted request of the load to have restored a
snapshot and no hit to have been cut back for want of one.
"""
from __future__ import annotations

import gc
import os
import types

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
docqa = common.load_module(os.path.join(_HERE, "serve_lm_docqa.py"))
state = common.load_module(os.path.join(_HERE, "serve_lm_state.py"))
latent = docqa.latent
_make_weights = docqa.make_weights
# the cache of the server `build_server` made: `serve_lm_docqa.run` keeps
# the server to itself, and the cache's counts outlive its close
_built = {}


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_lm_docqa.make_weights`, and over it the draws `assumed`
    names, small arrays made on the host from the same seed and
    committed to the device like the others."""
    import jax
    import jax.numpy as jnp

    g = _make_weights(shapes, seed, dtype)
    rng = np.random.default_rng([common.seed31(seed), 0xDE17A])
    for name in sorted(shapes):
        shape = tuple(shapes[name])
        if name.endswith("delta_conv.w_0"):
            v = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("delta_a_log.w_0"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("delta_dt.b_0"):
            dt = rng.uniform(1e-3, 1e-1, shape)
            v = dt + np.log(-np.expm1(-dt))
        else:
            continue
        g[name] = jax.device_put(jnp.asarray(v, jnp.float32).astype(dtype),
                                 jax.devices()[0])
    return g


def _pools(dec, slots: int, blocks: int):
    import jax

    return dec.init_pool(blocks + 1, jax.devices()[0], lanes=slots)


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_state.system_outputs` on the walks' one pool shape:
    `toks` through the step AS THE SERVER RUNS IT, `slots` lanes, the
    sequence in lane 0 from position 0 and the other lanes idle.  ->
    ([positions, vocab] logits, the routing of every position stacked
    on axis 1, and under "state" and "tails" lane 0's after the last
    position, [delta layers, ...])."""
    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = _pools(dec, slots, latent.walk_blocks(dec, slots, n))
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.arange(slots) == 0
    got, routed = [], []
    for pos in range(n):
        args = (g, pool_k, pool_v, tables,
                np.where(act, pos, 0).astype(np.int32),
                np.where(act, toks[pos], 0).astype(np.int32), zs, zt, act)
        logits, routing = dec.step_routing(*args)
        routed.append({k: v[:, :1] for k, v in routing.items()})
        got.append(logits[:1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return np.concatenate([np.asarray(x) for x in got]), {
        "state": np.stack([np.asarray(h[0]) for h in pool_k[1]]),
        "tails": np.stack([np.asarray(t[0]) for t in pool_v[1]]),
        **{k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
           for k in routed[0]}}


def walk(dec, g, toks, slots: int, blocks: int):
    """`serve_lm_latent.walk` with the lanes' states in the pools:
    `toks` [positions, slots] through every lane of the served step ->
    (the mean router input of every layer [layers, d], the assignments
    each routed expert got [layers, E])."""
    import jax
    import jax.numpy as jnp

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = _pools(dec, slots, blocks)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[:, :need] = 1 + np.arange(slots * need).reshape(slots, need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.ones(slots, bool)
    e_n = next(s[1] for name, s in dec.state_shapes.items()
               if name.endswith("router.w_0"))

    @jax.jit
    def add(total, loads, routing):
        layers = jnp.arange(loads.shape[0])[:, None, None]
        return (total + routing["inputs"].sum(axis=1),
                loads.at[layers, routing["experts"]].add(1.0))

    total = jnp.zeros((dec.moe_layers, dec.d_model), jnp.float32)
    loads = jnp.zeros((dec.moe_layers, e_n), jnp.float32)
    for pos in range(n):
        args = (g, pool_k, pool_v, tables, np.full(slots, pos, np.int32),
                toks[pos], zs, zt, act)
        total, loads = add(total, loads, dec.step_routing(*args)[1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return total / (n * slots), loads


def balance(cell, dec, g, n_tokens: int) -> dict:
    """`serve_lm_latent.balance` over the `walk` above: every layer's
    router matrix in `g` made even, in place."""
    inner, latent.walk = latent.walk, walk
    try:
        return latent.balance(cell, dec, g, n_tokens)
    finally:
        latent.walk = inner


def build_server(cell, run_):
    """`serve_lm_docqa.build_server` with the snapshot pool's rows from
    the traffic file.  -> (decoder, server)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.serving import GenerationServer

    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    dtype = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    block, d_inner = docqa.base.block_of(m)
    fw.reset_unique_names()
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], int(t["block_size"]),
        int(t["context"]) // int(t["block_size"]),
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=d_inner,
        kv_dtype=t["kv_dtype"], platform=platform, block=block)
    cell.mark("decoder built")
    g = make_weights(dec.state_shapes, cell.seed, dtype)
    jax.block_until_ready(g)
    cell.mark("weights made on the device")
    run_.notes["reference"] = docqa.check_against_reference(
        cell, dec, g, int(t["correct_tokens"]))
    cell.mark("compared with the reference")
    states = jax.device_get(g)
    del g
    gc.collect()
    cell.mark("weights copied to the host")
    server = GenerationServer(
        dec, states, slots=int(t["slots"]), kv_blocks=int(t["pool_blocks"]),
        place=place, max_queue=int(t["max_queue"]),
        prefix_cache=bool(t["prefix_cache"]),
        state_snapshots=int(t["state_snapshots"]))
    del states
    run_.notes["decoder_kernels"] = dict(dec.kernels)
    run_.notes["state"] = {
        "layers": dec.state_layers,
        "bytes_per_lane": dec.state_bytes_per_lane,
        "bytes": server.stats()["state_bytes"],
        "snapshot_pool_bytes": server.stats()["state_snapshot_pool_bytes"]}
    cell.mark("server built and warm")
    _built["cache"] = server._cache
    return dec, server


def run(cell):
    latent.system_outputs = system_outputs
    docqa.balance = balance
    docqa.build_server = build_server
    marks = types.SimpleNamespace(notes={})
    state.hbm_marks(cell, marks)
    run_ = docqa.run(cell)
    run_.notes.update(marks.notes)
    snaps = {k: v for k, v in _built["cache"].prefix_stats().items()
             if k.startswith(("state_snapshot", "prefix_blocks_cut"))}
    documents = len(cell.traffic["documents"]["lengths"])
    snaps["requests_started"] = int(run_.counters["requests_started"])
    run_.notes["snapshots"] = snaps
    # every request of the load that was ADMITTED started from a restored
    # snapshot, and no hit lost blocks to a missing one (the documents'
    # own builds restore nothing; the close may cut each client's last
    # request before its admission)
    run_.correct = bool(
        run_.correct and snaps["prefix_blocks_cut"] == 0
        and snaps["state_snapshots_restored"] >= (
            snaps["requests_started"] - int(cell.traffic["clients"]))
        and snaps["state_snapshots_saved"] >= documents)
    return run_
