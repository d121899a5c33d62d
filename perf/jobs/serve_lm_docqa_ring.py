"""Job `serve_lm_docqa_ring`: `serve_lm_docqa`'s document sessions for a
block whose SLIDING layers keep a RING of latent rows a lane beside the
full layers' selected latent table (`BlockSpec.sliding_kv_lora_rank`,
lm_block's thirteenth description) under the PREFIX CACHE, whose hits
start a lane from a SNAPSHOT of its rings
(`GenerationServer(state_snapshots=)`).  `serve_lm_docqa.run` is what
runs: the documents built in set-up, `DocLoad`, the ramp, the window and
its accounting, `check_served` (delivered tokens of requests on two
documents against the reference, every one of them served from a
restored ring), `warm_served`, `make_weights`, `balance` (the sigmoid
router's choice bias fitted from float32 zeros by
`serve_lm_balanced.fit`'s sign rule) and the comparison before the
window (`serve_lm_ring.check_against_reference` under
`serve_lm_latent.warm_reference`) are imported UNEDITED, as are
`serve_lm_docqa_state.build_server` (the server with `state_snapshots`
from the traffic file) and `serve_lm_state.hbm_marks`.

Replaced in those modules before `serve_lm_docqa.run` runs, because they
assume ONE table and no ring:

  `serve_lm_latent.system_outputs` (its lines 99 to 128, which
        `serve_lm_docqa.run` hands to the comparison)  there `step` is
        given one table and pools made without a ring; here
        `serve_lm_ring.system_outputs`' walk (its lines 79 to 111: the
        pair (tables, rings) and `window_blocks`) on the ONE pool shape
        of the walks before the window, and after the walk lane 0's
        TABLE ROWS of the full layers and RING BLOCKS of the sliding
        ones read from the pools, for the reference's `latent_rms_err`
        and `ring_rms_err`.
  `serve_lm_latent_bias.router_probs` (its lines 97 to 126, which
        `serve_lm_docqa.balance` walks the fit's tokens through)  there
        one table; here `serve_lm_balanced.router_scores` (its lines 70
        to 104), the same walk with a ring a lane, whose sigmoid scores
        are what `serve_lm_docqa.sigmoid_of` would have made of its
        inputs.
  `serve_lm_docqa.build_server` (its lines 184 to 227)  there the
        server is made without snapshots; here
        `serve_lm_docqa_state.build_server` (its lines 173 to 221),
        unedited.

After the run the notes gain the cache's snapshot counts, as
`serve_lm_docqa_state.run`'s (its lines 224 to 246), and `correct` also
needs every admitted request of the load to have restored a snapshot and
no hit to have been cut back for want of one.
"""
from __future__ import annotations

import os
import types

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
docqa = common.load_module(os.path.join(_HERE, "serve_lm_docqa.py"))
docqa_state = common.load_module(os.path.join(_HERE,
                                              "serve_lm_docqa_state.py"))
ring = common.load_module(os.path.join(_HERE, "serve_lm_ring.py"))
balanced = common.load_module(os.path.join(_HERE, "serve_lm_balanced.py"))
latent, latent_bias = docqa.latent, docqa.latent_bias


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_ring.system_outputs` on the walks' one pool shape
    (`serve_lm_balanced.router_scores`'): `toks` through the step AS THE
    SERVER RUNS IT, `slots` lanes, the sequence in lane 0 with its table
    blocks and its ring, the other lanes idle.  -> ([positions, vocab]
    logits, the routing of every position stacked on axis 1, and under
    "latent_rows" and "ring_rows" what lane 0's table blocks (a full
    layer a row of the array) and ring blocks (a sliding layer) hold
    after the last position)."""
    import jax

    n, nw = len(toks), dec.window_blocks_per_seq
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(
        latent.walk_blocks(dec, slots, n) + 1, jax.devices()[0],
        window_blocks=slots * nw + 1)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    rings = dec.slot_rings(slots)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.arange(slots) == 0
    got, routed = [], []
    for pos in range(n):
        args = (g, pool_k, pool_v, (tables, rings),
                np.where(act, pos, 0).astype(np.int32),
                np.where(act, toks[pos], 0).astype(np.int32), zs, zt, act)
        logits, routing = dec.step_routing(*args)
        routed.append({k: v[:, :1] for k, v in routing.items()})
        got.append(logits[:1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    table, ring_ = pool_k
    out = {k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
           for k in routed[0]}
    out["latent_rows"] = np.asarray(
        table[:, 1:1 + need], np.float32).reshape(table.shape[0], -1,
                                                  table.shape[-1])
    out["ring_rows"] = np.asarray(
        ring_[:, rings[0, 0]:rings[0, 0] + nw], np.float32).reshape(
            ring_.shape[0], -1, ring_.shape[-1])
    return np.concatenate([np.asarray(x) for x in got]), out


def router_probs(dec, g, routers, toks, slots: int, blocks: int, probs_of):
    """`serve_lm_balanced.router_scores`, under the signature
    `serve_lm_docqa.balance` calls: the sigmoid scores of every sparse
    layer over `toks` walked through table AND rings (`blocks` and
    `probs_of` are the one-table walk's: the ringed walk sizes its own
    pools and makes the same sigmoid)."""
    return balanced.router_scores(dec, g, routers, toks, slots)


def run(cell):
    latent.system_outputs = system_outputs
    latent_bias.router_probs = router_probs
    docqa.build_server = docqa_state.build_server
    marks = types.SimpleNamespace(notes={})
    docqa_state.state.hbm_marks(cell, marks)
    run_ = docqa.run(cell)
    run_.notes.update(marks.notes)
    snaps = {k: v
             for k, v in docqa_state._built["cache"].prefix_stats().items()
             if k.startswith(("state_snapshot", "prefix_blocks_cut"))}
    documents = len(cell.traffic["documents"]["lengths"])
    snaps["requests_started"] = int(run_.counters["requests_started"])
    run_.notes["snapshots"] = snaps
    # every request of the load that was ADMITTED started from a restored
    # ring, and no hit lost blocks to a missing snapshot
    run_.correct = bool(
        run_.correct and snaps["prefix_blocks_cut"] == 0
        and snaps["state_snapshots_restored"] >= (
            snaps["requests_started"] - int(cell.traffic["clients"]))
        and snaps["state_snapshots_saved"] >= documents)
    return run_
