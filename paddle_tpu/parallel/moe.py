"""Mixture-of-Experts with expert parallelism over an 'ep' mesh axis.

No reference analogue — the reference's closest machinery is the sparse
remote embedding (SURVEY.md §2.5: rows live on pservers, prefetched by
id).

Three execution forms share one gating implementation (`moe_gate`,
GShard/Switch dispatch-combine tensors, top-1 or top-2, static capacity,
fully differentiable — one-hot matmuls, no gathers on the backward
path):

  * `moe_dense(x, ...)` — mesh-free math: gating + batched expert
    matmuls as plain einsums.  This is what the DSL `layers.moe_ffn` op
    lowers to (single device or XLA-partitioned under ParallelExecutor
    with `param_shardings={w_in: P('ep'), ...}`), and the oracle the
    parallel forms are tested against.
  * `moe_ffn(x, ..., mesh)` — replicated routing, shard_map'd experts:
    the [T,E,C] dispatch/combine tensors materialize on every device
    (cheap at moderate T·E·C); only the [E,...] expert buffers are
    sharded.  Good when tokens-per-device is small.
  * `moe_ffn_a2a(x, ..., mesh)` — token-sharded routing with
    all_to_all dispatch (the GShard layout): each device gates its OWN
    T/n tokens, builds per-source capacity buffers [E, C_loc, D], and
    one all_to_all regroups them expert-major so each device runs its
    E/n experts on tokens from every source; a second all_to_all
    returns the outputs.  Memory per device is O(T/n · E · C_loc) —
    this is the form that scales T with the mesh.

Capacity semantics differ between the last two (global vs per-source
capacity) exactly as in GShard; with a non-overflowing capacity_factor
they are numerically identical (pinned in tests/test_moe.py).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import shard_map

__all__ = ["moe_gate", "moe_dense", "moe_ffn", "moe_ffn_a2a",
           "load_balance", "drop_rate"]


def moe_gate(x, gate_w, num_experts: int, capacity: int, top_k: int = 1):
    """Top-1 (Switch) or top-2 (GShard) gating.  x: [T, D]; gate_w: [D, E].

    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights,
    aux_loss scalar).  For top-2 the two gate values are renormalized to
    sum to 1 and second choices claim capacity only after ALL first
    choices (GShard's position rule), so a hot expert drops second
    choices first."""
    if top_k not in (1, 2):
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    logits = x @ gate_w                                  # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)                    # [T]
    mask1 = jax.nn.one_hot(idx1, num_experts, dtype=jnp.float32)
    g1 = jnp.sum(probs * mask1, axis=-1)

    # position of each token within its expert's capacity buffer
    pos1 = jnp.sum((jnp.cumsum(mask1, axis=0) - 1.0) * mask1, axis=-1)
    keep1 = (pos1 < capacity).astype(jnp.float32)
    pos1_1h = jax.nn.one_hot(pos1.astype(jnp.int32), capacity,
                             dtype=jnp.float32)
    d1 = mask1[:, :, None] * pos1_1h[:, None, :] * keep1[:, None, None]

    # load-balance aux loss (Switch eq. 4): E * sum_e f_e * p_e, with
    # f_e the fraction of tokens whose FIRST choice is e
    frac_tokens = jnp.mean(mask1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)

    if top_k == 1:
        return d1, d1 * g1[:, None, None], aux

    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, num_experts, dtype=jnp.float32)
    g2 = jnp.sum(probs * mask2, axis=-1)
    # second choices are placed after every first choice of that expert
    first_count = jnp.sum(mask1, axis=0)                 # [E]
    pos2 = jnp.sum(((jnp.cumsum(mask2, axis=0) - 1.0)
                    + first_count[None, :]) * mask2, axis=-1)
    keep2 = (pos2 < capacity).astype(jnp.float32)
    pos2_1h = jax.nn.one_hot(pos2.astype(jnp.int32), capacity,
                             dtype=jnp.float32)
    d2 = mask2[:, :, None] * pos2_1h[:, None, :] * keep2[:, None, None]

    denom = jnp.maximum(g1 + g2, 1e-9)
    combine = (d1 * (g1 / denom)[:, None, None]
               + d2 * (g2 / denom)[:, None, None])
    return d1 + d2, combine, aux


def _capacity(T: int, E: int, capacity_factor: float, top_k: int) -> int:
    return max(1, int(capacity_factor * top_k * T / E))


def _expert_mm(inp, wi, wo, activation):
    """[*, C, D] tokens through per-expert FFNs [*, D, H] / [*, H, D] —
    batched dense matmuls -> MXU."""
    h = activation(jnp.einsum("...cd,...dh->...ch", inp, wi))
    return jnp.einsum("...ch,...hd->...cd", h, wo)


def moe_dense(x, gate_w, w_in, w_out, capacity_factor: float = 1.25,
              top_k: int = 1, activation=jax.nn.relu,
              capacity: int = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mesh-free MoE FFN: the math every parallel form implements.
    x: [T, D]; returns (y [T, D], aux_loss)."""
    E = gate_w.shape[1]
    T = x.shape[0]
    if capacity is None:
        capacity = _capacity(T, E, capacity_factor, top_k)
    dispatch, combine, aux = moe_gate(x, gate_w, E, capacity, top_k)
    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                           dispatch).astype(x.dtype)
    expert_out = _expert_mm(expert_in, w_in, w_out, activation)
    y = jnp.einsum("ecd,tec->td", expert_out.astype(jnp.float32),
                   combine).astype(x.dtype)
    return y, aux


def moe_ffn(x, gate_w, w_in, w_out, mesh: Mesh, axis: str = "ep",
            capacity_factor: float = 1.25, top_k: int = 1,
            activation=jax.nn.relu) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel FFN, replicated routing (see module docstring).

    x: [T, D], gate_w: [D, E], w_in: [E, D, H], w_out: [E, H, D] with E
    divisible by the 'ep' axis size.  Returns (y [T, D], aux_loss)."""
    E = gate_w.shape[1]
    n = mesh.shape[axis]
    assert E % n == 0, f"experts {E} must divide ep axis {n}"
    T = x.shape[0]
    capacity = _capacity(T, E, capacity_factor, top_k)

    dispatch, combine, aux = moe_gate(x, gate_w, E, capacity, top_k)
    expert_in = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                           dispatch).astype(x.dtype)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis))
    def _experts(inp, wi, wo):
        return _expert_mm(inp, wi, wo, activation)

    expert_out = _experts(expert_in, w_in, w_out)        # [E, C, D]
    y = jnp.einsum("ecd,tec->td", expert_out.astype(jnp.float32),
                   combine).astype(x.dtype)
    return y, aux


def moe_ffn_a2a(x, gate_w, w_in, w_out, mesh: Mesh, axis: str = "ep",
                capacity_factor: float = 1.25, top_k: int = 1,
                activation=jax.nn.relu) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel FFN with token-sharded routing + all_to_all
    dispatch (the GShard layout; see module docstring).

    x: [T, D] with T divisible by the axis size; capacity is per
    (expert, source-shard): C_loc = capacity_factor * top_k * (T/n) / E,
    so a hot expert drops per-shard overflow locally before anything
    crosses the ICI.  Returns (y [T, D], mean aux_loss)."""
    E = gate_w.shape[1]
    n = mesh.shape[axis]
    assert E % n == 0, f"experts {E} must divide ep axis {n}"
    T = x.shape[0]
    assert T % n == 0, f"tokens {T} must divide ep axis {n}"
    c_loc = _capacity(T // n, E, capacity_factor, top_k)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P(axis)),
        out_specs=(P(axis), P()))
    def _run(x_blk, gw, wi, wo):
        dispatch, combine, aux = moe_gate(x_blk, gw, E, c_loc, top_k)
        # local capacity buffers per expert: [E, C_loc, D]
        buf = jnp.einsum("td,tec->ecd", x_blk.astype(jnp.float32),
                         dispatch).astype(x_blk.dtype)
        # all_to_all: split the expert dim across devices, concat the
        # source dim -> [E/n, n*C_loc, D] on each device
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)
        out = _expert_mm(buf, wi, wo, activation)
        # route outputs back to their source shards
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=True)                 # [E, C_loc, D]
        y = jnp.einsum("ecd,tec->td", out.astype(jnp.float32),
                       combine).astype(x_blk.dtype)
        return y, jax.lax.pmean(aux, axis)

    return _run(x, gate_w, w_in, w_out)


def load_balance(x, gate_w) -> dict:
    """Routing diagnostics: per-expert first-choice token fractions and
    their max/mean ratio (1.0 = perfectly balanced)."""
    probs = jax.nn.softmax((x @ gate_w).astype(jnp.float32), axis=-1)
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, -1),
                                   gate_w.shape[1]), axis=0)
    return {"frac": frac, "imbalance": jnp.max(frac) * gate_w.shape[1]}


def drop_rate(x, gate_w, capacity_factor: float = 1.25, top_k: int = 1,
              capacity: int = None, shards: int = 1) -> dict:
    """What static capacity actually costs at this routing state.

    An imbalanced router (load_balance imbalance > 1) overflows its hot
    experts' capacity buffers and the overflow tokens are DROPPED
    (their expert output is zero; the residual stream carries them) —
    the metric no artifact reported before r5.  Returns:
      assignment_drop  fraction of the T*top_k routing assignments that
                       lost their capacity slot
      weight_drop      fraction of total combine WEIGHT lost (second
                       choices carry less gate weight, so this is the
                       output-relevant number)
    `shards` > 1 evaluates per-source capacity (the moe_ffn_a2a layout:
    C_loc per shard, hot-expert overflow drops locally)."""
    E = gate_w.shape[1]
    T = x.shape[0]
    assert T % shards == 0, f"tokens {T} must divide shards {shards}"
    t_loc = T // shards
    cap = (_capacity(t_loc, E, capacity_factor, top_k)
           if capacity is None else capacity)
    assigned = kept = weight = weight_kept = 0.0
    for s in range(shards):
        xb = x[s * t_loc:(s + 1) * t_loc]
        dispatch, combine, _ = moe_gate(xb, gate_w, E, cap, top_k)
        probs = jax.nn.softmax((xb @ gate_w).astype(jnp.float32), -1)
        top = jax.lax.top_k(probs, top_k)[0]
        if top_k == 2:
            top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
        assigned += t_loc * top_k
        kept += jnp.sum(dispatch)
        weight += jnp.sum(top)
        weight_kept += jnp.sum(combine)
    return {"capacity": cap,
            "assignment_drop": float(1.0 - kept / assigned),
            "weight_drop": float(1.0 - weight_kept / weight)}
