"""SPMD pipeline parallelism (GPipe schedule) over a 'pp' mesh axis.

The reference's only pipeline-ish facility is per-layer device placement in
the legacy engine (ParallelNeuralNetwork,
/root/reference/paddle/gserver/gradientmachines/ParallelNeuralNetwork.h —
layers annotated with deviceId run on different GPUs).  The TPU rebuild
expresses the same capability the XLA way: every pipeline stage runs the
SAME traced computation under `shard_map`, each device holds only its
stage's parameters (a stacked pytree sharded on the leading axis), and
activations hop stage->stage with one `lax.ppermute` (one ICI hop) per
schedule tick.  Two schedules:

  * `spmd_pipeline` (GPipe): forward scan; JAX's autodiff derives the
    reverse pipeline automatically.  Fewest steps, but the scan buffers
    residuals for every tick — activation memory grows with n_micro.
  * `spmd_pipeline_1f1b`: forward and backward microbatches interleave
    in ONE scan with vjp residuals in an O(pp) ring buffer — flat
    activation memory for long n_micro (docs/design/parallelism.md has
    the measured table and the schedule math).

Constraints (documented, checked): every stage maps activations of one
fixed shape to the same shape — put embedding/classifier layers outside
the pipelined trunk (the usual GPipe decomposition).  GPipe bubble
fraction is (pp-1)/(n_micro+pp-1), so use n_micro >= ~4*pp for real
runs; `bubble_fraction` covers both schedules.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import shard_map

__all__ = ["spmd_pipeline", "spmd_pipeline_1f1b", "stack_stage_params",
           "microbatch", "unmicrobatch", "schedule_steps",
           "bubble_fraction"]


def stack_stage_params(per_stage: Sequence[Any]):
    """Stack a list of per-stage parameter pytrees along a new leading
    axis (to be sharded over the pp mesh axis)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage)


def microbatch(x, n_micro: int):
    """[batch, ...] -> [n_micro, batch/n_micro, ...]"""
    if x.shape[0] % n_micro:
        raise ValueError(
            f"batch {x.shape[0]} not divisible by n_micro {n_micro}")
    return x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])


def unmicrobatch(y):
    """[n_micro, mb, ...] -> [n_micro*mb, ...]"""
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])


def spmd_pipeline(stage_fn: Callable, stage_params, x, mesh: Mesh,
                  axis: str = "pp", batch_axis: str | None = None,
                  param_specs=None, auto_axes: Sequence[str] = (),
                  seq_axis: str | None = None, with_tick: bool = False):
    """Run `stage_fn` as a `pp`-stage GPipe pipeline.

    stage_fn:     (params, activation[mb, ...]) -> activation[mb, ...]
                  (same callable for every stage; per-stage behavior comes
                  from the per-stage params)
    stage_params: pytree whose leaves are stacked [pp, ...] per-stage
                  parameters (see stack_stage_params)
    x:            [n_micro, mb, ...] microbatched input (see microbatch)
    batch_axis:   optional mesh axis to shard the microbatch dim over
                  (dp x pp composition: each dp replica pipelines its own
                  batch shard; param grads psum over dp automatically in
                  shard_map's backward)
    param_specs:  optional pytree of PartitionSpecs (matching
                  stage_params' structure, or a single spec) whose FIRST
                  entry must be `axis` — lets stage weights also shard
                  over a tensor-parallel mesh axis (dp x pp x tp
                  composition); the stage_fn is then responsible for the
                  tp collectives (e.g. psum over 'tp' after a
                  row-parallel matmul).  Default: P(axis) on every leaf.
    auto_axes:    mesh axes left OUT of shard_map's manual set: arrays
                  keep (and propagate) GSPMD shardings over them inside
                  the stage body, so a tensor-parallel axis needs no
                  hand-written collectives at all — annotate the stacked
                  params' non-leading dims with the axis (NamedSharding
                  at device_put) and XLA inserts the Megatron psum where
                  sharding propagation demands it.  This is how
                  PipelineExecutor composes tp with a generic op-lowering
                  stage body (manual specs can't: op lowerings see global
                  shapes).  param_specs then must reference only manual
                  axes (pass the default P(axis)).
    seq_axis:     optional manual mesh axis to shard the activations'
                  dim 2 (the sequence dim of a [n_micro, mb, S, ...]
                  stream) — sequence parallelism; the stage body then
                  runs on local sequence blocks and its attention op must
                  use ring collectives over this axis (the
                  flash_attention lowering does when the ExecContext
                  carries sp_axis).
    returns:      [n_micro, mb, ...] last-stage outputs (sharded over
                  `batch_axis`/`seq_axis` if given, otherwise replicated).

    Differentiable end-to-end: grad through this function yields the
    reverse pipeline schedule, with per-stage param grads sharded exactly
    like the params.  During the pp-1 fill/drain bubble ticks stages run
    on recirculated real microbatch data (never synthetic zeros), so a
    stage_fn that divides by activation statistics stays NaN-free.
    """
    pp = mesh.shape[axis]
    n_micro = x.shape[0]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != pp:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != pipeline "
                f"axis size {pp}: one stacked stage per '{axis}' device "
                "(a mismatch would silently drop stages)")
    if seq_axis:
        x_spec = P(None, batch_axis, seq_axis)
    else:
        x_spec = P(None, batch_axis) if batch_axis else P()
    if param_specs is None:
        param_specs = P(axis)
    else:
        for spec in jax.tree_util.tree_leaves(
                param_specs, is_leaf=lambda s: isinstance(s, P)):
            if not len(spec) or spec[0] != axis:
                raise ValueError(
                    f"param_specs leaf {spec} must lead with the pipeline "
                    f"axis {axis!r} (stacked stage dim)")
    sm_kwargs = {}
    if auto_axes:
        manual = set(mesh.axis_names) - set(auto_axes)
        missing = set(auto_axes) - set(mesh.axis_names)
        if missing:
            raise ValueError(f"auto_axes {missing} not in mesh axes "
                             f"{mesh.axis_names}")
        for spec in jax.tree_util.tree_leaves(
                (param_specs, x_spec),
                is_leaf=lambda s: isinstance(s, P)):
            bad = set(spec) & set(auto_axes)
            if bad:
                raise ValueError(
                    f"spec {spec} references auto axis {bad}: auto-axis "
                    "sharding comes from the arrays' NamedShardings, not "
                    "from shard_map specs")
        sm_kwargs["axis_names"] = manual

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec, **sm_kwargs)
    def _run(params_blk, xs):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_blk)
        # drain ticks recirculate real data (see docstring); their outputs
        # are sliced away below
        pad = jnp.broadcast_to(xs[:1], (pp - 1,) + xs.shape[1:])
        stream = jnp.concatenate([xs, pad], axis=0)
        state0 = jax.lax.stop_gradient(xs[0])
        state0 = jax.lax.pcast(state0, (axis,), to="varying")

        def tick(state, xt_t):
            xt, t = xt_t
            # stage 0 ingests from the stream; others from the neighbor
            inp = jnp.where(stage == 0, xt, state)
            # with_tick: stage_fn(params, x, tick_index) — the schedule
            # position, from which a stage derives its microbatch index
            # (t - stage) for e.g. per-microbatch PRNG offsets
            out = (stage_fn(params_local, inp, t) if with_tick
                   else stage_fn(params_local, inp))
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % pp) for i in range(pp)])
            return nxt, out

        _, ys = jax.lax.scan(
            tick, state0,
            (stream, jnp.arange(stream.shape[0], dtype=jnp.int32)))
        # keep only the last stage's real emissions (drop the pp-1 warm-up
        # ticks BEFORE the psum so bubble outputs never cross the ICI),
        # then psum over the (otherwise-zero) mask to replicate them
        ys = jax.lax.slice_in_dim(ys, pp - 1, pp - 1 + n_micro, axis=0)
        mask = (stage == pp - 1).astype(ys.dtype)
        return jax.lax.psum(ys * mask, axis)

    return _run(stage_params, x)


# ---------------------------------------------------------------------------
# 1F1B schedule
# ---------------------------------------------------------------------------

def schedule_steps(n_micro: int, pp: int, schedule: str = "gpipe") -> int:
    """Schedule ticks holding one stage-computation each.  GPipe runs
    n_micro+pp-1 forward ticks (autodiff mirrors them backward); the
    lockstep 1F1B below runs n_micro+2pp-1 combined fwd+bwd steps."""
    if schedule == "gpipe":
        return n_micro + pp - 1
    if schedule == "1f1b":
        return n_micro + 2 * pp - 1
    raise ValueError(f"unknown schedule {schedule!r}")


def bubble_fraction(n_micro: int, pp: int, schedule: str = "gpipe") -> float:
    """Fraction of schedule steps a stage spends idle.  gpipe:
    (pp-1)/(n_micro+pp-1); 1f1b: (2pp-1)/(n_micro+2pp-1) — the lockstep
    SPMD 1F1B pays pp extra steps for its O(pp) activation memory (GPipe
    autodiff buffers residuals for all n_micro+pp-1 ticks)."""
    total = schedule_steps(n_micro, pp, schedule)
    return (total - n_micro) / total


def spmd_pipeline_1f1b(stage_fn: Callable, last_fn: Callable,
                       stage_params, last_params, x, y, mesh: Mesh,
                       axis: str = "pp", batch_axis: str | None = None,
                       auto_axes: Sequence[str] = (),
                       seq_axis: str | None = None,
                       with_tick: bool = False):
    """One-scan 1F1B training schedule: every scan step runs one forward
    sub-tick AND one backward sub-tick, with per-microbatch vjp residuals
    held in a ring buffer of depth 2*pp — activation memory is O(pp)
    in-flight microbatches instead of GPipe-autodiff's O(n_micro+pp)
    buffered ticks.  The price on a lockstep SPMD backend is pp extra
    schedule steps (see bubble_fraction); 1F1B here is the long-n_micro /
    tight-HBM configuration, GPipe the low-latency one.

    stage_fn:    (params, h[, tick]) -> h  (spmd_pipeline contract; tick
                 is the global fwd sub-tick index when with_tick)
    last_fn:     (last_params, h_mb, y_mb, m) -> scalar loss CONTRIBUTION
                 of microbatch m (callers targeting a batch-mean loss
                 scale by 1/n_micro inside); runs on the LAST stage right
                 after its forward — its vjp seeds the backward wave.
    stage_params: stacked [pp, ...] pytree (stack_stage_params)
    last_params:  pytree, replicated
    x:           [n_micro, mb, ...] trunk inputs
    y:           pytree with leading [n_micro, ...] (labels etc.)
    returns (loss_sum, outs, stage_grads, last_grads, dx):
      loss_sum    sum of last_fn over microbatches (replicated)
      outs        [n_micro, mb, ...] last-stage forward outputs
      stage_grads stacked like stage_params
      last_grads  like last_params (replicated)
      dx          [n_micro, mb, ...] cotangents w.r.t. x

    Schedule (stage s, microbatch m, step t): forward at t = s + m (as
    GPipe); backward at t = m + 2pp - 1 - s; the last stage's loss vjp
    seed is produced one step before its backward consumes it.
    Activations hop forward and cotangents hop backward with one
    ppermute each per step.
    """
    pp = mesh.shape[axis]
    n_micro = x.shape[0]
    T = schedule_steps(n_micro, pp, "1f1b")
    BUF = 2 * pp
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != pp:
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != pipeline "
                f"axis size {pp}")
    if seq_axis:
        x_spec = P(None, batch_axis, seq_axis)
    else:
        x_spec = P(None, batch_axis) if batch_axis else P()
    # y streams ride exactly with the trunk activations' sharding: under
    # sp every leaf must be [n_micro, mb, S, ...] with S the trunk's
    # seq dim (the executor validates this before choosing sp + 1f1b)
    y_spec = x_spec
    sm_kwargs = {}
    if auto_axes:
        sm_kwargs["axis_names"] = set(mesh.axis_names) - set(auto_axes)
    other_axes = tuple(a for a in (batch_axis, seq_axis) if a)

    # pad streams to T steps: x consumed by stage 0 at t = m; y consumed
    # by the last stage at t = pp - 1 + m (real data recirculates into
    # the masked ticks, keeping every traced computation finite)
    def pad_to(stream, lead):
        def pad_leaf(l):
            reps = [l[:1]] * lead + [l] + [l[:1]] * (T - lead - n_micro)
            return jnp.concatenate(reps, axis=0)
        return jax.tree_util.tree_map(pad_leaf, stream)

    x_stream = pad_to(x, 0)
    y_stream = pad_to(y, pp - 1)

    def c_psum(tree, axes):
        if not axes:
            return tree
        return jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, axes), tree)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(), x_spec, y_spec),
        out_specs=(P(), x_spec, P(axis), P(), x_spec), **sm_kwargs)
    def _run(params_blk, last_p, xs, ys_lab):
        stage = jax.lax.axis_index(axis)
        is_last = stage == pp - 1
        is_first = stage == 0
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_blk)
        if other_axes:
            # same invariant-diff hazard as last_p below: stage params
            # are replicated over dp/sp, so keep their grads per-device
            # local and do the one explicit psum at the end
            params_local = jax.tree_util.tree_map(
                lambda p: jax.lax.pcast(p, other_axes, to="varying"),
                params_local)
        # last_p arrives INVARIANT over the manual axes; differentiating
        # w.r.t. an invariant value makes the vjp transpose insert an
        # implicit psum (the transpose of the invariant->varying
        # broadcast), which would sum every device's masked-out garbage
        # gradient into each step.  Cast to varying first: grads stay
        # per-device local and the single masked psum at the end is the
        # only cross-device reduction.
        last_p_v = jax.tree_util.tree_map(
            lambda l: jax.lax.pcast(l, (axis,) + other_axes,
                                    to="varying"), last_p)

        def fwd_vjp(h, t):
            if with_tick:
                out, vjp_fn = jax.vjp(
                    lambda p, hh: stage_fn(p, hh, t), params_local, h)
            else:
                out, vjp_fn = jax.vjp(stage_fn, params_local, h)
            leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
            return out, leaves, treedef

        def last_vjp(h, yb, m):
            loss, vjp_fn = jax.vjp(
                lambda lp, hh: last_fn(lp, hh, yb, m), last_p_v, h)
            g_last, d_h = vjp_fn(jnp.ones_like(loss))
            return loss, g_last, d_h

        # prime the residual buffer with ONE real vjp (structure + finite
        # values for the masked early backward ticks)
        h0 = jax.lax.stop_gradient(xs[0])
        h0 = jax.lax.pcast(h0, (axis,), to="varying")
        out0, leaves0, treedef = fwd_vjp(h0, 0)
        res_buf0 = [jnp.broadcast_to(l, (BUF,) + l.shape) for l in leaves0]
        zeros_g = jax.tree_util.tree_map(jnp.zeros_like, params_local)
        zeros_gl = jax.tree_util.tree_map(jnp.zeros_like, last_p_v)

        carry0 = dict(
            fwd_state=out0 * 0.0,
            bwd_state=out0 * 0.0,
            seed=out0 * 0.0,
            res_buf=res_buf0,
            g_stage=zeros_g,
            g_last=zeros_gl,
            loss=jax.lax.pcast(jnp.zeros((), jnp.float32),
                               (axis,) + other_axes, to="varying"),
        )

        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
        bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]

        def step(c, xt):
            xb, yb, t = xt
            # ---- forward sub-tick: m_f = t - stage -------------------
            m_f = t - stage
            f_valid = (m_f >= 0) & (m_f < n_micro)
            inp = jnp.where(is_first, xb, c["fwd_state"])
            out, leaves, _ = fwd_vjp(inp, t)
            slot_f = jnp.clip(m_f, 0, n_micro - 1) % BUF
            res_buf = [
                jnp.where(
                    f_valid,
                    jax.lax.dynamic_update_index_in_dim(
                        buf, l, slot_f, 0),
                    buf)
                for buf, l in zip(c["res_buf"], leaves)]
            # last stage: loss + seed for its own backward next step
            loss_m, g_last_m, d_seed = last_vjp(out, yb, jnp.clip(
                m_f, 0, n_micro - 1))
            take = f_valid & is_last
            loss = c["loss"] + jnp.where(take, loss_m, 0.0)
            g_last = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(take, g, 0.0),
                c["g_last"], g_last_m)
            seed = jnp.where(take, d_seed, c["seed"] * 0.0)

            # ---- backward sub-tick: m_b = t + stage - (2pp - 1) ------
            m_b = t + stage - (2 * pp - 1)
            b_valid = (m_b >= 0) & (m_b < n_micro)
            slot_b = jnp.clip(m_b, 0, n_micro - 1) % BUF
            leaves_b = [
                jax.lax.dynamic_index_in_dim(buf, slot_b, 0,
                                             keepdims=False)
                for buf in res_buf]
            vjp_fn = jax.tree_util.tree_unflatten(treedef, leaves_b)
            ct = jnp.where(is_last, c["seed"], c["bwd_state"])
            g_p, d_h = vjp_fn(ct)
            g_stage = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(b_valid, g, 0.0),
                c["g_stage"], g_p)
            d_h = jnp.where(b_valid, d_h, 0.0)

            # ---- hops -----------------------------------------------
            nxt_fwd = jax.lax.ppermute(out, axis, fwd_perm)
            nxt_bwd = jax.lax.ppermute(d_h, axis, bwd_perm)
            c2 = dict(fwd_state=nxt_fwd, bwd_state=nxt_bwd, seed=seed,
                      res_buf=res_buf, g_stage=g_stage, g_last=g_last,
                      loss=loss)
            # emit: last-stage fwd outputs and first-stage dx
            return c2, (jnp.where(is_last & f_valid, out, 0.0),
                        jnp.where(is_first & b_valid, d_h, 0.0))

        ticks = jnp.arange(T, dtype=jnp.int32)
        cN, (ys_out, ys_dx) = jax.lax.scan(
            step, carry0, (xs, ys_lab, ticks))

        outs = jax.lax.psum(
            jax.lax.slice_in_dim(ys_out, pp - 1, pp - 1 + n_micro, axis=0),
            axis)
        dx = jax.lax.psum(
            jax.lax.slice_in_dim(ys_dx, 2 * pp - 1,
                                 2 * pp - 1 + n_micro, axis=0),
            axis)
        # stage grads: sum over replicas (params replicated over dp/sp),
        # re-stack over the pipeline axis via out_specs
        g_stage = c_psum(cN["g_stage"], other_axes)
        g_stage = jax.tree_util.tree_map(lambda g: g[None], g_stage)
        # last_fn grads + loss live on the last stage only
        mask = (stage == pp - 1).astype(jnp.float32)
        g_last = c_psum(
            jax.tree_util.tree_map(lambda g: g * mask, cN["g_last"]),
            (axis,) + other_axes)
        # NOTE on dp/sp: each replica accumulated loss / last-grads on its
        # OWN batch (or sequence) shard, so the psum over other_axes above
        # and here SUMS the shard contributions — last_fn must therefore
        # return a contribution normalized over the GLOBAL batch (e.g.
        # sum over its local rows / total_batch for a batch-mean loss)
        loss = jax.lax.psum(cN["loss"] * mask, (axis,) + other_axes)
        return loss, outs, g_stage, g_last, dx

    return _run(stage_params, last_params, x_stream, y_stream)
