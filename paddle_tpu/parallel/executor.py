"""ParallelExecutor: SPMD execution of a Program over a device mesh.

This one component replaces ALL of the reference's parallelism machinery
(SURVEY.md §2.5):
  * `parallel_do_op` worker threads + per-place scopes + grad sum
    (/root/reference/paddle/fluid/operators/parallel_do_op.cc:113-346)
    -> batch dp-sharded into one jit; XLA splits the work per device.
  * NCCL allreduce ops (operators/nccl_op.cu.cc, doc/design/paddle_nccl.md)
    -> the gradient all-reduce is inserted BY XLA's sharding propagation
    (replicated params x dp-sharded batch), riding ICI.
  * DistributeTranspiler + gRPC pserver (distribute_transpiler.py:133,
    operators/listen_and_serv_op.cc) -> `shard_optimizer_states=True`
    partitions optimizer accumulators across the mesh (the pserver
    block-shard analogue, ZeRO-1 numerics == sync pserver SGD), with
    reduce-scatter/all-gather chosen by the compiler.
  * MultiGradientMachine ring (gserver/gradientmachines/MultiGradientMachine.h)
    -> same allreduce, no hand-rolled ring.

Tensor-parallel layers: pass `param_shardings={param_name: PartitionSpec}`
to split weight matrices over a 'tp'/'mp' axis; activations follow by
propagation.
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, Optional, Sequence

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import profiler
from ..core.executor import (CPUPlace, Executor, end_run, program_to_fn,
                             run_clock)
from ..core.flags import get_flag, trace_flags
from ..core.framework import Variable, default_startup_program
from ..core.scope import Scope
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing
from .checkpoint import ShardedCheckpointMixin
from .mesh import make_mesh

# same series as core.executor's run histogram (get-or-create by name),
# with a "pe<N>" instance label and mode="parallel"
_PE_IDS = itertools.count()
_M_RUN_SECONDS = obs_metrics.histogram(
    "paddle_tpu_executor_run_seconds",
    "Executor.run wall latency by execution mode", ("exe", "mode"))


__all__ = ["ParallelExecutor", "DistributeTranspiler",
           "SimpleDistributeTranspiler", "ShardingTranspiler"]


class ParallelExecutor(ShardedCheckpointMixin):
    def __init__(
        self,
        program,
        feed_names: Sequence[str],
        fetch_list: Sequence,
        mesh,
        startup_program=None,
        batch_axis: str = "dp",
        param_shardings: Optional[Dict[str, P]] = None,
        shard_optimizer_states: bool = False,
        seed: int = 0,
        overlap: str = "off",
        spmd_plan=None,
    ):
        if isinstance(mesh, dict):
            mesh = make_mesh(mesh)
        if overlap not in ("off", "auto", "bucketed"):
            raise ValueError(
                f"overlap must be 'off', 'auto' or 'bucketed', got "
                f"{overlap!r}")
        self.mesh: Mesh = mesh
        self.batch_axis = batch_axis
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in fetch_list
        ]
        # PADDLE_TPU_VERIFY pre-flight, same contract as Executor.run
        # (gated inside preflight): a bad graph fails here in ms, not
        # minutes into the SPMD trace
        from ..analysis import preflight

        preflight(program, feed_names=self.feed_names,
                  fetch_names=self.fetch_names)
        # sharding annotations on the Program IR (layers.shard /
        # data(sharding=...)): complete them via the spmd propagation
        # and fold the derived placements under any explicit
        # param_shardings (explicit names win).  Unannotated programs
        # skip this entirely — plan stays None and the legacy defaults
        # (replicated params, batch-over-dp feeds) apply.
        from .spmd import (has_annotations, propagate_sharding,
                           spec_to_partition)

        blk0 = program.global_block()
        if spmd_plan is None and has_annotations(blk0):
            spmd_plan = propagate_sharding(
                program, mesh_axes={a: int(mesh.shape[a])
                                    for a in mesh.axis_names},
                batch_axis=batch_axis)
        self._spmd_plan = spmd_plan
        if spmd_plan is not None:
            spmd_plan.check()
            derived = {n: spec_to_partition(s)
                       for n, s in spmd_plan.param_specs.items()}
            derived.update(param_shardings or {})
            param_shardings = derived
        self._feed_specs = dict(spmd_plan.feed_specs) if spmd_plan \
            else {}
        self._fn = program_to_fn(program, self.feed_names, self.fetch_names)
        # explicit `donate=True` var hints fail HERE (build time) when
        # unsafe — e.g. a donated feed that is also fetched — not as a
        # deleted-buffer crash mid-train
        blk = program.global_block()
        hinted = [n for n in self.feed_names
                  if getattr(blk.vars.get(n), "donate", False)]
        if hinted:
            from ..memory_optimization_transpiler import plan_donation

            rw = [n for n in self._fn.state_in_names
                  if n in self._fn.state_out_names]
            plan_donation(program, self.feed_names, self.fetch_names,
                          state_rw_names=rw, requested=hinted).check()
        self._seed = seed
        self._step = 0
        self._clock = run_clock()
        param_shardings = dict(param_shardings or {})
        # kept for the overlap eligibility check: explicitly passed
        # placements must stand the overlap down exactly like derived
        # ones (the manual-dp shard_map would gather them)
        self._param_shardings = dict(param_shardings)

        # --- initialize states on host, then place with shardings ---------
        startup = startup_program or default_startup_program()
        scope = Scope()
        Executor(CPUPlace()).run(startup, scope=scope)

        param_names = {
            v.name for v in program.global_block().all_parameters()
        }
        self._state_shardings = {}
        states = {}
        for n in self._fn.state_in_names:
            val = scope.find_var(n)
            if val is None:
                raise RuntimeError(
                    f"state var {n!r} not produced by the startup program")
            spec = self._spec_for(n, np.asarray(val), param_names,
                                  param_shardings,
                                  shard_optimizer_states)
            sh = NamedSharding(self.mesh, spec)
            states[n] = jax.device_put(np.asarray(val), sh)
            self._state_shardings[n] = sh
        self._states = states

        data_sh = NamedSharding(self.mesh, P(self.batch_axis))
        self._data_sharding = data_sh
        # per-feed shardings: annotated feeds keep their spec (e.g. a
        # replicated lookup table fed alongside dp-sharded batches);
        # everything else gets the batch-over-dp default
        self._feed_shardings = {
            n: NamedSharding(self.mesh,
                             spec_to_partition(self._feed_specs[n]))
            for n in self.feed_names if n in self._feed_specs
        }

        fn = self._fn

        def step(feeds, states, key):
            fetches, new_states = fn(feeds, states, key)
            return fetches, new_states

        self._step_fn = step
        # compute/collective overlap (docs/performance.md "Multichip
        # sharding"): lower the step as shard_map over the dp axis with
        # the gradient all-reduce issued as size-capped bucketed psums,
        # so XLA's scheduler overlaps early buckets with the remaining
        # backward.  'auto' falls back to the GSPMD step (reason kept in
        # overlap_info) when the program shape rules it out; explicit
        # 'bucketed' raises instead.
        self.overlap_info = {"mode": "off",
                             "reason": "overlap='off' requested"}
        self._overlap_cfg = None
        if overlap != "off":
            cfg, reason = self._analyze_overlap(program, blk)
            if cfg is None:
                if overlap == "bucketed":
                    raise ValueError(
                        f"overlap='bucketed' is not applicable to this "
                        f"program: {reason}")
                self.overlap_info = {"mode": "off", "reason": reason}
            else:
                self._overlap_cfg = cfg
                self.overlap_info = {"mode": "bucketed"}
        self._jit_step = self._make_jit_step()
        self._hlo_registered = None    # the jit step hlo_scopes() knows
        self._trace_flags_state = trace_flags(parallel=True)

    def _make_jit_step(self):
        # donation plan (memory_optimization_transpiler via
        # program_to_fn): states are donated always — `run` rebinds
        # self._states to the returned dict, so the old buffers die with
        # the step (ZeRO-style in-place update).  Feed buffers (always
        # freshly device_put from host in `run`) join under the
        # memory_optimize flag when the plan covers every feed — jit
        # donation is per-argument, and a fetched feed must survive.
        donate = [1]
        plan = self._fn.donation_plan
        if get_flag("memory_optimize") and \
                set(self.feed_names) <= plan.feeds:
            donate.insert(0, 0)
        if self._overlap_cfg is not None:
            return self._make_overlap_step(tuple(donate))
        return jax.jit(
            self._step_fn,
            out_shardings=(None, self._out_state_shardings()),
            donate_argnums=tuple(donate),
        )

    # -- compute/collective overlap (bucketed grad all-reduce) --------------
    def _analyze_overlap(self, program, block):
        """Validate the program for the overlapped lowering and extract
        its structure.  Returns (cfg, None) or (None, reason).

        The overlapped step runs every op up to the first gradient
        consumer INSIDE a shard_map over the dp axis (each shard
        computes forward+backward on its local batch rows), reduces the
        parameter gradients with bucketed psums, and runs the update
        section (grad clip + optimizer ops) outside on the reduced
        values — numerically the serial program up to float
        associativity, because a mean loss over the global batch equals
        the pmean of per-shard local means."""
        from ..core import registry as op_registry
        from ..core.framework import (EMPTY_VAR_NAMES, Parameter,
                                      grad_var_name)

        ops = block.ops
        opt_ops = [op for op in ops
                   if "Param" in op.inputs and "ParamOut" in op.outputs]
        if not opt_ops:
            return None, ("no optimizer ops — the overlap lowers a "
                          "training step")
        # the reduction point is the first consumer of any RAW parameter
        # gradient — NOT the optimizer's Grad input, which may be a
        # clipped/regularized derivative of it: grad-clip (e.g.
        # global-norm) must see the REDUCED full-batch gradients, so
        # clip/regularizer ops belong to the update section
        all_produced = {n for op in ops for n in op.output_names()}
        grad_of = {}
        for v in block.vars.values():
            if isinstance(v, Parameter) and getattr(v, "trainable", True):
                g = grad_var_name(v.name)
                if g in all_produced:
                    grad_of[g] = v.name
        if not grad_of:
            return None, "no parameter gradients in the program"
        grad_names = set(grad_of)
        split = next((i for i, op in enumerate(ops)
                      if set(op.input_names()) & grad_names), None)
        if split is None:
            return None, "no op consumes the parameter gradients"
        produced = set()
        last_prod = {}
        for i, op in enumerate(ops[:split]):
            for n in op.output_names():
                produced.add(n)
                if n in grad_names:
                    last_prod[n] = i
        if not grad_names <= produced:
            missing = sorted(grad_names - produced)
            return None, (f"gradient(s) {missing} are produced after "
                          "their first consumer")
        if self._spmd_plan is not None and self._spmd_plan.model_axes:
            return None, (
                f"model-parallel placements over "
                f"{self._spmd_plan.model_axes} — the GSPMD step keeps "
                "them sharded; the manual-dp overlap would gather them")
        placed = sorted(n for n, s in self._param_shardings.items()
                        if s is not None and any(e is not None
                                                 for e in tuple(s)))
        if placed:
            return None, (
                f"explicit param_shardings on {placed} — the GSPMD "
                "step keeps them sharded; the manual-dp overlap would "
                "gather them")

        # the grad reduction is pmean (psum / dp), which equals the
        # serial gradient ONLY for a batch-MEAN loss (the book
        # convention; same assumption the 1F1B schedule documents) —
        # require the backward seed's loss var to come from a mean op
        from ..core.framework import GRAD_SUFFIX
        from .spmd import backward_start_index

        seed_idx = backward_start_index(block)
        if seed_idx >= split:
            return None, "no backward section (loss@GRAD seed) found"
        seed_out = ops[seed_idx].output_names()[0]
        loss_name = seed_out[:-len(GRAD_SUFFIX)]
        loss_var = block.vars.get(loss_name)
        if loss_var is None or loss_var.op is None or \
                loss_var.op.type != "mean":
            return None, (
                f"loss {loss_name!r} is not produced by a mean op — "
                "per-shard gradients averaged over dp only equal the "
                "serial gradient for a batch-mean loss")

        persistable = {v.name for v in program.list_vars()
                       if v.persistable}
        for i, op in enumerate(ops):
            if any(isinstance(v, dict) and "__block__" in v
                   for v in op.attrs.values()):
                return None, f"control-flow op {op.type!r} (sub-blocks)"
            try:
                info = op_registry.get_op_info(op.type)
            except KeyError:
                return None, f"unregistered op {op.type!r}"
            if info.host:
                return None, f"host op {op.type!r}"
            if info.random and not op.attrs.get("is_test", False):
                if i >= split:
                    # the update section runs under a different PRNG
                    # stream (fold_in(key, 1), indices restarting), so
                    # ANY stochastic op there diverges from serial
                    return None, (
                        f"stochastic op {op.type!r} in the update "
                        "section — its draws would differ from serial")
                if op.type != "dropout":
                    return None, (
                        f"stochastic op {op.type!r}: only dropout has "
                        "the batch-position-keyed PRNG that keeps "
                        "per-shard draws equal to serial")
            if i < split:
                if (op.type == "batch_norm"
                        and not op.attrs.get("is_test", False)):
                    return None, ("training-mode batch_norm couples "
                                  "rows across the dp shards")
                if any(n and n in persistable
                       for n in op.output_names()):
                    return None, (
                        f"op {op.type!r} writes persistable state "
                        "inside the sharded section")

        # the update section may read only persistables, the reduced
        # grads, and its own intermediates
        upd_prod = set()
        for op in ops[split:]:
            for n in op.input_names():
                if (not n or n in EMPTY_VAR_NAMES or n in grad_names
                        or n in upd_prod or n in persistable):
                    continue
                return None, (
                    f"update-section op {op.type} reads forward value "
                    f"{n!r} (e.g. a per-example regularizer input)")
            upd_prod.update(op.output_names())

        for n in self.feed_names:
            v = block.vars.get(n)
            if v is None:
                continue
            if v.lod_level:
                return None, f"LoD feed {n!r} (host-side metadata)"
            if not v.shape or v.shape[0] != -1:
                return None, f"feed {n!r} has no leading batch dim"
            spec = self._feed_specs.get(n)
            if spec is not None and (
                    not spec or spec[0] != self.batch_axis):
                return None, (
                    f"feed {n!r} is annotated {spec}, not sharded over "
                    f"the '{self.batch_axis}' batch axis")

        fetch_kinds = {}
        for n in self.fetch_names:
            if n not in produced:
                return None, (f"fetch {n!r} is produced by the update "
                              "section (not supported under overlap)")
            v = block.vars.get(n)
            if v is not None and v.shape and v.shape[0] == -1:
                fetch_kinds[n] = "batch"
                continue
            # non-batch fetches are combined by pmean over dp — only
            # correct for batch-mean quantities, so require a
            # mean-semantics producer
            if v is None or v.op is None or v.op.type not in (
                    "mean", "accuracy"):
                return None, (
                    f"fetch {n!r} is not a per-row output or a batch "
                    "mean — its per-shard values cannot be combined")
            fetch_kinds[n] = "mean"

        inside_state = sorted({
            n for op in ops[:split] for n in op.input_names()
            if n in persistable})
        grad_order = sorted(grad_names, key=lambda g: last_prod[g])
        grad_meta = []
        for g in grad_order:
            pv = block.vars.get(grad_of[g])
            if pv is None or pv.shape is None or any(
                    d < 0 for d in pv.shape):
                return None, f"parameter {grad_of[g]!r} has no static shape"
            grad_meta.append((g, tuple(pv.shape), pv.dtype or "float32"))
        return {
            "split": split,
            "inside": tuple(ops[:split]),
            "update": tuple(ops[split:]),
            "grad_meta": grad_meta,
            "inside_state": inside_state,
            "fetch_kinds": fetch_kinds,
        }, None

    def _make_overlap_step(self, donate):
        from ..core.execution import DictEnv, ExecContext, run_op
        from .mesh import shard_map
        import jax.numpy as jnp

        cfg = self._overlap_cfg
        mesh, dp_ax = self.mesh, self.batch_axis
        dp = int(mesh.shape[dp_ax])
        inside_ops, update_ops = cfg["inside"], cfg["update"]
        fetch_kinds = cfg["fetch_kinds"]
        inside_state = cfg["inside_state"]

        # size-capped buckets in gradient PRODUCTION (backward) order,
        # one stream per dtype (a bucket is one concatenated psum):
        # early buckets' all-reduces become schedulable against the
        # remaining backward compute — the DDP overlap, in-program
        from ..core.types import np_dtype

        cap = int(get_flag("overlap_bucket_bytes"))
        buckets, cur, cur_bytes, cur_dt = [], [], 0, None
        for g, shape, dtype in cfg["grad_meta"]:
            nbytes = int(np.prod(shape, dtype=np.int64)
                         * np_dtype(dtype).itemsize)
            if cur and (dtype != cur_dt
                        or (cap > 0 and cur_bytes + nbytes > cap)
                        or cap <= 0):
                buckets.append(tuple(cur))
                cur, cur_bytes = [], 0
            cur.append((g, shape, dtype))
            cur_dt, cur_bytes = dtype, cur_bytes + nbytes
        if cur:
            buckets.append(tuple(cur))
        self.overlap_info.update(
            buckets=len(buckets), grads=len(cfg["grad_meta"]),
            split=cfg["split"])

        feed_in_specs = {n: P(dp_ax) for n in self.feed_names}
        state_in_specs = {n: P() for n in inside_state}
        fetch_out_specs = {n: (P(dp_ax) if k == "batch" else P())
                           for n, k in fetch_kinds.items()}
        grad_out_specs = {g: P() for g, _, _ in cfg["grad_meta"]}

        def local_fwd_bwd(feeds, ro, key_data):
            key = jax.random.wrap_key_data(key_data)
            env = DictEnv({**ro, **feeds})
            ctx = ExecContext(key, compiled=True)
            # dropout masks are batch-position keyed: offset this
            # shard's rows so the composed draw equals serial's
            mb = next(iter(feeds.values())).shape[0] if feeds else 0
            ctx.row_offset = jax.lax.axis_index(dp_ax) * mb
            for op in inside_ops:
                run_op(ctx, op, env)
            grads = {}
            for bucket in buckets:
                flat = jnp.concatenate(
                    [jnp.ravel(env.get(g)) for g, _, _ in bucket]) \
                    if len(bucket) > 1 else jnp.ravel(
                        env.get(bucket[0][0]))
                red = jax.lax.psum(flat, dp_ax) / dp
                off = 0
                for g, shape, _ in bucket:
                    size = int(np.prod(shape, dtype=np.int64))
                    grads[g] = red[off:off + size].reshape(shape)
                    off += size
            fetches = {}
            for n, kind in fetch_kinds.items():
                v = env.get(n)
                fetches[n] = (v if kind == "batch"
                              else jax.lax.pmean(v, dp_ax))
            return fetches, grads

        sharded = shard_map(
            local_fwd_bwd, mesh=mesh,
            in_specs=(feed_in_specs, state_in_specs, P()),
            out_specs=(fetch_out_specs, grad_out_specs))

        fetch_names = list(self.fetch_names)

        def step(feeds, states, key):
            fet, grads = sharded(
                feeds, {n: states[n] for n in inside_state},
                jax.random.key_data(key))
            env = DictEnv({**states, **grads})
            ctx = ExecContext(jax.random.fold_in(key, 1), compiled=True)
            for op in update_ops:
                run_op(ctx, op, env)
            new_states = {n: env.d.get(n, states[n]) for n in states}
            return {n: fet[n] for n in fetch_names}, new_states

        return jax.jit(
            step,
            out_shardings=(None, self._out_state_shardings()),
            donate_argnums=donate,
        )

    def _refresh_trace_flags(self):
        # a flip of a flag read while tracing or building the step
        # leaves the input avals as they were: rebuild, or the old
        # executable serves the new values (core.flags.trace_flags)
        flags = trace_flags(parallel=True)
        if flags != self._trace_flags_state:
            self._jit_step = self._make_jit_step()
            self._trace_flags_state = flags

    # -- sharding policy -----------------------------------------------------
    def _spec_for(self, name, val, param_names, param_shardings,
                  shard_opt) -> P:
        # explicit spec wins (params and their accumulators)
        for pname, spec in param_shardings.items():
            if name == pname:
                return spec
            if name.startswith(pname + "_") and name.endswith("_acc"):
                # accumulator inherits its parameter's sharding
                if tuple(val.shape) and len(spec) <= len(val.shape):
                    return spec
        if shard_opt and name.endswith("_acc") and val.ndim >= 1:
            # ZeRO-1 / pserver-shard analogue: split accumulator dim 0
            dp = self.mesh.shape[self.batch_axis]
            if val.shape[0] % dp == 0 and val.shape[0] >= dp:
                return P(self.batch_axis)
        return P()

    def _out_state_shardings(self):
        return {n: self._state_shardings[n]
                for n in sorted(set(self._fn.state_in_names)
                                | set(self._fn.state_out_names))
                if n in self._state_shardings} or None

    # -- execution -----------------------------------------------------------
    def slow_steps(self):
        """As `Executor.slow_steps`: the newest 8 steps that took over
        twice the reference period, kept with tracing on or off."""
        return [dict(r) for r in self._clock.slow]

    def run(self, feed: Dict, fetch_list=None, return_numpy=True):
        clock = self._clock
        clock.mark("outside")
        t0 = time.perf_counter()
        self._refresh_trace_flags()
        fetch_names = ([v.name if isinstance(v, Variable) else str(v)
                        for v in fetch_list]
                       if fetch_list is not None else self.fetch_names)
        assert fetch_names == self.fetch_names, \
            "fetch_list must match construction-time fetch_list"
        # the same three children as core.executor's `executor.run`
        with obs_tracing.span("executor.run", mode="parallel") as run_span:
            with obs_tracing.span("executor.feed"):
                feeds = {
                    n: jax.device_put(
                        np.asarray(v),
                        self._feed_shardings.get(n, self._data_sharding))
                    for n, v in feed.items()
                }
            clock.mark("feed")
            with obs_tracing.span("executor.dispatch"):
                key = jax.random.fold_in(jax.random.key(self._seed),
                                         self._step)
                self._step += 1
                if self._hlo_registered is not self._jit_step:
                    # first run of this jit step: hlo_scopes() can
                    # read its compiled text later (shapes, no buffers)
                    self._hlo_registered = self._jit_step
                    profiler.register_jitted(
                        "parallel_executor.step", self._jit_step, feeds,
                        self._states, key)
                fetches, self._states = self._jit_step(
                    feeds, self._states, key)
                out = [fetches[n] for n in fetch_names]
            clock.mark("dispatch")
            if return_numpy:
                # the wait for the device
                with obs_tracing.span("executor.fetch"):
                    out = [np.asarray(v) for v in out]
            end_run(clock, run_span)
        if obs_metrics.enabled():
            if not hasattr(self, "_m_run"):
                self._m_run_id = f"pe{next(_PE_IDS)}"
                self._m_run = _M_RUN_SECONDS.labels(
                    exe=self._m_run_id, mode="parallel")
            self._m_run.observe(time.perf_counter() - t0)
        return out

    def close(self):
        """Reclaim this instance's registry series (per-instance
        telemetry contract: churned executors must not grow every
        metrics dump without bound).  The executor stays usable."""
        if hasattr(self, "_m_run"):
            _M_RUN_SECONDS.remove(exe=self._m_run_id, mode="parallel")

    def compiled_collectives(self, feed: Dict,
                             optimized: bool = True) -> Dict[str, int]:
        """Counts of cross-device collective ops in the train step
        compiled for `feed`'s shapes — pins the communication STRUCTURE
        of a mesh without the hardware (e.g. dp-N must show grad
        all-reduces and nothing else).

        `optimized=True` counts the optimized HLO, i.e. what runs — XLA's
        all-reduce combiner may have merged neighbouring reductions
        there.  `optimized=False` counts the module as TRACED (one op
        per psum the step issues), which is where a bucketing policy is
        pinned exactly."""
        from .mesh import count_collectives

        feeds = {
            n: jax.ShapeDtypeStruct(
                np.asarray(v).shape, np.asarray(v).dtype,
                sharding=self._feed_shardings.get(n,
                                                  self._data_sharding))
            for n, v in feed.items()
        }
        key = jax.random.key(self._seed)
        lowered = self._jit_step.lower(feeds, self._states, key)
        return count_collectives(lowered.compile().as_text() if optimized
                                 else lowered.as_text())

    def state(self, name, return_numpy=True):
        v = self._states[name]
        return np.asarray(v) if return_numpy else v

    def set_state(self, name, value):
        self._states[name] = jax.device_put(
            np.asarray(value), self._state_shardings[name])


class DistributeTranspiler:
    """API-compatible entry point for the reference's transpiler workflow
    (/root/reference/python/paddle/v2/fluid/distribute_transpiler.py:133).

    The reference rewrites the program into trainer (split/send/concat) and
    per-pserver (listen_and_serv + optimize-block) programs.  On a TPU mesh
    none of that rewriting exists as program surgery: `transpile` records
    the mesh layout, `get_trainer_program` returns the ORIGINAL program
    (configuration-as-compilation — sharding is an execution property), and
    `build_executor` yields a ParallelExecutor where
      * grad aggregation = psum over the dp axis (was: send + fan-in barrier
        + sum at the pserver, listen_and_serv_op.cc:114-153)
      * optimizer-state sharding = ZeRO-1 accumulator partitioning (was:
        ~1024-element param blocks round-robined over pservers,
        distribute_transpiler.py:91-132)
    """

    def __init__(self):
        self._mesh_axes = None
        self._program = None
        self._startup = None
        self._shard_opt = True
        self._endpoints = []
        self._assign = {}          # param name -> endpoint
        self._pairs_by_ep = {}     # endpoint -> [(param, grad)]
        self._optimize_ops = []
        self._mode = None
        self._plan = None
        self._overlap = "auto"
        self._batch_axis = "dp"

    def transpile(self, optimize_ops=None, params_grads=None,
                  trainers=1, pservers: str = "", program=None,
                  startup_program=None,
                  mesh_axes: Optional[Dict[str, int]] = None,
                  mesh=None,
                  mode: Optional[str] = None,
                  shard_optimizer_states: bool = True,
                  split_method=None, sync_mode: bool = True,
                  overlap: str = "auto", batch_axis: str = "dp"):
        """Prepare `program` for distributed execution.

        `mode`:
          * "pserver" (implied by a non-empty `pservers` list): the
            reference workflow — optimizer ops move to per-endpoint
            pserver programs, the trainer program gains one fused send.
          * "spmd" (default otherwise): GSPMD-style mesh lowering — the
            program's sharding annotations (layers.shard /
            data(sharding=...)) are completed by parallel/spmd.py's
            propagation, validated (inconsistent specs raise HERE, at
            transpile time), and recorded as the placement plan
            `build_executor` lowers onto the mesh through the proven
            strategy executors: ParallelExecutor (dp × tp × ZeRO-1,
            optional bucketed-psum compute/collective overlap) or
            PipelineExecutor when the program carries pipeline_stage
            annotations and the mesh a 'pp' axis.

        `mesh` is an alias for `mesh_axes` ({axis: size}); `overlap`
        is the ParallelExecutor overlap mode for the spmd path."""
        from ..core.framework import default_main_program

        self._program = program or default_main_program()
        self._startup = startup_program or default_startup_program()
        if mesh_axes is None and mesh is not None:
            mesh_axes = mesh
        if mesh_axes is None:
            # reference-style arg mapping: `trainers` data-parallel workers
            mesh_axes = {"dp": trainers}
        self._mesh_axes = mesh_axes
        self._shard_opt = shard_optimizer_states
        self._endpoints = [e.strip() for e in (pservers or "").split(",")
                           if e.strip()]
        self._optimize_ops = list(optimize_ops or [])
        self._trainers = trainers
        self._sync_mode = sync_mode
        self._overlap = overlap
        self._batch_axis = batch_axis
        if mode is None:
            mode = "pserver" if self._endpoints else "spmd"
        if mode not in ("pserver", "spmd"):
            raise ValueError(f"mode must be 'pserver' or 'spmd', "
                             f"got {mode!r}")
        self._mode = mode
        if mode == "pserver":
            if self._endpoints and params_grads:
                self._transpile_pserver(list(params_grads), split_method)
            return
        self._transpile_spmd()

    def _transpile_spmd(self):
        """Record the mesh on the program desc, complete the sharding
        annotations, and fail fast on inconsistent specs — the spmd
        analogue of the reference transpiler's program rewrite (the
        'rewrite' is a placement plan: sharding is an execution
        property on a TPU mesh)."""
        from .spmd import propagate_sharding

        self._program.mesh_axes = {str(k): int(v)
                                   for k, v in self._mesh_axes.items()}
        self._program.bump_version()
        self._plan = propagate_sharding(
            self._program, mesh_axes=self._program.mesh_axes,
            batch_axis=self._batch_axis).check()

    # -- real pserver mode (multi-process CPU clusters / host-side path) ----
    def _transpile_pserver(self, params_grads, split_method=None):
        """Rewrite the trainer program: optimizer ops out, ONE fused
        send op in (reference distribute_transpiler.py:134-231;
        whole-param placement per a distributed_spliter policy, default
        balanced_split — size-weighted so no pserver owns nearly all
        the bytes; round_robin/hash_name stay selectable)."""
        from . import distributed_spliter

        if split_method is None:
            split_method = distributed_spliter.balanced_split
        eps = self._endpoints
        self._pairs_by_ep = {ep: [] for ep in eps}
        placement = split_method([p for p, _ in params_grads], eps)
        for (p, g), ep in zip(params_grads, placement):
            self._assign[p.name] = ep
            self._pairs_by_ep[ep].append((p, g))

        block = self._program.global_block()
        drop = set(id(op) for op in self._optimize_ops)
        block.ops[:] = [op for op in block.ops if id(op) not in drop]
        if params_grads:
            # one bucketed send across ALL endpoints: per-var epmap for
            # the grads, out_epmap for the param pulls.  The runtime
            # (ops/distributed.py + parallel/comm.py) packs each
            # endpoint's grads into arrival-order buckets and overlaps
            # endpoints; the per-endpoint send ops emitted before this
            # forced one serial round per pserver.
            block.append_op(
                "send",
                {"X": [g.name for _, g in params_grads]},
                {"Out": [p.name for p, _ in params_grads]},
                {"endpoints": list(eps),
                 "epmap": [self._assign[p.name]
                           for p, _ in params_grads],
                 "out_epmap": [self._assign[p.name]
                               for p, _ in params_grads]})
        self._program.bump_version()

    def get_trainer_program(self):
        return self._program

    def get_pserver_program(self, endpoint=None):
        """Build the per-endpoint pserver program: one listen_and_serv op
        whose sub-block holds the optimizer ops of the params assigned to
        this endpoint (reference distribute_transpiler.py:523-618).

        On a TPU mesh (no `pservers` given) there is no pserver role and
        the original program is returned for API parity."""
        if not self._endpoints:
            return self._program
        from ..core.framework import Program, program_guard
        from ..layers.io import ListenAndServ

        pairs = self._pairs_by_ep.get(endpoint, [])
        mine = {p.name for p, _ in pairs}
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            serv = ListenAndServ(endpoint, fan_in=self._trainers,
                                 sync_mode=getattr(self, "_sync_mode",
                                                   True))
            with serv.do():
                sub = prog.current_block
                for op in self._optimize_ops:
                    param_in = op.inputs.get("Param", [])
                    if param_in and param_in[0] not in mine:
                        continue
                    for n in (op.input_names() + op.output_names()):
                        if not sub.has_var(n):
                            src = self._find_var(n)
                            sub.create_var(
                                name=n,
                                shape=src.shape if src else None,
                                dtype=src.dtype if src else "float32",
                                persistable=True)
                    sub.append_op(op.type, dict(op.inputs),
                                  dict(op.outputs), dict(op.attrs))
        return prog

    def _find_var(self, name):
        for blk in self._program.blocks:
            if blk.has_var(name):
                return blk.var(name)
        return None

    def get_startup_program(self, endpoint=None, pserver_program=None):
        """The pserver process initializes params/accumulators/lr with the
        same startup program the trainer uses (values are then owned by
        the pserver; reference get_startup_program :620)."""
        return self._startup or default_startup_program()

    def build_executor(self, feed_names, fetch_list, startup_program=None,
                       **kw):
        """Lower the transpiled program onto the mesh.  In spmd mode
        this dispatches by program shape: pipeline_stage annotations +
        a 'pp' mesh axis go to PipelineExecutor (dp × pp × tp × sp, the
        GPipe/1F1B schedules), everything else to ParallelExecutor
        (dp × tp with ZeRO-1 and the bucketed-psum overlap) — the
        proven strategy implementations the MULTICHIP dryruns pin."""
        startup_program = startup_program or self._startup
        if self._mode == "spmd" and self._uses_pipeline():
            from .pipeline_program import PipelineExecutor

            mesh = dict(self._mesh_axes)
            kw.setdefault("tp_axis",
                          "tp" if mesh.get("tp", 1) > 1 else None)
            kw.setdefault("sp_axis",
                          "sp" if mesh.get("sp", 1) > 1 else None)
            kw.setdefault("batch_axis", self._batch_axis)
            kw.setdefault("shard_optimizer_states", self._shard_opt)
            return PipelineExecutor(
                self._program, feed_names, fetch_list, mesh=mesh,
                startup_program=startup_program, **kw)
        if self._mode == "spmd":
            kw.setdefault("overlap", self._overlap)
            kw.setdefault("spmd_plan", self._plan)
            kw.setdefault("batch_axis", self._batch_axis)
        kw.setdefault("shard_optimizer_states", self._shard_opt)
        return ParallelExecutor(
            self._program, feed_names, fetch_list,
            mesh=self._mesh_axes, startup_program=startup_program, **kw)

    def _uses_pipeline(self) -> bool:
        if not self._program or self._mesh_axes.get("pp", 1) <= 1:
            return False
        return any("pipeline_stage" in op.attrs
                   for op in self._program.global_block().ops)


class ShardingTranspiler(DistributeTranspiler):
    """The GSPMD-annotation entry point: `transpile(program=...,
    mesh={'dp': 2, 'pp': 2, 'tp': 2})` + `build_executor(...)` lowers
    a sharding-annotated Program onto the mesh (always mode='spmd';
    docs/performance.md 'Multichip sharding')."""

    def transpile(self, *args, **kw):
        kw.setdefault("mode", "spmd")
        if kw["mode"] != "spmd":
            raise ValueError("ShardingTranspiler is spmd-only — use "
                             "DistributeTranspiler for the pserver path")
        return super().transpile(*args, **kw)


class SimpleDistributeTranspiler(DistributeTranspiler):
    """Whole-variable placement variant (reference
    distribute_transpiler_simple.py:1-256).  The base class already places
    whole params (no block splitting), so this is the same transpiler under
    the reference's other name — kept so both entry points exist."""
