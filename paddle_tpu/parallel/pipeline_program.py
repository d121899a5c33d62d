"""PipelineExecutor: run a `fluid.Program` under dp x pp pipeline
parallelism.

This closes the gap between the Program DSL and parallel/pipeline.py's
GPipe schedule: the reference made per-layer device placement reachable
from user config (ParallelNeuralNetwork,
/root/reference/paddle/gserver/gradientmachines/ParallelNeuralNetwork.h
+ .cpp, layer `deviceId`, flag `parallel_nn`
/root/reference/paddle/utils/Flags.cpp:37); here the user annotates the
Program's repeated trunk with `fluid.pipeline_stage(i)` and this executor
runs it as one jitted SPMD program:

  * forward ops before the first staged op ("pre", e.g. embedding) and
    after the last staged op ("post", e.g. classifier + loss) run on the
    FULL batch, dp-sharded, exactly as the serial interpreter would run
    them (same op lowerings, same per-op PRNG derivation);
  * the staged trunk is validated to be structurally homogeneous (same op
    sequence per stage), its per-stage parameters are stacked on a
    leading [pp] axis, and it executes through `spmd_pipeline`
    (shard_map + ppermute + lax.scan) on microbatched activations;
  * gradients come from `jax.value_and_grad` of that composed forward —
    autodiff derives the reverse pipeline schedule — and the Program's
    OWN optimizer ops then apply the update: stage-0's optimizer op runs
    once per parameter group on the stacked arrays (elementwise updates
    are stage-invariant; attrs are validated identical across stages),
    outer parameters run their op individually.

Tensor and sequence parallelism compose in the SAME program the
TPU-native way:

  * `tp_axis='tp'` Megatron-splits every staged weight by the
    alternation rule (see `_derive_tp_specs`) and leaves the tp axis in
    GSPMD-auto mode inside the pipeline's shard_map
    (spmd_pipeline auto_axes) — op lowerings keep seeing global shapes
    and XLA's sharding propagation inserts the tp psum after
    row-parallel matmuls.  No lowering knows tp exists.
  * `sp_axis='sp'` shards the trunk activations' sequence dim; the
    flash_attention lowering detects the manual sp axis on its
    ExecContext and runs ring attention (parallel/ring_attention.py
    ring_attention_local) — K/V blocks rotate over ICI while every
    other trunk op runs on its local sequence block unchanged.

So one `fluid.layers` Program trains under dp x pp x tp (x sp) with the
Program's own optimizer ops — the full composition the reference needed
three subsystems for (MultiGradientMachine x ParallelNeuralNetwork x
sharded pservers).

Stochastic and stateful ops in the trunk (the reference accepted ANY
layer under per-layer placement — dropout and batch-norm included):

  * dropout IS supported: masks are batch-position-keyed (each row's
    mask depends only on the op key and the row's GLOBAL batch index,
    ops/activation.py) and the stage body substitutes each stage's
    SERIAL op identity into the key derivation (stage_tags +
    ExecContext.tag_lookup), so a pipelined transformer with dropout
    reproduces the serial run bit-for-bit — pinned in
    tests/test_pipeline.py.  Under sp, each rank additionally folds its
    seq-block index (independent, distribution-equivalent to serial).
  * batch-norm stays OUT of the staged trunk by design: its running
    stats are persistable writes, and a cross-microbatch running mean
    inside one scanned schedule would make stage output depend on
    schedule order — the very nondeterminism BN's own batch statistics
    already cause across dp.  The supported placements: BN in pre/post
    (full-batch semantics, aux-state carried), or stateless
    normalization (layer_norm) in the trunk — which is also the
    transformer convention.  Other stochastic ops error with guidance.

Constraints (validated with explicit errors): stages must be
structurally identical with a single activation in/out of fixed shape
(the usual GPipe decomposition — embedding/classifier live outside the
trunk); stage count must equal the 'pp' mesh axis; trunk stages must be
stateless (no persistable writes); grad-transform ops (clip/regularizer)
are supported for outer params but not for staged params.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing

from ..core.execution import DictEnv, ExecContext, run_op
from ..core.framework import (GRAD_SUFFIX, Parameter, Variable,
                              default_startup_program, grad_var_name)
from ..core.executor import CPUPlace, Executor
from ..core.flags import trace_flags
from ..core.scope import Scope
from .checkpoint import ShardedCheckpointMixin
from .mesh import count_collectives, make_mesh
from .pipeline import microbatch, spmd_pipeline, unmicrobatch

__all__ = ["PipelineExecutor"]


def _attr_sig(attrs: Dict) -> tuple:
    """Hashable attr signature (pipeline_stage excluded) for comparing
    ops across stages."""
    def enc(v):
        if isinstance(v, np.ndarray):
            return ("nd", v.shape, str(v.dtype), v.tobytes())
        if isinstance(v, (list, tuple)):
            return tuple(enc(x) for x in v)
        if isinstance(v, dict):
            return tuple(sorted((k, enc(x)) for k, x in v.items()))
        return v
    return tuple(sorted((k, enc(v)) for k, v in attrs.items()
                        if k != "pipeline_stage"))


class PipelineExecutor(ShardedCheckpointMixin):
    def __init__(
        self,
        program,
        feed_names: Sequence[str],
        fetch_list: Sequence,
        mesh,
        startup_program=None,
        n_micro: int = 4,
        batch_axis: str = "dp",
        stage_axis: str = "pp",
        tp_axis: Optional[str] = None,
        sp_axis: Optional[str] = None,
        param_shardings: Optional[Dict[str, P]] = None,
        shard_optimizer_states: bool = False,
        schedule: str = "gpipe",
        seed: int = 0,
    ):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"schedule must be 'gpipe' or '1f1b', got {schedule!r}")
        self.schedule = schedule
        if isinstance(mesh, dict):
            mesh = make_mesh(mesh)
        self.mesh: Mesh = mesh
        self.batch_axis = batch_axis
        self.stage_axis = stage_axis
        for ax, what in ((tp_axis, "tp_axis"), (sp_axis, "sp_axis")):
            if ax is not None and ax not in mesh.shape:
                raise ValueError(f"{what}={ax!r} is not a mesh axis "
                                 f"(mesh has {tuple(mesh.shape)})")
        self.tp_axis = tp_axis if (tp_axis
                                   and mesh.shape[tp_axis] > 1) else None
        self.sp_axis = sp_axis if (sp_axis
                                   and mesh.shape[sp_axis] > 1) else None
        self._param_shardings = dict(param_shardings or {})
        self.n_micro = int(n_micro)
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in fetch_list
        ]
        self._seed = seed
        self._step = 0

        # PADDLE_TPU_VERIFY pre-flight, gated inside preflight
        # (distributed-lint checks the pipeline_stage annotations this
        # executor is about to trust)
        from ..analysis import preflight

        preflight(program, feed_names=self.feed_names,
                  fetch_names=self.fetch_names)
        block = program.global_block()
        self._persistable = {v.name for v in program.list_vars()
                             if v.persistable}
        self._partition(block)
        self.tp_param_specs = (self._derive_tp_specs(block)
                               if self.tp_axis else {})
        if self.sp_axis:
            shp = tuple(block.var(self._trunk_in).shape or ())
            if (len(shp) >= 2 and shp[1] > 0
                    and shp[1] % self.mesh.shape[self.sp_axis]):
                raise ValueError(
                    f"trunk activation {self._trunk_in!r} sequence dim "
                    f"{shp[1]} does not divide the '{self.sp_axis}' axis "
                    f"({self.mesh.shape[self.sp_axis]})")
            if any(op.type == "softmax" for op in self._stage_ops[0]):
                raise NotImplementedError(
                    "the staged trunk contains a softmax op — composed "
                    "(score-materializing) attention computes over the "
                    "LOCAL sequence block under sequence parallelism "
                    "and would silently truncate the context; use the "
                    "flash_attention path (no attention-weight dropout) "
                    "in an sp trunk")
        self._plan_update(block)
        if self.schedule == "1f1b":
            self._validate_1f1b(block)

        # --- host-side init, then stack + place -------------------------
        startup = startup_program or default_startup_program()
        scope = Scope()
        Executor(CPUPlace()).run(startup, scope=scope)
        self._init_states(scope, shard_optimizer_states)

        self._jit_step = self._make_jit_step()
        self._trace_flags_state = trace_flags(parallel=True)

    # ------------------------------------------------------------------
    # program partitioning
    # ------------------------------------------------------------------
    def _partition(self, block):
        pp = self.mesh.shape[self.stage_axis]
        ops = block.ops
        bwd_start = None
        for i, op in enumerate(ops):
            outs = op.output_names()
            if (op.type == "fill_constant" and len(outs) == 1
                    and outs[0].endswith(GRAD_SUFFIX)):
                bwd_start = i
                break
        if bwd_start is None:
            raise ValueError(
                "PipelineExecutor needs a training program: call "
                "optimizer.minimize(loss) before constructing it")
        self._bwd_start = bwd_start
        self._loss_name = ops[bwd_start].output_names()[0][
            : -len(GRAD_SUFFIX)]

        pre, post = [], []
        stages: Dict[int, list] = {}
        self._trunk_has_random = False
        mode = "pre"
        for op in ops[:bwd_start]:
            s = op.attrs.get("pipeline_stage")
            if s is None:
                if mode == "pre":
                    pre.append(op)
                else:
                    mode = "post"
                    post.append(op)
            else:
                if mode == "post":
                    raise ValueError(
                        f"op {op.type} tagged pipeline_stage={s} appears "
                        "after unstaged post-trunk ops — the staged trunk "
                        "must be contiguous")
                mode = "stage"
                stages.setdefault(int(s), []).append(op)
        if not stages:
            raise ValueError(
                "no ops tagged with fluid.pipeline_stage(i) — annotate "
                "the repeated trunk blocks to pipeline this program")
        idxs = sorted(stages)
        if idxs != list(range(len(idxs))):
            raise ValueError(f"stage indices must be 0..S-1, got {idxs}")
        if len(idxs) != pp:
            raise ValueError(
                f"{len(idxs)} pipeline stages but mesh axis "
                f"'{self.stage_axis}' has {pp} devices — they must match "
                "(fold several layers into one stage to reduce the count)")
        self._pre_ops, self._post_ops = pre, post
        self._stage_ops = [stages[i] for i in idxs]
        self._validate_stages(block)

        # persistable writes by pre/post (BN stats, counters) are carried
        # as rw aux state; staged ops must be stateless
        self._aux_writes = sorted({
            n for op in pre + post for n in op.output_names()
            if n in self._persistable})
        from ..core import registry as op_registry
        for s, sops in enumerate(self._stage_ops):
            bad = [n for op in sops for n in op.output_names()
                   if n in self._persistable]
            if bad:
                raise NotImplementedError(
                    f"stage {s} writes persistable var(s) {bad}: staged "
                    "trunk ops must be stateless (keep BN/counters in the "
                    "pre/post sections)")
            for op in sops:
                try:
                    info = op_registry.get_op_info(op.type)
                except KeyError:
                    continue
                if info.random and not op.attrs.get("is_test", False):
                    if op.type == "dropout":
                        # supported: batch-position-keyed masks + per-
                        # stage serial op tags make the pipelined draw
                        # bit-identical to serial (see _make_jit_step)
                        self._trunk_has_random = True
                        continue
                    raise NotImplementedError(
                        f"stage {s} contains stochastic op {op.type!r}: "
                        "only dropout has the batch-position-keyed "
                        "derivation that keeps one traced stage body "
                        "consistent with serial execution — run other "
                        "stochastic ops in the pre/post sections (or "
                        "set is_test)")

    def _stage_io(self, ops, block):
        """(ordered external activation reads, ordered Parameter reads,
        set of names written) for one stage's op list."""
        written, ext, params = set(), [], []
        for op in ops:
            for n in op.input_names():
                if not n or n in written or n in ext or n in params:
                    continue
                if n in self._persistable:
                    v = block.var(n)
                    if not isinstance(v, Parameter):
                        raise NotImplementedError(
                            f"stage op {op.type} reads persistable "
                            f"non-parameter {n!r}: staged trunks may only "
                            "read activations and their own parameters")
                    params.append(n)
                else:
                    ext.append(n)
            written.update(op.output_names())
        return ext, params, written

    def _validate_stages(self, block):
        pp = len(self._stage_ops)
        sigs, ios = [], []
        for sops in self._stage_ops:
            sigs.append([
                (op.type, _attr_sig(op.attrs),
                 tuple(sorted((k, len(v)) for k, v in op.inputs.items())),
                 tuple(sorted((k, len(v)) for k, v in op.outputs.items())))
                for op in sops])
            ios.append(self._stage_io(sops, block))
        for s in range(1, pp):
            if sigs[s] != sigs[0]:
                raise ValueError(
                    f"pipeline stage {s} is not structurally identical to "
                    "stage 0 (op sequence/attrs differ) — spmd_pipeline "
                    "runs ONE traced stage body with per-stage parameters, "
                    "so every stage must build the same layer stack")
        self._stage_params: List[List[str]] = [io[1] for io in ios]
        for s in range(1, pp):
            if len(self._stage_params[s]) != len(self._stage_params[0]):
                raise ValueError("per-stage parameter counts differ")
            for a, b in zip(self._stage_params[0], self._stage_params[s]):
                va, vb = block.var(a), block.var(b)
                if tuple(va.shape or ()) != tuple(vb.shape or ()):
                    raise ValueError(
                        f"stage param shape mismatch: {a} {va.shape} vs "
                        f"{b} {vb.shape}")

        # activation plumbing: one in, one out, chained stage to stage
        consumed_later: Dict[int, set] = {}
        later = {n for op in self._post_ops for n in op.input_names()}
        later |= set(self.fetch_names)
        for s in reversed(range(pp)):
            consumed_later[s] = set(later)
            later |= {n for op in self._stage_ops[s]
                      for n in op.input_names()}
        self._trunk_in = None
        self._stage_out: List[str] = []
        prev_out = None
        for s in range(pp):
            ext, _, written = ios[s]
            if len(ext) != 1:
                raise ValueError(
                    f"stage {s} reads {len(ext)} external activations "
                    f"({ext}): exactly one [batch, ...] activation may "
                    "cross a stage boundary")
            outs = sorted(written & consumed_later[s])
            if len(outs) != 1:
                raise ValueError(
                    f"stage {s} emits {len(outs)} activations consumed "
                    f"downstream ({outs}): exactly one may cross the "
                    "boundary")
            if s == 0:
                self._trunk_in = ext[0]
            elif ext[0] != prev_out:
                raise ValueError(
                    f"stage {s} input {ext[0]!r} is not stage {s-1}'s "
                    f"output {prev_out!r}")
            prev_out = outs[0]
            self._stage_out.append(prev_out)
        # the traced stage body (stage 0's ops) emits stage 0's boundary
        # name; the post section consumes the LAST stage's name
        self._trunk_out = self._stage_out[-1]

    # ------------------------------------------------------------------
    # 1F1B section analysis
    # ------------------------------------------------------------------
    def _validate_1f1b(self, block):
        """Under the 1F1B schedule the POST section (classifier + loss)
        runs per microbatch on the LAST stage, inside the schedule scan
        (spmd_pipeline_1f1b last_fn), so the backward wave can start
        while later microbatches are still in flight.  That imposes two
        structural requirements checked here: the post section may not
        write persistables (its per-microbatch execution would apply
        stateful updates n_micro times, e.g. BN stats), and any
        pre-section float activation consumed by post would need its
        gradient routed around the pipeline (not supported — keep such
        paths wholly in pre or post).  It also assumes the Program's
        loss is a batch MEAN (the book convention): per-microbatch
        losses are combined as sum/ (n_micro * dp [* sp]), which equals
        the serial value exactly for mean losses — pinned by the
        serial-equality tests."""
        post_reads = {n for op in self._post_ops for n in
                      op.input_names()}
        post_writes = {n for op in self._post_ops for n in
                       op.output_names()}
        post_aux = sorted(post_writes & set(self._persistable))
        if post_aux:
            raise NotImplementedError(
                f"schedule='1f1b': post section writes persistable "
                f"var(s) {post_aux} — per-microbatch post execution "
                "would apply them n_micro times (keep BN/counters in "
                "pre, or use schedule='gpipe')")
        pre_written = {n for op in self._pre_ops for n in
                       op.output_names()}
        side = sorted(
            n for n in post_reads
            if n in pre_written and n not in self._persistable
            and n != self._trunk_out and n)
        self._side_vars = side
        bad = [n for n in side
               if str(block.var(n).dtype).startswith(("float",
                                                      "bfloat"))]
        if bad:
            raise NotImplementedError(
                f"schedule='1f1b': float pre-section output(s) {bad} "
                "are consumed by the post section — their gradient "
                "would bypass the pipeline (not supported; use "
                "schedule='gpipe' or restructure)")
        if self.sp_axis:
            # the per-microbatch post section sees a sequence-sharded
            # trunk output, so EVERY y-stream leaf (post-read feeds AND
            # pre-produced side vars) must carry the same seq dim at
            # position 1 to shard alongside it.  The check is
            # positional and by-size (the [B, S, ...] batch-major
            # convention) — a non-sequence dim that coincidentally
            # equals S would pass; the serial-equality tests are the
            # backstop for such programs.  The combination also
            # assumes the post section is SEQ-LOCAL up to the final
            # batch-mean (true of the reshape + softmax_xent + mean
            # shape; a post op reducing ACROSS positions would compute
            # per-shard reductions — covered by the same tests).
            out_shape = tuple(block.var(self._trunk_out).shape or ())
            seq = out_shape[1] if len(out_shape) > 1 else None
            y_like = ([n for n in self.feed_names if n in post_reads]
                      + side)
            bad = []
            for n in y_like:
                shp = tuple(block.var(n).shape or ())
                if len(shp) < 2 or shp[1] != seq:
                    bad.append((n, shp))
            if bad:
                raise NotImplementedError(
                    f"schedule='1f1b' with sp_axis: post-section "
                    f"input(s) {bad} lack the trunk output's sequence "
                    f"dim {seq} at position 1, so they cannot shard "
                    "with the sequence-parallel trunk output — use "
                    "schedule='gpipe' (post on the gathered full batch)")

    # ------------------------------------------------------------------
    # tensor-parallel spec derivation (Megatron alternation)
    # ------------------------------------------------------------------
    def _derive_tp_specs(self, block) -> Dict[str, P]:
        """Walk stage 0's ops and assign each staged parameter a
        tensor-parallel PartitionSpec (WITHOUT the leading pp dim) by the
        Megatron alternation rule: a matmul consuming a feature-replicated
        activation splits its weight column-wise (output features over
        tp, activation becomes feature-sharded); a matmul consuming a
        feature-sharded activation splits row-wise (contraction over tp —
        XLA's sharding propagation inserts the psum — and the activation
        returns to replicated).  Biases follow their activation; LN
        params stay replicated (full-feature op on the replicated
        residual stream).  This reproduces Megatron's column->row split
        for attention (wq/wk/wv col, wo row) and FFN (w1 col, w2 row) on
        the DSL transformer block, and degrades to alternating col/row
        on a plain fc trunk.

        The specs are APPLIED purely as NamedShardings on the stacked
        arrays: the stage body runs under shard_map with the tp axis in
        GSPMD-auto mode (spmd_pipeline auto_axes), so op lowerings keep
        seeing global shapes and the compiler places the collectives —
        no manual psum in any lowering.  Reference capability:
        /root/reference/paddle/gserver/gradientmachines/
        ParallelNeuralNetwork.h (per-layer placement); the composition
        itself is beyond-reference (SURVEY.md §2.5)."""
        tp = self.tp_axis
        specs: Dict[str, P] = {}
        tagged = set()  # activations whose feature dim is tp-sharded
        param0 = set(self._stage_params[0])
        for op in self._stage_ops[0]:
            outs = op.output_names()
            if op.type == "mul":
                x = op.inputs["X"][0]
                y = op.inputs["Y"][0]
                if y in param0:
                    if y in specs:
                        raise NotImplementedError(
                            f"staged param {y!r} is read by two matmuls "
                            "— tp auto-split needs a single role per "
                            "weight (pass tp_axis=None or restructure)")
                    if x in tagged:
                        specs[y] = P(tp, None)      # row-parallel
                    else:
                        specs[y] = P(None, tp)      # column-parallel
                        tagged.update(outs)
                    continue
            elif op.type == "elementwise_add":
                x = op.inputs.get("X", [None])[0]
                y = op.inputs.get("Y", [None])[0]
                if y in param0:                     # bias
                    new = P(tp) if x in tagged else P()
                    if y in specs and specs[y] != new:
                        raise NotImplementedError(
                            f"staged bias {y!r} is consumed by adds with "
                            "different feature shardings — tp auto-split "
                            "needs a single role per param (pass "
                            "tp_axis=None or restructure)")
                    specs[y] = new
                    if x in tagged:
                        tagged.update(outs)
                    continue
            elif op.type == "layer_norm":
                # full-feature op on the replicated stream: params (and
                # output) replicated.  A tp-sharded input here would make
                # GSPMD all-gather — correct but wasteful; the pre-LN
                # trunk never produces one.
                continue
            # default: feature sharding propagates through elementwise /
            # reshape / transpose / attention ops
            if any(n in tagged for n in op.input_names()):
                tagged.update(outs)
        return specs

    # ------------------------------------------------------------------
    # update planning (the Program's own optimizer ops)
    # ------------------------------------------------------------------
    def _plan_update(self, block):
        ops = block.ops
        start = self._bwd_start
        stage0 = set(self._stage_params[0])
        stage_rest = {n for sp in self._stage_params[1:] for n in sp}
        # values the update phase can bind: every persistable EXCEPT
        # stage params of stages >= 1 (stored stacked under stage-0
        # names), plus the jax.grad cotangents under canonical names
        bindable = set(self._persistable) - stage_rest
        self._trainable = [p.name for p in block.all_parameters()
                           if p.trainable]
        grad_names = {grad_var_name(n) for n in self._trainable
                      if n not in stage_rest}
        bindable |= grad_names

        plan = []
        produced = set(bindable)
        self._group_opt_ops: Dict[str, object] = {}
        for op in ops[start:]:
            is_opt = "Param" in op.inputs and "ParamOut" in op.outputs
            pname = op.inputs["Param"][0] if is_opt else None
            if is_opt and pname in stage_rest:
                # covered by the stacked run of stage-0's op; validate
                plan.append(("skip_stage_opt", op))
                continue
            runnable = all((not n) or n in produced
                           for n in op.input_names())
            if runnable:
                plan.append(("run", op))
                produced.update(op.output_names())
                if is_opt and pname in stage0:
                    self._group_opt_ops[pname] = op
            else:
                # backward/grad-computation op: replaced by jax.grad
                # (empty/@EMPTY@ slots are pruned-grad placeholders)
                tainted_outs = [n for n in op.output_names()
                                if GRAD_SUFFIX in n
                                or n in ("", "@EMPTY@")]
                if len(tainted_outs) != len(op.output_names()) or is_opt:
                    raise NotImplementedError(
                        f"update-section op {op.type} "
                        f"({op.output_names()}) depends on forward "
                        "activations or unstacked stage state — not "
                        "supported under PipelineExecutor (grad-transform "
                        "ops on staged params, per-param hooks)")
                plan.append(("skip_grad", op))
        # every stage-rest optimizer op must mirror its stage-0 twin
        k_of = {}
        for s, names in enumerate(self._stage_params):
            for k, n in enumerate(names):
                k_of[n] = k
        sig0 = {}
        for kind, op in plan:
            if kind == "run" and op.inputs.get("Param", [None])[0] in stage0:
                sig0[k_of[op.inputs["Param"][0]]] = (op.type,
                                                     _attr_sig(op.attrs))
        for kind, op in plan:
            if kind != "skip_stage_opt":
                continue
            k = k_of[op.inputs["Param"][0]]
            if sig0.get(k) != (op.type, _attr_sig(op.attrs)):
                raise ValueError(
                    f"optimizer op for staged param "
                    f"{op.inputs['Param'][0]} differs from stage 0's "
                    "(type/attrs) — stacked update would be wrong")
        missing = [n for n in stage0 if n not in self._group_opt_ops]
        if missing:
            raise ValueError(
                f"staged params {missing} have no optimizer op")
        self._update_plan = plan
        # accumulators of stage-0 opt ops: stacked like their params.
        # slots beyond Param/Grad/LearningRate reference accumulators
        self._stage_acc: Dict[str, List[str]] = {}
        self._acc_owner: Dict[str, str] = {}
        for pname, op0 in self._group_opt_ops.items():
            k = k_of[pname]
            accs = [n for slot, ns in op0.inputs.items()
                    if slot not in ("Param", "Grad", "LearningRate")
                    for n in ns if n in self._persistable]
            for acc in accs:
                self._acc_owner[acc] = pname
                per_stage = [acc]
                for s in range(1, len(self._stage_params)):
                    twin = next(
                        op for kind, op in self._update_plan
                        if kind == "skip_stage_opt"
                        and op.inputs["Param"][0]
                        == self._stage_params[s][k])
                    slot = next(sl for sl, ns in op0.inputs.items()
                                if acc in ns)
                    per_stage.append(twin.inputs[slot][
                        op0.inputs[slot].index(acc)])
                self._stage_acc[acc] = per_stage
        # beta-pow style shared accumulators must not be stage-stacked
        # twice; sanity: an acc name appears in exactly one group
        flat = [n for v in self._stage_acc.values() for n in v]
        if len(flat) != len(set(flat)):
            raise NotImplementedError(
                "optimizer accumulators shared across staged params are "
                "not supported")

    # ------------------------------------------------------------------
    # state placement
    # ------------------------------------------------------------------
    def _init_states(self, scope, shard_opt):
        mesh, dp = self.mesh, self.mesh.shape[self.batch_axis]
        pp_ax, dp_ax = self.stage_axis, self.batch_axis
        stage0 = self._stage_params[0]
        stacked_members = {n for sp in self._stage_params[1:] for n in sp}
        for accs in self._stage_acc.values():
            stacked_members |= set(accs[1:])

        def val(n):
            v = scope.find_var(n)
            if v is None:
                raise RuntimeError(
                    f"state var {n!r} not produced by the startup program")
            return np.asarray(v)

        def tp_padded(p0, shape):
            """The param's tp spec (pipeline_stage dim EXCLUDED) padded
            with Nones to len(shape); only divisible dims keep the tp
            axis (GSPMD pads otherwise — correct but wasteful on the
            tiny virtual-mesh shapes)."""
            ndim = len(shape)
            spec = list(self.tp_param_specs.get(p0, ())) if self.tp_axis \
                else []
            spec += [None] * (ndim - len(spec))
            tp_n = mesh.shape[self.tp_axis] if self.tp_axis else 1
            return [None if (s == self.tp_axis and shape[i] % tp_n)
                    else s for i, s in enumerate(spec[:ndim])]

        unknown = sorted(k for k in self._param_shardings
                         if k not in self._persistable)
        if unknown:
            raise ValueError(
                f"param_shardings names {unknown} are not persistable "
                "vars of this program (typo?)")
        staged_keys = sorted(k for k in self._param_shardings
                             if k in set(stage0) or k in stacked_members)
        if staged_keys:
            raise ValueError(
                f"param_shardings entries {staged_keys} name STAGED "
                "params — staged weights are sharded by the tp_axis "
                "derivation (tp_param_specs), not per-name specs")

        states, shardings = {}, {}
        self._state_map = {}
        # stacked parameter groups + their accumulators
        for k, p0 in enumerate(stage0):
            stack = np.stack([val(sp[k]) for sp in self._stage_params])
            states[p0] = stack
            shardings[p0] = NamedSharding(
                mesh, P(pp_ax, *tp_padded(p0, stack.shape[1:])))
            for s, sp in enumerate(self._stage_params):
                self._state_map[sp[k]] = ("stacked", p0, s)
        for acc0, names in self._stage_acc.items():
            stack = np.stack([val(n) for n in names])
            states[acc0] = stack
            # accumulator shards exactly like its param (same shape),
            # plus ZeRO-1: the first still-free dim additionally shards
            # over dp when divisible
            spec = [pp_ax] + tp_padded(self._acc_owner.get(acc0, acc0),
                                       stack.shape[1:])
            if shard_opt:
                for i in range(1, stack.ndim):
                    if (spec[i] is None and stack.shape[i] % dp == 0
                            and stack.shape[i] >= dp):
                        spec[i] = dp_ax
                        break
            shardings[acc0] = NamedSharding(mesh, P(*spec))
            for s, n in enumerate(names):
                self._state_map[n] = ("stacked", acc0, s)
        # every other persistable the program touches
        for n in sorted(self._persistable):
            if n in states or n in stacked_members or n in self._state_map:
                continue
            if not scope.has_var(n) or scope.find_var(n) is None:
                continue  # produced mid-program (e.g. aux writes only)
            v = val(n)
            spec = self._param_shardings.get(n)
            if spec is None:
                # accumulator inherits its parameter's explicit spec
                # (same policy as ParallelExecutor._spec_for)
                for pname, ps in self._param_shardings.items():
                    if (n.startswith(pname + "_") and n.endswith("_acc")
                            and tuple(v.shape) and len(ps) <= v.ndim):
                        spec = ps
                        break
            if spec is None:
                spec = P()
                if (shard_opt and n.endswith("_acc") and v.ndim >= 1
                        and v.shape[0] % dp == 0 and v.shape[0] >= dp):
                    spec = P(dp_ax)
            states[n] = v
            shardings[n] = NamedSharding(mesh, spec)
            self._state_map[n] = ("direct", n, None)
        self._state_shardings = shardings
        self._states = {n: jax.device_put(v, shardings[n])
                        for n, v in states.items()}
        self._data_sharding = NamedSharding(mesh, P(self.batch_axis))

    # ------------------------------------------------------------------
    # the jitted train step
    # ------------------------------------------------------------------
    def _make_stage_fn_factory(self):
        """-> make_stage_fn(key) -> stage_fn(pvals, h, t), shared by the
        GPipe and 1F1B schedules.  The per-(stage, op) SERIAL rng-tag
        table is a closed-over constant indexed by the stage's
        axis_index: the one traced stage body runs stage 0's op descs for
        every stage, so a random op (dropout) must derive its key from
        the op identity the SERIAL executor would use for THAT stage
        (ExecContext.tag_lookup)."""
        import zlib

        from ..core import registry as op_registry
        from ..core.execution import _op_rng_tag

        mesh = self.mesh
        stage0 = list(self._stage_params[0])
        s0_ops = tuple(self._stage_ops[0])
        trunk_in, s0_out = self._trunk_in, self._stage_out[0]
        n_micro, batch_axis, stage_axis = (self.n_micro, self.batch_axis,
                                           self.stage_axis)
        sp_axis = self.sp_axis
        has_random = self._trunk_has_random
        stage_tags = np.zeros((len(self._stage_ops), len(s0_ops)),
                              np.int32)
        for s, sops in enumerate(self._stage_ops):
            for j, op in enumerate(sops):
                info = op_registry.get_op_info(op.type)
                stage_tags[s, j] = (
                    zlib.crc32(_op_rng_tag(op, info).encode())
                    & 0x7FFFFFFF)
        op_pos = {id(op): j for j, op in enumerate(s0_ops)}

        def make_stage_fn(key):
            def stage_fn(pvals, h, t):
                env = DictEnv(dict(zip(stage0, pvals)))
                env.set(trunk_in, h)
                ctx = ExecContext(
                    key if has_random else jax.random.key(0),
                    compiled=True)
                if sp_axis:
                    # the attention lowering rings K/V over this axis
                    ctx.sp_axis = sp_axis
                    ctx.sp_size = mesh.shape[sp_axis]
                if has_random:
                    tag_row = jnp.asarray(stage_tags)[
                        jax.lax.axis_index(stage_axis)]
                    ctx.tag_lookup = lambda op: (
                        tag_row[op_pos[id(op)]]
                        if id(op) in op_pos else None)
                    # global row offset of this (microbatch, dp shard):
                    # dropout keys masks by batch position, so the
                    # pipelined draw equals the serial full-batch draw
                    mb_loc = h.shape[0]
                    dp = mesh.shape[batch_axis]
                    micro = jnp.clip(
                        t - jax.lax.axis_index(stage_axis), 0,
                        n_micro - 1)
                    ctx.row_offset = (
                        micro * (mb_loc * dp)
                        + jax.lax.axis_index(batch_axis) * mb_loc)
                    if sp_axis:
                        ctx.rng_seq_block = jax.lax.axis_index(sp_axis)
                for op in s0_ops:
                    run_op(ctx, op, env)
                return env.get(s0_out)

            return stage_fn

        return make_stage_fn

    def _make_jit_step(self):
        if self.schedule == "1f1b":
            return self._make_jit_step_1f1b()
        return self._make_jit_step_gpipe()

    def _make_jit_step_gpipe(self):
        mesh = self.mesh
        stage0 = list(self._stage_params[0])
        pre_ops = tuple(self._pre_ops)
        post_ops = tuple(self._post_ops)
        s0_ops = tuple(self._stage_ops[0])
        trunk_in, trunk_out = self._trunk_in, self._trunk_out
        s0_out = self._stage_out[0]
        loss_name, fetch_names = self._loss_name, self.fetch_names
        n_micro, batch_axis, stage_axis = (self.n_micro, self.batch_axis,
                                           self.stage_axis)
        aux_writes = list(self._aux_writes)
        plan = tuple(self._update_plan)
        trainable = [n for n in self._trainable if n in self._states]
        outer_trainable = [n for n in trainable if n not in stage0]

        tp_axis, sp_axis = self.tp_axis, self.sp_axis
        has_random = self._trunk_has_random
        make_stage_fn = self._make_stage_fn_factory()

        def forward(outer_p, stack_p, rest, feeds, key):
            env = DictEnv({**rest, **outer_p, **feeds})
            ctx = ExecContext(key, compiled=True)
            for op in pre_ops:
                run_op(ctx, op, env)
            h = env.get(trunk_in)
            h = microbatch(h, n_micro)
            h = spmd_pipeline(make_stage_fn(key), tuple(stack_p), h,
                              mesh, axis=stage_axis,
                              batch_axis=batch_axis,
                              auto_axes=(tp_axis,) if tp_axis else (),
                              seq_axis=sp_axis, with_tick=True)
            env.set(trunk_out, unmicrobatch(h))
            for op in post_ops:
                run_op(ctx, op, env)
            loss = jnp.sum(env.get(loss_name))
            fetches = {n: env.get(n) for n in fetch_names}
            aux_new = {n: env.d[n] for n in aux_writes if n in env.d}
            return loss, (fetches, aux_new)

        grad_fn = jax.value_and_grad(forward, argnums=(0, 1),
                                     has_aux=True)

        def step(feeds, states, key):
            outer_p = {n: states[n] for n in outer_trainable}
            stack_p = [states[n] for n in stage0]
            rest = {n: v for n, v in states.items()
                    if n not in outer_trainable and n not in stage0}
            (loss, (fetches, aux_new)), (g_outer, g_stack) = grad_fn(
                outer_p, stack_p, rest, feeds, key)

            # --- the Program's own update ops on the computed grads ----
            env = DictEnv({**states, **aux_new})
            for n, g in g_outer.items():
                env.set(grad_var_name(n), g)
            for n, g in zip(stage0, g_stack):
                env.set(grad_var_name(n), g)
            ctx = ExecContext(jax.random.fold_in(key, 1), compiled=True)
            for kind, op in plan:
                if kind == "run":
                    run_op(ctx, op, env)
            # env.d already holds aux_new (merged at construction) and
            # every update-op write; anything untouched keeps its old value
            new_states = {n: env.d.get(n, states[n]) for n in states}
            return fetches, loss, new_states

        out_sh = {n: self._state_shardings[n] for n in self._states}
        return jax.jit(step, out_shardings=(None, None, out_sh),
                       donate_argnums=(1,))

    def _make_jit_step_1f1b(self):
        """The 1F1B schedule (parallel/pipeline.spmd_pipeline_1f1b): one
        scan interleaves forward and backward microbatches with vjp
        residuals in an O(pp) ring buffer — the long-n_micro /
        tight-HBM configuration.  The post section runs per microbatch
        as the schedule's last_fn (its params' grads accumulate inside
        the scan); pre-section grads come from the schedule's dx through
        jax.vjp of the pre ops; fetches are recomputed exactly on the
        full batch from the collected last-stage outputs (dropout's
        batch-position keying makes the recompute bit-identical to the
        per-microbatch draws)."""
        from .pipeline import spmd_pipeline_1f1b

        mesh = self.mesh
        stage0 = list(self._stage_params[0])
        pre_ops = tuple(self._pre_ops)
        post_ops = tuple(self._post_ops)
        trunk_in, trunk_out = self._trunk_in, self._trunk_out
        loss_name, fetch_names = self._loss_name, self.fetch_names
        n_micro, batch_axis, stage_axis = (self.n_micro, self.batch_axis,
                                           self.stage_axis)
        aux_writes = list(self._aux_writes)
        plan = tuple(self._update_plan)
        trainable = [n for n in self._trainable if n in self._states]
        outer_trainable = [n for n in trainable if n not in stage0]
        tp_axis, sp_axis = self.tp_axis, self.sp_axis
        make_stage_fn = self._make_stage_fn_factory()

        pre_reads = {n for op in pre_ops for n in op.input_names()}
        post_reads = {n for op in post_ops for n in op.input_names()}
        pre_params = [n for n in outer_trainable if n in pre_reads]
        post_params = [n for n in outer_trainable if n in post_reads]
        # non-trainable states the post section reads (closure, replicated)
        post_rest = [n for n in sorted(post_reads)
                     if n in self._states and n not in post_params
                     and n not in stage0]
        y_names = ([n for n in self.feed_names if n in post_reads]
                   + self._side_vars)
        dp = mesh.shape[batch_axis]
        sp = mesh.shape[sp_axis] if sp_axis else 1
        # batch-mean loss combination (see _validate_1f1b)
        scale = 1.0 / (n_micro * dp * sp)

        def make_last_fn(key, lrest):
            def last_fn(lp, h, y, m):
                env = DictEnv({**lrest, **lp, **y})
                env.set(trunk_out, h)
                ctx = ExecContext(key, compiled=True)
                mb_loc = h.shape[0]
                ctx.row_offset = (m * (mb_loc * dp)
                                  + jax.lax.axis_index(batch_axis)
                                  * mb_loc)
                if sp_axis:
                    ctx.rng_seq_block = jax.lax.axis_index(sp_axis)
                for op in post_ops:
                    run_op(ctx, op, env)
                return jnp.sum(env.get(loss_name)) * scale

            return last_fn

        def step(feeds, states, key):
            stack_p = [states[n] for n in stage0]
            rest = {n: v for n, v in states.items()
                    if n not in outer_trainable and n not in stage0}
            pre_p = {n: states[n] for n in pre_params}
            lp = {n: states[n] for n in post_params}
            lrest = {n: states[n] for n in post_rest}

            # full-batch pre pass: trunk input, side values, pre aux
            env = DictEnv({**rest,
                           **{n: states[n] for n in outer_trainable},
                           **feeds})
            ctx = ExecContext(key, compiled=True)
            for op in pre_ops:
                run_op(ctx, op, env)
            aux_new = {n: env.d[n] for n in aux_writes if n in env.d}
            x_mb = microbatch(env.get(trunk_in), n_micro)
            y_mb = {n: microbatch(env.get(n), n_micro) for n in y_names}

            loss_sum, outs, g_stack, g_last, dx = spmd_pipeline_1f1b(
                make_stage_fn(key), make_last_fn(key, lrest),
                tuple(stack_p), lp, x_mb, y_mb, mesh, axis=stage_axis,
                batch_axis=batch_axis,
                auto_axes=(tp_axis,) if tp_axis else (),
                seq_axis=sp_axis, with_tick=True)

            # pre-section grads from the schedule's input cotangents
            # (XLA CSEs this re-trace with the pre pass above: same key,
            # same ops, same operands)
            def pre_fn(pp_):
                env2 = DictEnv({**rest, **lp, **pp_, **feeds})
                ctx2 = ExecContext(key, compiled=True)
                for op in pre_ops:
                    run_op(ctx2, op, env2)
                return env2.get(trunk_in)

            _, pre_vjp = jax.vjp(pre_fn, pre_p)
            (g_pre,) = pre_vjp(unmicrobatch(dx))

            # fetches: exact full-batch post on the collected outputs
            env.set(trunk_out, unmicrobatch(outs))
            for op in post_ops:
                run_op(ctx, op, env)
            loss = jnp.sum(env.get(loss_name))
            fetches = {n: env.get(n) for n in fetch_names}

            # --- the Program's own update ops on the computed grads ----
            envU = DictEnv({**states, **aux_new})
            for n in outer_trainable:
                g = None
                if n in g_pre:
                    g = g_pre[n]
                if n in g_last:
                    g = g_last[n] if g is None else g + g_last[n]
                if g is not None:
                    envU.set(grad_var_name(n), g)
            for n, g in zip(stage0, g_stack):
                envU.set(grad_var_name(n), g)
            ctxU = ExecContext(jax.random.fold_in(key, 1), compiled=True)
            for kind, op in plan:
                if kind == "run":
                    run_op(ctxU, op, envU)
            new_states = {n: envU.d.get(n, states[n]) for n in states}
            return fetches, loss, new_states

        out_sh = {n: self._state_shardings[n] for n in self._states}
        return jax.jit(step, out_shardings=(None, None, out_sh),
                       donate_argnums=(1,))

    def _refresh_trace_flags(self):
        # see parallel/executor.py:_refresh_trace_flags
        flags = trace_flags(parallel=True)
        if flags != self._trace_flags_state:
            self._jit_step = self._make_jit_step()
            self._trace_flags_state = flags

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def run(self, feed: Dict, fetch_list=None, return_numpy=True):
        import time as _time

        t0 = _time.perf_counter()
        self._refresh_trace_flags()
        fetch_names = ([v.name if isinstance(v, Variable) else str(v)
                        for v in fetch_list]
                       if fetch_list is not None else self.fetch_names)
        assert fetch_names == self.fetch_names, \
            "fetch_list must match construction-time fetch_list"
        with obs_tracing.span("executor.run", mode="pipeline"):
            dp = self.mesh.shape[self.batch_axis]
            feeds = {}
            for n, v in feed.items():
                v = np.asarray(v)
                if v.shape[0] % self.n_micro:
                    raise ValueError(
                        f"batch {v.shape[0]} not divisible by n_micro "
                        f"{self.n_micro}")
                if (v.shape[0] // self.n_micro) % dp:
                    raise ValueError(
                        f"microbatch {v.shape[0] // self.n_micro} not "
                        f"divisible by the '{self.batch_axis}' axis "
                        f"({dp})")
                feeds[n] = jax.device_put(v, self._data_sharding)
            key = jax.random.fold_in(jax.random.key(self._seed),
                                     self._step)
            self._step += 1
            fetches, _loss, self._states = self._jit_step(
                feeds, self._states, key)
            out = [fetches[n] for n in fetch_names]
            if return_numpy:
                out = [np.asarray(v) for v in out]
        if obs_metrics.enabled():
            if not hasattr(self, "_m_run"):
                from .executor import _M_RUN_SECONDS, _PE_IDS
                self._m_run_id = f"pipe{next(_PE_IDS)}"
                self._m_run = _M_RUN_SECONDS.labels(
                    exe=self._m_run_id, mode="pipeline")
            self._m_run.observe(_time.perf_counter() - t0)
        return out

    def close(self):
        """Reclaim this instance's registry series (per-instance
        telemetry contract, same as ParallelExecutor.close)."""
        if hasattr(self, "_m_run"):
            from .executor import _M_RUN_SECONDS
            _M_RUN_SECONDS.remove(exe=self._m_run_id, mode="pipeline")

    def state(self, name, return_numpy=True):
        kind, store, idx = self._state_map[name]
        v = self._states[store]
        if kind == "stacked":
            v = v[idx]
        return np.asarray(v) if return_numpy else v

    def compiled_collectives(self, feed: Dict) -> Dict[str, int]:
        """Collective-op counts in the optimized HLO of the train step for
        `feed`'s shapes (collective-permute = pipeline hops; all-reduce =
        dp grad sums) — the communication-structure pin used by tests and
        run_scaling --virtual."""
        feeds = {
            n: jax.ShapeDtypeStruct(np.asarray(v).shape,
                                    np.asarray(v).dtype,
                                    sharding=self._data_sharding)
            for n, v in feed.items()
        }
        key = jax.random.key(self._seed)
        txt = self._jit_step.lower(feeds, self._states, key) \
            .compile().as_text()
        return count_collectives(txt)
