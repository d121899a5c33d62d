"""Shared op execution: dispatch, contexts, and the generic VJP gradient.

This module is the TPU-native replacement for the reference's
`OperatorWithKernel::RunImpl` dispatch chain
(/root/reference/paddle/fluid/framework/operator.cc:494-570): instead of
choosing a (place, layout, dtype, library) kernel at every step and
data-transforming inputs between kernel types, a single jax lowering per op is
executed either eagerly (interpreter) or under a trace (core/compiler.py) —
XLA owns layout, fusion and device placement.

Gradient ops named "<type>_grad" with no explicit lowering are executed by
`jax.vjp` over the forward lowering (`generic_grad_lower`), which makes every
registered op differentiable by construction.  The reference instead requires
a hand-written grad kernel per op (op_registry.h REGISTER_OP grad class).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from . import registry
from .lod import LoDTensor, SelectedRows, TensorArray

GRAD = "@GRAD"


# ---------------------------------------------------------------------------
# pytree registration so LoD/sparse values flow through jit/vjp transparently
# ---------------------------------------------------------------------------

jax.tree_util.register_pytree_node(
    LoDTensor,
    lambda t: ((t.data,), t.lod),
    lambda lod, kids: LoDTensor(kids[0], lod),
)
jax.tree_util.register_pytree_node(
    SelectedRows,
    lambda s: ((s.rows, s.value), s.height),
    lambda height, kids: SelectedRows(kids[0], kids[1], height),
)
jax.tree_util.register_pytree_node(
    TensorArray,
    lambda a: (tuple(a.tensors), None),
    lambda _, kids: TensorArray(list(kids)),
)


# ---------------------------------------------------------------------------
# execution context
# ---------------------------------------------------------------------------


def step_key(seed, step):
    """The key of one step of a Program: its seed's key with the count
    of the executor's runs folded in.  Called with two integers it is
    two executables sent to the device; a compiled step calls it with
    its two traced scalars and makes the same key inside itself."""
    return jax.random.fold_in(jax.random.key(seed), step)


class ExecContext:
    """Passed to every lowering.  Provides deterministic per-op PRNG keys and
    access to host-side facilities for interpreter-only ops.

    Key derivation: run_op folds a stable hash of the op's identity
    (type + output var names; for a generic grad op, its FORWARD op's
    identity) into the step key, so (a) randomness is independent of op
    order, and (b) the VJP re-trace of a random forward op (e.g. nce)
    draws exactly the forward's samples."""

    def __init__(self, rng_key=None, scope=None, executor=None, compiled=False):
        self._rng_key = rng_key
        self._rng_counter = 0
        self.scope = scope
        self.executor = executor
        self.compiled = compiled

    @property
    def platform(self) -> str:
        """Backend the ops under this context run on: the driving
        Executor's place when there is one, else the process default
        (the mesh executors build their meshes from jax.devices()).
        Lowerings that choose a backend-specific kernel read this, not
        jax.default_backend() — a CPUPlace executor on a TPU host must
        not trace Mosaic calls."""
        if self.executor is not None:
            return self.executor.place.jax_device().platform
        return jax.default_backend()

    def rng(self):
        """A fresh PRNG key, deterministic per (base key, call index)."""
        if self._rng_key is None:
            self._rng_key = jax.random.key(0)
        k = jax.random.fold_in(self._rng_key, self._rng_counter)
        self._rng_counter += 1
        return k

    def child(self, tag_hash: int) -> "ExecContext":
        """Per-op context: base key folded with the op-identity hash."""
        base = self._rng_key if self._rng_key is not None else jax.random.key(0)
        c = ExecContext(jax.random.fold_in(base, tag_hash & 0x7FFFFFFF),
                        self.scope, self.executor, self.compiled)
        return c

    def pure(self) -> "ExecContext":
        """Context for re-tracing a forward op inside its VJP: same rng
        stream restarted so forward recomputation matches (XLA CSEs it).
        Carries op/env so sub-block ops (dynamic_rnn) stay resolvable."""
        c = ExecContext(self._rng_key, self.scope, self.executor, self.compiled)
        c.op = getattr(self, "op", None)
        c.env = getattr(self, "env", None)
        c.root = getattr(self, "root", None)
        return c


# ---------------------------------------------------------------------------
# env protocol: interpreter uses Scope, tracer uses plain dict
# ---------------------------------------------------------------------------


class DictEnv:
    def __init__(self, init=None):
        self.d = dict(init or {})
        self.written = set()

    def get(self, name):
        return self.d.get(name)

    def set(self, name, value):
        self.d[name] = value
        self.written.add(name)

    def has(self, name):
        return name in self.d


class ScopeEnv:
    def __init__(self, scope):
        self.scope = scope
        self.written = set()

    def get(self, name):
        try:
            return self.scope.find_var(name)
        except KeyError:
            return None

    def set(self, name, value):
        self.scope.set_var(name, value)
        self.written.add(name)

    def has(self, name):
        return self.scope.has_var(name)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

from .framework import EMPTY_VAR_NAMES as _EMPTY


def gather_inputs(op, env) -> Dict[str, List]:
    return {
        slot: [env.get(n) if n not in _EMPTY else None for n in names]
        for slot, names in op.inputs.items()
    }


def scatter_outputs(op, env, outs: Dict[str, List]):
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        for name, val in zip(names, vals):
            if name not in _EMPTY:
                env.set(name, val)


def _op_rng_tag(op, info) -> str:
    """Stable op identity for PRNG key derivation.  A generic grad op gets
    its FORWARD op's tag (forward output names appear among the grad op's
    input slots), so VJP recomputation samples the same randomness."""
    if info.type != op.type:  # generic "<fwd>_grad"
        names = tuple(n for s in info.outputs for n in op.inputs.get(s, []))
        return f"{info.type}:{names}"
    return f"{op.type}:{tuple(op.output_names())}"


def run_op(ctx: ExecContext, op, env):
    """Execute one op desc against `env` (eager or traced)."""
    ins = gather_inputs(op, env)
    t = op.type
    try:
        info = registry.get_op_info(t)
    except KeyError:
        raise NotImplementedError(f"op '{t}' has no lowering") from None
    import zlib

    tag_hash = zlib.crc32(_op_rng_tag(op, info).encode())
    # PipelineExecutor's ONE traced stage body executes stage 0's op
    # descs for EVERY stage; tag_lookup substitutes the per-stage op's
    # serial identity (a traced int selected by the stage index) so a
    # random op in stage s draws exactly what the serial executor's
    # stage-s op would — see pipeline_program._make_jit_step
    lookup = getattr(ctx, "tag_lookup", None)
    if lookup is not None:
        traced_tag = lookup(op)
        if traced_tag is not None:
            tag_hash = traced_tag
    op_ctx = ctx.child(tag_hash)
    op_ctx.op = op
    op_ctx.env = env
    op_ctx.root = ctx
    # named scope per op: XLA op metadata carries "<type>:<first output>",
    # so device profiles/HLO dumps attribute fusions back to program ops
    # (reference executor.cc:124 wraps each op run in a RecordEvent; inside
    # a jit trace the scope name is the compile-time analogue)
    outs_names = op.output_names()
    scope = f"{t}:{outs_names[0]}" if outs_names else t
    with jax.named_scope(scope):
        if info.type == t:  # explicit lowering (fwd op, or custom grad)
            outs = info.lower(op_ctx, ins, {**info.attrs, **op.attrs})
        else:  # generic "<fwd>_grad" resolved to forward info
            outs = generic_grad_lower(op_ctx, ins,
                                      {**info.attrs, **op.attrs}, info)
    scatter_outputs(op, env, outs)


# ---------------------------------------------------------------------------
# generic VJP gradient
# ---------------------------------------------------------------------------


def _leaf_is_float(v) -> bool:
    leaves = jax.tree_util.tree_leaves(v)
    return bool(leaves) and all(
        jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) for x in leaves
    )


def generic_grad_lower(ctx, ins, attrs, fwd_info):
    """Grad-op convention (see backward.py): inputs = forward input slots +
    forward output slots + "<out_slot>@GRAD" cotangents; outputs =
    "<in_slot>@GRAD".  Missing cotangents must have been filled with
    fill_zeros_like by the backward builder."""
    fwd_ins = {
        s: ins[s] for s in fwd_info.inputs if s in ins and ins[s] is not None
    }
    # which inputs to differentiate
    if fwd_info.diff_inputs is not None:
        diff_slots = [s for s in fwd_info.diff_inputs if s in fwd_ins]
    else:
        diff_slots = [s for s in fwd_ins if _leaf_is_float(fwd_ins[s])]
    # which outputs carry cotangents
    if fwd_info.diff_outputs is not None:
        out_slots = [s for s in fwd_info.diff_outputs if s + GRAD in ins]
    else:
        out_slots = [s for s in fwd_info.outputs if s + GRAD in ins]
    if not diff_slots or not out_slots:
        return {}

    pure_ctx = ctx.pure()

    def fwd_fn(diff_vals):
        full = dict(fwd_ins)
        full.update(diff_vals)
        outs = fwd_info.lower(pure_ctx, full, attrs)
        res = {}
        for s in out_slots:
            v = outs[s]
            res[s] = v if isinstance(v, (list, tuple)) else [v]
        return res

    primals = {s: fwd_ins[s] for s in diff_slots}
    out_primals, vjp_fn = jax.vjp(fwd_fn, primals)
    cotangents = {}
    for s in out_slots:
        v = ins[s + GRAD]
        cotangents[s] = list(v) if isinstance(v, (list, tuple)) else [v]
    # jax.vjp demands cotangent avals match the primal outputs exactly;
    # under amp a downstream grad op may hand back a bf16 cotangent for
    # an f32 forward output (or vice versa) — cast leaf-wise to match
    def _cast_like(c, p):
        pd, cd = data_of(p), data_of(c)
        if (pd is None or cd is None or not hasattr(cd, "dtype")
                or cd.dtype == pd.dtype
                or not jnp.issubdtype(pd.dtype, jnp.floating)):
            return c
        if isinstance(c, LoDTensor):
            return LoDTensor(cd.astype(pd.dtype), c.lod)
        return cd.astype(pd.dtype)

    for s in out_slots:
        cotangents[s] = [_cast_like(c, p)
                         for c, p in zip(cotangents[s], out_primals[s])]
    (gin,) = vjp_fn(cotangents)
    return {s + GRAD: gin[s] for s in diff_slots}


# ---------------------------------------------------------------------------
# lowering helper utilities (imported by op modules)
# ---------------------------------------------------------------------------


def one(ins, slot):
    """Single (required) input value for a slot; unwraps length-1 lists."""
    v = ins.get(slot)
    if v is None:
        return None
    if isinstance(v, (list, tuple)):
        return v[0] if v else None
    return v


def many(ins, slot):
    v = ins.get(slot)
    if v is None:
        return []
    return list(v) if isinstance(v, (list, tuple)) else [v]


def data_of(v):
    """Dense array behind a value (LoDTensor -> .data)."""
    if isinstance(v, LoDTensor):
        return v.data
    return v


def with_lod_of(v, out_data):
    """Rewrap out_data with v's LoD if v carried one."""
    if isinstance(v, LoDTensor):
        return LoDTensor(out_data, v.lod)
    return out_data
