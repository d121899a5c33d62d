"""Operator registry: the TPU-native answer to the reference's OpRegistry.

Reference: /root/reference/paddle/fluid/framework/op_registry.h:127-241
(`REGISTER_OP*` macros) and op_info.h:34 (`OpInfo{grad_op_maker_, infer_shape_}`).

Instead of per-(place,dtype,layout,library) kernel pairs dispatched at runtime
(operator.cc:494 RunImpl), every op registers ONE `lower` function expressed in
jax.numpy / lax.  That single definition serves as:
  * the CPU interpreter kernel (eager execution, debuggable), and
  * the XLA lowering used when a whole Block is traced and jit-compiled
    (core/compiler.py) — kernel fusion, tiling and layout are left to XLA,
    which is the TPU replacement for the hand-written CUDA kernel corpus.

Gradients: ops may register an explicit `grad_maker` (emitting grad-op descs
like the reference's GradOpMaker), but the default is a *generic VJP grad*:
a `<type>_grad` op whose lowering calls `jax.vjp` on the forward lowering.
For XLA's own ops CSE dedupes the re-traced forward, so this costs nothing
after fusion and guarantees analytic gradients exactly consistent with the
forward op.  It does NOT dedupe a Mosaic (Pallas) custom call: the two calls
survive to the final module and the kernel runs twice.  An op whose lowering
is such a kernel saves the kernel's residuals in an output slot and registers
`<type>_grad` with a lowering of its own over the kernel's backward
(ops/attention.py: `flash_attention`'s `LSE`), keeping the generic VJP for
where the kernel is not selected.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence


@dataclasses.dataclass
class OpInfo:
    type: str
    # lower(ctx, ins, attrs) -> {output_slot: [values]}
    lower: Callable = None
    # infer_shape(op, block) -> None ; fills output VarDesc shapes at build time
    infer_shape: Callable = None
    # grad_maker(op, block, no_grad_set) -> list[OpSpec dicts] ; None = generic
    grad_maker: Callable = None
    # input slots that are differentiable (None = all float inputs)
    diff_inputs: Optional[Sequence[str]] = None
    # output slots that are differentiable (None = all)
    diff_outputs: Optional[Sequence[str]] = None
    # declared slot names (for validation / layer autogen); duplicable slots
    # accept a list of vars
    inputs: Sequence[str] = ()
    outputs: Sequence[str] = ()
    # slots that legitimately take MORE THAN ONE var (sum's X, concat's X,
    # split's Out...) — the reference marks these per-slot with
    # AsDuplicable() in the OpMaker (framework.proto OpProto::Var.duplicable);
    # the analysis arity pass flags multi-name bindings to any other slot
    dup_inputs: Sequence[str] = ()
    dup_outputs: Sequence[str] = ()
    # attr defaults
    attrs: Dict = dataclasses.field(default_factory=dict)
    # in-place aliases {output_slot: input_slot} (optimizer ops: ParamOut<-Param)
    inplace: Dict[str, str] = dataclasses.field(default_factory=dict)
    # True if op is stateful/random (needs a PRNG key via ctx)
    random: bool = False
    # True -> never differentiate through (metrics, optimizer ops)
    not_differentiable: bool = False
    # True -> must run on host (save/load, print, readers); forces the
    # executor to interpret rather than trace the enclosing block segment
    host: bool = False
    # static cost metadata (analysis/cost_model.py): `cost_kind` names the
    # estimator class ("matmul", "conv", "attention", "moe", "embedding",
    # "elementwise", "reduction", "norm", "data", "collective", "free");
    # `cost_fn(op, resolve)` (register_op_cost) overrides the class with an
    # exact per-op estimator.  Ops with neither report as cost-UNKNOWN —
    # the analyzer surfaces them instead of silently counting zero.
    cost_kind: Optional[str] = None
    cost_fn: Callable = None


_REGISTRY: Dict[str, OpInfo] = {}


def register_op(
    type: str,
    inputs: Sequence[str] = (),
    outputs: Sequence[str] = (),
    attrs: Dict = None,
    diff_inputs: Optional[Sequence[str]] = None,
    diff_outputs: Optional[Sequence[str]] = None,
    inplace: Dict[str, str] = None,
    random: bool = False,
    not_differentiable: bool = False,
    host: bool = False,
    dup_inputs: Sequence[str] = (),
    dup_outputs: Sequence[str] = (),
    cost: Optional[str] = None,
):
    """Decorator: register `fn` as the lowering for op `type`."""

    def deco(fn):
        info = _REGISTRY.get(type) or OpInfo(type=type)
        info.lower = fn
        if cost is not None:
            info.cost_kind = cost
        info.inputs = tuple(inputs)
        info.outputs = tuple(outputs)
        info.dup_inputs = tuple(dup_inputs)
        info.dup_outputs = tuple(dup_outputs)
        info.attrs = dict(attrs or {})
        info.diff_inputs = diff_inputs
        info.diff_outputs = diff_outputs
        info.inplace = dict(inplace or {})
        info.random = random
        info.not_differentiable = not_differentiable
        info.host = host
        _REGISTRY[type] = info
        return fn

    return deco


def register_infer_shape(type: str):
    def deco(fn):
        info = _REGISTRY.setdefault(type, OpInfo(type=type))
        info.infer_shape = fn
        return fn

    return deco


def register_op_cost(type: str, kind: Optional[str] = None):
    """Attach static cost metadata to op `type`'s OpInfo.

    Used as a decorator, registers an exact estimator
    `fn(op, resolve) -> analysis.cost_model.OpCost` (`resolve(name)`
    returns the `(shape, dtype)` of a var with -1 dims already
    substituted).  Called bare — `register_op_cost("relu",
    kind="elementwise")` — it records just the estimator class.  Either
    form may target an op registered elsewhere (the analysis layer
    annotates the existing corpus without touching every lowering)."""
    info = _REGISTRY.setdefault(type, OpInfo(type=type))
    if kind is not None:
        info.cost_kind = kind

    def deco(fn):
        info.cost_fn = fn
        return fn

    return deco


def set_op_cost_kind(type: str, kind: str, overwrite: bool = False):
    """Record the estimator class for `type` (no-op for unregistered ops
    — cost metadata must never invent op types)."""
    info = _REGISTRY.get(type)
    if info is not None and (overwrite or info.cost_kind is None):
        info.cost_kind = kind


def register_grad_maker(type: str):
    def deco(fn):
        info = _REGISTRY.setdefault(type, OpInfo(type=type))
        info.grad_maker = fn
        return fn

    return deco


def get_op_info(type: str) -> OpInfo:
    info = _REGISTRY.get(type)
    if info is None or info.lower is None:
        # grad ops resolve generically: "<fwd>_grad" with no explicit lowering
        if type.endswith("_grad") and type[: -len("_grad")] in _REGISTRY:
            return _REGISTRY[type[: -len("_grad")]]
        raise KeyError(f"op '{type}' is not registered")
    return info


def has_op(type: str) -> bool:
    try:
        get_op_info(type)
        return True
    except KeyError:
        return False


def registered_ops() -> List[str]:
    return sorted(t for t, i in _REGISTRY.items() if i.lower is not None)
