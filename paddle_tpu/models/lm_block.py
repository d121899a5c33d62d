"""The decoder-only LM's block as a DESCRIPTION, and the plain
functions a serving step computes it with.

`build_lm_paged_decoder` (models/transformer.py) was written for one
block: LayerNorm, biased projections, a learned position table added
at the embedding, a two-matrix ReLU FFN.  A `BlockSpec` says which of
each the block has; the builder derives the step's parameter names and
shapes (`param_layout`) and the step itself from it, so a second
architecture is a second description and not a second decoder.

  OPT          LayerNorm, learned positions, biases, ReLU FFN.  Its
               names and shapes come from the training Program
               (`transformer._lm_param_structure`), not from here.
  olmoe(...)   OLMoE (arXiv:2409.02060; `model_type: olmoe`): RMSNorm,
               RoPE (rotate-half, per head), RMSNorm on all of Q and K
               before the head split, no bias anywhere, and a DROPLESS
               top-k-of-E SwiGLU expert layer with float32 routing.

Both have an untied output head; a tied one would be one more field.
The fields are NOT five free axes yet: those two points of the space
are the ones that are built and tested, and `param_layout` refuses any
other combination by name rather than build something untried.

The functions (`norm`, `rope_tables`, `rope`, `route`, `moe_ffn`) are
pure `jax.numpy` over arrays: the step calls them under its own
`jax.named_scope` table (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import types
from typing import Dict, Tuple

__all__ = ["BlockSpec", "OPT", "olmoe", "param_layout", "norm",
           "rope_tables", "rope", "route", "moe_ffn", "MOE_COMPILER_SCOPES"]


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One decoder block.  Valid today: `OPT` and what `olmoe(...)`
    returns (any expert count, top-k, theta, eps)."""
    name: str
    norm: str                       # "layer_norm" | "rms_norm"
    positions: str                  # "learned" | "rope"
    ffn: str                        # "relu" | "moe_swiglu"
    bias: bool                      # on every projection and the head
    qk_norm: bool = False           # a norm on all of Q and of K
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    n_experts: int = 0
    experts_per_token: int = 0
    norm_topk_prob: bool = False    # renormalise the top-k weights


OPT = BlockSpec(name="opt", norm="layer_norm", positions="learned",
                ffn="relu", bias=True)


def olmoe(n_experts: int = 64, experts_per_token: int = 8,
          rope_theta: float = 10000.0, norm_eps: float = 1e-5,
          norm_topk_prob: bool = False) -> BlockSpec:
    return BlockSpec(name="olmoe", norm="rms_norm", positions="rope",
                     ffn="moe_swiglu", bias=False, qk_norm=True,
                     norm_eps=float(norm_eps),
                     rope_theta=float(rope_theta),
                     n_experts=int(n_experts),
                     experts_per_token=int(experts_per_token),
                     norm_topk_prob=bool(norm_topk_prob))


def param_layout(spec: BlockSpec, vocab_size: int, d_model: int,
                 n_layers: int, d_inner: int):
    """(layout, shapes) of a block whose parameters are named by the
    description itself (no training Program).  `layout` is what the
    step reads: `.tok`, `.pos` (None under RoPE), `.layers[l]` (a dict
    of (weight-or-scale, bias-or-shift-or-None) name pairs), `.final`,
    `.head`."""
    if (spec.norm, spec.positions, spec.ffn, spec.bias) != (
            "rms_norm", "rope", "moe_swiglu", False):
        raise NotImplementedError(
            f"block {spec.name!r}: only the OLMoE combination is laid "
            "out from its description; OPT's names come from the "
            "training Program")
    d, e, f = int(d_model), spec.n_experts, int(d_inner)
    shapes: Dict[str, Tuple[int, ...]] = {}

    def add(name, *shape):
        shapes[name] = tuple(int(s) for s in shape)
        return name, None

    layers = []
    for l in range(n_layers):
        p = f"layer_{l}."
        lay = {"norm1": add(p + "attn_norm.scale_0", d),
               "q": add(p + "q_proj.w_0", d, d),
               "k": add(p + "k_proj.w_0", d, d),
               "v": add(p + "v_proj.w_0", d, d),
               "o": add(p + "o_proj.w_0", d, d),
               "norm2": add(p + "ffn_norm.scale_0", d),
               "router": add(p + "router.w_0", d, e),
               "gate": add(p + "experts_gate.w_0", e, d, f),
               "up": add(p + "experts_up.w_0", e, d, f),
               "down": add(p + "experts_down.w_0", e, f, d)}
        if spec.qk_norm:
            lay["q_norm"] = add(p + "q_norm.scale_0", d)
            lay["k_norm"] = add(p + "k_norm.scale_0", d)
        layers.append(lay)
    layout = types.SimpleNamespace(
        tok=add("tok_embedding.w_0", vocab_size, d)[0], pos=None,
        layers=layers, final=add("final_norm.scale_0", d),
        head=add("lm_head.w_0", d, vocab_size))
    return layout, shapes


def norm(spec: BlockSpec, x, scale, shift=None):
    """LayerNorm (scale and shift) or RMSNorm (scale) over the last
    axis, in x's dtype (float32 in the step)."""
    import jax.numpy as jnp

    if spec.norm == "rms_norm":
        ms = (x * x).mean(-1, keepdims=True)
        return x / jnp.sqrt(ms + spec.norm_eps) * scale
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + spec.norm_eps) * scale + shift


def rope_tables(spec: BlockSpec, positions, d_head: int):
    """cos and sin [..., d_head] of each position's rotation, float32:
    column j and column j + d_head/2 of a head turn together by
    position * theta**(-2j/d_head) (the rotate-half form)."""
    import jax.numpy as jnp

    half = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
    ang = positions[..., None].astype(jnp.float32) * (
        spec.rope_theta ** -half)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin, n_heads: int):
    """Rotate x [..., n_heads * d_head] head by head; cos/sin are
    `rope_tables` of x's leading axes."""
    import jax.numpy as jnp

    lead, d_head = x.shape[:-1], x.shape[-1] // n_heads
    xh = x.reshape(lead + (n_heads, d_head))
    a, b = xh[..., : d_head // 2], xh[..., d_head // 2:]
    turned = jnp.concatenate([-b, a], axis=-1)
    out = xh * cos[..., None, :] + turned * sin[..., None, :]
    return out.reshape(x.shape)


# What the TPU compiler calls the instructions it makes of `moe_ffn`'s
# `ragged_dot`s (one offsets call, then a grouped matmul each), under
# an `op_name` of its own that drops the scope they were traced under:
# {its op_name: the step's scope}, for `profiler.register_jitted`.
MOE_COMPILER_SCOPES = {"ragged-dot-none": "paged_decoder/moe_experts",
                       "ragged-dot-metadata": "paged_decoder/moe_dispatch"}


def route(spec: BlockSpec, m, w_router):
    """The router: tokens m [T, D] (float32) -> (weights [T, k]
    float32, experts [T, k] int32), the k largest of the softmax over
    ALL experts, largest first.  Float32 at `highest` precision (one
    bf16 pass moves a probability by 1e-3 of itself and swaps the k-th
    and k+1-th expert wherever they lie that close); the weights are
    the probabilities as they are, renormalised only under
    `norm_topk_prob`."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(m, w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                 # [T, E]
    top_w, top_e = jax.lax.top_k(probs, spec.experts_per_token)
    if spec.norm_topk_prob:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    return top_w, top_e


def moe_ffn(spec: BlockSpec, m, w_router, w_gate, w_up, w_down,
            scope=None):
    """Dropless top-k-of-E SwiGLU expert layer over tokens m [T, D]
    (float32) -> ([T, D] float32, experts hit: int32 scalar, routing:
    `route`'s (weights, experts)).

    Every one of the T*k assignments is computed: they are sorted by
    expert and run as three grouped matmuls (`jax.lax.ragged_dot`, one
    group an expert), so an expert's matrices are read once however
    many rows it has and NO capacity bounds a group: what one token
    gets never depends on where the others went, which is what keeps a
    continuously batched sequence bit-identical to the same sequence
    alone.  Routing is `route`'s; the expert matmuls take the weights'
    dtype with float32 accumulation.  A token's k results are summed
    in top-k order."""
    import contextlib

    import jax
    import jax.numpy as jnp

    scope = scope or (lambda name: contextlib.nullcontext())
    t_n, k_n, e_n = m.shape[0], spec.experts_per_token, spec.n_experts
    with scope("moe_router"):
        top_w, top_e = route(spec, m, w_router)             # [T, k]
    with scope("moe_dispatch"):
        flat_e = top_e.reshape(t_n * k_n)
        order = jnp.argsort(flat_e, stable=True)            # by expert
        sizes = jnp.zeros(e_n, jnp.int32).at[flat_e].add(1)
        rows = m[order // k_n].astype(w_gate.dtype)         # [T*k, D]
        hit = jnp.sum(sizes > 0).astype(jnp.int32)
    with scope("moe_experts"):
        f32 = jnp.float32
        gate = jax.lax.ragged_dot(rows, w_gate, sizes,
                                  preferred_element_type=f32)
        up = jax.lax.ragged_dot(rows, w_up, sizes,
                                preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(w_down.dtype)
        out = jax.lax.ragged_dot(act, w_down, sizes,
                                 preferred_element_type=f32)
    with scope("moe_combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t_n * k_n, dtype=order.dtype))
        per_tok = out[back].reshape(t_n, k_n, -1)
        y = (per_tok * top_w[..., None]).sum(axis=1)
    return y, hit, (top_w, top_e)
