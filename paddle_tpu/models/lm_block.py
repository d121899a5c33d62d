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

  mellum-like  the third description (Mellum2-12B-A2.5B's config.json,
               `model_type: mellum`), with no constructor of its own:
               OLMoE's block without the QK-norm, plus the ATTENTION
               GEOMETRY fields: grouped-query heads (`n_kv_heads`) of a
               size that is not `d_model / n_heads` (`d_head`), a KIND
               for each layer (`layer_types`: "sliding_attention" sees
               the last `window` positions, itself included;
               "full_attention" sees all), and RoPE parameters per kind
               (`rope_parameters`: "default" or "yarn").

All have an untied output head; a tied one would be one more field.
The fields are NOT free axes yet: those points of the space are the
ones that are built and tested, and `param_layout` refuses any other
combination by name rather than build something untried.

The functions (`norm`, `rope_tables`, `rope`, `route`, `moe_ffn`) are
pure `jax.numpy` over arrays: the step calls them under its own
`jax.named_scope` table (docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import types
from typing import Dict, Tuple

__all__ = ["BlockSpec", "OPT", "olmoe", "param_layout", "norm",
           "rope_tables", "yarn_inv_freq", "rope", "route", "moe_ffn",
           "MOE_COMPILER_SCOPES", "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"


def _frozen(value):
    """A JSON value as something a frozen dataclass can hash: lists
    become tuples, dicts sorted tuples of (key, value) pairs."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One decoder block.  Valid today: `OPT`, what `olmoe(...)`
    returns (any expert count, top-k, theta, eps), and OLMoE's block
    with `qk_norm` off and the attention geometry below (module
    docstring).  `layer_types` and `rope_parameters` may be given as
    the JSON list and dict a config.json holds: they are kept as
    (nested) tuples, so the description stays hashable."""
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
    # -- attention geometry: 0 / empty = "as the model's width gives it"
    n_kv_heads: int = 0             # K/V heads (0: one a query head)
    d_head: int = 0                 # a head's size (0: d_model/n_heads)
    layer_types: tuple = ()         # SLIDING | FULL a layer (): all FULL
    window: int = 0                 # keys a SLIDING layer sees
    rope_parameters: tuple = ()     # {kind: {"rope_type", ...}}, frozen

    def __post_init__(self):
        for name in ("layer_types", "rope_parameters"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"layer_types: unknown kind(s) {sorted(bad)}")
        if SLIDING in self.layer_types and self.window < 1:
            raise ValueError(f"{SLIDING} layers need window >= 1")

    def heads(self, d_model: int, n_heads: int):
        """(K/V heads, head size) at a model width and query heads."""
        d_head = self.d_head or d_model // n_heads
        n_kv = self.n_kv_heads or n_heads
        if n_heads % n_kv:
            raise ValueError(
                f"{n_heads} query heads do not share {n_kv} K/V heads")
        return n_kv, d_head

    def kind_of(self, layer: int) -> str:
        """Layer `layer`'s kind; a depth cut short of the list takes
        its first entries, a description without kinds is all FULL."""
        if not self.layer_types:
            return FULL
        if layer >= len(self.layer_types):
            raise ValueError(
                f"block {self.name!r}: {len(self.layer_types)} "
                f"layer_types, and a layer {layer}")
        return self.layer_types[layer]

    def rope_of(self, kind: str) -> dict:
        """RoPE parameters of a layer kind: the kind's entry of
        `rope_parameters`, else plain RoPE at `rope_theta`."""
        params = dict(self.rope_parameters).get(kind)
        if params is None:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return dict(params)


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
                 n_heads: int, n_layers: int, d_inner: int):
    """(layout, shapes) of a block whose parameters are named by the
    description itself (no training Program).  `layout` is what the
    step reads: `.tok`, `.pos` (None under RoPE), `.layers[l]` (a dict
    of (weight-or-scale, bias-or-shift-or-None) name pairs), `.final`,
    `.head`.  Q and O are [d, H*dh] and [H*dh, d], K and V
    [d, Hkv*dh]: all [d, d] where the heads split the model's width."""
    if (spec.norm, spec.positions, spec.ffn, spec.bias) != (
            "rms_norm", "rope", "moe_swiglu", False):
        raise NotImplementedError(
            f"block {spec.name!r}: only the OLMoE combination is laid "
            "out from its description; OPT's names come from the "
            "training Program")
    n_kv, d_head = spec.heads(d_model, n_heads)
    if spec.qk_norm and (n_kv * d_head, n_heads * d_head) != (
            d_model, d_model):
        raise NotImplementedError(
            f"block {spec.name!r}: qk_norm is built over all of Q and "
            "K at the model's width, not per head of a grouped or "
            "wider geometry")
    for kind in set(spec.layer_types) or {FULL}:
        if spec.rope_of(kind)["rope_type"] not in ("default", "yarn"):
            raise NotImplementedError(
                f"block {spec.name!r}: rope_type "
                f"{spec.rope_of(kind)['rope_type']!r} on {kind} layers")
    d, e, f = int(d_model), spec.n_experts, int(d_inner)
    dq, dkv = n_heads * d_head, n_kv * d_head
    shapes: Dict[str, Tuple[int, ...]] = {}

    def add(name, *shape):
        shapes[name] = tuple(int(s) for s in shape)
        return name, None

    layers = []
    for l in range(n_layers):
        p = f"layer_{l}."
        lay = {"norm1": add(p + "attn_norm.scale_0", d),
               "q": add(p + "q_proj.w_0", d, dq),
               "k": add(p + "k_proj.w_0", d, dkv),
               "v": add(p + "v_proj.w_0", d, dkv),
               "o": add(p + "o_proj.w_0", dq, d),
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


def yarn_inv_freq(params: dict, d_head: int):
    """YaRN's (arXiv:2309.00071) per-pair frequencies, float64 numpy
    [d_head/2]: pairs that turn more than `beta_fast` times over the
    original context keep RoPE's frequency, pairs that turn fewer than
    `beta_slow` times take it divided by `factor`, a linear ramp
    between.  Static: one table at every length."""
    import math

    import numpy as np

    theta, d = float(params["rope_theta"]), int(d_head)
    extrap = theta ** -(np.arange(0, d, 2, dtype=np.float64) / d)
    interp = extrap / float(params["factor"])

    def pair_of(turns):
        return d * math.log(params["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (
                                2 * math.log(theta))

    low = max(math.floor(pair_of(params["beta_fast"])), 0)
    high = min(math.ceil(pair_of(params["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def rope_tables(spec: BlockSpec, positions, d_head: int, kind: str = FULL):
    """cos and sin [..., d_head] of each position's rotation on a layer
    of kind `kind`, float32: column j and column j + d_head/2 of a
    head turn together by position * theta**(-2j/d_head) (the
    rotate-half form), or by YaRN's frequency with both tables times
    its `attention_factor`."""
    import jax.numpy as jnp

    params = spec.rope_of(kind)
    if params["rope_type"] == "yarn":
        inv = jnp.asarray(yarn_inv_freq(params, d_head), jnp.float32)
    else:
        half = jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head
        inv = params["rope_theta"] ** -half
    ang = positions[..., None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    if params["rope_type"] == "yarn":
        gain = jnp.float32(params["attention_factor"])
        return jnp.cos(ang) * gain, jnp.sin(ang) * gain
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
# `ragged_dot`s (the fallback where the Pallas grouped matmul is
# refused: one offsets call, then a grouped matmul each), under
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
            scope=None, experts=None):
    """Dropless top-k-of-E SwiGLU expert layer over tokens m [T, D]
    (float32) -> ([T, D] float32, experts hit: int32 scalar, routing:
    `route`'s (weights, experts)).

    Every one of the T*k assignments is computed: they are sorted by
    expert and run as grouped matmuls (one group an expert), so an
    expert's matrices are read once however many rows it has and NO
    capacity bounds a group: what one token gets never depends on
    where the others went, which is what keeps a continuously batched
    sequence bit-identical to the same sequence alone.  Routing is
    `route`'s; the expert matmuls take the weights' dtype with float32
    accumulation.  A token's k results are summed in top-k order.

    `experts` is what `kernels.grouped_matmul.select_grouped_matmul`
    returned for these shapes: the Pallas kernel (gate, up and the
    gated product in one call, down in a second, over work items it
    plans from the group sizes under `moe_dispatch`), or None: three
    `jax.lax.ragged_dot`s, the one fallback."""
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
        plan = None if experts is None else experts.plan(sizes)
    with scope("moe_experts"):
        f32 = jnp.float32
        if experts is not None:
            act = experts.gate_up(rows, w_gate, w_up, plan)
            out = experts.down(act, w_down, plan)
        else:
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
