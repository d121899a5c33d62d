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

  granite-     the fourth (IBM Granite 4.0-H, `model_type:
  hybrid-like  granitemoehybrid`), again fields and no constructor:
               a layer kind with NO attention ("mamba": a Mamba-2
               mixer, arXiv:2405.21060, whose cache is a recurrent
               state of fixed size a lane, `mamba2_step`) beside
               "attention" (full attention under another name), no
               position signal at all (`positions: "none"`), a SHARED
               SwiGLU expert every token takes beside the routed ones
               (`shared_d_inner`), the experts HELD here a contiguous
               range of those routed over (`experts_first`,
               `experts_held`: one chip's share of an expert-parallel
               layer), an output head tied to the embedding
               (`tied_head`) and four scalar multipliers.  Its router
               needs no kind of its own: Granite takes the k largest
               LOGITS and a softmax over those k, which is `route`
               under `norm_topk_prob` (p_i / sum of the chosen p_j =
               exp(l_i) / sum of the chosen exp(l_j)).

  looped       the fifth (ByteDance Ouro, arXiv:2510.25741, `model_type:
  dense-like   ouro`), fields again: ONE stack of layers run `passes`
               times a token over the same weights, every (pass,
               layer) pair with K/V of its own; a dense SwiGLU FFN
               (`ffn: "swiglu"`, `swiglu` below: what Granite's shared
               expert already computes); an RMSNorm on each
               sub-block's OUTPUT before it joins the residual stream
               as well as on its input (`post_norm`: four scales a
               layer); the ONE final norm applied after EVERY pass,
               its output what the next pass starts from; and an exit
               gate, one linear map to a scalar and a sigmoid, read
               after each pass (`exit_gate`).  With
               `early_exit_threshold` 1 the gate decides nothing (all
               passes run for every token); a threshold under 1 is
               refused by name: lanes of one tick would run different
               numbers of passes.

  layer-kinds  the sixth (LG K-EXAONE, `model_type: exaone_moe`, whose
  MoE-like     keys are DeepSeek-V3's), fields again: an FFN KIND A
               LAYER (`mlp_layer_types`: a "dense" layer takes one
               SwiGLU of width `dense_d_inner` where a "sparse" one
               takes the experts and the shared expert; a leading dense
               layer before the sparse ones); the QK-norm PER HEAD
               (`qk_norm_per_head`: one scale of `d_head` for all heads
               of Q, one for K, over each head's own columns, on a
               grouped geometry); a position signal BY LAYER KIND
               (`rope_layers`: the kinds RoPE turns, here the sliding
               ones; a full layer then carries no position at all);
               and a router of its own (`router: "sigmoid"`): scores
               sigmoid(logits), a bias added for the CHOICE of the k
               alone (`router_bias`), the chosen scores renormalised
               (`norm_topk_prob`) and scaled (`routed_scaling_factor`).
               Ring, table, held experts and the shared expert are the
               third and fourth descriptions', together for the first
               time.

  latent-      the seventh (DeepSeek-V2, arXiv:2405.04434, `model_type:
  cache-like   deepseek_v2`), fields again: an ATTENTION KIND that is
               not Q, K, V, O (`kv_lora_rank` > 0).  The query goes
               down to `q_lora_rank`, through an RMSNorm and up to H
               heads of `qk_nope_head_dim` + `qk_rope_head_dim`
               columns; key and value go down TOGETHER to
               `kv_lora_rank` + `qk_rope_head_dim` columns: a latent
               (RMSNorm over the latent alone) and ONE rotated key
               part all heads share.  That row is all the cache holds
               a position a layer; a per-head matrix [kv_lora_rank,
               qk_nope_head_dim + v_head_dim] would widen it to keys
               and values, and the step never does: it multiplies the
               query's unrotated part by the key half of that matrix
               (the ABSORBED form: scores and context over the latent
               row itself) and the context by its value half.  RoPE
               turns `qk_rope_head_dim` columns alone; the query/key
               width is not the value width.  And a GROUP-LIMITED
               softmax router (`n_group`, `topk_group`: the experts in
               `n_group` consecutive groups, a group's score its
               largest probability, the k chosen from the `topk_group`
               best groups), its weights the probabilities times
               `routed_scaling_factor`, not renormalised.  Dense layer,
               held experts and the shared expert are the sixth's.

  double-      the eighth (Meituan LongCat-Flash, `model_type:
  layer-like   longcat_flash`), fields again: a LAYER THAT IS TWO
               SUB-BLOCKS (`sub_blocks` 2), each a latent attention
               with a cache plane of its own and a dense SwiGLU of
               `dense_d_inner`, on four norms, and ONE expert layer
               across them (the SHORTCUT): it reads what the FIRST
               dense FFN reads (the first sub-block's normed state
               after attention) and its result joins the stream after
               the SECOND dense FFN, so that an expert-parallel
               deployment exchanges rows while the second sub-block
               computes.  A router WIDER than its experts
               (`zero_experts`: columns `n_experts` onward are IDENTITY
               experts, which have no matrices; an assignment to one
               adds its weight times the expert layer's input and sends
               no row to the grouped matmul), a softmax over all
               columns with a CHOICE BIAS beside it (`router_bias`: the
               sixth's, under the other router), the weights p x
               `routed_scaling_factor`, not renormalised.  And a
               constant on each normed latent (`scale_q_lora`,
               `scale_kv_lora`: sqrt(d_model / rank)).  The latent
               cache and the held experts are the seventh's.

  selected-    the ninth (Zhipu GLM-5.2, `model_type: glm_moe_dsa`, whose
  latent-like  sparse attention is DeepSeek-V3.2's), fields again: a
               LIGHTNING INDEXER beside the latent attention
               (`index_topk` > 0).  On a layer whose `indexer_types`
               entry is "full" the normed query latent goes up to
               `index_n_heads` index queries of `index_head_dim`
               columns, the block's normed input down to ONE index key
               a position (a LayerNorm with a shift over it; RoPE on
               the first `qk_rope_head_dim` columns of both) and to a
               weight a head; the index score of a query position
               against an earlier one is the head-weighted sum of
               relu(query . key), and attention on that layer is the
               softmax over the `index_topk` positions of largest
               score alone (all of them while there are fewer; a tie
               to the lower position).  The index key is a SECOND kind
               of cache row, a plane a "full" layer on the latent
               rows' own block table.  A "shared" layer has no indexer
               and attends over the rows the nearest earlier "full"
               layer selected in the same step.  A value head
               (`v_head_dim`) may be wider than a key's unrotated
               part.  The router is the sixth's, dense layers and the
               held experts the seventh's.

  short-conv-   the tenth (LiquidAI LFM2, `model_type: lfm2_moe`), fields
  hybrid-like  again: a layer kind whose whole memory is A FEW ROWS
               ("conv": a gated short convolution, `short_conv_step`:
               the normed input goes to three gates B, C, u of the
               model's width, the product B * u through a depthwise
               causal convolution of `conv_width` taps with NO
               activation, times C, and back through one matrix; what a
               lane keeps is the last `conv_width - 1` rows of B * u,
               the fourth description's convolution tail at another
               width and with no state beside it) among full-attention
               layers under RoPE, so that the table is written by a
               MINORITY of the layers; three kinds of layer by two
               kinds of FFN in one stack (`mlp_layer_types` beside
               `layer_types` with "conv" in it); and the sixth's router
               with 1e-6 added to the sum the chosen scores are
               divided by (`norm_topk_eps`).

  delta-rule-  the eleventh (Upstage Solar-Open2, `model_type:
  hybrid-like  solar_open2`, whose linear mixer is Kimi Delta Attention's,
               arXiv:2510.26692), fields again: a layer kind whose memory
               is a MATRIX a head that a rank-one correction rewrites
               ("delta_rule": a gated delta rule, `delta_rule_step`: the
               normed input goes to q, k and v of `delta_heads` heads of
               `delta_d_head` columns, each through a depthwise causal
               convolution of `delta_conv` taps and a SiLU, q and k to
               unit length a head; a log decay a key CHANNEL and a write
               strength a head come from the input too; the state S
               [keys, values] a head decays by channel, is corrected by
               beta k (v - k^T S)^T and read by q; the result goes
               through a norm a head and a sigmoid gate and back through
               one matrix.  What a lane keeps is S in float32 and the last
               `delta_conv - 1` rows of q | k | v before the convolution:
               the fourth description's state and tail at other shapes)
               among full-attention layers with NO position signal, whose
               output passes a sigmoid gate of the layer's input before
               `o` (`attention_gate`), a minority of the layers.  The
               router is the second's renormalised, the held experts and
               the shared expert the fourth's.

  delta-rule-  the twelfth (inclusionAI Ling-3.0-flash, `model_type:
  latent-like  bailing_hybrid`), fields again, and the first COMPOSITION:
               the eleventh's delta-rule lanes BESIDE the seventh's
               latent table in one block (a period of layers of which
               the last is latent attention and the others delta rules),
               so that a lane keeps a matrix state and a tail a delta
               layer and the table ONE latent row a position for the
               minority of layers that attend.  Both gates of the delta
               rule FULL matrices (`delta_gate_rank` 0: one [d, H*K]
               matrix each, no low-rank pair, no bias on the output
               gate) and the decay BOUNDED (`delta_gate_floor` f < 0:
               g = f * sigmoid(exp(A_log) * (z W_f + dt_bias)), so that
               a channel's decay a step lies in (e^f, 1)); RoPE on the
               latent layers and NO position signal on the lanes'
               layers; a latent query WITHOUT a low-rank step
               (`q_lora_rank` 0: one matrix, no `q_a`, no norm); the
               latent layer's heads each times a sigmoid SCALAR of the
               layer's input before `o` (`attention_gate_per_head`: a
               matrix [d, H]); dense layers before the sparse ones
               beside the lanes (`mlp_layer_types`); the sixth's
               sigmoid router with its choice bias under the seventh's
               GROUP LIMIT, a group's score the SUM OF ITS TWO LARGEST
               biased scores (`group_score: "top2_sum"`); and a CLAMP a
               layer on the experts' and the shared expert's SwiGLU
               inputs (`expert_swiglu_limits`, `shared_swiglu_limits`).

  latent-ring- the thirteenth (dots-studio dots3-note-prev, `model_type:
  selected-    dots3_note`), fields again, and the second COMPOSITION: the
  latent-like  third's ring and table with BOTH on latent rows, the
               table's under the ninth's lightning indexer.  A SLIDING
               layer is latent attention with a geometry OF ITS OWN
               (`sliding_n_heads`, `sliding_q_lora_rank`,
               `sliding_kv_lora_rank`, `sliding_qk_nope_head_dim`,
               `sliding_qk_rope_head_dim`, `sliding_v_head_dim`: other
               heads, another latent rank and another unrotated key part
               than the full layers'; 0: the block's one set), RoPE
               parameters of its own (`rope_parameters` by kind) and a
               scale of its own (1 / sqrt of ITS head); its cache is a
               ring of latent rows a LANE, one row [its latent | its
               rotated key part] a position, at ITS row width, and the
               window need not be a whole number of blocks (the ring is
               the blocks that hold a window, under a mask of the last
               `window` rows).  A FULL layer is the ninth's selected
               latent on the table; a sliding layer computes no selection
               and reuses none (`INDEX_NONE`: it attends over its
               window), so a selection is never shared across one.  Each
               normed latent times sqrt(d_model / ITS OWN rank)
               (`scale_q_lora`, `scale_kv_lora`, by kind), and on both
               kinds a sigmoid scalar a head of the layer's input before
               `o` (`attention_gate_per_head`, the twelfth's, here with
               no delta-rule layer in the block).  The router is the
               sixth's, the dense layer and the held experts the
               seventh's.

  parallel-    the fourteenth (TII Falcon-H1, `model_type: falcon_h1`), fields
  hybrid-like  again, and the first layer with TWO MIXERS: every layer is
               the fourth description's Mamba-2 mixer AND a grouped-query
               attention under RoPE, both reading the layer's ONE normed
               input, their outputs summed into the stream
               (`parallel_attention` on a block whose `layer_types` are
               all "mamba"), so that every layer owns a lane's state and
               tail AND a plane of the K/V table.  The mixer has GROUPS
               of B and C (`ssm_groups`: head h reads group h // (heads /
               groups); the gated RMSNorm runs over each group's columns
               apart); its inner width is heads x head size (the
               family's key of its own for it is held to that where the
               configuration is loaded).  The family's
               muP multipliers are fields and folded into no weight: a
               VECTOR over the columns of the input projection's result
               (`ssm_multipliers`: five factors, on z, x, B, C and dt,
               so before the convolution), a factor on each mixer's
               input and output (`ssm_in_multiplier`,
               `ssm_out_multiplier`, `attention_in_multiplier`,
               `attention_out_multiplier`), on the keys before RoPE
               (`key_multiplier`), on the dense SwiGLU's gate input and
               its output (`mlp_multipliers`), on the embedding (the
               fourth's `embedding_multiplier`) and on the logits of an
               untied head (`lm_head_multiplier`).  The FFN is the
               fifth's dense SwiGLU.

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
import typing
from typing import Dict, Tuple

__all__ = ["BlockSpec", "OPT", "olmoe", "param_layout", "norm",
           "rope_tables", "yarn_inv_freq", "rope", "route", "moe_ffn",
           "swiglu", "clamped", "mamba2_step", "short_conv_step", "delta_rule_step",
           "delta_rule", "MOE_COMPILER_SCOPES", "SLIDING", "FULL", "MAMBA",
           "ATTENTION",
           "CONV", "DELTA", "DENSE", "SPARSE", "INDEX_FULL",
           "INDEX_SHARED", "INDEX_NONE", "LatentGeometry", "select_rows"]

SLIDING, FULL = "sliding_attention", "full_attention"
# an FFN kind a layer (`mlp_layer_types`)
DENSE, SPARSE = "dense", "sparse"
# Granite's names: a layer with no attention, and full attention
MAMBA, ATTENTION = "mamba", "attention"
# LFM2's name: a gated short convolution, whose memory is a tail a lane
CONV = "conv"
# Solar-Open2's linear mixer: a gated delta rule, whose memory is a
# matrix state a head and a tail a lane
DELTA = "delta_rule"
# an indexer kind a layer (`indexer_types`): a layer that computes a
# selection, and one that reuses the nearest earlier one's
INDEX_FULL, INDEX_SHARED = "full", "shared"
# and one that does neither: a SLIDING layer, which attends over its window
INDEX_NONE = "none"


class LatentGeometry(typing.NamedTuple):
    """A latent attention's sizes on one kind of layer
    (`BlockSpec.latent_of`)."""
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


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
    with `qk_norm` off and the attention geometry below, and that
    block with Mamba-2 layers and the fields under them, and the
    dense SwiGLU block on plain multi-head attention with the looped
    stack's fields, and the ring-and-table block with experts, an FFN
    kind a layer, the per-head QK-norm, RoPE on some layer kinds only
    and the sigmoid router, and the table-only block with experts on a
    LATENT cache under the group-limited softmax router, and the DOUBLE
    layer on a latent cache with identity experts under a softmax
    router with a choice bias, and the table-only block with experts,
    an FFN kind a layer and the per-head QK-norm whose other layers are
    gated short convolutions, and the table-only block with experts
    whose other layers are gated delta rules (attention on a K/V table
    without positions under an elementwise gate, or LATENT attention
    under RoPE with a gate a head, full-rank or low-rank delta gates,
    dense layers among the sparse ones, the group-limited sigmoid router
    with a choice bias), and the ring-and-table block with experts whose
    ring AND table hold latent rows (sliding layers with a latent
    geometry of their own and a window of any length, full layers under
    a lightning indexer, a gate a head on both, an FFN kind a layer, the
    sigmoid router with a choice bias), and the dense SwiGLU block whose
    every layer is a Mamba-2 mixer (groups of B and C) AND grouped-query
    attention under RoPE on one normed input, under the muP multipliers
    (module docstring).  `layer_types`,
    `mlp_layer_types`, `rope_layers` and `rope_parameters` may be given
    as the JSON list and dict a config.json holds: they are kept as
    (nested) tuples, so the description stays hashable."""
    name: str
    norm: str                       # "layer_norm" | "rms_norm"
    positions: str                  # "learned" | "rope" | "none"
    ffn: str                        # "relu" | "moe_swiglu" | "swiglu"
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
    layer_types: tuple = ()         # a kind a layer; (): all FULL
    window: int = 0                 # keys a SLIDING layer sees
    rope_parameters: tuple = ()     # {kind: {"rope_type", ...}}, frozen
    # -- the expert layer's share: the experts HELD here are
    #    [experts_first, experts_first + experts_held) of the n_experts
    #    the router routes over (0 held: all of them)
    experts_first: int = 0
    experts_held: int = 0
    shared_d_inner: int = 0         # a shared SwiGLU expert's width
    tied_head: bool = False         # the head is the embedding's rows
    # -- scalar multipliers (Granite's four)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0    # on each sub-block's output
    attention_multiplier: float = 0.0   # on q.k (0: 1 / sqrt(d_head))
    logits_scaling: float = 1.0         # the logits are DIVIDED by it
    # -- a MAMBA layer's geometry (Mamba-2)
    ssm_heads: int = 0              # heads H
    ssm_d_head: int = 0             # a head's size P (H * P columns)
    ssm_d_state: int = 0            # the state's size N
    ssm_conv: int = 0               # the causal convolution's width
    # groups of B and C: head h reads group h // (H / groups), and the
    # gated norm runs over each group's H * P / groups columns apart
    ssm_groups: int = 1
    # -- a PARALLEL layer: every MAMBA layer ALSO attends (grouped-query
    #    heads under RoPE on the K/V table), mixer and attention reading
    #    the layer's ONE normed input, their outputs summed; and the muP
    #    multipliers of such a block, none folded into a weight: a
    #    factor a SEGMENT of the input projection's columns (z, x, B, C,
    #    dt; (): none), on each mixer's input and output, on the keys
    #    before RoPE, on the dense SwiGLU's gate input and its output
    #    ((): none) and on the logits (multiplied; `logits_scaling`
    #    divides)
    parallel_attention: bool = False
    ssm_multipliers: tuple = ()
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_multipliers: tuple = ()
    lm_head_multiplier: float = 1.0
    # -- a LOOPED stack: the layers run `passes` times a token over the
    #    same weights, the final norm after every pass
    passes: int = 1
    post_norm: bool = False         # a norm on each sub-block's OUTPUT
    exit_gate: bool = False         # sigmoid(w . x_t + b) after a pass
    early_exit_threshold: float = 1.0   # 1: the gate decides nothing
    # -- an FFN kind a layer: DENSE layers take one SwiGLU of width
    #    `dense_d_inner`, SPARSE ones the experts (and the shared
    #    expert); (): every layer is what `ffn` says
    mlp_layer_types: tuple = ()
    dense_d_inner: int = 0
    qk_norm_per_head: bool = False  # `qk_norm` over each head's columns
    rope_layers: tuple = ()         # the kinds RoPE turns; (): all
    # -- the router: scores softmax or sigmoid of the logits, a bias
    #    [n_experts] added for the CHOICE of the k alone, and a factor
    #    on the (renormalised) weights
    router: str = "softmax"
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # added to the sum that `norm_topk_prob` divides the chosen by
    norm_topk_eps: float = 0.0
    # -- GROUP-LIMITED choice: the experts routed over are `n_group`
    #    consecutive groups, a group's score its largest score
    #    (`group_score` "max": the softmax router's) or the sum of its
    #    two largest scores + bias ("top2_sum": the sigmoid router's with
    #    a choice bias), and the k are chosen among the `topk_group` best
    #    groups (1 group: all)
    n_group: int = 1
    topk_group: int = 1
    group_score: str = "max"
    # -- LATENT attention (`kv_lora_rank` > 0): the cache holds one row
    #    [kv_lora_rank latent | qk_rope_head_dim rotated key] a position
    #    a layer for all heads; a query head is qk_nope_head_dim +
    #    qk_rope_head_dim columns, a value head v_head_dim
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the normed latents times sqrt(d_model / their rank)
    scale_q_lora: bool = False
    scale_kv_lora: bool = False
    # -- the SLIDING layers' own latent geometry, where they have one (0:
    #    the block's set above): query heads, the two ranks, a head's
    #    unrotated and rotated key columns and its value columns; their
    #    ring's row is [sliding_kv_lora_rank | sliding_qk_rope_head_dim]
    sliding_n_heads: int = 0
    sliding_q_lora_rank: int = 0
    sliding_kv_lora_rank: int = 0
    sliding_qk_nope_head_dim: int = 0
    sliding_qk_rope_head_dim: int = 0
    sliding_v_head_dim: int = 0
    # -- a DOUBLE layer (`sub_blocks` 2): two sub-blocks a layer, each a
    #    latent attention and a dense SwiGLU of `dense_d_inner` on norms
    #    of their own, and ONE expert layer whose input is the first
    #    sub-block's FFN input and whose result joins after the second
    #    dense FFN (the shortcut)
    sub_blocks: int = 1
    # -- IDENTITY experts: the router's columns [n_experts, n_experts +
    #    zero_experts) have no matrices; an assignment to one adds its
    #    weight times the expert layer's input
    zero_experts: int = 0
    # -- a LIGHTNING INDEXER (`index_topk` > 0): on an INDEX_FULL layer
    #    `index_n_heads` index queries of `index_head_dim` columns score
    #    ONE cached index key a position, and attention is over the
    #    `index_topk` positions of largest score; an INDEX_SHARED layer
    #    attends over the nearest earlier INDEX_FULL layer's selection; a
    #    SLIDING layer is INDEX_NONE (its window is what it attends over)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # a kind a layer; (): INDEX_FULL, and INDEX_NONE on a SLIDING layer
    indexer_types: tuple = ()
    # -- a CONV layer's taps (a gated short convolution; 0: none): a
    #    lane keeps the last `conv_width - 1` rows of its gated product
    conv_width: int = 0
    # -- a DELTA layer's geometry (a gated delta rule): `delta_heads`
    #    heads whose keys AND values are `delta_d_head` columns, the
    #    depthwise convolution's taps over q | k | v, the rank of the
    #    two low-rank gates (the decay's and the output's; 0: each ONE
    #    full matrix [d, H*K], the output gate's without a bias), whether
    #    the write strength is 2 sigmoid (eigenvalues down to -1) or
    #    sigmoid, and the log decay's lower bound (f < 0: g = f *
    #    sigmoid(exp(A_log) * (. + dt_bias)); 0: g = -exp(A_log) *
    #    softplus(. + dt_bias), unbounded)
    delta_heads: int = 0
    delta_d_head: int = 0
    delta_conv: int = 0
    delta_gate_rank: int = 0
    delta_neg_eigval: bool = False
    delta_gate_floor: float = 0.0
    # -- attention's output times sigmoid(the layer's normed input @ a
    #    matrix [d, H * d_head]) before `o`; `attention_gate_per_head`:
    #    the matrix is [d, H], one scalar a head (a latent layer's, at the
    #    heads of ITS kind)
    attention_gate: bool = False
    attention_gate_per_head: bool = False
    # -- a CLAMP a layer on the SwiGLU's inputs: with a limit L > 0 the
    #    gate input is min(. , L) and the up input clip(., -L, L), on the
    #    routed experts (`expert_swiglu_limits`) and on the shared expert
    #    (`shared_swiglu_limits`); (), or 0 at a layer: no clamp
    expert_swiglu_limits: tuple = ()
    shared_swiglu_limits: tuple = ()

    def __post_init__(self):
        for name in ("layer_types", "rope_parameters", "mlp_layer_types",
                     "rope_layers", "indexer_types", "expert_swiglu_limits",
                     "shared_swiglu_limits", "ssm_multipliers",
                     "mlp_multipliers"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        if len(self.ssm_multipliers) not in (0, 5) or len(
                self.mlp_multipliers) not in (0, 2):
            raise ValueError(
                f"block {self.name!r}: ssm_multipliers has a factor for "
                "each of z, x, B, C and dt (five, or none) and "
                "mlp_multipliers one for the gate input and one for the "
                "output (two, or none)")
        if self.group_score not in ("max", "top2_sum") or (
                self.group_score != "max" and self.n_group < 2):
            raise ValueError(
                f"group_score {self.group_score!r}: 'max' or 'top2_sum', "
                "the score of a group of a group-limited router (n_group "
                "> 1)")
        if min(self.expert_swiglu_limits + self.shared_swiglu_limits
               + (0,)) < 0:
            raise ValueError(
                f"block {self.name!r}: a SwiGLU limit is 0 (no clamp) or "
                "positive")
        if self.delta_gate_floor > 0:
            raise ValueError(
                f"delta_gate_floor {self.delta_gate_floor}: the log "
                "decay's lower bound is negative (0: unbounded)")
        if self.attention_gate_per_head and not self.attention_gate:
            raise ValueError(
                f"block {self.name!r}: attention_gate_per_head says how "
                "attention_gate is applied, and attention_gate is off")
        bad = set(self.mlp_layer_types) - {DENSE, SPARSE}
        if bad:
            raise ValueError(
                f"mlp_layer_types: unknown kind(s) {sorted(bad)}")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"router {self.router!r}: 'softmax' or "
                             "'sigmoid'")
        if set(self.rope_layers) - {SLIDING, FULL}:
            raise ValueError(f"rope_layers {self.rope_layers}: of "
                             f"{SLIDING!r} and {FULL!r}")
        bad = set(self.layer_types) - {SLIDING, FULL, MAMBA, ATTENTION,
                                       CONV, DELTA}
        if bad:
            raise ValueError(f"layer_types: unknown kind(s) {sorted(bad)}")
        if (DELTA in self.layer_types) != (self.delta_heads > 0):
            raise ValueError(
                f"block {self.name!r}: delta_heads {self.delta_heads} "
                f"{'without' if self.delta_heads else 'with'} {DELTA!r} "
                "layers: the heads are those layers' mixer's")
        if (CONV in self.layer_types) != (self.conv_width > 0):
            raise ValueError(
                f"block {self.name!r}: conv_width {self.conv_width} "
                f"{'without' if self.conv_width else 'with'} {CONV!r} "
                "layers: the width is the taps of those layers' "
                "convolution")
        if self.norm_topk_eps and not self.norm_topk_prob:
            raise ValueError(
                f"block {self.name!r}: norm_topk_eps is added to the sum "
                "norm_topk_prob divides by, and norm_topk_prob is off")
        if SLIDING in self.layer_types and self.window < 1:
            raise ValueError(f"{SLIDING} layers need window >= 1")
        if self.passes < 1:
            raise ValueError(f"passes {self.passes}: at least 1")
        if self.early_exit_threshold < 1:
            raise NotImplementedError(
                f"block {self.name!r}: early_exit_threshold "
                f"{self.early_exit_threshold} < 1 is adaptive exit: "
                "lanes of one tick would run different numbers of "
                "passes, and the scheduler's tick gives every lane the "
                "same work; only a threshold of 1 (every pass runs, "
                "the gate is reported and decides nothing) is built")
        if not 1 <= self.topk_group <= self.n_group or (
                self.n_experts % self.n_group):
            raise ValueError(
                f"{self.topk_group} of {self.n_group} groups over "
                f"{self.n_experts} experts: groups are equal parts of "
                "the experts, and at least one and at most all are kept")
        if not 0 <= self.experts_first <= (
                self.n_experts - self.experts_held):
            raise ValueError(
                f"experts [{self.experts_first}, {self.experts_first} + "
                f"{self.experts_held}) are not among the "
                f"{self.n_experts} routed over")
        if self.sub_blocks not in (1, 2):
            raise ValueError(f"sub_blocks {self.sub_blocks}: 1, or 2 (a "
                             "double layer)")
        if self.sub_blocks == 2 and (
                self.ffn != "moe_swiglu" or self.kv_lora_rank < 1
                or self.dense_d_inner < 1 or self.mlp_layer_types
                or self.layer_types or self.shared_d_inner
                or self.post_norm or self.passes > 1 or self.exit_gate):
            raise NotImplementedError(
                f"block {self.name!r}: a double layer (sub_blocks 2) is "
                "two latent attentions (kv_lora_rank), two dense FFNs "
                "(dense_d_inner) and one expert layer (ffn 'moe_swiglu') "
                "across them; no mlp_layer_types, layer_types, shared "
                "expert, post_norm, passes or exit_gate beside it")
        if self.zero_experts < 0 or (self.zero_experts and (
                self.ffn != "moe_swiglu" or self.router != "softmax"
                or self.norm_topk_prob or self.n_group > 1)):
            raise NotImplementedError(
                f"block {self.name!r}: identity experts (zero_experts) "
                "are columns of a softmax router over experts (ffn "
                "'moe_swiglu') whose weights are not renormalised "
                "(norm_topk_prob: over which of them?) and whose choice "
                "is not group-limited (n_group: they lie in no group)")
        if set(self.indexer_types) - {INDEX_FULL, INDEX_SHARED, INDEX_NONE}:
            raise ValueError(f"indexer_types {self.indexer_types}: of "
                             f"{INDEX_FULL!r}, {INDEX_SHARED!r} and (a "
                             f"sliding layer's) {INDEX_NONE!r}")
        own = [n for n in ("sliding_n_heads", "sliding_q_lora_rank",
                           "sliding_kv_lora_rank",
                           "sliding_qk_nope_head_dim",
                           "sliding_qk_rope_head_dim", "sliding_v_head_dim")
               if getattr(self, n)]
        if min([getattr(self, n) for n in own] + [0]) < 0 or (own and (
                self.kv_lora_rank < 1 or SLIDING not in self.layer_types)):
            raise ValueError(
                f"block {self.name!r}: {', '.join(own)}: the sliding "
                "layers' own latent geometry, of a block with a latent "
                "cache (kv_lora_rank) and sliding layers")
        if self.index_topk < 0 or (self.index_topk and (
                self.kv_lora_rank < 1 or self.sub_blocks != 1
                or self.index_n_heads < 1
                or self.index_head_dim < self.qk_rope_head_dim
                or self.indexer_types[:1] == (INDEX_SHARED,))):
            raise NotImplementedError(
                f"block {self.name!r}: a lightning indexer (index_topk) "
                "selects rows of a LATENT cache (kv_lora_rank) of single "
                "layers (sub_blocks 1) and needs index_n_heads and an "
                "index_head_dim of at least qk_rope_head_dim (RoPE turns "
                "that many of its columns); the first layer computes a "
                "selection (indexer_types starts 'full')")
        if (self.index_n_heads or self.index_head_dim
                or self.indexer_types) and not self.index_topk:
            raise ValueError(
                f"block {self.name!r}: index_n_heads, index_head_dim and "
                "indexer_types describe a lightning indexer, and "
                "index_topk is 0")
        if (self.scale_q_lora or self.scale_kv_lora) and (
                self.kv_lora_rank < 1):
            raise ValueError(
                f"block {self.name!r}: scale_q_lora and scale_kv_lora "
                "are constants on the normed latents of a latent cache "
                "(kv_lora_rank)")

    @property
    def held(self):
        """(first, count) of the experts whose matrices are here."""
        return self.experts_first, self.experts_held or self.n_experts

    @property
    def has_unheld(self) -> bool:
        """Whether a chosen column can be one with no matrices here: an
        absent expert's (a share is held) or an identity expert's.  Such
        an assignment is in no group of the grouped matmul."""
        return self.held[1] < self.n_experts or self.zero_experts > 0

    @property
    def latent(self) -> bool:
        """Whether attention keeps a latent row a position (module
        docstring, the seventh description)."""
        return self.kv_lora_rank > 0

    @property
    def sparse(self) -> bool:
        """Whether attention is over rows a lightning indexer selects
        (module docstring, the ninth description)."""
        return self.index_topk > 0

    def indexer_of(self, layer: int) -> str:
        """Layer `layer`'s indexer kind, INDEX_FULL, INDEX_SHARED or
        INDEX_NONE; a description without `indexer_types` is INDEX_FULL on
        every layer but the SLIDING ones, which are INDEX_NONE."""
        if not self.indexer_types:
            return (INDEX_NONE if self.kind_of(layer) == SLIDING
                    else INDEX_FULL)
        if layer >= len(self.indexer_types):
            raise ValueError(
                f"block {self.name!r}: {len(self.indexer_types)} "
                f"indexer_types, and a layer {layer}")
        return self.indexer_types[layer]

    def latent_of(self, kind: str, n_heads: int) -> LatentGeometry:
        """The latent attention's sizes on a layer of kind `kind` at the
        builder's `n_heads`: the block's one set, or on a SLIDING layer
        each size the sliding layers' own where they have one."""
        whole = LatentGeometry(
            n_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
        if kind != SLIDING:
            return whole
        own = (self.sliding_n_heads, self.sliding_q_lora_rank,
               self.sliding_kv_lora_rank, self.sliding_qk_nope_head_dim,
               self.sliding_qk_rope_head_dim, self.sliding_v_head_dim)
        return LatentGeometry(*(o or w for o, w in zip(own, whole)))

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
        kind = self.layer_types[layer]
        return FULL if kind == ATTENTION else kind

    def ffn_of(self, layer: int) -> str:
        """Layer `layer`'s FFN kind, DENSE or SPARSE; a description
        without `mlp_layer_types` is all SPARSE (what `ffn` says)."""
        if not self.mlp_layer_types:
            return SPARSE
        if layer >= len(self.mlp_layer_types):
            raise ValueError(
                f"block {self.name!r}: {len(self.mlp_layer_types)} "
                f"mlp_layer_types, and a layer {layer}")
        return self.mlp_layer_types[layer]

    def swiglu_limits_of(self, layer: int):
        """(the routed experts', the shared expert's) SwiGLU clamp at
        layer `layer`; 0.0: none (a description without the lists, or a
        depth past their end, has none)."""
        return tuple(float(ls[layer]) if layer < len(ls) else 0.0
                     for ls in (self.expert_swiglu_limits,
                                self.shared_swiglu_limits))

    def rotated(self, kind: str) -> bool:
        """Whether RoPE turns Q and K on a layer of this kind (a layer
        without attention has neither; a MAMBA layer attends where the
        block's layers are parallel)."""
        return self.positions == "rope" and kind not in (CONV, DELTA) and (
            kind != MAMBA or self.parallel_attention) and (
            not self.rope_layers or kind in self.rope_layers)

    def rope_of(self, kind: str) -> dict:
        """RoPE parameters of a layer kind: the kind's entry of
        `rope_parameters` (or the one set of parameters it holds for
        every kind), else plain RoPE at `rope_theta`."""
        params = dict(self.rope_parameters)
        params = params if "rope_type" in params else params.get(kind)
        if params is None:
            # (a float: a config.json's integer theta past 32 bits, 1e11,
            # is no jax scalar)
            return {"rope_type": "default",
                    "rope_theta": float(self.rope_theta)}
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
    step reads: `.tok`, `.pos` (None without a position table),
    `.layers[l]` (a dict of (weight-or-scale, bias-or-shift-or-None)
    name pairs), `.final`, `.head` (the embedding's own pair under
    `tied_head`: the step multiplies by its transpose).  Q and O are
    [d, H*dh] and [H*dh, d], K and V [d, Hkv*dh]: all [d, d] where the
    heads split the model's width.  A MAMBA layer has, in place of
    those four, the mixer's: `ssm_in` [d, 2*H*P + 2*G*N + H] (z, then x B
    C, then dt, side by side; G = `ssm_groups` groups of B, then of C),
    the depthwise convolution over x B C
    (`ssm_conv`: [width, H*P + 2*G*N] and its bias), `ssm_dt` (dt's
    bias), `ssm_a_log`, `ssm_d` [H], the gated norm's scale [H*P] and
    `ssm_out` [H*P, d]; no bias on a projection.  Under
    `parallel_attention` it has the four BESIDE those (`q`, `k`, `v`,
    `o` at the grouped geometry), on the mixer's one norm.  A CONV layer has three:
    `conv_in` [d, 3 * d] (the gates B, C and u, side by side in that
    order), the depthwise convolution's taps `conv_w` [conv_width, d]
    (row j multiplies the row `conv_width - 1 - j` positions back: the
    last row this position's own) and `conv_out` [d, d]; no bias, and
    no QK-norm scales (those are the attention layers').  A DELTA
    layer (H = `delta_heads`, K = `delta_d_head`, r = `delta_gate_rank`)
    has, in place of the four: `delta_in` [d, 3*H*K] (q, k and v side
    by side), the depthwise convolution over them `delta_conv`
    [delta_conv, 3*H*K] (rows as a CONV layer's; no bias), the decay's
    low-rank pair `delta_fa` [d, r] and `delta_fb` [r, H*K] with its
    bias `delta_dt` [H*K] and `delta_a_log` [H], the write strength's
    `delta_b` [d, H], the output gate's pair `delta_ga` [d, r] and
    `delta_gb` [r, H*K] with its bias `delta_g` [H*K], the head norm's
    ONE scale `delta_o_norm` [K] and `delta_out` [H*K, d]; at
    `delta_gate_rank` 0 the two pairs and the gate's bias give way to
    ONE matrix each, `delta_f` and `delta_gw` [d, H*K].  With
    `attention_gate` an attention layer has a fifth matrix, `attn_gate`
    [d, H*dh] (a latent layer under `attention_gate_per_head`:
    `attn_head_gate` [d, H]).  A LATENT
    layer has
    seven arrays (at the sizes of ITS kind, `BlockSpec.latent_of`: a
    SLIDING layer of a latent block is a latent layer at the sliding
    layers' own heads, ranks and head sizes, with no indexer) in place of the four: `q_a` [d, q_lora_rank], its
    norm's scale `q_a_norm`, `q_b` [q_lora_rank, H * (nope + rope)] (a
    head's unrotated columns, then its rotated ones), `kv_a` [d,
    kv_lora_rank + rope] (the latent, then the one key part),
    `kv_a_norm` [kv_lora_rank], `kv_b` [kv_lora_rank, H * (nope +
    v_head_dim)] (a head's key columns, then its value columns) and
    `o` [H * v_head_dim, d]; at `q_lora_rank` 0 there is no `q_a` and no
    `q_a_norm`, and `q_b` is the one query matrix [d, H * (nope +
    rope)].  With a lightning indexer an INDEX_FULL
    layer has four more: `idx_q` [q_lora_rank, index_n_heads *
    index_head_dim], `idx_k` [d, index_head_dim], its LayerNorm
    `idx_k_norm` (a scale and a shift of index_head_dim) and `idx_w`
    [d, index_n_heads]; an INDEX_SHARED or INDEX_NONE layer none.  The
    expert matrices
    are [experts HELD, ...]; the router keeps its published width.  A
    DENSE layer among sparse ones (`mlp_layer_types`) has the dense
    block's three matrices at `dense_d_inner` and no "router" key:
    that is how the step tells the two apart.  A DOUBLE layer
    (`sub_blocks` 2) is TWO entries of `.layers`, one a sub-block:
    each the latent layer's arrays and norms under `layer_<l>.sub_<i>.`
    and a dense SwiGLU at `dense_d_inner` under "dense_gate",
    "dense_up" and "dense_down"; the first also holds the layer's ONE
    expert layer (`layer_<l>.router.w_0`, ...; "router", "gate", "up",
    "down"), the second no "router".  The router and its choice bias
    are `n_experts + zero_experts` wide."""
    dense = spec.ffn == "swiglu"
    if (spec.norm, spec.bias) != ("rms_norm", False) or spec.ffn not in (
            "moe_swiglu", "swiglu"):
        raise NotImplementedError(
            f"block {spec.name!r}: only the OLMoE combination (RMSNorm, "
            "no bias, a SwiGLU FFN: experts, or dense) is laid out from "
            "its description; OPT's names come from the training "
            "Program")
    kinds = [spec.kind_of(l) for l in range(n_layers)]
    parallel = spec.parallel_attention
    if parallel and (
            not dense or set(kinds) != {MAMBA} or spec.latent
            or spec.n_experts or spec.shared_d_inner or spec.qk_norm
            or spec.tied_head
            or spec.mlp_layer_types or spec.attention_gate
            or spec.positions != "rope" or spec.rope_layers
            or spec.passes > 1 or spec.post_norm or spec.exit_gate
            or spec.residual_multiplier != 1.0
            or spec.attention_multiplier or spec.logits_scaling != 1.0
            or spec.ssm_groups < 1 or spec.ssm_heads % spec.ssm_groups):
        raise NotImplementedError(
            f"block {spec.name!r}: a parallel layer (parallel_attention) "
            "is built with EVERY layer a Mamba-2 mixer (layer_types all "
            "'mamba'; ssm_groups dividing ssm_heads) beside grouped-query "
            "attention under RoPE on the K/V table, a dense SwiGLU FFN "
            "(ffn 'swiglu') and an untied head, under its own "
            "multipliers; STILL refused: Mamba mixers beside a ring, a "
            "latent table or experts (a shared expert, a QK-norm, a tied "
            "head) in a parallel layer, positions "
            "other than RoPE there (rope_layers too), an attention gate, "
            "a looped stack, layers of another kind among them, and "
            "Granite's residual_multiplier, attention_multiplier and "
            "logits_scaling beside the muP set")
    if not parallel and (
            spec.ssm_groups != 1 or spec.ssm_multipliers
            or spec.mlp_multipliers or (
                spec.ssm_in_multiplier, spec.ssm_out_multiplier,
                spec.attention_in_multiplier, spec.attention_out_multiplier,
                spec.key_multiplier, spec.lm_head_multiplier) != (1.0,) * 6):
        raise NotImplementedError(
            f"block {spec.name!r}: groups of B and C (ssm_groups) "
            "and the muP multipliers (ssm_multipliers, "
            "ssm_in_multiplier, ssm_out_multiplier, "
            "attention_in_multiplier, attention_out_multiplier, "
            "key_multiplier, mlp_multipliers, lm_head_multiplier) are "
            "built and tested on a parallel layer (parallel_attention) "
            "alone")
    if dense and not parallel and (
            set(kinds) != {FULL} or spec.n_experts or spec.qk_norm
            or spec.shared_d_inner or spec.tied_head
            or spec.n_kv_heads not in (0, n_heads)):
        raise NotImplementedError(
            f"block {spec.name!r}: a dense SwiGLU FFN is built on plain "
            "multi-head full attention under RoPE with an untied head "
            "(or on a parallel layer: parallel_attention): "
            "no experts, shared expert, QK-norm, grouped K/V heads or "
            "other layer kinds beside it")
    if not dense and (spec.passes > 1 or spec.post_norm
                      or spec.exit_gate):
        raise NotImplementedError(
            f"block {spec.name!r}: passes, post_norm and exit_gate are "
            "built for the dense SwiGLU block alone (ffn 'swiglu')")
    mamba, conv, delta = MAMBA in kinds, CONV in kinds, DELTA in kinds
    if delta and (dense or mamba or conv or SLIDING in kinds or spec.sparse
                  or FULL not in kinds or spec.qk_norm
                  or min(spec.delta_heads, spec.delta_d_head,
                         spec.delta_conv - 1) < 1
                  or spec.delta_gate_rank < 0):
        raise NotImplementedError(
            f"block {spec.name!r}: gated delta-rule layers (delta_heads, "
            "delta_d_head, delta_conv >= 2, delta_gate_rank >= 0) are "
            "built among full-attention layers on the table (K and V, or "
            "a latent row) in a block with experts: no Mamba or conv "
            "layers, ring, lightning indexer, QK-norm or dense SwiGLU "
            "block beside them")
    if spec.delta_gate_floor and not delta:
        raise ValueError(
            f"block {spec.name!r}: delta_gate_floor bounds the log decay "
            "of delta-rule layers, and the block has none")
    if spec.attention_gate and (
            spec.attention_gate_per_head != spec.latent
            or not (delta or spec.latent)):
        raise NotImplementedError(
            f"block {spec.name!r}: attention_gate (the attention's output "
            "times a sigmoid of the layer's input) is built and tested "
            "elementwise ([d, H * d_head]) on the K/V table of a block "
            "with delta-rule layers, and as a scalar a head "
            "(attention_gate_per_head) on LATENT layers, with delta-rule "
            "layers beside them or without; STILL refused: an "
            "elementwise gate on a latent layer, a gate a head on K/V "
            "heads, and any gate on a K/V block without delta-rule "
            "layers")
    if conv and (dense or mamba or SLIDING in kinds or spec.latent
                 or FULL not in kinds or spec.conv_width < 2):
        raise NotImplementedError(
            f"block {spec.name!r}: gated short convolutions (conv_width "
            ">= 2) are built among full-attention layers on the table, "
            "in a block with experts: no Mamba layers, ring, latent "
            "cache or dense SwiGLU block beside them")
    # a latent layer's one shared key part IS a rotated one; a parallel
    # layer's attention is turned beside the Mamba mixer of the same layer
    unsigned = (mamba and not parallel) or (delta and not spec.latent)
    if spec.positions != ("none" if unsigned else "rope"):
        raise NotImplementedError(
            f"block {spec.name!r}: positions {spec.positions!r} "
            f"{'with' if unsigned else 'without'} Mamba layers, or "
            "delta-rule layers beside a K/V table; built are RoPE on a "
            "block of attention layers, on the LATENT layers beside "
            "delta-rule layers (which carry none themselves) and on the "
            "attention of a PARALLEL layer (parallel_attention: beside "
            "the Mamba mixer of the same layer), and no "
            "position signal where Mamba layers of their own, or "
            "delta-rule layers beside attention on a K/V table, carry "
            "the order")
    if mamba and SLIDING in kinds:
        raise NotImplementedError(
            f"block {spec.name!r}: Mamba layers beside sliding-window "
            "layers (a lane's state, a ring and the table at once)")
    if mamba and min(spec.ssm_heads, spec.ssm_d_head, spec.ssm_d_state,
                     spec.ssm_conv - 1) < 1:
        raise ValueError(
            f"block {spec.name!r}: Mamba layers need ssm_heads, "
            "ssm_d_head, ssm_d_state and ssm_conv >= 2")
    n_kv, d_head = spec.heads(d_model, n_heads)
    if spec.qk_norm_per_head and not spec.qk_norm:
        raise ValueError(
            f"block {spec.name!r}: qk_norm_per_head says how qk_norm "
            "is applied, and qk_norm is off")
    if spec.qk_norm and not spec.qk_norm_per_head and (
            n_kv * d_head, n_heads * d_head) != (d_model, d_model):
        raise NotImplementedError(
            f"block {spec.name!r}: qk_norm over all of Q and K is built "
            "at the model's width; a grouped or wider geometry takes "
            "it per head (qk_norm_per_head)")
    ffns = [spec.ffn_of(l) for l in range(n_layers)]
    if DENSE in ffns and (dense or mamba or spec.dense_d_inner < 1):
        raise NotImplementedError(
            f"block {spec.name!r}: dense layers among sparse ones "
            "(mlp_layer_types) are built for a block of attention (and "
            "short-convolution) layers with experts (ffn 'moe_swiglu') and "
            "need dense_d_inner, the dense layers' width")
    if spec.router != "sigmoid" and (
            spec.routed_scaling_factor != 1.0 and spec.norm_topk_prob):
        raise NotImplementedError(
            f"block {spec.name!r}: a scaling factor on renormalised "
            "weights is built and tested on the sigmoid router alone "
            "(the softmax router's probabilities take a factor as they "
            "are, and a choice bias)")
    if spec.n_group > 1 and (spec.router, spec.router_bias,
                             spec.group_score) not in (
            ("softmax", False, "max"), ("sigmoid", True, "top2_sum")):
        raise NotImplementedError(
            f"block {spec.name!r}: group-limited choice is built on the "
            "softmax router without a choice bias (a group's score its "
            "largest probability: group_score 'max') and on the sigmoid "
            "router with one (the sum of its two largest scores + bias: "
            "'top2_sum'); not a softmax router with a bias, a sigmoid "
            "router without, or the other score under either")
    swa = (spec.latent_of(SLIDING, n_heads) if spec.latent
           and SLIDING in kinds else None)
    if spec.latent and (
            dense or mamba or conv or spec.qk_norm
            or spec.n_kv_heads not in (0, n_heads) or spec.d_head
            or min(spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                   spec.v_head_dim) < 1 or spec.q_lora_rank < 0
            or spec.qk_rope_head_dim % 2
            or (spec.q_lora_rank < 1 and (spec.sparse or spec.scale_q_lora
                                          or spec.sub_blocks > 1))
            or (swa is not None and (
                FULL not in kinds or min(swa) < 1
                or swa.qk_rope_head_dim % 2))):
        raise NotImplementedError(
            f"block {spec.name!r}: a latent cache (kv_lora_rank) is "
            "built for a block of full-attention layers with experts "
            "under RoPE (delta-rule layers may stand beside them, or "
            "SLIDING layers that are latent attention themselves: a ring "
            "of latent rows beside the latent table), and "
            "needs qk_nope_head_dim, an even qk_rope_head_dim and "
            "v_head_dim (of the sliding layers' own geometry too, and a "
            "query through a rank there); q_lora_rank 0 is a query of "
            "ONE matrix, which a "
            "lightning indexer, scale_q_lora and a double layer are not "
            "built on (they read the query's latent); STILL refused: a "
            "ring of K and V heads beside a latent table (n_kv_heads, "
            "d_head: every head reads the one latent row, of the table "
            "and of the ring alike), Mamba or "
            "conv layers, QK-norm, grouped K/V heads or d_head beside it")
    if SLIDING in kinds and spec.sparse and (
            INDEX_SHARED in [spec.indexer_of(l) for l in range(n_layers)]
            or any((spec.indexer_of(l) == INDEX_NONE) != (k == SLIDING)
                   for l, k in enumerate(kinds))):
        raise NotImplementedError(
            f"block {spec.name!r}: beside sliding layers every full layer "
            "computes its own selection (indexer_types 'full') and a "
            "sliding layer none ('none': it attends over its window); "
            "STILL refused: a selection SHARED across a sliding layer "
            "('shared'), a selection on a sliding layer, and a full "
            "layer without one")
    if INDEX_NONE in spec.indexer_types and SLIDING not in kinds:
        raise ValueError(
            f"block {spec.name!r}: indexer_types 'none' is a sliding "
            "layer's, and the block has none")
    for kind in set(kinds) if spec.positions == "rope" else ():
        if not spec.rotated(kind):
            continue
        if spec.rope_of(kind)["rope_type"] not in ("default", "yarn"):
            raise NotImplementedError(
                f"block {spec.name!r}: rope_type "
                f"{spec.rope_of(kind)['rope_type']!r} on {kind} layers")
    d, e, f = int(d_model), spec.n_experts, int(d_inner)
    held, fs = spec.held[1], spec.shared_d_inner
    dq, dkv = n_heads * d_head, n_kv * d_head
    di = spec.ssm_heads * spec.ssm_d_head
    conv = di + 2 * spec.ssm_groups * spec.ssm_d_state
    shapes: Dict[str, Tuple[int, ...]] = {}

    def add(name, *shape):
        shapes[name] = tuple(int(s) for s in shape)
        return name, None

    def latent_arrays(p, kind=FULL):
        # the sizes of the layer's KIND: a sliding layer's own, where
        # the description gives them
        h, r_q, r_kv, d_nope, d_pe, d_v = spec.latent_of(kind, n_heads)
        dqk = d_nope + d_pe
        # without a low-rank step the one matrix stands where `q_b` does
        # and reads the block's normed input itself
        query = ({"q_a": add(p + "q_a_proj.w_0", d, r_q),
                  "q_a_norm": add(p + "q_a_norm.scale_0", r_q),
                  "q_b": add(p + "q_b_proj.w_0", r_q, h * dqk)} if r_q else
                 {"q_b": add(p + "q_proj.w_0", d, h * dqk)})
        gate = ({"attn_head_gate": add(p + "attn_gate.w_0", d, h)}
                if spec.attention_gate else {})
        return {"norm1": add(p + "attn_norm.scale_0", d), **query, **gate,
                "kv_a": add(p + "kv_a_proj.w_0", d, r_kv + d_pe),
                "kv_a_norm": add(p + "kv_a_norm.scale_0", r_kv),
                "kv_b": add(p + "kv_b_proj.w_0", r_kv, h * (d_nope + d_v)),
                "o": add(p + "o_proj.w_0", h * d_v, d)}

    def expert_arrays(p):
        wide = e + spec.zero_experts
        lay = {"router": add(p + "router.w_0", d, wide),
               "gate": add(p + "experts_gate.w_0", held, d, f),
               "up": add(p + "experts_up.w_0", held, d, f),
               "down": add(p + "experts_down.w_0", held, f, d)}
        if spec.router_bias:
            lay["router_bias"] = add(p + "router_bias.b_0", wide)
        return lay

    def double_layer(l):
        """Layer l's two sub-blocks, the expert layer with the first
        (where it is computed)."""
        subs = []
        for i in range(2):
            p, fd = f"layer_{l}.sub_{i}.", spec.dense_d_inner
            lay = latent_arrays(p)
            lay.update({"norm2": add(p + "ffn_norm.scale_0", d),
                        "dense_gate": add(p + "ffn_gate.w_0", d, fd),
                        "dense_up": add(p + "ffn_up.w_0", d, fd),
                        "dense_down": add(p + "ffn_down.w_0", fd, d)})
            if i == 0:
                lay.update(expert_arrays(f"layer_{l}."))
            subs.append(lay)
        return subs

    layers = []
    for l, kind in enumerate(kinds):
        if spec.sub_blocks == 2:
            layers += double_layer(l)
            continue
        p = f"layer_{l}."
        if kind == MAMBA:
            lay = {"norm1": add(p + "mixer_norm.scale_0", d),
                   "ssm_in": add(p + "ssm_in_proj.w_0", d,
                                 di + conv + spec.ssm_heads),
                   "ssm_conv": (add(p + "ssm_conv.w_0", spec.ssm_conv,
                                    conv)[0],
                                add(p + "ssm_conv.b_0", conv)[0]),
                   "ssm_dt": add(p + "ssm_dt.b_0", spec.ssm_heads),
                   "ssm_a_log": add(p + "ssm_a_log.w_0", spec.ssm_heads),
                   "ssm_d": add(p + "ssm_d.w_0", spec.ssm_heads),
                   "ssm_gate_norm": add(p + "ssm_gate_norm.scale_0", di),
                   "ssm_out": add(p + "ssm_out_proj.w_0", di, d)}
            if parallel:
                # the attention of the same layer, on the mixer's norm
                lay.update({"q": add(p + "q_proj.w_0", d, dq),
                            "k": add(p + "k_proj.w_0", d, dkv),
                            "v": add(p + "v_proj.w_0", d, dkv),
                            "o": add(p + "o_proj.w_0", dq, d)})
        elif kind == DELTA:
            hk = spec.delta_heads * spec.delta_d_head
            r = spec.delta_gate_rank
            # the decay's and the output gate's maps: a low-rank pair
            # each (and the gate's bias), or ONE full matrix each
            gates = ({"delta_fa": add(p + "delta_decay_a.w_0", d, r),
                      "delta_fb": add(p + "delta_decay_b.w_0", r, hk),
                      "delta_ga": add(p + "delta_gate_a.w_0", d, r),
                      "delta_gb": add(p + "delta_gate_b.w_0", r, hk),
                      "delta_g": add(p + "delta_gate.b_0", hk)} if r else
                     {"delta_f": add(p + "delta_decay.w_0", d, hk),
                      "delta_gw": add(p + "delta_gate.w_0", d, hk)})
            lay = {"norm1": add(p + "mixer_norm.scale_0", d),
                   "delta_in": add(p + "delta_in_proj.w_0", d, 3 * hk),
                   "delta_conv": add(p + "delta_conv.w_0", spec.delta_conv,
                                     3 * hk),
                   **gates,
                   "delta_dt": add(p + "delta_dt.b_0", hk),
                   "delta_a_log": add(p + "delta_a_log.w_0",
                                      spec.delta_heads),
                   "delta_b": add(p + "delta_beta.w_0", d,
                                  spec.delta_heads),
                   "delta_o_norm": add(p + "delta_o_norm.scale_0",
                                       spec.delta_d_head),
                   "delta_out": add(p + "delta_out_proj.w_0", hk, d)}
        elif kind == CONV:
            lay = {"norm1": add(p + "operator_norm.scale_0", d),
                   "conv_in": add(p + "conv_in_proj.w_0", d, 3 * d),
                   "conv_w": add(p + "conv.w_0", spec.conv_width, d),
                   "conv_out": add(p + "conv_out_proj.w_0", d, d)}
        elif spec.latent:
            lay = latent_arrays(p, kind)
            if spec.sparse and spec.indexer_of(l) == INDEX_FULL:
                hi, di = spec.index_n_heads, spec.index_head_dim
                lay.update({
                    "idx_q": add(p + "indexer_q.w_0", spec.q_lora_rank,
                                 hi * di),
                    "idx_k": add(p + "indexer_k.w_0", d, di),
                    "idx_k_norm": (
                        add(p + "indexer_k_norm.scale_0", di)[0],
                        add(p + "indexer_k_norm.shift_0", di)[0]),
                    "idx_w": add(p + "indexer_w.w_0", d, hi)})
        else:
            lay = {"norm1": add(p + "attn_norm.scale_0", d),
                   "q": add(p + "q_proj.w_0", d, dq),
                   "k": add(p + "k_proj.w_0", d, dkv),
                   "v": add(p + "v_proj.w_0", d, dkv),
                   "o": add(p + "o_proj.w_0", dq, d)}
            if spec.attention_gate:
                lay["attn_gate"] = add(p + "attn_gate.w_0", d, dq)
        lay["norm2"] = add(p + "ffn_norm.scale_0", d)
        if dense or ffns[l] == DENSE:
            # one SwiGLU every token takes: the block's FFN, or a dense
            # layer's among sparse ones (no router, no shared expert)
            fd = f if dense else spec.dense_d_inner
            lay.update({"gate": add(p + "ffn_gate.w_0", d, fd),
                        "up": add(p + "ffn_up.w_0", d, fd),
                        "down": add(p + "ffn_down.w_0", fd, d)})
        else:
            lay.update(expert_arrays(p))
        if spec.post_norm:
            # g2 and g4: on what attention and the FFN give, before the
            # residual stream takes it
            lay["post1"] = add(p + "attn_post_norm.scale_0", d)
            lay["post2"] = add(p + "ffn_post_norm.scale_0", d)
        if fs and "router" in lay:
            lay.update({"shared_gate": add(p + "shared_gate.w_0", d, fs),
                        "shared_up": add(p + "shared_up.w_0", d, fs),
                        "shared_down": add(p + "shared_down.w_0", fs, d)})
        if spec.qk_norm and kind != CONV:
            # one scale for all of Q (of K), or one of d_head for every
            # head of Q (of K)
            dn = d_head if spec.qk_norm_per_head else d
            lay["q_norm"] = add(p + "q_norm.scale_0", dn)
            lay["k_norm"] = add(p + "k_norm.scale_0", dn)
        layers.append(lay)
    tok = add("tok_embedding.w_0", vocab_size, d)
    layout = types.SimpleNamespace(
        tok=tok[0], pos=None, layers=layers,
        final=add("final_norm.scale_0", d),
        head=tok if spec.tied_head else add("lm_head.w_0", d, vocab_size),
        # w_exit [d, 1] and b_exit [1]: lambda_t = sigmoid(x_t . w + b)
        exit=((add("exit_gate.w_0", d, 1)[0], add("exit_gate.b_0", 1)[0])
              if spec.exit_gate else None))
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


def _largest(x, k: int):
    """`jax.lax.top_k(x, k)` without a sort: x [..., n] -> (values
    [..., k], indices [..., k] int32), largest first, a tie to the
    LOWER index, by k passes over the row (the TPU compiler makes a
    FULL sort of the row of a `top_k`, whatever k is).  A pass: of the
    positions no earlier pass took the largest value, and of those that
    hold it the lowest index.  Taken is a MASK, not a value written
    over the score: a row may hold `-inf` itself (`route`'s `left`
    outside the kept groups), and no position is chosen twice."""
    import jax
    import jax.numpy as jnp

    n = x.shape[-1]
    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    taken = jnp.zeros(x.shape, bool)
    values, indices = [], []
    for _ in range(k):
        best = jnp.max(jnp.where(taken, -jnp.inf, x), -1, keepdims=True)
        first = jnp.min(jnp.where((x == best) & ~taken, at, n), -1,
                        keepdims=True)
        taken = taken | (at == first)
        values.append(best)
        indices.append(first)
    return jnp.concatenate(values, -1), jnp.concatenate(indices, -1)


def _chosen(spec: BlockSpec, probs, b_router=None, choice=None):
    """`route`'s choice from the scores on: probs [T, E] float32 ->
    (the chosen experts' scores [T, k], the experts [T, k] int32), the
    group limit and the choice bias as `route` tells them.  Nothing
    here rounds: the same scores give the same experts in the same
    order with the same weights whichever of `_largest`'s passes and
    `choice`'s one call makes the choice."""
    import jax
    import jax.numpy as jnp

    top2 = spec.group_score == "top2_sum"
    if choice is not None:
        # what the choice reads, and the weights where they are not it
        biased = b_router is not None and (spec.n_group == 1 or top2)
        return choice.choose(
            probs + b_router.astype(jnp.float32) if biased else probs,
            probs if biased else None)
    if spec.n_group > 1:
        # by the biased scores and the sum of a group's two best, or by
        # the scores and a group's best; what is left out can never win
        with jax.named_scope("moe_group_choice"):
            by = probs + b_router.astype(jnp.float32) if top2 else probs
            grouped = by.reshape(probs.shape[:-1] + (spec.n_group, -1))
            _, kept = _largest(
                _largest(grouped, 2)[0].sum(-1) if top2
                else grouped.max(-1), spec.topk_group)
            keep = (kept[..., None] == jnp.arange(spec.n_group)).any(-2)
            left = jnp.where(keep[..., None], grouped,
                             -jnp.inf if top2 else 0.0).reshape(probs.shape)
        top_w, top_e = _largest(left, spec.experts_per_token)
        if top2:
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    elif b_router is None:
        top_w, top_e = _largest(probs, spec.experts_per_token)
    else:
        _, top_e = _largest(probs + b_router.astype(jnp.float32),
                            spec.experts_per_token)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
    return top_w, top_e


def route(spec: BlockSpec, m, w_router, b_router=None, choice=None):
    """The router: tokens m [T, D] (float32) -> (weights [T, k]
    float32, experts [T, k] int32), the k largest of the softmax over
    ALL experts, largest first.  Under `router: "sigmoid"` the scores
    are sigmoid(logits).  A CHOICE BIAS `b_router` [E] serves EITHER
    router: the k chosen are the k largest of scores + `b_router` (the
    bias decides the CHOICE alone: the weights are the scores as they
    are, probabilities or sigmoids), renormalised under `norm_topk_prob`
    (divided by their sum plus `norm_topk_eps`) and then times
    `routed_scaling_factor`; a tie goes to the lower
    expert index either way.  "All experts" are all the router's
    columns: with IDENTITY experts (`zero_experts`) the matrix and the
    bias are `n_experts + zero_experts` wide, the softmax is over all
    of them and an index at or past `n_experts` is an identity expert
    (`moe_ffn`).  Under `n_group` > 1 the choice is GROUP-LIMITED:
    the experts lie in `n_group` consecutive groups, a group's score is
    its largest probability, the `topk_group` groups of highest score
    are kept (a tie to the lower group), every other expert's score is
    set to 0 and the k are the largest of what is left.  Under
    `group_score: "top2_sum"` (the sigmoid router with its choice bias)
    everything the CHOICE reads is the biased score c = s + b: a group's
    score is the sum of its two largest c, the groups kept are the
    `topk_group` of highest score, and the k are the largest c among
    the kept groups' experts (an expert of another group is never
    chosen, whatever its c); the weights are the scores s of the chosen,
    as without groups.  The groups' scores and the mask lie under the
    named scope `moe_group_choice`, inside whatever scope the caller is
    in (`moe_ffn`'s `moe_router`).  Every choice is `_largest`'s (k
    passes of a maximum, no sort), or where `choice` is given
    (`kernels.router_choice.select_router_choice`'s kernel for these
    shapes) ONE Pallas call from the scores on: the group limit and the
    k passes over scores it holds in VMEM, the same experts in the same
    order with the same weights.
    Float32 at `highest` precision (one
    bf16 pass moves a probability by 1e-3 of itself and swaps the k-th
    and k+1-th expert wherever they lie that close); the weights are
    the probabilities as they are, renormalised only under
    `norm_topk_prob`.  Renormalised, they are also the softmax over
    the k largest LOGITS alone (Granite's form): p_i / sum of the
    chosen p_j = exp(l_i) / sum of the chosen exp(l_j), and the k
    largest probabilities are the k largest logits."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(m, w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if spec.router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)             # [T, E]
    top_w, top_e = _chosen(spec, probs, b_router, choice)
    if spec.norm_topk_prob:
        total = top_w.sum(-1, keepdims=True)
        # Python's 0.0: no operation, the other blocks' steps as they were
        top_w = top_w / (total + spec.norm_topk_eps
                         if spec.norm_topk_eps else total)
    if spec.routed_scaling_factor != 1.0:
        top_w = top_w * spec.routed_scaling_factor
    return top_w, top_e


def select_rows(scores, valid, k: int):
    """The lightning indexer's choice: scores [..., R] float32 and
    valid [..., R] bool -> bool [..., R], the `k` valid rows of largest
    score (every valid row where there are `k` or fewer), a tie at the
    k-th score to the LOWER row.  Exact and without a sort: a float32's
    bits, turned so that unsigned order is the floats' order, are
    searched a bit at a time for the largest key that `k` rows reach
    (32 counts over the rows), which is the k-th largest score; the
    rows above it are taken, and of the rows AT it the lowest until
    there are `k`.  An invalid row's key is 0, under every score's
    (a NaN score is refused nowhere: the step's are finite)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(
        scores.astype(jnp.float32) + 0.0, jnp.uint32)    # -0.0 -> +0.0
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)
    keys = jnp.where(valid, jnp.maximum(keys, jnp.uint32(1)),
                     jnp.uint32(0))

    def bit(i, kth):
        cand = kth | (top >> i.astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[..., None], axis=-1,
                        dtype=jnp.int32) >= k
        return jnp.where(reach, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(keys.shape[:-1], jnp.uint32))
    kth = kth[..., None]
    above = keys > kth
    tied = (keys == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                            <= room))


def _by_expert(flat_e, e_n: int):
    """A tick's assignments in expert order, by counting: flat_e [n]
    int32, each assignment's expert in [0, e_n] (`e_n`: an absent one,
    in no group) -> (order [n], place [n], sizes [e_n], all int32).
    `place[i]` is where assignment i stands once the assignments are in
    expert order, an expert's in the order they came: the number of
    assignments whose (expert, index) lies below its own, which is the
    inverse permutation of `jnp.argsort(flat_e, stable=True)`.  `order`
    is that `argsort` itself: the assignment whose place is p, found by
    comparing again (n x n comparisons twice, n a tick's rows x k: on a
    TPU a sort of n keys and a scatter each take longer).  `sizes[e]`
    counts expert e's assignments (an absent expert's stand past the
    last group and are counted nowhere)."""
    import jax.numpy as jnp

    n = flat_e.shape[0]
    index = jnp.arange(n, dtype=jnp.int32)
    key = flat_e.astype(jnp.int32) * n + index           # all distinct
    place = jnp.sum(key[None, :] < key[:, None], axis=-1, dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[None, :] == index[:, None],
                              index[None, :], 0), axis=-1, dtype=jnp.int32)
    sizes = jnp.sum(flat_e[:, None] == jnp.arange(e_n), axis=0,
                    dtype=jnp.int32)
    return order, place, sizes


def moe_ffn(spec: BlockSpec, m, w_router, w_gate, w_up, w_down,
            scope=None, experts=None, b_router=None, limit=0.0,
            choice=None):
    """Dropless top-k-of-E SwiGLU expert layer over tokens m [T, D]
    (float32) -> ([T, D] float32, experts hit: int32 scalar, routing:
    `route`'s (weights, experts)).

    Every one of the T*k assignments is computed: they are put in
    expert order (by counting, `_by_expert`: no sort) and run as
    grouped matmuls (one group an expert), so an
    expert's matrices are read once however many rows it has and NO
    capacity bounds a group: what one token gets never depends on
    where the others went, which is what keeps a continuously batched
    sequence bit-identical to the same sequence alone.  Routing is
    `route`'s; the expert matmuls take the weights' dtype with float32
    accumulation.  A token's k results are summed in top-k order.

    Where the description HOLDS a share of the experts (`spec.held`:
    `w_gate`, `w_up`, `w_down` are then those experts' matrices alone)
    the router still routes over all of them and the result is the
    part the held ones give: an assignment to an absent expert stands
    past the last group, is in no group and adds nothing, and its
    weight is NOT shared out among the others (the chip that holds the
    expert adds that part).  `hit` counts held experts.

    An IDENTITY assignment (`spec.zero_experts`: a chosen index at or
    past `n_experts`) is an expert with no matrices that returns its
    input: it adds its weight times the token's own row of `m`, under
    the scope `moe_zero`, and sends NO row to the grouped matmul (like
    an absent expert's it stands past the last group).  Every chip of an
    expert-parallel layer can add that part for its own tokens.

    `experts` is what `kernels.grouped_matmul.select_grouped_matmul`
    returned for these shapes: the Pallas kernel (gate, up and the
    gated product in one call, down in a second, over work items it
    plans from the group sizes under `moe_dispatch`), or None: three
    `jax.lax.ragged_dot`s, the one fallback.  `b_router`: the
    router's choice bias, where the description has one (`route`).
    `limit` L > 0 (the layer's entry of `expert_swiglu_limits`): every
    expert's gate input is min(., L) and its up input clip(., -L, L)
    (`clamped`); on the `ragged_dot`s alone: the kernel's gated product
    is inside its call, so the caller hands no kernel with a limit.
    `choice`: `route`'s (the router's choice as one Pallas call)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    scope = scope or (lambda name: contextlib.nullcontext())
    t_n, k_n = m.shape[0], spec.experts_per_token
    first, e_n = spec.held
    share = spec.has_unheld
    with scope("moe_router"):
        top_w, top_e = route(spec, m, w_router, b_router, choice)
    with scope("moe_dispatch"):
        flat_e = top_e.reshape(t_n * k_n)
        if share:
            # the held experts count from 0; an absent one is e_n,
            # which stands last and falls off the end of `sizes`
            here = (top_e >= first) & (top_e < first + e_n)
            flat_e = jnp.where(here.reshape(-1), flat_e - first, e_n)
        order, back, sizes = _by_expert(flat_e, e_n)
        rows = m[order // k_n].astype(w_gate.dtype)         # [T*k, D]
        hit = jnp.sum(sizes > 0).astype(jnp.int32)
        plan = None if experts is None else experts.plan(sizes)
        if share and plan is not None:
            # no row here at all: the plan's one item would be tile -1
            plan = (plan[0], jnp.maximum(plan[1], 0)) + tuple(plan[2:])
    with scope("moe_experts"):
        f32 = jnp.float32
        if experts is not None:
            assert not limit, "the grouped matmul kernel takes no clamp"
            act = experts.gate_up(rows, w_gate, w_up, plan)
            out = experts.down(act, w_down, plan)
        else:
            gate = jax.lax.ragged_dot(rows, w_gate, sizes,
                                      preferred_element_type=f32)
            up = jax.lax.ragged_dot(rows, w_up, sizes,
                                    preferred_element_type=f32)
            if limit:
                gate, up = clamped(gate, up, limit)
            act = (jax.nn.silu(gate) * up).astype(w_down.dtype)
            out = jax.lax.ragged_dot(act, w_down, sizes,
                                     preferred_element_type=f32)
    with scope("moe_combine"):
        per_tok = out[back].reshape(t_n, k_n, -1)
        if share:
            # rows past the last group are whatever the kernel left
            per_tok = jnp.where(here[..., None], per_tok, 0.0)
        y = (per_tok * top_w[..., None]).sum(axis=1)
    if spec.zero_experts:
        with scope("moe_zero"):
            zero_w = jnp.where(top_e >= spec.n_experts, top_w, 0.0)
            y = y + zero_w.sum(axis=1, keepdims=True) * m
    return y, hit, (top_w, top_e)


def clamped(gate, up, limit: float):
    """A SwiGLU's two inputs under a limit L > 0: the gate input
    min(., L) (SiLU is bounded below by itself), the up input
    clip(., -L, L)."""
    import jax.numpy as jnp

    return jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)


def swiglu(x, w_gate, w_up, w_down, limit=0.0, multipliers=()):
    """One SwiGLU FFN every row takes (a shared expert): x [T, D]
    float32 -> [T, D] float32, the matmuls in the weights' dtype with
    float32 accumulation and the gated product rounded to it, as an
    expert of `moe_ffn` rounds.  `limit` L > 0: the two inputs
    `clamped`.  `multipliers` (m_gate, m_down): the gate input times
    m_gate before the SiLU and the result times m_down, in float32
    (`BlockSpec.mlp_multipliers`; (): neither)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rows = x.astype(w_gate.dtype)
    gate = jnp.dot(rows, w_gate, preferred_element_type=f32)
    up = jnp.dot(rows, w_up, preferred_element_type=f32)
    if limit:
        gate, up = clamped(gate, up, limit)
    if multipliers:
        gate = gate * float(multipliers[0])
    act = (jax.nn.silu(gate) * up).astype(w_down.dtype)
    out = jnp.dot(act, w_down, preferred_element_type=f32)
    return out * float(multipliers[1]) if multipliers else out


def _tail_rows(tail, row, fresh, live):
    """A lane's convolution tail one position on, for every lane: tail
    [S, width - 1, C] float32 (the rows before this position's) and
    row [S, C] (this position's) -> (rows [S, width, C]: what the
    convolution's `width` taps multiply, oldest first; the tail after
    the position).  `fresh` [S] bool: the lane starts a sequence here
    and the rows before are zeros whatever it held; `live` [S] bool: a
    lane that is not keeps its tail as it was.  The one code path of a
    tail's reset, shift and hold: a Mamba-2 layer's x B C rows and a
    gated short convolution's products are the same object at another
    width."""
    import jax.numpy as jnp

    before = jnp.where(fresh[:, None, None], 0.0, tail)
    rows = jnp.concatenate([before, row[:, None, :]], axis=1)
    return rows, jnp.where(live[:, None, None], rows[:, 1:], tail)


def mamba2_step(spec: BlockSpec, u, state, tail, fresh, live, p,
                scope=None):
    """ONE position of a Mamba-2 mixer (Dao & Gu, arXiv:2405.21060;
    G = `ssm_groups` groups of B and C: one, Granite's, or more) for
    every lane: u [S, D] float32 (the normed
    residual) -> (out [S, D] float32, the lane's SSM state [S, H, P, N]
    float32, its convolution tail [S, width - 1, H*P + 2GN] float32:
    the last rows of x B C before the convolution, and what the
    recurrence was given: x B C after the convolution and dt after
    the softplus side by side, [S, H*P + 2GN + H] float32, for a
    comparison that judges the recurrence on its own inputs).

      z, xBC, dt = (u @ W_in) * m      (H*P, H*P + 2GN and H columns; m
                                        `ssm_multipliers` a segment)
      xBC = silu(sum_j w_conv[j] * (tail, xBC)[j] + b_conv)
      x [H, P], B [G, N], C [G, N] = xBC;  dt = softplus(dt + dt_bias)
      h = exp(dt * -exp(A_log)) * h + dt * (x outer B_g)   [H, P, N]
      y = h . C_g + D * x        (head h reads group g = h // (H / G))
      out = (rmsnorm(y * silu(z)) * w) @ W_out

    With one group the norm runs over all H*P columns and the step
    lowers to what it lowered to before groups were built; with more it
    runs over each group's H*P / G columns apart (the gate first, then
    the norm: `mamba_norm_before_gate` false), times the one scale
    [H*P].  `ssm_multipliers` (z, x, B, C, dt; (): none) multiply the
    projection's RESULT in float32, so x, B and C carry theirs into the
    convolution.

    The recurrence IS the prefill: the scheduler feeds a prompt one
    position a tick like any other, so there is no scan over a chunk.
    `fresh` [S] bool: the lane starts a sequence here (its cursor is
    0) and takes a zero state and a zero tail whatever it held, so
    nobody has to zero a lane at admission.  `live` [S] bool: a lane
    that is not keeps state and tail as they were.  `p`: the layer's
    arrays by `param_layout`'s keys.  The recurrence, the convolution
    and the norm are float32; the two projections take the weights'
    dtype with float32 accumulation."""
    import contextlib

    import jax
    import jax.numpy as jnp

    scope = scope or (lambda name: contextlib.nullcontext())
    f32 = jnp.float32
    s_n = u.shape[0]
    h_n, p_n, n_n = spec.ssm_heads, spec.ssm_d_head, spec.ssm_d_state
    g_n = spec.ssm_groups
    di = h_n * p_n
    conv_w, conv_b = p["ssm_conv"]
    with scope("ssm_in_proj"):
        zxd = jnp.dot(u.astype(p["ssm_in"].dtype), p["ssm_in"],
                      preferred_element_type=f32)
        if spec.ssm_multipliers:
            import numpy as np

            zxd = zxd * np.repeat(
                np.asarray(spec.ssm_multipliers, np.float32),
                (di, di, g_n * n_n, g_n * n_n, h_n))
        z, xbc, dt = (zxd[:, :di], zxd[:, di:-h_n], zxd[:, -h_n:])
    with scope("ssm_conv"):
        rows, tail = _tail_rows(tail, xbc, fresh, live)
        xbc = jax.nn.silu((rows * conv_w.astype(f32)[None]).sum(axis=1)
                          + conv_b.astype(f32))
    with scope("ssm_scan"):
        x = xbc[:, :di].reshape(s_n, h_n, p_n)
        if g_n == 1:
            # one B and one C for every head
            b, c = xbc[:, di:di + n_n], xbc[:, di + n_n:]
        else:
            # a group's B and C under each of its H / G heads
            b, c = (jnp.repeat(t.reshape(s_n, g_n, n_n), h_n // g_n, axis=1)
                    for t in (xbc[:, di:di + g_n * n_n],
                              xbc[:, di + g_n * n_n:]))
        dt = jax.nn.softplus(dt + p["ssm_dt"].astype(f32))      # [S, H]
        given = jnp.concatenate([xbc, dt], axis=-1)
        decay = jnp.exp(-dt * jnp.exp(p["ssm_a_log"].astype(f32)))
        h0 = jnp.where(fresh[:, None, None, None], 0.0, state)
        # [S, 1, 1, N] for all heads, or [S, H, 1, N]: a head's group's
        by_head = ((lambda t: t[:, None, None, :]) if g_n == 1
                   else (lambda t: t[:, :, None, :]))
        h = (decay[:, :, None, None] * h0
             + (dt[:, :, None] * x)[..., None] * by_head(b))
        y = ((h * by_head(c)).sum(axis=-1)
             + p["ssm_d"].astype(f32)[None, :, None] * x)
        state = jnp.where(live[:, None, None, None], h, state)
    with scope("ssm_gate_norm"):
        y = y.reshape(s_n, di) * jax.nn.silu(z)
        if g_n == 1:
            y = norm(spec, y, p["ssm_gate_norm"].astype(f32))
        else:
            # each group's columns apart, then the one scale
            y = norm(spec, y.reshape(s_n, g_n, di // g_n), 1.0).reshape(
                s_n, di) * p["ssm_gate_norm"].astype(f32)
    with scope("ssm_out_proj"):
        out = jnp.dot(y.astype(p["ssm_out"].dtype), p["ssm_out"],
                      preferred_element_type=f32)
    return out, state, tail, given


def short_conv_step(spec: BlockSpec, u, tail, fresh, live, p, scope=None):
    """ONE position of a gated short convolution (LFM2's "conv" layer)
    for every lane, `mamba2_step`'s sibling: u [S, D] float32 (the
    normed residual) -> (out [S, D] float32, the lane's tail
    [S, conv_width - 1, D] float32: the last rows of the product B * u
    before the convolution).

      B, C, x = u @ W_in               (three gates of D columns each)
      g = B * x
      c = sum_j w[j] * (tail, g)[j]    (depthwise, causal, NO activation)
      out = (C * c) @ W_out

    The step IS the prefill, as a Mamba layer's: a prompt goes through
    one position a tick.  `fresh` and `live` are `mamba2_step`'s, under
    the same contract and through the same code (`_tail_rows`): a lane
    whose cursor is 0 starts from a zero tail whatever it holds, a lane
    that is not live keeps its tail.  `p`: the layer's arrays by
    `param_layout`'s keys ("conv_in", "conv_w", "conv_out").  The
    product, the convolution and the second gate are float32; the two
    projections take the weights' dtype with float32 accumulation."""
    import contextlib

    import jax.numpy as jnp

    scope = scope or (lambda name: contextlib.nullcontext())
    f32 = jnp.float32
    d = u.shape[-1]
    with scope("conv_in_proj"):
        bcx = jnp.dot(u.astype(p["conv_in"].dtype), p["conv_in"],
                      preferred_element_type=f32)
        b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    with scope("conv_gate"):
        rows, tail = _tail_rows(tail, b * x, fresh, live)
        y = c * (rows * p["conv_w"].astype(f32)[None]).sum(axis=1)
    with scope("conv_out_proj"):
        out = jnp.dot(y.astype(p["conv_out"].dtype), p["conv_out"],
                      preferred_element_type=f32)
    return out, tail


def delta_rule(state, q, k, v, g, beta, fresh, live):
    """The recurrence of `delta_rule_step` in plain `jax.numpy`: state
    [S, H, K keys, K values], q, k, v and the log decay g [S, H, K],
    beta [S, H] (all float32), fresh and live bool [S] -> (the state
    after this position, o [S, H, K]).  What runs where
    `kernels.delta_rule.select_delta_rule` refuses, and what that
    kernel is held to."""
    import jax.numpy as jnp

    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    decayed = jnp.exp(g)[..., None] * s0
    seen = (k[..., None] * decayed).sum(axis=2)                 # k^T S'
    new = decayed + (beta[..., None] * k)[..., None] * (
        v - seen)[:, :, None, :]
    o = (new * q[..., None]).sum(axis=2)                        # S^T q
    return jnp.where(live[:, None, None, None], new, state), o


def delta_rule_step(spec: BlockSpec, u, state, tail, fresh, live, p,
                    scope=None, kernel=None):
    """ONE position of a gated delta-rule mixer (Solar-Open2's linear
    layer, after Kimi Delta Attention, arXiv:2510.26692) for every
    lane, `mamba2_step`'s sibling: u [S, D] float32 (the normed
    residual) -> (out [S, D] float32, the lane's state [S, H, K, K]
    float32: a matrix [keys, values] a head, its tail [S, width - 1,
    3*H*K] float32: the last rows of q | k | v before the convolution).

      q, k, v = silu(sum_j w_conv[j] * (tail, u @ W_in)[j])   [H, K] each
      q = q / |q| / sqrt(K);  k = k / |k|          (L2 a head, eps 1e-6)
      g = -exp(A_log) * softplus((u @ W_fa) @ W_fb + dt_bias)    [H, K]
          (under `delta_gate_floor` f: f * sigmoid(exp(A_log) * (. +
          dt_bias)); at `delta_gate_rank` 0 the pair is ONE matrix W_f)
      beta = sigmoid(u @ W_b) [H]          (times 2 under `delta_neg_eigval`)
      S' = exp(g)[:, :, None] * S          a decay a key CHANNEL
      S = S' + beta * k (v - k^T S')^T     the rank-one correction
      o = S^T q;  gate = sigmoid((u @ W_ga) @ W_gb + b_g)
          (at `delta_gate_rank` 0: sigmoid(u @ W_g), no bias)
      out = (rmsnorm_head(o) * w * gate) @ W_out

    The step IS the prefill, as a Mamba layer's.  `fresh` and `live` are
    `mamba2_step`'s, under the same contract and (for the tail) through
    the same code (`_tail_rows`): a lane whose cursor is 0 starts from a
    zero state and tail whatever it holds, a lane that is not live
    keeps both.  `p`: the layer's arrays by `param_layout`'s keys.  The
    recurrence, the convolution, the L2 norms, the gates and the head
    norm are float32; the projections take the weights' dtype with
    float32 accumulation.  `kernel`: what
    `kernels.delta_rule.select_delta_rule` returned for these shapes;
    its `rule` then stands where `delta_rule`'s lines do, a head's
    matrix crossing HBM once in and once out."""
    import contextlib

    import jax
    import jax.numpy as jnp

    scope = scope or (lambda name: contextlib.nullcontext())
    f32 = jnp.float32
    s_n = u.shape[0]
    h_n, k_n = spec.delta_heads, spec.delta_d_head
    hk = h_n * k_n

    def proj(x, *names):
        for name in names:
            x = jnp.dot(x.astype(p[name].dtype), p[name],
                        preferred_element_type=f32)
        return x

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    with scope("delta_in_proj"):
        qkv = proj(u, "delta_in")
    with scope("delta_conv"):
        rows, tail = _tail_rows(tail, qkv, fresh, live)
        qkv = jax.nn.silu(
            (rows * p["delta_conv"].astype(f32)[None]).sum(axis=1))
        q, k, v = (qkv[:, i * hk:(i + 1) * hk].reshape(s_n, h_n, k_n)
                   for i in range(3))
        q, k = unit(q) * (k_n ** -0.5), unit(k)
    # the gates' maps: a low-rank pair each, or one full matrix each
    decay_w, gate_w = ((("delta_fa", "delta_fb"), ("delta_ga", "delta_gb"))
                       if "delta_fa" in p else (("delta_f",), ("delta_gw",)))
    with scope("delta_gates"):
        if spec.delta_gate_floor:
            # bounded: a channel's decay a step lies in (e^floor, 1)
            g = spec.delta_gate_floor * jax.nn.sigmoid(
                jnp.exp(p["delta_a_log"].astype(f32))[None, :, None] * (
                    proj(u, *decay_w) + p["delta_dt"].astype(f32)
                ).reshape(s_n, h_n, k_n))
        else:
            g = -jnp.exp(p["delta_a_log"].astype(f32))[None, :, None] * (
                jax.nn.softplus(proj(u, *decay_w)
                                + p["delta_dt"].astype(f32))
            ).reshape(s_n, h_n, k_n)
        beta = jax.nn.sigmoid(proj(u, "delta_b"))               # [S, H]
        if spec.delta_neg_eigval:
            beta = 2.0 * beta
    with scope("delta_rule"):
        state, o = (delta_rule if kernel is None else kernel.rule)(
            state, q, k, v, g, beta, fresh, live)
    with scope("delta_gate_norm"):
        gate = proj(u, *gate_w)
        if "delta_g" in p:
            gate = gate + p["delta_g"].astype(f32)
        gate = jax.nn.sigmoid(gate)
        o = norm(spec, o, p["delta_o_norm"].astype(f32))
        y = o.reshape(s_n, hk) * gate
    with scope("delta_out_proj"):
        out = proj(y, "delta_out")
    return out, state, tail
