"""Transformer model family (decoder-only LM + encoder-decoder translator).

The reference predates the transformer as a packaged model: attention is
composed from primitive ops (/root/reference/python/paddle/v2/fluid/nets.py:162-219
scaled_dot_product_attention) and its NMT book model is a plain seq2seq
without attention (/root/reference/python/paddle/v2/fluid/tests/book/
test_machine_translation.py:54-121).  The rebuild promotes the transformer
to a first-class model family because it is the TPU-native long-sequence
answer to the reference's LoD/DynamicRNN machinery (SURVEY.md section 5.7):
static shapes + masking, flash-attention Pallas kernel on the hot path
(kernels/flash_attention.py), and ring/Ulysses sequence parallelism
(parallel/ring_attention.py) for contexts that exceed one chip.

All blocks are pre-LN (LN -> sublayer -> residual add), which keeps
activations bounded for bf16 training on the MXU.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import layers, nets
from ..core.flags import get_flag
from ..core.framework import pipeline_stage
from ..initializer import NormalInitializer

__all__ = [
    "multi_head_attention",
    "positionwise_ffn",
    "transformer_encoder",
    "transformer_decoder",
    "transformer_lm",
    "transformer_translate",
    "build_lm_generator",
    "build_lm_kv_decoder",
    "build_lm_paged_decoder",
    "PagedDecoder",
    "build_translate_generator",
    "build_lm_beam_search",
]


def _proj(x, size, name=None):
    """Linear projection over the feature axis of a [b, s, d] tensor."""
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     bias_attr=True, act=None, name=name)


def multi_head_attention(queries, keys, values, d_model, n_heads,
                         causal=False, dropout_rate=0.0, is_test=False):
    """Projected multi-head attention on [b, s, d] tensors.

    Projections + nets.scaled_dot_product_attention (which lowers to the
    Pallas flash-attention kernel whenever there is no attention-weight
    dropout); queries and keys/values may have different sequence lengths
    (cross attention).
    """
    q = _proj(queries, d_model)
    k = _proj(keys, d_model)
    v = _proj(values, d_model)
    ctx = nets.scaled_dot_product_attention(
        q, k, v, num_heads=n_heads, dropout_rate=dropout_rate,
        causal=causal, is_test=is_test)
    return _proj(ctx, d_model)


def positionwise_ffn(x, d_model, d_inner, dropout_rate=0.0, is_test=False):
    hidden = layers.fc(input=x, size=d_inner, num_flatten_dims=2,
                       act="relu")
    if dropout_rate:
        hidden = layers.dropout(hidden, dropout_prob=dropout_rate,
                                is_test=is_test)
    return layers.fc(input=hidden, size=d_model, num_flatten_dims=2)


def _pre_ln(x):
    return layers.layer_norm(x, begin_norm_axis=2)


def _embed(ids, vocab_size, d_model, max_len, dropout_rate, is_test):
    """Token embedding + learned positional embedding.

    ids: [b, s] int64.  Positions use a learned table sized to the static
    sequence length (static shapes are the TPU answer to the reference's
    LoD offsets — SURVEY.md section 5.7).
    """
    seq = int(ids.shape[1])
    if seq > max_len:
        raise ValueError(f"sequence length {seq} exceeds max_len {max_len}")
    # no fixed param names: two models in one program must not silently
    # share tables (Block.create_parameter overwrites same-named vars)
    emb = layers.embedding(
        ids, size=[vocab_size, d_model],
        param_attr={"initializer": NormalInitializer(0.0, 0.02)})
    # position table sized to max_len so checkpoints restore across
    # sequence lengths; the current static length slices into it
    pos_table = layers.create_parameter(
        shape=[max_len, d_model], dtype=emb.dtype,
        default_initializer=NormalInitializer(0.0, 0.02))
    pos = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq])
    x = layers.elementwise_add(emb, pos, axis=1)
    if dropout_rate:
        x = layers.dropout(x, dropout_prob=dropout_rate, is_test=is_test)
    return x


def _encoder_block(x, d_model, n_heads, d_inner, dropout_rate, is_test):
    ln_x = _pre_ln(x)
    a = multi_head_attention(ln_x, ln_x, ln_x, d_model, n_heads,
                             causal=False,
                             dropout_rate=dropout_rate, is_test=is_test)
    x = layers.elementwise_add(x, a)
    f = positionwise_ffn(_pre_ln(x), d_model, d_inner, dropout_rate, is_test)
    return layers.elementwise_add(x, f)


def _decoder_block(x, enc_out, d_model, n_heads, d_inner, dropout_rate,
                   is_test):
    ln_x = _pre_ln(x)
    a = multi_head_attention(ln_x, ln_x, ln_x, d_model, n_heads,
                             causal=True, dropout_rate=dropout_rate,
                             is_test=is_test)
    x = layers.elementwise_add(x, a)
    if enc_out is not None:
        c = multi_head_attention(_pre_ln(x), enc_out, enc_out, d_model,
                                 n_heads, causal=False,
                                 dropout_rate=dropout_rate, is_test=is_test)
        x = layers.elementwise_add(x, c)
    f = positionwise_ffn(_pre_ln(x), d_model, d_inner, dropout_rate, is_test)
    return layers.elementwise_add(x, f)


def transformer_encoder(src_ids, vocab_size, d_model=256, n_heads=4,
                        n_layers=2, d_inner=None, max_len=2048,
                        dropout_rate=0.0, is_test=False, remat=None):
    """Bidirectional encoder over [b, s] token ids -> [b, s, d_model].

    `remat=True` wraps each block in layers.recompute (jax.checkpoint):
    the block's internal activations are re-run in backward instead of
    living in HBM — the standard bytes-for-FLOPs trade on a
    memory-bound training step.  remat=None defers to the `remat` flag
    (PADDLE_TPU_REMAT, build-time)."""
    d_inner = d_inner or 4 * d_model
    if remat is None:
        remat = bool(get_flag("remat"))
    x = _embed(src_ids, vocab_size, d_model, max_len, dropout_rate,
               is_test)
    for _ in range(n_layers):
        if remat:
            x = layers.recompute(
                lambda x=x: _encoder_block(x, d_model, n_heads, d_inner,
                                           dropout_rate, is_test))
        else:
            x = _encoder_block(x, d_model, n_heads, d_inner, dropout_rate,
                               is_test)
    return _pre_ln(x)


def transformer_decoder(tgt_ids, enc_out, vocab_size, d_model=256,
                        n_heads=4, n_layers=2, d_inner=None, max_len=2048,
                        dropout_rate=0.0, is_test=False, remat=None,
                        pipeline_stages=None):
    """Causal decoder ([b, t] ids, optional [b, s, d] memory) -> [b, t, d].

    `pipeline_stages=S` annotates the block stack with
    `fluid.pipeline_stage` (n_layers/S consecutive blocks per stage) so
    the SAME program runs serially or as a GPipe pipeline under
    parallel.PipelineExecutor over a 'pp' mesh axis — the DSL-reachable
    counterpart of the reference's per-layer device placement
    (/root/reference/paddle/gserver/gradientmachines/ParallelNeuralNetwork.h).
    Embedding stays outside the trunk (the usual GPipe decomposition);
    the final layer_norm lands in the post section.
    """
    d_inner = d_inner or 4 * d_model
    if remat is None:
        # the `remat` flag never overrides a pipeline build (the GPipe
        # schedule already recomputes per-microbatch)
        remat = bool(get_flag("remat")) and not pipeline_stages
    if pipeline_stages:
        if n_layers % pipeline_stages:
            raise ValueError(
                f"n_layers {n_layers} must be a multiple of "
                f"pipeline_stages {pipeline_stages}")
        if remat:
            raise NotImplementedError(
                "remat inside pipeline stages is redundant: the GPipe "
                "schedule already recomputes per-microbatch")
    x = _embed(tgt_ids, vocab_size, d_model, max_len, dropout_rate,
               is_test)
    for i in range(n_layers):
        stage = (pipeline_stage(i * pipeline_stages // n_layers)
                 if pipeline_stages else contextlib.nullcontext())
        with stage:
            if remat:
                x = layers.recompute(
                    lambda x=x: _decoder_block(x, enc_out, d_model,
                                               n_heads, d_inner,
                                               dropout_rate, is_test))
            else:
                x = _decoder_block(x, enc_out, d_model, n_heads, d_inner,
                                   dropout_rate, is_test)
    return _pre_ln(x)


def transformer_lm(ids, vocab_size, d_model=256, n_heads=4, n_layers=2,
                   d_inner=None, max_len=2048, dropout_rate=0.0,
                   is_test=False, return_logits=False,
                   pipeline_stages=None):
    """Decoder-only causal language model: [b, s] ids -> [b, s, vocab]
    next-token softmax probabilities (raw logits with
    `return_logits=True`; `pipeline_stages` as in transformer_decoder)."""
    h = transformer_decoder(ids, None, vocab_size, d_model, n_heads,
                            n_layers, d_inner, max_len, dropout_rate,
                            is_test, pipeline_stages=pipeline_stages)
    logits = layers.fc(input=h, size=vocab_size, num_flatten_dims=2)
    if return_logits:
        return logits
    return layers.softmax(logits)


def transformer_translate(src_ids, tgt_ids, src_vocab, tgt_vocab,
                          d_model=256, n_heads=4, n_layers=2, d_inner=None,
                          max_len=2048, dropout_rate=0.0, is_test=False,
                          return_logits=False, remat=None):
    """Encoder-decoder translation model -> [b, t, tgt_vocab] softmax
    (or raw logits with `return_logits=True` — training should feed
    those to softmax_with_cross_entropy so the [b*t, vocab] probability
    tensor is never materialized in HBM: at vocab 30k that tensor plus
    its backward dominates the step's memory traffic)."""
    enc = transformer_encoder(src_ids, src_vocab, d_model, n_heads,
                              n_layers, d_inner, max_len, dropout_rate,
                              is_test, remat=remat)
    dec = transformer_decoder(tgt_ids, enc, tgt_vocab, d_model, n_heads,
                              n_layers, d_inner, max_len, dropout_rate,
                              is_test, remat=remat)
    logits = layers.fc(input=dec, size=tgt_vocab, num_flatten_dims=2)
    if return_logits:
        return logits
    return layers.softmax(logits)


def build_lm_generator(vocab_size, max_len, d_model=256, n_heads=4,
                       n_layers=2, d_inner=None):
    """Autoregressive generation for the decoder-only LM, fully on-device.

    Builds the LM Program once at width `max_len`, bridges it to a pure
    jax function (core/executor.program_to_fn), and wraps the decode loop
    in `jax.lax.fori_loop` inside ONE jit — the whole generation runs as a
    single XLA computation (no per-token host round-trips; the causal
    mask makes positions past the cursor inert, so the fixed-width
    forward is exact).  The reference's analogue is host-side While +
    beam_search ops over LoD (book/08 decode); this is the static-shape
    TPU counterpart for the transformer family.

    Returns (startup_program, generate) where
      generate(states, prompt_ids [B, P], num_steps,
               temperature=0.0, seed=0) -> ids [B, max_len]
    with greedy argmax at temperature 0 and softmax sampling otherwise.
    `states` is the param dict from the startup program (e.g. via
    `Parameters` or `_init_states`-style scope reads), so generation uses
    the same trained values as training.
    """
    import jax
    import jax.numpy as jnp

    from ..core.framework import Program, program_guard
    from ..core.executor import program_to_fn

    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids_in = layers.data(name="gen_ids", shape=[max_len],
                             dtype="int64")
        probs = transformer_lm(ids_in, vocab_size, d_model=d_model,
                               n_heads=n_heads, n_layers=n_layers,
                               d_inner=d_inner, max_len=max_len,
                               is_test=True)
    fn = program_to_fn(main, ["gen_ids"], [probs.name])

    # ONE jit for the builder's lifetime: defined here (not inside
    # generate) so repeated generate() calls hit the executable cache —
    # a per-call closure would re-trace+compile the whole decode loop
    # every time.  p/num_steps/temperature are static (re-trace only per
    # distinct shape/temperature).
    import functools

    @functools.partial(jax.jit,
                       static_argnames=("p", "num_steps", "temperature"))
    def _run(ids0, states, key, p, num_steps, temperature):
        def body(i, carry):
            ids, k = carry
            fetches, _ = fn({"gen_ids": ids}, states, k)
            pr = fetches[probs.name]              # [B, max_len, V]
            step_p = jax.lax.dynamic_slice_in_dim(
                pr, i - 1, 1, axis=1)[:, 0]       # [B, V] at cursor-1
            if temperature and temperature > 0.0:
                k, sub = jax.random.split(k)
                logits = jnp.log(step_p + 1e-9) / temperature
                nxt = jax.random.categorical(sub, logits, axis=-1)
            else:
                nxt = jnp.argmax(step_p, axis=-1)
            ids = jax.lax.dynamic_update_slice(
                ids, nxt[:, None].astype(jnp.int32), (0, i))
            return ids, k

        ids, _ = jax.lax.fori_loop(p, p + num_steps, body, (ids0, key))
        return ids

    def generate(states, prompt_ids, num_steps, temperature=0.0, seed=0):
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        b, p = prompt_ids.shape
        assert p + num_steps <= max_len, "prompt + steps exceeds max_len"
        ids0 = jnp.zeros((b, max_len), jnp.int32)
        ids0 = jax.lax.dynamic_update_slice(ids0, prompt_ids, (0, 0))
        key = jax.random.key(seed)
        return _run(ids0, states, key, p, int(num_steps),
                    float(temperature))

    generate.state_names = list(fn.state_in_names)
    return startup, generate


def _lm_param_structure(vocab_size, max_len, d_model, n_heads, n_layers,
                        d_inner):
    """Build the LM Program once and extract its parameter names
    STRUCTURALLY (op walk, creation order) so a hand-rolled incremental
    decoder computes over the SAME trained values as the Program path.

    Returns (startup, param_names, tok_emb, pos_tab, lns, weights,
    biases); shared by build_lm_kv_decoder (dense cache) and
    build_lm_paged_decoder (block-table cache)."""
    from ..core.framework import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids_in = layers.data(name="gen_ids", shape=[max_len],
                             dtype="int64")
        transformer_lm(ids_in, vocab_size, d_model=d_model,
                       n_heads=n_heads, n_layers=n_layers,
                       d_inner=d_inner, max_len=max_len, is_test=True)

    blk = main.global_block()
    params = {v.name for v in blk.all_parameters()}
    tok_emb = pos_tab = None
    lns, weights, biases = [], [], []
    for op in blk.ops:
        if op.type == "lookup_table":
            tok_emb = op.inputs["W"][0]
        elif op.type == "slice" and op.inputs["Input"][0] in params:
            pos_tab = op.inputs["Input"][0]
        elif op.type == "layer_norm":
            lns.append((op.inputs["Scale"][0], op.inputs["Bias"][0]))
        elif op.type == "mul":
            weights.append(op.inputs["Y"][0])
        elif op.type == "elementwise_add":
            y = op.inputs.get("Y", [None])[0]
            if y in params and len(biases) < len(weights):
                biases.append(y)
    assert tok_emb and pos_tab, "unexpected LM program structure"
    assert len(weights) == 6 * n_layers + 1, (len(weights), n_layers)
    assert len(lns) == 2 * n_layers + 1
    assert len(biases) == len(weights)
    shapes = {v.name: tuple(int(d) for d in v.shape)
              for v in blk.all_parameters()}
    return startup, shapes, tok_emb, pos_tab, lns, weights, biases


def build_lm_kv_decoder(vocab_size, max_len, d_model=256, n_heads=4,
                        n_layers=2, d_inner=None):
    """Incremental (KV-cache) generation for the decoder-only LM.

    `build_lm_generator` re-runs the full fixed-width forward per token
    (O(L) matmuls per step).  This fast path keeps per-layer K/V caches
    and computes ONE token per step — the standard serving decode loop —
    as a hand-rolled jax function over the SAME trained parameters:
    the LM Program is built once, its parameter names are extracted
    structurally (op walk, creation order), and the incremental math
    mirrors nets.scaled_dot_product_attention's feature-major head split.
    Token-identical greedy decode vs the full forward is pinned by
    tests/test_transformer.py.

    Returns (startup_program, generate) with the same signature as
    `build_lm_generator`.
    """
    import math

    import jax
    import jax.numpy as jnp

    d_inner = d_inner or 4 * d_model
    d_head = d_model // n_heads

    startup, shapes, tok_emb, pos_tab, lns, weights, biases = (
        _lm_param_structure(vocab_size, max_len, d_model, n_heads,
                            n_layers, d_inner))

    import functools

    scale = 1.0 / math.sqrt(d_head)

    # one jit per builder (executable cache survives across generate()
    # calls; p/num_steps/temperature are static)
    @functools.partial(jax.jit,
                       static_argnames=("p", "num_steps", "temperature"))
    def _run(ids0, caches0, g, key, p, num_steps, temperature):
        # params enter as ARGUMENTS (not jit-closure constants: baking
        # the weights into the executable makes XLA treat every matmul
        # operand as a literal — measured 10x slower on the chip)
        b = ids0.shape[0]

        def W(i):
            return g[weights[i]], g[biases[i]]

        def ln(x, i):
            s, b = g[lns[i][0]], g[lns[i][1]]
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(var + 1e-5) * s + b

        def body(i, carry):
            ids, caches, k = carry
            tok = jax.lax.dynamic_slice_in_dim(ids, i, 1, 1)[:, 0]
            x = g[tok_emb][tok] + g[pos_tab][i]        # [B, D]
            new_caches = []
            for l in range(n_layers):
                h = ln(x, 2 * l)
                wq, bq = W(6 * l + 0)
                wk, bk = W(6 * l + 1)
                wv, bv = W(6 * l + 2)
                wo, bo = W(6 * l + 3)
                q = h @ wq + bq
                kk = h @ wk + bk
                vv = h @ wv + bv
                ck, cv = caches[l]
                ck = jax.lax.dynamic_update_slice(
                    ck, kk[:, None, :], (0, i, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, vv[:, None, :], (0, i, 0))
                new_caches.append((ck, cv))
                qh = q.reshape(b, n_heads, d_head)
                kh = ck.reshape(b, max_len, n_heads, d_head)
                vh = cv.reshape(b, max_len, n_heads, d_head)
                sc = jnp.einsum("bhd,bshd->bhs", qh, kh) * scale
                sc = jnp.where(
                    (jnp.arange(max_len) <= i)[None, None, :],
                    sc, -jnp.inf)
                w_att = jax.nn.softmax(sc, axis=-1)
                ctxh = jnp.einsum("bhs,bshd->bhd", w_att, vh)
                x = x + (ctxh.reshape(b, d_model) @ wo + bo)
                h2 = ln(x, 2 * l + 1)
                w1, b1 = W(6 * l + 4)
                w2, b2 = W(6 * l + 5)
                x = x + (jax.nn.relu(h2 @ w1 + b1) @ w2 + b2)
            xf = ln(x, 2 * n_layers)
            wf, bf = W(6 * n_layers)
            logits = xf @ wf + bf                       # [B, V]
            if temperature and temperature > 0.0:
                k, sub = jax.random.split(k)
                nxt = jax.random.categorical(
                    sub, logits / temperature, axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            # past the prompt, the model's token becomes position i+1
            keep_prompt = (i + 1) < p
            cur = jax.lax.dynamic_slice_in_dim(ids, i + 1, 1, 1)[:, 0]
            wr = jnp.where(keep_prompt, cur, nxt.astype(jnp.int32))
            ids = jax.lax.dynamic_update_slice(
                ids, wr[:, None], (0, i + 1))
            return ids, tuple(new_caches), k

        ids, _, _ = jax.lax.fori_loop(0, p + num_steps - 1, body,
                                      (ids0, caches0, key))
        return ids

    def generate(states, prompt_ids, num_steps, temperature=0.0, seed=0):
        g_in = {n: jnp.asarray(v) for n, v in states.items()}
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        b, p = prompt_ids.shape
        assert p + num_steps <= max_len
        ids0 = jnp.zeros((b, max_len), jnp.int32)
        ids0 = jax.lax.dynamic_update_slice(ids0, prompt_ids, (0, 0))
        caches0 = tuple(
            (jnp.zeros((b, max_len, d_model)),
             jnp.zeros((b, max_len, d_model))) for _ in range(n_layers))
        return _run(ids0, caches0, g_in, jax.random.key(seed), p,
                    int(num_steps), float(temperature))

    generate.state_names = sorted(shapes)
    generate.state_shapes = shapes
    return startup, generate


# What a block cannot be served with, by the kind of state that rules it
# out and by what was asked: `PagedDecoder.refuses`, which
# `GenerationServer` raises as it stands.
_REFUSALS = {
    "ring": {
        "draft_model": (
            "a decoder with sliding-window layers takes no draft model: "
            "speculative verification writes a window of positions before it "
            "attends, which a ring one window long cannot hold "
            "(build_lm_paged_decoder's step_window refuses it too)")},
    # (the prefix cache works over a ring too: a cached prompt block's
    # sliding-layer rows live in the ring of the lane that wrote them, so
    # the decoder offers `snapshot_save` and `snapshot_restore` of a
    # lane's ring blocks and a hit ends at a block whose snapshot the
    # server restores, like a lane's state below; docs/serving.md "A
    # latent ring beside a selected table")
    # the prefix cache works: the decoder offers `snapshot_save` and
    # `snapshot_restore`, and a hit ends at a block whose snapshot of the
    # lane's state and tails the server restores (docs/serving.md "A
    # snapshot of a lane's state")
    "state": {
        "draft_model": (
            "a decoder whose layers keep a recurrent state or a convolution "
            "tail a lane takes no draft model: speculative verification runs "
            "a window of positions through step_window, and a lane's state "
            "is computed one position a step (and cannot be rolled back over "
            "rejected tokens: a snapshot is kept a prompt, not a position)")},
    # a cached block holds every layer's latent rows: the prefix cache
    # works on the latent table as on any other
    "latent": {
        "draft_model": (
            "a decoder with a latent cache takes no draft model: speculative "
            "verification runs a window of positions through step_window, "
            "whose XLA gather path knows a K pool and a V pool and is not "
            "built for one latent row a position "
            "(build_lm_paged_decoder's step_window refuses it too)")},
    # a cached block holds every layer's latent rows AND every index-key
    # plane's keys, on the one table: the prefix cache works
    "sparse": {
        "draft_model": (
            "a decoder with a lightning indexer takes no draft model: "
            "speculative verification runs a window of positions through "
            "step_window, and a selection is computed for ONE query position "
            "a lane a step (a chunk of positions would want a selection a "
            "position and the expanded form over each; "
            "build_lm_paged_decoder's step_window refuses it too)")},
    # a cached block holds every pass's K/V: the prefix cache works
    "loop": {
        "draft_model": (
            "a decoder with a looped stack takes no draft model: speculative "
            "verification runs a window of positions through step_window, "
            "which is not built for a stack run several passes a token "
            "(build_lm_paged_decoder's step_window refuses it too)")},
}


@dataclasses.dataclass(eq=False)
class PagedDecoder:
    """What `build_lm_paged_decoder` returns, its only constructor: the
    jitted steps of one block description (its docstring has their
    arguments), what they keep on the device, and what the builder
    alone knows of them (docs/serving.md "What a decoder tells the
    server").  Not frozen: a step's first trace sets `expert_kernel`,
    `router_choice` and `delta_kernel`."""

    step: Callable
    step_window: Callable
    step_logits: Callable
    # `step_logits` and what every layer's router (every pass of a
    # looped stack) computed, or, for a block of parallel layers, what
    # each layer's recurrence was given and left; None for a block with
    # none of them
    step_routing: Optional[Callable]
    # init_pool(num_blocks, device, window_blocks=, lanes=) -> the pools;
    # slot_rings(slots) -> int32 [slots, window_blocks_per_seq]
    init_pool: Callable
    slot_rings: Callable
    # the backend the steps were built for (selection, donation)
    platform: str
    # names of what `step` returns after the pools, counted on the
    # device: "moe_experts_hit" (and "moe_rows_held" where the block
    # holds a share of its experts, and "moe_tokens_here" where its
    # router is group-limited or has identity experts, and with those
    # "moe_zero_assignments" and "moe_assignments"), "exit_gate_open";
    # () without
    step_counters: Tuple[str, ...]
    # layers with experts: what `moe_experts_hit` and `moe_rows_held`
    # are summed over (0: a block without)
    moe_layers: int
    # a looped stack: the passes a token takes over the one stack (1: a
    # plain block) and the planes its table pool has
    passes: int
    kv_planes: int
    # {a parameter's op_name in compiled text: the part's scope}, for
    # `profiler.register_jitted`
    compiler_scopes: Dict[str, str]
    # the parameters: names (sorted) and shapes
    state_names: List[str]
    state_shapes: Dict[str, Tuple[int, ...]]
    block_size: int
    max_blocks_per_seq: int
    max_len: int
    n_layers: int
    d_model: int
    vocab_size: int
    # the pool's storage: "fp32", "bf16" or "int8"
    kv_dtype: str
    # K+V bytes of one table block over the planes that hold it (a
    # latent block's one row a position)
    bytes_per_block: int
    # the ring of a block with sliding layers: blocks a sequence (0:
    # every layer is full), the window, and a ring block's bytes
    window_blocks_per_seq: int
    window: int
    window_bytes_per_block: int
    # attention layers by where their K/V live: on the table (a plane
    # for every pass of a looped stack), on a slot's ring
    table_layers: int
    ring_layers: int
    # a lightning indexer's key planes: the layers that compute a
    # selection, each with an index key a position on the table's
    # blocks (0: no indexer)
    index_planes: int
    # what belongs to a LANE: how many layers keep a recurrent state or
    # a convolution tail (Mamba and delta-rule layers: both; gated short
    # convolutions: the tail alone; 0: none; EVERY layer of a block of
    # parallel layers, which `table_layers` counts too) and the float32
    # bytes a lane holds over them; for a block with sliding layers the
    # bytes of a lane's RING over them, in the pool's dtype (what a
    # snapshot of it holds)
    state_layers: int
    state_bytes_per_lane: int
    # SNAPSHOTS of what belongs to a lane, for a prefix cache over such a
    # block (None, all three, for a block whose lanes keep nothing; a
    # lane's ring blocks of every sliding layer for a block with a ring):
    # init_snapshots(rows, device=None) -> an opaque pool of `rows`
    # snapshots; snapshot_save(snapshots, pool_k, pool_v, lane, row) ->
    # snapshots, with lane `lane`'s state and tails of every such layer
    # copied into row `row`; snapshot_restore(pool_k, pool_v, snapshots,
    # lane, row) -> (pool_k, pool_v), with row `row` copied back into
    # lane `lane`.  Each donates what it writes; lane and row are int32
    # scalars
    init_snapshots: Optional[Callable]
    snapshot_save: Optional[Callable]
    snapshot_restore: Optional[Callable]
    # what attends in the resident step (`step`, `step_logits`,
    # `step_routing`): the streaming Pallas kernel ("pallas";
    # "pallas:latent" over a latent pool), or the XLA gather and the
    # reason the kernel was refused; `step_window` (a window of query
    # rows a slot) runs the gather always; a LATENT ring's kernel, which
    # is selected at the ring's own geometry, under
    # "paged_attention_ring" ("...:masked_pages" where the ring is
    # longer than its window); with a lightning indexer, once a step has
    # been traced (the lanes are its arguments), "index_selection":
    # "pallas:select_rows" (`kernels/select_rows.py`) or
    # "passes:<reason>" where `lm_block.select_rows`' passes select
    kernels: Dict[str, str]
    # (pages a chunk at most, pages of its smallest row window) of the
    # kernel over a slot's table and over its ring: what it copies and
    # multiplies in (`kernels.paged_attention.chunk_cut`,
    # `rows_multiplied`); None
    # on the gather path, and for a ring where there is none
    attention_tiling: Optional[Tuple[Any, Any]]
    # tick_counts(cursors, slots, windowed=False, saved=None) -> dict:
    # what one dispatched step reads and does (the builder's
    # `tick_counts`); starts_saved(tables=None, rings=None) -> {name:
    # int [lanes, groups + 1]}: the DMA starts the kernels' issue loop
    # saves over those lanes' tables and rings, made once a table, of
    # which `tick_counts` takes the rows of the lanes `cursors` names
    # (`saved`) and looks a tick's count up ({} on the gather path)
    tick_counts: Callable
    starts_saved: Callable
    # {"draft_model": reason, "prefix_cache": reason}: a key for each
    # the server must refuse this block
    refuses: Dict[str, str]
    # what the expert layer of the step traced last runs: the Pallas
    # grouped matmul's name, or "xla:<reason>" where `ragged_dot` does;
    # None until a step is traced (the weights' dtype and the rows are
    # the step's arguments) and for a block without experts
    expert_kernel: Optional[str] = None
    # what the router of that layer makes its choice with: the Pallas
    # call's name (`kernels/router_choice.py`), or "passes:<reason>"
    # where `lm_block._largest`'s fusions do; never a sort
    router_choice: Optional[str] = None
    # the same of the delta-rule layers' recurrence: the Pallas kernel's
    # name (`kernels/delta_rule.py`), or "xla:<reason>" where
    # `lm_block.delta_rule`'s lines run; None until a step is traced
    # (the lanes are the step's arguments) and for a block without
    delta_kernel: Optional[str] = None
    # bytes an element of the weights of the step traced last (what
    # `tick_counts` turns its weights' elements into bytes with); None
    # until a step is traced
    weight_itemsize: Optional[int] = None


def build_lm_paged_decoder(vocab_size, block_size, max_blocks_per_seq,
                           d_model=256, n_heads=4, n_layers=2,
                           d_inner=None, kv_dtype=None, platform=None,
                           block=None):
    """Paged-attention decode step for the decoder-only LM.

    `block` says WHICH block the step computes, as a
    `lm_block.BlockSpec` (models/lm_block.py: norm kind, position kind,
    QK-norm, biases, FFN kind): None is `lm_block.OPT`, the block
    `transformer_lm` trains (LayerNorm, learned positions, biases,
    ReLU FFN), whose parameter names and shapes are read from the
    training Program; `lm_block.olmoe(...)` is RMSNorm + RoPE +
    QK-norm + a dropless top-k SwiGLU expert layer (`d_inner` is then
    ONE expert's width), whose `state_shapes` come from the
    description alone: it has no training Program and its
    `startup_program` is None.  Cache, gather, attention, write,
    sampling, `step`, `step_logits` and `step_window` are one code
    path for every block.  A block with experts returns a FOURTH
    value from `step` and `step_window`: int32 [layers with experts:
    `decoder.moe_layers`], the distinct experts each routed to this
    call, and where it HOLDS a share of its experts a FIFTH, the
    assignments of live lanes that fell on held experts, a layer
    (`decoder.step_counters` names them; empty for a block without),
    and has `decoder.step_routing`: `step_logits` that also returns
    what every layer's router was given and what it chose, for a
    comparison that must not mistake a near-tie for a fault.

    `build_lm_kv_decoder` owns a dense per-sequence cache
    ([B, max_len, d]) whose lifetime is one generate() call — fine for
    a closed batch, wrong for serving: a batch slot holds max_len worth
    of HBM for its whole life and a new request cannot join a running
    loop.  This builder produces the vLLM-style alternative: K/V live
    in fixed-size BLOCKS inside one shared pool
    ([n_layers, num_blocks, block_size, d_model]) and each sequence
    owns an ordered block table mapping its logical positions onto pool
    blocks.  Attention gathers through the table, so the kernel sees
    exactly the values a dense cache would hold — per-slot math is
    independent of which physical blocks a sequence happens to own and
    of what other slots compute, which is what makes continuously-
    batched decode bit-identical to running the same prompt solo
    (tests/test_generation_serving.py pins this).

    Unlike the closed-batch builders this returns a SINGLE decode step
    (one token per active slot per call), because the serving scheduler
    (serving/generation.py GenerationServer) must get control back
    between steps to admit/evict sequences; the whole step is one jit
    with the pool buffers donated, so a tick is one dispatch and the
    pool updates in place on device.

    Returns (startup_program, decoder), the decoder a `PagedDecoder`:
      decoder.step(states, pool_k, pool_v, tables, positions, tokens,
                   seeds, temps, active)
          -> (next_tokens [S] int32, pool_k, pool_v)
        tables    [S, max_blocks_per_seq] int32 pool-block ids (unused
                  tail entries must point at a valid block, e.g. the
                  pool's reserved null block — they are masked out)
        positions [S] int32 logical cursor: `tokens[s]` is the token AT
                  this position; the step writes its K/V there and
                  returns the model's prediction for position+1
        seeds     [S] uint32 per-sequence sampling seed (the PRNG is
                  fold_in(key(seed), position): stateless, so a retried
                  / re-scheduled sequence resamples identically)
        temps     [S] float32, 0 = greedy argmax
        active    [S] bool; inactive slots write into the null block
                  and their outputs are meaningless
      decoder.init_pool(num_blocks) -> (pool_k, pool_v) zero blocks
      decoder.state_names — parameter names, same trained values as the
      Program path (shared structural extraction with the dense
      decoder).

    `kv_dtype` selects the POOL's storage precision (docs/serving.md
    "KV quantization": writes quantize, attention reads the gathered
    blocks as stored; scores, softmax and accumulation stay float32):
      * "fp32" (default): plain float32 blocks;
      * "bf16": blocks stored bfloat16 (half the resident bytes,
        ~mantissa-rounding error on attention values);
      * "int8": blocks stored int8 with ONE float32 scale per
        (layer, block).  A write re-quantizes the whole target block
        under the new running max (blocks fill strictly in position
        order, so the valid region is exactly the offsets below the
        cursor) — a quarter of the resident bytes.
    None is "fp32".  Pools for bf16/int8 are pytrees the caller
    treats opaquely; `decoder.bytes_per_block` reports the resident
    K+V bytes per block for sizing/telemetry.

    `decoder.step_window(states, pool_k, pool_v, tables, positions,
    tokens [S, W], seeds, temps, n_valid [S]) -> (preds [S, W], pools)`
    is the teacher-forced MULTI-position step: slot s processes
    positions `positions[s] .. positions[s]+n_valid[s]-1` with the
    given tokens in ONE dispatch (causal within the window), writing
    each position's K/V and returning each position's next-token
    prediction.  It is what chunked prefill and speculative-decoding
    verification (serving/generation.py) run; window rows past
    n_valid write into the null block and return garbage.

    A block with SLIDING-WINDOW layers (`BlockSpec.layer_types`) keeps
    two kinds of state: `pool_k` and `pool_v` are then each the pair
    (full layers' pool, sliding layers' pool), `tables` the pair
    (tables [S, max_blocks_per_seq], rings [S,
    decoder.window_blocks_per_seq]), and `init_pool` takes the ring
    pool's size as `window_blocks`.  The table's blocks come from
    serving/kv_cache.py as ever; a ring belongs to a LANE, not to a
    sequence (`decoder.slot_rings(S)`: lane s holds ring-pool blocks
    1 + s * window_blocks_per_seq onward, block 0 being that pool's
    null block).  A sliding layer writes position p into ring
    entry (p // block_size) % window_blocks_per_seq and attends over
    its ring under a mask made from the cursor alone (K is rotated
    before it is written, so order in the ring does not matter).
    Such a block is refused an int8 pool and `step_window`, by name.
    Grouped-query heads and a head size of its own (`n_kv_heads`,
    `d_head`) make a pool row n_kv_heads * d_head wide.

    A block with MAMBA layers (`layer_types` "mamba": a Mamba-2 mixer
    and no attention, `lm_block.mamba2_step`) keeps a THIRD kind of
    state, of fixed size and belonging to a LANE: per Mamba layer a
    float32 SSM state [S, H, P, N] and a float32 convolution tail
    [S, width - 1, H*P + 2N].  `pool_k` is then the pair (the
    attention layers' K pool, a tuple of the layers' states) and
    `pool_v` the pair (their V pool, a tuple of the tails): donated
    and updated in place with the pools, one buffer a layer.
    `init_pool` takes the lane count as `lanes` (the step's S).  A
    lane whose cursor is 0 starts from a zero state and tail whatever
    it holds (the reset is made from the cursor alone, like the ring's
    mask); an inactive lane's state does not move.  The table serves
    the attention layers alone.  `step_window` is refused by name;
    `decoder.state_layers` and `decoder.state_bytes_per_lane` say what
    a lane holds (0 for a block without such layers).

    A block with CONV layers (`layer_types` "conv": a gated short
    convolution and no attention, `lm_block.short_conv_step`; lm_block's
    tenth description, docs/serving.md "A convolution tail a lane")
    keeps the same kind of state at its smallest: a TAIL ONLY, per conv
    layer the float32 last `conv_width - 1` rows of the gated product
    [S, conv_width - 1, d_model].  The pair's layout is the Mamba
    block's with nothing where the states were: `pool_k` is (the
    attention layers' K pool, ()) and `pool_v` (their V pool, a tuple of
    the tails), donated and updated in place with the pools; `init_pool`
    takes `lanes`; the reset from the cursor, the hold of an idle lane
    and what is refused (a prefix cache, a draft model, `step_window`)
    are the Mamba block's, through the same code.  The table serves the
    attention layers alone (`table_layers`, `bytes_per_block` and the
    kernel's plane index count them), RoPE turns them alone, and an int8
    pool is refused by name.

    A block with DELTA layers (`layer_types` "delta_rule": a gated delta
    rule and no attention, `lm_block.delta_rule_step`; lm_block's
    eleventh description) keeps the Mamba block's kind of state at other
    shapes: per delta layer a float32 MATRIX state [S, H, K, K] (keys by
    values a head) beside K and a float32 tail [S, delta_conv - 1,
    3*H*K] (the rows of q | k | v before their convolution) beside V,
    donated and updated in place with the pools; the reset from the
    cursor and the hold of an idle lane are the Mamba block's.  Its
    attention layers carry no position signal and, under
    `BlockSpec.attention_gate`, multiply their context by a sigmoid of
    the layer's normed input before `o` (scope `attention_gate`).  A
    draft model, `step_window` and an int8 pool are refused by name.
    The recurrence itself (scope `delta_rule`) is the Pallas kernel of
    `kernels/delta_rule.py` where `select_delta_rule` returns it (a
    TPU, heads of a multiple of 128 columns: a head's matrix crosses HBM
    once in and once out, in place on the donated state) and
    `lm_block.delta_rule`'s `jax.numpy` lines elsewhere; chosen when a
    step is traced and reported as `decoder.delta_kernel`.

    DELTA layers BESIDE A LATENT TABLE (lm_block's twelfth description,
    docs/serving.md "A lane's state beside a latent table"): the
    attention layers of such a block are latent (`kv_lora_rank` > 0), so
    `pool_k` is (the latent pool [attention layers, blocks, block_size,
    row], the delta layers' states) and `pool_v` ((), their tails): the
    latent block's empty tuple rides where the V pool did.  RoPE turns
    the latent layers' rotated columns alone; a latent layer's heads
    each take a sigmoid scalar of the layer's input before `o`
    (`BlockSpec.attention_gate_per_head`, scope `attention_head_gate`);
    `bytes_per_block` counts the one plane an attention layer,
    `tick_counts` gives `latent_rows` AND `delta_layers` / `state_bytes`
    on one tick, a snapshot knows nothing of the table, and
    `decoder.refuses["draft_model"]` holds the lane's reason and the
    latent table's.

    Every block whose lanes keep something (Mamba, conv or delta layers)
    is served under a PREFIX CACHE through SNAPSHOTS: the decoder owns
    `init_snapshots`, `snapshot_save` and `snapshot_restore` (see
    `PagedDecoder`), two small jitted programs under the scopes
    `state_snapshot_save` and `state_snapshot_restore` that copy one
    lane's state and tails of every such layer into a row of a snapshot
    pool and back, so that serving/ moves a snapshot by its row and
    never learns what a state is (docs/serving.md "A snapshot of a
    lane's state").

    A LOOPED stack (`BlockSpec.passes` > 1: the layers run `passes`
    times a token over the same weights, the final norm after every
    pass, docs/serving.md "A looped stack") keeps the ONE table pool
    with `passes * n_layers` planes: pass t of layer l writes and reads
    plane t * n_layers + l (`decoder.kv_planes`, `decoder.passes`), and
    `bytes_per_block` counts them all; a block id is common to all
    planes, so tables, the cache manager and the kernel are untouched.
    The step holds ONE stack's body under a `lax.scan` over the pass
    (the plane index is traced into the write and the kernel's `layer`
    operand): program size and compile time do not grow with `passes`.
    `step` returns a fourth value, `exit_gate_open` (`step_counters`):
    (live lane, pass) pairs whose exit gate is over a half; the gate
    decides nothing.  `step_routing` returns every pass's x_t and
    gate.  `step_window` and an int8 pool are refused by name.

    A LATENT block (`BlockSpec.kv_lora_rank` > 0, lm_block's seventh
    description) keeps ONE pool: `pool_k` [layers, blocks, block_size,
    row] holds a position's compressed latent and its one rotated key
    part side by side (the row padded with exact zeros to the 128-lane
    grid), `pool_v` is the empty tuple, and `bytes_per_block` counts
    the one array.  The step is the ABSORBED form: the query's
    unrotated part times the key half of `kv_b` a head, attention over
    the latent rows themselves (scores over the whole row, the context
    over its latent columns), the context times the value half, `o`.
    `step_window`, a draft model and an int8 pool are refused by name;
    the prefix cache works (a block holds every layer's rows).

    A DOUBLE layer (`BlockSpec.sub_blocks` 2, lm_block's eighth
    description) is TWO latent sub-blocks, so the latent pool has
    `2 * n_layers` planes (sub-block i of layer l writes and reads plane
    2 * l + i: `decoder.kv_planes`, `table_layers`, `bytes_per_block`
    and `tick_counts`' `latent_rows` count them all), and ONE expert
    layer (`decoder.moe_layers` counts one a double layer): computed in
    the first sub-block on the state its dense FFN reads, held across
    the second, joined after the second dense FFN.  With IDENTITY
    experts (`BlockSpec.zero_experts`) `step` counts two more values
    (`step_counters`): `moe_zero_assignments`, the live lanes'
    assignments to identity experts a layer, and `moe_assignments`,
    all of their assignments.

    A SELECTED latent (`BlockSpec.index_topk` > 0, lm_block's ninth
    description; docs/serving.md "A selected latent") keeps a SECOND
    kind of cache row on the latent rows' own table: `pool_v` is then
    the INDEX-KEY pool [layers that compute a selection, blocks,
    block_size, index_head_dim] (`decoder.index_planes`; a block id
    names a block of both pools, so a prefix-cache block holds a
    position's latent rows and its index keys, and `bytes_per_block`
    counts both).  A layer whose `indexer_types` entry is "full" writes
    this position's index key, scores every row under the cursor
    against the position's index queries and selects `index_topk` of
    them (`lm_block.select_rows`); the selection, a row mask a lane
    [S, rows of the table], is carried inside the step to the "shared"
    layers after it; every layer attends over the selected rows alone.
    `step_routing` also returns what each selecting layer was given
    and chose: "index_inputs" [F, S, D] (the block's normed input),
    "index_latents" [F, S, q_lora_rank] (the normed query latent),
    "index_scores" [F, S, rows] float32 and "selected" [F, S, rows]
    bool.  `step_window`, a draft model and an int8 pool are refused
    by name.

    A LATENT RING beside a selected latent table (lm_block's thirteenth
    description; docs/serving.md "A latent ring beside a selected
    table"): the SLIDING layers of a latent block are latent attention
    at sizes of their own (`BlockSpec.latent_of`), so `pool_k` is (the
    latent table [full layers, blocks, block_size, table row], the
    latent RING pool [sliding layers, ring blocks, block_size, ring
    row]: ONE array a kind, each row padded to the 128-lane grid at its
    own width) and `pool_v` (the index-key pool of the full layers, or
    () without an indexer; ()), `tables` the pair (tables, rings).  The
    ring is `ceil(window / block_size)` blocks a lane; where that is
    longer than the window the sliding layers attend under a mask of the
    last `window` rows made from the cursor (the kernel's `select`; a
    ring exactly one window long lowers as it always did).  The query
    projections, the absorbed products, the head gate and the kernel are
    selected at the layer's KIND's sizes, a full layer alone reaches the
    indexer, and `tick_counts` gives `latent_rows`, `kv_rows_win`,
    `past_window`, `kv_rows_indexed`, `kv_rows_selected` and
    `ring_bytes` on one tick.  EVERY block with a ring (of K and V heads
    too) is served under a PREFIX CACHE through snapshots of a lane's
    ring blocks: `init_snapshots`, `snapshot_save` and
    `snapshot_restore` copy the `window_blocks_per_seq` blocks of every
    sliding layer (a ring holds position p at block (p // block_size) %
    window_blocks_per_seq whatever the lane, so a snapshot taken in one
    lane is right in another).

    A PARALLEL layer (`BlockSpec.parallel_attention`, lm_block's
    fourteenth description; docs/serving.md "A layer with two caches"):
    every layer is a Mamba-2 mixer AND grouped-query attention under
    RoPE on ONE normed input, so layer l owns plane l of the lanes'
    states, plane l of their tails AND plane l of the K and V table.
    The pools are the Mamba block's pair with a table of `n_layers`
    planes: `pool_k` (the K pool [layers, blocks, block_size, Hkv * dh],
    a float32 state [S, H, P, N] a layer), `pool_v` (the V pool, a
    float32 tail [S, width - 1, H*P + 2GN] a layer); `bytes_per_block`
    and `state_bytes_per_lane` both count EVERY layer, a snapshot holds
    every layer's state and tail while a prefix-cache block holds every
    layer's K and V rows, and `tick_counts` gives `state_lanes`,
    `kv_rows_full` (the table's rows under the cursors) and the table's
    pages on one tick, `step_bytes_cache` counting both caches.  The
    layer's scopes are the Mamba mixer's five (`ssm_*`) and the
    attention's (`qkv`, `rope`, `kv_write`, `attention`, `attn_out`)
    under one layer; its dense SwiGLU lies under `dense_ffn`.  A draft
    model, `step_window` and an int8 pool are refused by name.
    `step_routing` returns the logits, "ssm_inputs" and "ssm_states"
    (nothing is routed; what each layer's recurrence was given and what
    it left, from one program).

    `decoder.step_logits(...)` takes `step`'s arguments and returns the
    [S, vocab] float32 logits `step` samples from, without donating or
    updating the pools — the numerics gate between the Pallas and XLA
    attention paths (chip_smoke.py).

    `platform` names the backend the decoder will RUN on (None = the
    process default): kernel selection and pool donation follow it, and
    GenerationServer refuses a decoder built for another platform than
    its place's device (`decoder.platform`).
    """
    import contextlib
    import functools
    import math
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import lm_block

    d_inner = d_inner or 4 * d_model
    nb, bs = int(max_blocks_per_seq), int(block_size)
    max_len = nb * bs
    kv_dtype = str(kv_dtype or "fp32").lower()
    kv_dtype = {"float32": "fp32", "bfloat16": "bf16"}.get(
        kv_dtype, kv_dtype)
    if kv_dtype not in ("fp32", "bf16", "int8"):
        raise ValueError(
            f"kv_dtype {kv_dtype!r} not in ('fp32', 'bf16', 'int8')")

    # the kernel's own module decides, from the pool's geometry, its
    # dtype and the platform, whether the resident step's attention
    # reads the pages a slot's cursor has reached straight from the
    # pool inside the Pallas kernel (no logical-order gather copy) or
    # the XLA gather below runs (docs/performance.md "Kernel
    # selection")
    from ..kernels import delta_rule as _delta_rule
    from ..kernels import grouped_matmul as _grouped_matmul
    from ..kernels import router_choice as _router_choice
    from ..kernels import select_rows as _select_rows
    from ..kernels import paged_attention as _paged_attention
    from ..kernels import paged_index_scores as _paged_index_scores

    platform = platform or jax.default_backend()
    spec = lm_block.OPT if block is None else block
    if not isinstance(spec, lm_block.BlockSpec):
        raise TypeError(f"block={block!r}: a lm_block.BlockSpec "
                        "(lm_block.OPT, lm_block.olmoe(...)) or None")

    # -- attention geometry, from the description -------------------------
    # `group` query heads share one K/V head of `d_head` columns; a pool
    # row is the K/V heads side by side (`d_kv` wide: d_model for plain
    # multi-head attention).  SLIDING layers keep their K/V in a RING of
    # `nw` blocks a sequence (the window, in blocks) in a pool of their
    # own, FULL layers in the table of `nb` blocks.
    n_kv, d_head = spec.heads(d_model, n_heads)
    group, d_kv = n_heads // n_kv, n_kv * d_head
    # A LATENT block's pool row is the latent and the one rotated key
    # part, which every head reads whole ("one K/V head" of that
    # width), stored on the 128-lane grid: the pad columns are exact
    # zeros in the row and in the query, so they add nothing.
    latent = spec.latent
    if latent:
        d_lat, d_pe = spec.kv_lora_rank, spec.qk_rope_head_dim
        d_nope, d_v = spec.qk_nope_head_dim, spec.v_head_dim
        n_kv, group, d_head = 1, n_heads, d_nope + d_pe
        d_kv = -(-(d_lat + d_pe) // 128) * 128
        if kv_dtype == "int8":
            raise NotImplementedError(
                f"block {spec.name!r}: an int8 pool's scale a (layer, "
                "block) is not built for a latent row (one scale over a "
                "normed latent and a rotated key part of other ranges); "
                "kv_dtype fp32 or bf16")
    # the latent sizes by layer KIND (a sliding layer's may be its own:
    # `BlockSpec.latent_of`), each with its padded row and its scale
    def _geometry(kind):
        k = spec.latent_of(kind, n_heads)
        dh = k.qk_nope_head_dim + k.qk_rope_head_dim
        return types.SimpleNamespace(
            h=k.n_heads, r_q=k.q_lora_rank, d_lat=k.kv_lora_rank,
            d_nope=k.qk_nope_head_dim, d_pe=k.qk_rope_head_dim,
            d_v=k.v_head_dim, d_head=dh,
            d_kv=-(-(k.kv_lora_rank + k.qk_rope_head_dim) // 128) * 128,
            scale=spec.attention_multiplier or 1.0 / math.sqrt(dh))

    geo = ({kind: _geometry(kind)
            for kind in (lm_block.FULL, lm_block.SLIDING)}
           if latent else None)
    # A lightning indexer: the layers that compute a selection keep an
    # index key a position in a pool of their own, a plane each
    sparse = spec.sparse
    index_of = ([spec.indexer_of(l) for l in range(n_layers)]
                if sparse else [])
    index_plane = [index_of[:l].count(lm_block.INDEX_FULL)
                   for l in range(len(index_of))]
    n_index = index_of.count(lm_block.INDEX_FULL)
    d_idx, h_idx = spec.index_head_dim, spec.index_n_heads
    # a kind for every entry of `layout.layers`: a layer, or each of a
    # double layer's two sub-blocks (a cache plane each)
    subs = spec.sub_blocks
    kinds = [spec.kind_of(l) for l in range(n_layers) for _ in range(subs)]
    ringed = lm_block.SLIDING in kinds
    # the kind of layer that keeps something a LANE (`param_layout`
    # builds one such kind a block): Mamba layers a recurrent state and
    # a convolution tail, gated short convolutions the tail alone
    lane_kind = next((k for k in (lm_block.MAMBA, lm_block.CONV,
                                  lm_block.DELTA) if k in kinds), None)
    stateful = lane_kind is not None
    if lane_kind in (lm_block.CONV, lm_block.DELTA) and kv_dtype == "int8":
        raise NotImplementedError(
            f"block {spec.name!r}: an int8 pool beside convolution tails "
            "or delta-rule states is not built (its per-(layer, block) "
            "scales are untested on a table that a minority of the "
            "layers write); kv_dtype fp32 or bf16")
    # a PARALLEL layer: every Mamba layer also attends, so the table has
    # a plane for each of them (a layer's index among the lanes' states
    # is its plane of the table: every layer is of the one kind)
    parallel = spec.parallel_attention
    if parallel and kv_dtype == "int8":
        raise NotImplementedError(
            f"block {spec.name!r}: an int8 pool beside a lane's Mamba "
            "state in a parallel layer is not built (its per-(layer, "
            "block) scales are untested beside a float32 state that a "
            "snapshot restores); kv_dtype fp32 or bf16")
    n_full = kinds.count(lm_block.FULL) + (n_layers if parallel else 0)
    nw = 0
    if ringed:
        if spec.window % bs and not latent:
            raise ValueError(
                f"block {spec.name!r}: window {spec.window} is not a "
                f"whole number of {bs}-position blocks (a ring longer "
                "than its window, under a mask of the last `window` "
                "rows, is built for a ring of LATENT rows; a ring of K "
                "and V heads is still exactly one window long)")
        if kv_dtype == "int8":
            raise NotImplementedError(
                f"block {spec.name!r}: an int8 pool re-quantizes a "
                "block under the offsets written so far, which a ring "
                "that overwrites its oldest block in place breaks; "
                "sliding layers take kv_dtype fp32 or bf16")
        nw = min(-(-spec.window // bs), nb)
    # a ring LONGER than its window (the window is no whole number of
    # blocks): its oldest rows are past the window, and the sliding
    # layers attend under a mask of the last `window` rows
    ring_masked = ringed and nw * bs > spec.window
    # a layer's index inside the pool of its kind
    pool_index = [kinds[:l].count(k) for l, k in enumerate(kinds)]
    # A LOOPED stack (`BlockSpec.passes`, or its exit gate): the layers
    # run `passes` times a token over the same weights, and the pool
    # has a plane for every (pass, layer) pair: pass t of layer l
    # writes and reads plane t * layers + l and no other.
    passes = int(spec.passes)
    looped = passes > 1 or spec.exit_gate
    if looped and kv_dtype == "int8":
        raise NotImplementedError(
            f"block {spec.name!r}: an int8 pool under a looped stack is "
            "not built (its per-(plane, block) scales are untested "
            "there); kv_dtype fp32 or bf16")

    _attend, _refused = _paged_attention.select_paged_attention(
        d_model=d_model, n_heads=n_heads, d_head=d_head, kv_width=d_kv,
        block_size=bs, kv_dtype=kv_dtype, platform=platform,
        value_width=d_lat if latent else None)
    # a latent RING is read at the sliding layers' own geometry: a
    # selection of its own (a ring of K and V heads is read by the
    # table's kernel: their rows are one width).  One refused, both are:
    # the step then gathers on every layer
    _attend_ring, _ring_refused = _attend, _refused
    if ringed and latent:
        _ring = geo[lm_block.SLIDING]
        _attend_ring, _ring_refused = (
            _paged_attention.select_paged_attention(
                d_model=d_model, n_heads=_ring.h, d_head=_ring.d_head,
                kv_width=_ring.d_kv, block_size=bs, kv_dtype=kv_dtype,
                platform=platform, value_width=_ring.d_lat))
        if _attend is None or _attend_ring is None:
            _refused = _ring_refused = _refused or _ring_refused
            _attend = _attend_ring = None
    attend_of = {lm_block.FULL: _attend, lm_block.SLIDING: _attend_ring}
    # and the same of a lightning indexer's scores over its index-key
    # plane, from the plane's geometry
    _index_scores, _index_refused = (
        _paged_index_scores.select_index_scores(
            index_head_dim=d_idx, block_size=bs, kv_dtype=kv_dtype,
            platform=platform) if sparse else (None, None))
    if spec is lm_block.OPT:
        startup, shapes, tok_emb, pos_tab, lns, weights, biases = (
            _lm_param_structure(vocab_size, max_len, d_model, n_heads,
                                n_layers, d_inner))

        def fc(i):
            return weights[i], biases[i]

        layout = types.SimpleNamespace(
            tok=tok_emb, pos=pos_tab, final=lns[2 * n_layers],
            head=fc(6 * n_layers), exit=None,
            layers=[{"norm1": lns[2 * l], "q": fc(6 * l),
                     "k": fc(6 * l + 1), "v": fc(6 * l + 2),
                     "o": fc(6 * l + 3), "norm2": lns[2 * l + 1],
                     "w1": fc(6 * l + 4), "w2": fc(6 * l + 5)}
                    for l in range(n_layers)])
    else:
        startup = None
        layout, shapes = lm_block.param_layout(
            spec, vocab_size, d_model, n_heads, n_layers, d_inner)

    # layers with experts (a DENSE layer of `mlp_layer_types` has
    # none), and whether the experts here are a share of those routed
    moe_layers = (sum(spec.ffn_of(l) == lm_block.SPARSE
                      for l in range(n_layers))
                  if spec.ffn == "moe_swiglu" else 0)
    shares = spec.ffn == "moe_swiglu" and spec.has_unheld

    scale = spec.attention_multiplier or 1.0 / math.sqrt(d_head)
    # the residual stream takes each sub-block's output times this
    # (Python's 1.0: no operation, the other blocks' steps as they were)
    res_mult = spec.residual_multiplier

    def _residual(x, y):
        return x + (y if res_mult == 1.0 else res_mult * y)

    # buffer donation makes the pool update in place (no copy of the
    # whole cache per token); CPU has no donation support and would
    # warn once per compile, so only donate where it lands
    donate = (1, 2) if platform != "cpu" else ()

    # -- pool storage: quantize-on-write ---------------------------------
    def _write(pool, l, wb, wi, row):
        """Write `row` [S, D] at (layer l, block wb[s], offset wi[s])."""
        if kv_dtype == "fp32":
            return pool.at[l, wb, wi].set(row)
        if kv_dtype == "bf16":
            return pool.at[l, wb, wi].set(row.astype(jnp.bfloat16))
        q, sc_ = pool
        # int8, one scale per (layer, block): re-quantize the whole
        # block under the running max.  Blocks fill strictly in
        # position order, so offsets < wi are the valid entries and
        # everything above is stale garbage that must NOT widen the
        # scale (a freshly-reused block holds a dead sequence's data).
        blk = q[l, wb].astype(jnp.float32)                  # [S, BS, D]
        s_old = sc_[l, wb]                                  # [S]
        deq = blk * s_old[:, None, None]
        offs = jnp.arange(bs)
        deq = jnp.where((offs[None, :] < wi[:, None])[..., None],
                        deq, 0.0)
        deq = jnp.where((offs[None, :] == wi[:, None])[..., None],
                        row[:, None, :], deq)
        m = jnp.max(jnp.abs(deq), axis=(1, 2))
        new_scale = jnp.maximum(m, 1e-8) / 127.0
        qn = jnp.clip(jnp.round(deq / new_scale[:, None, None]),
                      -127, 127).astype(jnp.int8)
        return (q.at[l, wb].set(qn), sc_.at[l, wb].set(new_scale))

    # Names on the device's time (metadata only, the math is untouched):
    # everything a step traces lies under `paged_decoder/<part>`, so a
    # device trace joined through profiler.hlo_scopes() says what share
    # of a tick is the table gather, the attention over it, the weight
    # matmuls...  `kv_gather` is the gather through the block table
    # (values and int8 scales); `attention` is the two contractions,
    # the mask and the softmax (where selection takes the Pallas
    # `_attend`, its call, which reads the pool itself: no gather).
    # Inside a sub-block of a DOUBLE layer the parts every sub-block
    # has carry its index under their own name
    # (`paged_decoder/dense_ffn/sub1`): the two read apart, and still
    # sum under the part.
    sub_parts = ("latent_q", "latent_kv", "latent_absorb", "attention",
                 "attn_out", "dense_ffn")
    sub_traced = None                   # the sub-block being traced
    # Beside a latent RING the parts of a latent layer carry the layer's
    # kind the same way (`paged_decoder/latent_q/sliding`): the two
    # geometries read apart, and still sum under the part (`attention`
    # and `kv_gather` carry it through `_kind_scope`, as on every ring).
    kind_parts = ("latent_q", "latent_kv", "latent_absorb", "attn_out",
                  "attention_head_gate", "indexer_q", "indexer_k",
                  "indexer_scores", "indexer_topk")
    kind_traced = None                  # the kind of the layer being traced

    def scope(name):
        below = (f"sub{sub_traced}" if sub_traced is not None
                 and name in sub_parts else
                 kind_traced.split("_")[0] if kind_traced is not None
                 and name in kind_parts else None)
        if below is None:
            return jax.named_scope(name)
        both = contextlib.ExitStack()
        both.enter_context(jax.named_scope(name))
        both.enter_context(jax.named_scope(below))
        return both

    def _sample(logits, seeds, positions, temps):
        """Greedy/sampled next token per row; stateless per-sequence
        sampling: the key depends only on (seed, position), never on
        the slot or tick number."""
        with scope("sample"):
            greedy = jnp.argmax(logits, axis=-1)
            subs = jax.vmap(
                lambda sd, p: jax.random.fold_in(jax.random.key(sd), p))(
                    seeds, positions)
            safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
            sampled = jax.vmap(jax.random.categorical)(subs,
                                                       logits / safe_t)
            return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    # -- the block, from its description ---------------------------------
    # `layout` names every parameter as a (weight-or-scale,
    # bias-or-shift-or-None) pair; what a block lacks is None and adds
    # no operation, so OPT's step is the operations it always was.
    def _norm(g, x, pair):
        return lm_block.norm(spec, x, g[pair[0]],
                             None if pair[1] is None else g[pair[1]])

    def _fc(g, x, pair):
        y = x @ g[pair[0]]
        return y if pair[1] is None else y + g[pair[1]]

    def _embed(g, tokens, pos):
        """Token rows, plus the position table's where the block has
        one.  Under RoPE nothing is added and the residual stream is
        float32 from the start (with a table, OPT's stays in the
        weights' dtype until the first attention output widens it)."""
        # (every step embeds: the weights' dtype is the step's argument)
        decoder.weight_itemsize = g[layout.tok].dtype.itemsize
        with scope("embed"):
            if layout.pos is not None:
                return g[layout.tok][tokens] + g[layout.pos][pos]
            x = g[layout.tok][tokens].astype(jnp.float32)
            if spec.embedding_multiplier != 1.0:
                x = x * spec.embedding_multiplier
            return x

    def _rotation(pos):
        """{layer kind: cos and sin of each row's OWN position} (RoPE
        is per slot, and per window row, and a kind of layer may scale
        it its own way); None a kind for a block without."""
        if spec.positions != "rope":
            return dict.fromkeys(kinds)
        with scope("rope"):
            # a kind RoPE does not turn (`BlockSpec.rope_layers`)
            # carries no position signal at all
            return {kind: (lm_block.rope_tables(
                spec, pos, geo[kind].d_pe if latent else d_head, kind)
                           if spec.rotated(kind) else None)
                    for kind in dict.fromkeys(kinds)}

    def _qkv(g, lay, x, rot, normed=None):
        """`normed`: the layer's normed input where its caller has it (a
        parallel layer's, which the Mamba mixer reads too)."""
        with scope("qkv"):
            h = _norm(g, x, lay["norm1"]) if normed is None else normed
            q, kk, vv = (_fc(g, h, lay[n]) for n in ("q", "k", "v"))
            if spec.key_multiplier != 1.0:
                kk = kk * spec.key_multiplier
        if spec.qk_norm:
            with scope("qk_norm"):
                if spec.qk_norm_per_head:
                    # over each head's own columns, one scale of
                    # d_head for all heads of Q and one for K
                    q, kk = (_norm(g, t.reshape(t.shape[:-1]
                                                + (-1, d_head)),
                                   lay[n]).reshape(t.shape)
                             for t, n in ((q, "q_norm"), (kk, "k_norm")))
                else:
                    q = _norm(g, q, lay["q_norm"])
                    kk = _norm(g, kk, lay["k_norm"])
        if rot is not None:
            # K is turned BEFORE it is written: the pool holds rotated
            # keys, so a cached position is never turned again
            with scope("rope"):
                q = lm_block.rope(q, *rot, n_heads)
                kk = lm_block.rope(kk, *rot, n_kv)
        return q, kk, vv

    def _kv_b(g, lay, k):
        """`kv_b` by head: [latent, H, key columns | value columns], at
        the sizes `k` of the layer's kind (`geo`)."""
        return g[lay["kv_b"][0]].reshape(k.d_lat, k.h, k.d_nope + k.d_v)

    def _latent_down(g, lay, x, k):
        """The block's normed input and the normed query latent (times
        its constant where the description has one, `scale_q_lora`):
        what `_latent_qkv` and a lightning indexer both read (the input
        twice where the query has no low-rank step)."""
        with scope("latent_q"):
            h = _norm(g, x, lay["norm1"])
            if "q_a" not in lay:
                # a query of ONE matrix (`q_lora_rank` 0) reads h itself
                return h, h
            c_q = _norm(g, _fc(g, h, lay["q_a"]), lay["q_a_norm"])
            if spec.scale_q_lora:
                c_q = c_q * math.sqrt(d_model / k.r_q)
            return h, c_q

    def _latent_qkv(g, lay, h, c_q, rot, k):
        """The latent block's `_qkv`, from `_latent_down`'s two: -> (the
        ABSORBED query [S, W, H * row]: a head's unrotated part times
        the key half of `kv_b`, so that it meets the latent itself, then
        its rotated part, then the row's zero pad; this position's row
        [S, W, row]: the normed latent (times its constant,
        `scale_kv_lora`), the one rotated key part, the pad; None: there
        is no V).  The norms are float32; the rotation is of
        `qk_rope_head_dim` columns, a head's of the query and the one of
        the key.  `k`: the sizes of the layer's kind (`geo`)."""
        lead = h.shape[:-1]
        n_heads, d_lat, d_nope, d_pe = k.h, k.d_lat, k.d_nope, k.d_pe
        with scope("latent_q"):
            q = _fc(g, c_q, lay["q_b"]).reshape(lead + (n_heads, k.d_head))
            q_nope, q_pe = q[..., :d_nope], q[..., d_nope:]
            q_pe = lm_block.rope(q_pe.reshape(lead + (-1,)), *rot,
                                 n_heads).reshape(q_pe.shape)
        with scope("latent_kv"):
            ckv = _fc(g, h, lay["kv_a"])
            pad = jnp.zeros(lead + (k.d_kv - d_lat - d_pe,), ckv.dtype)
            c_kv = _norm(g, ckv[..., :d_lat], lay["kv_a_norm"])
            if spec.scale_kv_lora:
                # on the row as stored: `kv_b` meets it either side
                c_kv = c_kv * math.sqrt(d_model / d_lat)
            row = jnp.concatenate(
                [c_kv, lm_block.rope(ckv[..., d_lat:], *rot, 1), pad],
                axis=-1)
        with scope("latent_absorb"):
            q_lat = jnp.einsum("...hn,chn->...hc", q_nope,
                               _kv_b(g, lay, k)[..., :d_nope])
            q_abs = jnp.concatenate(
                [q_lat, q_pe, jnp.broadcast_to(
                    pad[..., None, :], lead + (n_heads, pad.shape[-1]))],
                axis=-1)
        return q_abs.reshape(lead + (n_heads * k.d_kv,)), row, None

    def _latent_values(g, lay, ctx, k):
        """The attention's context over the LATENT, [.., H * latent],
        times the value half of `kv_b` a head -> [.., H * v_head_dim]:
        what the expanded form's `p . V` gives."""
        with scope("latent_absorb"):
            out = jnp.einsum(
                "...hc,chv->...hv",
                ctx.reshape(ctx.shape[:-1] + (k.h, k.d_lat)),
                _kv_b(g, lay, k)[..., k.d_nope:])
            return out.reshape(ctx.shape[:-1] + (k.h * k.d_v,))

    def _indexer(g, lay, h, c_q, rot, pool_i, plane, tables, wb, wi,
                 positions, active):
        """The lightning indexer of a selecting layer, one query
        position a lane: from the block's normed input h [S, D] and the
        normed query latent c_q [S, q_lora_rank] -> (the selection
        [S, rows] bool over the rows of the lane's table, the index
        scores [S, rows] float32, the index-key pool with this
        position's key written at (block wb, offset wi) of plane
        `plane`).  The key is ONE row for all index heads (a LayerNorm
        with a shift over it), RoPE turns the first `qk_rope_head_dim`
        columns of the key and of every index query, and the score of
        row r is sum_j w_j relu(q_j . k_r) with w = h W_w / sqrt(heads
        x head size): products of the pool's dtype accumulated in
        float32, the rest float32.  The rows are read by the streaming
        kernel where `select_index_scores` returned it (the pages of
        the plane a cursor has reached, straight from the pool), else
        through the table in logical order (the XLA gather of the whole
        table); those past the cursor score nothing."""
        s_n = h.shape[0]

        def turned(t, n):
            """RoPE on the first `d_pe` columns of each of t's n heads."""
            th = t.reshape(s_n, n, d_idx)
            first = lm_block.rope(th[..., :d_pe].reshape(s_n, n * d_pe),
                                  *rot, n).reshape(s_n, n, d_pe)
            return jnp.concatenate([first, th[..., d_pe:]], axis=-1)

        with scope("indexer_q"):
            q_i = turned(_fc(g, c_q, lay["idx_q"]), h_idx)  # [S, Hi, di]
            w_i = _fc(g, h, lay["idx_w"]).astype(jnp.float32) * (
                1.0 / math.sqrt(h_idx * d_idx))
        with scope("indexer_k"):
            k_i = _fc(g, h, lay["idx_k"]).astype(jnp.float32)
            mu = k_i.mean(-1, keepdims=True)
            var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
            scale_, shift_ = (g[n].astype(jnp.float32)
                              for n in lay["idx_k_norm"])
            k_i = (k_i - mu) / jnp.sqrt(var + 1e-6) * scale_ + shift_
            k_i = turned(k_i, 1)[:, 0]
            pool_i = _write(pool_i, plane, wb, wi, k_i)
        with scope("indexer_scores"):
            if _index_scores is not None:
                # rows past the cursor are not the kernel's to define:
                # the mask below makes them minus infinity
                scores = _index_scores(
                    q_i, w_i, pool_i, tables,
                    jnp.where(active, positions + 1, 1), plane)
            else:
                keys = pool_i[plane, tables].reshape(s_n, nb * bs, d_idx)
                dots = jax.lax.dot_general(
                    q_i.astype(keys.dtype), keys,
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)   # [S, Hi, rows]
                scores = (jax.nn.relu(dots) * w_i[:, :, None]).sum(axis=1)
        # the kernel's own module decides from the lanes of this trace,
        # the table's rows and the platform whether the selection is its
        # Pallas call (the keys in VMEM for all 32 counts, whatever the
        # compiler does with the rest of the step) or `select_rows`'
        # passes
        selection, refused = _select_rows.select_index_selection(
            rows=nb * bs, lanes=s_n, k=spec.index_topk, platform=platform)
        decoder.kernels["index_selection"] = (
            selection.name if selection is not None
            else f"passes:{refused}")
        with scope("indexer_topk"):
            # a lane with no sequence sees row 0 of the null block
            cur = jnp.where(active, positions, 0)
            valid = jnp.arange(nb * bs)[None, :] <= cur[:, None]
            masked = jnp.where(valid, scores, -jnp.inf)
            chosen = (selection.select(scores, cur)
                      if selection is not None
                      else lm_block.select_rows(masked, valid,
                                                spec.index_topk))
        return chosen, masked, pool_i

    def _post_join(g, x, y, pair):
        """x + norm(y): a sub-block's output joins the residual stream
        through the norm `post_norm` puts on it (`loop_norm`: with the
        final norm of each pass, the norms a looped block adds)."""
        with scope("loop_norm"):
            return _residual(x, _norm(g, y, pair))

    # a clamp a layer on the SwiGLU inputs of the routed experts and of
    # the shared expert, by the identity of the layer's entry of
    # `layout.layers` (a description without the lists: no entry, 0.0)
    swiglu_limits = {id(lay): spec.swiglu_limits_of(i // spec.sub_blocks)
                     for i, lay in enumerate(layout.layers)
                     if spec.expert_swiglu_limits
                     or spec.shared_swiglu_limits}

    # two names for a dense FFN, and neither can move: `mlp` is what
    # OPT's and the looped stack's breakdowns and readers know theirs
    # by; `dense_ffn`, a dense SwiGLU in a layer that has other parts a
    # tick's share goes to (experts in the other layers, or here two
    # mixers), is what `serve_dense_ffn_share` reads (PERF.md section 3)
    ffn_scope = "dense_ffn" if parallel else "mlp"
    mlp_multipliers = ({"multipliers": spec.mlp_multipliers}
                       if spec.mlp_multipliers else {})

    def _ffn(g, lay, x, hits):
        """x + FFN(norm(x)); a block with experts appends (its count
        of distinct experts hit, the router's input, the weights and
        the experts it chose) to `hits`; a DENSE layer of such a
        block (no "router" among its names) is one SwiGLU under a
        scope of its own and appends nothing."""
        if spec.ffn == "moe_swiglu" and "router" not in lay:
            with scope("dense_ffn"):
                h2 = _norm(g, x, lay["norm2"])
                y = lm_block.swiglu(h2.reshape(-1, d_model), *(
                    g[lay[n][0]] for n in ("gate", "up", "down")))
                return _residual(x, y.reshape(x.shape))
        with scope(ffn_scope):
            h2 = _norm(g, x, lay["norm2"])
            if spec.ffn == "relu":
                return x + _fc(g, jax.nn.relu(_fc(g, h2, lay["w1"])),
                               lay["w2"])
            if spec.ffn == "swiglu":
                y = lm_block.swiglu(h2.reshape(-1, d_model), *(
                    g[lay[n][0]] for n in ("gate", "up", "down")),
                    **mlp_multipliers)
                if not spec.post_norm:
                    return _residual(x, y.reshape(x.shape))
        if spec.ffn == "swiglu":
            return _post_join(g, x, y.reshape(x.shape), lay["post2"])
        y = _experts(g, lay, h2.reshape(-1, d_model), hits)
        with scope("moe_combine"):
            return _residual(x, y.reshape(x.shape))

    def _experts(g, lay, h2, hits):
        """The expert layer of rows h2 [T, D] (and the shared expert's
        part, where the block has one) -> [T, D]; appends (its count of
        distinct experts hit, h2, the weights and the experts the
        router chose) to `hits`."""
        w_gate = g[lay["gate"][0]]
        limit, shared_limit = swiglu_limits.get(id(lay), (0.0, 0.0))
        # the kernel's own module decides from the rows of this trace,
        # the widths, the weights' dtype and the platform whether the
        # grouped matmuls are its Pallas kernel or `ragged_dot`
        experts, refused = _grouped_matmul.select_grouped_matmul(
            rows=h2.shape[0] * spec.experts_per_token, d_model=d_model,
            d_ff=w_gate.shape[-1], n_experts=spec.held[1],
            dtype=w_gate.dtype, platform=platform)
        if limit:
            # the kernel's gated product is inside its call: a clamped
            # layer runs the `ragged_dot`s
            experts, refused = None, "swiglu_limit"
        decoder.expert_kernel = (experts.name if experts is not None
                                 else f"xla:{refused}")
        # and the router's choice, from the rows and the router's shape
        choice, refused = _router_choice.select_router_choice(
            rows=h2.shape[0], width=spec.n_experts + spec.zero_experts,
            k=spec.experts_per_token, n_group=spec.n_group,
            topk_group=spec.topk_group, group_score=spec.group_score,
            platform=platform)
        decoder.router_choice = (choice.name if choice is not None
                                 else f"passes:{refused}")
        y, hit, routed = lm_block.moe_ffn(
            spec, h2, g[lay["router"][0]], w_gate, g[lay["up"][0]],
            g[lay["down"][0]], scope=scope, experts=experts,
            b_router=(g[lay["router_bias"][0]] if "router_bias" in lay
                      else None), limit=limit, choice=choice)
        hits.append((hit, h2) + routed)
        if spec.shared_d_inner:
            # the shared expert: every token, whole, weight 1
            with scope("shared_expert"):
                y = y + lm_block.swiglu(h2, *(
                    g[lay[n][0]] for n in ("shared_gate", "shared_up",
                                           "shared_down")),
                    limit=shared_limit)
        return y

    def _shortcut_ffn(g, lay, x, hits, held):
        """A sub-block of a DOUBLE layer after its attention: x + the
        dense SwiGLU of u = norm(x).  The FIRST sub-block (a "router"
        among its names) also computes the layer's ONE expert layer on
        the SAME u and holds its result; the SECOND is given it as
        `held` and adds it after its own dense FFN (the shortcut:
        across chips the experts' exchange has the second sub-block
        to hide under).  -> (x, what is held)."""
        first = "router" in lay
        with scope("dense_ffn"):
            u = _norm(g, x, lay["norm2"]).reshape(-1, d_model)
        if first:
            held = _experts(g, lay, u, hits).reshape(x.shape)
        with scope("dense_ffn"):
            x = _residual(x, lm_block.swiglu(u, *(
                g[lay[n][0]] for n in ("dense_gate", "dense_up",
                                       "dense_down"))).reshape(x.shape))
        if first:
            return x, held
        with scope("moe_shortcut_join"):
            return _residual(x, held), None

    def _head(g, x, normed=False):
        """The logits of x; `normed`: x is already through the final
        norm (a looped stack applies it after every pass)."""
        with scope("head"):
            h = x if normed else _norm(g, x, layout.final)
            logits = (h @ g[layout.tok].T if spec.tied_head
                      else _fc(g, h, layout.head))
            if spec.logits_scaling != 1.0:
                logits = logits / spec.logits_scaling
            if spec.lm_head_multiplier != 1.0:
                logits = logits * spec.lm_head_multiplier
            return logits

    def _ssm(g, lay, u, state, tail, fresh, live):
        """`mamba2_step` of the mixer's input u with the layer's arrays."""
        return lm_block.mamba2_step(
            spec, u, state, tail, fresh, live,
            {n: (tuple(g[w] for w in lay[n]) if n == "ssm_conv"
                 else g[lay[n][0]])
             for n in ("ssm_in", "ssm_conv", "ssm_dt", "ssm_a_log",
                       "ssm_d", "ssm_gate_norm", "ssm_out")},
            scope=scope)

    def _mixer(g, lay, x, state, tail, fresh, live):
        """x + the Mamba-2 mixer of norm(x), one position a lane, the
        layer's state and tail after it, and what its recurrence was
        given (`mamba2_step`)."""
        with scope("ssm_in_proj"):
            u = _norm(g, x, lay["norm1"])
        out, state, tail, given = _ssm(g, lay, u, state, tail, fresh, live)
        with scope("ssm_out_proj"):
            return _residual(x, out), state, tail, given

    def _parallel_mixer(g, lay, x, state, tail, fresh, live):
        """The Mamba half of a PARALLEL layer: -> (u = norm(x), the ONE
        normed input both mixers read; the mixer's output of
        `ssm_in_multiplier` * u, NOT yet in the stream; the layer's
        state and tail after it; what its recurrence was given)."""
        with scope("ssm_in_proj"):
            u = _norm(g, x, lay["norm1"])
            v = (u if spec.ssm_in_multiplier == 1.0
                 else u * spec.ssm_in_multiplier)
        return (u,) + _ssm(g, lay, v, state, tail, fresh, live)

    def _conv_mixer(g, lay, x, tail, fresh, live):
        """x + the gated short convolution of norm(x), one position a
        lane, and the layer's tail after it (`short_conv_step`)."""
        with scope("conv_in_proj"):
            u = _norm(g, x, lay["norm1"])
        out, tail = lm_block.short_conv_step(
            spec, u, tail, fresh, live,
            {n: g[lay[n][0]] for n in ("conv_in", "conv_w", "conv_out")},
            scope=scope)
        with scope("conv_out_proj"):
            return _residual(x, out), tail

    def _delta_mixer(g, lay, x, state, tail, fresh, live):
        """x + the gated delta rule of norm(x), one position a lane, and
        the layer's state and tail after it (`delta_rule_step`)."""
        with scope("delta_in_proj"):
            u = _norm(g, x, lay["norm1"])
        # the kernel's own module decides from the lanes of this trace,
        # the heads and the platform whether the recurrence is its
        # Pallas kernel, in place on the pool, or `lm_block.delta_rule`
        kernel, refused = _delta_rule.select_delta_rule(
            lanes=state.shape[0], heads=spec.delta_heads,
            d_head=spec.delta_d_head, platform=platform)
        decoder.delta_kernel = (kernel.name if kernel is not None
                                else f"xla:{refused}")
        out, state, tail = lm_block.delta_rule_step(
            spec, u, state, tail, fresh, live,
            {n: g[pair[0]] for n, pair in lay.items()
             if n.startswith("delta_")}, scope=scope, kernel=kernel)
        with scope("delta_out_proj"):
            return _residual(x, out), state, tail

    def _with_counts(out, hits, live):
        """`out` and what the step counted (`decoder.step_counters`):
        the distinct experts each layer with experts routed to and,
        where the block HOLDS a share of them, the assignments of the
        `live` rows [T] that fell on held experts, a layer; under a
        group limit (which is what bounds the chips a token reaches)
        also the live rows with at least one such assignment."""
        if not hits:
            return out
        out = out + (jnp.stack([h[0] for h in hits]),)
        if not shares:
            return out
        first, e_n = spec.held

        def here(h):
            return (h[3] >= first) & (h[3] < first + e_n) & live[:, None]

        with scope("moe_dispatch"):
            out = out + (jnp.stack([jnp.sum(here(h), dtype=jnp.int32)
                                    for h in hits]),)
            if spec.n_group > 1 or spec.zero_experts:
                out = out + (jnp.stack([jnp.sum(here(h).any(axis=1),
                                                dtype=jnp.int32)
                                        for h in hits]),)
            if spec.zero_experts:
                # every layer routes the same live rows, k each
                sent = jnp.sum(live, dtype=jnp.int32) * spec.experts_per_token
                out = out + (
                    jnp.stack([jnp.sum(
                        live[:, None] & (h[3] >= spec.n_experts),
                        dtype=jnp.int32) for h in hits]),
                    jnp.broadcast_to(sent, (len(hits),)))
            return out

    def _kind_scope(name, kind, under=None):
        """`paged_decoder/<name>`, and under it the layer's kind where
        the block has sliding layers: the gather and the attention of
        ring and table then read apart, and still sum under `name`.
        `under`: a scope between the two (`attention/selected/full`: the
        readers of a selection look for `attention/selected`)."""
        if not ringed and under is None:
            return scope(name)
        both = contextlib.ExitStack()
        both.enter_context(scope(name))
        if under is not None:
            both.enter_context(scope(under))
        if ringed:
            both.enter_context(scope(kind.split("_")[0]))
        return both

    def _gather(pool, l, tables, kind):
        """Layer `l` (its index in the pool of its kind) through the
        block table, as the pool stores it: ([S, NB*BS, Dkv] values in
        the pool's dtype, [S, NB*BS] float32 scales or None).  Layer
        and table index the pool TOGETHER, so no [num_blocks, BS, Dkv]
        slice of the pool is copied first, and table order IS logical
        order (ring order, on a sliding layer), so the rows are the
        dense cache's rows.  int8 values stay int8: their per-(layer,
        block) scale is applied to the scores and to the softmax
        weights (`_attention`), which is the same product."""
        s_n, rows = tables.shape[0], tables.shape[1] * bs
        with _kind_scope("kv_gather", kind):
            if kv_dtype == "int8":
                q, sc_ = pool
                return (q[l, tables].reshape(s_n, rows, d_kv),
                        jnp.repeat(sc_[l, tables], bs, axis=1))
            # (a latent ring's row is the sliding layers' own width)
            return pool[l, tables].reshape(
                s_n, rows, geo[kind].d_kv if latent else d_kv), None

    def _block_diagonal(q):
        """q [S, W, H*dh] laid out block-diagonally by K/V head:
        [S, W*H, Dkv], a query head's d_head columns in ITS K/V head's
        columns of a pool row and zero outside them, so `Qbd . K^T`
        over Dkv IS the per-head score."""
        s_n, w_n = q.shape[0], q.shape[1]
        if (group, d_kv) == (1, d_model):
            # a head's columns of q ARE its columns of a pool row
            q_wide = q[:, :, None, :]
        else:
            # a head's d_head columns, under every K/V head
            q_wide = jnp.tile(
                q.reshape(s_n, w_n, n_heads, d_head), (1, 1, 1, n_kv))
        return jnp.where(_head_cols(), q_wide, 0.0).reshape(
            s_n, w_n * n_heads, d_kv)

    def _head_cols():
        """[H, Dkv] bool: column c belongs to K/V head c // d_head,
        which query heads h with h // group == c // d_head share."""
        kv_head = jnp.arange(n_heads)[:, None]
        if group > 1:
            kv_head = kv_head // group
        return jnp.arange(d_kv)[None, :] // d_head == kv_head

    def _own_columns(ctx, w_n):
        """`weights . V` over whole pool rows [S, W*H, Dkv] ->
        [S, W, H*dh]: of all Dkv columns a query head keeps its K/V
        head's."""
        s_n = ctx.shape[0]
        kept = jnp.where(
            _head_cols(), ctx.reshape(s_n, w_n, n_heads, d_kv), 0.0)
        if (group, d_kv) == (1, d_model):
            return kept.sum(axis=2)
        return kept.reshape(s_n, w_n, n_heads, n_kv, d_head).sum(
            axis=3).reshape(s_n, w_n, n_heads * d_head)

    def _attention(q, pool_k, pool_v, l, tables, pos_mask, kind,
                   selected=False):
        """Attention of q [S, W, H*dh] over layer `l` of the paged
        pools -> [S, W, H*dh]; pos_mask [S, W, rows] says which rows
        of the table (logical positions; ring slots on a sliding
        layer) each window row sees.  The XLA gather path: what
        `step_window` runs, and the resident step where
        `select_paged_attention` refuses the pool.

        K and V are read once, in the pool's dtype, with the pool's
        row (the K/V heads side by side, d_model wide under plain
        multi-head attention) as the minor dimension all the way into
        the contraction: the query is block-diagonal by K/V head
        (`_block_diagonal`), and `weights . V` gives every query head
        all Dkv columns of which it keeps its K/V head's
        (`_own_columns`).  That spends n_kv_heads times the
        multiply-adds of a head-split contraction and never reshapes K
        or V to [.., heads, d_head] (on a TPU a relayout of the whole
        gathered view into half-empty lane tiles) nor widens them to
        float32.  Scores, mask, softmax and both accumulations are
        float32.  `selected`: the mask is a lightning indexer's
        selection under the cursor, and the products lie one scope down
        (`attention/selected`)."""
        w_n = q.shape[1]
        k, k_scale = _gather(pool_k, l, tables, kind)
        batched = ((0,), (0,))
        if latent:
            # one row for every head: the query is dense over it, the
            # value its latent columns, the context [S, W, H * latent]
            kg = geo[kind]
            with _kind_scope("attention", kind,
                             under="selected" if selected else None):
                sc = jax.lax.dot_general(
                    q.reshape(q.shape[0], w_n * kg.h, kg.d_kv), k,
                    (((2,), (2,)), batched),
                    preferred_element_type=jnp.float32) * kg.scale
                sc = jnp.where(jnp.repeat(pos_mask, kg.h, axis=1), sc,
                               -jnp.inf)
                ctx = jax.lax.dot_general(
                    jax.nn.softmax(sc, axis=-1), k[..., :kg.d_lat],
                    (((2,), (1,)), batched),
                    preferred_element_type=jnp.float32)
                return ctx.reshape(q.shape[0], w_n, kg.h * kg.d_lat)
        v, v_scale = _gather(pool_v, l, tables, kind)
        with _kind_scope("attention", kind):
            sc = jax.lax.dot_general(
                _block_diagonal(q), k, (((2,), (2,)), batched),
                preferred_element_type=jnp.float32) * scale
            if k_scale is not None:
                sc = sc * k_scale[:, None, :]
            sc = jnp.where(jnp.repeat(pos_mask, n_heads, axis=1), sc,
                           -jnp.inf)
            w_att = jax.nn.softmax(sc, axis=-1)
            if v_scale is not None:
                w_att = w_att * v_scale[:, None, :]
            ctx = jax.lax.dot_general(
                w_att, v, (((2,), (1,)), batched),
                preferred_element_type=jnp.float32)
            return _own_columns(ctx, w_n)

    def _streamed(q, kk, vv, pool_k, pool_v, l, tables, cursor, kind,
                  select=None):
        """`_write` and `_attention` of one position a slot, q
        [S, H*dh], through the Pallas kernel -> (context, pools):
        `cursor` is (the row of its table, its ring on a sliding
        layer, that slot s's K and V of this position go to, negative
        for a slot that writes nothing; the rows `lengths[s]` it then
        attends over).  The kernel reads those pages from the pool
        and no other, puts the new row into its page in VMEM and
        sends it back to the pool from there: no scatter and no
        logical-order copy exists.  The arithmetic is
        `_attention`'s (the kernel builds `_block_diagonal`'s operand
        and keeps `_own_columns`' columns itself, in VMEM), an online
        softmax over chunks of pages in place of one softmax over the
        table.  `select`: a lightning indexer's selection [S, rows]:
        the softmax is over those of the cursor's rows alone, under a
        scope of its own; on a SLIDING layer the ring's rows inside the
        window (`_ring_window`), under the ring's own scope."""
        row, lengths = cursor
        attend = attend_of[kind]
        kind_scale = geo[kind].scale if latent else scale
        if select is not None:
            with _kind_scope("attention", kind, under=(
                    None if kind == lm_block.SLIDING else "selected")):
                out = attend(q, pool_k, None, tables, lengths, l,
                             kind_scale, write=(kk, vv, row), select=select)
            return out + (pool_v,)
        with _kind_scope("attention", kind):
            out = attend(q, pool_k, pool_v, tables, lengths, l, kind_scale,
                         write=(kk, vv, row))
            # a latent pool is one array: the V pool is the empty
            # tuple it came as
            return out + (pool_v,) if latent else out

    def _by_kind(x):
        """A pool or the tables as the step is given them, by layer
        kind: one array (or int8 pair) where every layer is full, else
        the pair (full layers', sliding layers') or (attention
        layers', the layers' that keep something a lane: a list, one
        array a layer; empty where conv layers have no state)."""
        if stateful:
            return {lm_block.FULL: x[0], lane_kind: list(x[1])}
        if not ringed:
            return {lm_block.FULL: x}
        return {lm_block.FULL: x[0], lm_block.SLIDING: x[1]}

    def _joined(by_kind):
        if stateful:
            return (by_kind[lm_block.FULL], tuple(by_kind[lane_kind]))
        return (tuple(by_kind[k] for k in (lm_block.FULL,
                                           lm_block.SLIDING))
                if ringed else by_kind[lm_block.FULL])

    def _ring_block(win_tables, positions, active):
        """The ring block a sliding layer writes position c into: the
        ring's `nw` blocks hold position p at block (p // BS) % nw of
        the lane's ring table."""
        lane = jnp.arange(positions.shape[0])
        with scope("kv_write"):
            return jnp.where(
                active, win_tables[lane, (positions // bs) % nw], 0)

    def _ring_window(positions, active):
        """[S, ring rows] bool: the rows of a ring LONGER than its window
        that lie inside it.  Ring row r holds position c - (c - r) mod
        rows (the newest one congruent to r at or under the cursor c),
        which is inside the window iff that distance is under `window`;
        a row never written is hidden by `_sees`, as ever."""
        rows = nw * bs
        with _kind_scope("attention", lm_block.SLIDING):
            c = jnp.where(active, positions, 0)[:, None]
            return (c - jnp.arange(rows)[None, :]) % rows < spec.window

    def _sees(kind, positions, active):
        """What each slot sees, after this position's write, of the
        rows of its table (full layer) or ring (sliding layer), from
        the cursor alone.  On a table row j is logical position j,
        seen iff j <= cursor, which also hides unallocated tail
        entries.  Ring row r holds the newest position p <= cursor
        with p = r (mod the ring's rows), which is inside the window
        or (before the first wrap) negative: never written.  Either
        way the rows seen are the FIRST `min(cursor + 1, rows)`, in
        whatever order the ring holds them (the keys are rotated before
        they are written), so the kernel takes that length [S] (an
        inactive slot's is 1: a page read, the row thrown away) and the
        gather path the mask [S, rows] that says the same."""
        rows = (nw if kind == lm_block.SLIDING else nb) * bs
        with _kind_scope("attention", kind):
            if attend_of[kind] is not None:
                # and the row the kernel writes this position's K and
                # V to: on a table the position, on a ring where
                # `_ring_block` puts it; nothing for a slot with no
                # sequence
                return (jnp.where(active, positions % rows, -1),
                        jnp.minimum(jnp.where(active, positions + 1, 1),
                                    rows))
            c = positions[:, None]
            if kind != lm_block.SLIDING:
                return jnp.arange(rows)[None, :] <= c
            return c - (c - jnp.arange(rows)[None, :]) % rows >= 0

    def _step_logits(g, pool_k, pool_v, tables, positions, tokens,
                     active):
        s_n = tokens.shape[0]
        lane = jnp.arange(s_n)
        hits, scans, picks = [], [], []
        pools_k, pools_v = _by_kind(pool_k), _by_kind(pool_v)
        # only a ring brings a second table
        tabs = _by_kind(tables) if ringed else {lm_block.FULL: tables}
        tables = tabs[lm_block.FULL]
        x = _embed(g, tokens, positions)                      # [S, D]
        rot = _rotation(positions)
        with scope("kv_write"):
            # this tick's K/V land at the cursor's (block, offset);
            # inactive slots are routed to block 0 offset 0 — the
            # pool's reserved null/scratch block, never owned by a
            # sequence
            wb = jnp.where(active, tables[lane, positions // bs], 0)
            wi = jnp.where(active, positions % bs, 0)
        written = {lm_block.FULL: wb}
        if ringed:
            written[lm_block.SLIDING] = _ring_block(
                tabs[lm_block.SLIDING], positions, active)
        cursor = {kind: (wb, _sees(kind, positions, active))
                  for kind, wb in written.items()}
        in_window = (_ring_window(positions, active) if ring_masked
                     else None)

        def write_and_attend(kind, plane, q, kk, vv, only, pools_k,
                             pools_v):
            """This position's K and V rows into plane `plane` of the
            pool of `kind` (in `pools_k`, `pools_v`, updated), and the
            attention of q over what the cursor shows there, of which
            `only` (a selection or a window; None: all).  -> [S, H*Dv]"""
            wb, seen = cursor[kind]
            if attend_of[kind] is not None:
                ctx_av, pools_k[kind], pools_v[kind] = _streamed(
                    q, kk, vv, pools_k[kind], pools_v[kind], plane,
                    tabs[kind], seen, kind, select=only)
                return ctx_av
            with scope("kv_write"):
                pools_k[kind] = _write(pools_k[kind], plane, wb, wi, kk)
                if not latent:
                    pools_v[kind] = _write(pools_v[kind], plane, wb, wi,
                                           vv)
            if only is not None:
                seen = seen & only
            return _attention(
                q[:, None, :], pools_k[kind], pools_v[kind], plane,
                tabs[kind], seen[:, None, :], kind,
                selected=only is not None
                and kind != lm_block.SLIDING)[:, 0]

        def parallel_layer(lay, li, x, pools_k, pools_v):
            """A PARALLEL layer's two mixers on its ONE normed input:
            the Mamba half on plane `li` of the lanes' states and tails
            (they ride as a Mamba layer's), the attention half on plane
            `li` of the table, and the two outputs into the stream, each
            under its factor.  -> x before the layer's FFN."""
            lanes, table = lm_block.MAMBA, lm_block.FULL
            u, mixed, pools_k[lanes][li], pools_v[lanes][li], given = (
                _parallel_mixer(g, lay, x, pools_k[lanes][li],
                                pools_v[lanes][li], positions == 0, active))
            scans.append(given)
            q, kk, vv = _qkv(
                g, lay, x, rot[lanes],
                normed=(u if spec.attention_in_multiplier == 1.0
                        else u * spec.attention_in_multiplier))
            ctx_av = write_and_attend(table, li, q, kk, vv, None, pools_k,
                                      pools_v)
            with scope("attn_out"):
                return (x + spec.ssm_out_multiplier * mixed
                        + spec.attention_out_multiplier
                        * _fc(g, ctx_av, lay["o"]))

        def stack(x, pools_k, pools_v, plane0=None):
            """The layers once over x, each writing this position's K/V
            and attending; `plane0`: the first plane of this pass of a
            looped stack (traced), else a layer's plane is its index in
            the pool of its kind (a Python int, as ever).  A double
            layer's two sub-blocks are two turns of the loop, the
            expert layer's result `held` from the first to the second."""
            nonlocal sub_traced, kind_traced
            held = chosen = None
            for l, (lay, kind, li) in enumerate(zip(layout.layers, kinds,
                                                    pool_index)):
                sub_traced = li % subs if subs > 1 else None
                kind_traced = kind if ringed and latent else None
                if kind == lm_block.MAMBA and parallel:
                    x = _ffn(g, lay, parallel_layer(
                        lay, li, x, pools_k, pools_v), hits)
                    continue
                if kind == lm_block.MAMBA:
                    # the lane's state rides where a pool's K does, its
                    # convolution tail where the V does
                    x, pools_k[kind][li], pools_v[kind][li], given = (
                        _mixer(g, lay, x, pools_k[kind][li],
                               pools_v[kind][li], positions == 0, active))
                    scans.append(given)
                    x = _ffn(g, lay, x, hits)
                    continue
                if kind == lm_block.DELTA:
                    # state and tail ride as a Mamba layer's
                    x, pools_k[kind][li], pools_v[kind][li] = _delta_mixer(
                        g, lay, x, pools_k[kind][li], pools_v[kind][li],
                        positions == 0, active)
                    x = _ffn(g, lay, x, hits)
                    continue
                if kind == lm_block.CONV:
                    # the lane's tail rides where a pool's V does; there
                    # is no state beside it
                    x, pools_v[kind][li] = _conv_mixer(
                        g, lay, x, pools_v[kind][li], positions == 0,
                        active)
                    x = _ffn(g, lay, x, hits)
                    continue
                if latent:
                    h, c_q = _latent_down(g, lay, x, geo[kind])
                    q, kk, vv = _latent_qkv(g, lay, h, c_q, rot[kind],
                                            geo[kind])
                else:
                    q, kk, vv = _qkv(g, lay, x, rot[kind])
                plane = li if plane0 is None else plane0 + li
                if "idx_q" in lay:
                    # a selecting layer: this position's index key into
                    # its plane (the V pool's place), the selection the
                    # layers up to the next selecting one attend over
                    chosen, scores, pools_v[kind] = _indexer(
                        g, lay, h, c_q, rot[kind], pools_v[kind],
                        index_plane[l], tabs[kind], cursor[kind][0], wi,
                        positions, active)
                    picks.append((h, c_q, scores, chosen))
                # the rows attention is over, of those the cursor shows:
                # a selection on the table, the window on a ring longer
                # than it (a sliding layer reads no selection)
                only = in_window if kind == lm_block.SLIDING else chosen
                ctx_av = write_and_attend(kind, plane, q, kk, vv, only,
                                          pools_k, pools_v)
                if latent:
                    ctx_av = _latent_values(g, lay, ctx_av, geo[kind])
                if "attn_head_gate" in lay:
                    # a sigmoid SCALAR a head, on the heads' values
                    with scope("attention_head_gate"):
                        gate = jax.nn.sigmoid(_fc(g, h, lay["attn_head_gate"]))
                        ctx_av = (ctx_av.reshape(s_n, geo[kind].h,
                                                 geo[kind].d_v)
                                  * gate[..., None]).reshape(ctx_av.shape)
                if "attn_gate" in lay:
                    with scope("attention_gate"):
                        ctx_av = ctx_av * jax.nn.sigmoid(_fc(
                            g, _norm(g, x, lay["norm1"]), lay["attn_gate"]))
                with scope("attn_out"):
                    y = _fc(g, ctx_av, lay["o"])
                    if not spec.post_norm:
                        x = _residual(x, y)
                kind_traced = None
                if spec.post_norm:
                    x = _post_join(g, x, y, lay["post1"])
                if subs > 1:
                    x, held = _shortcut_ffn(g, lay, x, hits, held)
                else:
                    x = _ffn(g, lay, x, hits)
            sub_traced = None
            return x

        if not looped:
            x = stack(x, pools_k, pools_v)
            return (_head(g, x), _joined(pools_k), _joined(pools_v),
                    hits, picks if sparse else scans)         # [S, V]

        def one_pass(carry, t):
            """Pass t of the stack: ONE body in the program however
            many passes run it.  -> x_t (through the final norm: what
            the next pass starts from) and the exit gate lambda_t."""
            x, pk, pv = carry
            pools_k, pools_v = {lm_block.FULL: pk}, {lm_block.FULL: pv}
            # the loop's body starts a name stack of its own
            # (`.../while/body/...`): the parts keep the names the
            # scope tables join on, `paged_decoder/<part>`
            with scope("paged_decoder"):
                x = stack(x, pools_k, pools_v, plane0=t * n_full)
                with scope("loop_norm"):
                    x = _norm(g, x, layout.final)
                lam = jnp.zeros(s_n, jnp.float32)
                if layout.exit is not None:
                    with scope("exit_gate"):
                        w_exit, b_exit = (g[n].astype(jnp.float32)
                                          for n in layout.exit)
                        lam = jax.nn.sigmoid((x @ w_exit)[:, 0]
                                             + b_exit[0])
            return ((x, pools_k[lm_block.FULL], pools_v[lm_block.FULL]),
                    (x, lam))

        (x, pool_k, pool_v), (x_t, lam_t) = jax.lax.scan(
            one_pass, (x, pools_k[lm_block.FULL], pools_v[lm_block.FULL]),
            jnp.arange(passes, dtype=jnp.int32))
        return (_head(g, x, normed=True), pool_k, pool_v, hits,
                {"passes": x_t, "gates": lam_t})

    @functools.partial(jax.jit, donate_argnums=donate)
    def step(g, pool_k, pool_v, tables, positions, tokens, seeds, temps,
             active):
        with scope("paged_decoder"):
            logits, pool_k, pool_v, hits, loop = _step_logits(
                g, pool_k, pool_v, tables, positions, tokens, active)
            out = (_sample(logits, seeds, positions, temps), pool_k,
                   pool_v)
            if spec.exit_gate:
                # what an adaptive exit would look at: (lane, pass)
                # pairs whose gate is over a half, of the live lanes
                with scope("exit_gate"):
                    return out + (jnp.sum(
                        (loop["gates"] > 0.5) & active[None, :],
                        dtype=jnp.int32),)
            return _with_counts(out, hits, active)

    @jax.jit
    def step_logits(g, pool_k, pool_v, tables, positions, tokens, seeds,
                    temps, active):
        with scope("paged_decoder"):
            return _step_logits(g, pool_k, pool_v, tables, positions,
                                tokens, active)[0]

    @jax.jit
    def step_routing(g, pool_k, pool_v, tables, positions, tokens, seeds,
                     temps, active):
        """`step_logits`, and beside the logits every layer's routing
        as the step computed it: {"inputs": float32 [layers, S, D]
        (what the router was given), "weights": float32 [layers, S,
        k], "experts": int32 [layers, S, k]}, the layers those with
        experts (`decoder.moe_layers`), and for a block with
        Mamba layers "ssm_inputs": float32 [Mamba layers, S, H*P + 2N +
        H], what each layer's recurrence was given at this position
        (`lm_block.mamba2_step`); a block of PARALLEL layers gives
        beside them "ssm_states": float32 [layers, S, H, P, N], what
        each layer's recurrence LEFT (the planes `step` writes into the
        lanes' pool), so that ONE program holds a recurrence's inputs
        and its result: `step`, compiled apart, rounds the matmuls
        before a deeper layer its own way, and a state it advanced is
        not to the bit the recurrence of these inputs.  Its twin for a
        LOOPED stack, which routes nothing: {"passes": float32 [passes,
        S, D], x_t of every pass (through the final norm), "gates":
        float32 [passes, S], the exit gate lambda_t}, so that a pass
        that read another
        pass's plane shows at the pass where it happened."""
        with scope("paged_decoder"):
            logits, new_k, _, hits, scans = _step_logits(
                g, pool_k, pool_v, tables, positions, tokens, active)
            if looped:
                return logits, scans
            out = {}
            if hits:
                inputs, weights, experts = (
                    jnp.stack([h[i] for h in hits]) for i in (1, 2, 3))
                out = {"inputs": inputs, "weights": weights,
                       "experts": experts}
            if sparse:
                # what each selecting layer was given and chose
                for i, name in enumerate(("index_inputs", "index_latents",
                                          "index_scores", "selected")):
                    out[name] = jnp.stack([p[i] for p in scans])
            elif scans:
                out["ssm_inputs"] = jnp.stack(scans)
            if parallel:
                out["ssm_states"] = jnp.stack(new_k[1])
            return logits, out

    @functools.partial(jax.jit, donate_argnums=donate)
    def step_window(g, pool_k, pool_v, tables, positions, tokens, seeds,
                    temps, n_valid):
        with scope("paged_decoder"):
            return _step_window(g, pool_k, pool_v, tables, positions,
                                tokens, seeds, temps, n_valid)

    def _step_window(g, pool_k, pool_v, tables, positions, tokens, seeds,
                     temps, n_valid):
        if stateful:
            raise NotImplementedError(
                f"block {spec.name!r}: step_window runs a window of "
                "positions in one dispatch, and a lane's recurrent "
                "state or convolution tail is carried over them by the "
                "one-position `step` alone (a chunked scan, or a "
                "convolution over the tail and the chunk, is not "
                "built); such a block runs `step` alone (no draft "
                "model, no chunked prefill)")
        if latent:
            raise NotImplementedError(
                f"block {spec.name!r}: step_window is not built for a "
                "latent cache (a window of positions would want the "
                "EXPANDED form, keys and values widened a chunk at a "
                "time, and only the absorbed one-position `step` is "
                "built); such a block runs `step` alone (no draft "
                "model, no chunked prefill)")
        if looped:
            raise NotImplementedError(
                f"block {spec.name!r}: step_window is not built for a "
                "looped stack (a window of positions through every "
                "pass, each pass's K/V in its own planes); such a "
                "block runs `step` alone (no draft model, no chunked "
                "prefill)")
        if ringed:
            raise NotImplementedError(
                f"block {spec.name!r}: step_window writes a window of "
                "positions before it attends, and a ring exactly one "
                "window long has then overwritten keys its first rows "
                "still see; a block with sliding layers runs `step` "
                "alone (no draft model, no chunked prefill)")
        # teacher-forced multi-position step: slot s processes window
        # positions positions[s]+j for j < n_valid[s] in one dispatch.
        # Rows past n_valid write to the null block; their predictions
        # are garbage the scheduler ignores.
        s_n, w_n = tokens.shape
        lane = jnp.arange(s_n)
        offs_w = jnp.arange(w_n)
        hits = []
        pos_w = positions[:, None] + offs_w[None, :]          # [S, W]
        valid = offs_w[None, :] < n_valid[:, None]            # [S, W]
        pos_c = jnp.clip(pos_w, 0, max_len - 1)
        x = _embed(g, tokens, pos_c)                          # [S, W, D]
        rot = _rotation(pos_c)
        with scope("kv_write"):
            wb = jnp.where(valid,
                           tables[lane[:, None],
                                  jnp.clip(pos_w // bs, 0, nb - 1)], 0)
            wi = jnp.where(valid, pos_w % bs, 0)
        with scope("attention"):
            # causal within the window AND over the committed span:
            # window row j attends to absolute positions
            # <= positions[s]+j (row 0 reproduces `step`'s mask exactly)
            pos_mask = (jnp.arange(nb * bs)[None, None, :]
                        <= pos_w[:, :, None])                 # [S, W, L]
        for l, lay in enumerate(layout.layers):
            q, kk, vv = _qkv(g, lay, x, rot[kinds[l]])
            # the whole window's K/V is written before the gather, so
            # in-window attention sees the fresh values; int8 blocks
            # re-quantize per position, in order (the running-max
            # discipline needs offsets written low-to-high)
            with scope("kv_write"):
                for j in range(w_n):
                    pool_k = _write(pool_k, l, wb[:, j], wi[:, j],
                                    kk[:, j])
                    pool_v = _write(pool_v, l, wb[:, j], wi[:, j],
                                    vv[:, j])
            # a window of query rows a slot: the gather path (the
            # kernel is one row a slot: `decoder.kernels`)
            ctx_av = _attention(q, pool_k, pool_v, l, tables, pos_mask,
                                kinds[l])
            with scope("attn_out"):
                x = _residual(x, _fc(g, ctx_av, lay["o"]))
            x = _ffn(g, lay, x, hits)
        logits = _head(g, x)                                  # [S, W, V]
        seeds_w = jnp.broadcast_to(seeds[:, None], (s_n, w_n))
        temps_w = jnp.broadcast_to(temps[:, None], (s_n, w_n))
        preds = _sample(logits.reshape(s_n * w_n, -1),
                        seeds_w.reshape(-1), pos_c.reshape(-1),
                        temps_w.reshape(-1)).reshape(s_n, w_n)
        return _with_counts((preds, pool_k, pool_v), hits,
                            valid.reshape(-1))

    if kv_dtype == "fp32":
        elem_bytes = 4.0
    elif kv_dtype == "bf16":
        elem_bytes = 2.0
    else:
        # int8 payload + one f32 scale per (layer, block)
        elem_bytes = 1.0 + 4.0 / (bs * d_kv)
    n_win = kinds.count(lm_block.SLIDING)
    # K+V of one block over the planes that hold it: a table block
    # over the full layers (of every pass of a looped stack), a ring
    # block over the sliding ones
    planes = passes * n_full
    # (a latent block's one array holds keys and values at once)
    bytes_per_block = int((1 if latent else 2) * planes * bs * d_kv
                          * elem_bytes)
    # and an index key a position on each selecting layer's plane
    bytes_per_block += int(n_index * bs * d_idx * elem_bytes)
    # (a latent ring's ONE array, at the sliding layers' own row width)
    window_bytes_per_block = int(
        n_win * bs * geo[lm_block.SLIDING].d_kv * elem_bytes if latent
        else 2 * n_win * bs * d_kv * elem_bytes)
    # what a lane holds over the layers that keep something a lane,
    # float32: a Mamba layer its SSM state and the convolution tail of
    # x B C, a gated short convolution the tail of its product alone
    # a delta-rule layer its matrix state and the tail of q | k | v
    n_mamba = kinds.count(lm_block.MAMBA)
    n_conv = kinds.count(lm_block.CONV)
    n_delta = kinds.count(lm_block.DELTA)
    n_state = n_mamba + n_delta
    n_lane = n_state + n_conv
    if n_delta:
        state_shape = (spec.delta_heads, spec.delta_d_head,
                       spec.delta_d_head)
        tail_shape = (spec.delta_conv - 1,
                      3 * spec.delta_heads * spec.delta_d_head)
    else:
        state_shape = (spec.ssm_heads, spec.ssm_d_head, spec.ssm_d_state)
        tail_shape = ((spec.conv_width - 1, d_model) if n_conv else
                      (spec.ssm_conv - 1, spec.ssm_heads * spec.ssm_d_head
                       + 2 * spec.ssm_groups * spec.ssm_d_state))
    state_bytes_per_lane = 4 * (n_state * math.prod(state_shape)
                                + n_lane * math.prod(tail_shape))
    if ringed:
        # what belongs to a lane of a block with a ring: its ring blocks
        # of every sliding layer, which a snapshot holds
        state_bytes_per_lane = nw * window_bytes_per_block
    # what a step reads WHOLE whatever the traffic, in elements (their
    # bytes are the weights' dtype's, which a step's trace notes): every
    # array of the stack outside the routed experts, once a pass, and
    # what stands outside the stack (the final norm, the head, an exit
    # gate); not the embedding, of which a step reads a row a lane,
    # unless the head is the table, nor a position table.  And ONE
    # routed expert's three matrices (0: no experts)
    routed = [lay[key][0] for lay in layout.layers if "router" in lay
              for key in ("gate", "up", "down")]
    stack = {name for lay in layout.layers for pair in lay.values()
             for name in pair if name is not None} - set(routed)
    rows_of = {layout.pos} | ({layout.tok} - set(layout.head))
    weight_elems = sum(
        (passes if name in stack else 1) * math.prod(shapes[name])
        for name in set(shapes) - set(routed) - rows_of)
    expert_elems = sum(math.prod(shapes[name][1:]) for name in routed[:3])
    # a page of one plane of each pool, and the rows a lane writes a
    # position over all of them
    page_bytes = {"table": int((1 if latent else 2) * bs * d_kv
                               * elem_bytes),
                  "ring": window_bytes_per_block // max(n_win, 1),
                  "index": int(bs * d_idx * elem_bytes)}
    row_bytes = (bytes_per_block + window_bytes_per_block) // bs

    def init_pool(num_blocks, device=None, window_blocks=None,
                  lanes=None):
        """Zero pools of `num_blocks` blocks (the null block included)
        for the full layers and, for a block with sliding layers,
        `window_blocks` for their rings (read by no other block):
        (pool_k, pool_v), each one array (an int8 pair) or the pair
        (full, ring) `step` takes.  For a block with Mamba or delta-rule
        layers each is the pair (the attention layers' pool, one float32
        array such a layer: `lanes` states beside K, `lanes` convolution
        tails beside V), for one with conv layers the same pair with no
        states (the empty tuple beside K, a tail a conv layer beside
        V); `lanes` is the step's lane count and read by no other
        block.  A latent block's is (the one pool, ()), with a
        lightning indexer (the latent pool, the index-key pool), with
        delta-rule layers ((the latent pool, the states), ((), the
        tails)), with sliding layers ((the latent table, the latent ring
        pool of `window_blocks` blocks at the sliding layers' row), (the
        index-key pool or (), ()))."""
        def zeros(layers, blocks, width=d_kv):
            shape = (layers, int(blocks), bs, width)
            if kv_dtype == "int8":
                z = (jnp.zeros(shape, jnp.int8),
                     jnp.full(shape[:2], 1e-8, jnp.float32))
            else:
                z = jnp.zeros(shape, jnp.bfloat16 if kv_dtype == "bf16"
                              else jnp.float32)
            return z if device is None else jax.device_put(z, device)

        def lane_state(shape, layers):
            if lanes is None:
                raise ValueError(
                    f"block {spec.name!r} has layers with a state or a "
                    "tail a lane: init_pool needs lanes, the lane count "
                    "of the step")
            z = [jnp.zeros((int(lanes),) + shape, jnp.float32)
                 for _ in range(layers)]
            return tuple(z if device is None
                         else jax.device_put(z, device))

        if stateful:
            # (a latent table beside the lanes is ONE plane an attention
            # layer: nothing stands beside V but the tails)
            return ((zeros(n_full, num_blocks),
                     lane_state(state_shape, n_state)),
                    (() if latent else zeros(n_full, num_blocks),
                     lane_state(tail_shape, n_lane)))

        if ringed and window_blocks is None:
            raise ValueError(
                f"block {spec.name!r} has sliding layers: "
                "init_pool needs window_blocks, the ring pool's "
                "size (null block included)")
        index_keys = (zeros(n_index, num_blocks, d_idx) if sparse else ())
        if latent and ringed:
            # one array a kind beside K, each at its kind's row; beside
            # V the full layers' index keys and nothing of the ring's
            return ((zeros(planes, num_blocks),
                     zeros(n_win, window_blocks,
                           geo[lm_block.SLIDING].d_kv)),
                    (index_keys, ()))
        if latent:
            return zeros(planes, num_blocks), index_keys

        def z():
            if not ringed:
                return zeros(planes, num_blocks)
            return (zeros(n_layers - n_win, num_blocks),
                    zeros(n_win, window_blocks))

        return z(), z()

    def slot_rings(slots):
        """[slots, window_blocks_per_seq] int32: the ring-pool blocks
        of each lane of a `slots`-lane step, for a pool of
        `init_pool(..., window_blocks=slots * window_blocks_per_seq
        + 1)`."""
        import numpy as np

        return 1 + np.arange(slots * nw, dtype=np.int32).reshape(
            slots, nw)

    # -- snapshots of what belongs to a lane (a prefix cache's side of
    #    such a block): one row of every state and tail array a snapshot
    def init_snapshots(rows, device=None):
        """A pool of `rows` snapshots, zeros: (a float32 array [rows,
        ...] a layer with a state, one a layer with a tail), opaque to
        the caller.  For a block with a ring: (a lane's ring blocks
        beside K [rows, sliding layers, ring blocks, block_size, row] in
        the pool's dtype, the same beside V or () for a latent ring)."""
        if ringed:
            width = geo[lm_block.SLIDING].d_kv if latent else d_kv
            z = tuple(jnp.zeros(
                (int(rows), n_win, nw, bs, width),
                jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32)
                for _ in range(1 if latent else 2))
            z = z + ((),) * (2 - len(z))
        else:
            z = tuple(tuple(jnp.zeros((int(rows),) + shape, jnp.float32)
                            for _ in range(layers))
                      for shape, layers in ((state_shape, n_state),
                                            (tail_shape, n_lane)))
        return z if device is None else jax.device_put(z, device)

    @functools.partial(jax.jit,
                       donate_argnums=(0,) if platform != "cpu" else ())
    def snapshot_save(snapshots, pool_k, pool_v, lane, row):
        with jax.named_scope("state_snapshot_save"):
            if ringed:
                # the lane's `nw` ring blocks of every sliding layer,
                # from ring-pool block 1 + lane * nw (`slot_rings`)
                return tuple(
                    snap if isinstance(snap, tuple) else snap.at[row].set(
                        jax.lax.dynamic_slice_in_dim(
                            pool[1], 1 + lane * nw, nw, axis=1))
                    for snap, pool in zip(snapshots, (pool_k, pool_v)))
            return tuple(
                tuple(snap.at[row].set(held[lane])
                      for snap, held in zip(snaps, pool[1]))
                for snaps, pool in zip(snapshots, (pool_k, pool_v)))

    @functools.partial(jax.jit,
                       donate_argnums=(0, 1) if platform != "cpu" else ())
    def snapshot_restore(pool_k, pool_v, snapshots, lane, row):
        with jax.named_scope("state_snapshot_restore"):
            if ringed:
                return tuple(
                    pool if isinstance(snap, tuple) else (
                        pool[0], jax.lax.dynamic_update_slice_in_dim(
                            pool[1], snap[row], 1 + lane * nw, axis=1))
                    for snap, pool in zip(snapshots, (pool_k, pool_v)))
            return tuple(
                (pool[0], tuple(held.at[lane].set(snap[row])
                                for snap, held in zip(snaps, pool[1])))
                for snaps, pool in zip(snapshots, (pool_k, pool_v)))

    # The TPU compiler brings a matmul's weight in ahead of it in
    # slices of its own making (`slice-start` / `slice-done`, no
    # metadata), and the wait for them counts under their producer:
    # the step's PARAMETER, named `g['<weight>']`.  Only the builder
    # knows which part multiplies by which weight: {the parameter's
    # op_name as compiled text spells it: the part's scope}, for
    # `profiler.register_jitted`, beside the calls the compiler renames.
    parts = {"q": "qkv", "k": "qkv", "v": "qkv", "o": "attn_out",
             "w1": "mlp", "w2": "mlp", "q_a": "latent_q",
             "q_b": "latent_q", "kv_a": "latent_kv",
             "kv_b": "latent_absorb", "idx_q": "indexer_q",
             "idx_w": "indexer_q", "idx_k": "indexer_k",
             "dense_gate": "dense_ffn",
             "dense_up": "dense_ffn", "dense_down": "dense_ffn",
             "conv_in": "conv_in_proj", "conv_out": "conv_out_proj",
             "delta_in": "delta_in_proj", "delta_out": "delta_out_proj",
             "delta_fa": "delta_gates", "delta_fb": "delta_gates",
             "delta_f": "delta_gates",
             "delta_b": "delta_gates", "delta_ga": "delta_gate_norm",
             "delta_gb": "delta_gate_norm", "delta_gw": "delta_gate_norm",
             "attn_gate": "attention_gate",
             "attn_head_gate": "attention_head_gate"}
    if spec.ffn == "swiglu":
        parts.update(gate=ffn_scope, up=ffn_scope, down=ffn_scope)
    if parallel:
        parts.update(ssm_in="ssm_in_proj", ssm_out="ssm_out_proj")
    weights_of = [(lay[key], part) for lay in layout.layers
                  for key, part in parts.items() if key in lay]
    if spec.ffn == "moe_swiglu":
        # a dense layer among sparse ones: its three matrices
        weights_of += [(lay[key], "dense_ffn") for lay in layout.layers
                       if "router" not in lay
                       for key in ("gate", "up", "down") if key in lay]
    compiler_scopes = {
        f"g[\\'{name}\\']": f"paged_decoder/{part}"
        for pair, part in weights_of + [(layout.head, "head")]
        for name in pair or () if name is not None}
    if spec.ffn == "moe_swiglu":
        compiler_scopes.update(lm_block.MOE_COMPILER_SCOPES)

    window = spec.window if ringed else 0
    tiling = ((_attend.tiling(nb), _attend_ring.tiling(nw) if nw else None)
              if _attend is not None else None)
    index_tiling = (_index_scores.tiling(nb)
                    if _index_scores is not None else None)

    def starts_saved(tables=None, rings=None):
        """{"table", "index": from `tables` [lanes, table pages];
        "ring": from `rings` [lanes, ring pages]}, each int [lanes,
        groups + 1]: the DMA starts the attention kernel's issue loop
        (the index-score kernel's) saves a pool over each lane's table
        (ring), as `kernels.paged_attention.starts_saved`'s prefix sums
        over its groups at that kernel's chunk and unroll.  A request's
        table does not change after admission and a ring never: made
        once for each, so that `tick_counts` looks a tick's starts up.
        Only the keys of what was given and runs through a kernel."""
        saved = {}
        if tiling is not None and tables is not None:
            saved["table"] = _paged_attention.starts_saved(
                tables, tiling[0][0])
        if tiling is not None and n_win and rings is not None:
            saved["ring"] = _paged_attention.starts_saved(
                rings, tiling[1][0])
        if index_tiling is not None and tables is not None:
            saved["index"] = _paged_attention.starts_saved(
                tables, index_tiling[0], _index_scores.unroll)
        return saved

    def tick_counts(cursors, slots, windowed=False, saved=None):
        """What the step dispatched for a tick reads and does, for its
        `serving.decode_tick` span, from `cursors` (int array: the
        step's `positions` at the lanes that hold a sequence) and
        `slots`, its lanes; `windowed`: a `step_window` tick, which
        gathers always.  `kv_pages_read` of `kv_pages_table`,
        `kv_rows_multiplied` and, through the kernel alone,
        `kv_dma_ops` and `kv_pages_covered`: the K/V pages the step's
        attention reads of those the lanes' tables and rings hold, the
        rows its two products run over, the DMA starts and waits it
        performs and the pages whose copy is in flight under as many
        pages' products (the lanes in `cursors`' order), summed over
        lanes, attention layers and (the DMA operations) pools.  Through the
        Pallas kernel (`kernels`) they are what
        `kernels.paged_attention.stream_counts` says of the table's
        stream and the ring's at `attention_tiling` (`saved`:
        `starts_saved`'s rows for the lanes of `cursors`, in their
        order; without it every page counts a start); on the gather
        path every page and every row.
        With sliding layers `past_window` (cursors at or
        past the window: their rings have wrapped) and the rows a layer
        of each kind attends over, `kv_rows_full` (cursor + 1) and
        `kv_rows_win` (the window at most; a PARALLEL block gives the
        first for its table, which every layer reads, and 0).  With
        Mamba or conv layers
        `state_lanes` (lanes with a recurrent state or a convolution
        tail: all the tick's) and `state_resets` (those at position 0,
        which the step starts from zero); with conv layers also
        `conv_layers` and `conv_tail_bytes` (the float32 tails those
        lanes' conv layers read and write back); with delta-rule layers
        `delta_layers` and `state_bytes` (the float32 states and tails
        those lanes' delta layers read and write back).  With experts
        `moe_kernel` (1: the traced step's expert
        layer is the Pallas grouped matmul, 0: `ragged_dot`) and
        `moe_layers`; with delta-rule layers `delta_kernel` the same way
        (1: their recurrence is `kernels/delta_rule.py`, 0:
        `lm_block.delta_rule`'s lines).  With a looped stack
        `loop_passes` and `kv_planes`,
        the planes the pages are counted over (`kv_planes` with double
        layers too: two a layer).  With a latent cache `latent_rows`:
        the rows the lanes with a sequence hold under their cursors
        (cursor + 1), summed over them and the planes.  With a lightning
        indexer `kv_rows_indexed` (the rows its selecting
        layers score: cursor + 1 a lane a plane) and `kv_rows_selected`
        (the rows attention is over: `index_topk` at most of cursor + 1,
        a lane a latent plane), and `index_pages_read` of
        `index_pages_table`: the pages of the index planes its scores
        read, of those the lanes' tables hold: through the kernel
        (`kernels["lightning_indexer"]`) `stream_counts` of its stream,
        with `index_dma_ops` (a start a group of 16 entries that are a
        run); on the gather path every page.  Once a step has been
        traced also `select_kernel` (1: the selection of the traced
        step is `kernels/select_rows.py`'s call, 0: `lm_block
        .select_rows`' passes; `kernels["index_selection"]`).  With a LATENT ring
        `ring_bytes`: the ring rows the lanes with a sequence read
        (cursor + 1, the ring's rows at most), times the ring's stored
        row's bytes, summed over them and the sliding layers.
        And the BYTES the tick must move, under `perf/*_bytes.py`'s rule
        (what the algorithm needs, not what a compiler emitted), once a
        step has been traced (the weights' dtype is its argument) and
        never for a `step_window` tick: `step_bytes_weights`, every
        array the step reads whole whatever the traffic (each layer's
        matrices and scales outside the routed experts, once a pass of a
        looped stack; the final norm, the head, an exit gate; of the
        embedding a step reads a row a lane, which is left out unless
        the head is the table); `step_bytes_cache`, what follows the
        cursors: the table pages read times a table page's bytes a
        plane, the rings' pages times a ring page's (`ring_bytes` where
        the ring is latent), `index_pages_read` times an index page's,
        the ticking lanes' states and tails read and written back
        (`state_bytes`, `conv_tail_bytes`, a Mamba lane's state the same
        way) and the row a ticking lane writes a plane; and
        `expert_bytes`, ONE routed expert's three matrices (0 without
        experts), for the span's `moe_experts_hit` to multiply."""
        n = len(cursors)
        counts, saved = {}, saved or {}
        if passes > 1:
            counts["loop_passes"] = passes
        if passes > 1 or subs > 1:
            counts["kv_planes"] = planes
        rows = cursors.astype(np.int64) + 1  # K/V rows a lane attends
        table = slots * (planes * nb + n_win * nw)
        read, multiplied = table, table * bs
        read_ring = slots * n_win * nw       # of them, the rings' pages
        idle = slots - n                     # a page each, a plane
        if tiling is not None and not windowed:
            streamed = planes * np.array(_paged_attention.stream_counts(
                rows, idle, *tiling[0], bs, saved.get("table")))
            read_ring = 0
            if n_win:
                ring = n_win * np.array(_paged_attention.stream_counts(
                    np.minimum(rows, nw * bs), idle, *tiling[1], bs,
                    saved.get("ring")))
                read_ring = int(ring[0])
                streamed += ring
            read, multiplied, dma, covered = map(int, streamed)
            counts["kv_dma_ops"] = (1 if latent else 2) * dma
            counts["kv_pages_covered"] = covered
        read_table = read - read_ring
        counts["kv_pages_read"] = read
        counts["kv_pages_table"] = table
        counts["kv_rows_multiplied"] = multiplied
        if window:
            counts["past_window"] = int((rows > window).sum())
            counts["kv_rows_full"] = int(rows.sum())
            counts["kv_rows_win"] = int(np.minimum(rows, window).sum())
        if parallel:
            # the table's rows under the cursors, which EVERY layer reads
            # beside its lanes' states (no layer has a window)
            counts["kv_rows_full"] = int(rows.sum())
            counts["kv_rows_win"] = 0
        if window and latent:
            # the ring rows the lanes' sliding layers read (a ring holds
            # nw * bs rows at most), at the ring's stored row
            counts["ring_bytes"] = int(
                np.minimum(rows, nw * bs).sum()
                * window_bytes_per_block // bs)
        if latent:
            counts["latent_rows"] = planes * int(rows.sum())
        if sparse:
            counts["kv_rows_indexed"] = n_index * int(rows.sum())
            counts["kv_rows_selected"] = planes * int(
                np.minimum(rows, spec.index_topk).sum())
            counts["index_pages_table"] = n_index * slots * nb
            if index_tiling is None:
                counts["index_pages_read"] = counts["index_pages_table"]
            else:
                read, _, dma, _ = _paged_attention.stream_counts(
                    rows, idle, *index_tiling, bs, saved.get("index"),
                    _index_scores.unroll)
                counts["index_pages_read"] = n_index * read
                counts["index_dma_ops"] = n_index * dma
        if stateful:
            counts["state_lanes"] = n
            counts["state_resets"] = n - int(np.count_nonzero(cursors))
        if n_conv:
            counts["conv_layers"] = n_conv
            # read and written back: a conv block's lanes hold tails alone
            counts["conv_tail_bytes"] = 2 * n * state_bytes_per_lane
        if n_delta:
            counts["delta_layers"] = n_delta
            # the live lanes' states and tails, read and written back
            counts["state_bytes"] = 2 * n * state_bytes_per_lane
        if decoder.expert_kernel is not None:
            counts["moe_kernel"] = int(
                not decoder.expert_kernel.startswith("xla:"))
        if decoder.delta_kernel is not None:
            counts["delta_kernel"] = int(
                not decoder.delta_kernel.startswith("xla:"))
        if "index_selection" in decoder.kernels:
            counts["select_kernel"] = int(
                decoder.kernels["index_selection"].startswith("pallas:"))
        if moe_layers:
            counts["moe_layers"] = moe_layers
        if decoder.weight_itemsize is not None and not windowed:
            # what this tick must move (a `step_window` tick's written
            # rows are its caller's to know: no account of it)
            counts["step_bytes_weights"] = (
                weight_elems * decoder.weight_itemsize)
            counts["step_bytes_cache"] = int(
                read_table * page_bytes["table"]
                + counts.get("ring_bytes", read_ring * page_bytes["ring"])
                + counts.get("index_pages_read", 0) * page_bytes["index"]
                + (2 * n * state_bytes_per_lane if stateful else 0)
                + n * row_bytes)
            counts["expert_bytes"] = expert_elems * decoder.weight_itemsize
        return counts

    # where a block has two kinds of state, the ring's word stands; a
    # lane's state beside a latent table is refused for both reasons
    refuses = {}
    for kind, has in (("latent", latent), ("sparse", sparse),
                      ("loop", looped), ("state", stateful),
                      ("ring", ringed)):
        if has:
            refuses.update({
                what: (f"{refuses[what]}; and {why}"
                       if kind == "state" and latent and what in refuses
                       else why)
                for what, why in _REFUSALS[kind].items()})

    kernels = {"paged_attention_decode":
               f"xla:{_refused}" if _attend is None
               else "pallas:latent" if latent else "pallas",
               "paged_attention_window": f"xla:{_refused or 'window_rows'}"}
    if sparse:
        # how the selected rows are read: by page under a row mask (a
        # DMA moves whole sublane tiles, so a row list is read as the
        # pages that hold it), or the gather under the mask; the index
        # keys by the pages under the cursor, or through the table in
        # logical order
        kernels.update(
            paged_attention_selected=(
                f"xla:{_refused}:masked_gather" if _attend is None
                else "pallas:latent:masked_pages"),
            lightning_indexer=(
                f"xla:{_index_refused}:table_gather"
                if _index_scores is None else "pallas:paged_scores"))
    if ringed and latent:
        # the ring's kernel is a selection of its own, at the sliding
        # layers' geometry; a ring longer than its window is read by
        # page under the window's row mask
        kernels["paged_attention_ring"] = (
            f"xla:{_ring_refused}" + ":masked_gather" * ring_masked
            if _attend_ring is None
            else "pallas:latent" + ":masked_pages" * ring_masked)

    decoder = PagedDecoder(
        step=step, step_window=step_window, step_logits=step_logits,
        step_routing=(step_routing if spec.ffn == "moe_swiglu" or looped
                      or parallel else None),
        init_pool=init_pool, slot_rings=slot_rings, platform=platform,
        step_counters=(("moe_experts_hit",)
                       + (("moe_rows_held",) if shares else ())
                       + (("moe_tokens_here",)
                          if shares and (spec.n_group > 1
                                         or spec.zero_experts) else ())
                       + (("moe_zero_assignments", "moe_assignments")
                          if spec.zero_experts else ())
                       if spec.ffn == "moe_swiglu"
                       else ("exit_gate_open",) if spec.exit_gate
                       else ()),
        moe_layers=moe_layers, passes=passes, kv_planes=planes,
        compiler_scopes=compiler_scopes,
        state_names=sorted(shapes), state_shapes=shapes, block_size=bs,
        max_blocks_per_seq=nb, max_len=max_len, n_layers=n_layers,
        d_model=d_model, vocab_size=vocab_size, kv_dtype=kv_dtype,
        bytes_per_block=bytes_per_block,
        window_blocks_per_seq=nw, window=window,
        window_bytes_per_block=window_bytes_per_block,
        table_layers=planes, ring_layers=n_win, index_planes=n_index,
        state_layers=n_lane, state_bytes_per_lane=state_bytes_per_lane,
        init_snapshots=init_snapshots if stateful or ringed else None,
        snapshot_save=snapshot_save if stateful or ringed else None,
        snapshot_restore=snapshot_restore if stateful or ringed else None,
        kernels=kernels,
        attention_tiling=tiling, tick_counts=tick_counts,
        starts_saved=starts_saved, refuses=refuses)
    return startup, decoder


def build_translate_generator(src_vocab, tgt_vocab, max_src_len,
                              max_tgt_len, d_model=256, n_heads=4,
                              n_layers=2, d_inner=None, bos_id=0,
                              eos_id=1):
    """Greedy translation decode for the encoder-decoder transformer,
    on-device (same single-jit fori_loop design as build_lm_generator:
    the full fixed-width decoder re-runs per step; the causal mask makes
    positions past the cursor inert).  The book seq2seq's host-side
    beam_search ops remain the LoD-era path; this is the static-shape
    transformer counterpart.

    Returns (startup_program, translate) where
      translate(states, src_ids [B, max_src_len], num_steps) ->
          tgt ids [B, max_tgt_len] starting with bos_id; positions after
          an emitted eos_id keep repeating eos_id.
    """
    import jax
    import jax.numpy as jnp

    from ..core.framework import Program, program_guard
    from ..core.executor import program_to_fn

    main, startup = Program(), Program()
    with program_guard(main, startup):
        src = layers.data(name="gen_src", shape=[max_src_len],
                          dtype="int64")
        tgt = layers.data(name="gen_tgt", shape=[max_tgt_len],
                          dtype="int64")
        probs = transformer_translate(
            src, tgt, src_vocab, tgt_vocab, d_model=d_model,
            n_heads=n_heads, n_layers=n_layers, d_inner=d_inner,
            max_len=max(max_src_len, max_tgt_len), is_test=True)
    fn = program_to_fn(main, ["gen_src", "gen_tgt"], [probs.name])

    import functools

    @functools.partial(jax.jit, static_argnames=("num_steps",))
    def _run(src_ids, tgt0, g, num_steps):
        def body(i, tgt):
            fetches, _ = fn({"gen_src": src_ids, "gen_tgt": tgt}, g,
                            jax.random.key(0))
            pr = fetches[probs.name]              # [B, T, V]
            step_p = jax.lax.dynamic_slice_in_dim(
                pr, i - 1, 1, axis=1)[:, 0]
            nxt = jnp.argmax(step_p, axis=-1).astype(jnp.int32)
            # once a row emitted eos, keep emitting eos
            prev = jax.lax.dynamic_slice_in_dim(
                tgt, i - 1, 1, axis=1)[:, 0]
            nxt = jnp.where(prev == eos_id, eos_id, nxt)
            return jax.lax.dynamic_update_slice(
                tgt, nxt[:, None], (0, i))

        return jax.lax.fori_loop(1, 1 + num_steps, body, tgt0)

    def translate(states, src_ids, num_steps):
        src_ids = jnp.asarray(src_ids, jnp.int32)
        b = src_ids.shape[0]
        assert num_steps < max_tgt_len
        tgt0 = jnp.full((b, max_tgt_len), eos_id, jnp.int32)
        tgt0 = tgt0.at[:, 0].set(bos_id)
        g = {n: jnp.asarray(v) for n, v in states.items()}
        return _run(src_ids, tgt0, g, int(num_steps))

    translate.state_names = list(fn.state_in_names)
    return startup, translate


def build_lm_beam_search(vocab_size, max_len, beam_size=4, d_model=256,
                         n_heads=4, n_layers=2, d_inner=None):
    """Static-shape beam search for the decoder-only LM, on-device.

    The LoD-era path (reference beam_search/beam_search_decode ops, kept
    for the book seq2seq) prunes hypotheses host-side with dynamic
    shapes; on TPU the beam is a fixed [B, K] lane structure folded into
    the batch: each step scores all K beams in one fixed-width forward
    (B*K rows), takes top-K over the K*V continuation scores, and
    gathers the winning prefixes — all inside one jit.

    Returns (startup_program, search) where
      search(states, prompt_ids [B, P], num_steps) ->
          (ids [B, K, max_len], scores [B, K]) sorted best-first;
    scores are sum log p.  (No EOS handling: all beams share one length,
    so GNMT-style length normalization would be a constant rescale —
    deliberately not offered as a knob.)
    """
    import functools

    import jax
    import jax.numpy as jnp

    from ..core.framework import Program, program_guard
    from ..core.executor import program_to_fn

    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids_in = layers.data(name="gen_ids", shape=[max_len],
                             dtype="int64")
        probs = transformer_lm(ids_in, vocab_size, d_model=d_model,
                               n_heads=n_heads, n_layers=n_layers,
                               d_inner=d_inner, max_len=max_len,
                               is_test=True)
    fn = program_to_fn(main, ["gen_ids"], [probs.name])
    K = int(beam_size)

    @functools.partial(jax.jit, static_argnames=("p", "num_steps"))
    def _run(ids0, states, p, num_steps):
        b = ids0.shape[0]

        def body(i, carry):
            ids, scores = carry            # [B, K, L], [B, K]
            flat = ids.reshape(b * K, max_len)
            fetches, _ = fn({"gen_ids": flat}, states,
                            jax.random.key(0))
            pr = fetches[probs.name]       # [B*K, L, V]
            step_p = jax.lax.dynamic_slice_in_dim(
                pr, i - 1, 1, axis=1)[:, 0].reshape(b, K, vocab_size)
            logp = jnp.log(step_p + 1e-9)
            # at the first expansion only beam 0 is a real hypothesis
            first = (i == p)
            beam_mask = jnp.where(
                first,
                jnp.concatenate([jnp.zeros((1,)),
                                 jnp.full((K - 1,), -jnp.inf)])[None, :],
                jnp.zeros((1, K)))
            cand = scores[:, :, None] + logp + beam_mask[:, :, None]
            flat_cand = cand.reshape(b, K * vocab_size)
            top_scores, top_idx = jax.lax.top_k(flat_cand, K)   # [B, K]
            src_beam = top_idx // vocab_size
            tok = (top_idx % vocab_size).astype(jnp.int32)
            ids = jnp.take_along_axis(
                ids, src_beam[:, :, None], axis=1)              # regather
            ids = jax.lax.dynamic_update_slice(
                ids, tok[:, :, None], (0, 0, i))
            return ids, top_scores

        ids0 = jnp.broadcast_to(ids0[:, None, :],
                                (b, K, max_len)).copy()
        scores0 = jnp.zeros((b, K))
        ids, scores = jax.lax.fori_loop(p, p + num_steps, body,
                                        (ids0, scores0))
        return ids, scores

    def search(states, prompt_ids, num_steps):
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        b, p = prompt_ids.shape
        assert p + num_steps <= max_len
        ids0 = jnp.zeros((b, max_len), jnp.int32)
        ids0 = jax.lax.dynamic_update_slice(ids0, prompt_ids, (0, 0))
        g = {n: jnp.asarray(v) for n, v in states.items()}
        return _run(ids0, g, p, int(num_steps))

    search.state_names = list(fn.state_in_names)
    return startup, search
