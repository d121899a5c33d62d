"""The streaming paged-attention decode kernel and its selection
(paddle_tpu/kernels/paged_attention.py).

Pins two contracts:

  * the kernel is an IMPLEMENTATION swap, never a semantics change: at
    the four serving cells' geometries (rehearsal sizes), bf16 and fp32
    pools, the resident step's logits through the kernel (the Pallas
    TPU interpreter on the CPU: async copies, semaphores and all) equal
    the XLA gather path's to a stated float32 tolerance at the cursors
    that break such kernels, it never reads a page past a slot's
    cursor, the K and V it writes for this position are bit-equal to
    a scatter's, greedy and sampled streams agree, and speculative
    verify (`step_window`) keeps the gather path beside it;
  * which of the two runs is a function of the pool's geometry, its
    dtype and the platform the decoder is built for, and of nothing
    else: a refused pool returns None with its reason,
    `decoder.kernels` and `GenerationServer.stats()` carry it, and the
    benchmark's own geometries select what PERF.md section 7 says they
    do.
"""
import contextlib
import functools
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.core.framework as fw
from paddle_tpu.kernels import paged_attention
from paddle_tpu.serving import GenerationServer

V = 29

_DECODERS = {}


@contextlib.contextmanager
def _interpreted(chunk_bytes=None, tile_rows=None):
    """Inside this context `build_lm_paged_decoder`'s one call of
    `select_paged_attention` asks for the Pallas interpreter: the entry
    point's own argument for tests.  `chunk_bytes` makes a chunk that
    small (toy pages are a few hundred bytes: a chunk the module's own
    size holds a whole table), so a slot's pages come in several, and
    `tile_rows` a row tile that short, so a chunk comes in several."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "select_paged_attention",
                   functools.partial(
                       paged_attention.select_paged_attention,
                       interpret=True))
        if chunk_bytes is not None:
            mp.setattr(paged_attention, "_CHUNK_BYTES", chunk_bytes)
        if tile_rows is not None:
            mp.setattr(paged_attention, "_TILE_ROWS", tile_rows)
        yield


def _decoder(kv_dtype=None, interpret=False, block_size=4, max_blocks=4,
             d_model=32, n_heads=2, n_layers=2):
    """Build (or reuse) a paged decoder on the CPU, `interpret` under
    `_interpreted()`.  Every variant of one geometry shares the SAME
    parameter values (the fp32 XLA entry is built first: reset unique
    names make the param set reproducible across builds), so a
    comparison swaps the attention path, never the model."""
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    geo = (block_size, max_blocks, d_model, n_heads, n_layers)
    key = (kv_dtype, interpret) + geo
    base = (None, False) + geo
    if key not in _DECODERS:
        if key != base and base not in _DECODERS:
            _decoder(block_size=block_size, max_blocks=max_blocks,
                     d_model=d_model, n_heads=n_heads,
                     n_layers=n_layers)
        # a page a chunk: a sequence's pages come in several
        with (_interpreted(chunk_bytes=1) if interpret
              else contextlib.nullcontext()):
            fw.reset_unique_names()
            startup, dec = build_lm_paged_decoder(
                V, block_size, max_blocks, d_model=d_model,
                n_heads=n_heads, n_layers=n_layers, kv_dtype=kv_dtype,
                platform="cpu")
        if key != base:
            states = _DECODERS[base][1]
        else:
            scope = fluid.Scope()
            fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
            states = {n: np.asarray(scope.find_var(n))
                      for n in dec.state_names}
        _DECODERS[key] = (dec, states)
    return _DECODERS[key]


def _serve(dec, states, prompts, max_news, **kw):
    """The PR 8 staggered mixed-length harness: first wave mid-decode
    when the second arrives, early finishers evicted under load."""
    srv = GenerationServer(dec, states, slots=3, kv_blocks=12,
                           place=fluid.CPUPlace(), **kw)
    try:
        first = [srv.submit(p, m)
                 for p, m in zip(prompts[:3], max_news[:3])]
        while srv.stats()["generated_tokens"] == 0:
            time.sleep(0.002)
        rest = [srv.submit(p, m)
                for p, m in zip(prompts[3:], max_news[3:])]
        out = [s.result(timeout=120) for s in first + rest]
        stats = srv.stats()
    finally:
        srv.close()
    return out, stats


# ---------------------------------------------------------------------------
# the kernel against `_attention` on the gather path
# ---------------------------------------------------------------------------

# The four serving cells' attention geometries at rehearsal sizes: pages
# of 16 positions as the cells have them, a table of 8 (a context of
# 128) that the kernel copies in chunks of 4 pages and multiplies over
# the chunk's first 2 (`TILE` rows) or all 4, and where the block has
# sliding layers a ring of 2 (a window of 32: one chunk of one row
# window, as K-EXAONE's ring is).
BS, NB, WINDOW = 16, 8, 32
CHUNK_PAGES, TILE = 4, 32


def _geometry(name):
    from paddle_tpu.models import lm_block

    moe = dict(norm="rms_norm", ffn="moe_swiglu", bias=False,
               n_experts=4, experts_per_token=2, norm_topk_prob=True)
    return {
        # opt-1.3b: plain multi-head attention, heads of 64
        "heads-of-64": dict(d_model=128, n_heads=2, n_layers=2),
        # olmoe-1b-7b: multi-head attention of 128, RoPE, QK-norm
        "mha-of-128": dict(
            d_model=256, n_heads=2, n_layers=2, d_inner=32,
            block=lm_block.olmoe(n_experts=4, experts_per_token=2)),
        # mellum2: query heads 8 to a K/V head, wider together than the
        # model, sliding layers on a ring 3:1
        "grouped-8-with-a-ring": dict(
            d_model=48, n_heads=16, n_layers=4, d_inner=16,
            block=lm_block.BlockSpec(
                name="mellum", positions="rope", n_kv_heads=2, d_head=8,
                layer_types=[lm_block.SLIDING] * 3 + [lm_block.FULL],
                window=WINDOW, rope_parameters={
                    k: {"rope_type": "default", "rope_theta": 500.0}
                    for k in (lm_block.SLIDING, lm_block.FULL)}, **moe)),
        # granite-4.0-h: query heads 4 to a K/V head, no position signal,
        # scores times `attention_multiplier`, one attention layer among
        # Mamba layers
        "grouped-4-with-a-scale": dict(
            d_model=32, n_heads=8, n_layers=2, d_inner=16,
            block=lm_block.BlockSpec(
                name="granitemoehybrid", positions="none", n_kv_heads=2,
                layer_types=[lm_block.MAMBA, lm_block.ATTENTION],
                attention_multiplier=0.2, ssm_heads=4, ssm_d_head=16,
                ssm_d_state=8, ssm_conv=4, **moe)),
    }[name]


# what a slot's cursor does to such a kernel: the first row of all, the
# last row of a page and the first of the next, the last row of a row
# tile (which is the ring's last before its first wrap) and the first
# of the next tile (the first after the wrap), the last row of a chunk
# and a row in the second chunk's first page, the table's last row
CURSORS = [0, 15, 16, TILE - 1, TILE, CHUNK_PAGES * BS - 1,
           CHUNK_PAGES * BS + 5, BS * NB - 1]
assert (WINDOW, CURSORS[3], CURSORS[4]) == (TILE, WINDOW - 1, WINDOW)
# and beside them a slot with no sequence (None), and one in mid-page
SLOTS = CURSORS + [None, 20]


def _map_kv(dec, pool, fn):
    """`fn(array, is_ring)` over the K/V arrays of a pool as `step`
    takes it (Mamba layers' states ride beside them untouched)."""
    if dec.state_layers:
        return fn(pool[0], False), pool[1]
    if dec.window_blocks_per_seq:
        return fn(pool[0], False), fn(pool[1], True)
    return fn(pool, False)


def _kernel_against_gather(name, kv_dtype):
    """(logits through the kernel, through the gather path, over the
    slots with a sequence) of one resident step at `SLOTS`' cursors,
    over random pools.  Every table entry past a cursor's page, and
    every ring block a cursor has not reached, is a STALE id: a block
    that the pools given to the kernel hold NaN in (given to the gather
    path, which reads it under a weight of zero, it would be NaN too:
    those pools hold zeros there)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.transformer import build_lm_paged_decoder

    geo = _geometry(name)
    s_n = len(SLOTS)

    def build(interpreted):
        with interpreted:
            fw.reset_unique_names()
            return build_lm_paged_decoder(
                V, BS, NB, kv_dtype=kv_dtype, platform="cpu", **geo)[1]

    dec_x = build(contextlib.nullcontext())
    # a chunk of 4 pages (a block's K and V over a layer are 2 pages'
    # bytes of K) with row windows of 2 pages and of 4: a table comes in
    # two chunks, a ring in one of one window
    dec_p = build(_interpreted(
        CHUNK_PAGES * dec_x.bytes_per_block // (2 * dec_x.table_layers),
        TILE))
    assert dec_p.kernels["paged_attention_decode"] == "pallas"
    assert dec_p.attention_tiling == (
        (CHUNK_PAGES, TILE // BS),
        (WINDOW // BS, TILE // BS) if dec_x.window_blocks_per_seq
        else None)
    assert dec_x.attention_tiling is None
    assert dec_x.kernels["paged_attention_decode"] == "xla:not_tpu"
    r = np.random.RandomState(7)
    g = {n: jnp.asarray((1.0 if "scale" in n or "layer_norm" in n else 0.0)
                        + r.normal(0, 0.1, shape).astype(np.float32))
         for n, shape in sorted(dec_x.state_shapes.items())}

    ring = dec_x.window_blocks_per_seq
    clean = 1 + s_n * NB                  # table blocks 1 .. s_n * NB
    stale = np.arange(clean, clean + NB)  # and NB no sequence owns
    tables = np.zeros((s_n, NB), np.int32)
    positions = np.zeros(s_n, np.int32)
    active = np.zeros(s_n, bool)
    stale_ring = []
    rings = dec_x.slot_rings(s_n) if ring else None
    for s, cur in enumerate(SLOTS):
        tables[s] = 1 + s * NB + np.arange(NB)
        reach = 1 if cur is None else cur // BS + 1
        tables[s, reach:] = stale[:NB - reach]
        if ring:
            stale_ring += list(rings[s, min(reach, ring):])
        if cur is not None:
            positions[s], active[s] = cur, True

    pools = dec_x.init_pool(clean + len(stale), lanes=s_n,
                            window_blocks=1 + s_n * ring)
    keys = iter(jax.random.split(jax.random.key(3), 8))

    def random(x, is_ring):
        return jax.random.normal(next(keys), x.shape, jnp.float32
                                 ).astype(x.dtype)

    def poisoned(x, is_ring):
        return x.at[:, np.asarray(stale_ring if is_ring else stale,
                                  np.int32)].set(jnp.nan)

    def zeroed(x, is_ring):
        return x.at[:, np.asarray(stale_ring if is_ring else stale,
                                  np.int32)].set(0.0)

    pools = [_map_kv(dec_x, p, random) for p in pools]
    step_tables = (tables, rings) if ring else tables
    args = (step_tables, positions, r.randint(0, V, s_n).astype(np.int32),
            np.zeros(s_n, np.uint32), np.zeros(s_n, np.float32), active)
    got = dec_p.step_logits(
        g, *(_map_kv(dec_p, p, poisoned) for p in pools), *args)
    want = dec_x.step_logits(
        g, *(_map_kv(dec_x, p, zeroed) for p in pools), *args)
    return np.asarray(got)[active], np.asarray(want)[active]


# float32 pools: the same float32 products, summed a chunk at a time
# under a running maximum instead of all at once: measured 2e-7 to
# 1.5e-6 of the largest logit over the four geometries
TOL_FP32 = 2e-5
# bf16 pools: the kernel hands the MXU the query and the softmax
# weights in the pool's dtype (what a TPU's default precision makes of
# them on the gather path too; the CPU's gather path keeps them
# float32): 8 bits of mantissa on each, measured 1e-3 to 4e-3
TOL_BF16 = 2e-2


@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("name", ["heads-of-64", "mha-of-128",
                                  "grouped-8-with-a-ring",
                                  "grouped-4-with-a-scale"])
def test_kernel_equals_the_gather_path_and_reads_no_stale_page(
        name, kv_dtype):
    """One resident step at the cursors that break such kernels, a
    slot with no sequence beside them, every page past a cursor
    poisoned: the logits are finite (no stale page was read) and the
    gather path's to the stated tolerance."""
    got, want = _kernel_against_gather(name, kv_dtype)
    assert got.shape == (len(SLOTS) - 1, V)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err <= (TOL_FP32 if kv_dtype == "fp32" else TOL_BF16), err


@pytest.mark.parametrize("dtype,h,dh,n_kv,bs,nb", [
    ("float32", 4, 8, 4, 16, 8), ("bfloat16", 4, 8, 4, 16, 8),
    ("bfloat16", 4, 16, 2, 16, 8), ("float32", 2, 16, 2, 4, 8),
], ids=["fp32-mha", "bf16-mha", "bf16-grouped", "fp32-pages-of-4"])
def test_kernel_writes_the_row_a_scatter_would(dtype, h, dh, n_kv, bs, nb):
    """`write=`: the kernel's result and its pools are BIT-equal to a
    scatter of this position's K and V followed by the kernel, at rows
    on a page's, a sublane tile's and a chunk's edges, layer after
    layer over the pools it returned; a slot with a negative row
    writes nothing and nothing else of the pools moves (the scatter
    path puts such a slot's row into the null block 0)."""
    import jax.numpy as jnp

    r = np.random.RandomState(0)
    dtype, d_kv, layers = jnp.dtype(dtype), n_kv * dh, 2
    pos = np.array([0, bs - 1, bs, 4 * bs - 1, 4 * bs + 5, nb * bs - 1, 7])
    active = np.array([1, 1, 1, 0, 1, 1, 1], bool)
    s_n = len(pos)
    pool_k, pool_v = (jnp.asarray(
        r.randn(layers, 1 + s_n * nb, bs, d_kv), dtype) for _ in "kv")
    tables = 1 + np.arange(s_n * nb, dtype=np.int32).reshape(s_n, nb)
    lengths = jnp.asarray(np.where(active, pos + 1, 1), jnp.int32)
    q, k_new, v_new = (jnp.asarray(r.randn(s_n, w), jnp.float32)
                       for w in (h * dh, d_kv, d_kv))
    attend = functools.partial(
        paged_attention.paged_attention, scale=0.3, pages=4, tile=2,
        n_heads=h, d_head=dh, interpret=True)
    lane = np.arange(s_n)
    wb = np.where(active, tables[lane, pos // bs], 0)
    wi = np.where(active, pos % bs, 0)
    want_k, want_v = pool_k, pool_v
    for layer in range(layers):
        want_k = want_k.at[layer, wb, wi].set(k_new.astype(dtype))
        want_v = want_v.at[layer, wb, wi].set(v_new.astype(dtype))
        want = attend(q, want_k, want_v, tables, lengths, layer)
        got, pool_k, pool_v = attend(
            q, pool_k, pool_v, tables, lengths, layer,
            write=(k_new, v_new, np.where(active, pos, -1)))
        np.testing.assert_array_equal(np.asarray(got)[active],
                                      np.asarray(want)[active])
    for got, want in ((pool_k, want_k), (pool_v, want_v)):
        np.testing.assert_array_equal(
            np.asarray(got[:, 1:], np.float32),
            np.asarray(want[:, 1:], np.float32))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
def test_latent_kernel_equals_plain_attention_over_the_row(dtype, tol):
    """The latent form (`pool_v` None, `d_value` columns) in the
    interpreter against plain `jax.numpy`: ONE pool whose row (40
    columns used of the 128 stored: the pad is zeros in row and query)
    every one of 8 heads reads whole, the value its first 32 columns;
    lengths that end inside a page, on a page's and a chunk's edge and
    past several chunks; this position's row written by the kernel, a
    negative row writing nothing; layer after layer over the pool it
    returned."""
    import jax
    import jax.numpy as jnp

    r = np.random.RandomState(1)
    dtype = jnp.dtype(dtype)
    h, used, width, d_v, bs, nb, layers = 8, 40, 128, 32, 4, 12, 2
    pos = np.array([0, 2, bs - 1, bs, 4 * bs - 1, 4 * bs, 9 * bs + 1,
                    nb * bs - 1, 5])
    active = np.array([1, 1, 1, 1, 1, 1, 1, 1, 0], bool)
    s_n = len(pos)

    def padded(rows):
        return jnp.asarray(np.concatenate(
            [rows, np.zeros(rows.shape[:-1] + (width - used,))], -1))

    pool = padded(r.randn(layers, 1 + s_n * nb, bs, used)).astype(dtype)
    tables = 1 + np.arange(s_n * nb, dtype=np.int32).reshape(s_n, nb)
    lengths = np.where(active, pos + 1, 1)
    q = padded(r.randn(s_n, h, used)).astype(jnp.float32)
    new = padded(r.randn(s_n, used)).astype(jnp.float32)
    attend = functools.partial(
        paged_attention.paged_attention, scale=0.3, pages=4, tile=2,
        n_heads=h, d_head=0, d_value=d_v, interpret=True)
    lane = np.arange(s_n)
    wb = np.where(active, tables[lane, pos // bs], 0)
    wi = np.where(active, pos % bs, 0)
    want_pool = pool
    for layer in range(layers):
        want_pool = want_pool.at[layer, wb, wi].set(new.astype(dtype))
        got, pool = attend(
            q.reshape(s_n, h * width), pool, None, tables,
            jnp.asarray(lengths, jnp.int32), layer,
            write=(new, None, np.where(active, pos, -1)))
        assert got.shape == (s_n, h * d_v) and got.dtype == jnp.float32
        for s in np.flatnonzero(active):
            rows = want_pool[layer, tables[s]].reshape(nb * bs, width)[
                :lengths[s]].astype(jnp.float32)
            sc = q[s].astype(dtype).astype(jnp.float32) @ rows.T * 0.3
            want = jax.nn.softmax(sc, -1) @ rows[:, :d_v]
            np.testing.assert_allclose(
                np.asarray(got[s]).reshape(h, d_v), np.asarray(want),
                atol=tol * float(np.abs(want).max()))
    np.testing.assert_array_equal(np.asarray(pool[:, 1:], np.float32),
                                  np.asarray(want_pool[:, 1:], np.float32))


# A chunk's DMA bookkeeping (PR 46).  name: query heads, head width, K/V
# heads, value columns (0: a K and a V pool), pages a chunk, pool dtype.
# Chunks of 5 and of 3 pages are not powers of two (DeepSeek's are 51);
# the grouped one is a ring's power of two.
_DMA_GEOMETRIES = {
    "kv-plain": (4, 8, 4, 0, 5, "float32"),
    "kv-grouped": (4, 16, 2, 0, 4, "bfloat16"),
    "latent": (8, 128, 1, 32, 3, "float32"),
}
# The issue loop's groups (PR 56): chunks of 11 pages, so a chunk is a
# group of `_ISSUE_UNROLL` (8) table entries and three pages that go
# one by one, over a K and a V pool and over a latent one.
_DMA_GEOMETRIES.update({
    "kv-runs": (4, 8, 4, 0, 11, "float32"),
    "latent-runs": (8, 128, 1, 32, 11, "float32"),
})
# pages a slot holds, by name; None: a lane with no sequence
_DMA_LENGTHS = {
    "1": lambda pages: 1, "2": lambda pages: 2, "3": lambda pages: 3,
    "chunk-1": lambda pages: pages - 1, "chunk": lambda pages: pages,
    "chunk+1": lambda pages: pages + 1,
    "2chunks+3": lambda pages: 2 * pages + 3, "idle": lambda pages: None,
}


def _dma_case(geometry, lanes_pages, write, interpret=True, tables=None,
              select=False, behind=None, nb=None, write_at=None):
    """The kernel over lanes of `lanes_pages` pages (None: a lane with
    no sequence) against plain `jax.numpy` after a scatter of the
    written row -> (got, want, pools got, pools wanted, active).
    `tables` [lanes, 2 * pages + 3]: the lanes' block ids (the lanes'
    own blocks shuffled without).  `select`: a latent pool's row mask,
    a third of the rows and a lane's first.  `behind`: a permutation of
    the pool's block ids; the kernel is then given the SAME pages
    behind another table (block b's page in block `behind[b]`, the
    table renamed), and the pools it returns are read back through
    it.  `nb`: the table's pages (two chunks and three).  `write_at`:
    the cursor's row -> the row the lane writes, under its length (the
    cursor's own: a table's; a ring writes anywhere under it)."""
    import jax
    import jax.numpy as jnp

    h, dh, n_kv, d_value, pages, dtype = _DMA_GEOMETRIES[geometry]
    bs, nb = 4, nb or 2 * pages + 3
    row, dtype = n_kv * dh, jnp.dtype(dtype)
    r = np.random.RandomState(7)
    s_n = len(lanes_pages)
    active = np.array([n is not None for n in lanes_pages])
    # the cursor ends inside its last page, but for a lane that fills
    # its pages to the last row
    pos = np.array([(n or 1) * bs - 1 - (i % bs if (n or 1) > 1 else 0)
                    for i, n in enumerate(lanes_pages)])
    lengths = np.where(active, pos + 1, 1)
    wrow = pos if write_at is None else np.array([write_at(p) for p in pos])
    pools = [jnp.asarray(r.randn(2, 1 + s_n * nb, bs, row), dtype)
             for _ in range(1 if d_value else 2)]
    # a table names any block: the lanes' blocks shuffled
    shuffled = 1 + r.permutation(s_n * nb).astype(np.int32).reshape(s_n, nb)
    tables = shuffled if tables is None else np.asarray(tables, np.int32)
    q = jnp.asarray(r.randn(s_n, h * (row if d_value else dh)), jnp.float32)
    news = [jnp.asarray(r.randn(s_n, row), jnp.float32) for _ in pools]
    mask = None
    if select:
        mask = r.rand(s_n, nb * bs) < 1 / 3
        mask[:, 0] = True
    wanted = list(pools)
    if write:
        lane = np.arange(s_n)
        wb = np.where(active, tables[lane, wrow // bs], 0)
        wanted = [pool.at[1, wb, wrow % bs].set(new.astype(dtype))
                  for pool, new in zip(pools, news)]
    given, names = pools, tables
    if behind is not None:
        # block b's page now lies in block behind[b]
        given = [pool[:, np.argsort(behind)] for pool in pools]
        names = np.asarray(behind, np.int32)[tables]
    out = paged_attention.paged_attention(
        q, given[0], None if d_value else given[1], names,
        jnp.asarray(lengths, jnp.int32), 1, scale=0.3, pages=pages,
        tile=2, n_heads=h, d_head=dh, d_value=d_value,
        interpret=interpret,
        write=((news[0], None if d_value else news[1],
                np.where(active, wrow, -1)) if write else None),
        select=None if mask is None else jnp.asarray(mask))
    # (the interpreter's callbacks read arrays on a thread of their
    # own: an op dispatched beside a running kernel can deadlock it)
    out = jax.block_until_ready(out)
    got, got_pools = (out[0], out[1:]) if write else (out, given)
    if behind is not None:
        got_pools = [pool[:, behind] for pool in got_pools]
    want = []
    for lane in range(s_n):
        rows = [pool[1, tables[lane]].reshape(nb * bs, row)[
            :lengths[lane]].astype(jnp.float32) for pool in wanted]
        keys, values = rows[0], rows[-1][:, :d_value or row]
        ql = q[lane].astype(dtype).astype(jnp.float32)
        if d_value:
            sc = ql.reshape(h, row) @ keys.T * 0.3
            if select:
                sc = jnp.where(mask[lane, :lengths[lane]], sc, -jnp.inf)
            want.append((jax.nn.softmax(sc, -1) @ values).reshape(-1))
            continue
        ql = ql.reshape(n_kv, h // n_kv, dh)
        sc = jnp.einsum("gid,tgd->git", ql, keys.reshape(-1, n_kv, dh))
        want.append(jnp.einsum(
            "git,tgd->gid", jax.nn.softmax(sc * 0.3, -1),
            values.reshape(-1, n_kv, dh)).reshape(-1))
    return (np.asarray(got), np.asarray(jnp.stack(want)),
            got_pools, wanted, active)


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize("length", sorted(_DMA_LENGTHS))
@pytest.mark.parametrize("geometry", sorted(_DMA_GEOMETRIES))
def test_a_chunk_is_waited_for_on_its_summed_bytes(geometry, length, write):
    """Every bit pattern of the pages copied into a chunk (one wait a
    set bit, on 2^b pages' bytes) between two lanes of other lengths,
    whose first chunks the lane before starts: results and pools equal
    plain attention after a scatter, at a K and a V pool under plain
    and grouped heads and at a latent pool."""
    pages = _DMA_GEOMETRIES[geometry][4]
    got, want, pools, wanted, active = _dma_case(
        geometry, [pages + 2, _DMA_LENGTHS[length](pages), 1], write)
    tol = 2e-2 if _DMA_GEOMETRIES[geometry][5] == "bfloat16" else 2e-6
    np.testing.assert_allclose(got[active], want[active],
                               atol=tol * float(np.abs(want).max()))
    # (the scatter puts a lane that writes nothing into the null block)
    for pool, same in zip(pools, wanted):
        np.testing.assert_array_equal(np.asarray(pool[:, 1:], np.float32),
                                      np.asarray(same[:, 1:], np.float32))


# The cut (PR 66).  cap, row tile, issue group: the cells' caps (64
# pages of a latent row, 80 of agent96's 16 KB, 40 of 32 KB, 16 of 64
# KB, dots3's ring of 33, the indexer's 512 under groups of 16) and the
# tests' own, whole groups or not
_CUTS = [(64, 8, 8), (80, 8, 8), (40, 8, 8), (16, 8, 8), (33, 8, 8),
         (512, 8, 16), (51, 8, 8), (24, 2, 8), (11, 2, 8), (5, 2, 8),
         (3, 1, 8)]


@pytest.mark.parametrize("cap,tile,unroll", _CUTS)
def test_a_lane_is_cut_once_in_chunks_of_one_stride(cap, tile, unroll):
    """`chunk_cut` over lanes of 1 to 4 caps + 1 pages, integers, numpy
    and jax arrays alike: the chunks cover `[0, n_pages)` once; a lane
    the cap holds is ONE chunk; a longer one is `ceil(n / the cap's
    whole groups)` chunks (`ceil(n / cap)` at a cap of whole groups:
    every cell's), every one but its last a whole number of groups or
    the cap itself, none over the cap, the last no longer than the
    others and short of them by less than a group a chunk; the rule
    walked by hand (`_brute_cut`) says the same, and every stride is
    one of the row windows."""
    import jax.numpy as jnp

    group = paged_attention.cut_group(cap, tile, unroll)
    assert group == _brute_group(cap, tile, unroll)
    assert group % min(unroll, cap) == 0 and group <= cap
    windows = paged_attention._windows(cap, tile, unroll)
    assert list(windows) == _brute_windows(cap, tile, unroll)
    n_pages = np.arange(1, 4 * cap + 2)
    strides, counts = paged_attention.chunk_cut(n_pages, cap, group)
    for xp_cut in (paged_attention.chunk_cut(jnp.asarray(n_pages), cap,
                                             group),
                   zip(*(paged_attention.chunk_cut(int(n), cap, group)
                         for n in n_pages))):
        for got, want in zip(xp_cut, (strides, counts)):
            np.testing.assert_array_equal(np.asarray(got), want)
    whole = cap // group * group
    for n, stride, count in zip(n_pages, strides, counts):
        chunks = [min(stride, n - c * stride) for c in range(count)]
        assert chunks == _brute_cut(int(n), cap, tile, unroll)
        assert sum(chunks) == n and min(chunks) > 0 and max(chunks) <= cap
        assert count == (1 if n <= cap else -(-n // whole))
        if cap % group == 0:
            assert count == -(-n // cap)
        assert all(c % group == 0 or c == cap for c in chunks[:-1])
        assert len(set(chunks[:-1])) <= 1 and chunks[-1] <= chunks[0]
        assert count == 1 or chunks[0] - chunks[-1] < group * count
        assert count == 1 or stride in windows


# lanes of a page, one short of a stride, a stride, one over, two
# strides and one, a ring's 33, under a cap of 24 pages whose strides
# are 16 and 24: 25 are 16 + 9, 33 are 24 + 9, 49 are 24 + 24 + 1;
# and two whole strides, 16 + 16 and 24 + 24
_CUT_LANES = [1, 23, 24, 25, 49, 33, 32, 48]
_DMA_GEOMETRIES["latent-strides"] = (8, 128, 1, 32, 24, "float32")
_DMA_GEOMETRIES["kv-strides"] = (4, 8, 4, 0, 24, "float32")
_WRITTEN = {"first": lambda pos: min(pos, 5),
            "middle": lambda pos: pos // 2, "last": lambda pos: pos}


@pytest.mark.parametrize("written", sorted(_WRITTEN))
@pytest.mark.parametrize("pool", ["kv", "latent", "latent-select"])
def test_equal_chunks_equal_plain_attention(pool, written):
    """Lanes cut 1, 23, 24, 16 + 9, 24 + 24 + 1, 24 + 9, 16 + 16 and
    24 + 24 in ONE call, each started by the lane before it: results
    and pools equal plain attention after a scatter, over a K and a V
    pool and over a latent one, with a selection and without, the row
    written in a lane's first chunk, in a middle one (the 49-page
    lane's second) and in its last."""
    geometry = "kv-strides" if pool == "kv" else "latent-strides"
    assert [_brute_cut(n, 24, 2) for n in (25, 49, 33, 32)] == [
        [16, 9], [24, 24, 1], [24, 9], [16, 16]]
    got, want, pools, wanted, active = _dma_case(
        geometry, _CUT_LANES, True, select=pool.endswith("select"),
        write_at=_WRITTEN[written])
    np.testing.assert_allclose(got, want,
                               atol=2e-6 * float(np.abs(want).max()))
    for pool_got, same in zip(pools, wanted):
        np.testing.assert_array_equal(np.asarray(pool_got[:, 1:]),
                                      np.asarray(same[:, 1:]))


@pytest.mark.parametrize("select", [False, True], ids=["plain", "select"])
def test_a_ring_of_33_pages_is_one_chunk(select):
    """dots3's ring: 33 pages under a buffer of 33 (one chunk a lane:
    four groups of 8 and a page), full lanes between shorter ones, the
    row written anywhere in the ring: results and pools equal plain
    attention after a scatter, under the window's row mask and
    without."""
    _DMA_GEOMETRIES["ring-33"] = (8, 128, 1, 32, 33, "float32")
    try:
        assert paged_attention.chunk_cut(33, 33, 8) == (33, 1)
        got, want, pools, wanted, active = _dma_case(
            "ring-33", [33, 33, 7, 33, 1, 32], True, nb=33, select=select,
            write_at=lambda pos: (pos * 7) % (pos + 1))
    finally:
        del _DMA_GEOMETRIES["ring-33"]
    np.testing.assert_allclose(got, want,
                               atol=2e-6 * float(np.abs(want).max()))
    for pool_got, same in zip(pools, wanted):
        np.testing.assert_array_equal(np.asarray(pool_got[:, 1:]),
                                      np.asarray(same[:, 1:]))


# What a table names, [lanes, table pages] int32 over the lanes' own
# blocks (lane s's: 1 + s * nb on), by name.  `group`: the issue loop's.
def _ascending_tables(s_n, nb, pages, group):
    return 1 + np.arange(s_n * nb, dtype=np.int32).reshape(s_n, nb)


def _descending_tables(s_n, nb, pages, group):
    return _ascending_tables(s_n, nb, pages, group)[:, ::-1].copy()


def _shuffled_tables(s_n, nb, pages, group):
    return 1 + np.random.RandomState(11).permutation(
        s_n * nb).astype(np.int32).reshape(s_n, nb)


def _broken_inside_a_group(s_n, nb, pages, group):
    """Runs but for two neighbours swapped inside the first chunk's
    first group, at another place a lane."""
    tables = _ascending_tables(s_n, nb, pages, group)
    for lane in range(s_n):
        at = lane % (group - 1)
        tables[lane, [at, at + 1]] = tables[lane, [at + 1, at]]
    return tables


def _run_across_a_chunks_edge(s_n, nb, pages, group):
    """No run up to three pages before the first chunk's end, one run
    from there on: it starts inside a group, covers the chunk's pages
    that go one by one and goes on through the next chunk's groups."""
    tables = _ascending_tables(s_n, nb, pages, group)
    r = np.random.RandomState(12)
    for lane in range(s_n):
        head = pages - 3 - lane % 2
        tables[lane, :head] = r.permutation(tables[lane, :head])
    return tables


def _prefix_then_fresh(s_n, nb, pages, group):
    """Every lane's first `group - 3` pages are lane 0's (a prefix hit:
    one run), its own fresh run after them: the first group straddles
    the two.  (No lane's cursor is in a shared page: a lane writes
    where it alone reads.)"""
    tables = _ascending_tables(s_n, nb, pages, group)
    tables[:, :group - 3] = tables[0, :group - 3]
    return tables


def _idle_ring_of_block_0(s_n, nb, pages, group):
    """Runs, and a lane with no sequence between them: every entry of
    its table the null block."""
    tables = _ascending_tables(s_n, nb, pages, group)
    tables[1] = 0
    return tables


_RUN_TABLES = {f.__name__.strip("_"): f for f in (
    _ascending_tables, _descending_tables, _shuffled_tables,
    _broken_inside_a_group, _run_across_a_chunks_edge, _prefix_then_fresh,
    _idle_ring_of_block_0)}
# pages a lane holds: the whole table (two chunks and three pages), a
# chunk to its last page, lengths that end inside a chunk's group and
# among the pages after it
_RUN_LANES = (2 * 11 + 3, 11, 11 + 5, 11 + 9)


@pytest.mark.parametrize("pool", ["kv-write", "latent-write",
                                  "latent-select"])
@pytest.mark.parametrize("kind", sorted(_RUN_TABLES) + ["ends_inside_a_group"])
def test_a_run_of_pages_is_one_copy_and_the_same_pages(kind, pool):
    """Where the table entries an issue-loop iteration takes are
    consecutive ascending block ids the kernel starts ONE copy of the
    group, else a copy a page, and nothing else may follow from it:
    whatever a table names (runs up or down, no run, a run broken
    inside a group, one that crosses a chunk's edge, a shared prefix
    then fresh blocks, an idle lane's block 0 throughout, lengths that
    end inside a group) the result and the pools equal plain attention
    after a scatter, AND equal, bit for bit, the kernel's own result on
    the same pages behind a shuffled table (a copy a page)."""
    geometry = "kv-runs" if pool.startswith("kv") else "latent-runs"
    pages = _DMA_GEOMETRIES[geometry][4]
    group = min(paged_attention._ISSUE_UNROLL, pages)
    assert (pages, group) == (11, 8)
    lanes = list(_RUN_LANES)
    if kind == "ends_inside_a_group":
        lanes, kind = [3, group - 1, pages + 2, pages + group - 1], \
            "ascending_tables"
    if kind == "idle_ring_of_block_0":
        lanes[1] = None
    nb = 2 * pages + 3
    tables = _RUN_TABLES[kind](len(lanes), nb, pages, group)
    # the groups of these tables that are runs, by `starts_saved`: all
    # of an ascending table's, none of a descending or shuffled one's
    saved = paged_attention.starts_saved(tables, pages)[:, -1] // (group - 1)
    whole = nb // group
    assert {"ascending_tables": (saved == whole).all(),
            "descending_tables": not saved.any(),
            "shuffled_tables": not saved.any()}.get(kind, saved.any())
    # a selection as the cell has it, beside the written row
    kw = dict(write=True, tables=tables, select=pool == "latent-select")
    got, want, pools, wanted, active = _dma_case(geometry, lanes, **kw)
    np.testing.assert_allclose(got[active], want[active],
                               atol=2e-6 * float(np.abs(want).max()))
    behind = np.concatenate([[0], 1 + np.random.RandomState(13).permutation(
        len(lanes) * nb)])
    twin, _, twin_pools, _, _ = _dma_case(geometry, lanes, behind=behind,
                                          **kw)
    assert not paged_attention.starts_saved(
        behind[tables], pages)[:, -1].any()
    np.testing.assert_array_equal(got[active], twin[active])
    for pool_got, pool_twin, same in zip(pools, twin_pools, wanted):
        np.testing.assert_array_equal(np.asarray(pool_got[:, 1:]),
                                      np.asarray(same[:, 1:]))
        np.testing.assert_array_equal(np.asarray(pool_twin[:, 1:]),
                                      np.asarray(same[:, 1:]))


def _brute_group(cap, tile, unroll=None):
    """What a stride is a whole number of: the issue loop's groups in
    whole row tiles (the groups alone where the cap holds no such),
    and the fewest of those at a time of which the cap holds no more
    than eight."""
    import math

    unroll = min(unroll or paged_attention._ISSUE_UNROLL, cap)
    group = math.lcm(unroll, tile)
    if group > cap:
        return unroll
    return next(k * group for k in range(1, cap + 1)
                if cap <= 8 * k * group)


def _brute_windows(cap, tile, unroll=None):
    """A chunk's row windows, in pages: the tile doubled under the
    cap, the strides over half the cap, the cap."""
    group = _brute_group(cap, tile, unroll)
    doubled = [tile << i for i in range(cap.bit_length())
               if tile << i < cap]
    strides = [g for g in range(group, cap, group) if 2 * g > cap]
    return sorted(set(doubled + strides)) + [cap]


def _brute_cut(n_pages, cap, tile, unroll=None):
    """The pages of each chunk of a lane of `n_pages` pages under a cap
    of `cap`, by the rule and not by `chunk_cut`'s arithmetic: one
    chunk where the cap holds the lane, else the fewest chunks of the
    cap's whole groups, all of the smallest stride of whole groups
    that reaches the lane's end (`_brute_group`)."""
    group = _brute_group(cap, tile, unroll)
    if n_pages <= cap:
        return [n_pages]
    whole = max(g for g in range(group, cap + 1, group))
    count = next(c for c in range(2, n_pages + 1) if c * whole >= n_pages)
    stride = next(g for g in range(group, whole + 1, group)
                  if count * g >= n_pages)
    chunks = [stride] * (count - 1) + [n_pages - (count - 1) * stride]
    assert 0 < chunks[-1] <= stride
    return chunks


def _loops_around(jaxpr, name, depth=0):
    """The loop depths at which primitive `name` stands in `jaxpr`."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(depth)
        inside = depth + (eqn.primitive.name in ("while", "scan"))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops_around(sub, name, inside)
    return found


@pytest.mark.parametrize("geometry", sorted(_DMA_GEOMETRIES))
def test_dma_ops_count_the_starts_and_waits(geometry):
    """`dma_ops` is a start a page and, chunk by chunk of the lane's
    cut, a wait for each set bit of the pages copied; and the kernel's
    waits stand in no
    loop over pages: under the chunk loop alone, a static list as long
    as the chunk's bit length a pool (and one for the written row),
    where its starts stand in a loop of their own."""
    import jax
    import jax.numpy as jnp

    h, dh, n_kv, d_value, pages, dtype = _DMA_GEOMETRIES[geometry]
    n_pages = [1, 2, 3, pages - 1, pages, pages + 1, 2 * pages + 3,
               7 * pages + pages // 2]
    brute = [sum(copied + bin(copied).count("1")
                 for copied in _brute_cut(n, pages, 2)) for n in n_pages]
    assert [paged_attention.dma_ops(n, pages) for n in n_pages] == brute
    assert list(paged_attention.dma_ops(np.asarray(n_pages), pages)) == brute

    n_pools, row = (1 if d_value else 2), n_kv * dh
    pool = jnp.zeros((2, 9, 4, row), dtype)
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_attention.paged_attention, scale=0.3, pages=pages, tile=2,
        n_heads=h, d_head=dh, d_value=d_value))(
            jnp.zeros((2, h * (row if d_value else dh))), pool,
            None if d_value else pool, jnp.zeros((2, 4), jnp.int32),
            jnp.ones((2,), jnp.int32), 0,
            write=(jnp.zeros((2, row)),
                   None if d_value else jnp.zeros((2, row)),
                   jnp.zeros((2,), jnp.int32))).jaxpr
    waits = _loops_around(jaxpr, "dma_wait")
    assert waits == [1] * (n_pools * (pages.bit_length() + 1))
    assert max(_loops_around(jaxpr, "dma_start")) == 2


def test_a_semaphore_holds_one_chunk_at_a_time(monkeypatch, capfd):
    """The invariant the waits on summed bytes stand on: `sems[pool,
    buf]` never has more than one chunk's copies outstanding, so the
    bytes a wait takes are its chunk's own.  Lanes whose first chunks
    differ in size, each started by the lane before it, under the
    interpreter's race detector (a wait that took another chunk's bytes
    would leave pages of its own chunk not arrived: the interpreter
    runs a copy when it is waited for, so they read NaN) and its check
    that every semaphore is back at zero when the kernel ends."""
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as mosaic_interpret)
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pltpu, "InterpretParams", functools.partial(
        pltpu.InterpretParams, detect_races=True))
    paged_attention.paged_attention.clear_cache()
    try:
        got, want, pools, wanted, _ = _dma_case(
            "kv-plain", [7, 2, 13, 5, 1, 6], write=True)
    finally:
        paged_attention.paged_attention.clear_cache()
    assert not mosaic_interpret.races.races_found
    assert "non-zero count" not in capfd.readouterr().out
    np.testing.assert_allclose(got, want,
                               atol=2e-6 * float(np.abs(want).max()))
    for pool, same in zip(pools, wanted):
        np.testing.assert_array_equal(np.asarray(pool), np.asarray(same))


@pytest.mark.parametrize("kv_dtype,kernel", [
    (None, "pallas"), ("bf16", "pallas"), ("int8", "xla:kv_dtype")])
def test_greedy_decode_agrees_pallas_vs_xla(kv_dtype, kernel):
    """Greedy streams through the kernel (the TPU interpreter on the
    CPU, a page a chunk: every slot's pages come in several) under
    staggered mixed-length serving: a float32 pool gives the gather
    path's tokens, a bf16 pool nine in ten of them (the kernel rounds
    the query and the weights to the pool's dtype: `TOL_BF16`), and an
    int8 pool is refused by name and served by the gather path."""
    dec_x, states = _decoder(kv_dtype=kv_dtype)
    dec_p, _ = _decoder(kv_dtype=kv_dtype, interpret=True)
    assert dec_x.kernels["paged_attention_decode"] == "xla:not_tpu"
    assert dec_p.kernels["paged_attention_decode"] == kernel

    r = np.random.RandomState(2)
    prompts = [list(r.randint(0, V, n)) for n in (3, 6, 2, 5, 4)]
    max_news = [6, 9, 12, 4, 8]
    want, _ = _serve(dec_x, states, prompts, max_news)
    got, st = _serve(dec_p, states, prompts, max_news)
    assert st["decode_kernel"] == kernel
    assert all(len(o) == m for o, m in zip(got, max_news))
    if kv_dtype == "bf16":
        same = sum(a == b for o, w in zip(got, want) for a, b in zip(o, w))
        assert same >= 0.9 * sum(max_news)
    else:
        assert got == want


def test_spec_verify_keeps_the_gather_path_beside_the_kernel():
    """step_window (speculative verify: spec_k+1 query rows per slot in
    one dispatch) attends through the gather path whatever the resident
    step runs, `decoder.kernels` says so, and accepted streams stay
    those of the plain XLA server."""
    dec_x, states = _decoder()
    dec_p, _ = _decoder(interpret=True)
    draft, dstates = _decoder(d_model=16, n_heads=2, n_layers=1)
    assert dec_p.kernels == {"paged_attention_decode": "pallas",
                             "paged_attention_window": "xla:window_rows"}
    assert dec_x.kernels["paged_attention_window"] == "xla:not_tpu"

    r = np.random.RandomState(3)
    prompts = [list(r.randint(0, V, n)) for n in (3, 5, 2, 6)]
    max_news = [6, 8, 10, 5]
    want, _ = _serve(dec_x, states, prompts, max_news)
    got, st = _serve(dec_p, states, prompts, max_news,
                     draft_decoder=draft, draft_states=dstates,
                     spec_k=3)
    assert got == want
    assert st["draft_proposed"] > 0
    assert st["decode_kernel"] == "pallas"


def test_sampled_decode_identical_through_kernel():
    """The (seed, position) PRNG rides on top of the kernel's logits:
    sampled streams match the oracle server's exactly."""
    dec_x, states = _decoder()
    dec_p, _ = _decoder(interpret=True)
    outs = []
    for dec in (dec_x, dec_p):
        srv = GenerationServer(dec, states, slots=2, kv_blocks=8,
                               place=fluid.CPUPlace())
        try:
            outs.append(srv.submit([3, 1, 4], 6, temperature=0.7,
                                   seed=11).result(timeout=120))
        finally:
            srv.close()
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# selection: a function of geometry and platform alone
# ---------------------------------------------------------------------------

def _cell_geometry(workload):
    """The decoder geometry a serving cell of BENCHMARK.json builds
    (perf/jobs/serve_closed.py), read from the cell's own files."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(repo, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = load("perf", "configs", w["config"] + ".json")
    t = load("perf", "traffic", w["traffic"] + ".json")
    geometry = dict(d_model=m["hidden_size"],
                    n_heads=m["num_attention_heads"],
                    block_size=int(t["block_size"]),
                    kv_dtype=t["kv_dtype"])
    if "num_key_value_heads" in m:
        # a configuration that states its K/V geometry: what the
        # decoder derives from the block description (a head is the
        # model's width over its query heads where no key says other)
        d_head = m.get("head_dim", m["hidden_size"]
                       // m["num_attention_heads"])
        geometry.update(
            d_head=d_head, kv_width=m["num_key_value_heads"] * d_head)
    if "kv_lora_rank" in m:
        # a latent cache: ONE row a position for all heads, the latent
        # and the rotated key part, stored on the 128-lane grid
        row = m["kv_lora_rank"] + m["qk_rope_head_dim"]
        geometry.pop("d_head")
        geometry.update(kv_width=-(-row // 128) * 128,
                        value_width=m["kv_lora_rank"])
    return geometry


def _cell_expert_shapes(workload):
    """What a serving cell's step asks `select_grouped_matmul` when it
    is traced: the slots' assignments, the widths and the experts HELD,
    read from the cell's own files."""
    import json
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(repo, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == workload)
    m = load("perf", "configs", w["config"] + ".json")
    t = load("perf", "traffic", w["traffic"] + ".json")
    held = m[m["block"]["from_keys"].get("experts_held")
             or m["block"]["from_keys"]["n_experts"]]
    return dict(rows=int(t["slots"]) * m["num_experts_per_tok"],
                d_model=m["hidden_size"], d_ff=m[m["block"]["d_inner"]],
                n_experts=held, dtype=m["dtype"])


CHIP_SMOKE = dict(d_model=1024, n_heads=8, block_size=16)


@pytest.mark.parametrize("geometry,platform,interpret,want", [
    # d2048, 32 heads of 64, 32 blocks of 16, bf16: pages of 64 KB
    ("opt-1.3b-serve-closed32", "tpu", False, None),
    # d2048, 16 heads of 128, 64 blocks of 16 (context 1024), bf16
    ("olmoe-1b-7b-serve-chat32", "tpu", False, None),
    # d2304, 32 query heads of 128 over 4 K/V heads (rows of 512: pages
    # of 16 KB), sliding layers on a ring: a ring is a table
    ("mellum2-12b-a2.5b-serve-agent96", "tpu", False, None),
    # d4096, 32 query heads of 128 over 8 K/V heads (rows of 1024) on
    # its one attention layer in ten, context 1024
    ("granite-4.0-h-small-serve-chat64", "tpu", False, None),
    # d2048, 16 heads of 128 (OLMoE's page, 64 KB) on every one of a
    # looped stack's 4 x 48 planes: the layer is the kernel's operand
    ("ouro-2.6b-serve-chat12", "tpu", False, None),
    # no part of a K/V geometry is refused: the query comes
    # block-diagonal by K/V head, whatever the heads
    (dict(CHIP_SMOKE, kv_dtype="bf16", kv_width=256), "tpu", False, None),
    (dict(CHIP_SMOKE, kv_dtype="bf16", d_head=256), "tpu", False, None),
    # d6144, 64 query heads of 128 over 8 K/V heads, sliding layers on
    # a ring: the selection takes no word on rings or on a table's
    # length (a ring is a table, the scratch is two chunks)
    ("k-exaone-236b-a23b-serve-chat64", "tpu", False, None),
    # d5120, 128 heads on ONE latent row of 512 + 64 columns stored 640
    # wide: the latent form (one pool, a dense query); as it lies (576)
    # the row is off the lane grid
    ("deepseek-v2-serve-agent64", "tpu", False, None),
    (dict(d_model=5120, n_heads=128, block_size=16, kv_dtype="bf16",
          kv_width=576, value_width=512), "tpu", False, "lane_misaligned"),
    # what Mosaic's tiling refuses: a pool row off the 128-lane grid, a
    # page that is not whole sublane tiles of its dtype (16 rows of bf16)
    (dict(CHIP_SMOKE, kv_dtype="bf16", kv_width=192), "tpu", False,
     "lane_misaligned"),
    (dict(CHIP_SMOKE, kv_dtype="bf16", block_size=8), "tpu", False,
     "sublane_misaligned"),
    # chip_smoke.py --legs serve_lm: heads of 128, context 512
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "tpu", False, None),
    # an int8 pool is refused by name: no cell serves one
    (dict(CHIP_SMOKE, kv_dtype="int8"), "tpu", False, "kv_dtype"),
    (dict(CHIP_SMOKE, kv_dtype="fp32", block_size=8), "tpu", False, None),
    # off a TPU there is nothing to compile the kernel with...
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "cpu", False, "not_tpu"),
    # ...unless a test asks for the Pallas interpreter
    (dict(CHIP_SMOKE, kv_dtype="fp32"), "cpu", True, None),
], ids=["opt-1.3b-tpu", "olmoe-1b-7b-1chip-tpu", "mellum2-1chip-tpu",
        "granite-4.0-h-small-1chip-tpu", "ouro-2.6b-tpu", "grouped-kv-tpu",
        "wide-heads-tpu",
        "ring-tpu", "latent-tpu", "latent-unpadded-tpu",
        "row-off-the-lanes-tpu", "half-tile-pages-tpu",
        "chip_smoke-fp32-tpu", "chip_smoke-int8-tpu",
        "fp32-pages-of-8-tpu", "chip_smoke-cpu",
        "chip_smoke-cpu-interpret"])
def test_selection_follows_geometry_and_platform(geometry, platform,
                                                 interpret, want):
    """The benchmark's own geometries: this is the test PERF.md section
    7 cites for "every serving cell takes the paged kernel"."""
    if isinstance(geometry, str):
        geometry = _cell_geometry(geometry)
    kern, reason = paged_attention.select_paged_attention(
        platform=platform, interpret=interpret, **geometry)
    assert reason == want
    assert (kern is None) == (want is not None)
    # the heads lay the query out; they refuse nothing
    pool = {k: v for k, v in geometry.items()
            if k not in ("n_heads", "d_head")}
    assert paged_attention.paged_attention_supports(
        platform=platform, interpret=interpret, **pool) == want


@pytest.mark.parametrize("workload,pages,ring_pages,tiling", [
    ("opt-1.3b-serve-closed32", 32, None, ((16, 8), None)),
    ("olmoe-1b-7b-serve-chat32", 64, None, ((16, 8), None)),
    ("mellum2-12b-a2.5b-serve-agent96", 256, 64, ((80, 8), (64, 8))),
    ("granite-4.0-h-small-serve-chat64", 64, None, ((40, 8), None)),
    ("ouro-2.6b-serve-chat12", 64, None, ((16, 8), None)),
    ("k-exaone-236b-a23b-serve-chat64", 64, 8, ((40, 8), (8, 8))),
    ("deepseek-v2-serve-agent64", 256, None, ((64, 8), None))])
def test_the_cells_select_the_tiling_of_a_cap_of_whole_groups(
        workload, pages, ring_pages, tiling, monkeypatch):
    """For seven serving cells, from their own files, the selection
    returns the kernel with the cap and the row tile
    `decoder.attention_tiling` reports: 1.25 MiB of pages in whole
    issue groups where the table is longer (the three of 64 KB a page
    keep PR 44's 16), the ring itself where that is shorter; six are
    called with two pools and no `d_value`, the seventh takes the
    latent form."""
    geometry = _cell_geometry(workload)
    kern, reason = paged_attention.select_paged_attention(
        platform="tpu", **geometry)
    assert reason is None
    assert (kern.tiling(pages), ring_pages and kern.tiling(ring_pages)) \
        == tiling
    called = {}
    monkeypatch.setattr(paged_attention, "paged_attention",
                        lambda *a, **kw: called.update(kw, pool_v=a[2]))
    kern(None, "k", "v", np.zeros((2, pages), np.int32), None, 0, 0.5)
    latent = "value_width" in geometry
    assert called["d_value"] == (512 if latent else 0)
    assert called["pool_v"] == "v" and called["pages"] == tiling[0][0]
    assert called["n_heads"] == geometry["n_heads"]


@pytest.mark.parametrize("workload,held,rows", [
    ("olmoe-1b-7b-serve-chat32", 64, 256),
    ("mellum2-12b-a2.5b-serve-agent96", 64, 768),
    # one chip's 36 of the 72 experts the router routes 640 rows over
    ("granite-4.0-h-small-serve-chat64", 36, 640)])
def test_expert_kernel_selection_follows_the_cells_shapes(workload, held,
                                                          rows):
    """The cells with experts, from their own files: every one runs the
    Pallas grouped matmul on a TPU (what `sched_moe_kernel_share` reads
    as 100) and `ragged_dot` off one."""
    from paddle_tpu.kernels import grouped_matmul

    shapes = _cell_expert_shapes(workload)
    assert (shapes["n_experts"], shapes["rows"]) == (held, rows)
    kern, reason = grouped_matmul.select_grouped_matmul(
        platform="tpu", **shapes)
    assert reason is None and kern.name == grouped_matmul.NAME
    assert grouped_matmul.select_grouped_matmul(
        platform="cpu", **shapes) == (None, "not_tpu")


def test_unsupported_shape_is_refused_with_its_reason():
    """A refused pool never crashes a build: the decoder runs the XLA
    gather path, and `decoder.kernels` and the server's stats say
    why."""
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    # a pool row of 64 columns: half a lane tile
    geometry = dict(d_model=64, n_heads=2, block_size=16,
                    kv_dtype="fp32")
    assert paged_attention.select_paged_attention(
        platform="tpu", **geometry) == (None, "lane_misaligned")
    fw.reset_unique_names()
    _, dec = build_lm_paged_decoder(
        V, 16, 4, d_model=64, n_heads=2, n_layers=1, platform="tpu")
    assert dec.kernels == {
        "paged_attention_decode": "xla:lane_misaligned",
        "paged_attention_window": "xla:lane_misaligned"}
    dec_x, states = _decoder()
    srv = GenerationServer(dec_x, states, slots=2, kv_blocks=8,
                           place=fluid.CPUPlace())
    try:
        assert srv.stats()["decode_kernel"] == "xla:not_tpu"
    finally:
        srv.close()


@pytest.mark.parametrize("pages,tile,n_pages,want", [
    # closed32's table: chunks of 16 pages, windows of 8 and 16
    (16, 8, [1, 8, 9, 16, 17, 25, 32],
     [128, 128, 256, 256, 384, 512, 512]),
    # DeepSeek's: a cap of 64, windows of 8, 16, 32 and the strides 40,
    # 48, 56, 64; 65 pages are cut 40 + 25 and 100 are 56 + 44
    (64, 8, [1, 9, 17, 33, 64, 65, 100],
     [128, 256, 512, 640, 1024, 1152, 1664]),
    # a table of 12 pages: the chunk is the last window
    (12, 8, [8, 9, 12], [128, 192, 192]),
    # K-EXAONE's ring: one window, the chunk
    (8, 8, [1, 8], [128, 128]),
], ids=["two-windows", "seven-windows", "odd-chunk", "one-window"])
def test_rows_multiplied_follow_the_windows(pages, tile, n_pages, want):
    """What `kv_rows_multiplied` sums: a lane's equal chunks whole
    (a stride is a window), the last chunk's smallest window (the
    tile doubled up to the cap, or a stride) that holds its pages, for
    integers and for arrays alike."""
    got = paged_attention.rows_multiplied(np.asarray(n_pages), pages,
                                          tile, 16)
    assert list(got) == want
    assert [paged_attention.rows_multiplied(n, pages, tile, 16)
            for n in n_pages] == want


def _tick_attrs(dec, states, new=6, requests=1):
    """The attributes of the `serving.decode_tick` spans of `requests`
    requests, one after another, of 5 prompt tokens and `new` new ones
    on a server of 3 slots."""
    from paddle_tpu.observability import tracing

    tracing.set_enabled(True)
    tracing.clear()
    srv = GenerationServer(dec, states, slots=3, kv_blocks=12,
                           place=fluid.CPUPlace())
    try:
        for i in range(requests):       # no prompt a prefix of another
            srv.submit([3 + i, 1, 4, 1, 5], new).result(timeout=120)
        assert not srv._tables.any()
    finally:
        srv.close()
        tracing.set_enabled(False)
    return [s["attrs"] for s in tracing.finished_spans()
            if s["name"] == "serving.decode_tick"]


def test_tick_spans_count_the_pages_read():
    """`kv_pages_read` of `kv_pages_table` on `serving.decode_tick`:
    through the kernel the pages each cursor has reached (and one for a
    slot with no sequence), on the gather path every page of every
    slot's table."""
    dec_x, states = _decoder()
    dec_p, _ = _decoder(interpret=True)
    # 2 layers, 3 slots, tables of 4 blocks of 4 positions
    for a in _tick_attrs(dec_x, states):
        assert a["kv_pages_read"] == a["kv_pages_table"] == 2 * 3 * 4
    got = _tick_attrs(dec_p, states)
    assert [a["kv_pages_table"] for a in got] == [24] * len(got)
    # one sequence at cursors 0, 1, 2...: ceil((cursor + 1) / 4) pages a
    # layer, and a page a layer for each of the two idle slots
    assert [a["kv_pages_read"] for a in got] == [
        2 * (-(-(c + 1) // 4) + 2) for c in range(len(got))]
    assert len(got) == 10


def test_tick_spans_count_a_start_for_a_group_that_is_a_run():
    """`kv_dma_ops` on `serving.decode_tick` counts what the issue loop
    does with the slot's table as the cache manager made it: a fresh
    pool hands a request's 4 blocks out ascending, so once the cursor
    reaches the fourth page the chunk's one group of 4 is ONE start
    (and one wait) a pool a layer where three pages were three starts
    and two waits; the idle slots' page a start and a wait each.  The
    span's READER looks the count up in what it makes of the request's
    table, once a table, long after the request has gone
    (`decoder.starts_saved`, a memo on `_Seq.table`: the deferred
    account of PR 67): the second request's table, set in a used slot,
    counts the same, and the first's ticks still count by THEIR table."""
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    _, states = _decoder()
    with _interpreted():        # the table's 4 pages one chunk
        fw.reset_unique_names()
        _, dec_p = build_lm_paged_decoder(
            V, 4, 4, d_model=32, n_heads=2, n_layers=2, platform="cpu")
    assert dec_p.attention_tiling[0][0] == 4
    got = _tick_attrs(dec_p, states, new=11, requests=2)
    assert len(got) == 2 * 15
    for tick, a in enumerate(got):
        n = tick % 15 // 4 + 1
        ops = 2 if n == 4 else n + bin(n).count("1")
        # 2 layers, a K and a V pool; two idle slots
        assert a["kv_dma_ops"] == 2 * 2 * (ops + 2 * (1 + 1))


def test_tick_spans_count_the_rows_multiplied():
    """`kv_rows_multiplied` on `serving.decode_tick`: through the
    kernel the smallest row window that holds a slot's pages (here
    2 pages of 4 rows, or the chunk: the table's 4), on the gather path
    every row of every slot's table; the benchmark's reader divides it
    by the rows of the pages read."""
    import importlib.util
    import os
    import types

    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.observability import tracing

    dec_x, states = _decoder()
    with _interpreted(tile_rows=8):
        fw.reset_unique_names()
        _, dec_p = build_lm_paged_decoder(
            V, 4, 4, d_model=32, n_heads=2, n_layers=2, platform="cpu")
    assert dec_p.attention_tiling == ((4, 2), None)
    assert dec_x.attention_tiling is None
    for a in _tick_attrs(dec_x, states):
        assert a["kv_rows_multiplied"] == 4 * a["kv_pages_table"] == 96
    got = _tick_attrs(dec_p, states)
    assert len(got) == 10
    # one sequence at cursors 0, 1, 2...: a window of two pages or of
    # four, and the smaller a layer for each of the two idle slots
    pages = [-(-(c + 1) // 4) for c in range(len(got))]
    assert [a["kv_rows_multiplied"] for a in got] == [
        2 * 8 * (-(-p // 2) + 2) for p in pages]

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf", "metrics",
        "sched_kv_rows_multiplied_share.py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ticks = [s for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"]
    run = types.SimpleNamespace(
        spans=[{k: s[k] for k in ("name", "ts", "dur")} for s in ticks],
        cell=types.SimpleNamespace(traffic={"block_size": 4}))
    want = 100.0 * sum(a["kv_rows_multiplied"] for a in got) / (
        4 * sum(a["kv_pages_read"] for a in got))
    assert reader.compute(run) == pytest.approx(want)
    assert 100.0 < want < 200.0
    # a program without the attribute (the parent): nothing to read
    for s in ticks:
        del s["attrs"]["kv_rows_multiplied"]
    assert reader.compute(run) is None


# ---------------------------------------------------------------------------
# analyzer: the rows reflect what runs
# ---------------------------------------------------------------------------


def test_analyze_rows_follow_the_platform(monkeypatch):
    import jax

    from paddle_tpu import analysis

    # rows of 256 columns and 16-row pages: a pool the TPU accepts
    spec = {"vocab_size": V, "d_model": 256, "n_heads": 2,
            "n_layers": 2, "block_size": 16, "max_blocks_per_seq": 4,
            "kv_dtype": "bf16"}
    rep = analysis.analyze_generation_spec(spec, slots=4)
    assert rep["kernels"][0]["backend"] == "xla"
    assert all(r["kernel"] != "paged_attention_decode"
               for r in rep["kernels"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rep = analysis.analyze_generation_spec(spec, slots=4)
    assert rep["kernels"][0]["backend"] == "pallas"
    kern = [r for r in rep["kernels"]
            if r["kernel"] == "paged_attention_decode"][0]
    gather = [r for r in rep["kernels"]
              if r["kernel"] == "paged_attention_gather"][0]
    # at half the context the kernel reads the 2 pages of 4 a slot's
    # cursor has reached, the gather all 4 and writes and reads a copy
    assert (kern["pages_read"], gather["pages_read"]) == (16, 32)
    assert gather["pool_bytes"] == 2 * kern["pool_bytes"]
    assert kern["gather_bytes_avoided"] == (gather["bytes"]
                                            - kern["pool_bytes"])
    assert kern["bytes"] < gather["bytes"] / 5
    # the gather's cost does not depend on the cursor, the kernel's does
    short = analysis.serving_kernel_cost
    assert short("paged_attention_gather", spec, slots=4, context=1,
                 kv_dtype="bf16")["bytes"] == gather["bytes"]
    assert short("paged_attention_decode", spec, slots=4, context=1,
                 kv_dtype="bf16")["pages_read"] == 8
    # a pool the TPU refuses keeps the XLA rows there too
    for refused in (dict(spec, d_model=32), dict(spec, kv_dtype="int8")):
        rep = analysis.analyze_generation_spec(refused, slots=4)
        assert rep["kernels"][0]["backend"] == "xla"


# ---------------------------------------------------------------------------
# one page stream under both paged kernels, and one account of it
# ---------------------------------------------------------------------------


def test_both_paged_kernels_trace_through_the_one_stream(monkeypatch):
    """The chunk loop, its starts and waits, the prefetch across lanes
    and the buffer cursor are `stream_chunks` and nothing else: tracing
    the attention kernel (K and V, and a latent pool) and the
    index-score kernel calls it once each, with that kernel's pools and
    its own issue group, and neither `_kernel` copies or counts pages
    itself."""
    import inspect

    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_index_scores

    real = paged_attention.stream_chunks
    assert paged_index_scores.stream_chunks is real
    calls = []

    def counted(*args, **kw):
        calls.append((len(args[6]), kw["unroll"], kw["pages"]))
        return real(*args, **kw)

    monkeypatch.setattr(paged_attention, "stream_chunks", counted)
    monkeypatch.setattr(paged_index_scores, "stream_chunks", counted)
    tables = jnp.zeros((3, 7), jnp.int32)
    lengths = jnp.ones((3,), jnp.int32)
    pool = jnp.zeros((2, 9, 4, 24), jnp.float32)
    latent = jnp.zeros((2, 9, 4, 40), jnp.float32)
    # shapes no other test of this process has traced: the calls sit
    # behind module-level `jax.jit`s, which keep their traces
    jax.make_jaxpr(functools.partial(
        paged_attention.paged_attention, scale=0.3, pages=3, tile=1,
        n_heads=3, d_head=8))(
            jnp.zeros((3, 24)), pool, pool, tables, lengths, 1)
    jax.make_jaxpr(functools.partial(
        paged_attention.paged_attention, scale=0.3, pages=5, tile=1,
        n_heads=3, d_head=0, d_value=32))(
            jnp.zeros((3, 120)), latent, None, tables, lengths, 1)
    jax.make_jaxpr(functools.partial(
        paged_index_scores.paged_index_scores, pages=4, tile=2))(
            jnp.zeros((3, 2, 24)), jnp.zeros((3, 2)), pool, tables,
            lengths, 1)
    assert calls == [(2, paged_attention._ISSUE_UNROLL, 3),
                     (1, paged_attention._ISSUE_UNROLL, 5),
                     (1, paged_index_scores._ISSUE_UNROLL, 4)]
    for module in (paged_attention, paged_index_scores):
        own = inspect.getsource(module._kernel)
        assert "stream_chunks(" in own
        assert "start_pages" not in own and "cursor_ref[" not in own
        assert ".wait()" not in own.replace("copy.wait()", "")


# the ten served blocks' toys: each configuration's file under its own
# `rehearse` overlay, as the benchmark's jobs build them
_TOYS = ["opt-1.3b", "olmoe-1b-7b-1chip", "mellum2-12b-a2.5b-1chip",
         "granite-4.0-h-small-1chip", "ouro-2.6b",
         "k-exaone-236b-a23b-1chip", "deepseek-v2-1chip",
         "longcat-flash-1chip", "glm-5.2-1chip", "lfm2-24b-a2b-1chip"]
# what of a tick's counts the page streams decide
_STREAMED = ("kv_pages_read", "kv_rows_multiplied", "kv_dma_ops",
             "kv_pages_covered", "index_pages_read", "index_dma_ops")


def _toy_decoder(name, block_size, max_blocks):
    """-> (the toy of configuration `name` with every paged kernel it
    can run selected (the interpreter's selection: nothing is run), in
    chunks of 2048 bytes and row tiles of 8 rows; the index-score
    kernel's tiling over its table, or None)."""
    import json
    import os

    from paddle_tpu.kernels import paged_index_scores
    from paddle_tpu.models import lm_block
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "configs", name + ".json")) as f:
        m = json.load(f)
    m.update(m["rehearse"])
    spec, d_inner = None, m.get("ffn_dim")
    if "block" in m:
        b = m["block"]
        spec = lm_block.BlockSpec(**dict(
            b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
        d_inner = m[b["d_inner"]]
    index = functools.partial(paged_index_scores.select_index_scores,
                              interpret=True)
    with _interpreted(chunk_bytes=2048, tile_rows=8), \
            pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_index_scores, "select_index_scores", index)
        mp.setattr(paged_index_scores, "_CHUNK_BYTES", 2048)
        fw.reset_unique_names()
        _, dec = build_lm_paged_decoder(
            m["vocab_size"], block_size, max_blocks,
            d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
            n_layers=m["num_hidden_layers"], d_inner=d_inner,
            platform="cpu", block=spec)
        tiling = None
        if dec.index_planes:
            tiling = index(
                index_head_dim=spec.index_head_dim, block_size=block_size,
                kv_dtype="fp32", platform="cpu")[0].tiling(max_blocks)
    return dec, tiling


def _brute_stream(n_pages, tables, cap, tile, bs, unroll, cut=None):
    """One call of the page stream walked lane by lane, chunk by chunk
    (`cut`: `_brute_cut`), copy by copy: `n_pages` a lane in the
    stream's order, `tables` [lanes, table pages] or None (no group is
    a run) -> (pages read, rows multiplied, DMA starts and waits a
    pool, pages whose copy is in flight under as many pages'
    products)."""
    cut = cut or _brute_cut
    windows = _brute_windows(cap, tile, unroll)
    unroll = min(unroll, cap)
    read = multiplied = dma = covered = 0
    window_before = None        # of the chunk before in the stream
    for lane, n in enumerate(n_pages):
        first = 0
        for copied in cut(int(n), cap, tile, unroll):
            assert copied <= cap
            entries = (None if tables is None
                       else tables[lane, first:first + copied])
            for g in range(copied // unroll):
                group = (None if entries is None
                         else entries[g * unroll:(g + 1) * unroll])
                run = group is not None and (
                    np.diff(group) == 1).all() and unroll > 1
                dma += 1 if run else unroll
            dma += copied % unroll + bin(copied).count("1")
            window = next(w for w in windows if w >= copied)
            if window_before is not None:
                covered += min(copied, window_before)
            read, multiplied = read + copied, multiplied + window * bs
            window_before, first = window, first + copied
    return read, multiplied, dma, covered


def _brute_streamed_counts(dec, index_tiling, rows, slots, tables, rings):
    """`tick_counts`' account of the page streams by a walk of them
    (`_brute_stream`), once for the table, for the ring and for the
    index planes, the idle lanes (a page each, no run) after the
    others: the oracle of `stream_counts`.  `tables`, `rings`: the
    lanes' own, or None for no saved starts."""
    bs, idle = dec.block_size, slots - len(rows)
    pools = 1 if dec.kernels["paged_attention_decode"].endswith(
        "latent") else 2

    def walk(pages, names, cap, tile, unroll):
        if names is not None:
            # an idle lane's entries are block 0 throughout: no run
            names = np.concatenate(
                [names, np.zeros((idle, names.shape[1]), names.dtype)])
        return np.array(_brute_stream(
            np.concatenate([pages, np.ones(idle, np.int64)]), names, cap,
            tile, bs, unroll))

    unroll = paged_attention._ISSUE_UNROLL
    total = dec.table_layers * walk(
        -(-rows // bs), tables, *dec.attention_tiling[0], unroll)
    if dec.ring_layers:
        total += dec.ring_layers * walk(
            -(-np.minimum(rows, dec.window_blocks_per_seq * bs) // bs),
            rings, *dec.attention_tiling[1], unroll)
    read, multiplied, dma, covered = map(int, total)
    want = {"kv_pages_read": read, "kv_rows_multiplied": multiplied,
            "kv_dma_ops": pools * dma, "kv_pages_covered": covered}
    if index_tiling is not None:
        from paddle_tpu.kernels.paged_index_scores import _ISSUE_UNROLL
        read, _, dma, _ = walk(-(-rows // bs), tables, *index_tiling,
                               _ISSUE_UNROLL)
        want["index_pages_read"] = dec.index_planes * int(read)
        want["index_dma_ops"] = dec.index_planes * int(dma)
    return want


@pytest.mark.parametrize("cut,share", [("28+5", 39.0), ("33", 100.0)])
def test_copy_covered_share_on_the_rings_two_cuts(cut, share, monkeypatch):
    """`sched_kv_copy_covered_share` on dots3's rings, 64 lanes of 33
    pages, three sliding layers: cut 28 + 5 (the parent's, walked by
    hand) a lane's 28-page copy is in flight under the 8-page window of
    the five pages before it and its 5 under the 28: (8 + 5) / 33 = 39;
    as ONE chunk (`stream_counts` at the ring's tiling) every copy but
    the call's first lies under 33 pages' products: 100 less a lane in
    64.  The benchmark's reader sums both attributes over the window's
    tick spans."""
    import importlib.util
    import os
    import types

    from paddle_tpu.observability import tracing

    rows = np.full(64, 33 * 16)
    if cut == "28+5":
        read, _, _, covered = _brute_stream(
            rows // 16, None, 28, 8, 16, 8,
            cut=lambda n, cap, tile, unroll: [28, 5])
        assert covered == 63 * 8 + 64 * 5
    else:
        kern, _ = paged_attention.select_paged_attention(
            d_model=64 * 256, n_heads=64, block_size=16, kv_dtype="bf16",
            platform="tpu", kv_width=1152, value_width=1024)
        assert kern.tiling(33) == (33, 8)
        read, _, _, covered = paged_attention.stream_counts(
            rows, 0, 33, 8, 16)
        assert (covered, read) == _brute_stream(
            rows // 16, None, 33, 8, 16, 8)[3::-3]
        assert covered == 63 * 33
    assert read == 64 * 33
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf", "metrics",
        "sched_kv_copy_covered_share.py")
    spec = importlib.util.spec_from_file_location("_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ticks = [{"name": "serving.decode_tick", "ts": 10.0 * i, "dur": 5.0,
              "attrs": {"kv_pages_read": 3 * read,
                        "kv_pages_covered": 3 * covered}}
             for i in range(4)]
    monkeypatch.setattr(tracing, "finished_spans", lambda: ticks)
    run = types.SimpleNamespace(spans=ticks)
    got = reader.compute(run)
    assert got == pytest.approx(100.0 * covered / read)
    assert abs(got - share) < (2.0 if cut == "33" else 0.1)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        "kernels", "%", "itl_p95_ms", "program_span")
    # a program without the attribute (the parent): nothing to read
    for tick in ticks:
        del tick["attrs"]["kv_pages_covered"]
    assert reader.compute(run) is None
    assert reader.compute(types.SimpleNamespace(spans=[])) is None


@pytest.mark.parametrize("name", _TOYS)
def test_the_one_account_is_a_walk_of_the_streams(name):
    """`decoder.tick_counts` over random cursors, tables with runs and
    without, rings and idle lanes: what the streams decide
    (`stream_counts`, once for the table, for the ring and for the
    index planes) is what a walk of the same cut counts, lane by lane,
    chunk by chunk, copy by copy (`_brute_stream`), with and without
    the saved starts, and every other attribute is the one the gather
    path reports for the same cursors."""
    slots, bs, nb = 6, 4, 11
    dec, index_tiling = _toy_decoder(name, bs, nb)
    assert dec.attention_tiling is not None
    assert (index_tiling is not None) == bool(dec.index_planes)
    r = np.random.RandomState(len(name))
    rings = (np.asarray(dec.slot_rings(slots)) if dec.ring_layers
             else None)
    seen = set()
    for trial in range(40):
        # a lane's table: ascending blocks from somewhere, of which a
        # few trials shuffle a few lanes (no run survives there)
        tables = (1 + r.randint(0, 50, (slots, 1))
                  + np.arange(nb)[None, :])
        for lane in r.permutation(slots)[:trial % 4]:
            tables[lane] = r.permutation(tables[lane])
        held = dec.starts_saved(tables, rings)
        lanes = np.sort(r.permutation(slots)[:r.randint(0, slots + 1)])
        cursors = r.randint(0, bs * nb, len(lanes)).astype(np.int32)
        for saved in ({}, {k: v[lanes] for k, v in held.items()}):
            got = dec.tick_counts(cursors, slots, saved=saved)
            want = _brute_streamed_counts(
                dec, index_tiling, cursors.astype(np.int64) + 1, slots,
                tables[lanes] if saved else None,
                rings[lanes] if saved and rings is not None else None)
            assert {k: got[k] for k in _STREAMED if k in got} == want
            assert all(type(v) is int for v in got.values())
            gathered = dec.tick_counts(cursors, slots, windowed=True)
            assert {k: v for k, v in got.items()
                    if k not in _STREAMED} == {
                        k: v for k, v in gathered.items()
                        if k not in _STREAMED}
            seen |= set(got)
    assert set(want) <= seen
    assert ("index_dma_ops" in seen) == (name == "glm-5.2-1chip")


# ---------------------------------------------------------------------------
# the kernel-alone harness (tools/kernel_pace.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [
    "deepseek-v2-serve-agent64", "mellum2-12b-a2.5b-serve-agent96",
    "opt-1.3b-serve-closed32"])
def test_kernel_pace_rehearses_a_cells_shape(shape, tmp_path):
    """`tools/kernel_pace.py --rehearse --check`: a cell's shape cut to
    a toy walks the whole kernel in the interpreter, over every table
    and ring of the shape, and its result is plain attention's (a
    bfloat16 pool); off a TPU the tool gives a time for nothing else."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "kernel_pace.py")
    spec = importlib.util.spec_from_file_location("kernel_pace", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "pace.json"
    res = tool.main(["--shape", shape, "--rehearse", "--check",
                     "--out", str(out)])
    assert res == json.loads(out.read_text())
    assert res["rehearsal"] and res["bs16.whole"] > 0
    assert res["bs16.check"] < 1e-2
    assert res["bs16.pages"] * 16 >= res["rows"] > 0
    assert set(tool.VARIANTS) == {"whole", "no_copies", "no_products"}
    with pytest.raises(SystemExit, match="no TPU"):
        tool.run(shape)
