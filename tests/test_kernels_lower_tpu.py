"""Every Pallas kernel cross-lowered for TPU from the CPU, and the
paged-attention and index-score kernels compiled for a described v5e.

The interpret-mode parity suites (test_paged_attention.py,
test_flash_attention.py) prove the kernels' MATH; they say nothing
about whether the Pallas TPU lowering accepts the kernel at all, and
that is where the first chip bring-up found kernels refused (a dot
with no free lhs dimension, a (1, 1) VMEM block over a [layers,
blocks] array).  Lowering for the
"tpu" platform needs no TPU: `jit(f).trace(*args).lower(
lowering_platforms=("tpu",))` runs the whole Pallas->Mosaic lowering
on the host, in seconds.  What it cannot see is Mosaic's own compile
(VMEM fit, layouts): the TPU's compiler, which is installed here,
compiles for a chip that is described and not attached
(`compiled_for_v5e`), at the serving cells' real pool shapes.

Contract: at a pool `select_paged_attention(platform="tpu")` accepts,
the kernel it returns lowers to ONE Mosaic custom call however many
layers call it, and compiles for a v5e at every serving cell's
geometry; a lightning indexer's score kernel the same at its plane's,
and both kernels' Mosaic modules, source locations apart, are the ones
pinned here (what the chip's readings were taken of);
a gated delta rule's kernel lowers and compiles for a v5e at the
Solar cell's and the Ling cell's states with its output state ALIASING
its input;
a router's choice kernel compiles for a v5e at the ten expert cells'
rows and routers, and an expert layer from the router's matmul through
`moe_combine`, compiled there at Ling's and LongCat's shapes with the
kernel and with `_largest`'s passes, holds no `sort`;
the flash kernels lower forward and backward, compile for a
v5e at the training cell's shape, and a training step holds the forward
kernel once a layer; a language model's AMP training step holds no
float32 array of [tokens, vocab].
"""
import base64
import contextlib
import functools
import hashlib
import json
import re
import signal

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels import delta_rule
from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import select_paged_attention
from paddle_tpu.kernels.paged_index_scores import select_index_scores

MOSAIC_CALL = "tpu_custom_call"

# name: slots, query heads, pool row, page rows, table pages, layers,
# pool dtype (a head is 128 wide but for OPT's 64: the pool row over
# its K/V heads).  chip_smoke.py's serving leg (d_model 1024, 8 heads
# of 128, 8 slots) and the six serving cells' tables and rings at their
# real sizes (perf/configs, perf/traffic)
POOLS = {
    "chip_smoke-fp32": (8, 8, 1024, 16, 32, 2, "fp32"),
    "chip_smoke-bf16": (8, 8, 1024, 16, 32, 2, "bf16"),
    "opt-1.3b-closed32": (32, 32, 2048, 16, 32, 24, "bf16"),
    "olmoe-chat32": (32, 16, 2048, 16, 64, 12, "bf16"),
    "mellum2-agent96-table": (96, 32, 512, 16, 256, 2, "bf16"),
    "mellum2-agent96-ring": (96, 32, 512, 16, 64, 6, "bf16"),
    "granite-chat64": (64, 32, 1024, 16, 64, 1, "bf16"),
    # a plane for every (pass, layer) pair of the looped stack
    "ouro-chat12": (12, 16, 2048, 16, 64, 192, "bf16"),
    "k-exaone-chat64-table": (64, 64, 1024, 16, 64, 2, "bf16"),
    "k-exaone-chat64-ring": (64, 64, 1024, 16, 8, 6, "bf16"),
    # the latent form: ONE pool, 128 heads on a row of 512 + 64 stored
    # 640 wide, of which 512 are the value
    "deepseek-v2-agent64-latent": (64, 128, 640, 16, 256, 5, "bf16"),
    # the same row under 64 heads, on the 8 planes of 4 double layers
    "longcat-flash-agent64-latent": (64, 64, 640, 16, 256, 8, "bf16"),
    # the same row again under a lightning indexer's SELECTION (a row
    # mask a slot over the table's 432 pages: 2048 rows selected), 64
    # heads whose value is the latent's 512 columns, 5 planes
    "glm-5.2-docqa64-selected": (64, 64, 640, 16, 432, 5, "bf16"),
    # a K and a V pool under a cap of 51 pages: a table of 51 is one
    # chunk of no whole number of issue groups and no power of two, so
    # its waits on summed bytes take four sizes and its issue loop a
    # remainder (a row of 5 heads of 128: DeepSeek's page bytes under
    # grouped heads; the cells' caps since PR 66 are whole groups but
    # for dots3's ring of 33)
    "kv-chunks-of-51": (16, 20, 640, 16, 51, 2, "bf16"),
    # K-EXAONE's row (8 K/V heads of 128) on the ONE attention layer of
    # four, over docqa64's table of 432 pages a lane (PR 59)
    "solar-open2-docqa64": (64, 64, 1024, 16, 432, 1, "bf16"),
    # the latent row under 32 heads at 128 lanes, ONE plane: the latent
    # layer of six beside five delta-rule layers (PR 62)
    "ling-3.0-flash-agent128-latent": (128, 32, 640, 16, 256, 1, "bf16"),
    # latent RINGS beside a selected latent table (PR 65): the table's
    # row under 128 heads and the indexer's selection on the 2 full
    # layers' planes, and the sliding layers' own row (1024 + 64 stored
    # 1152 wide, 1024 of them the value) under 64 heads over a ring of
    # 33 pages a lane, the last 513 rows of it under the window's mask
    "dots3-docqa64-table": (64, 128, 640, 16, 432, 2, "bf16"),
    "dots3-docqa64-ring": (64, 64, 1152, 16, 33, 3, "bf16"),
    # a K and a V pool of 4 heads of 128 (a row of 512) under 20 query
    # heads, FIVE a K/V head, on the 5 planes of a block whose every
    # layer attends beside its Mamba mixer (PR 69)
    "falcon-h1-docqa64": (64, 20, 512, 16, 432, 5, "bf16"),
}
D_HEAD = {"opt-1.3b-closed32": 64}
D_VALUE = {"deepseek-v2-agent64-latent": 512,
           "longcat-flash-agent64-latent": 512,
           "glm-5.2-docqa64-selected": 512,
           "ling-3.0-flash-agent128-latent": 512,
           "dots3-docqa64-table": 512, "dots3-docqa64-ring": 1024}
# rows a slot's mask selects (the indexer's `index_topk`)
SELECTED = {"glm-5.2-docqa64-selected": 2048,
            "dots3-docqa64-table": 2048, "dots3-docqa64-ring": 513}
# pages a chunk over each pool: what the waits' static list and the
# issue loop's groups follow
CHUNK_PAGES = {
    "chip_smoke-fp32": 16, "chip_smoke-bf16": 32, "opt-1.3b-closed32": 16,
    "olmoe-chat32": 16, "mellum2-agent96-table": 80,
    "mellum2-agent96-ring": 64, "granite-chat64": 40, "ouro-chat12": 16,
    "k-exaone-chat64-table": 40, "k-exaone-chat64-ring": 8,
    "deepseek-v2-agent64-latent": 64, "kv-chunks-of-51": 51,
    "longcat-flash-agent64-latent": 64, "glm-5.2-docqa64-selected": 64,
    "solar-open2-docqa64": 40, "ling-3.0-flash-agent128-latent": 64,
    "dots3-docqa64-table": 64, "dots3-docqa64-ring": 33,
    "falcon-h1-docqa64": 80,
}


def lower_tpu(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def mosaic_modules(text):
    """The Mosaic module of every `tpu_custom_call` in `text` (what
    `lower_tpu` returns), printed WITHOUT what names Python source: the
    call carries its module as serialised bytecode with a location on
    every operation, so the raw text moves with any line shift in a
    kernel's file.  Printed with locations off it holds no file, no
    line and no Python function's name (its functions are `main` and
    the block specs' `transform_<i>`): it is the kernel the compiler is
    given, operation for operation."""
    from jax._src.lib.mlir import ir

    modules = []
    for config in re.findall(
            r'backend_config = "((?:[^"\\]|\\.)*)"', text):
        body = json.loads(config.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            modules.append(ir.Module.parse(
                base64.b64decode(body)).operation.get_asm(
                    enable_debug_info=False))
    return modules


def _paged(name, sharding=None):
    """(a function that attends over every layer of pool `name`
    through the selected kernel, its arguments as shapes)."""
    s_n, h, d_kv, bs, nb, layers, kv_dtype = POOLS[name]
    d_head = D_HEAD.get(name, 128)
    d_value = D_VALUE.get(name)
    kern, reason = select_paged_attention(
        d_model=h * d_head, n_heads=h, d_head=d_head, kv_width=d_kv,
        block_size=bs, kv_dtype=kv_dtype, platform="tpu",
        value_width=d_value)
    assert reason is None
    assert kern.tiling(nb)[0] == CHUNK_PAGES[name]
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    # Ouro's planes at 12 slots would be 19 GB of pool: the planes its
    # 288 blocks hold
    blocks = min(s_n * nb, 288) + 1
    pool = shape((layers, blocks, bs, d_kv), dtype)

    def attend_latent(q, pool, _, tables, lengths):
        # the one pool from layer to layer, a head's query a whole row
        # and its result the value's columns
        row = jnp.zeros((s_n, d_kv), dtype)
        # a selection: the first `SELECTED` rows of every slot's table
        select = ({"select": jnp.arange(nb * bs)[None, :]
                   < jnp.minimum(lengths, SELECTED[name])[:, None]}
                  if name in SELECTED else {})
        for l in range(layers):
            out, pool = kern(q, pool, None, tables, lengths, l, 0.115,
                             write=(row, None, lengths - 1), **select)
            q = jnp.pad(out.reshape(s_n, h, d_value), (
                (0, 0), (0, 0), (0, d_kv - d_value))).reshape(
                    s_n, h * d_kv).astype(dtype)
        return out, pool

    if d_value:
        return attend_latent, (
            shape((s_n, h * d_kv), dtype), pool, None,
            shape((s_n, nb), jnp.int32), shape((s_n,), jnp.int32))

    def attend(q, pool_k, pool_v, tables, lengths):
        # layer upon layer, as a step's are (a looped stack's planes
        # under a scan, the layer traced): each query from the last
        # layer's result
        # and this position's K and V written by the kernel, into
        # pools that pass from layer to layer as the step's do
        kv = jnp.zeros((s_n, d_kv), dtype)

        def layer(carry, l):
            q, pool_k, pool_v = carry
            out, pool_k, pool_v = kern(
                q, pool_k, pool_v, tables, lengths, l, 0.125,
                write=(kv, kv, lengths - 1))
            return ((q + out).astype(dtype), pool_k, pool_v), out

        carry = (q, pool_k, pool_v)
        if layers > 24:
            carry, outs = jax.lax.scan(layer, carry, jnp.arange(layers))
            return outs[-1], carry[1], carry[2]
        for l in range(layers):
            carry, out = layer(carry, l)
        return out, carry[1], carry[2]

    return attend, (shape((s_n, h * d_head), dtype), pool, pool,
                    shape((s_n, nb), jnp.int32), shape((s_n,), jnp.int32))


@functools.lru_cache(maxsize=None)
def _lowered(name):
    """`lower_tpu` of pool or plane `name`, once for the tests that
    read it."""
    f, args = _paged(name) if name in POOLS else _index_scores(name)
    return lower_tpu(f, *args)


@pytest.mark.parametrize("name", sorted(POOLS))
def test_paged_attention_lowers_for_tpu(name):
    """One Mosaic module for all the layers of a program: the call sits
    behind a module-level `jax.jit` with the layer a traced scalar."""
    assert _lowered(name).count(MOSAIC_CALL) == 1


@pytest.fixture(scope="module")
def one_v5e():
    """A described, not attached, v5e chip to compile for."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(POOLS))
def test_paged_attention_compiles_for_a_v5e(name, one_v5e):
    """Mosaic's own compile (VMEM fit, tiling, the dynamic page loop's
    DMAs) at the real pool shapes, and nothing but a layer's result
    and the next one's query lives outside the kernel: no
    logical-order copy of a pool."""
    attend, args = _paged(name, one_v5e)
    # the pools donated, as the step's are: the kernel writes in place
    compiled = jax.jit(attend, donate_argnums=(1, 2)).lower(
        *args).compile()
    assert MOSAIC_CALL in compiled.as_text()
    # a query and a result at the heads' own width: where the
    # block-diagonal operand lived outside the kernel this bound was
    # n_kv times as wide
    s_n, width = args[0].shape
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        4 * s_n * width * 4


# name: lanes, index heads, an index key's width, page rows, table
# pages, selecting layers (a plane each), blocks a plane, pool dtype:
# the GLM cell's index-key pool, and a float32 one in pages of 8
INDEX_PLANES = {
    "glm-5.2-docqa64": (64, 32, 128, 16, 432, 2, 9216, "bf16"),
    # the same planes under 64 index heads (PR 65)
    "dots3-docqa64": (64, 64, 128, 16, 432, 2, 9216, "bf16"),
    "fp32-pages-of-8": (8, 4, 128, 8, 40, 3, 321, "fp32"),
}


def _index_scores(name, sharding=None):
    """(a function that scores every plane of `name` through the
    selected kernel, as the selecting layers of a step do one after
    another, its arguments as shapes)."""
    s_n, h, d, bs, nb, planes, blocks, kv_dtype = INDEX_PLANES[name]
    kern, reason = select_index_scores(
        index_head_dim=d, block_size=bs, kv_dtype=kv_dtype,
        platform="tpu")
    assert reason is None
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    def scores(q, w, pool, tables, lengths):
        out = [kern(q, w, pool, tables, lengths, plane)
               for plane in range(planes)]
        valid = jnp.arange(nb * bs)[None, :] < lengths[:, None]
        return [jnp.where(valid, o, -jnp.inf) for o in out]

    return scores, (shape((s_n, h, d), jnp.float32),
                    shape((s_n, h), jnp.float32),
                    shape((planes, blocks, bs, d), dtype),
                    shape((s_n, nb), jnp.int32), shape((s_n,), jnp.int32))


@pytest.mark.parametrize("name", sorted(INDEX_PLANES))
def test_index_scores_lower_for_tpu(name):
    """One Mosaic module however many selecting layers call it: the
    call sits behind a module-level `jax.jit`, the plane traced."""
    assert _lowered(name).count(MOSAIC_CALL) == 1


# sha256 of each kernel's Mosaic module (`mosaic_modules`), taken from
# PR 66's own tree, which moved every one of them (a lane's stride and
# count of chunks on the scalar-prefetch lane, the row windows of the
# strides, the switch's longest window first, a selection read from a
# chunk's first row on) and timed parent and change with
# `tools/kernel_pace.py`: the v5e readings in PERF.md section 6, PR 66,
# are of THESE kernels.  A refactoring at trace time emits these
# operations in this order; a PR that means to move one re-pins it from
# its own tree and times both.
MOSAIC_SHA256 = {
    # (the geometries PR 65 brought)
    "dots3-docqa64-table":
        "a08fadbffc51b87afc0c1f5eee0ba2457904286e82a29a6dd578ac4ca97318aa",
    "dots3-docqa64-ring":
        "6e4fd2eaf46391df2dd75760a100ec6c35ad596d5e196c1dab3d235702279baf",
    "dots3-docqa64":
        "273606f80ecb7604a0a9ea55bb2ba5a4171bd90ff040aba6295e572cdbd2982f",
    # (the geometry PR 69 brought)
    "falcon-h1-docqa64":
        "d01c881ece4761a081c25dad0686de0d6224535c5dd26dc23fd452e7ba694fc3",
    "chip_smoke-bf16":
        "885657113170485435814d3d481a1431cd26e4a844dcc3d82a03431564535480",
    "chip_smoke-fp32":
        "170669b9eb1d68ee7e67e4baad2e637ba0f276018bf934d5729e28b12668a587",
    "deepseek-v2-agent64-latent":
        "4b82bbd556315f9ca91b582a9d84a32ba5fa328d76f981426cabc58bc7ebfacb",
    "glm-5.2-docqa64-selected":
        "8a6bc2d01f90f52abcd5b53a13aa61651f68c95f70c85210e798d7923de806e3",
    "granite-chat64":
        "e4fdf13f18c1921a8ce82da49d78728f7a7ffdad5a13e1773a4a434f0813356e",
    "k-exaone-chat64-ring":
        "5696d992651821808b836303a96ba45947baad5890d1f9c583390c6750c75618",
    "k-exaone-chat64-table":
        "bca36a91f7912acaba2ee64e8a82c6fb79cee4a6bdf0fcc0a2a8ef795ecb63c6",
    "kv-chunks-of-51":
        "f8667e276ce7113243ef0cd3fed63be5af708564c066086b3489224ceb25c296",
    # (the geometry PR 62 brought)
    "ling-3.0-flash-agent128-latent":
        "a7832d6541bd23902835ae77fea0ad08ebc16cdf230a24cddb937ebcb3627467",
    "longcat-flash-agent64-latent":
        "2d7d22afc4b2392d18000518a0ca373f0d4c6b4576a6a05ad18298ca81a882fd",
    "mellum2-agent96-ring":
        "d0a49d1184cde5a2070932facd6d2383f3286df79d8d57e25aefcdfd91ddd928",
    "mellum2-agent96-table":
        "ac22a655211a6251a28125e0338656398625ff86042c60a33fec87e6f1d69d0d",
    "olmoe-chat32":
        "e14e18ea005c7c27f5d9978a8ac0440acbd8f830af1067f358975d7c5072b3e7",
    "opt-1.3b-closed32":
        "c48778ca859e0e024d157e0fb3c8fc00a07213a6cf73e861f8560831e1f3804a",
    "solar-open2-docqa64":
        "4a3f7b7d6cbd664dbd53b736c3537f4df1a75117abdf70abc1726127aa46ae05",
    "ouro-chat12":
        "c25aba33b0fe3c02ff8a5f593e62ea3889338507fa3028574f0d510981b574c5",
    # the two index planes
    "fp32-pages-of-8":
        "fba18d6b462124fc1389c7dbf2ca706448ebd1a65f2ec8dab558c442aca5b02d",
    "glm-5.2-docqa64":
        "0065c788ff7ab505e75d31c19f2d15d1f29589ddd95a733f233b9e71af9a6015",
}


@pytest.mark.parametrize("name", sorted(POOLS) + sorted(INDEX_PLANES))
def test_the_kernel_the_compiler_is_given_is_the_pinned_one(name):
    """Source locations apart, the Mosaic module of each paged kernel
    at each cell's pool is, letter for letter, the one its readings on
    the chip were taken of."""
    (module,) = mosaic_modules(_lowered(name))
    assert "loc(" not in module and ".py" not in module
    assert set(re.findall(r'sym_name = "([^"]*)"', module)) <= (
        {"main"} | {f"transform_{i}" for i in range(16)})
    assert hashlib.sha256(module.encode()).hexdigest() == \
        MOSAIC_SHA256[name]


def dma_sequence(module):
    """The copies a kernel issues and waits for, in the order its
    Mosaic module (`mosaic_modules`) has them, each with the memories
    and shapes it moves between, and the loops they stand in."""
    sequence = []
    for line in module.splitlines():
        op = re.search(r'"stable_mosaic\.(tpu\.enqueue_dma|tpu\.wait_dma2|'
                       r'scf\.for|scf\.while)"', line)
        if op:
            sequence.append(op.group(1) + "".join(
                re.findall(r"memref<[^>]*>", line.split(" : ")[-1])
                if op.group(1).startswith("tpu") else []))
    return sequence


# sha256 of `dma_sequence` of a ring that is ONE chunk a lane, taken at
# PR 66's PARENT: the cut moved no copy and no wait of a lane the
# buffer already held (the issue loops over runs, over the other
# groups and over the last pages, the first lane's start, the next
# lane's, the waits on summed bytes, the written row's way back)
DMA_SEQUENCE_SHA256 = {
    "k-exaone-chat64-ring":
        "a68bb60d73316257fcb68898bcce142c7cab8886f37f1b857ea63214e0ef34a4",
    "mellum2-agent96-ring":
        "5c36beeca9fdd9200b641cbc39e95cc57c4d216efb584964f4906815b496b47a",
}


@pytest.mark.parametrize("name", sorted(DMA_SEQUENCE_SHA256))
def test_a_one_chunk_lanes_copies_and_waits_are_the_parents(name):
    """agent96's ring (64 pages of 16 KB) and K-EXAONE's (8 pages) were
    one chunk a lane before the cut and are one after it: the kernel
    starts and waits for the same copies in the same order."""
    nb = POOLS[name][4]
    assert CHUNK_PAGES[name] == nb
    (module,) = mosaic_modules(_lowered(name))
    sequence = dma_sequence(module)
    assert sum(op.startswith("tpu.enqueue_dma") for op in sequence) > 8
    assert hashlib.sha256("\n".join(sequence).encode()).hexdigest() == \
        DMA_SEQUENCE_SHA256[name]


@pytest.mark.parametrize("name", sorted(INDEX_PLANES))
def test_index_scores_compile_for_a_v5e(name, one_v5e):
    """Mosaic's own compile at the cell's plane (the table's 432 pages
    one chunk, the products over windows of 128 to 6912 rows), the pool
    read where it lies: nothing but the scores themselves lives
    outside the kernel (no logical-order copy of the keys, no
    [lanes, heads, rows] products)."""
    scores, args = _index_scores(name, one_v5e)
    compiled = jax.jit(scores).lower(*args).compile()
    assert MOSAIC_CALL in compiled.as_text()
    s_n, _, _, bs, nb, planes = INDEX_PLANES[name][:6]
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        2 * planes * s_n * nb * bs * 4


# name: lanes, heads, a head's keys (and values): one delta-rule
# layer's states on `solar-open2-250b-serve-docqa64`, and a block of
# heads that is no power of two
DELTA_STATES = {
    "solar-open2-docqa64": (64, 64, 128),
    # `ling-3.0-flash-serve-agent128`: twice the lanes, half the heads
    "ling-3.0-flash-agent128": (128, 32, 128),
    "heads-of-24": (3, 24, 128),
}


def _delta_rule(name, sharding=None):
    """-> (the selected kernel, its `rule`'s arguments as shapes)."""
    s_n, h_n, k_n = DELTA_STATES[name]
    kern, why = delta_rule.select_delta_rule(
        lanes=s_n, heads=h_n, d_head=k_n, platform="tpu")
    assert kern is not None, why

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    row = sds((s_n, h_n, k_n))
    return kern, (sds((s_n, h_n, k_n, k_n)), row, row, row, row,
                  sds((s_n, h_n)), sds((s_n,), jnp.bool_),
                  sds((s_n,), jnp.bool_))


@pytest.mark.parametrize("name", sorted(DELTA_STATES))
def test_delta_rule_lowers_for_tpu(name):
    """One Mosaic call a layer's recurrence, float32 end to end: no
    operand or result of the kernel in fewer bits."""
    kern, args = _delta_rule(name)
    text = lower_tpu(kern.rule, *args)
    assert text.count(MOSAIC_CALL) == 1
    (module,) = mosaic_modules(text)
    assert "bf16" not in module and "f16" not in module


@pytest.mark.parametrize("name", sorted(DELTA_STATES))
def test_delta_rule_compiles_for_a_v5e_in_place(name, one_v5e):
    """Mosaic's own compile at the cell's states (a step's blocks fit
    the VMEM the call asks for), and the state donated, as the step
    donates its pools, comes back as the SAME buffer: the output
    aliases the input and nothing the size of a state lives beside it
    (PR 59's `snapshot_restore` held 302 MB of temporaries that no CPU
    test saw)."""
    kern, args = _delta_rule(name, one_v5e)
    compiled = jax.jit(kern.rule, donate_argnums=(0,)).lower(
        *args).compile()
    assert MOSAIC_CALL in compiled.as_text()
    s_n, h_n, k_n = DELTA_STATES[name]
    state, row = 4 * s_n * h_n * k_n * k_n, 4 * s_n * h_n * k_n
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == state
    # the three transposed columns and the repeated beta, no more
    assert memory.temp_size_in_bytes <= 6 * row
    assert delta_rule._vmem_bytes(kern.heads_block, k_n) <= (
        delta_rule._VMEM_BLOCK_BUDGET) < delta_rule._VMEM_LIMIT_BYTES
    assert h_n % kern.heads_block == 0
    assert kern.grid == (s_n, h_n // kern.heads_block)


def _routed_layer(cell, kernel, sharding):
    """(an expert layer of the cell as its step traces it on a TPU:
    `lm_block.moe_ffn` with the grouped matmul and, under `kernel`, the
    choice kernel; its arguments as shapes)."""
    from test_moe_routing import cell_router, choice_kernel

    from paddle_tpu.kernels import grouped_matmul
    from paddle_tpu.models import lm_block

    spec, t_n, d = cell_router(cell)
    width, k_n = spec.n_experts + spec.zero_experts, spec.experts_per_token
    (_, e_n), d_ff = spec.held, {"ling": 768, "longcat": 2048}[
        cell.split("-")[0]]
    experts, refused = grouped_matmul.select_grouped_matmul(
        rows=t_n * k_n, d_model=d, d_ff=d_ff, n_experts=e_n,
        dtype=jnp.bfloat16, platform="tpu")
    assert refused is None
    choice, refused = choice_kernel(spec, t_n, platform="tpu")
    assert refused is None

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def layer(m, w_router, b_router, w_gate, w_up, w_down):
        return lm_block.moe_ffn(
            spec, m, w_router, w_gate, w_up, w_down, experts=experts,
            b_router=b_router, choice=choice if kernel else None)[:2]

    bf16 = jnp.bfloat16
    return layer, (sds((t_n, d)), sds((d, width)), sds((width,)),
                   sds((e_n, d, d_ff), bf16), sds((e_n, d, d_ff), bf16),
                   sds((e_n, d_ff, d), bf16))


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "passes"])
@pytest.mark.parametrize("cell", ["ling-3.0-flash-serve-agent128",
                                  "longcat-flash-serve-agent64"])
def test_routed_layer_compiles_for_a_v5e_without_a_sort(cell, kernel,
                                                        one_v5e):
    """From the router's matmul through `moe_combine` the compiled
    layer orders nothing: no `sort` (the TPU compiler's `top_k` and
    `argsort` are FULL sorts of the row: four a layer on Ling's cell
    until PR 63), with the choice kernel (one Mosaic call more than the
    experts' two) and with `_largest`'s passes, the fallback."""
    layer, args = _routed_layer(cell, kernel, one_v5e)
    text = jax.jit(layer).lower(*args).compile().as_text()
    assert " sort(" not in text and "sort." not in text
    assert text.count(MOSAIC_CALL) == 2 + kernel


@pytest.mark.parametrize("cell", [
    "olmoe-1b-7b-serve-chat32", "mellum2-12b-a2.5b-serve-agent96",
    "granite-4.0-h-small-serve-chat64", "k-exaone-236b-a23b-serve-chat64",
    "deepseek-v2-serve-agent64", "longcat-flash-serve-agent64",
    "glm-5.2-serve-docqa64", "lfm2-24b-a2b-serve-agent128",
    "solar-open2-250b-serve-docqa64", "ling-3.0-flash-serve-agent128"])
def test_router_choice_compiles_for_a_v5e(cell, one_v5e):
    """Mosaic's own compile at every expert cell's rows and router (a
    group of 20 experts is no multiple of a sublane tile: DeepSeek's),
    one call for the group limit and the k passes."""
    from test_moe_routing import cell_router, choice_kernel

    from paddle_tpu.kernels import router_choice

    spec, t_n, _ = cell_router(cell)
    width = spec.n_experts + spec.zero_experts
    kern, refused = choice_kernel(spec, t_n, platform="tpu")
    assert refused is None and kern.name == router_choice.NAME
    scores = jax.ShapeDtypeStruct((t_n, width), jnp.float32,
                                  sharding=one_v5e)
    text = jax.jit(kern.choose).lower(scores, scores).compile().as_text()
    assert text.count(MOSAIC_CALL) == 1 and " sort(" not in text


def test_index_selection_compiles_for_a_v5e(one_v5e):
    """Mosaic's own compile of the lightning indexer's selection at the
    two selecting cells' shape ([64 lanes, 6912 rows], k 2048): one
    call, the 64 lanes one block, and no loop of XLA's beside it."""
    from paddle_tpu.kernels import select_rows

    kern, refused = select_rows.select_index_selection(
        rows=6912, lanes=64, k=2048, platform="tpu")
    assert refused is None and kern.name == select_rows.NAME
    assert kern.lanes_block == 64
    scores = jax.ShapeDtypeStruct((64, 6912), jnp.float32, sharding=one_v5e)
    cursors = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_v5e)
    text = jax.jit(kern.select).lower(scores, cursors).compile().as_text()
    assert text.count(MOSAIC_CALL) == 1 and " while(" not in text


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "passes"])
def test_a_selecting_step_compiles_for_a_v5e_without_a_loop(kernel, one_v5e,
                                                            monkeypatch):
    """The served step of GLM's toy (the configuration file's rehearsal
    sizes on a table of 128 rows, 8 lanes) built for "tpu" and compiled
    for the described v5e: under `paged_decoder/indexer_topk` stand the
    selection's Mosaic calls, one a selecting layer, and NO `while`
    (`lm_block.select_rows`' 32 counts, whose keys the compiler's
    memory-space assignment may leave in HBM: PERF.md section 7, "From
    PR 68"); with the kernel refused the same scope holds the two loops
    again, which is what this test would miss were they renamed."""
    import os

    from paddle_tpu.kernels import select_rows
    from paddle_tpu.models import lm_block
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    if not kernel:
        monkeypatch.setattr(select_rows, "select_index_selection",
                            lambda **kw: (None, "refused_here"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "glm-5.2-1chip.json")) as f:
        m = json.load(f)
    m.update(m["rehearse"])
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    slots, bs, nb = 8, 8, 16
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], bs, nb, d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_layers=m["num_hidden_layers"],
        d_inner=m[b["d_inner"]], kv_dtype="bf16", platform="tpu", block=spec)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    g = {n: sds(s, jnp.bfloat16) for n, s in dec.state_shapes.items()}
    pools = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: dec.init_pool(slots * nb + 1)))
    text = dec.step.lower(
        g, *pools, sds((slots, nb)), sds((slots,)), sds((slots,)),
        sds((slots,), jnp.uint32), sds((slots,), jnp.float32),
        sds((slots,), jnp.bool_)).compile().as_text()
    assert dec.kernels["index_selection"] == (
        select_rows.NAME if kernel else "passes:refused_here")
    under = [line for line in text.splitlines()
             if "paged_decoder/indexer_topk" in line]
    loops = [line for line in under if " while(" in line]
    calls = [line for line in under if MOSAIC_CALL in line]
    assert (len(loops), len(calls)) == ((0, dec.index_planes) if kernel
                                        else (dec.index_planes, 0))


@pytest.mark.parametrize("shape,dtype,calls", [
    ((1, 8192, 6, 128), jnp.bfloat16, 2),   # the 1024x2048 block table
    ((1, 4096, 8, 64), jnp.bfloat16, 2),    # d64 head-pair packing
    ((1, 2560, 4, 128), jnp.float32, 2),    # non-default tile divisor
    ((1, 16384, 2, 64), jnp.bfloat16, 3),   # dq's scratch past its budget
])
def test_flash_attention_fwd_and_bwd_lower_for_tpu(shape, dtype, calls):
    q = jnp.zeros(shape, dtype)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, platform="tpu")
        return jnp.sum(out.astype(jnp.float32))

    text = lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # the forward and ONE backward kernel for dq, dk and dv; where the
    # head pair's dq does not fit VMEM, a dq and a dk/dv kernel
    assert text.count(MOSAIC_CALL) == calls


@pytest.mark.parametrize("shape,calls", [
    ((4, 2048, 32, 64), 2),     # opt-1.3b-train-seq2048: 4 sequences a step
    ((1, 8192, 32, 64), 2),     # the same tokens as one sequence
    ((1, 8192, 6, 128), 2),     # a head that fills the lanes alone
    ((1, 16384, 2, 64), 3),     # the dq kernel and the dk/dv kernel apart
])
def test_flash_attention_compiles_for_a_v5e(shape, calls, one_v5e):
    """Mosaic's own compile of the forward (statistics transposed to one
    lane a query) and of the backward (fused: dq's whole-sequence
    scratch, the product that contracts the tile's sublanes) with a
    causal tile's cases in it (`_causal_keys`: two bodies a kernel at
    these blocks, their score tiles stacked in VMEM).  At 8192 (blocks
    of 1024 x 2048) the fused backward takes 26.0 MiB of VMEM (24.8 at a
    head of 128) and the forward 17.8, which the calls ask for
    (`VMEM_LIMIT`, 32 MiB)."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_v5e)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, platform="tpu")
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert compiled.as_text().count(MOSAIC_CALL) == calls


def _compiled_step_text(main, feeds, loss, chip):
    """The compiled text of a training Program's step (`program_to_fn`,
    float32 states) for the described chip; feeds: name -> (shape,
    dtype)."""
    from paddle_tpu.core.executor import program_to_fn

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(int(i) for i in shape), dtype,
                                    sharding=chip)

    fn = program_to_fn(main, list(feeds), [loss.name])
    blk = main.global_block()
    states = {n: spec(blk.vars[n].shape, jnp.float32)
              for n in fn.state_in_names}
    return jax.jit(fn).lower(
        {n: spec(*f) for n, f in feeds.items()}, states,
        spec((), jax.random.key(0).dtype)).compile().as_text()


def test_training_step_runs_the_forward_kernel_once_a_layer(
        one_v5e, monkeypatch):
    """The guard for the op's own gradient: a one-layer training Program
    compiled for the described chip holds TWO Mosaic calls, the forward
    and the fused backward.  With the generic VJP grad it held four: the
    forward again under the grad op (XLA does not merge two Mosaic calls
    as it merges its own ops), a dq and a dk/dv kernel."""
    import paddle_tpu as fluid

    # what the lowerings ask where no executor drives them
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    b, s, h, d = 2, 256, 2, 64
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[s, h * d], dtype="float32")
        qkv = fluid.layers.fc(input=x, size=3 * h * d, num_flatten_dims=2)
        q, k, v = (fluid.layers.reshape(t, shape=[0, s, h, d]) for t in
                   fluid.layers.split(qkv, 3, dim=2))
        att = fluid.layers.flash_attention(q, k, v, causal=True,
                                           min_seq_k=0)
        loss = fluid.layers.mean(fluid.layers.square(att))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    text = _compiled_step_text(
        main, {"x": ((b, s, h * d), jnp.float32)}, loss, one_v5e)
    assert text.count(MOSAIC_CALL) == 2


@contextlib.contextmanager
def _time_limit(seconds):
    """Fail, not hang: SIGALRM raises in the test where Python runs."""
    def expired(signum, frame):
        raise TimeoutError(f"over its time limit of {seconds} s")

    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


LM_STEP = (2, 256, 4096)        # sequences, their length, the vocabulary


@pytest.fixture(scope="module")
def lm_step_text(one_v5e):
    """The compiled text of a small language model's AMP training step
    (one layer, Adam, `softmax_with_cross_entropy` on hard labels over
    2 x 256 tokens and 4096 words) for the described chip: compiled
    once for the guards below."""
    import paddle_tpu as fluid
    from paddle_tpu.models.transformer import transformer_lm

    b, s, vocab = LM_STEP
    main, startup = fluid.Program(), fluid.Program()
    with pytest.MonkeyPatch.context() as patch, _time_limit(120), \
            fluid.amp.bf16_guard():
        # what the lowerings ask where no executor drives them
        patch.setattr(jax, "default_backend", lambda: "tpu")
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data(name="ids", shape=[s], dtype="int64")
            lbl = fluid.layers.data(name="lbl", shape=[s, 1], dtype="int64")
            logits = transformer_lm(ids, vocab, d_model=128, n_heads=2,
                                    n_layers=1, d_inner=256, max_len=s,
                                    return_logits=True)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    fluid.layers.reshape(logits, shape=[-1, vocab]),
                    fluid.layers.reshape(lbl, shape=[-1, 1])))
            fluid.Adam(learning_rate=1e-3).minimize(loss)
        return _compiled_step_text(
            main, {"ids": ((b, s), jnp.int32),
                   "lbl": ((b, s, 1), jnp.int32)}, loss, one_v5e)


def test_lm_training_step_writes_no_float32_logits(lm_step_text):
    """The guard for the loss on hard labels: a small language model's
    AMP training step compiled for the described chip holds no float32
    array of [tokens, vocab], in either order, flattened or not.  The
    loss is logsumexp minus the picked logit over the bf16 logits; as
    log_softmax then take_along_axis it wrote the upcast logits and
    log_p whole and kept the first for the backward."""
    b, s, vocab = LM_STEP
    # what the entry computation's instructions write is what lives in
    # HBM; inside a fusion's body a float32 value is registers
    entry = lm_step_text[lm_step_text.index("\nENTRY "):]
    assert f"bf16[{b},{s},{vocab}]" in entry    # the logits themselves
    wide = [line.split(" = ")[0].strip() for line in entry.splitlines()
            if re.search(rf" = \(?[^=]*f32\[(?:{b},{s},{vocab}|{vocab},{b},{s}"
                         rf"|{b * s},{vocab}|{vocab},{b * s})\]", line)]
    assert not wide, wide


def test_lm_training_step_forms_the_loss_gradient_once(lm_step_text):
    """The guard for the written gradient: in the same compiled step
    exactly TWO instructions evaluate an exponential over [tokens,
    vocab], the forward's sum of exponentials and the ONE pass that
    writes `softmax - onehot`, and neither of the head's gradient
    products is one of them.  Left to itself the compiler clones that
    producer into the prologue of each consumer (both products and the
    bias's reduction: four), where a tile is formed anew for every
    output tile that reads it.  Counted by the benchmark's own reader
    (`perf/metrics/train_head_exp_passes.py`)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf", "metrics",
        "train_head_exp_passes.py")
    spec = importlib.util.spec_from_file_location("_exp_passes", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    b, s, vocab = LM_STEP
    passes = reader.exponential_passes(lm_step_text, b * s * vocab)
    assert len(passes) == 2, passes
    under_products = [
        name for name in passes
        if re.search(rf'%?{re.escape(name)} = [^\n]*op_name="[^"]*mul_grad:',
                     lm_step_text)]
    assert not under_products, under_products
