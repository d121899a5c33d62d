"""Every Pallas kernel cross-lowered for TPU from the CPU, and the
paged-attention kernel compiled for a described v5e.

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
geometry; the flash kernels lower forward and backward.
"""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import select_paged_attention

MOSAIC_CALL = "tpu_custom_call"

# name: slots, query heads, pool row, page rows, table pages, layers,
# pool dtype, scale.  chip_smoke.py's serving leg (d_model 1024, 8
# heads of 128, 8 slots) and the four serving cells' tables and ring at
# their real sizes (perf/configs, perf/traffic)
POOLS = {
    "chip_smoke-fp32": (8, 8, 1024, 16, 32, 2, "fp32"),
    "chip_smoke-bf16": (8, 8, 1024, 16, 32, 2, "bf16"),
    "opt-1.3b-closed32": (32, 32, 2048, 16, 32, 24, "bf16"),
    "olmoe-chat32": (32, 16, 2048, 16, 64, 12, "bf16"),
    "mellum2-agent96-table": (96, 32, 512, 16, 256, 2, "bf16"),
    "mellum2-agent96-ring": (96, 32, 512, 16, 64, 6, "bf16"),
    "granite-chat64": (64, 32, 1024, 16, 64, 1, "bf16"),
}


def lower_tpu(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _paged(name, sharding=None):
    """(a function that attends over every layer of pool `name`
    through the selected kernel, its arguments as shapes)."""
    s_n, h, d_kv, bs, nb, layers, kv_dtype = POOLS[name]
    kern, reason = select_paged_attention(
        d_model=d_kv, n_heads=h, block_size=bs, max_blocks_per_seq=nb,
        kv_dtype=kv_dtype, platform="tpu")
    assert reason is None
    dtype = jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    pool = shape((layers, s_n * nb + 1, bs, d_kv), dtype)

    def attend(q, pool_k, pool_v, tables, lengths):
        # layer upon layer, as a step's are: each query from the last
        # layer's result
        for layer in range(layers):
            out = kern(q, pool_k, pool_v, tables, lengths, layer, 0.125)
            q = (q + out).astype(dtype)
        return out

    return attend, (shape((s_n, h, d_kv), dtype), pool, pool,
                    shape((s_n, nb), jnp.int32), shape((s_n,), jnp.int32))


@pytest.mark.parametrize("name", sorted(POOLS))
def test_paged_attention_lowers_for_tpu(name):
    """One Mosaic module for all the layers of a program: the call sits
    behind a module-level `jax.jit` with the layer a traced scalar."""
    attend, args = _paged(name)
    text = lower_tpu(attend, *args)
    assert text.count(MOSAIC_CALL) == 1


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
    compiled = jax.jit(attend).lower(*args).compile()
    assert MOSAIC_CALL in compiled.as_text()
    s_n, h, d_kv = args[0].shape
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        4 * s_n * h * d_kv * 4


@pytest.mark.parametrize("shape,dtype", [
    ((1, 8192, 6, 128), jnp.bfloat16),   # the 1024x2048 block table
    ((1, 4096, 8, 64), jnp.bfloat16),    # d64 head-pair packing
    ((1, 2560, 4, 128), jnp.float32),    # non-default tile divisor
])
def test_flash_attention_fwd_and_bwd_lower_for_tpu(shape, dtype):
    q = jnp.zeros(shape, dtype)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, platform="tpu")
        return jnp.sum(out.astype(jnp.float32))

    text = lower_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    # forward, dq and dk/dv are three separate Mosaic kernels
    assert text.count(MOSAIC_CALL) == 3
