"""Every Pallas kernel cross-lowered for TPU from the CPU.

The interpret-mode parity suites (test_paged_attention.py,
test_flash_attention.py) prove the kernels' MATH; they say nothing
about whether the Pallas TPU lowering accepts the kernel at all, and
that is where the first chip bring-up found kernels refused (a dot
with no free lhs dimension, a (1, 1) VMEM block over a [layers,
blocks] array).  Lowering for the
"tpu" platform needs no TPU: `jit(f).trace(*args).lower(
lowering_platforms=("tpu",))` runs the whole Pallas->Mosaic lowering
on the host, in seconds.  What it cannot see is Mosaic's own compile
(VMEM fit, layouts) — chip_smoke.py covers that on the device.

Contract: at a geometry `select_paged_attention(platform="tpu")`
accepts, the kernel it returns lowers to a Mosaic custom call, for
every pool dtype and window; the flash kernels lower forward and
backward.
"""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.kernels.flash_attention import flash_attention
from paddle_tpu.kernels.paged_attention import select_paged_attention

MOSAIC_CALL = "tpu_custom_call"

# the serving leg's decoder geometry (chip_smoke.py): d_model 1024,
# 8 heads x 128, 16-position blocks x 32 per sequence, 8 slots
D, H, BS, NB, S, L = 1024, 8, 16, 32, 8, 2
NBLK = S * NB + 1


def lower_tpu(f, *args):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _pool(kv_dtype):
    if kv_dtype == "int8":
        return (jnp.zeros((L, NBLK, BS, D), jnp.int8),
                jnp.ones((L, NBLK), jnp.float32))
    return jnp.zeros((L, NBLK, BS, D),
                     jnp.bfloat16 if kv_dtype == "bf16" else jnp.float32)


def _paged_args(kv_dtype, window):
    pool = _pool(kv_dtype)
    return (jnp.zeros((S, window, D), jnp.float32), pool, pool,
            jnp.zeros((S, NB), jnp.int32), jnp.zeros((S,), jnp.int32))


@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_attention_lowers_for_tpu(kv_dtype, window):
    kern, reason = select_paged_attention(
        d_model=D, n_heads=H, block_size=BS, max_blocks_per_seq=NB,
        kv_dtype=kv_dtype, platform="tpu")
    assert reason is None
    text = lower_tpu(lambda q, pk, pv, t, p: kern(q, pk, pv, t, p, 1),
                     *_paged_args(kv_dtype, window))
    assert MOSAIC_CALL in text


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
