"""Every Pallas kernel cross-lowered for TPU from the CPU.

The interpret-mode parity suites (test_serving_kernels.py,
test_flash_attention.py) prove the kernels' MATH; they say nothing
about whether the Pallas TPU lowering accepts the kernel at all, and
that is where the first chip bring-up found three of four serving
kernels refused (a dot with no free lhs dimension, a (1, 1) VMEM block
over a [layers, blocks] array, an in-kernel cumsum).  Lowering for the
"tpu" platform needs no TPU: `jit(f).trace(*args).lower(
lowering_platforms=("tpu",))` runs the whole Pallas->Mosaic lowering
on the host, in seconds.  What it cannot see is Mosaic's own compile
(VMEM fit, layouts) — chip_smoke.py covers that on the device.

Contract: at one geometry its `supports(platform="tpu")` accepts,
each registered kernel lowers to a Mosaic custom call; a kernel whose
predicate names a TPU reason is never picked there — it is a counted
fallback, not a trace-time crash.
"""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core.flags import get_flag, set_flags
from paddle_tpu.kernels import registry as kreg
from paddle_tpu.kernels.flash_attention import flash_attention

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


# kernel name -> [(selection ctx, example-args builder, call adapter)]:
# one entry per variant that has its own lowering path
CASES = {
    "paged_attention_decode": [
        (dict(d_model=D, n_heads=H, block_size=BS, max_blocks_per_seq=NB,
              kv_dtype=kv_dtype, window=window),
         lambda kv_dtype=kv_dtype, window=window:
             _paged_args(kv_dtype, window),
         lambda kern: lambda q, pk, pv, t, p: kern(q, pk, pv, t, p, 1))
        for kv_dtype in ("fp32", "bf16", "int8") for window in (1, 5)],
    "moe_gate_dispatch": [
        (dict(tokens=64, d_model=128, num_experts=4, capacity=32,
              top_k=2, dtype="float32"),
         lambda: (jnp.zeros((64, 128), jnp.float32),
                  jnp.zeros((128, 4), jnp.float32)),
         lambda kern: kern)],
    "fused_bucket_update": [
        (dict(numel=1_000_003, dtype="float32"),
         lambda: (jnp.zeros((1_000_003,), jnp.float32),
                  jnp.zeros((1_000_003,), jnp.float32), jnp.float32(0.1)),
         lambda kern: kern)],
}


@pytest.fixture
def armed_auto():
    """`serving_kernels=auto`: arms exactly where platform == "tpu"."""
    prev = get_flag("serving_kernels")
    set_flags({"serving_kernels": "auto"})
    yield
    set_flags({"serving_kernels": prev})


def test_every_registered_kernel_has_a_lowering_case():
    assert sorted(CASES) == sorted(kreg._REGISTRY), \
        "a newly registered kernel needs a TPU cross-lowering case here"


@pytest.mark.parametrize(
    "name,case", [(n, i) for n, cs in CASES.items()
                  for i in range(len(cs))])
def test_registered_kernel_lowers_for_tpu_or_is_a_named_fallback(
        name, case, armed_auto):
    ctx, make_args, adapt = CASES[name][case]
    kdef = kreg._REGISTRY[name]
    reason = kdef.supports(platform="tpu", **ctx)
    sel = kreg.Selection()
    try:
        picked = sel.pick(name, platform="tpu", **ctx)
        if reason is not None:
            # the predicate names a TPU reason: selection must route to
            # the oracle, counted under that name — never build
            assert picked is None
            assert sel.chosen[name] == f"xla:{reason}"
            return
        assert sel.chosen[name] == "pallas"
        assert MOSAIC_CALL in lower_tpu(adapt(picked), *make_args())
    finally:
        sel.close()


def test_moe_dispatch_is_refused_by_name_on_tpu():
    """Mosaic has no cumsum lowering; the predicate must say so at any
    geometry (this is the case the parametrized test above routes
    through its fallback branch — pinned here so a predicate that
    starts accepting TPU has to bring a kernel that lowers)."""
    ctx = CASES["moe_gate_dispatch"][0][0]
    assert kreg._REGISTRY["moe_gate_dispatch"].supports(
        platform="tpu", **ctx) == "mosaic_no_cumsum"
    assert kreg._REGISTRY["moe_gate_dispatch"].supports(
        platform="cpu", **ctx) is None


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
