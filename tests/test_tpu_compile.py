"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (the rest of the kernel suite) cannot see the chip
compiler's rules: block tiling, VMEM limits, dtype support.  These tests
hand the kernels shapes on a DESCRIBED v5e chip (no chip attached) and
compile them ahead of time, so a kernel the chip would refuse fails here.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest-xdist
worker imports every test file.  The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import distill_loss as dl
from repro.kernels import paged_attention as pa
from repro.models import attention as attn_mod

# serving shapes: 8 slots of up to 1,024 tokens in 16-token pages
SLOTS, MAX_SEQ, PAGE = 8, 1024, 16
PAGES_PER_SLOT = MAX_SEQ // PAGE
N_PAGES = SLOTS * PAGES_PER_SLOT


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _paged_args(one_chip, heads, kv_heads, dk, dv, dq, kv_dtype, dr=0):
    """Abstract operands of one decode call over a full pool."""
    store = attn_mod.kv_storage_dtype(kv_dtype, jnp.bfloat16)
    args = [_spec((SLOTS, heads, dq), jnp.bfloat16, one_chip),
            _spec((N_PAGES, PAGE, kv_heads, dk), store, one_chip),
            _spec((N_PAGES, PAGE, kv_heads, dv), store, one_chip),
            _spec((SLOTS, PAGES_PER_SLOT), jnp.int32, one_chip),
            _spec((SLOTS,), jnp.int32, one_chip)]
    quant = attn_mod.kv_quantized(kv_dtype)
    scales = ([_spec((N_PAGES, PAGE, kv_heads), jnp.float32, one_chip)] * 2
              if quant else [None, None])
    extra = (_spec((N_PAGES, PAGE, kv_heads, dr), jnp.bfloat16, one_chip)
             if dr else None)
    return args, scales, extra


@pytest.mark.parametrize("kv_dtype", ["f32", "int8", "fp8"])
def test_paged_gqa_decode_compiles_at_deepseek_7b_widths(one_chip, kv_dtype):
    """32 kv heads x 128 (kv == heads); 'f32' stores the model's bf16."""
    args, (ks, vs), _ = _paged_args(one_chip, 32, 32, 128, 128, 128,
                                    kv_dtype)
    fn = lambda q, k, v, t, n, ks, vs: pa.paged_attention(  # noqa: E731
        q, k, v, t, n, scale=128 ** -0.5, k_scale=ks, v_scale=vs)
    _assert_kernel(jax.jit(fn).lower(*args, ks, vs).compile())


def test_paged_absorbed_mla_decode_compiles_at_deepseek_v2_widths(one_chip):
    """Latent 512 + rope 64 over one latent 'kv head', 128 query heads."""
    args, _, extra = _paged_args(one_chip, 128, 1, 512, 512, 512 + 64,
                                 "f32", dr=64)
    fn = lambda q, k, v, t, n, ke: pa.paged_attention(  # noqa: E731
        q, k, v, t, n, scale=(128 + 64) ** -0.5, k_extra=ke)
    _assert_kernel(jax.jit(fn).lower(*args, extra).compile())


@pytest.mark.parametrize("vocab", [100, 102400])
def test_fused_distill_loss_fwd_bwd_compiles_vmapped(one_chip, vocab):
    """K=4 members' Eqn-9 loss and its gradient in one program, at
    NiN's 100 classes and deepseek-7b's vocabulary."""
    K, N = 4, 256
    logits = _spec((K, N, vocab), jnp.bfloat16, one_chip)
    labels = _spec((K, N), jnp.int32, one_chip)
    pseudo = _spec((K, N, vocab), jnp.float32, one_chip)
    lam = _spec((K,), jnp.float32, one_chip)
    loss_grad = jax.vmap(jax.value_and_grad(dl.fused_distill_loss))
    _assert_kernel(jax.jit(loss_grad).lower(logits, labels, pseudo,
                                            lam).compile())
