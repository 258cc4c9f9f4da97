"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.distill_loss import fused_distill_loss
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.wkv6 import wkv6


def tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [
    # (B, T, S, H, Hkv, dh)
    (1, 17, 17, 4, 4, 32),     # MHA, odd seq
    (2, 64, 64, 8, 2, 64),     # GQA
    (1, 130, 130, 4, 1, 128),  # kv=1 (gemma-like), unaligned seq
    (2, 32, 96, 4, 4, 32),     # cross-ish: kv longer than q
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 13),
                                           (False, 0)])
def test_flash_attention_sweep(shape, dtype, causal, window):
    B, T, S, H, Hkv, dh = shape
    if S != T and causal:
        pytest.skip("causal requires aligned positions in this harness")
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, T, H, dh), dtype)
    kk = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, dh), dtype)
    vv = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, dh), dtype)
    got = flash_attention(q, kk, vv, causal=causal, window=window,
                          bq=32, bk=32, interpret=True)
    want = ref.attention(q, kk, vv, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.parametrize("n,v,bn,bv", [
    (8, 100, 8, 32), (33, 517, 16, 128), (64, 2048, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distill_loss_sweep(n, v, bn, bv, dtype):
    k = jax.random.PRNGKey(0)
    logits = (jax.random.normal(k, (n, v)) * 3).astype(dtype)
    labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
    pseudo = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(2), (n, v))).astype(dtype)
    lam = jnp.float32(0.4)
    got = fused_distill_loss(logits, labels, pseudo, lam, bn, bv,
                             interpret=True)
    want = ref.distill_loss(logits, labels, pseudo, lam)
    np.testing.assert_allclose(float(got), float(want), rtol=3e-3)


def test_distill_loss_grad_matches():
    n, v = 24, 300
    logits = jax.random.normal(jax.random.PRNGKey(0), (n, v))
    labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
    pseudo = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (n, v)))
    lam = jnp.float32(0.8)
    gf = jax.grad(lambda z: fused_distill_loss(z, labels, pseudo, lam,
                                                interpret=True))(logits)
    gr = jax.grad(lambda z: ref.distill_loss(z, labels, pseudo, lam))(logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-6)


@pytest.mark.parametrize("shape", [
    (1, 16, 2, 8), (2, 50, 3, 16), (1, 100, 1, 64)])  # (B,T,H,dh)
@pytest.mark.parametrize("chunk", [8, 32])
def test_wkv6_sweep(shape, chunk):
    B, T, H, dh = shape
    k = jax.random.PRNGKey(0)
    mk = lambda i: jax.random.normal(jax.random.PRNGKey(i),  # noqa: E731
                                     (B, T, H, dh))
    r, kk, vv = mk(1), mk(2), mk(3)
    lw = -jnp.exp(mk(4).clip(-3, 2))  # strong + weak decays
    u = jax.random.normal(jax.random.PRNGKey(5), (H, dh)) * 0.3
    s0 = jax.random.normal(jax.random.PRNGKey(6), (B, H, dh, dh)) * 0.1
    y_got, s_got = wkv6(r, kk, vv, lw, u, s0, chunk=chunk,
                        interpret=True)
    y_ref, s_ref = ref.wkv6(r, kk, vv, lw, u, s0)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_ref),
                               atol=5e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("shape", [(1, 16, 8, 4), (2, 37, 24, 8),
                                   (1, 128, 64, 16)])  # (B,T,D,N)
@pytest.mark.parametrize("chunk,bd", [(16, 16), (64, 256)])
def test_ssm_scan_sweep(shape, chunk, bd):
    B, T, D, N = shape
    k = jax.random.PRNGKey(0)
    a = jnp.exp(-jnp.abs(jax.random.normal(k, (B, T, D, N))))
    b = jax.random.normal(jax.random.PRNGKey(1), (B, T, D, N)) * 0.2
    h0 = jax.random.normal(jax.random.PRNGKey(2), (B, D, N)) * 0.1
    hs_got, hT_got = ssm_scan(a, b, h0, chunk=chunk, bd=bd,
                              interpret=True)
    hs_ref, hT_ref = ref.ssm_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(hs_got), np.asarray(hs_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(hT_got), np.asarray(hT_ref),
                               atol=1e-5, rtol=1e-5)


def test_model_chunked_paths_match_refs():
    """models/ssm.py's chunked jnp forms == sequential oracles."""
    from repro.configs import registry
    from repro.models import ssm as mssm
    cfg = registry.get_config("rwkv6-7b", reduced=True)
    B, T, d = 2, 40, cfg.d_model
    H, dh = mssm.rwkv_dims(cfg)
    p = mssm.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, d)) * 0.5
    r, k, v, g, lw = mssm._rwkv_proj(
        p, x, jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :T], cfg)
    hd = lambda t: t.astype(jnp.float32).reshape(B, T, H, dh)  # noqa: E731
    s0 = jnp.zeros((B, H, dh, dh))
    y_c, s_c = mssm._wkv_chunked(hd(r), hd(k), hd(v), hd(lw),
                                 p["rwkv_first"], s0)
    y_r, s_r = ref.wkv6(hd(r), hd(k), hd(v), hd(lw), p["rwkv_first"], s0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                               atol=1e-4, rtol=1e-3)
