"""Fused dual-CE distillation loss (paper Eqn 9) as a Pallas TPU kernel.

    L_i = (1+lam)*logsumexp(z_i) - z_i[y_i] - lam * <p̄_i, z_i>

EC-DNN evaluates this loss every step of the compression phase over LM
vocabs up to 262k — the naive form materializes log_softmax (N, V) f32 and
reads the logits twice (once for the true-label CE, once for the pseudo
CE).  This kernel streams the vocabulary through VMEM in (BN, BV) tiles,
maintaining per-row online-logsumexp, gold-logit and <p̄, z> accumulators
in scratch, so HBM traffic is exactly one read of logits + pseudo —
2x fewer logits bytes than the two-pass form and no (N, V) f32 temporary.

Backward is a second single-pass kernel: given the saved row lse,
    dL/dz = g/N * ((1+lam)*exp(z - lse) - onehot(y) - lam*p̄)
(elementwise per tile; no extra reductions), wired via jax.custom_vjp.

Grid: (N/BN, V/BV), vocab dim sequential ("arbitrary") for the running
accumulators; rows parallel.  BV=512 keeps the working set
(BN*BV*(logits+pseudo)*4B ≈ 2 MB at BN=512) inside one core's VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BN = 256
DEFAULT_BV = 512
NEG_INF = -2.0 ** 30


def _fwd_kernel(labels_ref, logits_ref, pseudo_ref,
                lse_ref, gold_ref, dot_ref, m_s, l_s, g_s, d_s):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        g_s[:] = jnp.zeros_like(g_s)
        d_s[:] = jnp.zeros_like(d_s)

    z = logits_ref[:].astype(jnp.float32)           # (BN, BV)
    p = pseudo_ref[:].astype(jnp.float32)
    bn, bv = z.shape

    m_old = m_s[:]
    m_new = jnp.maximum(m_old, z.max(axis=1))
    alpha = jnp.exp(m_old - m_new)
    l_s[:] = l_s[:] * alpha + jnp.exp(z - m_new[:, None]).sum(axis=1)
    m_s[:] = m_new
    d_s[:] = d_s[:] + (p * z).sum(axis=1)

    # gold gather: label relative to this vocab tile
    y = labels_ref[:, 0] - j * bv                   # (BN,)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    hit = cols == y[:, None]
    g_s[:] = g_s[:] + jnp.where(hit, z, 0.0).sum(axis=1)

    @pl.when(j == nv - 1)
    def _emit():
        lse_ref[:, 0] = m_s[:] + jnp.log(jnp.maximum(l_s[:], 1e-30))
        gold_ref[:, 0] = g_s[:]
        dot_ref[:, 0] = d_s[:]


def _bwd_kernel(labels_ref, lse_ref, gcoef_ref, logits_ref, pseudo_ref,
                dz_ref):
    j = pl.program_id(1)
    z = logits_ref[:].astype(jnp.float32)
    p = pseudo_ref[:].astype(jnp.float32)
    bn, bv = z.shape
    lse = lse_ref[:, 0]
    g = gcoef_ref[0, 0]       # upstream grad / N
    lam = gcoef_ref[0, 1]
    soft = jnp.exp(z - lse[:, None])
    y = labels_ref[:, 0] - j * bv
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    onehot = (cols == y[:, None]).astype(jnp.float32)
    dz_ref[:] = (g * ((1.0 + lam) * soft - onehot - lam * p)
                 ).astype(dz_ref.dtype)


def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def fused_distill_loss(logits, labels, pseudo, lam,
                       bn=DEFAULT_BN, bv=DEFAULT_BV, interpret=False):
    loss, _ = _fwd(logits, labels, pseudo, lam, bn, bv, interpret)
    return loss


def _parts(logits, labels, pseudo, bn, bv, interpret):
    """Run the forward kernel over flattened rows. -> (lse, gold, dot)."""
    V = logits.shape[-1]
    z2 = logits.reshape(-1, V)
    p2 = pseudo.reshape(-1, V)
    y2 = labels.reshape(-1, 1).astype(jnp.int32)
    N = z2.shape[0]
    bn = min(bn, max(8, N))
    z2 = _pad_to(_pad_to(z2, bn, 0, value=0.0), bv, 1, value=NEG_INF)
    p2 = _pad_to(_pad_to(p2, bn, 0), bv, 1)
    y2 = _pad_to(y2, bn, 0)
    Np, Vp = z2.shape
    grid = (Np // bn, Vp // bv)
    out_shape = [jax.ShapeDtypeStruct((Np, 1), jnp.float32)] * 3
    lse, gold, dot = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bn, 1), lambda i, j: (i, 0))] * 3,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bn,), jnp.float32)] * 4,
        interpret=interpret,
        name="distill_loss_fwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(y2, z2, p2)
    return (lse[:N, 0], gold[:N, 0], dot[:N, 0]), (z2, p2, y2, Np, Vp, N)


def _fwd(logits, labels, pseudo, lam, bn, bv, interpret):
    (lse, gold, dot), aux = _parts(logits, labels, pseudo, bn, bv,
                                   interpret)
    lam_f = jnp.asarray(lam, jnp.float32)
    loss = ((1.0 + lam_f) * lse - gold - dot * lam_f).mean()
    res = (logits, labels, pseudo, lam_f, lse)
    return loss, res


def _bwd(bn, bv, interpret, res, g):
    logits, labels, pseudo, lam_f, lse = res
    V = logits.shape[-1]
    z2 = logits.reshape(-1, V)
    p2 = pseudo.reshape(-1, V)
    y2 = labels.reshape(-1, 1).astype(jnp.int32)
    N = z2.shape[0]
    bn_ = min(bn, max(8, N))
    z2p = _pad_to(_pad_to(z2, bn_, 0), bv, 1, value=NEG_INF)
    p2p = _pad_to(_pad_to(p2, bn_, 0), bv, 1)
    y2p = _pad_to(y2, bn_, 0, value=-1)
    lse_p = _pad_to(lse.reshape(-1, 1), bn_, 0)
    Np, Vp = z2p.shape
    gcoef = jnp.stack([g / N, lam_f]).reshape(1, 2)
    dz = pl.pallas_call(
        _bwd_kernel,
        grid=(Np // bn_, Vp // bv),
        in_specs=[
            pl.BlockSpec((bn_, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((bn_, bv), lambda i, j: (i, j)),
            pl.BlockSpec((bn_, bv), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn_, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Vp), logits.dtype),
        interpret=interpret,
        name="distill_loss_bwd",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(y2p, lse_p, gcoef, z2p, p2p)
    dz = dz[:N, :V].reshape(logits.shape)
    return dz, None, None, None


fused_distill_loss.defvjp(_fwd, _bwd)
