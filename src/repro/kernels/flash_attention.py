"""Flash attention (tiled online-softmax) as a Pallas TPU kernel.

Supports causal + sliding-window masks and GQA natively: q heads are
grouped by their kv head and flattened into the row dimension, so one
kernel instance streams one (batch, kv-head)'s KV once for all g grouped
q heads — KV HBM traffic is 1/g of an MHA-layout kernel, which is the
whole point of GQA on a bandwidth-limited chip.

Layout: q2 (BH, g*T, dh), kv2 (BH, S, dh) where BH = B*Hkv.  Row r of q2
is query position r % T (g-major flattening), which makes the causal /
window mask position-exact even when a row block spans two q heads.

Grid (BH, q_blocks, kv_blocks); kv dim is sequential ("arbitrary") with
the (m, l, acc) online-softmax state in VMEM scratch, emitted as
acc / l at the last kv block.  Block sizes default to (128, 128) — MXU
aligned; dh rides along whole (128 or 256 for the assigned archs).

A production causal kernel would also prune fully-masked upper-triangle
kv blocks via a q-block-dependent grid bound; correctness is identical,
so the oracle sweep (tests/test_kernels.py) covers this version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                 scale, t_q, s_valid, causal, window):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)               # (BQ, dh)
    k = k_ref[0].astype(jnp.float32)               # (BK, dh)
    v = v_ref[0].astype(jnp.float32)
    bq, bk = q.shape[0], k.shape[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    q_pos = rows % t_q                              # g-major flattening
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < s_valid                            # mask KV padding
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window > 0:
        ok = ok & (k_pos > q_pos - window)
    s = jnp.where(ok, s, NEG_INF)

    m_old = m_s[:]
    m_new = jnp.maximum(m_old, s.max(axis=1))
    alpha = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_s[:] = l_s[:] * alpha + p.sum(axis=1)
    acc_s[:] = acc_s[:] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[:] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        o_ref[0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _pad_axis(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    scale: float | None = None, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False):
    """q: (B,T,H,dh), k/v: (B,S,Hkv,dh) -> (B,T,H,dh)."""
    B, T, H, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = scale if scale is not None else dh ** -0.5

    # group-major flatten: (B*Hkv, g*T, dh)
    q2 = q.reshape(B, T, Hkv, g, dh).transpose(0, 2, 3, 1, 4) \
        .reshape(B * Hkv, g * T, dh)
    k2 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dh)
    v2 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dh)

    bq_ = min(bq, g * T)
    bk_ = min(bk, S)
    q2 = _pad_axis(q2, bq_, 1)
    k2 = _pad_axis(k2, bk_, 1)
    v2 = _pad_axis(v2, bk_, 1)
    gt, sp = q2.shape[1], k2.shape[1]

    kern = functools.partial(_attn_kernel, scale=scale, t_q=T, s_valid=S,
                             causal=causal, window=window)
    o2 = pl.pallas_call(
        kern,
        grid=(B * Hkv, gt // bq_, sp // bk_),
        in_specs=[
            pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, gt, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, dh), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q2, k2, v2)

    o2 = o2[:, : g * T]
    return o2.reshape(B, Hkv, g, T, dh).transpose(0, 3, 1, 2, 4) \
        .reshape(B, T, H, dh)
