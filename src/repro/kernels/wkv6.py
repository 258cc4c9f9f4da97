"""RWKV6 wkv recurrence (data-dependent decay) as a chunked Pallas kernel.

TPU adaptation of the CUDA wkv6 kernel: instead of one thread per channel
stepping token-by-token, the sequence is cut into CH-token chunks; within
a chunk the recurrence is expanded into dense (CH x CH) decay-weighted
score matmuls (MXU work), and only the (dh x dh) state crosses chunks —
carried in VMEM scratch across the sequential chunk grid dimension.

Per chunk (log-space, exponents always <= 0 so arbitrary per-token decays
cannot overflow — see models/ssm.py for the same recurrence in jnp):
    la      = cumsum(lw)                        (CH, dh)
    y_intra = [(r_t·k_j) decayed by exp(la_{t-1}-la_j)]_{j<t} v
    y_bonus = (r_t·(u∘k_t)) v_t
    y_cross = (r_t ∘ exp(la_{t-1})) S
    S'      = S ∘ exp(la_CH) + Σ_j (k_j ∘ exp(la_CH - la_j))ᵀ v_j

Grid: (B*H, T/CH) with the chunk dim sequential; state scratch (dh, dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                y_ref, sT_ref, s_s):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        s_s[:] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)     # (CH, dh)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)     # (1, dh) -> broadcast
    S = s_s[:]
    ch = r.shape[0]

    la = jnp.cumsum(lw, axis=0)                     # (CH, dh) inclusive
    la_prev = la - lw                                # exclusive

    # intra-chunk: pairwise decay exp(la_prev[t] - la[j]) masked j < t
    ld = la_prev[:, None, :] - la[None, :, :]        # (CH, CH, dh)
    tri = jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 1) \
        < jax.lax.broadcasted_iota(jnp.int32, (ch, ch), 0)
    w_pair = jnp.where(tri[:, :, None], jnp.exp(ld), 0.0)
    scores = jnp.einsum("td,jd,tjd->tj", r, k, w_pair)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # bonus (j == t)
    y = y + (r * u * k).sum(axis=1, keepdims=True) * v
    # cross-chunk state contribution
    r_in = r * jnp.exp(la_prev)
    y = y + jax.lax.dot_general(r_in, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update
    k_out = k * jnp.exp(la[-1:] - la)
    s_s[:] = S * jnp.exp(la[-1])[:, None] + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(c == nc - 1)
    def _emit():
        sT_ref[0] = s_s[:].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, log_w, u, s0, chunk: int = DEFAULT_CHUNK,
         interpret: bool = False):
    """r/k/v/log_w: (B,T,H,dh) f32; u: (H,dh); s0: (B,H,dh,dh).
    -> (y (B,T,H,dh), s_T (B,H,dh,dh))."""
    B, T, H, dh = r.shape
    ch = min(chunk, T)
    pad = (-T) % ch

    def flat(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(B * H, T + pad, dh)

    r2, k2, v2, lw2 = map(flat, (r, k, v, log_w))
    u2 = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, 1, dh)
    s02 = s0.reshape(B * H, dh, dh)
    nc = (T + pad) // ch

    y2, sT = pl.pallas_call(
        _wkv_kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, ch, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ch, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dh, dh), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T + pad, dh), r.dtype),
            jax.ShapeDtypeStruct((B * H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(r2, k2, v2, lw2, u2, s02)

    y = y2[:, :T].reshape(B, H, T, dh).transpose(0, 2, 1, 3)
    return y, sT.reshape(B, H, dh, dh)
