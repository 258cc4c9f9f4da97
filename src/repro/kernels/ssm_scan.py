"""Mamba selective-scan h_t = a_t*h_{t-1} + b_t as a chunked Pallas kernel.

TPU adaptation: the GPU kernel's per-thread sequential scan becomes a
chunk-sequential grid with the (BD, N) state block in VMEM scratch; inside
a chunk the recurrence runs as a fori_loop over CH steps of (BD, N)
vector ops (the scan is elementwise — there is no MXU work to recover, so
the win is purely keeping h and the chunk's a/b tiles VMEM-resident
instead of round-tripping HBM per step).

The channel dim is blocked (BD) so d_inner=8192 models stream; grid is
(B, D/BD, T/CH) with time sequential ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
DEFAULT_BD = 256


def _scan_kernel(a_ref, b_ref, h0_ref, hs_ref, hT_ref, h_s):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_s[:] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)   # (CH, BD, N)
    b = b_ref[0].astype(jnp.float32)
    ch = a.shape[0]

    def step(t, h):
        h = a[t] * h + b[t]
        hs_ref[0, t] = h.astype(hs_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, ch, step, h_s[:])
    h_s[:] = h

    @pl.when(c == nc - 1)
    def _emit():
        hT_ref[0] = h.astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def ssm_scan(a, b, h0, chunk: int = DEFAULT_CHUNK, bd: int = DEFAULT_BD,
             interpret: bool = False):
    """a/b: (B,T,D,N) f32, h0: (B,D,N) -> (hs (B,T,D,N), h_T (B,D,N))."""
    B, T, D, N = a.shape
    ch = min(chunk, T)
    bd = min(bd, D)
    pad_t = (-T) % ch
    pad_d = (-D) % bd
    az = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_d), (0, 0)),
                 constant_values=1.0)
    bz = jnp.pad(b, ((0, 0), (0, pad_t), (0, pad_d), (0, 0)))
    h0z = jnp.pad(h0, ((0, 0), (0, pad_d), (0, 0)))
    Tp, Dp = T + pad_t, D + pad_d

    hs, hT = pl.pallas_call(
        _scan_kernel,
        grid=(B, Dp // bd, Tp // ch),
        in_specs=[
            pl.BlockSpec((1, ch, bd, N), lambda b_, d, c: (b_, c, d, 0)),
            pl.BlockSpec((1, ch, bd, N), lambda b_, d, c: (b_, c, d, 0)),
            pl.BlockSpec((1, bd, N), lambda b_, d, c: (b_, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, bd, N), lambda b_, d, c: (b_, c, d, 0)),
            pl.BlockSpec((1, bd, N), lambda b_, d, c: (b_, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Dp, N), a.dtype),
            jax.ShapeDtypeStruct((B, Dp, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(az, bz, h0z)
    return hs[:, :T, :D], hT[:, :D]
