"""Paged-attention decode as a Pallas TPU kernel.

The serving engine's paged KV pool stores each slot's cache as a chain
of fixed-size pages (serving/kv_cache.PageAllocator); this kernel is
the decode-step attention over that pool: one query per slot, KV read
through the slot's page table.

Grid (B * Hkv, P): one kernel instance streams one (slot, kv-head)'s
live pages sequentially with the (m, l, acc) online-softmax state in
VMEM scratch (the flash_attention recurrence), emitting acc / l at the
last page.  GQA rides the same way as kernels/flash_attention.py: the
g grouped q heads of a kv head form the row dimension, so each page is
fetched ONCE for all g heads.

The page table and per-slot lengths are scalar-prefetched
(pltpu.PrefetchScalarGridSpec), so the BlockSpec index_map — not the
kernel body — resolves logical page j of slot b to the physical page
`table[b, j]`: the pipeline DMAs exactly the pages the slot owns.  Two
properties make the read volume O(len) instead of O(max_seq):

  - grid step j of a slot with `live = ceil(len / page)` pages clamps
    its index_map to the last live page for j >= live; consecutive
    identical block indices are not re-fetched by the pipeline, so dead
    trailing pages cost no DMA;
  - the kernel body skips compute for j >= live via pl.when.

Unallocated table entries (sentinel >= n_pages) are clamped in the
index_map and masked by the position bookkeeping (k_pos < len), so a
partially-grown slot reads garbage it then multiplies by exactly 0.

Supports dk != dv (MLA-shaped heads: the expanded latent has 192-d keys
and 128-d values) and sliding-window masking.  interpret=True runs the
same program on CPU — that is what CI tests against kernels/ref
.paged_attention and ref.attention.  A production kernel would also
fuse the new token's KV scatter; here the scatter is a jnp one-liner in
models/attention.gqa_decode_paged and the kernel only reads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                  page, hkv, scale, window, quant, extra):
    opt = iter(rest[:-4])
    ks_ref = next(opt) if quant else None
    vs_ref = next(opt) if quant else None
    ke_ref = next(opt) if extra else None
    o_ref, m_s, l_s, acc_s = rest[-4:]
    bh = pl.program_id(0)
    j = pl.program_id(1)
    b = bh // hkv

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    length = lens_ref[b]
    live = (length + page - 1) // page

    @pl.when(j < live)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)      # (g, dk [+ dr])
        k = k_ref[0, 0].astype(jnp.float32)      # (page, dk)
        v = v_ref[0, 0].astype(jnp.float32)      # (page, dv)
        g = q.shape[0]
        dk = k.shape[1]
        s = jax.lax.dot_general(q[:, :dk], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quant:
            # per-token absmax scales ride next to the page as a (1, page)
            # row; a token's scale factors out of its score and its value
            # row, so dequant is one multiply per score, not per element
            s = s * ks_ref[0, 0]
        if extra:
            # unquantized extra key features (absorbed-MLA rope keys):
            # score = q_main . k_deq + q_extra . k_extra
            s = s + jax.lax.dot_general(
                q[:, dk:], ke_ref[0, 0].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * scale
        k_pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        ok = k_pos < length
        if window > 0:
            ok = ok & (k_pos > length - 1 - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_s[:], s.max(axis=1))
        alpha = jnp.exp(m_s[:] - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_s[:] = l_s[:] * alpha + p.sum(axis=1)
        if quant:
            p = p * vs_ref[0, 0]                 # p @ (v * s) == (p * s) @ v
        acc_s[:] = acc_s[:] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        o_ref[0, 0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def paged_attention(q, k_pages, v_pages, table, lens, window: int = 0,
                    scale: float | None = None, k_scale=None, v_scale=None,
                    k_extra=None, interpret: bool = False):
    """q: (B, H, dk); k_pages: (n_pages, page, Hkv, dk); v_pages:
    (n_pages, page, Hkv, dv); table: (B, P) int32 (>= n_pages means
    unallocated); lens: (B,) int32 valid entries -> (B, H, dv).

    Quantized pools pass k_scale/v_scale (n_pages, page, Hkv) per-token
    absmax scales; each scale page is a tiny extra input block indexed
    by the SAME table lookup as its plane, and dequant happens in VMEM
    after the ~4x-smaller quantized DMA (a token's scale multiplies its
    score and its softmax weight).  k_extra
    (n_pages, page, Hkv, dr) is an unquantized extra key block
    (absorbed-MLA rope keys); q then carries dk + dr features and the
    score is the sum of the two dots.  All three default to None ==
    today's exact unquantized program."""
    B, H, dkq = q.shape
    n_pages, page, Hkv, dk = k_pages.shape
    dv = v_pages.shape[-1]
    g = H // Hkv
    P = table.shape[1]
    scale = scale if scale is not None else dkq ** -0.5
    quant = k_scale is not None
    extra = k_extra is not None

    q2 = q.reshape(B, Hkv, g, dkq)                    # group-major rows
    kp = k_pages.transpose(0, 2, 1, 3)                # (n_pages, Hkv, page, dk)
    vp = v_pages.transpose(0, 2, 1, 3)

    def kv_index(bh, j, table_ref, lens_ref):
        b, h = bh // Hkv, bh % Hkv
        live = (lens_ref[b] + page - 1) // page
        # clamp dead trailing grid steps onto the last live page: the
        # pipeline skips the re-fetch of an unchanged block index, so a
        # slot's DMA volume is its LIVE pages, not P
        jj = jnp.minimum(j, jnp.maximum(live - 1, 0))
        phys = jnp.clip(table_ref[b, jj], 0, n_pages - 1)
        return (phys, h, 0, 0)

    def scale_index(bh, j, table_ref, lens_ref):
        phys, h, _, _ = kv_index(bh, j, table_ref, lens_ref)
        return (phys, h, 0, 0)

    def q_index(bh, j, table_ref, lens_ref):
        return (bh // Hkv, bh % Hkv, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, dkq), q_index),
        pl.BlockSpec((1, 1, page, dk), kv_index),
        pl.BlockSpec((1, 1, page, dv), kv_index),
    ]
    operands = [q2, kp, vp]
    if quant:
        # scales as (n_pages, Hkv, 1, page): the unit axis makes the
        # block's last two dims (1, page) equal the array's, which the
        # TPU tiling rule needs of a block narrower than (8, 128)
        in_specs += [pl.BlockSpec((1, 1, 1, page), scale_index),
                     pl.BlockSpec((1, 1, 1, page), scale_index)]
        operands += [k_scale.transpose(0, 2, 1)[:, :, None, :],
                     v_scale.transpose(0, 2, 1)[:, :, None, :]]
    if extra:
        dr = k_extra.shape[-1]
        in_specs += [pl.BlockSpec((1, 1, page, dr), kv_index)]
        operands += [k_extra.transpose(0, 2, 1, 3)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * Hkv, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dv), q_index),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dv), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, page=page, hkv=Hkv,
                             scale=scale, window=window,
                             quant=quant, extra=extra)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, dv), q.dtype),
        interpret=interpret,
        name="paged_attention",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(table.astype(jnp.int32), lens.astype(jnp.int32), *operands)
    return out.reshape(B, H, dv)
