"""Per-kernel benchmark: correctness sweep + VMEM/roofline accounting.

This container executes Pallas in interpret mode (no wall-clock value),
so each kernel reports its STRUCTURAL numbers for the TPU target instead:
tile shapes, VMEM working set, FLOPs, HBM bytes, arithmetic intensity,
and the v5e roofline bound implied (compute- vs bandwidth-limited) —
plus an allclose check against ref.py at benchmark shapes.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.launch.mesh import HBM_BW, PEAK_FLOPS_BF16

VMEM_BYTES = 128 * 2 ** 20  # v5e VMEM per core


def report(name, flops, hbm, vmem, err, note=""):
    ai = flops / max(hbm, 1)
    bound = "compute" if ai > PEAK_FLOPS_BF16 / HBM_BW else "bandwidth"
    ok = "OK " if vmem < VMEM_BYTES else "OVER"
    print(f"  {name:34s} flops={flops:9.3e} hbm={hbm:9.3e} "
          f"AI={ai:7.1f} ({bound}-bound) vmem={vmem/2**20:6.1f}MiB[{ok}] "
          f"max_err={err:.2e} {note}")


def bench_flash(fast):
    from repro.kernels.flash_attention import flash_attention
    shapes = [(1, 512, 8, 2, 128, 128, 128)] if fast else [
        (1, 512, 8, 2, 128, 128, 128),
        (1, 1024, 4, 1, 256, 128, 128),   # gemma-like kv=1
        (2, 512, 16, 16, 64, 128, 256),
    ]
    for B, T, H, Hkv, dh, bq, bk in shapes:
        q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, dh),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, dh),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, T, Hkv, dh),
                              jnp.bfloat16)
        got = flash_attention(q, k, v, bq=bq, bk=bk, interpret=True)
        want = ref.attention(q, k, v)
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32)).max())
        flops = 4.0 * B * H * T * T * dh / 2  # causal half
        hbm = 2 * (B * T * H * dh + 2 * B * T * Hkv * dh)
        vmem = (bq * dh + 2 * bk * dh) * 4 + bq * bk * 4 \
            + bq * dh * 4 + 2 * bq * 4
        report(f"flash_attn B{B} T{T} H{H}/{Hkv} dh{dh}", flops, hbm,
               vmem, err, f"tiles=({bq},{bk})")


def bench_paged_decode(fast):
    """Decode-shaped attention (q_len=1, long KV): the serving engine's
    hottest read.  Three implementations at the same shape:

      dense decode   — the contiguous engine's per-step read: the full
                       masked max_seq row (ref.attention semantics)
      paged gather   — ref.paged_attention: same O(max_seq) reads, page
                       indirection only (the CPU reference path)
      paged kernel   — kernels/paged_attention.py: walks only the live
                       pages, so HBM reads scale with len, not max_seq

    The reported HBM figures make the win visible structurally: the
    kernel's read volume is live/max_seq of the dense row.  allclose is
    checked against ref.attention's last causal row (the oracle the
    kernel test suite pins)."""
    from repro.kernels.paged_attention import paged_attention as pk
    shapes = [(4, 2048, 128, 64, 8, 2, 64)] if fast else [
        (4, 2048, 128, 64, 8, 2, 64),
        (8, 8192, 256, 128, 4, 1, 128),   # gemma-like kv=1, long budget
        (2, 4096, 512, 64, 16, 16, 64),   # MHA-shaped (MLA-expanded)
    ]
    rng = np.random.default_rng(0)
    for B, S_max, live, page, H, Hkv, dh in shapes:
        P = S_max // page
        n_pages = B * (live // page) + 1
        kp = rng.normal(size=(n_pages, page, Hkv, dh)).astype(np.float32)
        vp = rng.normal(size=(n_pages, page, Hkv, dh)).astype(np.float32)
        q = rng.normal(size=(B, H, dh)).astype(np.float32)
        table = np.full((B, P), n_pages, np.int32)
        ids = rng.permutation(n_pages - 1)
        per = live // page
        for b in range(B):
            table[b, :per] = ids[b * per:(b + 1) * per]
        lens = np.full((B,), live, np.int32)
        got = pk(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(table), jnp.asarray(lens), interpret=True)
        t = np.minimum(table[0], n_pages - 1)
        k0 = kp[t].reshape(S_max, Hkv, dh)[None, :live]
        v0 = vp[t].reshape(S_max, Hkv, dh)[None, :live]
        qf = np.zeros((1, live, H, dh), np.float32)
        qf[0, -1] = q[0]
        want = ref.attention(jnp.asarray(qf), jnp.asarray(k0),
                             jnp.asarray(v0))[0, -1]
        err = float(jnp.abs(got[0] - want).max())
        flops = 4.0 * B * H * live * dh
        hbm_dense = 4 * 2 * B * S_max * Hkv * dh   # full masked row, f32
        hbm_paged = 4 * 2 * B * live * Hkv * dh    # live pages only
        vmem = (H // Hkv * dh + 2 * page * dh) * 4 \
            + (H // Hkv) * (dh + 2) * 4
        report(f"paged_decode B{B} S{S_max} len{live} pg{page}", flops,
               hbm_paged, vmem, err,
               f"dense reads {hbm_dense/2**20:.1f}MiB -> paged "
               f"{hbm_paged/2**20:.1f}MiB ({S_max/live:.0f}x fewer)")

        # int8 variant: quantize the same pages per-token/per-head, feed
        # the kernel the int8 planes + f32 scale sidecars, compare with
        # the f32 answer above.  DMA moves 1-byte K/V elements plus one
        # f32 scale per (token, head) — ~4x fewer bytes at dh=64.
        from repro.models.attention import kv_quantize
        kq, ks = kv_quantize(jnp.asarray(kp), jnp.int8)
        vq, vs = kv_quantize(jnp.asarray(vp), jnp.int8)
        got_q = pk(jnp.asarray(q), kq, vq, jnp.asarray(table),
                   jnp.asarray(lens), k_scale=ks, v_scale=vs,
                   interpret=True)
        err_q = float(jnp.abs(got_q[0] - want).max())
        hbm_int8 = B * live * Hkv * (2 * 1 * dh + 2 * 4)  # planes+scales
        report(f"paged_decode int8 B{B} S{S_max} len{live}", flops,
               hbm_int8, vmem, err_q,
               f"f32 reads {hbm_paged/2**20:.2f}MiB -> int8 "
               f"{hbm_int8/2**20:.2f}MiB "
               f"({hbm_paged/hbm_int8:.1f}x fewer)")


def bench_distill(fast):
    from repro.kernels.distill_loss import fused_distill_loss
    shapes = [(256, 8192, 256, 512)] if fast else [
        (256, 8192, 256, 512), (512, 128256, 256, 512),
        (128, 262144, 128, 512)]
    for n, v, bn, bv in shapes:
        logits = jax.random.normal(jax.random.PRNGKey(0), (n, v)) * 2
        labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
        pseudo = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(2), (n, v)))
        got = float(fused_distill_loss(logits, labels, pseudo,
                                       jnp.float32(0.5), bn, bv,
                                       interpret=True))
        want = float(ref.distill_loss(logits, labels, pseudo, 0.5))
        flops = 6.0 * n * v
        hbm_fused = 2 * 4 * n * v          # one read of logits+pseudo
        vmem = bn * bv * 8 + bn * (4 * 4 + 4)
        report(f"distill_loss N{n} V{v}", flops, hbm_fused, vmem,
               abs(got - want),
               f"vs 2-pass: {2*hbm_fused/hbm_fused:.1f}x logit reads saved")


def bench_wkv(fast):
    from repro.kernels.wkv6 import wkv6
    shapes = [(1, 256, 4, 64, 32)] if fast else [
        (1, 256, 4, 64, 32), (2, 512, 8, 64, 32)]
    for B, T, H, dh, ch in shapes:
        mk = lambda i: jax.random.normal(jax.random.PRNGKey(i),  # noqa
                                         (B, T, H, dh))
        r, k, v = mk(0), mk(1), mk(2)
        lw = -jnp.exp(mk(3).clip(-3, 1))
        u = mk(4)[:, 0, :, :][0] * 0.3
        s0 = jnp.zeros((B, H, dh, dh))
        y, sT = wkv6(r, k, v, lw, u, s0, chunk=ch, interpret=True)
        yr, sr = ref.wkv6(r, k, v, lw, u, s0)
        err = float(jnp.abs(y - yr).max())
        flops = B * H * T * (2 * ch * dh + 4 * dh * dh)
        hbm = 4 * 4 * B * T * H * dh + 2 * 4 * B * H * dh * dh
        vmem = (4 * ch * dh + dh * dh + ch * ch * dh) * 4
        report(f"wkv6 B{B} T{T} H{H} dh{dh} ch{ch}", flops, hbm, vmem, err)


def bench_ssm(fast):
    from repro.kernels.ssm_scan import ssm_scan
    shapes = [(1, 256, 128, 16, 64, 128)] if fast else [
        (1, 256, 128, 16, 64, 128), (2, 512, 512, 16, 64, 256)]
    for B, T, D, N, ch, bd in shapes:
        a = jnp.exp(-jnp.abs(jax.random.normal(jax.random.PRNGKey(0),
                                               (B, T, D, N))))
        b = jax.random.normal(jax.random.PRNGKey(1), (B, T, D, N)) * 0.2
        h0 = jnp.zeros((B, D, N))
        hs, hT = ssm_scan(a, b, h0, chunk=ch, bd=bd, interpret=True)
        hr, hTr = ref.ssm_scan(a, b, h0)
        err = float(jnp.abs(hs - hr).max())
        flops = 3.0 * B * T * D * N
        hbm = 4 * (2 * B * T * D * N + B * T * D * N)  # a,b in; hs out
        vmem = (2 * ch * bd * N + bd * N) * 4
        report(f"ssm_scan B{B} T{T} D{D} N{N}", flops, hbm, vmem, err)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args(argv)
    print("# kernel benchmarks (interpret-mode correctness + v5e "
          "structural roofline)")
    bench_flash(args.fast)
    bench_paged_decode(args.fast)
    bench_distill(args.fast)
    bench_wkv(args.fast)
    bench_ssm(args.fast)
    return 0


if __name__ == "__main__":
    main()
