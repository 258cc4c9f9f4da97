"""Plain reference of a dense decoder LM ensemble (llama layout), in
float32 at full matmul precision: the yardstick the served tokens of a
`dense_lm` configuration are judged by.  It imports nothing of the
program and makes its weights again from the seed, one slice at a time
(bench/harness/weights.py).

Per member: token embedding; per layer a pre-norm block, x += attn(rms(x))
then x += mlp(rms(x)), where attention is causal multi-head attention
with rotary positions (half-split rotation, theta from the config) and
the MLP is SwiGLU, silu(x Wg) * (x Wu) Wd; then a final RMSNorm and the
LM head.  The ensemble's distribution is Eqn 6 of the paper, the mean of
the members' softmax outputs, taken here in log space.

`quant="fp8"` computes every weight matmul with both operands rounded to
float8 (e4m3, one scale per tensor): the lower precision that the
comparison has to reject.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from harness import weights
from harness.frozen import Frozen

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, hd): rotate the two halves of each head by pos * freq."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (T, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v):
    """Causal attention of (T, H, hd) tensors, in blocks of queries."""
    T, H, hd = q.shape
    nb = T // Q_BLOCK
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    return jax.lax.map(block, jnp.arange(nb)).reshape(T, H, hd)


@partial(jax.jit, static_argnames=("m", "quant"))
def _block(x, w, *, m, quant):
    """One layer over a batch of padded sequences x (R, T, d)."""
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]

    def one(xr):
        T = xr.shape[0]
        pos = jnp.arange(T)
        h = _rms(xr, w["norm_mix"], eps)
        q = _mm(h, w["w_q"], quant).reshape(T, H, hd)
        k = _mm(h, w["w_k"], quant).reshape(T, Hkv, hd)
        v = _mm(h, w["w_v"], quant).reshape(T, Hkv, hd)
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
        rep = H // Hkv
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        xr = xr + _mm(_attention(q, k, v).reshape(T, H * hd), w["w_o"],
                      quant)
        h = _rms(xr, w["norm_ffn"], eps)
        g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"],
                                                          quant)
        return xr + _mm(g, w["w_down"], quant)

    return jax.lax.map(one, x)


@partial(jax.jit, static_argnames=("m", "quant"))
def _head(x, norm, head, *, m, quant):
    """Rows x (N, d) -> log-softmax over the vocabulary (N, V)."""
    h = _rms(x, norm, m["norm_eps"])
    if quant == "fp8":
        h, head = _fp8(h), _fp8(head)
    logits = jnp.matmul(h, head.T, precision=HI)
    return jax.nn.log_softmax(logits, -1)


_LEAF = {path[-1] if path[-1] != "norm_scale" else path[0]: i
         for i, (path, *_rest) in enumerate(weights.lm_leaves(
             {"d_model": 1, "n_heads": 1, "n_kv_heads": 1, "head_dim": 1,
              "d_ff": 1, "vocab_size": 1}))}


def _f32(m, seed, name, member, layer=0):
    return weights.lm_slice(m, seed, _LEAF[name], member,
                            layer).astype(jnp.float32)


def fused_log_probs(m: dict, seed: int, tokens, rows, quant: str = ""):
    """Eqn-6 ensemble log-probs at chosen positions.

    tokens: (R, T) int32, each sequence padded at its end (causal
    attention keeps padding out of every earlier position); T a multiple
    of Q_BLOCK.  rows: (N, 2) int32 (sequence, position) pairs.
    -> (N, V) float32 log of the mean of the members' distributions.
    """
    mh = Frozen(m)
    acc = None
    for member in range(m["members"]):
        x = jnp.take(_f32(m, seed, "embed", member), tokens, axis=0)
        for layer in range(m["n_layers"]):
            w = {k: _f32(m, seed, k, member, layer)
                 for k in ("norm_mix", "w_q", "w_k", "w_v", "w_o",
                           "norm_ffn", "w_gate", "w_up", "w_down")}
            x = _block(x, w, m=mh, quant=quant)
            del w
        sel = x[rows[:, 0], rows[:, 1]]
        del x
        lp = _head(sel, _f32(m, seed, "final_norm", member),
                   _f32(m, seed, "head", member), m=mh, quant=quant)
        acc = lp if acc is None else jnp.logaddexp(acc, lp)
    return acc - math.log(m["members"])

