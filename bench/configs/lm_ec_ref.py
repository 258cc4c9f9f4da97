"""Plain reference of the EC round on a dense decoder LM (llama layout),
in float32 at full matmul precision: the local and distillation steps
(paper Eqn 9 against top-M targets, momentum SGD) and the relabel (Eqn 6,
then its top M): the yardstick of an `lm_ec` configuration.  It imports
nothing of the program and makes the weights of the local steps again
from the seed (bench/harness/weights.py), one slice at a time.

Per member and sequence, the layer of bench/configs/llama_ref.py (its
RMSNorm and rotary helpers): x += attn(rms(x)), then x += mlp(rms(x)),
causal multi-head attention with rotary positions and a SwiGLU MLP; then
a final RMSNorm and the LM head.  Written again here so that a gradient
can flow at 4,096 tokens: each layer, and within it each block of
queries, is recomputed in the backward pass, so one layer's block of
scores is live at a time.

Loss (Eqn 9): the mean over tokens of the cross-entropy against the next
token, plus lambda times the cross-entropy against the ensemble's top-M
target renormalized over its kept entries.  A batch's loss and gradient
are the mean of its sequences' (all of one length).  Update, member by
member: mu <- momentum mu + g, w <- w - lr mu, each parameter then
stored in the configuration's type (the norm scales in float32, the
rest in `dtype`), as the configuration states: kept between steps as an
array of that type, since a compiler that may keep excess precision can
drop a round trip through it inside one program.  lambda falls linearly
from lambda_0 to 0 over the first p steps of a round.  Relabel (Eqn 6):
at every position the mean over the members of their softmax outputs,
then its M largest entries.

`quant="fp8"` rounds both operands of every weight matmul and of the
head, and the gradients flowing back into them, to float8 (e4m3, one
scale per tensor; nin_ref's rounding): the next lower precision, which
the comparison has to reject.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from configs.llama_ref import Q_BLOCK, _f32, _rms, _rope
from configs.nin_ref import _fp8
from harness.frozen import Frozen

HI = jax.lax.Precision.HIGHEST
LAYER = ("norm_mix", "w_q", "w_k", "w_v", "w_o", "norm_ffn", "w_gate",
         "w_up", "w_down")
TOP = ("embed", "head", "final_norm")
F32_LEAVES = ("norm_mix", "norm_ffn", "final_norm")


def seed_member(m: dict, seed: int, member: int) -> dict:
    """One member's weights as the seed makes them, in float32:
    {leaf: value}, each layer leaf stacked over the layers."""
    w = {n: _f32(m, seed, n, member) for n in TOP}
    for n in LAYER:
        w[n] = jnp.stack([_f32(m, seed, n, member, layer)
                          for layer in range(m["n_layers"])])
    return w


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _attention(q, k, v):
    """Causal attention of (T, H, hd) tensors, in blocks of queries, each
    recomputed in the backward pass."""
    T, H, hd = q.shape
    qb = min(Q_BLOCK, T)
    kpos = jnp.arange(T)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    return jax.lax.map(block, jnp.arange(T // qb)).reshape(T, H, hd)


def _layer(x, w, m, quant):
    """One layer over one sequence x (T, d)."""
    T = x.shape[0]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(T)
    h = _rms(x, w["norm_mix"], m["norm_eps"])
    q = _mm(h, w["w_q"], quant).reshape(T, H, hd)
    k = _mm(h, w["w_k"], quant).reshape(T, Hkv, hd)
    v = _mm(h, w["w_v"], quant).reshape(T, Hkv, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    k, v = jnp.repeat(k, H // Hkv, 1), jnp.repeat(v, H // Hkv, 1)
    x = x + _mm(_attention(q, k, v).reshape(T, H * hd), w["w_o"], quant)
    h = _rms(x, w["norm_ffn"], m["norm_eps"])
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


def _log_probs(w, tokens, m, quant):
    """One sequence of ids (T,) -> log-softmax over the vocabulary (T, V)."""
    x = jnp.take(w["embed"], tokens, axis=0)
    for layer in range(m["n_layers"]):
        x = jax.checkpoint(partial(_layer, m=m, quant=quant))(
            x, {n: w[n][layer] for n in LAYER})
    h, head = _rms(x, w["final_norm"], m["norm_eps"]), w["head"]
    if quant == "fp8":
        h, head = _fp8(h), _fp8(head)
    return jax.nn.log_softmax(jnp.matmul(h, head.T, precision=HI), -1)


def _seq_loss(w, tokens, labels, pvals, pidx, lam, m, quant):
    logp = _log_probs(w, tokens, m, quant)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])
    if pvals is not None:
        wt = pvals / jnp.sum(pvals, -1, keepdims=True)
        loss = loss - lam * jnp.mean(jnp.sum(
            wt * jnp.take_along_axis(logp, pidx, 1), -1))
    return loss


def _stored(m, w: dict) -> dict:
    """Each parameter in the type the configuration stores it in."""
    return {n: v.astype(jnp.float32 if n in F32_LEAVES else m["dtype"])
            for n, v in w.items()}


def _f32_tree(w: dict) -> dict:
    return {n: v.astype(jnp.float32) for n, v in w.items()}


@partial(jax.jit, static_argnames=("m", "quant"))
def _seq_grad(w, tokens, labels, pvals, pidx, lam, *, m, quant):
    return jax.value_and_grad(_seq_loss)(_f32_tree(w), tokens, labels,
                                         pvals, pidx, lam, m, quant)


@partial(jax.jit, static_argnames=("m",))
def _update(w, mu, g, *, m):
    mu = {n: m["momentum"] * mu[n] + g[n] for n in g}
    return _stored(m, {n: w[n].astype(jnp.float32) - m["lr"] * mu[n]
                       for n in w}), mu


@jax.jit
def _norms(tree, sub=None):
    return {n: jnp.linalg.norm((v.astype(jnp.float32) - (
        0 if sub is None else sub[n].astype(jnp.float32))).ravel())
        for n, v in tree.items()}


def lam_at(m: dict, t: int) -> float:
    """lambda at step t of a round (Section 4.3: linear to 0 over p)."""
    return m["lam"] * max(0.0, 1.0 - t / m["p_steps"])


def sgd_steps(m: dict, w: dict, mu: dict, tokens, labels, pseudo=None,
              lams=None, quant: str = "") -> tuple:
    """As many momentum-SGD steps of one member as batches are given,
    from weights w and momentum mu ({leaf: float32}, each weight a value
    of its stored type).  tokens, labels
    (S, B, T); for distillation steps also pseudo, the top-M targets
    (vals, idx) each (S, B, T, M), and lams (S,).  -> (losses (S,),
    {leaf: norm of the first step's gradient}, {leaf: norm of the
    change over the steps})."""
    mh = Frozen(m)
    w = w0 = _stored(m, w)
    S, B = tokens.shape[:2]
    losses, g1 = [], None
    for s in range(S):
        loss, g = 0.0, None
        for b in range(B):
            pv = pi = None
            if pseudo is not None:
                pv, pi = jnp.asarray(pseudo[0][s, b]), jnp.asarray(
                    pseudo[1][s, b])
            lam = 0.0 if lams is None else float(lams[s])
            lv, gb = _seq_grad(w, jnp.asarray(tokens[s, b]),
                               jnp.asarray(labels[s, b]), pv, pi,
                               jnp.float32(lam), m=mh, quant=quant)
            loss = loss + lv
            g = gb if g is None else jax.tree.map(jnp.add, g, gb)
            del gb
        g = jax.tree.map(lambda x: x / B, g)
        losses.append(float(loss) / B)
        if g1 is None:
            g1 = jax.device_get(_norms(g))
        w, mu = _update(w, mu, g, m=mh)
        del g
    change = jax.device_get(_norms(w, w0))
    return (np.asarray(losses),
            {n: float(v) for n, v in g1.items()},
            {n: float(v) for n, v in change.items()})


@partial(jax.jit, static_argnames=("m", "quant"))
def _probs(w, tokens, *, m, quant):
    return jnp.exp(_log_probs(w, tokens, m, quant))


def relabel(m: dict, members: list, tokens, top_m: int,
            quant: str = "") -> tuple:
    """Eqn 6 at every position of each member's sequences tokens
    (K, R, T), by all the members (each {leaf: float32}), then its
    `top_m` largest entries.  -> (vals, idx) (K, R, T, top_m), on the
    host."""
    mh = Frozen(m)
    K, R = tokens.shape[:2]
    vals = np.zeros((K, R, tokens.shape[2], top_m), np.float32)
    idx = np.zeros(vals.shape, np.int32)
    for k in range(K):
        for r in range(R):
            seq = jnp.asarray(tokens[k, r])
            acc = sum(_probs(w, seq, m=mh, quant=quant) for w in members)
            v, i = jax.lax.top_k(acc / len(members), top_m)
            vals[k, r], idx[k, r] = np.asarray(v), np.asarray(i)
    return vals, idx
