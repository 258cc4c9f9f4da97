"""Compression-phase loss (paper Eqn 9) and the lambda schedule.

  L = CE(f(x), y_true) + lambda * CE(f(x), y_pseudo)

y_pseudo is the ensemble output distribution — dense (..., V) probs for the
faithful CIFAR path, or a TopM sparse accumulator for LM vocabs.  lambda
anneals linearly from lam0 to 0 over p steps (paper: lam0=0.5, p=tau/2), so
the compression phase *is* the start of the next local-training phase — no
extra wall-clock beyond the relabel forward pass.

The dense dual-CE is also implemented as a fused Pallas kernel
(kernels/distill_loss.py) that streams vocab tiles through VMEM, computing
both CE terms in one pass over the logits; `mixed_ce` dispatches through
kernels/ops.py (impl="pallas" on TPU, pure-jnp here).
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression as comp


def lam_schedule(step_in_round: jax.Array, lam0: float,
                 p_steps: int) -> jax.Array:
    """Linear anneal lam0 -> 0 over p steps, 0 afterwards (Section 4.3)."""
    if p_steps <= 0:
        return jnp.zeros_like(jnp.asarray(step_in_round, jnp.float32))
    frac = 1.0 - jnp.asarray(step_in_round, jnp.float32) / p_steps
    return lam0 * jnp.clip(frac, 0.0, 1.0)


def lam_host(step_in_round: int, lam0: float, p_steps: int) -> np.float32:
    """`lam_schedule` worked out on the host in float32, op for op, so
    a step gets its lambda without a device program."""
    f32 = np.float32
    if p_steps <= 0:
        return f32(0)
    frac = f32(1) - f32(step_in_round) / f32(p_steps)
    return f32(lam0) * np.clip(frac, f32(0), f32(1))


def pseudo_ce_dense(logits: jax.Array, pseudo_probs: jax.Array) -> jax.Array:
    """-sum_c p̄_c log softmax(logits)_c, mean over tokens."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -(pseudo_probs * logp).sum(-1).mean()


def pseudo_ce_topm(logits: jax.Array, t: comp.TopM) -> jax.Array:
    """Sparse CE against a TopM target.

    Only the kept classes contribute (the pruned mass's CE contribution is
    unknowable post-compression); targets are renormalized over the kept
    entries so the loss stays a proper CE up to the documented L1 bound.
    """
    t = comp.normalize(t)
    kept = t.vals.sum(-1)
    w = t.vals / jnp.maximum(kept[..., None], 1e-30)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    safe_idx = jnp.maximum(t.idx, 0)
    gathered = jnp.take_along_axis(logp, safe_idx, axis=-1)
    gathered = jnp.where(t.idx < 0, 0.0, gathered)
    return -(w * gathered).sum(-1).mean()


def true_ce(logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is not None:
        return -(gold * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -gold.mean()


def mixed_ce(logits: jax.Array, labels: jax.Array,
             pseudo: Union[jax.Array, comp.TopM, None],
             lam: jax.Array, impl: str = "auto") -> jax.Array:
    """Eqn 9. pseudo=None or lam==0 degrades to plain CE."""
    ce = true_ce(logits, labels)
    if pseudo is None:
        return ce
    if isinstance(pseudo, comp.TopM):
        return ce + lam * pseudo_ce_topm(logits, pseudo)
    if impl in ("pallas", "auto"):
        from repro.kernels import ops
        if ops.pallas_enabled() or impl == "pallas":
            # fused kernel computes CE_true + lam*CE_pseudo in one pass
            return ops.fused_distill_loss(logits, labels, pseudo, lam)
    return ce + lam * pseudo_ce_dense(logits, pseudo)
