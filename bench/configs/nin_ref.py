"""Plain reference of the paper's NiN member, its training step, the
ensemble relabel and the distillation loss, in float32 at full
precision: the yardstick the EC round is judged by.  It imports nothing
of the program and makes its weights again from the seed
(bench/harness/weights.py).

Network (Lin et al., arXiv:1312.4400, as the EC-DNN paper uses it for
CIFAR): each convolution is "SAME"-padded and followed by its bias and a
ReLU; pools are 3x3, stride 2, "SAME" (max, then average, the average
over the 9 window positions); a 1x1 convolution onto the classes is
averaged over the remaining positions into the logits.  Loss (paper
Eqn 9): softmax cross-entropy against the true labels, plus lambda times
the cross-entropy against the ensemble's distribution where a
distillation step has one, plus 1e-4 times the squared norm of every
convolution kernel; lambda falls linearly from lambda_0 to 0 over the
first p steps of a round.  Update: momentum SGD, mu <- 0.9 mu + g and
w <- w - lr * mu, member by member.  Relabel (Eqn 6): the mean over the
K members of each member's softmax output on an image.

The configuration computes its convolutions at JAX's default TPU
precision, bfloat16 operands with float32 accumulation.  `precision=
"fp8"` rounds both operands of every convolution, and in the backward
pass the gradients flowing into them, to float8 (e4m3, one scale per
tensor) instead: the next lower precision, which the comparison has to
reject.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """x rounded to float8 with a scale of its own; the gradient that
    flows back through it is rounded so too, with its own scale (an
    unscaled cast would flush small gradients to zero)."""
    return _q8(x)


_fp8.defvjp(lambda x: (_q8(x), None), lambda _, g: (_q8(g),))


def _conv(x, w, precision):
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI)


def _pool(x, k, s, kind):
    if kind == "maxpool":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                     (1, s, s, 1), "SAME")
    return jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, k, k, 1),
                                 (1, s, s, 1), "SAME") / (k * k)


def logits(m: dict, p: dict, images, precision: str = ""):
    x = images
    for i, (kind, _out, k, s) in enumerate(m["layers"]):
        if kind == "conv":
            x = jax.nn.relu(_conv(x, p[f"conv_{i}_w"], precision)
                            + p[f"bias_{i}"])
        else:
            x = _pool(x, k, s, kind)
    x = _conv(x, p["conv_out_w"], precision) + p["bias_out"]
    return x.mean(axis=(1, 2))


def loss(m: dict, p: dict, images, labels, pseudo=None, lam=0.0,
         precision: str = ""):
    z = logits(m, p, images, precision)
    logp = jax.nn.log_softmax(z, -1)
    nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])
    if pseudo is not None:
        nll = nll + lam * -jnp.mean(jnp.sum(pseudo * logp, -1))
    reg = sum(jnp.sum(w * w) for k, w in p.items() if k.endswith("_w"))
    return nll + m["l2"] * reg


def lam_at(m: dict, t: int) -> float:
    """lambda at step t of a round (Section 4.3: linear to 0 over p)."""
    return m["lam"] * max(0.0, 1.0 - t / m["p_steps"])


@partial(jax.jit, static_argnames=("m", "precision"))
def sgd_steps(p, mu, images, labels, pseudo=None, lams=None, *, m,
              precision=""):
    """As many momentum-SGD steps of one member as batches are given,
    from parameters p and momentum mu.  images (S, B, h, w, c), labels
    (S, B); for distillation steps also pseudo (S, B, classes) and lams
    (S,).  -> (losses (S,), the first step's gradient, params after the
    last step)."""
    g1, losses = None, []
    for s in range(images.shape[0]):
        ps = None if pseudo is None else pseudo[s]
        lam = 0.0 if lams is None else lams[s]
        lv, g = jax.value_and_grad(lambda q: loss(
            m, q, images[s], labels[s], ps, lam, precision))(p)
        mu = jax.tree.map(lambda a, b: m["momentum"] * a + b, mu, g)
        p = jax.tree.map(lambda w, u: w - m["lr"] * u, p, mu)
        losses.append(lv)
        if g1 is None:
            g1 = g
    return jnp.stack(losses), g1, p


@partial(jax.jit, static_argnames=("m", "precision"))
def member_probs(p, images, *, m, precision=""):
    """One member's softmax output on a block of images."""
    return jax.nn.softmax(logits(m, p, images, precision), -1)


def relabel(m: dict, members: list, images, precision: str = "",
            block: int = 256):
    """Eqn 6: for each member's images (K, n, ...), the mean over all K
    members of their softmax outputs.  -> (K, n, classes), worked out
    member by member and in blocks of images."""
    K, n = images.shape[:2]
    out = []
    for k in range(K):
        acc = 0.0
        for p in members:
            acc = acc + jnp.concatenate([
                member_probs(p, images[k, i:i + block], m=m,
                             precision=precision)
                for i in range(0, n, block)])
        out.append(acc / len(members))
    return jnp.stack(out)
