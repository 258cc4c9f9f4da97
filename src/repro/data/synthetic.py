"""Deterministic synthetic datasets with learnable structure.

The container has no dataset downloads, so the faithful CIFAR-100
experiment runs on a synthetic stand-in with the same shape contract
(32x32x3, 100 classes) and genuine class structure: class prototypes +
Gaussian noise + random horizontal flips (the paper's only augmentation).
Models trained on it exhibit the real learning dynamics EC/MA differ on
(local fit -> aggregation -> re-fit), which is what the reproduction
validates; absolute error rates are not comparable to the paper's table
and EXPERIMENTS.md says so.

LM streams: affine-recurrent token sequences x_{t+1} = (a*x_t + b) mod V
with per-sequence (a, b) drawn from a small pool, plus noise tokens — a
next-token task a small transformer provably reduces below uniform CE.

Everything is keyed by (seed, member, epoch) so runs are bit-reproducible
and each ensemble member holds a DISJOINT shard, like the paper's random
partition of the training set.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# image classification (paper stand-in)
# ---------------------------------------------------------------------------

def image_member_datasets(key, n_members: int, per_member: int,
                          n_classes: int = 100, img: int = 32,
                          noise: float = 0.35) -> Tuple[dict, dict]:
    """-> (train_shards {images (K,n,h,w,3), labels (K,n)}, test set)."""
    kproto, ktrain, ktest = jax.random.split(key, 3)
    protos = jax.random.normal(kproto, (n_classes, img, img, 3)) * 0.8

    def make_split(k, total):
        kl, kn, kf = jax.random.split(k, 3)
        labels = jax.random.randint(kl, (total,), 0, n_classes)
        x = protos[labels] + noise * jax.random.normal(
            kn, (total, img, img, 3))
        flip = jax.random.bernoulli(kf, 0.5, (total,))
        x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
        return x.astype(jnp.float32), labels.astype(jnp.int32)

    xtr, ytr = make_split(ktrain, n_members * per_member)
    xte, yte = make_split(ktest, max(per_member, 512))
    train = {"images": xtr.reshape(n_members, per_member, img, img, 3),
             "labels": ytr.reshape(n_members, per_member)}
    test = {"images": xte, "labels": yte}
    return train, test


# ---------------------------------------------------------------------------
# language modeling
# ---------------------------------------------------------------------------

def _affine_stream(key, n_seq: int, seq_len: int, vocab: int,
                   n_rules: int = 0, noise_p: float = 0.05):
    """n_rules=0 scales the pool with the vocab (vocab//4, clamped to
    [2, 16]): a small vocab with as many rules as tokens mixes ~vocab
    affine maps into a near-uniform bigram table, destroying the
    marginal structure the stream promises (tests/test_data.py checks
    bigram entropy is well below uniform)."""
    if n_rules <= 0:
        n_rules = min(16, max(2, vocab // 4))
    kr, k0, kn, kz = jax.random.split(key, 4)
    rule_a = jax.random.randint(kr, (n_rules,), 1, max(vocab - 1, 2))
    rule_b = jax.random.randint(kz, (n_rules,), 0, vocab)
    rid = jax.random.randint(k0, (n_seq,), 0, n_rules)
    x0 = jax.random.randint(kn, (n_seq,), 0, vocab)

    def gen(carry, _):
        x = carry
        nxt = (x * rule_a[rid] + rule_b[rid]) % vocab
        return nxt, nxt

    _, toks = jax.lax.scan(gen, x0, None, length=seq_len)
    toks = toks.T  # (n_seq, seq_len)
    knoise = jax.random.split(key, 1)[0]
    mask = jax.random.bernoulli(knoise, noise_p, toks.shape)
    rnd = jax.random.randint(knoise, toks.shape, 0, vocab)
    return jnp.where(mask, rnd, toks).astype(jnp.int32)


def lm_member_datasets(key, n_members: int, per_member: int, seq_len: int,
                       vocab: int) -> Tuple[dict, dict]:
    """-> ({tokens (K,n,T)}, test {tokens (n_test,T)}). labels = shift."""
    ktr, kte = jax.random.split(key)
    tr = _affine_stream(ktr, n_members * per_member, seq_len + 1, vocab)
    te = _affine_stream(kte, max(per_member // 2, 32), seq_len + 1, vocab)
    train = {"tokens": tr[:, :-1].reshape(n_members, per_member, seq_len),
             "labels": tr[:, 1:].reshape(n_members, per_member, seq_len)}
    test = {"tokens": te[:, :-1], "labels": te[:, 1:]}
    return train, test


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def batch_indices(rng: np.random.Generator, tree, batch: int) -> np.ndarray:
    """(K, batch) row indices into a member-stacked tree: same batch
    size, independent indices, drawn with replacement."""
    K, n = jax.tree.leaves(tree)[0].shape[:2]
    return rng.integers(0, n, size=(K, batch))


def relabel_indices(rng: np.random.Generator, tree,
                    fraction: float) -> np.ndarray:
    """(K, m) row indices, m = `fraction` of each member's n rows, drawn
    without replacement."""
    K, n = jax.tree.leaves(tree)[0].shape[:2]
    m = max(1, int(n * fraction))
    return np.stack([rng.permutation(n)[:m] for _ in range(K)])


def gather_members(tree, idx):
    """out[k] = a[k, idx[k]] for every leaf a of a member-stacked tree.
    Traceable: under one jit a whole batch is one program."""
    take = jax.vmap(lambda a, i: a[i])
    return jax.tree.map(lambda a: take(a, idx), tree)


def sample_batch(rng: np.random.Generator, shards: dict, batch: int) -> dict:
    """Per-member minibatch: same batch size, independent indices."""
    return gather_members(shards, batch_indices(rng, shards, batch))


def sample_relabel_subset(rng: np.random.Generator, shards: dict,
                          fraction: float) -> Tuple[dict, np.ndarray]:
    """The paper relabels a fraction of D_k (70% default). Returns the
    subset and the indices (so the distill phase can pair pseudo-labels
    with true labels)."""
    idx = relabel_indices(rng, shards, fraction)
    return gather_members(shards, idx), idx
