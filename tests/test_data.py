"""Synthetic data: determinism, disjoint member shards, learnable structure."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import (gather_members, image_member_datasets,
                        lm_member_datasets, sample_batch,
                        sample_relabel_subset)


def test_deterministic():
    k = jax.random.PRNGKey(7)
    a, _ = lm_member_datasets(k, 2, 8, 16, 100)
    b, _ = lm_member_datasets(k, 2, 8, 16, 100)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))


def test_member_shards_disjoint():
    k = jax.random.PRNGKey(0)
    train, _ = lm_member_datasets(k, 4, 16, 12, 50)
    t = np.asarray(train["tokens"])
    # sequences across members differ (random partition of the stream)
    assert not (t[0] == t[1]).all()


def test_labels_are_shifted_tokens():
    k = jax.random.PRNGKey(0)
    train, _ = lm_member_datasets(k, 2, 4, 10, 64)
    # labels[t] is the next-token target: labels[:-1] aligns with
    # tokens[1:] by construction of the stream
    np.testing.assert_array_equal(np.asarray(train["tokens"][..., 1:]),
                                  np.asarray(train["labels"][..., :-1]))


def test_lm_structure_is_learnable():
    """Bigram statistics beat uniform: the affine rules leak into counts."""
    k = jax.random.PRNGKey(1)
    train, _ = lm_member_datasets(k, 1, 64, 32, 16)
    toks = np.asarray(train["tokens"][0]).reshape(-1)
    nxt = np.asarray(train["labels"][0]).reshape(-1)
    counts = np.zeros((16, 16))
    np.add.at(counts, (toks, nxt), 1)
    probs = counts / np.maximum(counts.sum(1, keepdims=True), 1)
    # per-row entropy far below uniform ln(16)
    ent = -(probs * np.log(np.maximum(probs, 1e-12))).sum(1)
    assert ent[counts.sum(1) > 10].mean() < 0.6 * np.log(16)


def test_image_classes_separable():
    k = jax.random.PRNGKey(2)
    train, test = image_member_datasets(k, 2, 128, n_classes=4, img=8,
                                        noise=0.3)
    x = np.asarray(train["images"]).reshape(-1, 8 * 8 * 3)
    y = np.asarray(train["labels"]).reshape(-1)
    # nearest-class-mean classifier should beat chance comfortably
    means = np.stack([x[y == c].mean(0) for c in range(4)])
    pred = ((x[:, None] - means[None]) ** 2).sum(-1).argmin(1)
    assert (pred == y).mean() > 0.8


def test_sampling_shapes():
    rng = np.random.default_rng(0)
    k = jax.random.PRNGKey(3)
    train, _ = image_member_datasets(k, 3, 32, n_classes=5, img=8)
    b = sample_batch(rng, train, 4)
    assert b["images"].shape == (3, 4, 8, 8, 3)
    sub, idx = sample_relabel_subset(rng, train, 0.5)
    assert sub["images"].shape == (3, 16, 8, 8, 3)
    # indices unique per member (sampling without replacement)
    assert all(len(set(row)) == len(row) for row in idx)


def _eager_take(tree, idx):
    """The eager advanced-indexing draw: a[arange(K)[:, None], idx]."""
    rows = np.arange(idx.shape[0])[:, None]
    return jax.tree.map(lambda a: np.asarray(a)[rows, idx], tree)


@pytest.mark.parametrize("data", ["image", "lm"])
@pytest.mark.parametrize("draw", ["batch", "relabel_subset"])
def test_draws_equal_eager_gather_bit_for_bit(draw, data):
    """Indices from the rng as drawn before, rows gathered exactly: the
    draws equal the eager advanced-indexing gather on the same rng state,
    and leave the rng where it left it."""
    k = jax.random.PRNGKey(4)
    if data == "image":
        train, _ = image_member_datasets(k, 3, 40, n_classes=5, img=8)
    else:
        train, _ = lm_member_datasets(k, 3, 40, 12, 50)
    K, n = jax.tree.leaves(train)[0].shape[:2]
    rng, ref_rng = np.random.default_rng(11), np.random.default_rng(11)
    if draw == "batch":
        got = sample_batch(rng, train, 7)
        idx = ref_rng.integers(0, n, size=(K, 7))
    else:
        got, got_idx = sample_relabel_subset(rng, train, 0.7)
        idx = np.stack([ref_rng.permutation(n)[:28] for _ in range(K)])
        np.testing.assert_array_equal(got_idx, idx)
    want = _eager_take(train, idx)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    assert rng.integers(0, 2 ** 31) == ref_rng.integers(0, 2 ** 31)


def test_gather_members_jitted_equals_eager():
    k = jax.random.PRNGKey(5)
    train, _ = image_member_datasets(k, 2, 16, n_classes=3, img=4)
    idx = np.random.default_rng(0).integers(0, 16, size=(2, 5))
    got = jax.jit(gather_members)(train, idx)
    want = _eager_take(train, idx)
    for name in ("images", "labels"):
        assert np.asarray(got[name]).tobytes() == want[name].tobytes()
