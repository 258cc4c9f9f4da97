"""Eqn-9 mixed loss: schedule, dense/sparse paths, fused-kernel parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression as comp
from repro.core import distill


def test_lam_schedule_anneals_to_zero():
    lams = [float(distill.lam_schedule(t, 0.5, 10)) for t in range(12)]
    assert lams[0] == pytest.approx(0.5)
    assert lams[5] == pytest.approx(0.25)
    assert lams[10] == 0.0 and lams[11] == 0.0
    assert all(a >= b for a, b in zip(lams, lams[1:]))


@pytest.mark.parametrize("p_steps", [10, 195])
def test_lam_host_equals_lam_schedule_bit_for_bit(p_steps):
    for t in range(p_steps + 2):
        got = distill.lam_host(t, 0.5, p_steps)
        want = np.asarray(distill.lam_schedule(t, 0.5, p_steps))
        assert isinstance(got, np.float32) and want.dtype == np.float32
        assert got.tobytes() == want.tobytes(), (t, got, want)


def _setup(n=12, v=50):
    k = jax.random.PRNGKey(0)
    logits = jax.random.normal(k, (n, v)) * 2
    labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
    pseudo = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2), (n, v)))
    return logits, labels, pseudo


def test_mixed_ce_dense_matches_manual():
    logits, labels, pseudo = _setup()
    lam = 0.3
    got = distill.mixed_ce(logits, labels, pseudo, lam, impl="jnp")
    logp = jax.nn.log_softmax(logits)
    ce_true = -jnp.take_along_axis(logp, labels[:, None], 1).mean()
    ce_ps = -(pseudo * logp).sum(-1).mean()
    np.testing.assert_allclose(float(got), float(ce_true + lam * ce_ps),
                               rtol=1e-5)


def test_mixed_ce_lam_zero_is_plain_ce():
    logits, labels, pseudo = _setup()
    a = distill.mixed_ce(logits, labels, pseudo, 0.0, impl="jnp")
    b = distill.true_ce(logits, labels)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_mixed_ce_topm_full_m_matches_dense():
    logits, labels, pseudo = _setup(n=8, v=20)
    t = comp.from_dense(pseudo, 20)  # lossless
    lam = 0.7
    sparse = distill.mixed_ce(logits, labels, t, lam)
    dense = distill.mixed_ce(logits, labels, pseudo, lam, impl="jnp")
    np.testing.assert_allclose(float(sparse), float(dense), rtol=1e-4)


def test_fused_pallas_matches_jnp(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    logits, labels, pseudo = _setup(n=16, v=600)
    lam = 0.45
    fused = distill.mixed_ce(logits, labels, pseudo, lam, impl="pallas")
    ref = distill.mixed_ce(logits, labels, pseudo, lam, impl="jnp")
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5)
    # gradients too (custom VJP)
    gf = jax.grad(lambda z: distill.mixed_ce(z, labels, pseudo, lam,
                                             impl="pallas"))(logits)
    gr = jax.grad(lambda z: distill.mixed_ce(z, labels, pseudo, lam,
                                             impl="jnp"))(logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-5)
