"""Trainer integration: EC/MA/sync rounds, failure restart, straggler,
elastic K, pseudo-label distillation path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import ECConfig, ModelConfig
from repro.data import image_member_datasets, lm_member_datasets
from repro.optim import adamw, sgd_momentum
from repro.runtime.trainer import Trainer


def _cnn_trainer(aggr="ec", ckpt=None, K=4, tau=4, label_mode="dense",
                 seed=1):
    cfg = ModelConfig(name="nin-t", family="cnn", n_layers=9, d_model=48,
                      vocab_size=10)
    key = jax.random.PRNGKey(0)
    train, test = image_member_datasets(key, K, per_member=64,
                                        n_classes=10, img=8)
    ec = ECConfig(tau=tau, lam=0.5, p_steps=tau // 2, relabel_fraction=0.5,
                  label_mode=label_mode, aggregator=aggr, top_m=4)
    return Trainer(cfg, ec, sgd_momentum(0.02), K, key, train, test,
                   batch_size=16, ckpt_dir=ckpt, seed=seed)


def _lm_trainer(aggr="ec", K=2, label_mode="topk"):
    from repro.configs import registry
    cfg = registry.get_config("deepseek-7b", reduced=True)
    key = jax.random.PRNGKey(0)
    train, test = lm_member_datasets(key, K, per_member=32, seq_len=16,
                                     vocab=cfg.vocab_size)
    ec = ECConfig(tau=3, lam=0.5, p_steps=2, relabel_fraction=0.5,
                  label_mode=label_mode, aggregator=aggr, top_m=8)
    return Trainer(cfg, ec, adamw(1e-3), K, key, train, test,
                   batch_size=4, seed=2)


@pytest.mark.parametrize("aggr", ["ec", "ma", "sync"])
def test_round_runs_and_evaluates(aggr):
    tr = _cnn_trainer(aggr)
    loss = tr.run_round()
    assert np.isfinite(loss)
    ev = tr.evaluate()
    assert 0 <= ev["local_err"] <= 1 and np.isfinite(ev["global_loss"])


def test_ec_distill_phase_uses_pseudo_buffer():
    tr = _cnn_trainer("ec")
    tr.run_round()
    assert tr.pseudo_buffer is not None
    subset, pseudo = tr.pseudo_buffer
    assert jax.tree.leaves(subset)[0].shape[0] == tr.K
    p = np.asarray(pseudo)
    # dense pseudo labels are distributions
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-4)
    tr.run_round()  # distill steps consume the buffer without error


def test_ec_lm_topk_pseudo_path():
    tr = _lm_trainer("ec", label_mode="topk")
    tr.run_round()
    from repro.core.compression import TopM
    assert isinstance(tr.pseudo_buffer[1], TopM)
    tr.run_round()
    ev = tr.evaluate()
    assert np.isfinite(ev["global_loss"])


def test_jensen_guarantee_on_real_models():
    """Paper Section 3 on actual trained members: ensemble nll <= mean."""
    tr = _cnn_trainer("ec")
    for _ in range(2):
        tr.run_round()
    ev = tr.evaluate()
    assert ev["global_loss"] <= ev["local_loss"] + 1e-5


def test_restart_from_checkpoint(tmp_path):
    ckpt = str(tmp_path)
    tr = _cnn_trainer("ec", ckpt=ckpt, tau=2)
    tr.run_round()
    tr.run_round()
    tr.ckpt.wait()
    w_before = np.asarray(jax.tree.leaves(tr.state["params"])[0])
    r_before = tr.round

    # simulate a node failure: fresh trainer process, resume from disk
    tr2 = _cnn_trainer("ec", ckpt=ckpt, tau=2)
    assert tr2.resume()
    assert tr2.round == r_before
    w_after = np.asarray(jax.tree.leaves(tr2.state["params"])[0])
    np.testing.assert_allclose(w_after, w_before)
    tr2.run_round()  # training continues


def test_straggler_drop_renormalizes():
    tr = _cnn_trainer("ec", K=4)
    mask = np.array([1.0, 1.0, 1.0, 0.0])  # member 3 lags
    tr.run_round(straggler_mask=mask)
    subset, pseudo = tr.pseudo_buffer
    p = np.asarray(pseudo)
    np.testing.assert_allclose(p.sum(-1), 1.0, atol=1e-4)
    # pseudo labels must not depend on the dropped member: recompute with
    # only 3 members
    import repro.core.aggregation as agg
    from repro.runtime import steps
    logits_fn = steps.make_logits_fn(tr.cfg)
    sub3 = jax.tree.map(lambda x: x[:3], subset)
    p3 = jax.jit(lambda pp, b: agg.allgather_relabel(
        pp, b, logits_fn, tr.ec))(
        jax.tree.map(lambda x: x[:3], tr.state["params"]), sub3)
    # member k's own-batch labels with quorum == labels from the 3-member
    # ensemble on the same batches
    np.testing.assert_allclose(p[:3], np.asarray(p3), atol=1e-4)


def test_elastic_reshard_grow_and_shrink():
    tr = _cnn_trainer("ec", K=4, tau=2)
    tr.run_round()
    tr.reshard(6, key=jax.random.PRNGKey(1))
    assert jax.tree.leaves(tr.state["params"])[0].shape[0] == 6
    loss = tr.run_round()
    assert np.isfinite(loss)
    tr.reshard(2)
    loss = tr.run_round()
    assert np.isfinite(loss)


def test_ma_equals_manual_mean():
    tr = _cnn_trainer("ma", K=3, tau=1)
    before = jax.tree.map(lambda x: np.asarray(x).copy(),
                          tr.state["params"])
    tr.run_round()
    after = tr.state["params"]
    for a in jax.tree.leaves(after):
        a = np.asarray(a)
        np.testing.assert_allclose(a[0], a.mean(0), rtol=1e-5, atol=1e-6)


def test_best_member_selection():
    tr = _cnn_trainer("ec", K=3)
    tr.run_round()
    best, k = tr.best_member()
    assert 0 <= k < 3
    assert jax.tree.leaves(best)[0].shape \
        == jax.tree.leaves(tr.state["params"])[0].shape[1:]


# -- program names, host spans and retrace counts ----------------------------


def _lowered(tr, program):
    """Lower one of the trainer's programs on arguments of its shapes."""
    from repro.data import sample_batch, sample_relabel_subset
    batch = sample_batch(tr.rng, tr.shards, tr.batch)
    if program == "ec_local_step":
        return tr._plain_step.lower(tr.state, batch)
    if program == "ec_sync_step":
        return tr._sync_step.lower(tr.state, batch)
    if program == "ec_distill_step":
        V = tr.cfg.vocab_size
        pseudo = jnp.full((tr.K, tr.batch, V), 1.0 / V)
        return tr._distill_step.lower(tr.state, batch, pseudo, 0.5)
    if program == "ec_ma_step":
        return tr._ma_step.lower(tr.state, None)
    if program == "ec_sample":
        return tr._sample.lower(tr.shards, np.zeros((tr.K, tr.batch),
                                                    np.int32))
    subset, _ = sample_relabel_subset(tr.rng, tr.shards, 0.5)
    return tr._relabel_program().lower(tr.state["params"], subset)


@pytest.mark.parametrize("program", ["ec_local_step", "ec_sync_step",
                                     "ec_distill_step", "ec_ma_step",
                                     "ec_relabel", "ec_sample"])
def test_program_module_names(program):
    tr = _cnn_trainer("ec", K=2, tau=2)
    text = _lowered(tr, program).as_text()
    assert f"module @jit_{program} " in text, text[:200]
    assert "jit__lambda" not in text


def _traced_rounds(tr, rounds, trace_dir):
    """Run `rounds` rounds under the profiler (no Python tracer) ->
    [(name, stats)] of the host events whose names start with "ec."."""
    import glob
    import os
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for _ in range(rounds):
            tr.run_round()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, dict(ev.stats)) for plane in pd.planes
            if plane.name == "/host:CPU" for line in plane.lines
            for ev in line.events if ev.name.startswith("ec.")]


def test_round_phases_leave_host_spans(tmp_path):
    tau = 4
    tr = _cnn_trainer("ec", K=2, tau=tau)
    spans = _traced_rounds(tr, 2, tmp_path)
    names = [n for n, _ in spans]
    assert names.count("ec.sample") == names.count("ec.step") == tau * 2
    assert names.count("ec.loss_readback") == 2
    assert names.count("ec.relabel") == 2
    kinds = sorted(st["kind"] for n, st in spans if n == "ec.step")
    # round 2 distils its first p = tau / 2 steps from round 1's relabel
    assert kinds == ["distill"] * (tau // 2) + ["local"] * (tau * 3 // 2)
    rounds = sorted({st["round"] for n, st in spans if n == "ec.step"})
    assert rounds == [0, 1]
    images = {st["images"] for n, st in spans if n == "ec.relabel"}
    assert images == {2 * 32}  # K x half of 64 images a member


def test_retrace_counters_match_the_trace(tmp_path):
    tr = _cnn_trainer("ec", K=2, tau=4)
    spans = _traced_rounds(tr, 3, tmp_path)
    names = [n for n, _ in spans]
    c = tr.counters
    assert c["trace.ec_local_step"] == 1
    assert c["trace.ec_distill_step"] == 1
    assert c["trace.ec_relabel"] == names.count("ec.trace.ec_relabel") >= 1
    assert names.count("ec.trace.ec_local_step") == 1
    assert c["local_steps"] == 4 + 2 + 2 and c["distill_steps"] == 2 + 2


# -- the batch draw: host indices, one gather program ------------------------


def _eager_take(tree, idx):
    rows = np.arange(idx.shape[0])[:, None]
    return jax.tree.map(lambda a: np.asarray(a)[rows, idx], tree)


def _assert_bits_equal(got, want):
    got = jax.device_get(got)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.tobytes() == w.tobytes()


@pytest.mark.parametrize("draw", ["local", "relabel_subset", "pseudo_dense",
                                  "pseudo_topk"])
def test_trainer_draws_equal_eager_gather(draw):
    """Every draw of a round gathers the rows the rng gives, bit for bit
    as the eager a[arange(K)[:, None], idx], in the rng's call order."""
    import copy
    tau = 4
    mode = "topk" if draw == "pseudo_topk" else "dense"
    tr = _cnn_trainer("ec", K=2, tau=tau, label_mode=mode)
    K, n = jax.tree.leaves(tr.shards)[0].shape[:2]
    ref_rng = copy.deepcopy(tr.rng)
    seen = []
    step = tr._plain_step

    def recording_step(state, batch):
        seen.append(jax.device_get(batch))
        return step(state, batch)
    tr._plain_step = recording_step
    tr.run_round()
    idx = [ref_rng.integers(0, n, size=(K, tr.batch)) for _ in range(tau)]
    if draw == "local":
        assert len(seen) == tau
        for got, i in zip(seen, idx):
            _assert_bits_equal(got, _eager_take(tr.shards, i))
        return
    m = int(n * tr.ec.relabel_fraction)
    sub_idx = np.stack([ref_rng.permutation(n)[:m] for _ in range(K)])
    if draw == "relabel_subset":
        _assert_bits_equal(tr.pseudo_buffer[0],
                           _eager_take(tr.shards, sub_idx))
        return
    from repro.core.compression import TopM
    assert isinstance(tr.pseudo_buffer[1], TopM) == (mode == "topk")
    buf = jax.device_get(tr.pseudo_buffer)
    got = tr._sample_pseudo_batch()
    _assert_bits_equal(got, _eager_take(
        buf, ref_rng.integers(0, m, size=(K, tr.batch))))


def test_sample_program_traces_once_per_input():
    """Three inputs (the local batch, the relabel subset, the pseudo
    batch with its targets), traced in the first two rounds and never
    again, while the relabel's jit is built anew every round."""
    tr = _cnn_trainer("ec", K=2, tau=4)
    got = []
    for _ in range(4):
        tr.run_round()
        got.append((tr.counters["trace.ec_sample"],
                    tr.counters["trace.ec_relabel"]))
    assert got == [(2, 1), (3, 2), (3, 3), (3, 4)]


def test_distill_step_program_same_for_host_lambda():
    """lambda as np.float32 from the host lowers to the program the
    device scalar of `lam_schedule` lowered to."""
    from repro.core import distill
    tr = _cnn_trainer("ec", K=2, tau=4)
    batch = tr._sample(tr.shards, np.zeros((tr.K, tr.batch), np.int32))
    V = tr.cfg.vocab_size
    pseudo = jnp.full((tr.K, tr.batch, V), 1.0 / V)
    host = distill.lam_host(1, 0.5, 2)
    dev = distill.lam_schedule(1, 0.5, 2)
    assert host.tobytes() == np.asarray(dev).tobytes()
    text = [tr._distill_step.lower(tr.state, batch, pseudo, lam).as_text()
            for lam in (host, dev)]
    assert "module @jit_ec_distill_step" in text[0]
    assert text[0] == text[1]
