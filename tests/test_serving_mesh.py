"""Multi-device serving: the member-sharded engine vs the reference.

The engine's mesh path (shard_map kernels, psum-style Eqn-6 fusion)
must be a pure placement change: same tokens, same NLLs, same quorum
semantics as the single-device engine — only the bytes-per-device move.

These tests build the mesh with `common.sharding.local_mesh`, which
degrades to a 1x1 grid on a single-device host, so the SAME shard_map
program (collectives included) is exercised on plain CPU CI; run under
  XLA_FLAGS=--xla_force_host_platform_device_count=2
(scripts/ci.sh does) and the member axis actually spans two devices.
Tests that only make sense with real sharding skip below 2 devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import sharding as shd
from repro.configs import registry
from repro.core import ensemble as ens
from repro.models import transformer as tf
from repro.serving import EnsembleEngine, Scheduler, kv_cache

CFG = registry.get_config("gemma3-1b", reduced=True).with_(dtype="float32")
K = 4
MULTI = len(jax.devices()) >= 2
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs >= 2 devices (XLA_FLAGS="
    "--xla_force_host_platform_device_count=2)")


def _params(cfg, k=K, seed=0):
    return jax.vmap(lambda kk: tf.init(kk, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), k))


@pytest.fixture(scope="module")
def mesh():
    return shd.local_mesh(2, 1)


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


# -- placement helpers -------------------------------------------------------


def test_local_mesh_degrades_to_available_devices():
    """Oversized requests clamp instead of erroring, so the shard_map
    code path always runs — 1x1 on a single-device CI box."""
    m = shd.local_mesh(64, 64)
    n = len(jax.devices())
    assert m.axis_names == (shd.MEMBER_AXIS, shd.DATA_AXIS)
    assert m.shape[shd.MEMBER_AXIS] * m.shape[shd.DATA_AXIS] <= n
    assert shd.local_mesh(1, 1).devices.size == 1


def test_parse_mesh_arg():
    assert shd.parse_mesh_arg("") is None
    assert shd.parse_mesh_arg("1x1") is None
    with pytest.raises(ValueError, match="MxD"):
        shd.parse_mesh_arg("two-by-one")
    if MULTI:
        assert shd.parse_mesh_arg("2x1").shape[shd.MEMBER_AXIS] == 2
    else:
        # never a silent clamp onto the one device present
        with pytest.raises(ValueError, match="needs 2 devices"):
            shd.parse_mesh_arg("2x1")


def test_member_pspecs_shard_leading_axis_only():
    tree = {"a": jnp.zeros((4, 3, 2)), "b": {"c": jnp.zeros((4,))}}
    specs = shd.member_pspecs(tree)
    assert specs["a"] == jax.sharding.PartitionSpec("member", None, None)
    assert specs["b"]["c"] == jax.sharding.PartitionSpec("member")


def test_fusion_psum_matches_logsumexp(mesh):
    """ensemble_log_probs_psum under shard_map == the single-device
    reference, including zero-weight (dropped) members."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (K, 3, 33)) * 4
    w = jnp.array([1.0, 1.0, 0.0, 1.0])
    f = jax.jit(shd.shard_map(
        lambda lg, ww: ens.ensemble_log_probs_psum(lg, ww, "member"),
        mesh,
        in_specs=(jax.sharding.PartitionSpec("member"),
                  jax.sharding.PartitionSpec("member")),
        out_specs=jax.sharding.PartitionSpec()))
    got = f(logits, w)
    ref = ens.ensemble_log_probs(logits, weights=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    assert (np.asarray(got.argmax(-1)) == np.asarray(ref.argmax(-1))).all()


# -- engine equivalence: decode / prefill / score ----------------------------


def _drive_with_quorum_drop(eng, prompts, max_new, drop_at, drop_mask):
    """Admit -> chunked prefill -> decode, dropping a member mid-stream
    at decode step `drop_at`.  Returns the generated tokens per slot."""
    eng.update_slots(release=range(eng.n_slots),
                     admits=[(i, p, max_new) for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        for _ in range(-(-len(p) // eng.prefill_chunk)):
            eng.prefill(i)
    for t in range(max_new - 1):
        if t == drop_at:
            eng.set_quorum(drop_mask)
        eng.step()
    st = jax.device_get(eng.state)
    return [st.out[i, : st.n_gen[i]] for i in range(len(prompts))]


def test_mesh_decode_and_prefill_match_single_device(mesh, params):
    """Chunked-prefill generate on the mesh == the single-device engine,
    token for token, K=4, mixed prompt lengths — with a quorum drop
    mid-stream in both (straggler drop is placement-independent)."""
    prompts = [np.arange(1, 10) % CFG.vocab_size, np.arange(2, 5)]
    kw = dict(n_slots=2, max_prompt=12, max_out=8, prefill_chunk=4)
    drop = dict(max_new=8, drop_at=3, drop_mask=[1.0, 1.0, 0.0, 1.0])
    ref = _drive_with_quorum_drop(
        EnsembleEngine(CFG, params, **kw), prompts, **drop)
    got = _drive_with_quorum_drop(
        EnsembleEngine(CFG, params, mesh=mesh, **kw), prompts, **drop)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_mesh_per_token_reference_path_matches_single_device(mesh, params):
    """prefill_chunk=0 (the teacher-forcing reference baseline) is also
    served through shard_map and stays token-exact."""
    prompts = [np.arange(1, 8), np.arange(3, 6)]
    kw = dict(n_slots=2, max_prompt=8, max_out=6, prefill_chunk=0)
    ref = EnsembleEngine(CFG, params, **kw).generate(prompts, max_new=6)
    got = EnsembleEngine(CFG, params, mesh=mesh, **kw).generate(
        prompts, max_new=6)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(a, b)


def test_mesh_score_matches_single_device(mesh, params):
    """Teacher-forced scoring: global (K,) member NLLs and the fused
    ensemble NLL agree across placements, quorum-weighted included."""
    toks = jax.random.randint(jax.random.PRNGKey(3), (3, 5), 0,
                              CFG.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(4), (3, 5), 0,
                                CFG.vocab_size)
    kw = dict(n_slots=1, max_prompt=1, max_out=1,
              quorum=[1.0, 0.0, 1.0, 1.0])
    m_ref, e_ref = EnsembleEngine(CFG, params, **kw).score(toks, labels)
    m_got, e_got = EnsembleEngine(CFG, params, mesh=mesh, **kw).score(
        toks, labels)
    assert m_got.shape == (K,)
    np.testing.assert_allclose(np.asarray(m_got), np.asarray(m_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(e_got), float(e_ref),
                               rtol=1e-6, atol=1e-6)
    # Jensen guarantee survives the placement change: the fused NLL is
    # bounded by the mean over the SURVIVING (quorum-weighted) members
    alive = np.asarray(m_got)[[0, 2, 3]]
    assert float(e_got) <= float(alive.mean()) + 1e-5


def test_mesh_scheduler_serves_identically(mesh, params):
    """Continuous batching over a mesh engine: completions match the
    single-device scheduler run, request for request."""
    reqs = [(np.arange(1, 7), 4), (np.arange(2, 5), 3), (np.arange(3, 9), 4)]
    kw = dict(n_slots=2, max_prompt=8, max_out=4, prefill_chunk=4)
    ref = Scheduler(EnsembleEngine(CFG, params, **kw))
    got = Scheduler(EnsembleEngine(CFG, params, mesh=mesh, **kw))
    rids_r = [ref.submit(t, m) for t, m in reqs]
    rids_g = [got.submit(t, m) for t, m in reqs]
    comp_r, comp_g = ref.run(), got.run()
    for rr, rg in zip(rids_r, rids_g):
        np.testing.assert_array_equal(comp_g[rg].tokens, comp_r[rr].tokens)


# -- placement-specific behavior ---------------------------------------------


@needs_devices
def test_cache_bytes_reports_per_device_not_global(mesh, params):
    """Under a member-sharded pool, cache_bytes must report what ONE
    device holds — global/M — not the global figure (the regression
    this guards: telemetry overstating per-chip footprint M-fold)."""
    kw = dict(n_slots=2, max_prompt=8, max_out=8)
    single = EnsembleEngine(CFG, params, **kw)
    sharded = EnsembleEngine(CFG, params, mesh=mesh, **kw)
    M = mesh.shape[shd.MEMBER_AXIS]
    assert M == 2
    assert sharded.cache_bytes() == single.cache_bytes() // M
    # the global (logical) allocation is unchanged by placement
    assert kv_cache.pool_bytes(sharded.cache, per_device=False) \
        == single.cache_bytes()


@needs_devices
def test_mesh_params_and_pool_actually_shard(mesh, params):
    """Each device must hold 1/M of every param and cache leaf — the
    whole point of the member placement."""
    eng = EnsembleEngine(CFG, params, mesh=mesh, n_slots=2, max_prompt=4,
                         max_out=4)
    M = mesh.shape[shd.MEMBER_AXIS]
    for leaf in jax.tree.leaves(eng.params) + jax.tree.leaves(eng.cache):
        shard = leaf.sharding.shard_shape(leaf.shape)
        assert shard[0] == leaf.shape[0] // M, (leaf.shape, shard)


@needs_devices
def test_mesh_rejects_nondivisible_member_count(mesh):
    p3 = _params(CFG, k=3)
    with pytest.raises(ValueError, match="does not divide"):
        EnsembleEngine(CFG, p3, mesh=mesh, n_slots=1, max_prompt=4,
                       max_out=4)
