"""Paged KV-cache pool + paged-attention kernel (ISSUE 4).

Equivalence strategy mirrors the rest of the serving suite: every paged
configuration is compared against the path it replaces —

  - the Pallas kernel (interpret mode) against `ref.attention` on the
    live prefix (GQA grouping, MLA-shaped dk != dv heads, sliding
    window) and against the gather oracle `ref.paged_attention`;
  - the paged engine (paged=True) against the contiguous engine
    token-for-token on a float32 config, across archs covering paged
    GQA, paged MLA latents, ring+paged mixes (gemma3), hybrid
    mamba+attn (jamba) and M-RoPE (qwen2-vl), both prefill paths;
  - the scheduler under memory pressure (n_pages too small for the
    queue) against the unpressured run: FIFO completion order, no
    starvation of preempted requests, identical tokens.

Paged planes shard over the mesh member axis exactly like the
contiguous pool; run under
  XLA_FLAGS=--xla_force_host_platform_device_count=2
(scripts/ci.sh does) and the member axis actually spans two devices.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.common import sharding as shd
from repro.configs import registry
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.models import transformer as tf
from repro.serving import EnsembleEngine, Scheduler, kv_cache

CFG = registry.get_config("gemma3-1b", reduced=True).with_(dtype="float32")


def _params(cfg, K, seed=0):
    return jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))


# -- Pallas kernel vs oracles ------------------------------------------------


def _paged_case(B, S_max, lens, page, Hkv, dk, dv, seed=0):
    """Random paged planes + a shuffled page table, plus the gathered
    contiguous (B, S, Hkv, d) view for the dense oracle."""
    rng = np.random.default_rng(seed)
    P = -(-S_max // page)
    n_pages = B * P + 3  # a few pages stay free (unallocated sentinel)
    k_pages = rng.normal(size=(n_pages, page, Hkv, dk)).astype(np.float32)
    v_pages = rng.normal(size=(n_pages, page, Hkv, dv)).astype(np.float32)
    perm = rng.permutation(n_pages)
    table = np.full((B, P), n_pages, np.int32)
    pi = 0
    gk, gv = [], []
    for b in range(B):
        live = -(-int(lens[b]) // page)
        table[b, :live] = perm[pi:pi + live]
        pi += live
        t = np.minimum(table[b], n_pages - 1)
        gk.append(k_pages[t].reshape(P * page, Hkv, dk))
        gv.append(v_pages[t].reshape(P * page, Hkv, dv))
    return k_pages, v_pages, table, np.stack(gk), np.stack(gv)


@pytest.mark.parametrize("name,H,Hkv,dk,dv,window", [
    ("gqa-grouped", 8, 2, 32, 32, 0),        # g=4 grouped query heads
    ("gqa-kv1", 4, 1, 32, 32, 0),            # gemma-like shared kv head
    ("mla-expanded", 4, 4, 48, 32, 0),       # MLA: dk=nope+rope != dv
    ("sliding-window", 8, 2, 32, 32, 24),    # window < live length
])
def test_paged_kernel_matches_ref_attention(name, H, Hkv, dk, dv, window):
    """Interpret-mode kernel == ref.attention's decode row (the last
    query position of a causal run over the live prefix), fp32 tol."""
    B, S_max, page = 3, 64, 8
    lens = np.array([5, 33, 64])
    q = np.random.default_rng(1).normal(size=(B, H, dk)).astype(np.float32)
    kp, vp, table, gk, gv = _paged_case(B, S_max, lens, page, Hkv, dk, dv)
    got = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(table), jnp.asarray(lens, jnp.int32),
                          window=window, interpret=True)
    for b in range(B):
        L = int(lens[b])
        qf = np.zeros((1, L, H, dk), np.float32)
        qf[0, L - 1] = q[b]
        want = ref.attention(jnp.asarray(qf), jnp.asarray(gk[b:b + 1, :L]),
                             jnp.asarray(gv[b:b + 1, :L]), causal=True,
                             window=window)
        np.testing.assert_allclose(np.asarray(got[b]),
                                   np.asarray(want[0, L - 1]),
                                   atol=2e-5, rtol=1e-5)


def test_paged_kernel_matches_gather_oracle():
    """Kernel == kernels/ref.paged_attention (the lax reference the
    model path dispatches to off-TPU), same inputs bit for bit."""
    B, S_max, page, H, Hkv, dk, dv = 4, 32, 4, 8, 2, 16, 16
    lens = np.array([1, 7, 17, 32])
    q = np.random.default_rng(3).normal(size=(B, H, dk)).astype(np.float32)
    kp, vp, table, _, _ = _paged_case(B, S_max, lens, page, Hkv, dk, dv,
                                      seed=4)
    got = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(table), jnp.asarray(lens, jnp.int32),
                          interpret=True)
    want = ref.paged_attention(jnp.asarray(q), jnp.asarray(kp),
                               jnp.asarray(vp), jnp.asarray(table),
                               jnp.asarray(lens, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


# -- paged engine vs contiguous engine ---------------------------------------


@pytest.mark.parametrize("arch", ["gemma3-1b", "deepseek-7b",
                                  "deepseek-v2-236b", "jamba-v0.1-52b",
                                  "qwen2-vl-2b"])
def test_paged_engine_matches_contiguous(arch):
    """generate() through the paged pool == the contiguous engine,
    token for token: paged GQA, paged MLA latents, gemma3's ring+paged
    mix, jamba's mamba+attn hybrid, and M-RoPE all covered, with mixed
    prompt lengths exercising per-row positions and chunk-tail drops."""
    cfg = registry.get_config(arch, reduced=True).with_(dtype="float32")
    params = _params(cfg, 2)
    prompts = [np.arange(1, 10) % cfg.vocab_size, np.arange(2, 6)]
    kw = dict(n_slots=2, max_prompt=12, max_out=6, prefill_chunk=4)
    ref_eng = EnsembleEngine(cfg, params, **kw)
    got_eng = EnsembleEngine(cfg, params, paged=True, page_size=4, **kw)
    ref_out = ref_eng.generate(prompts, max_new=6)
    got_out = got_eng.generate(prompts, max_new=6)
    for a, b in zip(got_out, ref_out):
        np.testing.assert_array_equal(a, b)
    # recycling slots through the allocator leaks nothing
    again = got_eng.generate(prompts, max_new=6)
    for a, b in zip(again, ref_out):
        np.testing.assert_array_equal(a, b)


def test_paged_engine_per_token_reference_path():
    """prefill_chunk=0 (teacher-forcing prompt walk) also runs paged —
    decode-path writes land in prompt pages grown at admission."""
    params = _params(CFG, 2)
    prompts = [np.arange(1, 12) % CFG.vocab_size, np.arange(2, 5)]
    kw = dict(n_slots=2, max_prompt=12, max_out=6, prefill_chunk=0)
    ref_out = EnsembleEngine(CFG, params, **kw).generate(prompts, max_new=6)
    got = EnsembleEngine(CFG, params, paged=True, page_size=4,
                         **kw).generate(prompts, max_new=6)
    for a, b in zip(got, ref_out):
        np.testing.assert_array_equal(a, b)


def test_paged_engine_through_pallas_kernel(monkeypatch):
    """REPRO_USE_PALLAS=1 routes paged GQA decode through the interpret
    Pallas kernel; greedy tokens still match the contiguous engine."""
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    params = _params(CFG, 2)
    prompts = [np.arange(1, 8), np.arange(2, 5)]
    kw = dict(n_slots=2, max_prompt=8, max_out=4, prefill_chunk=4)
    got = EnsembleEngine(CFG, params, paged=True, page_size=4,
                         **kw).generate(prompts, max_new=4)
    monkeypatch.delenv("REPRO_USE_PALLAS")
    ref_out = EnsembleEngine(CFG, params, **kw).generate(prompts, max_new=4)
    for a, b in zip(got, ref_out):
        np.testing.assert_array_equal(a, b)


def test_paged_engine_on_member_mesh():
    """Paged pool + page table shard their leading (K,) axis over the
    member mesh like the contiguous pool: same tokens, K/M the cache
    bytes per device (1x1 degradation on a single-device host still
    runs the same shard_map program)."""
    params = _params(CFG, 4)
    mesh = shd.local_mesh(2, 1)
    M = mesh.shape[shd.MEMBER_AXIS]
    prompts = [np.arange(1, 10) % CFG.vocab_size, np.arange(2, 5)]
    kw = dict(n_slots=2, max_prompt=12, max_out=6, prefill_chunk=4,
              paged=True, page_size=6)
    single = EnsembleEngine(CFG, params, **kw)
    sharded = EnsembleEngine(CFG, params, mesh=mesh, **kw)
    ref_out = single.generate(prompts, max_new=6)
    got = sharded.generate(prompts, max_new=6)
    for a, b in zip(got, ref_out):
        np.testing.assert_array_equal(a, b)
    if M > 1:
        assert sharded.cache_bytes() == single.cache_bytes() // M


def test_paged_rejects_enc_dec_and_oversized_requests():
    whisper = registry.get_config("whisper-tiny", reduced=True)
    with pytest.raises(ValueError, match="enc-dec"):
        EnsembleEngine(whisper, _params(whisper, 1), n_slots=1,
                       max_prompt=4, max_out=4, paged=True)
    params = _params(CFG, 1)
    eng = EnsembleEngine(CFG, params, n_slots=2, max_prompt=8, max_out=8,
                         paged=True, page_size=4, n_pages=2)
    # 8 prompt + 8 new tokens needs 4 pages; the pool holds 2 — this
    # request could NEVER complete, so it must be rejected at the door
    with pytest.raises(ValueError, match="pages"):
        eng.validate_request(np.arange(1, 9), 8)


def test_paged_step_raises_when_pool_dry():
    """engine.step() without a preempting scheduler must fail loudly —
    silently stalling a slot would corrupt its stream."""
    params = _params(CFG, 1)
    eng = EnsembleEngine(CFG, params, n_slots=2, max_prompt=4, max_out=8,
                         prefill_chunk=4, paged=True, page_size=4,
                         n_pages=3)  # each request alone fits (3 pages)
    eng.update_slots(admits=[(0, np.arange(1, 5), 8),
                             (1, np.arange(1, 5), 8)])
    eng.prefill(0)
    eng.prefill(1)
    with pytest.raises(RuntimeError, match="out of pages"):
        for _ in range(8):  # both slots want a decode page; only 1 free
            eng.step()


def test_generate_oversubscribed_pool_with_eos_finishes():
    """The host page mirror cannot see an EOS finish; generate() (no
    harvest loop) must fetch done flags on an oversubscribed pool so a
    finished slot stops taking pages — without that, the free list runs
    dry on pages nobody needs and step() raises spuriously."""
    params = _params(CFG, 1)
    kw = dict(n_slots=2, max_prompt=4, max_out=8, prefill_chunk=4,
              paged=True, page_size=4)
    prompts = [np.arange(1, 5), np.arange(2, 6)]
    probe = EnsembleEngine(CFG, params, **kw)
    eos = int(probe.generate(prompts, max_new=8)[0][0])  # slot 0's first
    ref_out = EnsembleEngine(CFG, params, eos_id=eos, **kw).generate(
        prompts, max_new=8)
    # 5 pages: enough for the EOS-shortened run, NOT enough if the done
    # slot kept growing its chain to the full plen+max_new
    tight = EnsembleEngine(CFG, params, eos_id=eos, n_pages=5, **kw)
    got = tight.generate(prompts, max_new=8)
    for a, b in zip(got, ref_out):
        np.testing.assert_array_equal(a, b)


# -- allocator unit behavior -------------------------------------------------


def test_page_allocator_alloc_release_reuse():
    a = kv_cache.PageAllocator(n_pages=6, page_size=4, n_slots=3,
                               pages_per_slot=4)
    assert a.free_pages == 6 and a.pages_for(9) == 3
    assert a.alloc(0, 2) and a.alloc(1, 3)
    assert a.free_pages == 1 and a.held_pages(0) == 2
    assert a.holds(0, 7) and not a.holds(0, 8)
    # all-or-nothing: a failed grow leaves state untouched
    assert not a.alloc(2, 2)
    assert a.free_pages == 1 and a.held_pages(2) == 0
    # per-slot table width is enforced even with pages free
    assert not a.alloc(0, 5)
    t = a.table()
    assert t.shape == (3, 4)
    assert set(t[0, :2]) | set(t[1, :3]) == set(range(5))
    assert (t[2] == 6).all() and (t[0, 2:] == 6).all()  # sentinel
    assert a.release(1) == 3 and a.free_pages == 4
    # released pages are reusable and tables stay disjoint
    assert a.alloc(2, 4)
    t = a.table()
    assert len(set(t[0, :2]) | set(t[2])) == 6


def test_release_leaves_in_flight_slot_planes_bit_identical():
    """Satellite regression: releasing one slot must not touch the
    other B-1 slots' planes — masked per-slot update, bit-exact."""
    K, B = 2, 3
    pool = kv_cache.init_pool(CFG, K, B, 16)
    # make every leaf nonzero so an accidental full-plane zeroing shows
    pool = jax.tree.map(
        lambda x: x + jnp.arange(x.size, dtype=x.dtype).reshape(x.shape)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, pool)
    mask = jnp.array([False, True, False])  # release only slot 1
    reset = kv_cache.reset_slots(pool, mask)

    def rows(tree, b):
        return [np.asarray(leaf[:, :, b]) for leaf in
                jax.tree.leaves(tree["segments"])]

    for b in (0, 2):  # in-flight neighbors: bit-identical
        for before, after in zip(rows(pool, b), rows(reset, b)):
            np.testing.assert_array_equal(before, after)
    np.testing.assert_array_equal(np.asarray(reset["idx"][:, 1]), [0] * K)


# -- scheduler under memory pressure -----------------------------------------


def _pressure_setup(n_pages=None):
    cfg = registry.get_config("deepseek-7b", reduced=True).with_(
        dtype="float32")
    params = _params(cfg, 2)
    eng = EnsembleEngine(cfg, params, n_slots=4, max_prompt=8, max_out=8,
                         prefill_chunk=4, paged=True, page_size=4,
                         n_pages=n_pages)
    reqs = [(np.arange(1, 8), 8), (np.arange(2, 7), 8), (np.arange(3, 9), 8),
            (np.arange(1, 5), 8), (np.arange(2, 5), 8), (np.arange(4, 9), 6)]
    return eng, reqs


def test_scheduler_memory_pressure_preempts_and_stays_fifo():
    """More requests queued than the page pool can hold concurrently:
    the free list runs dry mid-decode, the scheduler preempts back to
    the queue, and the run must (a) complete every request, (b) finish
    in FIFO order, (c) not starve preempted requests, (d) emit exactly
    the unpressured run's tokens."""
    ref_eng, reqs = _pressure_setup()           # default pool: no pressure
    ref_sched = Scheduler(ref_eng)
    ref_rids = [ref_sched.submit(t, m) for t, m in reqs]
    ref_comp = ref_sched.run()
    assert ref_sched.preemptions == 0

    eng, reqs = _pressure_setup(n_pages=6)      # 6 pages for a 4-slot batch
    sched = Scheduler(eng)
    rids = [sched.submit(t, m) for t, m in reqs]
    comps = sched.run()

    assert set(comps) == set(rids)              # nobody starved
    assert sched.preemptions > 0                # pressure actually bit
    for r_ref, r in zip(ref_rids, rids):        # token-for-token
        np.testing.assert_array_equal(comps[r].tokens,
                                      ref_comp[r_ref].tokens)
    finish_order = sorted(rids, key=lambda r: comps[r].finish_t)
    assert finish_order == rids                 # FIFO completions
    # under pressure fewer requests fit concurrently than slots exist
    assert sched.peak_in_flight <= eng.n_slots


def test_scheduler_admits_by_pages_not_slots():
    """With a roomy pool the paged scheduler fills every slot; with a
    tiny one it admits only what the free list covers."""
    eng, reqs = _pressure_setup()
    sched = Scheduler(eng)
    for t, m in reqs[:4]:
        sched.submit(t, m)
    sched._fill_slots()
    assert sched.peak_in_flight == 4

    eng2, reqs = _pressure_setup(n_pages=5)     # room for two 2-page prompts
    sched2 = Scheduler(eng2)
    for t, m in reqs[:4]:
        sched2.submit(t, m)
    sched2._fill_slots()
    assert sched2.peak_in_flight == 2
    assert len(sched2.pending) == 2
