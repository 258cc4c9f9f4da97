"""Quantized KV pages + absorbed-MLA paged decode (ISSUE 9).

Layers of evidence, cheapest first:

  - unit: kv_quantize/kv_dequantize roundtrip error bounds (int8 within
    half a quantization step of its per-token absmax scale; fp8 within
    e4m3's relative precision), all-zero vectors exact;
  - kernel: the Pallas paged_attention with k_scale/v_scale/k_extra
    inputs vs kernels/ref.paged_attention's dequant reference, plus the
    unquantized path staying exact;
  - pool layout: int8 pools store "_pages" planes at 1 byte with f32
    "_scale_pages" sidecars, MLA rope keys stay native (they feed the
    kernel as the unquantized k_extra block), sliding-window rings and
    recurrent state stay untouched, page_bytes accounts the real
    (quantized) bytes;
  - engine: int8 paged greedy output vs the f32 contiguous reference
    within a bounded agreement delta across the GQA / ring-mix / MLA
    archs (tiny random-init members sit near argmax ties, so the bound
    is generous, not zero); kv_dtype="f32" allocates the IDENTICAL pool
    as today; absorbed-MLA paged decode stays token-exact at f32 with
    per-step FLOPs ~flat in max_seq;
  - composition: prefix-cache COW sharing, speculative rollback and a
    member mesh all run over quantized pages unchanged (warm vs cold
    and spec vs plain stay token-exact WITHIN the int8 engines: the
    same stored pages dequantize to the same values everywhere).

The >= 2x equal-bytes concurrency gate lives in
benchmarks/serving_bench.py --kv-quant (scripts/ci.sh runs it, also
under a forced-2-device mesh).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.common import sharding as shd
from repro.configs import registry
from repro.kernels import ref
from repro.kernels import paged_attention as pk
from repro.models import transformer as tf
from repro.models.attention import KV_DTYPES, kv_dequantize, kv_quantize
from repro.serving import EnsembleEngine, kv_cache

GQA = registry.get_config("deepseek-7b", reduced=True).with_(
    dtype="float32")
GEMMA = registry.get_config("gemma3-1b", reduced=True).with_(
    dtype="float32")
MLA = registry.get_config("deepseek-v2-236b", reduced=True).with_(
    dtype="float32")
ARCHS = {"deepseek-7b": GQA, "gemma3-1b": GEMMA, "deepseek-v2-236b": MLA}


def _params(cfg, K=2, seed=0):
    return jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))


def _prompts(cfg):
    return [np.arange(1, 12) % cfg.vocab_size, np.arange(2, 5),
            np.arange(3, 10), np.arange(1, 7)]


_KW = dict(n_slots=4, max_prompt=12, max_out=8, prefill_chunk=4)


@pytest.fixture(scope="module")
def contig_ref():
    """f32 contiguous greedy outputs per arch — the quality reference."""
    out = {}
    for name, cfg in ARCHS.items():
        eng = EnsembleEngine(cfg, _params(cfg), **_KW)
        out[name] = eng.generate(_prompts(cfg), max_new=8)
    return out


# -- roundtrip bounds --------------------------------------------------------


def test_int8_roundtrip_error_bound():
    v = jax.random.normal(jax.random.PRNGKey(0), (64, 8, 32),
                          jnp.float32) * 3.0
    q, s = kv_quantize(v, jnp.int8)
    assert q.dtype == jnp.int8 and s.shape == v.shape[:-1]
    d = kv_dequantize(q, s)
    # within half a quantization step of each vector's absmax scale
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    assert np.all(np.abs(np.asarray(d - v)) <= bound)


def test_fp8_roundtrip_error_bound():
    v = jax.random.normal(jax.random.PRNGKey(1), (32, 4, 16), jnp.float32)
    q, s = kv_quantize(v, jnp.float8_e4m3fn)
    d = kv_dequantize(q, s)
    amax = np.abs(np.asarray(v)).max(-1, keepdims=True)
    # e4m3 keeps ~4 bits of mantissa headroom at the top of the range
    assert np.all(np.abs(np.asarray(d - v)) <= 0.08 * amax + 1e-6)


def test_quantize_all_zero_vector_is_exact():
    v = jnp.zeros((4, 2, 8), jnp.float32)
    q, s = kv_quantize(v, jnp.int8)
    np.testing.assert_array_equal(np.asarray(s), 0.0)
    np.testing.assert_array_equal(np.asarray(kv_dequantize(q, s)), 0.0)


# -- kernel vs dequant reference ---------------------------------------------


def _paged_inputs(dk, dv, dr=0, B=3, Hkv=2, n_pages=12, page=4, P=4,
                  seed=0):
    rng = np.random.default_rng(seed)
    kq = jnp.asarray(rng.integers(-127, 128, (n_pages, page, Hkv, dk)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (n_pages, page, Hkv, dv)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, (n_pages, page, Hkv)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.01, 0.1, (n_pages, page, Hkv)),
                     jnp.float32)
    ke = jnp.asarray(rng.normal(size=(n_pages, page, Hkv, dr)),
                     jnp.float32) if dr else None
    table = jnp.asarray(rng.permutation(n_pages)[:B * P].reshape(B, P),
                        jnp.int32)
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 2 * Hkv, dk + dr)), jnp.float32)
    return q, kq, vq, ks, vs, ke, table, lens


def test_kernel_matches_ref_quantized():
    q, kq, vq, ks, vs, _, table, lens = _paged_inputs(16, 16)
    want = ref.paged_attention(q, kq, vq, table, lens, k_scale=ks,
                               v_scale=vs)
    got = pk.paged_attention(q, kq, vq, table, lens, k_scale=ks,
                             v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_ref_quantized_with_extra():
    """The absorbed-MLA shape: int8 latents + unquantized rope keys."""
    q, kq, vq, ks, vs, ke, table, lens = _paged_inputs(16, 16, dr=8)
    scale = (16 + 8) ** -0.5
    want = ref.paged_attention(q, kq, vq, table, lens, scale=scale,
                               k_scale=ks, v_scale=vs, k_extra=ke)
    got = pk.paged_attention(q, kq, vq, table, lens, scale=scale,
                             k_scale=ks, v_scale=vs, k_extra=ke,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_unquantized_path_still_exact():
    rng = np.random.default_rng(3)
    kf = jnp.asarray(rng.normal(size=(12, 4, 2, 16)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(12, 4, 2, 16)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
    table = jnp.asarray(rng.permutation(12).reshape(3, 4), jnp.int32)
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    want = ref.paged_attention(q, kf, vf, table, lens)
    got = pk.paged_attention(q, kf, vf, table, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


# -- pool layout + accounting ------------------------------------------------


def _pool_leaves(pool):
    out = {}

    def visit(path, x):
        name = next((str(e.key) for e in reversed(path)
                     if isinstance(e, jax.tree_util.DictKey)), "")
        out.setdefault(name, []).append(x)

    jax.tree_util.tree_map_with_path(visit, pool["segments"])
    return out


def test_pool_layout_int8_gqa_ring_untouched():
    # max_seq above gemma's reduced local_window (16) so the sliding
    # layers keep rings while the global layers page
    pool = kv_cache.init_pool(GEMMA, 2, 2, 32, page_size=4, n_pages=8,
                              kv_dtype="int8")
    leaves = _pool_leaves(pool)
    for x in leaves["k_pages"] + leaves["v_pages"]:
        assert x.dtype == jnp.int8
    for x in leaves["k_scale_pages"] + leaves["v_scale_pages"]:
        assert x.dtype == jnp.float32
        assert x.shape[-1] == GEMMA.attn.n_kv_heads  # per-token/per-head
    # gemma3's sliding-window rings stay contiguous AND unquantized
    for x in leaves["k"] + leaves["v"]:
        assert x.dtype == jnp.float32


def test_pool_layout_int8_mla_rope_stays_native():
    pool = kv_cache.init_pool(MLA, 2, 2, 16, page_size=4, n_pages=8,
                              kv_dtype="int8")
    leaves = _pool_leaves(pool)
    for x in leaves["c_kv_pages"]:
        assert x.dtype == jnp.int8
    for x in leaves["c_kv_scale_pages"]:
        assert x.dtype == jnp.float32
    # rope keys feed the kernel as the unquantized k_extra block
    for x in leaves["k_r_pages"]:
        assert x.dtype == jnp.float32
    assert "k_r_scale_pages" not in leaves


def test_pool_f32_is_identical_to_default():
    base = kv_cache.init_pool(GQA, 2, 2, 16, page_size=4, n_pages=8)
    same = kv_cache.init_pool(GQA, 2, 2, 16, page_size=4, n_pages=8,
                              kv_dtype="f32")
    assert (jax.tree_util.tree_structure(base)
            == jax.tree_util.tree_structure(same))
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(same)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_page_bytes_accounts_quantized_bytes():
    kw = dict(page_size=4, n_pages=8)
    pb = {d: kv_cache.page_bytes(
        kv_cache.init_pool(GQA, 2, 2, 16, kv_dtype=d, **kw), 8)
        for d in ("f32", "bf16", "int8")}
    assert pb["bf16"] == pb["f32"] // 2
    # int8 planes cost 1/4 the bytes; the f32 scale sidecar adds
    # 1/head_dim back, still well under a third of the f32 pool
    assert pb["int8"] < pb["f32"] // 3
    assert kv_cache.page_bytes(
        kv_cache.init_pool(GQA, 2, 2, 16, kv_dtype="int8", **kw),
        8) * 8 < kv_cache.pool_bytes(
        kv_cache.init_pool(GQA, 2, 2, 16, kv_dtype="int8", **kw))


def test_engine_kv_dtype_validation():
    params = _params(GQA)
    with pytest.raises(ValueError, match="kv_dtype"):
        EnsembleEngine(GQA, params, kv_dtype="int4", **_KW)
    with pytest.raises(ValueError, match="paged"):
        EnsembleEngine(GQA, params, kv_dtype="int8", **_KW)
    assert "int8" in KV_DTYPES and "fp8" in KV_DTYPES


def test_engine_page_stats_reports_bytes():
    eng = EnsembleEngine(GQA, _params(GQA), paged=True, page_size=4,
                         kv_dtype="int8", **_KW)
    ps = eng.page_stats()
    assert ps["kv_dtype"] == "int8" and ps["kv_quantized"] == 1
    assert ps["page_bytes"] > 0
    assert ps["bytes_per_token"] == ps["page_bytes"] // ps["page_size"]


# -- engine quality ----------------------------------------------------------


@pytest.mark.parametrize("arch", list(ARCHS))
def test_int8_quality_bounded_vs_f32_reference(arch, contig_ref):
    cfg = ARCHS[arch]
    got = EnsembleEngine(cfg, _params(cfg), paged=True, page_size=4,
                         kv_dtype="int8", **_KW).generate(_prompts(cfg),
                                                          max_new=8)
    agree = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                     for a, b in zip(got, contig_ref[arch])])
    assert agree >= 0.75, f"{arch} int8 agreement {agree:.3f}"


def test_fp8_quality_bounded(contig_ref):
    got = EnsembleEngine(GQA, _params(GQA), paged=True, page_size=4,
                         kv_dtype="fp8", **_KW).generate(_prompts(GQA),
                                                         max_new=8)
    agree = np.mean([np.mean(np.asarray(a) == np.asarray(b))
                     for a, b in zip(got, contig_ref["deepseek-7b"])])
    assert agree >= 0.5, f"fp8 agreement {agree:.3f}"


# -- absorbed MLA ------------------------------------------------------------


def test_absorbed_mla_token_exact_f32(contig_ref):
    """The absorbed reassociation must not change greedy output at f32
    (paged vs contiguous stays token-exact, the PR-4 invariant)."""
    got = EnsembleEngine(MLA, _params(MLA), paged=True, page_size=4,
                         **_KW).generate(_prompts(MLA), max_new=8)
    for a, b in zip(got, contig_ref["deepseek-v2-236b"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_absorb_mla_params_matches_inline_split():
    from repro.models.attention import mla_absorbed
    params = tf.init(jax.random.PRNGKey(0), MLA)
    absorbed = tf.absorb_mla_params(MLA, params)
    seg_raw = params["segments"][0]["slot_0"]["attn"]
    seg_abs = absorbed["segments"][0]["slot_0"]["attn"]
    assert "kv_uk" in seg_abs and "kv_uk" not in seg_raw
    per_layer = {k: v[0] for k, v in seg_raw.items()}
    w_uk, w_uv = mla_absorbed(per_layer, MLA.attn)  # inline fallback
    np.testing.assert_array_equal(np.asarray(w_uk),
                                  np.asarray(seg_abs["kv_uk"][0]))
    np.testing.assert_array_equal(np.asarray(w_uv),
                                  np.asarray(seg_abs["kv_uv"][0]))


def test_absorbed_step_flops_flat_in_max_seq():
    """Regression: the per-step gather+kv_up expand put O(max_seq)
    FLOPs on the decode loop (~3.4x at 4x max_seq on these shapes);
    absorbed decode must stay under 2x."""
    p = tf.absorb_mla_params(MLA, tf.init(jax.random.PRNGKey(0), MLA))

    def step_flops(max_seq):
        cache = tf.init_slot_cache(MLA, 2, max_seq, page_size=16,
                                   n_pages=2 * (max_seq // 16))
        toks = jnp.zeros((2, 1), jnp.int32)
        comp = jax.jit(
            lambda pr, c, t: tf.decode_step_paged(pr, MLA, c, t)
        ).lower(p, cache, toks).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca.get("flops", 0.0))

    ratio = step_flops(512) / max(step_flops(128), 1.0)
    assert ratio <= 2.0, f"decode-step FLOPs grew {ratio:.2f}x over 4x"


def test_swap_params_validates_raw_tree_and_reabsorbs():
    """swap_params takes RAW checkpoints (no absorbed leaves) and must
    re-derive kv_uk/kv_uv from the new weights."""
    old = _params(MLA, seed=0)
    new = _params(MLA, seed=1)
    eng = EnsembleEngine(MLA, old, paged=True, page_size=4, **_KW)
    eng.swap_params(new)
    got = eng.generate(_prompts(MLA), max_new=8)
    want = EnsembleEngine(MLA, new, paged=True, page_size=4,
                          **_KW).generate(_prompts(MLA), max_new=8)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # wrong-K stacks still rejected against the RAW spec
    with pytest.raises(ValueError, match="swap_params"):
        eng.swap_params(_params(MLA, K=3))


# -- composition: prefix/COW, speculative rollback, member mesh --------------


def test_prefix_cow_int8_warm_exact_vs_cold():
    """Prefix hits replay QUANTIZED pages written by another request;
    COW copies planes + scales together — warm must stay token-exact
    vs a cold int8 engine."""
    params = _params(GQA)
    kw = dict(n_slots=3, max_prompt=24, max_out=6, prefill_chunk=4,
              paged=True, page_size=4, kv_dtype="int8", seed=0)
    shared = list(range(100, 118))
    p1 = np.array(shared + [7, 8], np.int32)
    p2 = np.array(shared + [9, 10, 11], np.int32)  # diverges mid-page
    cold = EnsembleEngine(GQA, params, **kw)
    ref_out = cold.generate([p1, p2], 5)
    warm = EnsembleEngine(GQA, params, prefix_cache=True, **kw)
    np.testing.assert_array_equal(ref_out[0],
                                  warm.generate([p1], 5)[0])
    np.testing.assert_array_equal(ref_out[1],
                                  warm.generate([p2], 5)[0])
    ps = warm.page_stats()
    assert ps["prefix_hits"] >= 1 and ps["cow_pages"] >= 1
    # and the original pages survived the COW writer bit-intact
    np.testing.assert_array_equal(ref_out[0],
                                  warm.generate([p1], 5)[0])


def test_spec_rollback_int8_bit_identical():
    """Speculative decoding over quantized pages (verify writes gamma
    quantized tokens, rejection truncates the page chain) must never
    change tokens vs the plain int8 engine."""
    from repro.serving import SpeculativeEngine
    K, B, plen, steps = 2, 3, 6, 8
    params = _params(GEMMA, K=K, seed=7)
    student = jax.tree.map(lambda x: x[0], params)
    prompts = list(np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, plen), 0, GEMMA.vocab_size)))
    kw = dict(n_slots=B, max_prompt=plen, max_out=steps,
              prefill_chunk=4, paged=True, page_size=4, n_pages=64,
              kv_dtype="int8")
    ref_out = EnsembleEngine(GEMMA, params, **kw).generate(
        prompts, max_new=steps)
    spec = SpeculativeEngine(GEMMA, params, student, gamma=3, **kw)
    outs = spec.generate(prompts, max_new=steps)
    for a, b in zip(outs, ref_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert spec.spec_stats()["spec_steps"] > 0


def test_mesh_int8_token_exact_and_sharded_scales():
    """Quantized planes AND their scale sidecars shard over the member
    axis; the sharded int8 engine is token-exact vs unsharded int8."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device host "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    params = _params(GQA)
    kw = dict(paged=True, page_size=4, kv_dtype="int8", **_KW)
    want = EnsembleEngine(GQA, params, **kw).generate(_prompts(GQA),
                                                      max_new=8)
    mesh = shd.local_mesh(2, 1)
    eng = EnsembleEngine(GQA, params, mesh=mesh, **kw)
    got = eng.generate(_prompts(GQA), max_new=8)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-device pool: each device holds its K/M members' planes
    assert eng.cache_bytes() < kv_cache.pool_bytes(eng.cache,
                                                   per_device=False)
