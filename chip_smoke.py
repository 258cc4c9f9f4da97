#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls.  It proves that the program starts and answers correctly on
the chip; its timings are smoke, not benchmark numbers.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips: the member-sharded path only

One chip, in one process and in this order:
  kernels   the Pallas paged-attention kernel against kernels/ref at the
            serving shapes below, with bf16 and int8 pages, and the fused
            distillation loss (forward and gradient) against its reference
  serving   deepseek-7b at its published widths, cut to 4 of its 30
            layers: K=2 members, bf16 weights, a paged pool of 8 slots,
            behind the objects `serve.py --http` builds (EnsembleEngine ->
            Replica/Router -> serve_frontend).  16 requests over HTTP with
            128-1024-token prompts and 32-64 new tokens, once with native
            (bf16) pages and once with int8 pages
  training  the EC round on the paper's NiN (`repro.launch.train.main`):
            K=4 members, 2 rounds of tau=2 steps; round 2 starts with a
            distillation step through the fused loss kernel

Four chips: K=4 deepseek-7b members at published widths, one per chip on a
4x1 ("member", "data") mesh, fused across chips by ensemble_log_probs_psum;
compared with the same 4-member stack on one chip on the fused log-probs of
the first teacher-forced decode steps.

Any failed check exits non-zero.  The last line of stdout is one JSON
object naming the device; nothing is printed there when a check fails or
when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent

# serving shapes of the one-chip smoke
SLOTS, MAX_PROMPT, MAX_OUT, PAGE = 8, 1024, 64, 16
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 16, (128, 1024), (32, 64)
SERVE_MEMBERS, SERVE_LAYERS = 2, 4

# Tolerances, each with its reason:
# - paged kernel vs reference: both return the model's bf16, whose step
#   is 2^-8 of the value; attention outputs of unit-normal values are
#   below 4 in magnitude, so 2e-2 allows a few bf16 steps and nothing more.
KERNEL_TOL = dict(atol=2e-2, rtol=2e-2)
# - fused distillation loss vs its f32 reference: the same f32 sums taken
#   in another order, so only f32 rounding differs.
DISTILL_TOL = dict(atol=1e-5, rtol=1e-4)
# - mesh vs one chip, fused log-probs: the same bf16 members compiled for
#   a batch of 1 member per chip or of 4 on one chip, so matmul tilings and
#   hence bf16 roundings may differ.  Member logits are about unit normal,
#   where a bf16 step is 2^-8..2^-7; 0.05 nats allows several such steps,
#   while dropping one member moves the fused log-probs by far more (the
#   run prints that distance next to the tolerance).
FUSED_TOL = 0.05


def say(msg: str):
    print(f"smoke: {msg}", flush=True)


def fail(msg: str):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


# -- kernels ------------------------------------------------------------------


def paged_inputs(cfg, kv_dtype: str, seed: int = 0):
    """A full pool at the serving shapes with random live lengths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import attention as attn_mod
    a = cfg.attn
    per_slot = -(-(MAX_PROMPT + MAX_OUT) // PAGE)
    n_pages = SLOTS * per_slot
    rng = np.random.default_rng(seed)
    kv = rng.normal(size=(2, n_pages, PAGE, a.n_kv_heads, a.head_dim))
    q = jnp.asarray(rng.normal(size=(SLOTS, a.n_heads, a.head_dim)),
                    jnp.bfloat16)
    table = jnp.asarray(rng.permutation(n_pages).reshape(SLOTS, per_slot),
                        jnp.int32)
    lens = jnp.asarray(rng.integers(1, per_slot * PAGE + 1, SLOTS),
                       jnp.int32)
    k, v = jnp.asarray(kv[0], jnp.bfloat16), jnp.asarray(kv[1], jnp.bfloat16)
    scales = {}
    if attn_mod.kv_quantized(kv_dtype):
        qdt = attn_mod.kv_storage_dtype(kv_dtype, jnp.bfloat16)
        k, ks = attn_mod.kv_quantize(k, qdt)
        v, vs = attn_mod.kv_quantize(v, qdt)
        scales = dict(k_scale=ks, v_scale=vs)
    jax.block_until_ready((k, v))
    return (q, k, v, table, lens), scales


def kernel_checks(cfg):
    """The paged kernel the decode step calls (ops -> Pallas on TPU) vs
    kernels/ref at full precision, bf16 and int8 pages; then the fused
    Eqn-9 loss, vmapped over members like the trainer's step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    check(ops.pallas_enabled(), "Pallas kernels are not enabled")
    scale = cfg.attn.head_dim ** -0.5
    for kv_dtype in ("f32", "int8"):
        args, scales = paged_inputs(cfg, kv_dtype)
        got = jax.jit(lambda *a, **kw: ops.paged_attention(
            *a, scale=scale, **kw))(*args, **scales)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a, **kw: ref.paged_attention(
                *a, scale=scale, **kw))(*args, **scales)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"paged kernel ({kv_dtype}): "
              f"non-finite output")
        err = float(np.abs(got - want).max())
        ok = np.allclose(got, want, **KERNEL_TOL)
        say(f"paged kernel vs ref, {kv_dtype} pages, {SLOTS} slots x "
            f"{cfg.attn.n_heads} heads x {cfg.attn.head_dim}, page "
            f"{PAGE}: max |diff| {err:.3e} (tol {KERNEL_TOL})")
        check(ok, f"paged kernel ({kv_dtype}) disagrees with the reference")

    K, N, V = 4, 64, 100
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    logits = jax.random.normal(key[0], (K, N, V)) * 3
    labels = jax.random.randint(key[1], (K, N), 0, V)
    pseudo = jax.nn.softmax(jax.random.normal(key[2], (K, N, V)))
    lam = jnp.full((K,), 0.5)

    def both(loss):
        return jax.jit(jax.vmap(jax.value_and_grad(loss)))(
            logits, labels, pseudo, lam)

    (l_got, g_got) = both(ops.fused_distill_loss)
    with jax.default_matmul_precision("highest"):
        (l_want, g_want) = both(ref.distill_loss)
    err = max(float(jnp.abs(l_got - l_want).max()),
              float(jnp.abs(g_got - g_want).max()))
    say(f"fused distill loss + grad vs ref, K={K} x {N} rows x {V} "
        f"classes: max |diff| {err:.3e} (tol {DISTILL_TOL})")
    check(np.allclose(l_got, l_want, **DISTILL_TOL)
          and np.allclose(g_got, g_want, **DISTILL_TOL),
          "fused distillation loss disagrees with the reference")


# -- serving ------------------------------------------------------------------


def memory_line(dev) -> str:
    st = dev.memory_stats() or {}
    gib = lambda k: st.get(k, 0) / 2 ** 30  # noqa: E731
    return (f"device memory {gib('bytes_in_use'):.2f} GiB in use, peak "
            f"{gib('peak_bytes_in_use'):.2f} GiB, limit "
            f"{gib('bytes_limit'):.2f} GiB")


def http_requests(url: str, reqs, concurrency: int):
    """Drive `reqs` over POST /v1/generate (SSE) from `concurrency`
    client threads; -> (results, errors)."""
    from repro.serving import client
    results, errors = [None] * len(reqs), []
    nxt, lock = iter(range(len(reqs))), threading.Lock()

    def worker():
        while True:
            with lock:
                i = next(nxt, None)
            if i is None:
                return
            toks, max_new = reqs[i]
            try:
                results[i] = client.http_generate(url, toks, max_new,
                                                  stream=True, timeout=600)
            except Exception as e:  # noqa: BLE001 — reported, then fatal
                errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, errors


def serve_pass(cfg, params, kv_dtype: str, reqs, dev):
    """One engine behind the HTTP frontend, as `serve.py --http` mounts
    it; every request must come back with its requested token count."""
    from repro.serving import EnsembleEngine
    from repro.serving.frontend import Replica, Router, serve_frontend
    t0 = time.time()
    engine = EnsembleEngine(cfg, params, n_slots=SLOTS,
                            max_prompt=MAX_PROMPT, max_out=MAX_OUT,
                            paged=True, page_size=PAGE, kv_dtype=kv_dtype)
    # compile prefill + decode before the frontend opens, as a replica
    # process does before its ready handshake
    engine.generate([reqs[0][0]], max_new=2)
    engine.update_slots(release=range(engine.n_slots))
    ps = engine.page_stats()
    say(f"[{kv_dtype} pages] engine built and compiled in "
        f"{time.time() - t0:.1f} s: pool {ps['n_pages']} pages x {PAGE} "
        f"tokens, {engine.cache_bytes() / 2 ** 30:.2f} GiB, "
        f"{ps['bytes_per_token']} B/token; {memory_line(dev)}")

    srv = serve_frontend(Router([Replica("r0", engine)]), port=0,
                         verbose=False)
    try:
        t0 = time.time()
        results, errors = http_requests(srv.url, reqs, concurrency=SLOTS)
        wall = time.time() - t0
    finally:
        srv.shutdown(drain=True)
    check(not errors, f"[{kv_dtype} pages] failed requests: {errors[:3]}")
    for i, ((toks, max_new), out) in enumerate(zip(reqs, results)):
        check(len(out["tokens"]) == max_new,
              f"[{kv_dtype} pages] request {i} returned "
              f"{len(out['tokens'])} of {max_new} tokens")
    n_tok = sum(len(r["tokens"]) for r in results)
    ttft = sorted(r["ttft"] for r in results)
    say(f"[{kv_dtype} pages] {len(results)} requests over HTTP, each with "
        f"its requested token count: {n_tok} tokens in {wall:.1f} s, "
        f"ttft median {ttft[len(ttft) // 2]:.2f} s (smoke timings, not a "
        f"benchmark); {memory_line(dev)}")
    del engine, srv
    gc.collect()


def scoring(cfg, params):
    """Teacher-forced NLLs through the engine's scoring step, which runs
    the member forward and Eqn-6 fusion of decode: finite NLLs mean
    finite logits, and the mixture never scores worse than its members'
    mean (Jensen).  A contiguous engine: `score` has no paged form."""
    import jax
    import numpy as np

    from repro.serving import EnsembleEngine
    rng = np.random.default_rng(1)
    toks, labels = rng.integers(0, cfg.vocab_size, (2, 2, 16),
                                dtype=np.int32)
    engine = EnsembleEngine(cfg, params, n_slots=1, max_prompt=1, max_out=1)
    m_nll, e_nll = jax.device_get(engine.score(toks, labels))
    say(f"scoring: member NLL {np.round(m_nll, 4).tolist()}, ensemble "
        f"NLL {float(e_nll):.4f}")
    check(np.isfinite(m_nll).all() and np.isfinite(e_nll),
          "non-finite logits in the scoring step")
    check(float(e_nll) <= float(np.mean(m_nll)) + 1e-4,
          "fused NLL above the members' mean (Jensen bound broken)")


def serving(dev):
    import jax

    from repro.configs import registry
    from repro.models import transformer as tf
    from repro.serving import client
    full = registry.get_config("deepseek-7b")
    cfg = full.with_(n_layers=SERVE_LAYERS)
    say(f"serving deepseek-7b at published widths (d_model {cfg.d_model}, "
        f"{cfg.attn.n_heads} heads x {cfg.attn.head_dim}, d_ff "
        f"{cfg.ffn.d_ff}, vocab {cfg.vocab_size}); depth cut from "
        f"{full.n_layers} to {cfg.n_layers} layers; random weights from "
        f"seed 0")
    kernel_checks(cfg)
    t0 = time.time()
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), SERVE_MEMBERS))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    say(f"K={SERVE_MEMBERS} members, {cfg.dtype} params "
        f"{n_bytes / 2 ** 30:.2f} GiB, initialized in "
        f"{time.time() - t0:.1f} s; {memory_line(dev)}")
    reqs = client.make_requests(N_REQUESTS, cfg.vocab_size,
                                prompt_len=PROMPT_LEN, max_new=NEW_TOKENS,
                                seed=0)
    for kv_dtype in ("f32", "int8"):
        serve_pass(cfg, params, kv_dtype, reqs, dev)
    scoring(cfg, params)
    del params
    gc.collect()


# -- training -----------------------------------------------------------------


def training():
    from repro.kernels import ops
    from repro.launch import train
    argv = ["--arch", "paper_nin", "--members", "4", "--rounds", "2",
            "--tau", "2", "--p-steps", "1"]
    say(f"EC round on paper_nin: train.main({' '.join(argv)})")
    t0 = time.time()
    with mock.patch.object(ops, "fused_distill_loss",
                           wraps=ops.fused_distill_loss) as fused:
        rc = train.main(argv)
    check(rc == 0, "EC round: a loss was not finite")
    check(fused.call_count > 0,
          "EC round: no distillation step went through the fused kernel")
    say(f"EC round done in {time.time() - t0:.1f} s, distillation step "
        f"traced through the fused kernel")


# -- four chips ---------------------------------------------------------------


def fused_step(engine, B: int, T: int):
    """A compiled teacher-forced decode step returning the Eqn-6 fused
    log-probs (B, V): the engine's own member forward and fusion
    (`_member_logits`, `_fuse`), compiled as the engine compiles its
    kernels (`_compile`: shard_map over the member axis on a mesh).
    -> (compiled step, factory of empty (B, T) cache pools)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.common import sharding as shd
    from repro.serving import kv_cache

    def pool():
        return kv_cache.init_pool(engine.cfg, engine.n_members, B, T,
                                  mesh=engine.mesh)

    def step(params, cache, tok, quorum):
        logits, cache = engine._member_logits(params, cache, tok)
        return engine._fuse(logits, quorum), cache

    cspec = shd.member_pspecs(pool())
    jitted = engine._compile(
        step, donate=(1,),
        in_specs=(shd.member_pspecs(engine.params), cspec, P(),
                  P(shd.MEMBER_AXIS)),
        out_specs=(P(), cspec))
    compiled = jitted.lower(engine.params, pool(), jnp.zeros((B,), jnp.int32),
                            engine.quorum).compile()
    return compiled, pool


def program_bytes(compiled) -> int:
    """Device bytes a compiled program holds at once (memory_analysis)."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def fused_log_probs(engine, tokens, quorums):
    """(T, B, V) fused log-probs of teacher-forced decode steps from an
    empty cache, once per quorum mask.  -> (arrays, compiled step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    B, T = tokens.shape
    compiled, pool = fused_step(engine, B, T)
    toks = jnp.asarray(tokens)
    outs = []
    for mask in quorums:
        engine.set_quorum(mask)
        cache, lps = pool(), []
        for t in range(T):
            lp, cache = compiled(engine.params, cache, toks[:, t],
                                 engine.quorum)
            lps.append(lp)
        outs.append(np.asarray(jax.device_get(jnp.stack(lps))))
    return outs, compiled


def four_chips(devs):
    """The member-sharded ensemble on a 4x1 mesh vs one chip."""
    import jax
    import numpy as np

    from repro.common import sharding as shd
    from repro.configs import registry
    from repro.models import transformer as tf
    from repro.serving import EnsembleEngine
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    K, B, T = 4, 4, 8
    full = registry.get_config("deepseek-7b")
    limit = devs[0].memory_stats()["bytes_limit"]
    keys = jax.random.split(jax.random.PRNGKey(0), K)
    kw = dict(n_slots=B, max_prompt=T, max_out=T)

    def init(cfg):
        return jax.jit(jax.vmap(lambda k: tf.init(k, cfg)))

    def reference_bytes(n_layers):
        """memory_analysis of the one-chip reference step at this depth,
        compiled from parameter shapes alone (nothing allocated)."""
        cfg = full.with_(n_layers=n_layers)
        shapes = jax.eval_shape(init(cfg), keys)
        return program_bytes(fused_step(EnsembleEngine(cfg, shapes, **kw),
                                        B, T)[0])

    # the deepest cut (up to 4 layers) whose one-chip reference program
    # needs at most 90% of the chip; the rest is left for the buffers
    # the process holds outside that program
    n_layers, need, over = 0, 0, ""
    for n in range(1, 5):
        b = reference_bytes(n)
        if b > 0.9 * limit:
            over = f"; depth {n} would need {b / 2 ** 30:.2f} GiB"
            break
        n_layers, need = n, b
    check(n_layers > 0, "not even one layer of K=4 members fits one chip")
    cfg = full.with_(n_layers=n_layers)
    say(f"four chips: K={K} deepseek-7b members at published widths, depth "
        f"cut from {full.n_layers} to {n_layers} layers, the deepest whose "
        f"one-chip reference step needs <= 90% of the chip: "
        f"{need / 2 ** 30:.2f} of {limit / 2 ** 30:.2f} GiB "
        f"(memory_analysis){over}")
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (B, T),
                                               dtype=np.int32)
    drop = [1.0, 1.0, 1.0, 0.0]

    t0 = time.time()
    params = init(cfg)(keys)
    ref = EnsembleEngine(cfg, params, **kw)
    (want, dropped), _ = fused_log_probs(ref, tokens, [[1.0] * K, drop])
    say(f"one-chip reference ran in {time.time() - t0:.1f} s; "
        f"{memory_line(devs[0])}")
    host = jax.device_get(params)
    del ref, params
    gc.collect()

    t0 = time.time()
    mesh = shd.parse_mesh_arg("4x1")
    sharded = EnsembleEngine(cfg, host, mesh=mesh, **kw)
    (got,), compiled = fused_log_probs(sharded, tokens, [[1.0] * K])
    say(f"4x1 mesh: {K // sharded.member_shards} member per chip, step "
        f"needs {program_bytes(compiled) / 2 ** 30:.2f} GiB per chip, "
        f"all-reduce in the program: {'all-reduce' in compiled.as_text()}, "
        f"ran in {time.time() - t0:.1f} s")
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "non-finite fused log-probs")
    err = float(np.abs(got - want).max())
    gap = float(np.abs(dropped - want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say(f"fused log-probs, mesh vs one chip, {T} decode steps x {B} slots "
        f"x {cfg.vocab_size}: max |diff| {err:.3e} nats (tol {FUSED_TOL}; "
        f"dropping one member moves them by {gap:.3e}), argmax agreement "
        f"{agree:.3f}")
    check(gap > FUSED_TOL, "the tolerance cannot tell a dropped member")
    check(err <= FUSED_TOL, "mesh fused log-probs disagree with one chip")


# -----------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the member-sharded path on a "
                         "4x1 mesh and its one-chip comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro next to {Path(__file__).name}: run it from a "
             f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.common.compile_cache import use_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    use_compile_cache()
    say(f"{len(devs)} x {devs[0].device_kind}, jax {jax.__version__}")
    t0 = time.time()
    if args.chips == 4:
        four_chips(devs)
    else:
        serving(devs[0])
        training()
    say(f"all phases passed in {time.time() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
