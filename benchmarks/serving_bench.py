"""Serving throughput + TTFT + mesh placement + paged cache + HTTP
frontend: engine vs baselines.

Gates:

  - throughput (ISSUE 1): the vmapped single-program engine vs the
    seed's K-jit-calls-per-token Python loop (kept alive below as the
    baseline and as the equivalence reference for tests).  Engine must
    be >= 2x at K=4 on the reduced gemma3-1b config, CPU.
  - TTFT (ISSUE 2): batched chunk prefill vs the engine's own per-token
    teacher-forcing prompt path (prefill_chunk=0).  Admission-to-first-
    token must improve >= 4x at K=4 with prompt_len >= 32 — a prompt is
    decode-ready after ceil(prompt/chunk) programs instead of `prompt`
    engine steps.
  - mesh placement (ISSUE 3, --mesh MxD): the member-sharded engine's
    PER-DEVICE cache bytes must be <= single-device bytes / M (the slot
    state is replicated and lives outside the pool, so the pool itself
    divides exactly), with tokens matching the single-device engine.
    Per-device tok/s is reported for the record — on a forced-host-CPU
    mesh the "devices" share the same silicon, so no speedup gate.
  - paged cache (ISSUE 4, --paged): (a) the paged engine (paged=True)
    must emit token-exact output vs the contiguous engine at K=4 on a
    float32 config, and (b) at EQUAL pool bytes, with short requests
    against a max_seq-sized budget, the paged scheduler must admit
    >= 2x the concurrent requests the contiguous engine's slot count
    allows — the pool serves tokens in flight, not slots x max_seq.
  - frontend (ISSUE 5, --frontend): the end-to-end HTTP path must be
    token-exact vs in-process generate() at K=4, both non-streamed and
    SSE-streamed, AND a hot-swap rollout under sustained load must
    complete with zero dropped requests, every completion token-exact
    vs its old- or new-model offline reference, and zero recompiles of
    the decode step (same jitted callable, same jit cache size, before
    and after the swap).
  - speculative decoding (ISSUE 6, --spec): the compressed student
    drafting for its own teachers must be bit-identical to the fused
    path at >= 2x decode tok/s (perfect-distillation ceiling), and
    --draft off must stay bit-identical to the base engine.
  - prefix cache (ISSUE 7, --prefix): a warm request sharing a cached
    prompt prefix must reach first token >= 5x faster than the cold
    path at K=4, with warm tokens EXACT vs a cold engine on both GQA
    and MLA cache layouts, prefix-off bit-identical to the contiguous
    engine, and zero leaked pages after 10k churned host-level
    requests over the refcounted allocator + trie pair.
  - fleet (ISSUE 8, --fleet): replica processes over sockets behind
    the FleetRouter — a client disconnect must reclaim its slot and
    pages (reclaim latency recorded), SIGKILL + restart must recover
    to a token-exact completion, and backpressure must answer 429
    only past the configured queue depth, with zero hard errors.
  - observability (ISSUE 10, --obs): the always-on obs layer (request
    lifecycle traces, log-bucketed latency histograms, tick-phase
    profiler) must cost < 2% decode tok/s vs the Scheduler(obs=False)
    kill-switch, and the server-side /metrics histogram TTFT p99 must
    agree with the client-measured p99 within 20%.
  - quantized pages + absorbed MLA (ISSUE 9, --kv-quant): at EQUAL
    pool bytes an int8 paged pool must admit >= 2x the concurrent
    requests of the f32 paged pool (deepseek-7b: the page-bytes win
    turned into admission), int8 greedy output must agree with the
    f32 contiguous reference within a bounded quality delta, and the
    absorbed-MLA paged decode (deepseek-v2) must stay token-exact vs
    the contiguous engine at f32 while its per-step FLOPs stay flat
    as max_seq grows (the O(max_seq) gather+expand is gone).

--json PATH writes the machine-readable metrics (tok/s, TTFT p50/p99,
admissible concurrency, per-device cache bytes, gate results) so the
perf trajectory accumulates across commits — benchmarks/run.py and
scripts/ci.sh write BENCH_serving.json.

  PYTHONPATH=src python benchmarks/serving_bench.py [--fast]
  # mesh stage on a forced 2-device CPU host:
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      PYTHONPATH=src python benchmarks/serving_bench.py \
      --fast --mesh 2x1 --mesh-only
  # paged stage alone:
  PYTHONPATH=src python benchmarks/serving_bench.py --paged --paged-only
  # frontend stage alone:
  PYTHONPATH=src python benchmarks/serving_bench.py \
      --frontend --frontend-only
  # prefix-cache stage alone:
  PYTHONPATH=src python benchmarks/serving_bench.py \
      --prefix --prefix-only
  # multi-process fleet stage alone:
  PYTHONPATH=src python benchmarks/serving_bench.py \
      --fleet --fleet-only
  # observability stage alone:
  PYTHONPATH=src python benchmarks/serving_bench.py --obs --obs-only
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import sharding as shd
from repro.configs import registry
from repro.core import ensemble as ens
from repro.models import transformer as tf
from repro.serving import EnsembleEngine, client


def python_loop_decode(cfg, params, K, prompt, steps):
    """The seed's decode path, verbatim: K jit calls + host fusion per
    token.  The single kept copy — the baseline for this gate AND the
    equivalence reference tests/test_serving.py imports."""
    B, plen = prompt.shape
    caches = [tf.init_cache(cfg, B, max_seq=plen + steps) for _ in range(K)]
    step = jax.jit(lambda p, c, t: tf.decode_step(p, cfg, c, t))
    tok = prompt[:, :1]
    out = []
    for i in range(plen + steps - 1):
        member_logits = []
        for m in range(K):
            pm = jax.tree.map(lambda x: x[m], params)
            logits, caches[m] = step(pm, caches[m], tok)
            member_logits.append(logits[:, 0])
        probs = ens.ensemble_probs(jnp.stack(member_logits))
        if i + 1 < plen:
            tok = prompt[:, i + 1: i + 2]
        else:
            tok = probs.argmax(-1)[:, None].astype(jnp.int32)
            out.append(tok)
    return np.asarray(jnp.concatenate(out, axis=1))  # sync


def bench_k(cfg, K, batch, plen, steps, repeats, seed=0):
    key = jax.random.PRNGKey(seed)
    params = jax.vmap(lambda k: tf.init(k, cfg))(jax.random.split(key, K))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, plen), 0,
                                cfg.vocab_size)
    n_tok = batch * steps

    ref = python_loop_decode(cfg, params, K, prompt, steps)  # warmup/compile
    t0 = time.time()
    for _ in range(repeats):
        python_loop_decode(cfg, params, K, prompt, steps)
    loop_s = n_tok * repeats / (time.time() - t0)

    engine = EnsembleEngine(cfg, params, n_slots=batch, max_prompt=plen,
                            max_out=steps)
    prompts = list(np.asarray(prompt))
    outs = engine.generate(prompts, max_new=steps)  # warmup/compile
    t0 = time.time()
    for _ in range(repeats):
        engine.generate(prompts, max_new=steps)
    eng_s = n_tok * repeats / (time.time() - t0)

    # token agreement: member logits are bitwise-identical across the two
    # paths (tests/test_serving.py), but the seed fuses in prob space
    # where exp() can round a near-tie flat — a flipped argmax then forks
    # the greedy rollout.  Report the match fraction, not strict equality.
    match = np.mean([np.mean(np.asarray(o) == r)
                     for o, r in zip(outs, ref)])
    return loop_s, eng_s, match


def bench_ttft(cfg, K, batch, plen, chunk, max_out, repeats, seed=0):
    """Admission-to-first-token: chunked prefill vs per-token prompt walk.

    Both paths run the same engine shape (batch slots, K members); one
    request is admitted into slot 0 and driven until its first token is
    out (exactly `plen` decode steps for the baseline, ceil(plen/chunk)
    prefill programs for the chunked path), host-synced like a real
    server's TTFT stamp.
    """
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (plen,), 0, cfg.vocab_size))

    def time_first_token(engine, drive):
        def once():
            engine.update_slots(release=range(engine.n_slots),
                                admits=[(0, prompt, max_out)])
            drive(engine)
            jax.block_until_ready(engine.state.out)
        once()  # warmup/compile
        t0 = time.time()
        for _ in range(repeats):
            once()
        return (time.time() - t0) / repeats

    base = EnsembleEngine(cfg, params, n_slots=batch, max_prompt=plen,
                          max_out=max_out, prefill_chunk=0)
    t_base = time_first_token(
        base, lambda e: [e.step() for _ in range(plen)])

    eng = EnsembleEngine(cfg, params, n_slots=batch, max_prompt=plen,
                         max_out=max_out, prefill_chunk=chunk)
    rounds = -(-plen // eng.prefill_chunk)
    t_pref = time_first_token(
        eng, lambda e: [e.prefill(0) for _ in range(rounds)])
    return t_base, t_pref


def bench_mesh(cfg, mesh_arg, K, batch, plen, steps, repeats, seed=0):
    """Member-sharded engine vs single-device: per-device cache bytes,
    tok/s, and token equality.  -> (ok, lines to print)."""
    lines = []
    want_m = int(mesh_arg.lower().split("x")[0]) if "x" in mesh_arg else 1
    if want_m < 2 or want_m > len(jax.devices()):
        # a gate without a real member axis would "PASS" while verifying
        # no sharding at all.  Skip loudly instead.
        return True, [f"mesh: --mesh {mesh_arg} needs {max(want_m, 2)} "
                      f"devices on the member axis (have "
                      f"{len(jax.devices())}); skipping the gate "
                      f"(XLA_FLAGS=--xla_force_host_platform_device_count="
                      f"{max(want_m, 2)})"]
    mesh = shd.parse_mesh_arg(mesh_arg)
    M = mesh.shape[shd.MEMBER_AXIS]
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (batch, plen), 0, cfg.vocab_size))
    kw = dict(n_slots=batch, max_prompt=plen, max_out=steps)

    single = EnsembleEngine(cfg, params, **kw)
    ref = single.generate(list(prompt), max_new=steps)
    bytes_single = single.cache_bytes()

    eng = EnsembleEngine(cfg, params, mesh=mesh, **kw)
    outs = eng.generate(list(prompt), max_new=steps)  # warmup/compile
    t0 = time.time()
    for _ in range(repeats):
        eng.generate(list(prompt), max_new=steps)
    tok_s = batch * steps * repeats / (time.time() - t0)
    bytes_mesh = eng.cache_bytes()

    match = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(outs, ref))
    lines.append(
        f"mesh {dict(mesh.shape)} K={K}: cache "
        f"{bytes_single / 2**20:.2f} MiB/device single -> "
        f"{bytes_mesh / 2**20:.2f} MiB/device sharded "
        f"({bytes_single / bytes_mesh:.2f}x smaller), {tok_s:.1f} tok/s, "
        f"tokens {'match' if match else 'MISMATCH'}")
    gate = match and bytes_mesh <= bytes_single // M
    lines.append(f"mesh per-device cache acceptance "
                 f"(<= single/{M}, tokens equal): "
                 f"{'PASS' if gate else 'FAIL'}")
    return gate, lines


def bench_paged(K=4, seed=0):
    """Paged pool acceptance: token-exact vs contiguous, then >= 2x
    admissible concurrency at equal pool bytes.  -> (ok, lines)."""
    from repro.serving import Scheduler
    lines = []

    # (a) token-exact: gemma3's 5:1 ring+paged layer mix at K=4, f32
    # (greedy argmax must match bit for bit through both prefill paths)
    cfg = registry.get_config("gemma3-1b", reduced=True).with_(
        dtype="float32")
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    prompts = [np.arange(1, 12) % cfg.vocab_size, np.arange(2, 5),
               np.arange(3, 10), np.arange(1, 7)]
    kw = dict(n_slots=4, max_prompt=12, max_out=8, prefill_chunk=4)
    ref = EnsembleEngine(cfg, params, **kw).generate(prompts, max_new=8)
    got = EnsembleEngine(cfg, params, paged=True, page_size=4,
                         **kw).generate(prompts, max_new=8)
    exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got, ref))
    lines.append(f"paged K={K} gemma3 f32: tokens "
                 f"{'match (exact)' if exact else 'MISMATCH'} vs "
                 f"contiguous engine")

    # (b) admissible concurrency at equal pool bytes: short requests,
    # max_seq >> typical length.  The contiguous engine reserves a full
    # max_seq row per slot, so pool bytes buy exactly n_slots requests;
    # the paged engine spends the SAME bytes on pages and admits by
    # tokens in flight.
    cfg2 = registry.get_config("deepseek-7b", reduced=True).with_(
        dtype="float32")  # pure full attention: every plane is paged
    params2 = jax.vmap(lambda k: tf.init(k, cfg2))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    page, contig_slots = 16, 4
    size = dict(max_prompt=96, max_out=32)          # max_seq = 128
    contig = EnsembleEngine(cfg2, params2, n_slots=contig_slots,
                            prefill_chunk=16, **size)
    pages_eq = contig_slots * ((size["max_prompt"] + size["max_out"])
                               // page)             # equal plane bytes
    paged = EnsembleEngine(cfg2, params2, n_slots=4 * contig_slots,
                           prefill_chunk=16, paged=True, page_size=page,
                           n_pages=pages_eq, **size)
    b_c, b_p = contig.cache_bytes(), paged.cache_bytes()
    reqs = client.make_requests(24, cfg2.vocab_size, prompt_len=(4, 8),
                                max_new=(4, 8), seed=seed)
    s_c, s_p = Scheduler(contig), Scheduler(paged)
    rid_c = [s_c.submit(t, m) for t, m in reqs]
    rid_p = [s_p.submit(t, m) for t, m in reqs]
    comp_c, comp_p = s_c.run(), s_p.run()
    match = all(np.array_equal(comp_c[a].tokens, comp_p[b].tokens)
                for a, b in zip(rid_c, rid_p))
    conc = s_p.peak_in_flight / max(s_c.peak_in_flight, 1)
    lines.append(
        f"paged admission: {b_c / 2**20:.2f} MiB contiguous pool = "
        f"{contig_slots} slots x max_seq {contig.max_seq} -> "
        f"{b_p / 2**20:.2f} MiB paged pool ({pages_eq} pages x {page}), "
        f"short requests: {s_c.peak_in_flight} -> {s_p.peak_in_flight} "
        f"concurrent ({conc:.2f}x), {s_p.preemptions} preemptions, "
        f"tokens {'match' if match else 'MISMATCH'}")
    gate = (exact and match and b_p <= b_c * 1.02
            and s_p.peak_in_flight >= 2 * s_c.peak_in_flight)
    lines.append(f"paged acceptance (token-exact, equal bytes, >= 2x "
                 f"concurrency): {'PASS' if gate else 'FAIL'}")
    return gate, lines


def bench_kv_quant(K=4, seed=0):
    """Quantized-pages + absorbed-MLA acceptance (ISSUE 9).

    (a) quality: deepseek-7b int8 paged greedy output vs the f32
        contiguous reference — the per-token agreement delta must stay
        bounded (tiny random-init members sit near argmax ties, so a
        small bound, not zero, is the honest gate);
    (b) concurrency: at EQUAL pool bytes, the int8 paged pool must
        admit >= 2x the concurrent requests of the f32 paged pool —
        the ~3.5x page-bytes shrink turned into admission headroom;
    (c) absorbed MLA: deepseek-v2 paged f32 must stay TOKEN-EXACT vs
        contiguous (the absorbed reassociation may not change greedy
        output), and the compiled decode step's FLOPs must stay ~flat
        as max_seq grows 4x — the expanded path's per-step
        gather+kv_up matmul put O(max_seq) FLOPs on the hot loop
        (ratio ~3.4x at these shapes); absorbed is ~1.3x.
    -> (ok, lines, metrics).
    """
    from repro.serving import Scheduler
    from repro.serving import kv_cache
    lines, metrics = [], {}

    # (a) int8 quality delta vs f32 contiguous reference
    cfg = registry.get_config("deepseek-7b", reduced=True).with_(
        dtype="float32")
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    prompts = [np.arange(1, 12) % cfg.vocab_size, np.arange(2, 5),
               np.arange(3, 10), np.arange(1, 7)]
    kw = dict(n_slots=4, max_prompt=12, max_out=8, prefill_chunk=4)
    ref = EnsembleEngine(cfg, params, **kw).generate(prompts, max_new=8)
    got = EnsembleEngine(cfg, params, paged=True, page_size=4,
                         kv_dtype="int8", **kw).generate(prompts,
                                                         max_new=8)
    agree = float(np.mean([np.mean(np.asarray(a) == np.asarray(b))
                           for a, b in zip(got, ref)]))
    delta = 1.0 - agree
    metrics["kv_quant_quality_delta"] = delta
    q_ok = delta <= 0.25
    lines.append(f"kv-quant K={K} deepseek-7b int8: token agreement "
                 f"{agree:.3f} vs f32 contiguous (delta {delta:.3f}, "
                 f"bound 0.25)")

    # (b) equal-bytes admissible concurrency: both engines paged, same
    # page-pool bytes; int8 pages are ~3.5x smaller so the same bytes
    # buy ~3.5x the pages.  Short requests (<= 1 page each) against an
    # oversubscribed pool make admission page-bound on both sides.
    page = 16
    size = dict(max_prompt=96, max_out=32)          # max_seq = 128
    n_f32 = 8                                        # oversubscribed
    probe32 = kv_cache.init_pool(cfg, 1, 1, 128, page_size=page,
                                 n_pages=2, kv_dtype="f32")
    probe8 = kv_cache.init_pool(cfg, 1, 1, 128, page_size=page,
                                n_pages=2, kv_dtype="int8")
    pb_f32 = kv_cache.page_bytes(probe32, 2)
    pb_int8 = kv_cache.page_bytes(probe8, 2)
    n_int8 = (n_f32 * pb_f32) // pb_int8             # equal pool bytes
    e_f32 = EnsembleEngine(cfg, params, n_slots=32, prefill_chunk=16,
                           paged=True, page_size=page, n_pages=n_f32,
                           **size)
    e_int8 = EnsembleEngine(cfg, params, n_slots=32, prefill_chunk=16,
                            paged=True, page_size=page, n_pages=n_int8,
                            kv_dtype="int8", **size)
    reqs = client.make_requests(24, cfg.vocab_size, prompt_len=(4, 8),
                                max_new=(4, 8), seed=seed)
    s_f, s_i = Scheduler(e_f32), Scheduler(e_int8)
    for t, m in reqs:
        s_f.submit(t, m)
        s_i.submit(t, m)
    s_f.run()
    s_i.run()
    conc = s_i.peak_in_flight / max(s_f.peak_in_flight, 1)
    metrics["kv_quant_concurrency_x"] = conc
    metrics["kv_quant_bytes_per_token_f32"] = pb_f32 // page
    metrics["kv_quant_bytes_per_token_int8"] = pb_int8 // page
    c_ok = conc >= 2.0
    lines.append(
        f"kv-quant admission: {n_f32} f32 pages ({pb_f32} B each) = "
        f"{n_int8} int8 pages ({pb_int8} B each), short requests: "
        f"{s_f.peak_in_flight} -> {s_i.peak_in_flight} concurrent "
        f"({conc:.2f}x, >= 2x)")

    # (c) absorbed-MLA: token-exact at f32 + step FLOPs flat in max_seq
    cfg2 = registry.get_config("deepseek-v2-236b", reduced=True).with_(
        dtype="float32")
    params2 = jax.vmap(lambda k: tf.init(k, cfg2))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    ref2 = EnsembleEngine(cfg2, params2, **kw).generate(prompts,
                                                        max_new=8)
    got2 = EnsembleEngine(cfg2, params2, paged=True, page_size=4,
                          **kw).generate(prompts, max_new=8)
    exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(got2, ref2))
    lines.append(f"absorbed-MLA K={K} deepseek-v2 f32: tokens "
                 f"{'match (exact)' if exact else 'MISMATCH'} vs "
                 f"contiguous engine")

    p_abs = tf.absorb_mla_params(cfg2, jax.tree.map(lambda x: x[0],
                                                    params2))

    def step_flops(max_seq):
        cache = tf.init_slot_cache(cfg2, 2, max_seq, page_size=16,
                                   n_pages=2 * (max_seq // 16))
        toks = jnp.zeros((2, 1), jnp.int32)
        comp = jax.jit(
            lambda p, c, t: tf.decode_step_paged(p, cfg2, c, t)
        ).lower(p_abs, cache, toks).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca.get("flops", 0.0))

    f_lo, f_hi = step_flops(128), step_flops(512)
    flat = f_hi / max(f_lo, 1.0)
    metrics["mla_absorbed_step_flat"] = flat
    m_ok = exact and flat <= 2.0
    lines.append(f"absorbed-MLA step FLOPs: max_seq 128 -> 512 (4x) "
                 f"grows {flat:.2f}x (<= 2x; expanded path ~3.4x)")

    ok = q_ok and c_ok and m_ok
    lines.append(f"kv-quant acceptance (quality delta <= 0.25, >= 2x "
                 f"equal-bytes concurrency, absorbed-MLA exact + flat):"
                 f" {'PASS' if ok else 'FAIL'}")
    return ok, lines, metrics


def bench_spec(K=4, seed=0, gamma=8, batch=4, plen=8, steps=64, repeats=8):
    """Speculative decoding acceptance (ISSUE 6): the compressed student
    drafting for its own teachers must reach >= 2x decode tok/s at K=4
    (gemma3 f32, greedy) with BIT-IDENTICAL tokens vs the non-speculative
    fused path, and --draft off must stay bit-identical to today's
    engine.  -> (ok, lines, metrics).

    The gate measures the mechanism at its ceiling: a PERFECTLY distilled
    student.  Members are full-depth stacks whose upper layers are
    residual-identity (w_o and w_down zeroed: x + attn(norm(x)) @ 0 == x
    bitwise), so the 2-layer truncation of the same weights IS the
    student distillation converges to — its logits match the members'
    bit for bit, acceptance -> 1, and every speculative iteration turns
    gamma+1 fused-ensemble dispatches into one cheap-draft + one verify
    program.  Timing covers the DECODE loop only (admission + prefill
    run outside the clock on both sides; the base engine dispatches its
    fixed-stride loop without per-step syncs, exactly as generate()
    does).  A distinct-member run (low acceptance) rides along as the
    correctness check under disagreement — speculation must NEVER
    change tokens, only their cost.
    """
    from repro.serving import Scheduler, SpeculativeEngine
    lines, metrics = [], {}
    cfg = registry.get_config("gemma3-1b", reduced=True).with_(
        dtype="float32")
    draft_cfg = cfg.with_(n_layers=2)
    full = tf.init(jax.random.PRNGKey(seed), cfg)

    def _slots(segments, c):
        """Layer params in depth order: (segment dict, slot name)."""
        out = []
        for seg, (count, specs) in zip(segments, c.segments()):
            assert count == 1, "bench construction expects unrolled segments"
            out.extend((seg, f"slot_{i}") for i in range(len(specs)))
        return out

    # student = the 2-layer truncation of `full` (embed + first layers +
    # final norm, weights shared bitwise)
    student = tf.init(jax.random.PRNGKey(seed + 1), draft_cfg)
    student["embed"] = full["embed"]
    student["final_norm"] = full["final_norm"]
    f_slots = _slots(full["segments"], cfg)
    for (d_seg, d_name), (f_seg, f_name) in zip(
            _slots(student["segments"], draft_cfg), f_slots):
        d_seg[d_name] = f_seg[f_name]

    # member = `full` with every layer past the student's depth made a
    # bitwise residual no-op (w_o = w_down = 0 => x + 0 == x), so the
    # student IS its perfect distillation: identical logits, bit for bit
    member = jax.tree.map(lambda x: x, full)
    member["segments"] = [dict(s) for s in member["segments"]]
    for seg, name in _slots(member["segments"], cfg)[draft_cfg.n_layers:]:
        layer = dict(seg[name])
        layer["attn"] = dict(layer["attn"])
        layer["mlp"] = dict(layer["mlp"])
        layer["attn"]["w_o"] = jnp.zeros_like(layer["attn"]["w_o"])
        layer["mlp"]["w_down"] = jnp.zeros_like(layer["mlp"]["w_down"])
        seg[name] = layer
    params = jax.tree.map(lambda x: jnp.stack([x] * K), member)
    prompts = list(np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (batch, plen), 0, cfg.vocab_size)))
    kw = dict(n_slots=batch, max_prompt=plen, max_out=steps,
              prefill_chunk=8)
    n_tok = batch * steps

    def _prep(eng):
        eng.update_slots(release=list(range(batch)))
        eng.update_slots(admits=[(b, list(prompts[b]), steps, None)
                                 for b in range(batch)])
        for b in range(batch):
            while True:
                st = eng.prefill(b)
                if int(jax.device_get(st.pos)[b]) >= plen:
                    break
        jax.block_until_ready(eng.state.tok)

    def _decode_pass(eng, synced):
        """One timed decode pass; admission/prefill and the final token
        fetch stay outside the clock."""
        _prep(eng)
        t0 = time.time()
        if synced:
            # variable per-row stride: fetch done flags each iteration,
            # exactly as the speculative generate() does
            while True:
                st = eng.step()
                act, done = jax.device_get((st.active, st.done))
                if not np.any(np.asarray(act) & ~np.asarray(done)):
                    break
        else:
            for _ in range(steps - 1):  # fixed stride, dispatch-only
                eng.step()
        jax.block_until_ready(eng.state.tok)
        dt = time.time() - t0
        outs = [np.asarray(jax.device_get(eng.state.out[b][:steps]))
                for b in range(batch)]
        eng.update_slots(release=list(range(batch)))
        return outs, dt

    base = EnsembleEngine(cfg, params, **kw)
    spec = SpeculativeEngine(cfg, params, student, draft_cfg=draft_cfg,
                             gamma=gamma, **kw)
    # interleave the repeat passes so a machine-load transient hits both
    # engines alike instead of skewing whichever ran during it; the
    # first (warmup/compile) pass of each stays off the clock
    ref, _ = _decode_pass(base, synced=False)
    outs, _ = _decode_pass(spec, synced=True)
    base_t = spec_t = float("inf")
    for _ in range(repeats):
        _, dt_b = _decode_pass(base, synced=False)
        _, dt_s = _decode_pass(spec, synced=True)
        base_t = min(base_t, dt_b)
        spec_t = min(spec_t, dt_s)
    base_s = n_tok / base_t
    spec_s = n_tok / spec_t
    st = spec.spec_stats()

    exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(outs, ref))
    speedup = spec_s / base_s
    lines.append(
        f"spec K={K} gamma={gamma} gemma3 f32 greedy: base {base_s:.1f} "
        f"-> spec {spec_s:.1f} tok/s ({speedup:.2f}x), acceptance "
        f"{st['acceptance_rate']:.1%}, mean accepted "
        f"{st['mean_accepted_len']:.2f}/step (p50 "
        f"{st['accepted_len_p50']:.0f}), tokens "
        f"{'match (bit-identical)' if exact else 'MISMATCH'}")

    # --draft off: per-request opt-out must be bit-identical to the
    # plain engine (same program: the spec step never runs)
    sched = Scheduler(spec)
    rids = [sched.submit(p, steps, draft=False) for p in prompts]
    comps = sched.run()
    off_exact = all(np.array_equal(np.asarray(comps[r].tokens),
                                   np.asarray(ref[i]))
                    for i, r in enumerate(rids))
    lines.append(f"spec --draft off: tokens "
                 f"{'match (bit-identical)' if off_exact else 'MISMATCH'} "
                 f"vs non-speculative engine")

    # correctness under disagreement: K distinct members, a student that
    # proposes mostly-rejected drafts — output must still be identical
    params_d = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    ref_d = EnsembleEngine(cfg, params_d, **kw).generate(prompts,
                                                         max_new=steps)
    spec_d = SpeculativeEngine(cfg, params_d,
                               jax.tree.map(lambda x: x[0], params_d),
                               gamma=gamma, **kw)
    out_d = spec_d.generate(prompts, max_new=steps)
    d_exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(out_d, ref_d))
    st_d = spec_d.spec_stats()
    lines.append(
        f"spec distinct members: acceptance {st_d['acceptance_rate']:.1%} "
        f"(drafts mostly rejected), tokens "
        f"{'match (bit-identical)' if d_exact else 'MISMATCH'}")

    ok = exact and off_exact and d_exact and speedup >= 2.0
    metrics.update({
        "spec_tok_s": spec_s,
        "spec_base_tok_s": base_s,
        "spec_speedup": speedup,
        "spec_acceptance_rate": st["acceptance_rate"],
        "spec_mean_accepted_len": st["mean_accepted_len"],
        "spec_accepted_len_p50": st["accepted_len_p50"],
        "spec_exact": bool(exact),
        "spec_draft_off_exact": bool(off_exact),
    })
    lines.append(f"spec acceptance (bit-identical, --draft off identical, "
                 f">= 2x decode tok/s): {'PASS' if ok else 'FAIL'}")
    return ok, lines, metrics


def bench_prefix(K=4, seed=0, repeats=5):
    """Prefix-cache acceptance (ISSUE 7): a warm shared-prefix request
    must reach first token >= 5x faster than the cold path at K=4, the
    warm tokens must be EXACT vs a cold engine on GQA (deepseek-7b) AND
    MLA (deepseek-v2-236b) layouts, prefix-off must stay bit-identical
    to the contiguous engine, and a 10k-request host-level churn storm
    over the allocator+trie pair must leak zero pages.
    -> (ok, lines, metrics)."""
    from repro.serving import PrefixCache
    from repro.serving.kv_cache import PageAllocator
    lines, metrics = [], {}

    # (a) warm-vs-cold TTFT: one long prompt fully cached by a prior
    # request.  deepseek-7b reduced f32 — pure full attention, every
    # plane paged, so the hit skips real prefill programs.  The prompt
    # spans 24 pages; the warm hit covers 95 of 96 tokens (23 full
    # pages + a 3-token COW tail), so admission-to-first-token is one
    # prefill chunk instead of twenty-four.
    cfg = registry.get_config("deepseek-7b", reduced=True).with_(
        dtype="float32")
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    plen, page, chunk = 96, 4, 4
    eng = EnsembleEngine(cfg, params, n_slots=4, max_prompt=plen,
                         max_out=8, prefill_chunk=chunk, paged=True,
                         page_size=page, prefix_cache=True)
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (plen,), 0, cfg.vocab_size))

    def ttft(warm):
        # prep outside the clock: drain slot 0 (its release inserts the
        # finished chain into the trie); a cold pass then empties it
        eng.update_slots(release=range(eng.n_slots))
        if not warm:
            eng.allocator.flush_cache()
        t0 = time.time()
        hits = eng.update_slots(admits=[(0, prompt, 4)])
        left = plen - hits.get(0, 0)
        for _ in range(-(-left // chunk)):
            eng.prefill(0)
        jax.block_until_ready(eng.state.tok)
        return time.time() - t0, hits.get(0, 0)

    ttft(warm=False)                   # compile the cold programs
    _, hit_tok = ttft(warm=True)       # compile COW + share path
    t_cold = t_warm = float("inf")
    for _ in range(repeats):
        t_cold = min(t_cold, ttft(warm=False)[0])
        t_warm = min(t_warm, ttft(warm=True)[0])
    speedup = t_cold / t_warm
    ps = eng.page_stats()
    lines.append(
        f"prefix K={K} deepseek-7b f32 prompt={plen}: TTFT cold "
        f"{t_cold * 1e3:.1f} ms -> warm {t_warm * 1e3:.1f} ms "
        f"({speedup:.2f}x), hit {hit_tok}/{plen} tokens, "
        f"cow_pages {ps['cow_pages']}")
    metrics.update({"prefix_ttft_cold_ms": t_cold * 1e3,
                    "prefix_ttft_warm_ms": t_warm * 1e3,
                    "prefix_ttft_speedup": speedup,
                    "prefix_hit_tokens": int(hit_tok)})

    # (b) token-exactness: warm output vs a cold engine on BOTH cache
    # layouts the pool supports — GQA (k/v planes) and MLA (latent +
    # rope planes) — plus prefix-off == contiguous bit-identity (the
    # refactor must not perturb the existing paths)
    exact_all = True
    for name in ("deepseek-7b", "deepseek-v2-236b"):
        c = registry.get_config(name, reduced=True).with_(dtype="float32")
        p = jax.vmap(lambda k: tf.init(k, c))(
            jax.random.split(jax.random.PRNGKey(seed), K))
        shared = [int(t) % c.vocab_size for t in range(5, 23)]
        prompts = [np.array(shared + [2, 3], np.int32),
                   np.array(shared + [4, 5, 6], np.int32),   # COW split
                   np.array(shared[:10] + [7, 8], np.int32)]  # mid-page
        kw = dict(n_slots=3, max_prompt=24, max_out=6, prefill_chunk=4,
                  paged=True, page_size=4)
        contig = EnsembleEngine(c, p, n_slots=3, max_prompt=24,
                                max_out=6, prefill_chunk=4)
        ref_c = contig.generate(prompts, max_new=5)
        off = EnsembleEngine(c, p, **kw)
        ref = off.generate(prompts, max_new=5)
        off_exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(ref, ref_c))
        on = EnsembleEngine(c, p, prefix_cache=True, **kw)
        on.generate([prompts[0]], max_new=5)        # cold: primes trie
        warm_out = on.generate(prompts, max_new=5)  # warm: shares pages
        warm_exact = all(np.array_equal(np.asarray(a), np.asarray(b))
                         for a, b in zip(warm_out, ref))
        st = on.page_stats()
        hit = st["prefix_hits"] >= 2
        exact_all &= off_exact and warm_exact and hit
        layout = "MLA" if name.startswith("deepseek-v2") else "GQA"
        lines.append(
            f"prefix {name} ({layout}) f32: warm tokens "
            f"{'match (exact)' if warm_exact else 'MISMATCH'} vs cold "
            f"({st['prefix_hits']} hits, hit rate "
            f"{st['prefix_hit_rate']:.1%}, cow {st['cow_pages']}), "
            f"prefix-off {'bit-identical' if off_exact else 'MISMATCH'} "
            f"vs contiguous")
        metrics[f"prefix_exact_{layout.lower()}"] = bool(warm_exact)

    # (c) 10k churned host-level requests against a small pool: admit /
    # cancel-mid-prompt / preempt with six shared prefixes; afterwards
    # every refcount must be zero and the free list WHOLE — the no-leak
    # invariant admission accounting assumes on every scheduler tick
    rng = np.random.default_rng(seed)
    n_pages_c, page_c, n_slots_c = 64, 4, 8
    a = PageAllocator(n_pages_c, page_c, n_slots_c, 8)
    a.cache = PrefixCache(page_c)
    prefixes = [list(rng.integers(1, 1000, rng.integers(4, 20)))
                for _ in range(6)]
    live, churn_ok = {}, True
    for _ in range(10_000):
        b = int(rng.integers(n_slots_c))
        if b in live:
            toks, written = live.pop(b)
            n = -(-written // page_c)
            if written > 0 and len(a.chain(b)) >= n:
                a.cache.insert(toks[:written], a.chain(b)[:n])
            a.release(b)
        pre = prefixes[int(rng.integers(len(prefixes)))]
        toks = list(pre) + list(rng.integers(1, 1000,
                                             rng.integers(1, 8)))
        hit, full, tail = a.cache.match(toks, len(toks) - 1)
        want = -(-len(toks) // page_c)
        live_hit = sum(1 for q in full if a.ref(q) > 0)
        if want - live_hit > a.available_pages:
            continue  # the queue would hold it; nothing mutated
        if full or tail:
            a.share(b, full + ([tail[0]] if tail else []))
        if tail is not None:
            churn_ok &= a.cow(b, len(full)) is not None
        churn_ok &= a.alloc(b, want)
        live[b] = (toks, int(rng.integers(hit, len(toks) + 1)))
    for b in list(live):
        a.release(b)
    a.flush_cache()
    leak_free = (churn_ok and a.free_pages == a.n_pages
                 and sorted(a._free) == list(range(a.n_pages))
                 and all(r == 0 for r in a._ref)
                 and a.cow_count > 0 and a.cache.evicted_pages > 0)
    lines.append(
        f"prefix churn: 10k requests over {n_pages_c} pages / "
        f"{n_slots_c} slots: {a.cow_count} COWs, "
        f"{a.cache.evicted_pages} evictions, free list "
        f"{'WHOLE (no leaks)' if leak_free else 'LEAKED'}")
    metrics["prefix_churn_leak_free"] = bool(leak_free)

    ok = (speedup >= 5.0 and hit_tok > 0 and exact_all and leak_free)
    lines.append(f"prefix acceptance (>= 5x warm TTFT, token-exact "
                 f"GQA+MLA, prefix-off bit-identical, zero leaks): "
                 f"{'PASS' if ok else 'FAIL'}")
    return ok, lines, metrics


def decode_cache_size(engine):
    """jit-cache entries of the decode step (private jax API; None when
    unavailable).  A hot-swap must not grow this."""
    try:
        return engine._step._cache_size()
    except AttributeError:
        return None


def bench_frontend(K=4, seed=0, n_replicas=2, load_requests=12):
    """Frontend acceptance: HTTP token-exactness (non-streamed + SSE)
    vs in-process generate() at K=4, then a hot-swap rollout under
    sustained load with zero drops and zero decode recompiles.
    -> (ok, lines, metrics)."""
    import threading

    from repro.serving import client as cl
    from repro.serving.frontend import FrontendServer, Replica, Router

    lines, metrics = [], {}
    cfg = registry.get_config("gemma3-1b", reduced=True).with_(
        dtype="float32")
    kw = dict(n_slots=4, max_prompt=12, max_out=8, prefill_chunk=4)
    key = jax.random.PRNGKey(seed)
    params_old = jax.vmap(lambda k: tf.init(k, cfg))(jax.random.split(key, K))
    params_new = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed + 101), K))
    prompts = [np.arange(1, 12) % cfg.vocab_size, np.arange(2, 5),
               np.arange(3, 10), np.arange(1, 7)]
    max_new = 8

    # offline references, one isolated generate() per prompt per model
    # (row-independent vmap makes isolation == in-batch, tested)
    ref_old_eng = EnsembleEngine(cfg, params_old, **kw)
    refs_old = [ref_old_eng.generate([p], max_new=max_new)[0].tolist()
                for p in prompts]
    refs_new = [EnsembleEngine(cfg, params_new, **kw)
                .generate([p], max_new=max_new)[0].tolist()
                for p in prompts]

    replicas = [Replica(f"r{i}", EnsembleEngine(cfg, params_old, **kw))
                for i in range(n_replicas)]
    for r in replicas:
        # compile BOTH kernels (prefill + decode: max_new=2 forces one
        # decode step) before any measurement — otherwise a replica the
        # router happened not to exercise in phase (a) would grow its
        # jit cache on first use in phase (b) and read as a recompile
        r.engine.generate([prompts[0]], max_new=2)
    router = Router(replicas)
    srv = FrontendServer(router)
    srv.start()
    try:
        # (a) HTTP token-exactness, non-streamed and SSE-streamed
        exact = True
        for i, p in enumerate(prompts):
            plain = cl.http_generate(srv.url, p, max_new, stream=False)
            sse = cl.http_generate(srv.url, p, max_new, stream=True)
            exact &= (plain["tokens"] == refs_old[i]
                      and sse["tokens"] == refs_old[i])
        lines.append(f"frontend K={K}: HTTP non-streamed + SSE tokens "
                     f"{'match (exact)' if exact else 'MISMATCH'} vs "
                     f"in-process generate()")

        # (b) hot-swap rollout under sustained load
        sizes_before = [decode_cache_size(r.engine) for r in replicas]
        steps_before = [id(r.engine._step) for r in replicas]
        results: dict = {}
        errors: list = []

        def fire(i):
            try:
                out = cl.http_generate(srv.url, prompts[i % len(prompts)],
                                       max_new, stream=(i % 2 == 0))
                results[i] = out["tokens"]
            except Exception as e:  # noqa: BLE001 — a drop IS the failure
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(load_requests)]
        for i, t in enumerate(threads):
            t.start()
            if i == load_requests // 3:
                router.rollout(params_new)  # mid-load, under traffic
        for t in threads:
            t.join()

        dropped = load_requests - len(results)
        matched = sum(
            results.get(i) in (refs_old[i % len(prompts)],
                               refs_new[i % len(prompts)])
            for i in range(load_requests))
        sizes_after = [decode_cache_size(r.engine) for r in replicas]
        steps_after = [id(r.engine._step) for r in replicas]
        no_recompile = (sizes_before == sizes_after
                        and steps_before == steps_after)
        swapped = all(r.engine.swaps_done == 1 for r in replicas)
        lines.append(
            f"frontend hot-swap under load: {len(results)}/{load_requests} "
            f"completed ({dropped} dropped, {len(errors)} errors), "
            f"{matched}/{load_requests} token-exact vs old/new refs, "
            f"decode jit cache {sizes_before} -> {sizes_after} "
            f"({'same callable' if no_recompile else 'RECOMPILED'}), "
            f"swaps {[r.engine.swaps_done for r in replicas]}")
        ok = (exact and dropped == 0 and not errors
              and matched == load_requests and no_recompile and swapped)
        metrics.update({
            "frontend_exact": bool(exact),
            "frontend_dropped": int(dropped),
            "frontend_recompiled": not no_recompile,
        })
        lines.append(f"frontend acceptance (token-exact HTTP+SSE, 0 drops, "
                     f"0 recompiles across swap): "
                     f"{'PASS' if ok else 'FAIL'}")
        if errors:
            lines.extend(f"  error: req {i}: {e}" for i, e in errors[:4])
        return ok, lines, metrics
    finally:
        srv.shutdown(drain=True, timeout=60.0)


def bench_fleet(K=2, seed=0):
    """Fleet acceptance over sockets (ISSUE 8): replica processes behind
    the FleetRouter, measuring the three numbers the fleet design is
    judged on — SIGKILL-to-served recovery time, client-disconnect
    cancellation reclaim latency, and the queue depth at 429 onset.
    -> (ok, lines, metrics)."""
    import socket
    import struct
    import threading
    from http.client import HTTPConnection

    from repro.serving import client as cl
    from repro.serving.frontend import EngineSpec, FleetRouter

    lines, metrics = [], {}
    depth = 4
    spec = EngineSpec(arch="deepseek-7b", reduced=True, dtype="float32",
                      members=K, seed=seed, n_slots=2, max_prompt=16,
                      max_out=32, prefill_chunk=4, paged=True,
                      page_size=4, prefix_cache=True)
    fleet = FleetRouter(spec, n=2, max_queue_depth=depth)
    fleet.start(timeout=600.0)
    try:
        prompt = [1, 2, 3, 4, 5, 6]
        ref = fleet.generate(prompt, 6)["tokens"]

        # (a) cancellation reclaim: open an SSE stream straight at one
        # replica, drop the socket abortively (RST) after the first
        # token, clock until /healthz reports the pool whole again
        proc = fleet.procs[0]
        body = json.dumps({"tokens": prompt, "max_new": 32,
                           "stream": True}).encode()
        conn = HTTPConnection(proc.host, proc.port, timeout=60.0)
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        got = b""
        while b"\n\n" not in got:
            got += resp.read1(4096)
        sock = resp.fp.raw._sock
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        t0 = time.time()
        resp.close()
        conn.close()
        reclaim_s = None
        while time.time() - t0 < 60.0:
            r = proc.healthz()["replicas"][0]
            if (r["cancelled"] == 1 and r["live_slots"] == 0
                    and r["available_pages"] == r["n_pages"]):
                reclaim_s = time.time() - t0
                break
            time.sleep(0.005)
        lines.append("fleet cancel: disconnect -> slot+pages reclaimed "
                     + (f"in {reclaim_s:.3f}s" if reclaim_s is not None
                        else "NEVER (timed out)"))

        # (b) SIGKILL -> restart -> first served completion (includes
        # child spawn + engine compile: the honest recovery number)
        victim = fleet.procs[1]
        victim.kill()
        t0 = time.time()
        latched = fleet.health_sweep()
        fleet.restart(victim.name, timeout=600.0)
        out = fleet.generate(prompt, 6)
        recovery_s = time.time() - t0
        rec_exact = out["tokens"] == ref
        lines.append(f"fleet recovery: SIGKILL {victim.name} (latched "
                     f"{latched}) -> restarted + served token-exact="
                     f"{rec_exact} in {recovery_s:.1f}s")

        # (c) 429 onset: waves of c SIMULTANEOUS requests at ONE
        # replica, c ramping up — the first wave size that sheds is
        # the onset depth (all of a wave's submits land before any
        # completes, so wave size == peak queue depth + 1)
        onset = None
        hard_errors: list = []

        def probe(i, shed_evt):
            try:
                cl.http_generate(proc.url, [1 + i, 2, 3, 4], 32,
                                 timeout=120.0)
            except cl.Backpressure:
                shed_evt.set()
            except Exception as e:  # noqa: BLE001 — a drop IS a failure
                hard_errors.append(repr(e))

        for c in range(1, 2 * depth + 3):
            shed_evt = threading.Event()
            wave = [threading.Thread(target=probe, args=(i, shed_evt),
                                     daemon=True) for i in range(c)]
            for t in wave:
                t.start()
            for t in wave:
                t.join(180.0)
            if shed_evt.is_set():
                onset = c
                break
        lines.append(f"fleet 429 onset: first shed at wave size {onset} "
                     f"(configured queue depth {depth}), "
                     f"{len(hard_errors)} hard errors")

        ok = (reclaim_s is not None and rec_exact and recovery_s < 300.0
              and onset is not None and onset > depth
              and not hard_errors)
        metrics.update({
            "fleet_cancel_reclaim_s": reclaim_s,
            "fleet_recovery_s": recovery_s,
            "fleet_429_onset_depth": onset,
        })
        lines.append(f"fleet acceptance (reclaim observed, kill/restart "
                     f"token-exact, 429 past queue depth): "
                     f"{'PASS' if ok else 'FAIL'}")
        return ok, lines, metrics
    finally:
        fleet.stop()


def bench_obs(K=4, seed=0, repeats=5):
    """Observability acceptance (ISSUE 10): the always-on obs layer
    (request traces + latency histograms + tick-phase profiler) must
    cost < 2% decode tok/s vs the obs=False kill-switch, and the
    server-side histogram TTFT p99 exported on /metrics must agree
    with the client-measured p99 within 20% (or 20 ms absolute —
    sub-interpolation-error TTFTs make a relative bound meaningless).
    -> (ok, lines, metrics)."""
    from repro.serving.frontend import FrontendServer, Replica, Router
    lines, metrics = [], {}

    cfg = registry.get_config("gemma3-1b", reduced=True).with_(
        dtype="float32")
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))
    eng = EnsembleEngine(cfg, params, n_slots=4, max_prompt=16,
                         max_out=32, prefill_chunk=8)
    reqs = client.make_requests(16, cfg.vocab_size, prompt_len=(4, 16),
                                max_new=(16, 32), seed=seed)
    eng.generate([reqs[0][0]], max_new=2)  # compile outside the clock

    # (a) overhead: the same engine + request set through run_load with
    # obs on vs the kill-switch, interleaved best-of-N so a machine
    # transient hits both sides alike instead of skewing one; an
    # untimed warmup per side first — runs are short (~0.5 s), so one
    # cold scheduler pass would otherwise read as fake overhead
    client.run_load(eng, reqs, obs=False)
    client.run_load(eng, reqs, obs=True)
    on_s = off_s = 0.0
    for _ in range(repeats):
        off_s = max(off_s, client.run_load(eng, reqs,
                                           obs=False)["tok_s"])
        on_s = max(on_s, client.run_load(eng, reqs, obs=True)["tok_s"])
    overhead = 100.0 * (1.0 - on_s / max(off_s, 1e-9))
    o_ok = overhead < 2.0
    metrics["obs_overhead_pct"] = overhead
    metrics["obs_tok_s"] = on_s
    lines.append(f"obs K={K}: {off_s:.1f} tok/s obs=False -> "
                 f"{on_s:.1f} tok/s obs=True "
                 f"({overhead:+.2f}% overhead, gate < 2%)")

    # (b) client/server percentile agreement over HTTP: the report's
    # headline TTFT comes from the server-side /metrics histograms,
    # with the client-clock view kept for exactly this cross-check
    srv = FrontendServer(Router([Replica("r0", eng)]))
    srv.start()
    try:
        http_reqs = client.make_requests(12, cfg.vocab_size,
                                         prompt_len=(8, 16),
                                         max_new=(8, 16), seed=seed + 1)
        report = client.run_http_load(srv.url, http_reqs, concurrency=4)
    finally:
        srv.shutdown(drain=True, timeout=60.0)
    div = report.get("ttft_p99_divergence")
    srv_p99 = report["ttft_p99_ms"]
    cli_p99 = report.get("client_ttft_p99_ms", srv_p99)
    abs_ms = abs(srv_p99 - cli_p99)
    d_ok = div is not None and (div <= 0.20 or abs_ms <= 20.0)
    metrics["ttft_p99_divergence"] = div
    metrics["obs_server_ttft_p99_ms"] = srv_p99
    metrics["obs_client_ttft_p99_ms"] = cli_p99
    lines.append(
        f"obs percentiles: server /metrics ttft p99 {srv_p99:.1f} ms "
        f"vs client-clock {cli_p99:.1f} ms "
        + (f"(divergence {div:.1%}, gate <= 20% or <= 20 ms)"
           if div is not None else "(server histograms MISSING)"))

    ok = o_ok and d_ok
    lines.append(f"obs acceptance (< 2% decode overhead, server/client "
                 f"p99 within 20%): {'PASS' if ok else 'FAIL'}")
    return ok, lines, metrics


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--members", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--ttft-prompt", type=int, default=64,
                    help="prompt length for the TTFT gate (>= 32)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--fast", action="store_true",
                    help="CI-sized run (fewer members/steps)")
    ap.add_argument("--mesh", default="",
                    help="'MxD': also run the member-sharded engine and "
                         "gate per-device cache bytes (e.g. 2x1)")
    ap.add_argument("--mesh-only", action="store_true",
                    help="skip the throughput/TTFT gates (CI runs them "
                         "in the single-device stage already)")
    ap.add_argument("--paged", action="store_true",
                    help="also gate the paged KV pool: token-exact vs "
                         "contiguous + >= 2x admissible concurrency at "
                         "equal pool bytes")
    ap.add_argument("--paged-only", action="store_true",
                    help="run only the paged stage")
    ap.add_argument("--frontend", action="store_true",
                    help="also gate the HTTP frontend: token-exact "
                         "non-streamed + SSE vs in-process generate(), "
                         "and hot-swap under load with zero drops and "
                         "zero decode recompiles")
    ap.add_argument("--frontend-only", action="store_true",
                    help="run only the frontend stage")
    ap.add_argument("--prefix", action="store_true",
                    help="also gate the prefix cache: >= 5x warm TTFT "
                         "at K=4, warm tokens exact vs cold on GQA and "
                         "MLA layouts, prefix-off bit-identical, zero "
                         "leaked pages after 10k churned requests")
    ap.add_argument("--prefix-only", action="store_true",
                    help="run only the prefix-cache stage")
    ap.add_argument("--fleet", action="store_true",
                    help="also gate the multi-process fleet: SIGKILL -> "
                         "restart recovery served token-exact, client "
                         "disconnect reclaims slot+pages, 429 fires "
                         "past the queue depth with zero hard errors")
    ap.add_argument("--fleet-only", action="store_true",
                    help="run only the fleet stage")
    ap.add_argument("--kv-quant", action="store_true",
                    help="also gate quantized KV pages + absorbed MLA: "
                         "int8 quality delta bounded vs f32, >= 2x "
                         "admissible concurrency at equal pool bytes, "
                         "absorbed-MLA token-exact + step-FLOPs flat "
                         "in max_seq")
    ap.add_argument("--kv-quant-only", action="store_true",
                    help="run only the kv-quant stage")
    ap.add_argument("--spec", action="store_true",
                    help="also gate speculative decoding: student-drafted "
                         "ensemble must be bit-identical and >= 2x decode "
                         "tok/s at K=4, --draft off bit-identical")
    ap.add_argument("--spec-only", action="store_true",
                    help="run only the speculative-decoding stage")
    ap.add_argument("--obs", action="store_true",
                    help="also gate the observability layer: < 2% "
                         "decode tok/s overhead vs obs=False, and "
                         "server-side /metrics histogram TTFT p99 "
                         "within 20% of the client-measured p99")
    ap.add_argument("--obs-only", action="store_true",
                    help="run only the observability stage")
    ap.add_argument("--gamma", type=int, default=8,
                    help="draft tokens per speculative iteration (--spec)")
    ap.add_argument("--json", default="",
                    help="write machine-readable metrics (tok/s, TTFT "
                         "p50/p99, admissible concurrency, per-device "
                         "cache bytes, gates) to this path")
    args = ap.parse_args(argv)
    if args.prefill_chunk <= 0:
        ap.error("--prefill-chunk must be >= 1: the TTFT gate measures "
                 "chunked prefill against the per-token baseline")
    if args.mesh_only and not args.mesh:
        ap.error("--mesh-only needs --mesh MxD")

    metrics: dict = {"argv": argv if argv is not None else sys.argv[1:]}

    def finish(ok: bool) -> int:
        metrics["pass"] = bool(ok)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(metrics, f, indent=2, sort_keys=True)
            print(f"wrote {args.json}")
        return 0 if ok else 1

    if args.paged_only:
        ok, lines = bench_paged()
        print("\n".join(lines))
        return finish(ok)
    if args.frontend_only:
        ok, lines, m = bench_frontend()
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.prefix_only:
        ok, lines, m = bench_prefix()
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.kv_quant_only:
        ok, lines, m = bench_kv_quant()
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.spec_only:
        ok, lines, m = bench_spec(gamma=args.gamma)
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.obs_only:
        ok, lines, m = bench_obs()
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.fleet_only:
        ok, lines, m = bench_fleet()
        metrics.update(m)
        print("\n".join(lines))
        return finish(ok)
    if args.fast:
        args.members, args.steps, args.repeats = [1, 4], 8, 1
        args.ttft_prompt = 32

    cfg = registry.get_config(args.arch, reduced=True)
    if args.mesh_only:
        ok, lines = bench_mesh(cfg, args.mesh, 4, args.batch,
                               args.prompt_len, args.steps, args.repeats)
        print("\n".join(lines))
        return finish(ok)
    print(f"{args.arch} (reduced) | batch={args.batch} "
          f"prompt={args.prompt_len} steps={args.steps} "
          f"repeats={args.repeats}")
    print(f"{'K':>3} {'loop tok/s':>12} {'engine tok/s':>13} "
          f"{'speedup':>8}  {'tok match':>9}")
    speedups = {}
    for K in args.members:
        loop_s, eng_s, match = bench_k(cfg, K, args.batch, args.prompt_len,
                                       args.steps, args.repeats)
        speedups[K] = eng_s / loop_s
        metrics[f"tok_s_k{K}"] = eng_s
        metrics[f"speedup_k{K}"] = speedups[K]
        print(f"{K:>3} {loop_s:>12.1f} {eng_s:>13.1f} "
              f"{speedups[K]:>7.2f}x  {match:>8.1%}")

    t_base, t_pref = bench_ttft(cfg, 4, args.batch, args.ttft_prompt,
                                args.prefill_chunk, args.steps,
                                args.repeats)
    ttft_x = t_base / t_pref
    print(f"TTFT K=4 prompt={args.ttft_prompt} chunk={args.prefill_chunk}: "
          f"per-token {t_base * 1e3:.1f} ms -> prefill {t_pref * 1e3:.1f} ms "
          f"({ttft_x:.2f}x)")
    metrics["ttft_speedup"] = ttft_x

    # continuous-batching load report: TTFT/latency percentiles,
    # admissible concurrency, per-device cache bytes — the trajectory
    # numbers BENCH_serving.json accumulates
    K_load = max(args.members)
    params = jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), K_load))
    eng = EnsembleEngine(cfg, params, n_slots=args.batch,
                         max_prompt=args.prompt_len, max_out=args.steps,
                         prefill_chunk=args.prefill_chunk)
    reqs = client.make_requests(
        4 * args.batch, cfg.vocab_size,
        prompt_len=(max(2, args.prompt_len // 4), args.prompt_len),
        max_new=(max(1, args.steps // 2), args.steps))
    eng.generate([reqs[0][0]], max_new=2)  # compile outside the clock
    report = client.run_load(eng, reqs)
    metrics.update({
        "load_k": K_load,
        "load_tok_s": report["tok_s"],
        "load_ttft_p50_ms": report["ttft_p50_ms"],
        "load_ttft_p99_ms": report["ttft_p99_ms"],
        "load_latency_p99_ms": report["latency_p99_ms"],
        "admissible_concurrency": report["peak_in_flight"],
        "cache_bytes_per_device": int(eng.cache_bytes()),
    })
    print(f"load K={K_load}: {report['tok_s']:.1f} tok/s, ttft p50 "
          f"{report['ttft_p50_ms']:.1f} / p99 {report['ttft_p99_ms']:.1f} "
          f"ms, {report['peak_in_flight']} admissible concurrent, "
          f"{report['cache_mb']:.2f} MiB/device cache")

    ok = True
    if 4 in speedups:
        gate = speedups[4] >= 2.0
        ok &= gate
        print(f"K=4 throughput acceptance (>= 2x): "
              f"{'PASS' if gate else 'FAIL'} ({speedups[4]:.2f}x)")
    gate = ttft_x >= 4.0
    ok &= gate
    print(f"K=4 TTFT acceptance (>= 4x): {'PASS' if gate else 'FAIL'} "
          f"({ttft_x:.2f}x)")

    if args.mesh:
        mesh_ok, lines = bench_mesh(cfg, args.mesh, 4, args.batch,
                                    args.prompt_len, args.steps,
                                    args.repeats)
        print("\n".join(lines))
        ok &= mesh_ok

    if args.paged:
        paged_ok, lines = bench_paged()
        print("\n".join(lines))
        ok &= paged_ok

    if args.frontend:
        fe_ok, lines, m = bench_frontend()
        metrics.update(m)
        print("\n".join(lines))
        ok &= fe_ok

    if args.prefix:
        px_ok, lines, m = bench_prefix()
        metrics.update(m)
        print("\n".join(lines))
        ok &= px_ok

    if args.kv_quant:
        kq_ok, lines, m = bench_kv_quant()
        metrics.update(m)
        print("\n".join(lines))
        ok &= kq_ok

    if args.spec:
        sp_ok, lines, m = bench_spec(gamma=args.gamma)
        metrics.update(m)
        print("\n".join(lines))
        ok &= sp_ok

    if args.obs:
        ob_ok, lines, m = bench_obs()
        metrics.update(m)
        print("\n".join(lines))
        ok &= ob_ok

    if args.fleet:
        fl_ok, lines, m = bench_fleet()
        metrics.update(m)
        print("\n".join(lines))
        ok &= fl_ok
    return finish(ok)


if __name__ == "__main__":
    raise SystemExit(main())
