"""Serving launcher: the EC-DNN_G ensemble engine behind a CLI.

EC-DNN_G serving: all K members score each step inside ONE compiled
program (repro.serving.EnsembleEngine) and the output distributions are
averaged (paper Eqn 6) before sampling — the ensemble IS the product
when resources allow.  --members 1 serves a single member / compressed
model (EC-DNN_L) through the identical path.

Static batch (tok/s):
  python -m repro.launch.serve --arch gemma3-1b --reduced --members 4 \
      --batch 8 --steps 16 --ensemble

Continuous batching under synthetic load (tok/s + TTFT + latency
percentiles):
  python -m repro.launch.serve --arch gemma3-1b --reduced --members 4 \
      --ensemble --continuous --requests 32

--quorum "1,1,0,1" drops member 2 (straggler policy): the fused
distribution renormalizes over the survivors, no recompile.

--mesh MxD shards the member axis over M devices (x D data devices,
reserved) and runs every kernel under shard_map — per-device cache and
FLOPs scale with K/M.  On CPU, force host devices first:
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
  python -m repro.launch.serve --arch gemma3-1b --reduced --members 4 \
      --ensemble --mesh 2x1

HTTP frontend (streaming SSE + /metrics + /healthz, N replicas behind
a least-loaded router, Ctrl-C drains gracefully):
  python -m repro.launch.serve --arch gemma3-1b --reduced --members 4 \
      --ensemble --http --port 8000 --replicas 2
  curl -s localhost:8000/v1/generate -d '{"tokens":[1,2,3],"max_new":8}'
--watch-ckpt DIR polls a CheckpointManager root for newly committed
rounds and hot-swaps each one into the fleet with the zero-downtime
drain -> swap -> rejoin rollout (the paper's train -> compress -> serve
loop, closed).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def serve_http(args, cfg, build_engine):
    """Mount --replicas engines behind the router + HTTP frontend."""
    from repro.serving import client
    from repro.serving.frontend import Replica, Router, serve_frontend

    replicas = [Replica(f"r{i}", build_engine(),
                        prefill_budget=args.prefill_budget,
                        obs=not args.no_obs,
                        trace_log=args.trace_log or None,
                        profile_dir=args.profile_dir or None)
                for i in range(max(1, args.replicas))]
    router = Router(replicas, max_queue_depth=args.max_queue_depth)
    srv = serve_frontend(router, host=args.host, port=args.port,
                         verbose=not args.load,
                         profile_dir=args.profile_dir or None)
    print(f"frontend: {srv.url}  ({len(replicas)} replica(s), "
          f"K={replicas[0].engine.n_members} members, "
          f"{replicas[0].engine.n_slots} slots each)")
    print(f"  POST {srv.url}/v1/generate  "
          '{"tokens": [...], "max_new": N, "stream": true|false}')
    print(f"  GET  {srv.url}/healthz   GET  {srv.url}/metrics")

    try:
        if args.load:
            reqs = client.make_requests(
                args.requests, cfg.vocab_size,
                prompt_len=(max(2, args.prompt_len // 4), args.prompt_len),
                max_new=(max(1, args.steps // 2), args.steps),
                seed=args.seed)
            client.print_report(client.run_http_load(
                srv.url, reqs, concurrency=2 * len(replicas)))
            return 0
        if args.watch_ckpt:
            watch_checkpoints(args.watch_ckpt, router, canary=args.canary)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        print("\ndraining ...")
    finally:
        srv.shutdown(drain=True)
    print("drained; bye")
    return 0


def serve_fleet(args, cfg):
    """--fleet: each replica its own OS process behind a FleetRouter.

    The processes rebuild bit-identical engines from one EngineSpec
    (seed-pinned init), so a request retried after a crash is
    token-exact.  --load drives the synthetic requests through the
    fleet with crash-retry and 429 backoff; --watch-ckpt rolls new
    rounds out over POST /admin/swap (with --canary staging).
    """
    import threading

    from repro.serving import client
    from repro.serving.frontend import EngineSpec, FleetRouter

    spec = EngineSpec(
        arch=args.arch, reduced=args.reduced,
        members=args.members if args.ensemble else 1, seed=args.seed,
        n_slots=args.batch, max_prompt=args.prompt_len,
        max_out=args.steps, prefill_chunk=args.prefill_chunk,
        temperature=args.temperature, top_k=args.top_k,
        eos_id=args.eos_id,
        quorum=([float(x) for x in args.quorum.split(",")]
                if args.quorum else None),
        mesh=args.mesh, paged=args.paged, page_size=args.page_size,
        n_pages=args.n_pages, prefix_cache=args.prefix_cache,
        kv_dtype=args.kv_dtype,
        draft_member0=(args.draft_ckpt == "member0"),
        gamma=args.gamma, spec_sampling=args.spec_sampling,
        ckpt=(args.draft_ckpt if args.draft_ckpt
              not in ("", "member0") else ""),
        prefill_budget=args.prefill_budget,
        obs=not args.no_obs, trace_log=args.trace_log,
        profile_dir=args.profile_dir)
    fleet = FleetRouter(spec, n=max(1, args.replicas), host=args.host,
                        max_queue_depth=args.max_queue_depth)
    print(f"spawning {max(1, args.replicas)} replica process(es) "
          f"(K={spec.members} members each) ...")
    fleet.start()
    for p in fleet.procs:
        print(f"  {p.name}: pid {p.proc.pid}  {p.url}")
    try:
        if args.load:
            reqs = client.make_requests(
                args.requests, cfg.vocab_size,
                prompt_len=(max(2, args.prompt_len // 4), args.prompt_len),
                max_new=(max(1, args.steps // 2), args.steps),
                seed=args.seed)
            done, errs = [], []
            lock = threading.Lock()
            nxt = {"i": 0}

            def worker():
                while True:
                    with lock:
                        i = nxt["i"]
                        if i >= len(reqs):
                            return
                        nxt["i"] += 1
                    try:
                        out = fleet.generate(*reqs[i])
                        with lock:
                            done.append(out)
                    except Exception as e:  # noqa: BLE001
                        with lock:
                            errs.append(repr(e))

            t0 = time.time()
            threads = [threading.Thread(target=worker, daemon=True)
                       for _ in range(2 * len(fleet.procs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.time() - t0
            n_tok = sum(r["n_gen"] for r in done)
            s = fleet.stats()
            print(f"fleet served {len(done)}/{len(reqs)} requests "
                  f"({len(errs)} errors) | {n_tok} tokens in "
                  f"{wall:.2f}s = {n_tok / max(wall, 1e-9):.1f} tok/s")
            print(f"  retried {s['retried']}, 429 backoffs "
                  f"{s['backoffs']}, latched {s['latched']}")
            return 1 if errs else 0
        if args.watch_ckpt:
            from repro.checkpoint.store import latest_step
            served = None
            while True:
                latest = latest_step(args.watch_ckpt)
                if latest is not None and latest != served:
                    fleet.rollout(ckpt=args.watch_ckpt, step=latest,
                                  canary=args.canary)
                    served = latest
                    print(f"rolled out round {served} fleet-wide")
                time.sleep(5.0)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        print("\nterminating fleet ...")
    finally:
        fleet.stop()
    print("fleet down; bye")
    return 0


def watch_checkpoints(root: str, router, poll_s: float = 5.0,
                      canary: float = 0.0):
    """Poll a CheckpointManager root; hot-swap each newly committed
    round into the fleet (drain -> swap -> rejoin, zero drops).
    canary > 0 routes that traffic fraction at one swapped replica
    first and aborts the rollout if it fails.

    The round already on disk at startup is rolled in FIRST: a
    restarted server must serve the trained weights, not the random
    init its engines were constructed with.
    """
    from repro.checkpoint.store import latest_step, restore_checkpoint

    served = None
    print(f"watching {root} "
          f"(round on disk: {latest_step(root)})")
    while True:
        latest = latest_step(root)
        if latest is not None and latest != served:
            template = router.replicas[0].engine.params
            new_params = restore_checkpoint(root, latest, template)
            router.rollout(new_params, canary=canary)
            served = latest
            print(f"rolled out round {served} "
                  f"(swaps: "
                  f"{[r.engine.swaps_done for r in router.replicas]})")
        time.sleep(poll_s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--members", type=int, default=1)
    ap.add_argument("--ensemble", action="store_true",
                    help="EC-DNN_G: average member distributions")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (concurrent requests)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16,
                    help="max new tokens per request")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per prefill program (0: "
                         "per-token reference path; default: autotuned "
                         "from --prompt-len and --page-size)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prompt tokens prefilled per scheduler "
                         "iteration (default: 2 chunks)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool: full-attention caches become "
                         "fixed-size pages behind a per-slot page "
                         "table; admission bounds by free pages")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (--paged)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="physical pages in the pool (--paged; default "
                         "slots x ceil(max_seq/page) = full capacity, "
                         "smaller oversubscribes and relies on "
                         "preemption)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=["f32", "bf16", "int8", "fp8"],
                    help="paged KV page storage format (--paged): f32 "
                         "keeps the bit-exact native planes; int8/fp8 "
                         "quantize pages with per-token absmax scale "
                         "sidecars dequantized inside the kernel, "
                         "~4x/~4x fewer cache bytes per token so the "
                         "same pool admits ~4x the concurrency")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share KV pages across requests with a common "
                         "prompt prefix (--paged only): a shared-prefix "
                         "trie skips prefill below the hit, refcounted "
                         "copy-on-write pages keep slots isolated")
    ap.add_argument("--draft-ckpt", default="",
                    help="speculative decoding: serve the compressed "
                         "student at this CheckpointManager root as the "
                         "draft model for the ensemble (EC-DNN_L "
                         "drafting for EC-DNN_G); 'member0' drafts with "
                         "member 0's weights (demo without a ckpt)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens proposed per speculative "
                         "iteration (--draft-ckpt)")
    ap.add_argument("--spec-sampling", action="store_true",
                    help="stochastic speculative decoding (rejection "
                         "sampling against the fused distribution) "
                         "instead of greedy exact-match accept")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--quorum", default="",
                    help="comma 0/1 per member, e.g. 1,1,0,1")
    ap.add_argument("--mesh", default="",
                    help="'MxD' member x data device grid (e.g. 2x1): "
                         "shard the member axis over M devices; empty "
                         "or 1x1 keeps the single-device path")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching under synthetic load")
    ap.add_argument("--requests", type=int, default=32,
                    help="synthetic requests (--continuous / --load)")
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP: POST /v1/generate (SSE "
                         "streaming), GET /metrics, GET /healthz; "
                         "Ctrl-C drains gracefully")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port (--http; 0 picks an ephemeral one)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the frontend router "
                         "(--http); each gets its own cache pool")
    ap.add_argument("--fleet", action="store_true",
                    help="with --http: run each replica as its own OS "
                         "PROCESS (engine + scheduler + HTTP surface) "
                         "behind a crash-latching FleetRouter instead "
                         "of threads in this one")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="backpressure: past this fleet-wide queue "
                         "depth, POST /v1/generate answers 429 with "
                         "Retry-After instead of queueing")
    ap.add_argument("--canary", type=float, default=0.0,
                    help="rollout canary fraction: swap one replica "
                         "first and route this share of traffic at it "
                         "before the fleet-wide swap (--watch-ckpt)")
    ap.add_argument("--load", action="store_true",
                    help="with --http: drive the synthetic requests "
                         "through the HTTP path and print the report "
                         "instead of serving until Ctrl-C")
    ap.add_argument("--watch-ckpt", default="",
                    help="with --http: poll this CheckpointManager "
                         "root and hot-swap each newly committed round "
                         "into the fleet (drain -> swap -> rejoin)")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the observability layer (request "
                         "traces, latency histograms, tick-phase "
                         "profiler); on by default at <2%% overhead")
    ap.add_argument("--trace-log", default="",
                    help="append one JSON line per finished request "
                         "trace to this file (obs must be on)")
    ap.add_argument("--profile-dir", default="",
                    help="jax.profiler output dir; arms POST "
                         "/admin/profile {\"ticks\": N} to capture "
                         "device traces for N scheduler ticks")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.common import sharding as shd
    from repro.common.compile_cache import use_compile_cache
    from repro.configs import registry
    from repro.models import transformer as tf
    from repro.serving import EnsembleEngine, client

    cfg = registry.get_config(args.arch, reduced=args.reduced)
    if args.http and args.fleet:
        # fleet mode: the replica PROCESSES build the engines; the
        # parent never initializes params at all
        return serve_fleet(args, cfg)
    use_compile_cache()
    key = jax.random.PRNGKey(args.seed)
    K = args.members if args.ensemble else 1
    params = jax.vmap(lambda k: tf.init(k, cfg))(jax.random.split(key, K))
    quorum = ([float(x) for x in args.quorum.split(",")]
              if args.quorum else None)
    if quorum is not None and len(quorum) != K:
        raise SystemExit(f"--quorum needs {K} entries, got {len(quorum)}")
    mesh = shd.parse_mesh_arg(args.mesh)

    draft_params = None
    if args.draft_ckpt:
        if args.draft_ckpt == "member0":
            draft_params = jax.tree.map(lambda x: x[0], params)
        else:
            from repro.checkpoint.store import (latest_step,
                                                restore_checkpoint)
            step = latest_step(args.draft_ckpt)
            if step is None:
                raise SystemExit(
                    f"--draft-ckpt {args.draft_ckpt}: no committed round")
            template = tf.init(jax.random.PRNGKey(0), cfg)
            draft_params = restore_checkpoint(args.draft_ckpt, step,
                                              template)
            print(f"draft model: round {step} from {args.draft_ckpt}")

    def build_engine():
        kw = dict(
            n_slots=args.batch, max_prompt=args.prompt_len,
            max_out=args.steps, prefill_chunk=args.prefill_chunk,
            temperature=args.temperature, top_k=args.top_k,
            eos_id=args.eos_id, quorum=quorum, seed=args.seed, mesh=mesh,
            paged=args.paged, page_size=args.page_size,
            n_pages=args.n_pages, prefix_cache=args.prefix_cache,
            kv_dtype=args.kv_dtype)
        if draft_params is not None:
            from repro.serving import SpeculativeEngine
            return SpeculativeEngine(cfg, params, draft_params,
                                     gamma=args.gamma,
                                     spec_sampling=args.spec_sampling,
                                     **kw)
        return EnsembleEngine(cfg, params, **kw)

    if args.http:
        return serve_http(args, cfg, build_engine)

    engine = build_engine()
    place = ("single-device" if mesh is None else
             f"mesh {dict(mesh.shape)} over {mesh.devices.size} devices, "
             f"{K // engine.member_shards} members/device")
    print(f"engine: K={K} members, {args.batch} slots, "
          f"prefill chunk {engine.prefill_chunk}, {place}, "
          f"cache pool {engine.cache_bytes() / 2**20:.1f} MiB/device")
    if args.paged:
        ps = engine.page_stats()
        print(f"paged pool: {ps['n_pages']} pages/device x "
              f"{ps['page_size']} tok ({ps['pages_per_slot']} pages/slot "
              f"max), free list {ps['free_pages']}/{ps['n_pages']} "
              f"({ps['used_pages'] / max(ps['n_pages'], 1):.0%} used)")
        print(f"kv pages: {ps['kv_dtype']} storage, "
              f"{ps['page_bytes']} B/page, "
              f"{ps['bytes_per_token']} B/token across all paged layers")

    if args.continuous:
        reqs = client.make_requests(
            args.requests, cfg.vocab_size,
            prompt_len=(max(2, args.prompt_len // 4), args.prompt_len),
            max_new=(max(1, args.steps // 2), args.steps), seed=args.seed)
        # compile outside the timed run so percentiles measure serving;
        # max_new=2 forces one decode step, so BOTH kernels (prefill +
        # decode) are built here, not inside the first timed iteration
        engine.generate([reqs[0][0]], max_new=2)
        client.print_report(client.run_load(
            engine, reqs, prefill_budget=args.prefill_budget,
            obs=not args.no_obs, trace_log=args.trace_log or None))
        return 0

    B = args.batch
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, args.prompt_len), 0, cfg.vocab_size))
    engine.generate(list(prompt), max_new=args.steps)  # warmup/compile
    t0 = time.time()
    outs = engine.generate(list(prompt), max_new=args.steps)
    dt = time.time() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"served batch={B} members={K} steps={args.steps}: "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tok/s)")
    if hasattr(engine, "spec_stats"):
        sp = engine.spec_stats()
        print(f"speculation: gamma={sp['gamma']}, "
              f"acceptance {sp['acceptance_rate']:.1%}, "
              f"mean accepted {sp['mean_accepted_len']:.2f} tok/step "
              f"(p50 {sp['accepted_len_p50']:.0f})")
    print("sample:", outs[0][:16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
