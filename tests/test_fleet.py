"""Multi-process replica fleet: sockets, cancellation, backpressure.

Two tiers of test here:

  - IN-PROCESS cancellation regressions: Scheduler.cancel through every
    release path it composes with — queued, mid-decode, mid-chunked-
    prefill, prefix-shared pages, speculative rollback, and the HTTP
    SSE-disconnect trigger.  Each asserts the page pool is WHOLE
    afterwards (engine.assert_pool_whole walks refcounts, the free
    list, and trie ownership) and that surviving requests stay
    token-exact.
  - PROCESS-FLEET soak/chaos: replicas as OS processes (EngineSpec ->
    ReplicaProcess -> FleetRouter), requests over real sockets, with a
    SIGKILL + restart injected mid-load.  The contract under test:
    every request completes token-exact against an offline reference
    built from the SAME spec (crash-retried requests rerun on a
    survivor — seed-pinned init makes the rerun bit-identical), zero
    wedged handlers, and zero leaked pages, asserted over the wire
    from /healthz page accounting.

Token-exactness uses the repo's standard strategy: float32 config so
greedy argmax cannot fork on near-ties, references from the same
engine class through the batch generate() path.
"""
import dataclasses
import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request
from http.client import HTTPConnection

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as tf
from repro.serving import EnsembleEngine, Scheduler, client
from repro.serving.frontend import (EngineSpec, FleetRouter, FrontendServer,
                                    QueueFull, Replica, Router)

# deepseek-7b reduced: every mixer pages its positional state, so the
# prefix cache is eligible at any max_prompt/max_out (gemma3-1b's
# sliding-window layers would cap the sequence at the window)
CFG = registry.get_config("deepseek-7b", reduced=True).with_(dtype="float32")


def _params(K, seed=0, cfg=CFG):
    return jax.vmap(lambda k: tf.init(k, cfg))(
        jax.random.split(jax.random.PRNGKey(seed), K))


def _mk_engine(params, **over):
    kw = dict(n_slots=2, max_prompt=16, max_out=8, prefill_chunk=4,
              paged=True, page_size=4, prefix_cache=True)
    kw.update(over)
    return EnsembleEngine(CFG, params, **kw)


@pytest.fixture(scope="module")
def params_k2():
    return _params(2)


def _serve(sched):
    t = threading.Thread(target=sched.serve_forever, daemon=True)
    t.start()
    return t


# -- cancellation: the scheduler-level contract ------------------------------


def test_cancel_queued_request_never_admits(params_k2):
    """Cancelling a still-pending rid removes it before admission: no
    slot, no pages, no callbacks, pool untouched."""
    eng = _mk_engine(params_k2)
    sched = Scheduler(eng)
    fired = []
    rids = [sched.submit(np.arange(1, 6), 4,
                         on_done=lambda c: fired.append(c.rid))
            for _ in range(4)]  # 2 slots: the last two stay pending
    assert sched.cancel(rids[-1])
    comps = sched.run()
    assert rids[-1] not in comps and rids[-1] not in fired
    assert sorted(fired) == rids[:-1]
    assert sched.n_cancelled == 1
    eng.assert_pool_whole()


def test_cancel_unknown_rid_is_benign(params_k2):
    sched = Scheduler(_mk_engine(params_k2))
    assert not sched.cancel(999)
    rid = sched.submit(np.arange(1, 5), 3)
    sched.run()
    assert not sched.cancel(rid)  # already finished: benign False
    assert sched.n_cancelled == 0


def test_cancel_mid_decode_releases_pages_survivors_exact(params_k2):
    """Cancel a LIVE slot after its first streamed token: the slot and
    its pages free mid-decode, survivors finish token-exact, the pool
    is whole (refcounts zero, free list unbroken)."""
    prompts = [np.arange(1, 7), np.arange(2, 9), np.arange(3, 8)]
    refs = [_mk_engine(params_k2, max_out=32)
            .generate([p], max_new=6)[0].tolist() for p in prompts]
    eng = _mk_engine(params_k2, max_out=32)
    sched = Scheduler(eng, retain_completions=True)
    first_tok = threading.Event()
    done = threading.Semaphore(0)
    # the cancel target decodes far longer than the survivors, so the
    # cancel always lands while it is still live — no timing luck
    rid0 = sched.submit(prompts[0], 32,
                        on_token=lambda r, i, t: first_tok.set())
    others = [sched.submit(p, 6, on_done=lambda c: done.release())
              for p in prompts[1:]]
    t = _serve(sched)
    try:
        assert first_tok.wait(60.0)  # rid0 is live and decoding
        assert sched.cancel(rid0)
        for _ in others:
            assert done.acquire(timeout=60.0)
        assert sched.wait_quiesced(60.0)
        assert sched.n_cancelled == 1
        assert rid0 not in sched.completions
        for rid, ref in zip(others, refs[1:]):
            assert sched.completions[rid].tokens.tolist() == ref
        eng.assert_pool_whole()
    finally:
        sched.stop()
        t.join(10.0)


def test_cancel_during_chunked_prefill(params_k2):
    """Cancel while the prompt is mid-chunked-prefill (prefill_left >
    0): the partially-filled chain frees completely."""
    eng = _mk_engine(params_k2, max_prompt=16)
    sched = Scheduler(eng, prefill_budget=4)  # 16-token prompt: 4 rounds
    rid = sched.submit(np.arange(1, 17), 6)
    t = _serve(sched)
    try:
        deadline = time.time() + 60.0
        while time.time() < deadline:  # wait for admission to a slot
            if any(m is not None and m.req.rid == rid
                   for m in sched.slots):
                break
            time.sleep(0.001)
        assert sched.cancel(rid)
        assert sched.wait_quiesced(60.0)
        assert sched.n_cancelled == 1
        eng.assert_pool_whole()
        # the loop still serves after the mid-prefill cancel
        out = {}
        ev = threading.Event()
        sched.submit(np.arange(1, 6), 4,
                     on_done=lambda c: (out.setdefault("c", c), ev.set()))
        assert ev.wait(60.0)
        ref = _mk_engine(params_k2).generate(
            [np.arange(1, 6)], max_new=4)[0]
        np.testing.assert_array_equal(out["c"].tokens, ref)
    finally:
        sched.stop()
        t.join(10.0)


def test_cancel_prefix_shared_request_keeps_trie_whole(params_k2):
    """Cancel a request decoding on SHARED prefix pages: its refs drop,
    the trie keeps the pages (evictable, not leaked), and a repeat of
    the workload still serves token-exact from cache."""
    shared = list(range(50, 62))
    pa = np.array(shared + [7, 8], np.int32)
    pb = np.array(shared + [9], np.int32)
    ref_b = _mk_engine(params_k2, max_out=32).generate(
        [pb], max_new=6)[0].tolist()
    eng = _mk_engine(params_k2, max_out=32)
    sched = Scheduler(eng, retain_completions=True)
    t = _serve(sched)
    try:
        ev = threading.Event()
        sched.submit(pa, 6, on_done=lambda c: ev.set())  # warm the trie
        assert ev.wait(60.0)
        assert sched.wait_quiesced(60.0)
        assert eng.page_stats()["cached_pages"] > 0

        first_tok = threading.Event()
        rid = sched.submit(pb, 24,  # shares the cached prefix; long
                           # decode so the cancel lands mid-flight
                           on_token=lambda r, i, tk: first_tok.set())
        assert first_tok.wait(60.0)
        assert sched.cancel(rid)
        assert sched.wait_quiesced(60.0)
        assert sched.n_cancelled == 1
        eng.assert_pool_whole()  # trie-owned pages evictable, none lost

        ev2 = threading.Event()
        out = {}
        rid2 = sched.submit(pb, 6, on_done=lambda c: (
            out.setdefault("c", c), ev2.set()))
        assert ev2.wait(60.0)
        assert out["c"].tokens.tolist() == ref_b
        del rid2
    finally:
        sched.stop()
        t.join(10.0)


def test_cancel_during_speculative_decode(params_k2):
    """Cancel mid-decode on a SpeculativeEngine: the cancel composes
    with draft-cache rollback — survivors stay token-exact vs the
    plain fused reference and the paged pool comes back whole."""
    from repro.serving import SpeculativeEngine
    student = jax.tree.map(lambda x: x[0], params_k2)
    kw = dict(n_slots=2, max_prompt=8, max_out=32, prefill_chunk=4,
              paged=True, page_size=4, n_pages=32)
    prompts = [np.arange(1, 7), np.arange(2, 8), np.arange(3, 6)]
    refs = [EnsembleEngine(CFG, params_k2, **kw)
            .generate([p], max_new=8)[0].tolist() for p in prompts]
    eng = SpeculativeEngine(CFG, params_k2, student, gamma=3, **kw)
    sched = Scheduler(eng, retain_completions=True)
    first_tok = threading.Event()
    done = threading.Semaphore(0)
    # speculation accepts runs of tokens per iteration, so the cancel
    # target gets a long budget to guarantee it is still mid-decode
    rid0 = sched.submit(prompts[0], 32,
                        on_token=lambda r, i, tk: first_tok.set())
    others = [sched.submit(p, 8, on_done=lambda c: done.release())
              for p in prompts[1:]]
    t = _serve(sched)
    try:
        assert first_tok.wait(60.0)
        assert sched.cancel(rid0)
        for _ in others:
            assert done.acquire(timeout=60.0)
        assert sched.wait_quiesced(60.0)
        assert sched.n_cancelled == 1
        for rid, ref in zip(others, refs[1:]):
            assert sched.completions[rid].tokens.tolist() == ref
        eng.assert_pool_whole()
    finally:
        sched.stop()
        t.join(10.0)


# -- cancellation + backpressure at the HTTP door ----------------------------


def test_http_sse_disconnect_cancels_in_process(params_k2):
    """A client that opens an SSE stream and drops the socket after the
    first token CANCELS its request: the scheduler counts it, the slot
    and pages free, and the server keeps serving."""
    eng = _mk_engine(params_k2, max_out=64)
    rep = Replica("r0", eng)
    router = Router([rep])
    srv = FrontendServer(router)
    srv.start()
    try:
        body = json.dumps({"tokens": [1, 2, 3, 4], "max_new": 48,
                           "stream": True}).encode()
        conn = HTTPConnection(srv.host, srv.port, timeout=30.0)
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        got = b""
        while b"\n\n" not in got:  # one token event crossed the socket
            got += resp.read1(4096)
        # Abortive close: a plain close() sends a FIN and the kernel keeps
        # ACKing the server's small SSE writes into a dead buffer, so the
        # handler never sees an error. linger(on, 0) turns close() into an
        # RST — the server's next write raises and the handler cancels.
        # (Connection: close moved the socket onto the response object.)
        sock = resp.fp.raw._sock
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))
        resp.close()
        conn.close()

        deadline = time.time() + 60.0
        while rep.scheduler.n_cancelled == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert rep.scheduler.n_cancelled == 1
        assert rep.scheduler.wait_quiesced(60.0)
        eng.assert_pool_whole()
        out = client.http_generate(srv.url, np.arange(1, 5), 4)
        assert len(out["tokens"]) == 4  # loop unharmed
        assert client.http_get_json(srv.url, "/healthz")["cancelled"] == 1
    finally:
        srv.shutdown(drain=False)


def test_http_429_backpressure_with_retry_after(params_k2):
    """Past max_queue_depth the door answers 429 + Retry-After instead
    of parking handlers; shed requests are counted and the typed
    client exception carries the backoff hint."""
    eng = _mk_engine(params_k2, n_slots=2)
    rep = Replica("r0", eng)
    router = Router([rep], max_queue_depth=1)
    srv = FrontendServer(router)
    srv.start()
    try:
        slow = threading.Thread(
            target=lambda: client.http_generate(srv.url, [1, 2, 3], 8),
            daemon=True)
        slow.start()
        deadline = time.time() + 30.0
        while router.queue_depth == 0 and time.time() < deadline:
            time.sleep(0.001)
        with pytest.raises(client.Backpressure) as ei:
            client.http_generate(srv.url, [4, 5, 6], 4)
        assert ei.value.retry_after > 0
        # raw header shape too: integer seconds per RFC 9110
        req = urllib.request.Request(
            srv.url + "/v1/generate",
            data=json.dumps({"tokens": [7], "max_new": 2}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req)
            raised = None
        except urllib.error.HTTPError as e:
            raised = e
        if raised is not None:  # the slow request may have finished
            assert raised.code == 429
            assert int(raised.headers["Retry-After"]) >= 1
        slow.join(60.0)
        assert router.stats()["shed"] >= 1
        # capacity freed: the same request now serves
        out = client.http_generate(srv.url, [4, 5, 6], 4)
        assert len(out["tokens"]) == 4
    finally:
        srv.shutdown()


def test_queuefull_fields():
    e = QueueFull(depth=7, limit=4, retry_after=0.35)
    assert e.depth == 7 and e.limit == 4 and e.retry_after == 0.35
    assert "queue depth 7" in str(e)


def test_router_add_remove_replica(params_k2):
    """Elastic membership on the in-process tier: add_replica grows the
    fleet under a running router; remove_replica drains and detaches
    (and refuses to empty the fleet)."""
    r0 = Replica("r0", _mk_engine(params_k2))
    router = Router([r0])
    router.start()
    try:
        router.add_replica(Replica("r1", _mk_engine(params_k2)))
        assert {r.name for r in router.replicas} == {"r0", "r1"}
        ev = threading.Event()
        router.submit(np.arange(1, 5), 3, on_done=lambda c: ev.set())
        assert ev.wait(60.0)
        gone = router.remove_replica("r1", timeout=60.0)
        assert gone.name == "r1" and not gone.scheduler.has_work
        assert [r.name for r in router.replicas] == ["r0"]
        with pytest.raises(ValueError, match="last replica"):
            router.remove_replica("r0")
    finally:
        router.stop()


# -- the process fleet -------------------------------------------------------

FLEET_SPEC = EngineSpec(
    arch="deepseek-7b", reduced=True, dtype="float32", members=2, seed=0,
    n_slots=2, max_prompt=16, max_out=8, prefill_chunk=4,
    paged=True, page_size=4, prefix_cache=True,
    # on the forced-2-device CI host every child process shards its two
    # members over a REAL 2-device mesh (XLA_FLAGS inherits through the
    # child's environment); single-device runs keep the unsharded engine
    mesh="2x1" if len(jax.devices()) >= 2 else "")


def test_engine_spec_json_roundtrip():
    assert EngineSpec.from_json(FLEET_SPEC.to_json()) == FLEET_SPEC
    assert EngineSpec.from_json(
        dataclasses.replace(FLEET_SPEC, seed=3).to_json()) != FLEET_SPEC


def _wait_replica_drained(proc, timeout=60.0):
    """Poll /healthz until the replica process reports no live or
    pending work and a whole page pool; -> the final replica dict."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        r = proc.healthz()["replicas"][0]
        if (r["live_slots"] == 0 and r["pending"] == 0
                and r["available_pages"] == r["n_pages"]):
            return r
        time.sleep(0.05)
    raise AssertionError(
        f"replica {proc.name} never drained: {proc.healthz()}")


@pytest.fixture(scope="module")
def fleet2():
    """One 2-process fleet shared by the soak + rollout + scale tests
    (each compile costs ~10s of wall clock; the tests that mutate the
    fleet restore its shape before returning)."""
    fleet = FleetRouter(FLEET_SPEC, n=2)
    fleet.start(timeout=600.0)
    yield fleet
    fleet.stop()


@pytest.fixture(scope="module")
def fleet_refs():
    """Offline reference map {prompt tuple -> tokens} from the SAME
    spec the processes build from — the cross-process ground truth."""
    shared = list(range(50, 62))
    prompts = ([tuple(shared + [i]) for i in range(4)]
               + [tuple(range(1 + i, 7 + i)) for i in range(4)]
               + [tuple(range(90, 90 + 3 + i)) for i in range(4)])
    eng = FLEET_SPEC.build_engine()
    refs = {}
    for p in prompts:
        refs[p] = eng.generate([list(p)], max_new=6)[0].tolist()
    return refs


def test_fleet_soak_sigkill_restart_token_exact(fleet2, fleet_refs):
    """THE soak gate: ~200 threaded requests against a 2-process fleet
    while one replica is SIGKILLed and restarted mid-load.  Every
    request must complete token-exact against the offline reference
    (lost ones retried on the survivor) — zero drops, zero wedged
    handlers — and both processes must end with whole page pools."""
    prompts = list(fleet_refs)
    n_total = 200
    results = [None] * n_total
    errors = []
    nxt = {"i": 0}
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = nxt["i"]
                if i >= n_total:
                    return
                nxt["i"] += 1
            p = prompts[i % len(prompts)]
            try:
                out = fleet2.generate(list(p), 6, retries=5)
                results[i] = (p, out["tokens"])
            except Exception as e:  # noqa: BLE001 — a drop is the bug
                with lock:
                    errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(8)]
    for t in threads:
        t.start()

    # chaos: wait until the fleet is genuinely mid-load, then SIGKILL
    # one replica; restart it while the survivor absorbs the traffic
    deadline = time.time() + 120.0
    while time.time() < deadline:
        with lock:
            started = nxt["i"]
        if started >= 20:
            break
        time.sleep(0.01)
    with lock:
        assert nxt["i"] < n_total, "load finished before the kill"
    victim = fleet2.procs[1]
    victim.kill()
    assert not victim.alive
    assert fleet2.health_sweep() == ["p1"]
    fleet2.restart("p1", timeout=600.0)
    assert fleet2.procs[1].alive

    for t in threads:
        t.join(600.0)
    assert not any(t.is_alive() for t in threads), "wedged workers"
    assert errors == []  # zero drops
    for i, item in enumerate(results):
        assert item is not None, f"request {i} vanished"
        p, toks = item
        assert toks == fleet_refs[p], f"request {i} not token-exact"
    # the kill was observed by the router (latched) whenever a request
    # was in flight on the victim; either way the fleet recovered
    s = fleet2.stats()
    assert s["n_live"] == 2
    for proc in fleet2.procs:
        r = _wait_replica_drained(proc, timeout=60.0)
        assert r["failed"] is None


def test_fleet_canary_rollout_over_sockets(fleet2, fleet_refs):
    """rollout(seed=7, canary=0.5): one process swaps first and serves
    the canary fraction; once its completions land, the fleet follows.
    Post-rollout outputs match a fresh seed-7 reference engine."""
    prompt = list(next(iter(fleet_refs)))
    ref7 = dataclasses.replace(FLEET_SPEC, seed=7).build_engine() \
        .generate([prompt], max_new=6)[0].tolist()
    assert ref7 != fleet_refs[tuple(prompt)]  # swap must be observable

    stop = threading.Event()
    errs = []

    def traffic():  # the canary window needs live requests to observe
        while not stop.is_set():
            try:
                fleet2.generate(prompt, 6, retries=3)
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))
                return

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    try:
        try:
            fleet2.rollout(seed=7, canary=0.5, canary_requests=2,
                           canary_timeout=300.0)
        finally:
            stop.set()
            t.join(120.0)
        assert not errs
        for proc in fleet2.procs:
            r = _wait_replica_drained(proc, timeout=60.0)
            assert r["swaps_done"] >= 1
        out = fleet2.generate(prompt, 6)
        assert out["tokens"] == ref7
    finally:
        # restore the module fixture's round even on failure, so later
        # tests sharing fleet2 see seed-0 weights
        fleet2.rollout(seed=FLEET_SPEC.seed)


def test_fleet_scale_to_and_autoscale(fleet2, fleet_refs):
    """Elastic membership on the socket tier: scale_to spawns/retires
    whole processes; autoscale is a pure function of queue depth."""
    assert len(fleet2.live()) == 2
    fleet2.scale_to(3, timeout=600.0)
    assert len(fleet2.live()) == 3
    p, ref = next(iter(fleet_refs.items()))
    out = fleet2.generate(list(p), 6)
    assert out["tokens"] == ref  # the new process serves the same spec
    fleet2.scale_to(2)
    assert len(fleet2.live()) == 2
    # autoscale: idle fleet (depth 0 <= low) shrinks toward min_n ...
    assert fleet2.autoscale(min_n=2, max_n=4) == 2
    # ... and a depth past high_depth grows by one
    with fleet2._lock:
        fleet2._in_flight[fleet2.procs[0].name] += 99
    try:
        assert fleet2.autoscale(min_n=2, max_n=4, high_depth=8) == 3
    finally:
        with fleet2._lock:
            fleet2._in_flight[fleet2.procs[0].name] -= 99
    fleet2.scale_to(2)
    assert len(fleet2.live()) == 2


def test_fleet_sigterm_is_graceful():
    """SIGTERM drains: the process serves out in-flight work and exits
    0 — the retirement half of elasticity, distinct from SIGKILL."""
    spec = dataclasses.replace(FLEET_SPEC, prefix_cache=False)
    fleet = FleetRouter(spec, n=1)
    fleet.start(timeout=600.0)
    try:
        out = fleet.generate([1, 2, 3, 4], 4)
        assert len(out["tokens"]) == 4
        code = fleet.procs[0].terminate(timeout=60.0)
        assert code == 0  # drained, not murdered
    finally:
        fleet.stop()


def test_fleet_429_over_sockets():
    """A replica process enforces its own max_queue_depth: saturating
    it answers 429 over the wire, FleetRouter backs off per
    Retry-After and still completes everything."""
    spec = dataclasses.replace(FLEET_SPEC, n_slots=1, max_prompt=8,
                               max_out=16, prefix_cache=False,
                               paged=False)
    fleet = FleetRouter(spec, n=1, max_queue_depth=2)
    fleet.start(timeout=600.0)
    try:
        errs, oks = [], []

        def fire():
            try:
                oks.append(fleet.generate([1, 2, 3], 12, retries=2))
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=fire, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
        assert not errs and len(oks) == 6  # backoff, not failure
        h = fleet.procs[0].healthz()
        assert h["shed"] >= 1, "the queue never overflowed"
        assert fleet.n_backoffs >= 1
    finally:
        fleet.stop()


# -- one chip per child on a TPU host -----------------------------------------


def test_fleet_pins_one_chip_per_child(monkeypatch):
    """On a 4-chip host each child's environment names a chip of its
    own (and a runtime port of its own); a fifth replica is refused,
    at construction and at scale-out alike.  A host without chips
    leaves the children's environment unpinned."""
    from repro.serving.frontend import replica as replica_mod
    monkeypatch.setattr(replica_mod, "tpu_chips", lambda: [0, 1, 2, 3])
    fleet = FleetRouter(FLEET_SPEC, n=4)
    envs = [p.env() for p in fleet.procs]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert "src" in e["PYTHONPATH"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        port = e["TPU_PROCESS_PORT"]
        assert e["TPU_PROCESS_ADDRESSES"] == f"localhost:{port}"
    with pytest.raises(ValueError, match="need a TPU chip each"):
        FleetRouter(FLEET_SPEC, n=5)
    with pytest.raises(ValueError, match="0 of this host's 4 chips"):
        fleet.scale_to(5)

    monkeypatch.setattr(replica_mod, "tpu_chips", lambda: [])
    cpu = FleetRouter(FLEET_SPEC, n=5)
    assert all(p.chip is None for p in cpu.procs)
    assert "TPU_VISIBLE_CHIPS" not in cpu.procs[0].env()


def test_tpu_chips_honors_the_chips_this_process_was_given(monkeypatch):
    """A supervisor that was itself handed a subset of the host's chips
    hands out only those, whatever the PCI bus shows."""
    from repro.serving.frontend import replica as replica_mod
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert replica_mod.tpu_chips() == [2, 3]
    fleet = FleetRouter(FLEET_SPEC, n=2)
    assert [p.chip for p in fleet.procs] == [2, 3]
    with pytest.raises(ValueError, match="need a TPU chip each"):
        FleetRouter(FLEET_SPEC, n=3)


def test_fleet_refuses_child_that_came_up_off_the_chip(monkeypatch):
    """A child pinned to a chip that reports another platform in its
    ready handshake (here: the CPU, as a child whose chip the parent
    holds can) is killed and the fleet refuses to start."""
    from repro.serving.frontend import replica as replica_mod
    monkeypatch.setattr(replica_mod, "tpu_chips", lambda: [0])
    spec = dataclasses.replace(FLEET_SPEC, prefix_cache=False, mesh="")
    fleet = FleetRouter(spec, n=1)
    with pytest.raises(RuntimeError, match="came up on cpu"):
        fleet.start(timeout=600.0)
    assert fleet.procs[0].platform == "cpu"
    assert not fleet.procs[0].alive
