#!/usr/bin/env python3
"""CPU self-check of the benchmark: no timed run, no chip.

  JAX_PLATFORMS=cpu python3 bench/selfcheck.py

1. BENCHMARK.json against the contract's shape: names, units, bounds,
   sources, run_seconds and what a full check of 24 cells would cost.
2. Every configuration, traffic mix, limit file and per-layer reader is
   found by name and loads; every cell reports setup_s, another
   end-to-end metric and a per-layer metric; every per-layer metric's
   cells report the end-to-end metric it moves.  A later change that adds a
   cell with its files and one entry learns here whether it is whole.
3. Traffic: every seed gets the same work in another order.
4. Metric arithmetic: tails over all samples, rates over the window.
5. FLOP and byte functions against counts made another way.
6. The trace reduction on a small trace recorded on the CPU and kept in
   bench/data/ (a device number is never read from it).
Prints one line per check and exits non-zero on the first failure.
"""
from __future__ import annotations

import json
import math
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRACE = os.path.join(BENCH, "data", "cpu_trace.xplane.pb")


def ok(msg: str):
    print(f"ok   {msg}", flush=True)


def check(cond: bool, msg: str):
    if not cond:
        print(f"FAIL {msg}", file=sys.stderr, flush=True)
        raise SystemExit(1)
    ok(msg)


def line_ok(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


# -- 1, 2: the spec and the files it names ------------------------------------


def spec_checks():
    from harness import cells
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    check(len(raw.encode()) <= 64 * 1024, "BENCHMARK.json within 64 KiB")
    spec = json.loads(raw)
    check(set(spec) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"},
          "BENCHMARK.json has exactly the contract's keys")
    check(all(line_ok(w) for w in spec["command"])
          and len(spec["command"]) <= 32, "command words")
    check(1 <= len(spec["paths"]) <= 16 and all(
        re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and not p.startswith("/")
        and ".." not in p.split("/") for p in spec["paths"]), "paths")
    R = spec["run_seconds"]
    check(isinstance(R, int) and 1 <= R <= 51, "run_seconds in 1..51")
    full = (2 + 14 * 24) * (R + 60) + 24 * 2 * 90 + 1200
    check(full <= 43200, f"a full check of 24 cells fits ({full} s)")

    configs = {c["name"]: c for c in spec["configs"]}
    check(len(configs) == len(spec["configs"]) and 1 <= len(configs) <= 24,
          "configuration names unique")
    files = set()
    for c in spec["configs"]:
        check(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config {c['name']}: keys")
        check(bool(NAME.match(c["name"])) and line_ok(c["source"])
              and line_ok(c["why"]), f"config {c['name']}: name/source/why")
        check(c["file"] not in files and c["file"].startswith("bench/"),
              f"config {c['name']}: its own file under bench/")
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        check(sorted(body["reduced"]) == sorted(c["reduced"])
              and len(c["reduced"]) <= 16
              and all(NAME.match(k) and k in body
                      and k in body.get("published", {})
                      for k in c["reduced"]),
              f"config {c['name']}: reduced keys match the file and "
              f"name their published values")
        widths = [k for k in c["reduced"] if k.endswith(("_dim", "_rank"))
                  or k in ("d_model", "d_ff", "n_heads", "n_kv_heads",
                           "head_dim", "channels", "img")]
        check(not widths, f"config {c['name']}: no width reduced")
        check(os.path.isfile(os.path.join(ROOT, body["reference"])),
              f"config {c['name']}: plain reference beside it")

    e2e = {m["name"]: m for m in spec["end_to_end"]}
    per = {m["name"]: m for m in spec["per_layer"]}
    check("setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25,
          "setup_s present, bound <= 0.25")
    check(len(set(e2e) | set(per)) == len(e2e) + len(per),
          "metric names unique")
    for m in spec["end_to_end"]:
        check(set(m) <= {"name", "unit", "better", "bound", "source",
                         "workloads"} and NAME.match(m["name"])
              and UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                            "higher")
              and m["source"] in ("host_clock", "device_trace")
              and 0.01 <= m["bound"] <= 0.25,
              f"end-to-end {m['name']}: shape, source, bound")
    layers = {}
    for m in spec["per_layer"]:
        check(set(m) <= {"name", "unit", "better", "source", "layer",
                         "moves", "workloads"} and NAME.match(m["name"])
              and UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                            "higher")
              and m["source"] in SOURCES and line_ok(m["layer"])
              and m["moves"] in e2e,
              f"per-layer {m['name']}: shape, source, moves")
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            check(m["name"].split(".")[0].endswith(("_roofline", "mfu"))
                  or m["name"].startswith("mfu"),
                  f"per-layer {m['name']}: named as a share of a peak")
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    check(all(len(v) == 1 for v in layers.values()),
          "each layer spelled one way")

    cells_ = {w["name"]: w for w in spec["workloads"]}
    check(len(cells_) == len(spec["workloads"]) and 1 <= len(cells_) <= 24,
          "cell names unique")
    check(len({(w["config"], w["traffic"]) for w in spec["workloads"]})
          == len(cells_), "each configuration and traffic pair once")
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    check(four <= max(1, len(cells_) // 2), "cells on four chips")
    used = set()
    for w in spec["workloads"]:
        name = w["name"]
        check(set(w) == {"name", "config", "traffic", "chips", "why"}
              and NAME.match(name) and NAME.match(w["traffic"])
              and w["chips"] in (1, 4) and line_ok(w["why"])
              and w["config"] in configs, f"cell {name}: shape")
        used.add(w["config"])
        ctx = cells.load(ROOT, name)
        check(ctx.mix["kind"] in ("open_loop", "closed_loop", "ec_rounds"),
              f"cell {name}: config, traffic and limits found by name")
        rep = {m["name"] for m in cells.metrics_of(spec, name,
                                                   "end_to_end")}
        check("setup_s" in rep and len(rep) >= 2,
              f"cell {name}: reports setup_s and another end-to-end metric")
        pl = cells.metrics_of(spec, name, "per_layer")
        check(len(pl) >= 1, f"cell {name}: reports a per-layer metric")
        for m in pl:
            check(m["moves"] in rep, f"cell {name}: {m['name']} moves "
                  f"{m['moves']}, which the cell reports")
            check(callable(cells.reader(m["name"])),
                  f"cell {name}: reader of {m['name']} loads")
    check(used == set(configs), "every configuration used by a cell")
    for m in spec["per_layer"]:
        for w in m.get("workloads", []):
            check(w in cells_, f"{m['name']}: lists only known cells")
    for m in spec["end_to_end"]:
        for w in m.get("workloads", []):
            check(w in cells_, f"{m['name']}: lists only known cells")
    return spec


# -- 3: the same work for every seed ------------------------------------------


def traffic_checks(spec):
    from harness import cells, traffic
    W = spec["run_seconds"]
    for w in spec["workloads"]:
        mix = cells.load(ROOT, w["name"]).mix
        if mix["kind"] not in ("open_loop", "closed_loop"):
            continue
        a, b = (sorted((len(r["tokens"]), r["max_new"]) for r in traffic.plan(
            mix, seed, W, vocab=2)["requests"]) for seed in (1, 2 ** 31 + 99))
        check(a == b, f"{w['traffic']}: every seed gets the same sizes")
        p1 = traffic.plan(mix, 5, W, 1000)
        p2 = traffic.plan(mix, 5, W, 1000)
        check(p1 == p2, f"{w['traffic']}: a seed gives the same plan")
        if mix["kind"] == "open_loop":
            dues = sorted(r["due"] for r in p1["requests"])
            inw = [d for d in dues if mix["ramp_s"] <= d < mix["ramp_s"] + W]
            check(len(inw) == round(mix["rate_rps"] * W),
                  f"{w['traffic']}: {len(inw)} arrivals in the window")


# -- 4: metric arithmetic -----------------------------------------------------


def stats_checks():
    import numpy as np

    from harness import stats
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        xs = rng.lognormal(0, 1, n).tolist()
        for p in (50, 90, 95, 99):
            check(math.isclose(stats.percentile(xs, p),
                               float(np.percentile(xs, p)), rel_tol=1e-12),
                  f"percentile p{p} of {n} samples")
    check(stats.rate(120, 40.0) == 3.0, "rate over the window")


# -- 5: FLOP and byte functions -----------------------------------------------


def flops_checks():
    import jax
    import jax.numpy as jnp

    from configs import nin_ref
    from harness import flops, weights
    from harness.frozen import Frozen
    with open(os.path.join(BENCH, "configs", "deepseek-7b-k2.json")) as f:
        m = json.load(f)
    # projections + MLP = 2 FLOPs per weight of the layer leaves
    per_layer = sum(math.prod(shape) for path, shape, _s, _m, layer, kind
                    in weights.lm_leaves(m) if layer and kind == "w")
    want = 2 * per_layer * m["n_layers"]
    check(flops.lm_matmul_flops_per_token(m) == want,
          f"deepseek-7b-k2: {want / 1e9:.3f} GFLOP of layer matmuls per "
          f"token per member")
    head = 2 * m["vocab_size"] * m["d_model"]
    check(flops.lm_head_flops(m) == head, "LM head FLOPs per token")
    for n in (1, 5, 512):
        loop = sum(flops.decode_token_flops(m, p + 1) for p in range(n))
        pre = flops.prefill_flops(m, n)
        # prefill scores one LM-head row, decode one per token
        check(math.isclose(pre + (n - 1) * m["members"] * head, loop,
                           rel_tol=1e-12),
              f"prefill of {n} tokens == {n} decodes over live lengths")
    for kv, elem, scale in (("f32", 2, 0), ("bf16", 2, 0), ("int8", 1, 4),
                            ("fp8", 1, 4)):
        f, b = flops.paged_attn_cost(m, kv, 100, 16)
        live = 112 * 2 * m["n_kv_heads"] * (m["head_dim"] * elem + scale)
        check(b == live + 2 * m["n_heads"] * m["head_dim"] * 2
              and f == 4 * m["n_heads"] * m["head_dim"] * 100,
              f"paged attention over 100 live tokens, {kv} pages: "
              f"{b} B, {f} FLOPs")
    per_tok = flops.kv_bytes_per_token(m, "f32") * m["n_layers"] * m["members"]
    check(per_tok == 131072, "bf16 KV pool: 131072 B per token over the "
          "layers and members (the engine's own page_stats agree)")
    with open(os.path.join(BENCH, "configs", "paper-nin-k4.json")) as f:
        n = json.load(f)
    mh = Frozen(n)
    p = {k: v for k, v in weights.nin_member(n, 0, 0).items()}
    x = jnp.zeros((1, n["img"], n["img"], n["channels"]))
    cost = jax.jit(lambda p, x: nin_ref.logits(mh, p, x)).lower(
        p, x).compile().cost_analysis()
    xla = float((cost[0] if isinstance(cost, list) else cost)["flops"])
    mine = flops.nin_forward_flops(n["img"], n["channels"], n["n_classes"])
    check(0 <= (xla - mine) / mine < 0.02,
          f"NiN forward {mine / 1e6:.1f} MFLOP per image vs XLA's count "
          f"{xla / 1e6:.1f} (which adds biases, ReLUs and pools)")
    check(flops.nin_train_flops() == 3 * mine, "NiN training = 3x forward")
    with open(os.path.join(BENCH, "configs", "deepseek-7b-k2-ec.json")) as f:
        e = json.load(f)
    T = e["seq_len"]
    # a causal sequence: position p attends over p + 1 positions
    fwd = sum(flops.lm_matmul_flops_per_token(e) + flops.lm_head_flops(e)
              + flops.lm_attn_flops(e, p + 1) for p in range(T)) / T
    check(math.isclose(flops.lm_train_flops_per_token(e, T), 3 * fwd,
                       rel_tol=1e-12),
          f"deepseek-7b-k2-ec: {3 * fwd / 1e9:.3f} GFLOP to train a token "
          f"of a {T}-token sequence, 3x the forward summed over positions")


# -- 6: the trace reduction ---------------------------------------------------


def record_trace(path: str):
    """Record the small CPU trace the reduction is checked on (once)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from harness import xtrace
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    g(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(3):
            f(x).block_until_ready()
            g(x).block_until_ready()
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        shutil.copy(xtrace.latest_xplane(d), path)


def trace_checks():
    from harness import xtrace
    Op = xtrace.Op

    def op(name, s, d, dev="d0", mod="m"):
        return Op(name, mod, float(s), float(d), dev, "", 0, 0)

    ops = [op("a", 0, 10), op("b", 5, 10), op("c", 30, 5),
           op("a", 40, 10), op("x", 0, 50, dev="d1")]
    check(xtrace.union([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)],
          "union of intervals")
    busy = xtrace.busy_ns(ops, 0, 60)
    check(busy == {"d0": 30.0, "d1": 50.0}, "busy per device")
    check(xtrace.kernel_ns(ops, lambda o: o.name == "a") == 20,
          "kernel time by name")
    host = [xtrace.HostEv("tick", 14, 20), xtrace.HostEv("sleep", 50, 30)]
    gaps = xtrace.idle_gaps(ops, host, 0, 60, device="d0")
    want = [("tick", 15e-9), ("sleep", 10e-9), ("no host event", 5e-9)]
    check([g[0] for g in gaps] == [w[0] for w in want] and all(
        math.isclose(g[1], w[1]) for g, w in zip(gaps, want)),
        "idle gaps, longest first, named by the host event overlapping most")
    top = xtrace.top_ops(ops)[0]
    check(top[0] == "m/x" and math.isclose(top[1], 50e-9),
          "top ops by total time")

    if not os.path.isfile(TRACE):
        record_trace(TRACE)
    s = xtrace.summarize(TRACE, cpu=True)
    mods = {o.module for o in s["ops"]}
    check(any("lambda" in m for m in mods),
          f"recorded CPU trace: ops of the jitted programs ({len(s['ops'])})")
    busy = s["busy_s"]
    # the same union computed by sweeping sorted endpoints
    ev = sorted([(o.start, 1) for o in s["ops"]]
                + [(o.start + o.dur, -1) for o in s["ops"]])
    depth, last, tot = 0, None, 0.0
    for t, d in ev:
        if depth > 0:
            tot += t - last
        depth += d
        last = t
    check(math.isclose(busy, tot * 1e-9, rel_tol=1e-9)
          and 0 < busy <= s["window_s"],
          f"busy {busy * 1e3:.3f} ms of a {s['window_s'] * 1e3:.3f} ms "
          f"window, by two methods")
    gap_sum = sum(g for _, g in xtrace.idle_gaps(
        s["ops"], s["host"], s["t0"], s["t1"], n=10 ** 9))
    check(math.isclose(gap_sum + busy, s["window_s"], rel_tol=1e-6),
          "idle gaps and busy time fill the window")


def main() -> int:
    spec = spec_checks()
    traffic_checks(spec)
    stats_checks()
    flops_checks()
    trace_checks()
    print("selfcheck passed", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
