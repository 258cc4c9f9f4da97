"""A cell found by name, run once, and its result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it:

  bench/configs/<config>.json     sizes, source, reduced/assumed, and
                                  `kind`: the runner that runs it
  bench/traffic/<traffic>.json    the mix (lengths, rates, rounds) and
                                  the engine settings it is served with
  bench/limits/<cell>.json        the limits of the correctness check
  bench/metrics/<metric>.py       reduce(run) -> value or None
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import SimpleNamespace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the runner of each configuration kind: a module of bench/harness
RUNNERS = {"dense_lm_serve": "serve", "nin_ec": "ec"}


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str, workload: str) -> SimpleNamespace:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    config = _json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    if config.get("kind") not in RUNNERS:
        raise ValueError(f"configuration {cell['config']} has no known "
                         f"kind ({config.get('kind')!r})")
    mix = _json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(BENCH, "limits", workload + ".json"))
    return SimpleNamespace(spec=spec, cell=cell, config=config, mix=mix,
                           limits=limits["limits"], trace_dir=None,
                           seed=0, seconds=0.0, t_start=0.0)


def use_compile_cache(root: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), keeping every
    program however fast it compiled, so that only a cell's first run
    in a checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def runner(ctx):
    import importlib
    return importlib.import_module("harness." + RUNNERS[ctx.config["kind"]])


def metrics_of(spec: dict, cell: str, kind: str) -> list:
    """The metric entries this cell reports: end-to-end ("end_to_end")
    or per-layer ("per_layer"), by each entry's `workloads` key, or, for
    a per-layer metric without it, wherever the metric it moves is."""
    e2e = {m["name"]: m for m in spec["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return cell in m["workloads"]
        if kind == "per_layer":
            return reports(e2e[m["moves"]])
        return True

    return [m for m in spec[kind] if reports(m)]


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.reduce


def device_of(devs, peak_bytes: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def run(ctx, devs) -> dict:
    """Run the cell's runner, then build the result line."""
    from harness import peaks, xtrace
    drv = runner(ctx)
    res = drv.run(ctx)
    dev = device_of(devs, res["memory_peak_bytes"])
    correct = drv.passed(res["checks"])
    name = ctx.cell["name"]
    values = dict(res["e2e"], setup_s=res["setup_s"])
    metrics = {}
    breakdown = None
    if ctx.trace_dir:
        tr = xtrace.summarize(xtrace.latest_xplane(ctx.trace_dir))
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        run_view = SimpleNamespace(res=res, trace=tr, cell=ctx.cell,
                                   config=ctx.config, mix=ctx.mix,
                                   peak=peaks.peak(devs[0].device_kind))
        for m in metrics_of(ctx.spec, name, "per_layer"):
            v = reader(m["name"])(run_view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = tr["breakdown"]
    else:
        for m in metrics_of(ctx.spec, name, "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics, "device": dev,
            "run": {k: res[k] for k in ("window_s", "late_max_s",
                                        "late_mean_s", "steps_in_window",
                                        "pool_bytes_per_token")
                    if k in res}}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = res["checks"]
    return line
