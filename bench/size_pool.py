#!/usr/bin/env python3
"""Size a serving cell's paged KV pool to fill one chip.

  JAX_PLATFORMS=cpu python3 bench/size_pool.py --workload <cell> [--margin-gib 0.75]

Compiles the engine's decode step and prefill chunk for a described TPU
v5e (no chip is needed) at two pool sizes, reads each program's
memory_analysis (arguments + outputs - aliased + temporaries), fits the
bytes as a line in the number of pages, and prints the largest page
count whose fuller program stays under the chip's memory less the
margin, checked by one more compile.  The count goes into the traffic
file's `engine.n_pages` by hand: a run never sizes itself.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

GIB = 2 ** 30
CHIP_BYTES = 15.75 * GIB  # what XLA lets one v5e program use


def programs(ctx, n_pages: int, sharding):
    """-> {"decode": bytes, "prefill": bytes} at this pool size."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models import transformer as tf
    from repro.serving import EnsembleEngine, kv_cache

    from harness import serve, traffic
    ops.pallas_enabled = lambda: True   # compile what the chip runs
    ops._interpret = lambda: False
    m = serve.model_numbers(ctx.config)
    e = ctx.mix["engine"]
    cfg = serve.program_config(m)
    max_prompt, max_out = traffic.max_lengths(ctx.mix)
    params = jax.eval_shape(jax.vmap(lambda k: tf.init(k, cfg)),
                            jax.random.split(jax.random.PRNGKey(0),
                                             m["members"]))
    eng = EnsembleEngine(cfg, params, n_slots=e["slots"],
                         max_prompt=max_prompt, max_out=max_out, paged=True,
                         page_size=e["page_size"], n_pages=1,
                         kv_dtype=e["kv_dtype"])
    cache = jax.eval_shape(lambda: kv_cache.init_pool(
        cfg, m["members"], e["slots"], eng.max_seq,
        page_size=e["page_size"], n_pages=n_pages, kv_dtype=e["kv_dtype"]))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         eng.state)
    q = jax.ShapeDtypeStruct((m["members"],), jnp.float32)
    args = on_chip((params, cache, state, q))
    out = {}
    step = jax.jit(eng._step_impl, donate_argnums=(1, 2))
    out["decode"] = _bytes(step.lower(*args).compile())
    pre = jax.jit(eng._prefill_impl, donate_argnums=(1, 2))
    slot = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    out["prefill"] = _bytes(pre.lower(*args, slot).compile())
    return out


def _bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--margin-gib", type=float, default=0.75)
    ap.add_argument("--probe", type=int, nargs=2, default=(1024, 2048))
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import cells
    jax.config.update("jax_enable_compilation_cache", False)
    ctx = cells.load(ROOT, args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    budget = CHIP_BYTES - args.margin_gib * GIB
    a, b = args.probe
    pa, pb = programs(ctx, a, chip), programs(ctx, b, chip)
    best = None
    for name in pa:
        slope = (pb[name] - pa[name]) / (b - a)
        fit = int(a + (budget - pa[name]) // slope)
        print(f"{name}: {pa[name] / GIB:.3f} GiB at {a} pages, "
              f"{pb[name] / GIB:.3f} GiB at {b}; {slope / 2 ** 20:.4f} "
              f"MiB per page; fits {fit} pages", flush=True)
        best = fit if best is None else min(best, fit)
    got = programs(ctx, best, chip)
    for name, v in got.items():
        print(f"check at {best} pages: {name} {v / GIB:.3f} GiB of "
              f"{budget / GIB:.3f}", flush=True)
    if max(got.values()) > budget:
        print("the fit overshoots; take fewer pages", file=sys.stderr)
        return 1
    print(f"n_pages {best}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
