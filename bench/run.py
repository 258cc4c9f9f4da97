#!/usr/bin/env python3
"""Run one cell of the benchmark once.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout: the directory that holds BENCHMARK.json and
the program under src/.  The cell is an entry of BENCHMARK.json's
`workloads`; its configuration is bench/configs/<config>.json, its
traffic bench/traffic/<traffic>.json, the limits of its correctness
check bench/limits/<cell>.json, and each per-layer metric's reader
bench/metrics/<metric>.py, all found by name.

It needs as many TPU chips as the cell asks for and exits non-zero
without a result when JAX finds fewer (it never falls back to the CPU).
The last line of stdout is one JSON object: correct, attempted, failed,
metrics (the cell's end-to-end metrics, or with --trace 1 its per-layer
metrics), device, with --trace 1 breakdown, and last `checks`, each
compared number beside its limit; the same checks end stderr.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import cells  # noqa: E402


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, copy the profiler's .xplane.pb "
                         "into DIR (for reading a trace by hand)")
    args = ap.parse_args(argv)

    root = os.getcwd()
    if not os.path.isfile(os.path.join(root, "BENCHMARK.json")):
        return fail("run from the checkout's root (no BENCHMARK.json here)")
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        return fail("no program here: src/repro is missing")
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        ctx = cells.load(root, args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        return fail(str(e))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devs[0].platform!r}); "
                    f"the benchmark measures chips only", 3)
    if len(devs) < ctx.cell["chips"]:
        return fail(f"cell {ctx.cell['name']} needs {ctx.cell['chips']} "
                    f"chips, JAX found {len(devs)}", 3)
    cells.use_compile_cache(root)

    ctx.seed, ctx.seconds, ctx.t_start = args.seed, args.seconds, T_START
    trace_tmp = None
    if args.trace:
        trace_tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        ctx.trace_dir = trace_tmp.name
    try:
        line = cells.run(ctx, devs)
        if trace_tmp is not None and args.keep_trace:
            import shutil

            from harness import xtrace
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xtrace.latest_xplane(trace_tmp.name),
                        args.keep_trace)
    finally:
        if trace_tmp is not None:
            trace_tmp.cleanup()
    checks = line["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
