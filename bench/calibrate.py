#!/usr/bin/env python3
"""Read the numbers a cell's correctness limits are set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 101 102 ... \\
      [--control-seeds 101 102 103] [--fault-seeds 101 102 103] \\
      [--seconds 20] [--out calib.jsonl]

One process, set up once.  For every seed it runs the cell's timed path
as a run does (a serving cell: that seed's weights and a window of its
traffic at the cell's own load; an EC cell: that seed's trainer through
its warm rounds, which hold every recorded call) and prints the program's readings; for the control
seeds also the reference in the next lower precision put in the
program's place (fp8 in both kinds: the configurations compute
with bfloat16 operands); for the fault seeds of an EC
cell also each planted fault (bench/harness/faults.py).  One JSON line
per reading.  Limits are then set by hand in bench/limits/<cell>.json,
between the largest program reading and the smallest control or fault
reading, as PERF.md records.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def emit(out, **rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def serving(ctx, args):
    from harness import serve
    srv = serve.Served(ctx)
    try:
        for i, seed in enumerate(args.seeds):
            if i:
                srv.reseed(seed)
            t0 = time.time()
            win = srv.window(ctx.mix, seed, args.seconds)
            res = serve.reduce_records(win.result, srv.m, ctx.mix,
                                       srv.eng_cfg)
            checks = serve.judge(
                srv.m, ctx.mix, ctx.limits, seed, args.seconds,
                win.result["records"],
                control="fp8" if seed in args.control_seeds else "")
            emit(args.out, seed=seed, failed=res["failed"],
                 attempted=res["attempted"], e2e=res["e2e"],
                 checks=checks, seconds=time.time() - t0)
    finally:
        srv.close()


def training(ctx, args, drv):
    import gc

    from harness import faults
    for seed in args.seeds:
        ctx.seed = seed
        runs = [""]
        if seed in args.fault_seeds:
            runs += [f for f in faults.EC_FAULTS if f != "state_unchanged"]
        for fault in runs:
            t0 = time.time()
            tr, recs = drv.trainer_for(ctx, fault)
            losses = [tr.run_round() for _ in range(ctx.mix["warm_rounds"])]
            for r in recs.values():
                r.release()
            del tr
            gc.collect()
            r = drv.readings(ctx.config, seed, recs)
            emit(args.out, seed=seed, run=fault or "program",
                 seconds=time.time() - t0, round_losses=losses, **r)
            if not fault and seed in args.control_seeds:
                r = drv.readings(ctx.config, seed, recs, precision="fp8")
                emit(args.out, seed=seed, run="control_fp8", **r)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from harness import cells
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 3
    cells.use_compile_cache(ROOT)
    ctx = cells.load(ROOT, args.workload)
    ctx.seconds, ctx.t_start, ctx.seed = args.seconds, time.time(), \
        args.seeds[0]
    drv = cells.runner(ctx)
    if drv.CALIBRATE == "serving":
        serving(ctx, args)
    else:
        training(ctx, args, drv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
