#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains, on the chip.

  python3 bench/knee.py --workload ds7b-k2.chat --rates 1 2 3 4 5 6 \\
      [--seconds 30] [--seed 1] [--out knee.jsonl]

One process, set up once; one window of the cell's open-loop mix per
rate, the rates in rising order, stopping at the first that is not
sustained.  A rate is sustained when the queue does not grow through
the window: the median time to first token of the requests due in its
last third exceeds that of its first third by at most half a second
(or half the first, if that is more), and none failed.  The knee is
the highest sustained rate; the cell's mix then offers about four
fifths of it, by hand, as PERF.md records.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def thirds(load: dict) -> tuple:
    """Median TTFT of the requests due in the window's first third, and
    of those due in its last third."""
    t0, t1 = load["window"]
    third = (t1 - t0) / 3

    def med(a, b):
        xs = [r["t"][0] - r["due"] for r in load["records"]
              if a <= r["due"] < b and r["t"]]
        return statistics.median(xs) if xs else float("inf")

    return med(t0, t0 + third), med(t1 - third, t1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from harness import cells, serve
    if jax.devices()[0].platform != "tpu":
        print("knee: JAX found no TPU", file=sys.stderr)
        return 3
    cells.use_compile_cache(ROOT)
    ctx = cells.load(ROOT, args.workload)
    ctx.seed = args.seed
    srv = serve.Served(ctx)
    knee = None
    try:
        for rate in args.rates:
            mix = dict(ctx.mix, rate_rps=rate, drain_s=0)
            win = srv.window(mix, args.seed, args.seconds)
            res = serve.reduce_records(win.result, srv.m, mix, srv.eng_cfg)
            first, last = thirds(win.result)
            ok = res["failed"] == 0 and last - first <= max(0.5,
                                                             0.5 * first)
            knee = rate if ok else knee
            rec = {"rate_rps": rate, "sustained": ok,
                   "ttft_med_first_third_s": first,
                   "ttft_med_last_third_s": last,
                   "failed": res["failed"], "attempted": res["attempted"],
                   "late_max_s": res["late_max_s"],
                   "steps": win.steps["closed"] - win.steps["open"],
                   **res["e2e"]}
            line = json.dumps(rec)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
            if not ok:
                break
    finally:
        srv.close()
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
