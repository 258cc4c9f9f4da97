"""The EC trainer's own names in a trace: its programs' runs, and the
host spans that divide its round into phases.

The trainer (`src/repro/runtime/trainer.py`) names its programs
(`jit_ec_local_step`, `jit_ec_distill_step`, `jit_ec_relabel`, ...) and
writes host spans with `jax.profiler.TraceAnnotation` into the
profiler's own trace, which places device events on the host's clock
(on a v5e the two agree to within about a millisecond, so a shorter gap
may be set against the neighbouring span):

  ec.sample, ec.step                    each step of the round
  ec.loss_readback, ec.relabel,         the round's end
  ec.ma, ec.checkpoint
  ec.trace.<program>                    one per (re)trace of a program

The phase spans do not nest in one another; JAX's own host events (a
`PjitFunction(...)` inside `ec.step`) and the `ec.trace.*` spans do nest
inside them, and are no phase.  Every function here returns None where
the names it looks for are absent, as in a trace of a program that
predates them.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from harness import xtrace

STEP_PHASES = ("ec.sample", "ec.step")
ROUND_END_PHASES = ("ec.loss_readback", "ec.relabel", "ec.ma",
                    "ec.checkpoint")
PHASES = STEP_PHASES + ROUND_END_PHASES
TRACE_PREFIX = "ec.trace."


def run_ns(ops: List[xtrace.Op]) -> Dict[str, List[float]]:
    """Per module: the device time of each of its runs, the union of
    that run's op intervals (ops of one run may overlap, as a `while`
    op holds its body's ops)."""
    per: Dict[tuple, list] = {}
    for o in ops:
        per.setdefault((o.module, o.program, o.run), []).append(
            (o.start, o.start + o.dur))
    out: Dict[str, List[float]] = {}
    for (mod, _, _), iv in per.items():
        out.setdefault(mod, []).append(
            sum(e - s for s, e in xtrace.union(iv)))
    return out


def _runs(trace: dict) -> Dict[str, List[float]]:
    if "ec_run_ns" not in trace:
        trace["ec_run_ns"] = run_ns(trace["ops"])
    return trace["ec_run_ns"]


def mean_run_ms(trace: dict, module: str) -> Optional[float]:
    """Mean device time of a run of `module`, in ms."""
    runs = _runs(trace).get(module)
    if not runs:
        return None
    return sum(runs) / len(runs) * 1e-6


def busy_share(trace: dict, module: str) -> Optional[float]:
    """% of the device's busy time spent in runs of `module`."""
    runs = _runs(trace).get(module)
    if not runs:
        return None
    return 100.0 * sum(runs) * 1e-9 / trace["busy_s"]


def gaps(ops: List[xtrace.Op], t0: float, t1: float) -> List[tuple]:
    """Every gap in [t0, t1] with no op on the first device, in time
    order."""
    devs = sorted({o.device for o in ops})
    if not devs:
        return [(t0, t1)]
    dev = devs[0]
    busy = xtrace.union([(max(o.start, t0), min(o.start + o.dur, t1))
                         for o in ops if o.device == dev
                         and o.start + o.dur > t0 and o.start < t1])
    out, prev = [], t0
    for s, e in busy:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    if t1 > prev:
        out.append((prev, t1))
    return out


def attribute(gap_list: List[tuple], host: List[xtrace.HostEv]
              ) -> Dict[str, float]:
    """Idle ns per phase: each gap goes whole to the phase span that
    overlaps it most; a gap no phase span overlaps goes nowhere.  One
    sweep over gaps and spans in time order."""
    spans = sorted((h for h in host if h.name in PHASES),
                   key=lambda h: h.start)
    out = {n: 0.0 for n in PHASES}
    active: List[xtrace.HostEv] = []
    j = 0
    for s, e in gap_list:
        while j < len(spans) and spans[j].start < e:
            active.append(spans[j])
            j += 1
        active = [h for h in active if h.start + h.dur > s]
        best, best_ov = None, 0.0
        for h in active:
            ov = min(e, h.start + h.dur) - max(s, h.start)
            if ov > best_ov:
                best, best_ov = h.name, ov
        if best is not None:
            out[best] += e - s
    return out


def idle_split(trace: dict) -> Optional[Dict[str, float]]:
    """% of the traced window the device was idle, by what the host
    was doing: `steps` (ec.sample, ec.step), `round_end` (the round-end
    phases), `rest` (no phase span), `total`.  None without phase
    spans."""
    if "ec_idle_split" in trace:
        return trace["ec_idle_split"]
    split = None
    if any(h.name in PHASES for h in trace["host"]):
        t0, t1 = trace["t0"], trace["t1"]
        g = gaps(trace["ops"], t0, t1)
        per = attribute(g, trace["host"])
        pct = 100.0 / (t1 - t0)
        total = sum(e - s for s, e in g) * pct
        steps = sum(per[n] for n in STEP_PHASES) * pct
        end = sum(per[n] for n in ROUND_END_PHASES) * pct
        split = {"steps": steps, "round_end": end,
                 "rest": total - steps - end, "total": total}
    trace["ec_idle_split"] = split
    return split


def retraces(host: List[xtrace.HostEv], t0: float, t1: float
             ) -> Optional[int]:
    """`ec.trace.*` spans that start in [t0, t1]; None where the trace
    holds no `ec.step` span (no trainer spans at all)."""
    if not any(h.name == "ec.step" for h in host):
        return None
    return sum(1 for h in host
               if h.name.startswith(TRACE_PREFIX) and t0 <= h.start <= t1)
