"""Reduction of a JAX profiler trace to device metrics.

`ops_from_xspace` reads the `.xplane.pb` the profiler writes and returns
every device operation (name, XLA module, start and duration in ns,
device, descriptive stats, program and run ids), plus the host events.
An op that carries no module of its own is given the module run that
holds it on the device's "XLA Modules" line.  The rest works on those
lists alone:

  busy_ns      union of the operation intervals of one device
  kernel_ns    summed durations of the operations a predicate picks
  top_ops      operations that took the most time, by "module/name"
  idle_gaps    the longest gaps between busy intervals, each named by
               the host event that overlaps it most

On a TPU the operations are the events of each `/device:TPU:<n>` plane's
"XLA Ops" line.  A CPU backend has no device plane: its operations run
on the host's XLA client threads, which carry the same `hlo_op` and
`hlo_module` stats; `cpu=True` reads those (used by the self-check on a
trace recorded on the CPU, never for a reported number).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple


class Op(NamedTuple):
    name: str
    module: str
    start: float   # ns, on the trace's clock
    dur: float     # ns
    device: str
    desc: str      # the op's long name / source stats, for matching
    program: int   # XLA program id (one compiled program)
    run: int       # run id (one execution of that program)


class HostEv(NamedTuple):
    name: str
    start: float
    dur: float


def start(trace_dir: str):
    """Start the profiler with JAX's host events (dispatches, transfers)
    and without the Python tracer, whose per-call events would slow the
    server's threads far more than the device work being measured."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop():
    import jax
    jax.profiler.stop_trace()


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


DESC_STATS = ("long_name", "tf_op", "kernel_details", "hlo_category")


def _op(ev, st: dict, device: str) -> Op:
    """An op event.  Where the event's name is the op's whole HLO text
    ("%fusion.7 = f32[...] fusion(...)"), the name is the op's own
    ("fusion.7") and the text goes into `desc`."""
    name, desc = ev.name, [str(st[k]) for k in DESC_STATS if k in st]
    if name.startswith("%") and " = " in name:
        desc.insert(0, name)
        name = name[1:name.index(" = ")]
    return Op(name, str(st.get("hlo_module", "")), float(ev.start_ns),
              float(ev.duration_ns), device, " ".join(desc),
              int(st.get("program_id", -1) or -1),
              int(st.get("run_id", -1) or -1))


MODULE_ID = re.compile(r"^(.*)\((\d+)\)$")


def _in_modules(ops: List[Op], modules: list) -> List[Op]:
    """Give each op that has no module the module run (an event of the
    device's "XLA Modules" line, "jit_f(<program id>)") that holds it:
    its name, its program id, and the run's index on the line as run id
    where the op has none."""
    if not modules:
        return ops
    modules.sort()
    starts = [m[0] for m in modules]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if o.module or i < 0 or o.start >= modules[i][1]:
            out.append(o)
            continue
        name = modules[i][2]
        prog = o.program
        m = MODULE_ID.match(name)
        if m:
            name, prog = m.group(1), (prog if prog >= 0
                                      else int(m.group(2)))
        out.append(o._replace(module=name, program=prog,
                              run=o.run if o.run >= 0 else i))
    return out


def ops_from_xspace(path: str, cpu: bool = False
                    ) -> Tuple[List[Op], List[HostEv]]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    host: List[HostEv] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not cpu:
            dev_ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_ops += [_op(ev, _stats(ev), plane.name)
                                for ev in line.events]
                elif line.name == "XLA Modules":
                    modules += [(float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns),
                                 ev.name) for ev in line.events]
            ops += _in_modules(dev_ops, modules)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                client = line.name.startswith("tf_XLAPjRtCpuClient")
                for ev in line.events:
                    if cpu and client:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            ops.append(_op(ev, st, "cpu"))
                            continue
                    if ev.duration_ns > 0 and not client:
                        host.append(HostEv(ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
    return ops, host


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: List[Op], t0: float, t1: float) -> Dict[str, float]:
    """Per device: nanoseconds inside [t0, t1] in which an op ran."""
    per: Dict[str, List[Tuple[float, float]]] = {}
    for o in ops:
        s, e = max(o.start, t0), min(o.start + o.dur, t1)
        if e > s:
            per.setdefault(o.device, []).append((s, e))
    return {d: sum(e - s for s, e in union(iv)) for d, iv in per.items()}


def kernel_ns(ops: List[Op], match) -> float:
    """Summed durations of the ops for which `match(op)` holds."""
    return sum(o.dur for o in ops if match(o))


def top_ops(ops: List[Op], n: int = 10) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = {}
    for o in ops:
        key = f"{o.module}/{o.name}"
        tot[key] = tot.get(key, 0.0) + o.dur
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v * 1e-9) for k, v in best]


def idle_gaps(ops: List[Op], host: List[HostEv], t0: float, t1: float,
              device: Optional[str] = None, n: int = 10
              ) -> List[Tuple[str, float]]:
    """The n longest gaps in [t0, t1] with no op on `device` (the first
    device when None), each named by the host event overlapping it most
    ("no host event" when none does)."""
    devs = sorted({o.device for o in ops})
    if not devs:
        return [("no device op", (t1 - t0) * 1e-9)]
    dev = device or devs[0]
    busy = union([(max(o.start, t0), min(o.start + o.dur, t1))
                  for o in ops if o.device == dev
                  and o.start + o.dur > t0 and o.start < t1])
    gaps, prev = [], t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if t1 > prev:
        gaps.append((prev, t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        best, best_ov = "no host event", 0.0
        for h in host:
            ov = min(e, h.start + h.dur) - max(s, h.start)
            if ov > best_ov:
                best, best_ov = h.name, ov
        out.append((best, (e - s) * 1e-9))
    return out


def window_of(ops: List[Op], host: List[HostEv]) -> Tuple[float, float]:
    """The traced window: from the first to the last event seen."""
    starts = [o.start for o in ops] + [h.start for h in host]
    ends = [o.start + o.dur for o in ops] + [h.start + h.dur for h in host]
    return min(starts), max(ends)


def summarize(path: str, cpu: bool = False) -> dict:
    """Everything the per-layer readers and the breakdown need."""
    ops, host = ops_from_xspace(path, cpu=cpu)
    if not ops:
        raise RuntimeError(f"no device operation in the trace {path}")
    t0, t1 = window_of(ops, host)
    busy = busy_ns(ops, t0, t1)
    n_dev = max(len(busy), 1)
    return {
        "ops": ops, "host": host, "t0": t0, "t1": t1,
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy.values()) / n_dev * 1e-9,
        "breakdown": {"device_ops": [list(x) for x in top_ops(ops)],
                      "idle_gaps": [list(x) for x in
                                    idle_gaps(ops, host, t0, t1)]},
    }
