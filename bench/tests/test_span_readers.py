"""The readers of the EC trainer's program names and host spans
(bench/harness/spans.py and the metrics that use it): on synthetic op
and host-event lists, on a tiny EC run traced on the CPU, and on the
trace such a run recorded (bench/data/cpu_trace_ec.xplane.pb).  CPU
numbers only check the arithmetic; no device number comes from here."""
import math
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, TINY_NIN, ctx
from harness import cells, ec, spans, xtrace

Op, HostEv = xtrace.Op, xtrace.HostEv
TRACE_EC = os.path.join(BENCH, "data", "cpu_trace_ec.xplane.pb")
NEW = ("ec.local_step_ms", "ec.distill_step_ms", "ec.relabel_program_share",
       "ec.idle_in_steps", "ec.idle_at_round_end", "ec.retraces_per_round")
MODULES = ("jit_ec_local_step", "jit_ec_distill_step", "jit_ec_relabel")


def op(module, run, s, d, dev="d0"):
    return Op("fusion", module, float(s), float(d), dev, "", 7, run)


def trace_of(ops, host, t0=None, t1=None):
    t0 = min(o.start for o in ops) if t0 is None else t0
    t1 = max(o.start + o.dur for o in ops) if t1 is None else t1
    busy = xtrace.busy_ns(ops, t0, t1)
    return {"ops": ops, "host": host, "t0": t0, "t1": t1,
            "window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(busy.values()) / max(len(busy), 1) * 1e-9}


def read(name, trace, rounds=1):
    return cells.reader(name)(SimpleNamespace(
        trace=trace, res={"ec": {"rounds": rounds}}))


def test_step_time_is_the_union_of_each_run():
    # run 1: two overlapping ops (a while op and its body) cover 15 ns;
    # run 2 covers 10; another module's run is not counted
    ops = [op("jit_ec_local_step", 1, 0, 10), op("jit_ec_local_step", 1, 5, 10),
           op("jit_ec_local_step", 2, 100, 10), op("jit_gather", 3, 50, 5),
           op("jit_ec_relabel", 4, 200, 20)]
    tr = trace_of(ops, [])
    assert math.isclose(read("ec.local_step_ms", tr), 12.5e-6)
    assert read("ec.distill_step_ms", tr) is None
    # relabel: 20 of the 50 busy ns
    assert math.isclose(read("ec.relabel_program_share", tr), 40.0)


def test_gaps_go_to_the_phase_span_overlapping_most():
    ops = [op("jit_ec_local_step", 1, 0, 50), op("jit_ec_local_step", 2,
                                                 60, 35),
           op("jit_ec_relabel", 3, 210, 40), op("jit_ec_local_step", 4,
                                                300, 200),
           op("jit_ec_local_step", 5, 520, 80)]
    host = [HostEv("ec.step", 0, 100),
            HostEv("PjitFunction(ec_local_step)", 10, 80),  # nested
            HostEv("ec.trace.ec_local_step", 20, 40),       # nested
            HostEv("ec.relabel", 100, 200),
            HostEv("ec.trace.ec_relabel", 105, 190),        # nested
            HostEv("ec.sample", 510, 30)]
    tr = trace_of(ops, host, 0, 600)
    # gaps: [50,60] in ec.step; [95,210]: 5 ns under ec.step, 110 under
    # ec.relabel; [250,300] in ec.relabel; [500,520]: 10 under
    # ec.sample, 10 under no span
    per = spans.attribute(spans.gaps(ops, 0, 600), host)
    assert per["ec.step"] == 10 and per["ec.relabel"] == 115 + 50
    assert per["ec.sample"] == 20
    split = spans.idle_split(tr)
    assert math.isclose(split["steps"], 100 * 30 / 600)
    assert math.isclose(split["round_end"], 100 * 165 / 600)
    assert math.isclose(split["total"], 100 * 195 / 600)
    assert math.isclose(split["rest"], 0.0, abs_tol=1e-9)
    assert math.isclose(read("ec.idle_in_steps", tr), 5.0)
    assert read("ec.retraces_per_round", tr, rounds=2) == 1.0


def test_readers_return_none_without_the_names():
    """A trace of a program without the names: every step `jit__lambda`,
    JAX's own host events only."""
    ops = [op("jit__lambda", r, 20 * r, 15) for r in range(5)]
    host = [HostEv("PjitFunction(<lambda>)", 20 * r, 3) for r in range(5)]
    tr = trace_of(ops, host)
    assert {n: read(n, tr) for n in NEW} == dict.fromkeys(NEW)


@pytest.mark.parametrize("seed", range(6))
def test_idle_pieces_never_exceed_the_idle_time(seed):
    rng = np.random.default_rng(seed)
    ops = [op("jit_ec_local_step", i, s, d) for i, (s, d) in enumerate(
        zip(rng.uniform(0, 1e6, 300), rng.uniform(10, 4e3, 300)))]
    names = spans.PHASES + ("PjitFunction(ec_local_step)",
                            "ec.trace.ec_relabel")
    host = [HostEv(str(rng.choice(names)), s, d) for s, d in zip(
        rng.uniform(0, 1e6, 400), rng.uniform(10, 2e4, 400))]
    tr = trace_of(ops, host)
    split = spans.idle_split(tr)
    idle = 100 * (1 - tr["busy_s"] / tr["window_s"])
    assert math.isclose(split["total"], idle, rel_tol=1e-9)
    assert split["steps"] >= 0 and split["round_end"] >= 0
    assert split["steps"] + split["round_end"] <= idle * (1 + 1e-12)
    assert split["rest"] >= -1e-9


def test_traced_cpu_run_names_programs_and_counts_retraces(monkeypatch,
                                                           tmp_path):
    """A tiny EC run traced on the CPU: the trainer's three programs are
    found by name, and the `ec.trace.*` spans in the window are the
    traces `Trainer.counters` counted in it."""
    got = {}
    trainer_for, start = ec.trainer_for, xtrace.start

    def keep_trainer(c, fault=""):
        got["tr"], recs = trainer_for(c, fault)
        return got["tr"], recs

    def start_window(d):
        got["before"] = dict(got["tr"].counters)
        start(d)

    monkeypatch.setattr(ec, "trainer_for", keep_trainer)
    monkeypatch.setattr(xtrace, "start", start_window)
    c = ctx(TINY_NIN, {"kind": "ec_rounds", "warm_rounds": 2,
                       "test_images": 16}, "nin-k4.ec", 2 ** 31 + 1313, 0.2)
    c.trace_dir = str(tmp_path)
    res = ec.run(c)
    assert ec.passed(res["checks"]), res["checks"]
    path = xtrace.latest_xplane(str(tmp_path))
    if not os.path.isfile(TRACE_EC):
        shutil.copy(path, TRACE_EC)
    tr = xtrace.summarize(path, cpu=True)
    assert set(MODULES) <= {o.module for o in tr["ops"]}
    assert not any("lambda" in o.module for o in tr["ops"])
    counters, before = got["tr"].counters, got["before"]
    traced = sum(v - before.get(k, 0) for k, v in counters.items()
                 if k.startswith("trace."))
    n = sum(1 for h in tr["host"] if h.name.startswith("ec.trace."))
    assert n == traced >= res["ec"]["rounds"]  # the relabel, each round
    assert counters["trace.ec_local_step"] == 1
    assert counters["trace.ec_distill_step"] == 1
    run = SimpleNamespace(trace=tr, res=res)
    assert math.isclose(cells.reader("ec.retraces_per_round")(run),
                        traced / res["ec"]["rounds"])


def test_recorded_cpu_trace_feeds_every_reader():
    tr = xtrace.summarize(TRACE_EC, cpu=True)
    rounds = sum(1 for h in tr["host"] if h.name == "ec.loss_readback")
    assert rounds >= 1
    got = {n: read(n, tr, rounds) for n in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    split = spans.idle_split(tr)
    idle = 100 * (1 - tr["busy_s"] / tr["window_s"])
    assert split["steps"] + split["round_end"] <= idle
    assert got["ec.retraces_per_round"] >= 1.0
