"""Runners are found by the configuration's `kind`, as a module of
bench/harness, with no table to edit: a kind that has a module loads and
runs through cells.run, an unknown kind fails as it always has, and
bench/calibrate.py reads the way it calibrates from the runner."""
import json
import os
from types import SimpleNamespace

import pytest

import calibrate
import harness
from conftest import ROOT
from harness import cells

TOY = '''
CALIBRATE = "training"


def run(ctx):
    return {"attempted": 3, "failed": 0, "setup_s": 1.5,
            "memory_peak_bytes": 7, "window_s": 2.0,
            "e2e": {"toy_rate": 42.0},
            "checks": {"gap": {"value": 0.1, "limit": 0.2}}}


def passed(checks):
    return all(c["value"] <= c["limit"] for c in checks.values())
'''


def bench_tree(tmp_path, kind: str):
    """A checkout whose one cell's configuration is of `kind`."""
    root, bench = tmp_path / "root", tmp_path / "bench"
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "toy.json").write_text(json.dumps({"kind": kind}))
    (bench / "traffic" / "mix.json").write_text(json.dumps({"kind": "x"}))
    (bench / "limits" / "toy.cell.json").write_text(
        json.dumps({"limits": {"gap": 0.2}}))
    root.mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "toy.cell", "config": "toy",
                       "traffic": "mix", "chips": 1, "why": "a test"}],
        "end_to_end": [{"name": "toy_rate", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    return str(root), str(bench)


def test_a_new_kind_runs_with_no_table_to_edit(tmp_path, monkeypatch):
    mods = tmp_path / "mods"
    mods.mkdir()
    (mods / "toy_kind.py").write_text(TOY)
    monkeypatch.setattr(harness, "__path__", list(harness.__path__)
                        + [str(mods)])
    root, bench = bench_tree(tmp_path, "toy_kind")
    monkeypatch.setattr(cells, "BENCH", bench)
    ctx = cells.load(root, "toy.cell")
    drv = cells.runner(ctx)
    assert drv.__name__ == "harness.toy_kind"
    assert drv.CALIBRATE == "training"
    devs = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    line = cells.run(ctx, devs)
    assert line["correct"] and line["attempted"] == 3
    assert line["metrics"] == {
        "toy_rate": {"value": 42.0, "unit": "1/s"},
        "setup_s": {"value": 1.5, "unit": "s"}}


@pytest.mark.parametrize("kind", ["no_such_kind", "harness", "../ec", 7,
                                  None])
def test_an_unknown_kind_fails_as_before(tmp_path, monkeypatch, kind):
    root, bench = bench_tree(tmp_path, kind)
    monkeypatch.setattr(cells, "BENCH", bench)
    with pytest.raises(ValueError, match=r"configuration toy has no known "
                                         r"kind \(" + repr(kind).replace(
                                             ".", r"\.") + r"\)"):
        cells.load(root, "toy.cell")


@pytest.mark.parametrize("cell,runner,how", [
    ("nin-k4.ec", "harness.nin_ec", "training"),
    ("ds7b-k2.ec", "harness.lm_ec", "training")])
def test_each_cell_finds_its_runner(cell, runner, how):
    drv = cells.runner(cells.load(ROOT, cell))
    assert drv.__name__ == runner and drv.CALIBRATE == how


def test_serving_kind_calibrates_as_serving():
    ctx = SimpleNamespace(config={"kind": "dense_lm_serve"})
    assert cells.runner(ctx).CALIBRATE == "serving"
    assert calibrate.serving and calibrate.training
    with open(os.path.join(ROOT, "bench", "configs",
                           "deepseek-7b-k2.json")) as f:
        assert cells.has_runner(json.load(f)["kind"])
