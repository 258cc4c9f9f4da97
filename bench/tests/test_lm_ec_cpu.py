"""The `lm_ec` runner end to end on the CPU at a tiny size: a sound run
is correct, each fault a training cell on one chip can have makes it
incorrect (one chip has no exchange between chips to leave out), and the
control, the reference in fp8 in the program's place, fails a limit."""
import math

import pytest

from conftest import LM_MIX, TINY_LM_EC, ctx
from harness import faults, lm_ec

SEED = 2 ** 31 + 4711


def run(fault=""):
    res = lm_ec.run(ctx(TINY_LM_EC, LM_MIX, "ds7b-k2.ec", SEED, 1.0), fault)
    return res, lm_ec.passed(res["checks"])


def test_sound_run_is_correct():
    res, ok = run()
    assert res["attempted"] >= 1
    assert ok, res["checks"]
    assert all(math.isfinite(x) for x in res["round_losses"])
    m = TINY_LM_EC
    tokens = res["ec"]["rounds"] * m["tau_steps"] * m["members"] \
        * m["batch"] * m["seq_len"]
    assert res["ec"]["samples"] == tokens
    assert math.isclose(res["e2e"]["ec_tok_s"], tokens / res["window_s"])


@pytest.mark.parametrize("fault", faults.EC_FAULTS)
def test_fault_is_incorrect(fault):
    res, ok = run(fault)
    assert not ok, res["checks"]


def test_control_fails_a_limit():
    c = ctx(TINY_LM_EC, LM_MIX, "ds7b-k2.ec", SEED, 1.0)
    tr, recs = lm_ec.trainer_for(c)
    for _ in range(2):
        tr.run_round()
    low = lm_ec.readings(c.config, SEED, recs, precision="fp8")
    assert any(low[k] > c.limits[k] for k in c.limits), (low, c.limits)


def test_topm_total_variation():
    import numpy as np
    va = np.array([[0.5, 0.3, 0.0]])
    ia = np.array([[1, 2, 7]])
    vb = np.array([[0.4, 0.2, 0.1]])
    ib = np.array([[2, 1, 5]])
    # union {1, 2, 5, 7}: |0.5-0.2| + |0.3-0.4| + 0.1 + 0 = 0.5
    assert math.isclose(float(lm_ec.topm_tv(va, ia, vb, ib)[0]), 0.25)
