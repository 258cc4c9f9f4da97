"""The EC-round runner end to end on the CPU at a tiny size: a sound run
is correct, and each fault a training cell on one chip can have makes it
incorrect (one chip has no exchange between chips to leave out)."""
import pytest

from conftest import TINY_NIN, ctx
from harness import ec, faults

SEED = 2 ** 31 + 777
MIX = {"kind": "ec_rounds", "warm_rounds": 2, "test_images": 16}


def run(fault=""):
    res = ec.run(ctx(TINY_NIN, MIX, "nin-k4.ec", SEED, 1.0), fault)
    return res, ec.passed(res["checks"])


def test_sound_run_is_correct():
    res, ok = run()
    assert res["attempted"] >= 1
    assert ok, res["checks"]


@pytest.mark.parametrize("fault", faults.EC_FAULTS)
def test_fault_is_incorrect(fault):
    res, ok = run(fault)
    assert not ok, res["checks"]
