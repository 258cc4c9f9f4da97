"""The serving runner end to end on the CPU at a tiny size: a sound run
is correct, and each fault a serving cell can have makes it incorrect."""
import pytest

from conftest import TINY_CHAT, TINY_LM, ctx
from harness import faults, serve

SEED = 2 ** 31 + 12345  # more than 32 signed bits hold


def run():
    c = ctx(TINY_LM, TINY_CHAT, "ds7b-k2.chat", SEED, 3.0, min_tokens=20)
    res = serve.run(c)
    return res, serve.passed(res["checks"])


def test_sound_run_is_correct():
    res, ok = run()
    assert res["attempted"] > 0 and res["failed"] == 0
    assert ok, res["checks"]
    assert {"ttft_p90_s", "itl_p95_s", "out_tok_s"} <= set(res["e2e"])


@pytest.mark.parametrize("fault", faults.SERVE_FAULTS)
def test_fault_is_incorrect(fault):
    undo = faults.serve_fault(fault)
    try:
        res, ok = run()
    finally:
        undo()
    assert not ok, res["checks"]
