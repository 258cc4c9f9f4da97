"""The control of each cell's comparison: the plain reference computed
in the next lower precision than the configuration's (fp8 operands;
the configurations compute with bfloat16 operands), put in the
program's place, must come out as not correct under the cell's own
limits.  bench/calibrate.py reads it on the chip at each cell's own
size, where the limits are set (PERF.md gives those readings); here it
runs at a size the CPU holds."""
from conftest import TINY_CHAT, TINY_LM, TINY_NIN, ctx
from harness import ec, serve

SEED = 2 ** 31 + 4242


def test_serving_control_fails_the_limit():
    c = ctx(TINY_LM, TINY_CHAT, "ds7b-k2.chat", SEED, 3.0, min_tokens=20)
    srv = serve.Served(c)
    try:
        win = srv.window(c.mix, SEED, 3.0)
    finally:
        srv.close()
    checks = serve.judge(srv.m, c.mix, c.limits, SEED, 3.0,
                         win.result["records"], control="fp8")
    assert serve.passed(checks), checks
    ctl = checks["control_logprob_gap"]
    assert ctl["value"] > ctl["limit"], checks


def test_training_control_fails_a_limit():
    c = ctx(TINY_NIN, {"kind": "ec_rounds", "warm_rounds": 2,
                       "test_images": 16}, "nin-k4.ec", SEED, 1.0)
    tr, recs = ec.trainer_for(c)
    for _ in range(2):
        tr.run_round()
    low = ec.readings(c.config, SEED, recs, precision="fp8")
    assert any(low[k] > c.limits[k] for k in c.limits), (low, c.limits)
