"""Traces of the trainer's programs in the window per round: the
`ec.trace.<program>` host spans that start in the traced window (one
per (re)trace of a program), over the rounds run in it
(bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    n = spans.retraces(run.trace["host"], run.trace["t0"], run.trace["t1"])
    rounds = run.res["ec"]["rounds"]
    return None if n is None or not rounds else n / rounds
