"""Traces of the trainer's programs in the window per round of the EC
round on a language model: the `ec.trace.<program>` host spans that
start in the traced window, over the rounds run in it
(bench/harness/spans.py)."""
from harness import spans


def reduce(run):
    n = spans.retraces(run.trace["host"], run.trace["t0"], run.trace["t1"])
    rounds = run.res["ec"]["rounds"]
    return None if n is None or not rounds else n / rounds
