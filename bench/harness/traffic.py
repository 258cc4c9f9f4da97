"""Traffic generation from a mix file and a seed (numpy only).  The plan
goes to the load generator's child process as JSON.

Every seed gets the SAME work: the multiset of (prompt length, output
length) pairs and of inter-arrival gaps is drawn once from the mix's own
`work_seed`; `--seed` only permutes their order and draws the token ids.
So runs of one cell differ in arrangement, never in the amount of work.
"""
from __future__ import annotations

import math

import numpy as np

# seeds up to a little over 2**31 (and beyond) are fine for numpy
SEED_MOD = 2 ** 63


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % SEED_MOD, int(stream)])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n integer lengths from a length spec:
    {"dist": "lognormal", "median", "sigma", "min", "max"} (clipped) or
    {"dist": "uniform", "min", "max"} (inclusive)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def max_lengths(mix: dict) -> tuple:
    """(longest prompt, longest output) the mix can draw."""
    return int(mix["prompt_tokens"]["max"]), int(mix["output_tokens"]["max"])


def _gaps(n: int, span: float, rng: np.random.Generator) -> np.ndarray:
    """n exponential gaps rescaled to sum to exactly `span` seconds: a
    Poisson process conditioned on n arrivals in the span."""
    g = rng.exponential(1.0, n)
    return g * (span / g.sum())


def plan(mix: dict, seed: int, window_s: float, vocab: int) -> dict:
    """-> {"mode", "ramp_s", "window_s", "requests": [...], ...}.

    Open loop: `rate_rps` arrivals per second through a ramp (filling
    the server to steady state) and the window; each request carries
    its due time.  Closed loop: a pool of requests served `concurrency`
    at a time, each due when sent.
    """
    work = rng_for(mix["work_seed"], 0)
    order = rng_for(seed, 1)
    ids = rng_for(seed, 2)
    ramp = float(mix.get("ramp_s", 0.0))
    if mix["kind"] == "open_loop":
        rate = float(mix["rate_rps"])
        n_ramp = int(round(rate * ramp))
        n_win = int(round(rate * window_s))
        n = n_ramp + n_win
        gaps_r = _gaps(n_ramp, ramp, work) if n_ramp else np.zeros(0)
        gaps_w = _gaps(n_win, window_s, work)
        # the first arrival of each phase lands at its start, so the
        # arrivals fill [start, end) rather than (start, end]
        def dues(gaps, start):
            g = order.permutation(gaps)
            return start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        due = np.concatenate([dues(gaps_r, 0.0) if n_ramp else np.zeros(0),
                              dues(gaps_w, ramp)])
    elif mix["kind"] == "closed_loop":
        # enough requests that the pool never runs dry at the mix's
        # stated upper bound on completions per second
        n = int(mix["concurrency"]) + int(
            math.ceil(mix["max_done_per_s"] * (ramp + window_s)))
        due = np.full(n, -1.0)  # due when sent
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    plen = draw_lengths(mix["prompt_tokens"], n, work)
    olen = draw_lengths(mix["output_tokens"], n, work)
    perm = order.permutation(n)
    plen, olen = plen[perm], olen[perm]
    reqs = []
    for i in range(n):
        toks = ids.integers(0, vocab, int(plen[i]), dtype=np.int64)
        reqs.append({"id": i, "due": float(due[i]),
                     "tokens": toks.tolist(), "max_new": int(olen[i])})
    out = {"mode": mix["kind"], "ramp_s": ramp, "window_s": float(window_s),
           "requests": reqs}
    if mix["kind"] == "closed_loop":
        out["concurrency"] = int(mix["concurrency"])
    return out

