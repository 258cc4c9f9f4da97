"""Serving cells: the ensemble behind the HTTP frontend, driven by the
load generator in a child process.

Set-up builds the objects `serve.py --http` builds (EnsembleEngine ->
Replica/Router -> serve_frontend) with weights the benchmark makes from
the seed, compiles every program the traffic uses (one admission
update, one prefill chunk, one decode step), and sends one request over
HTTP.  The window is the mix's: arrivals after a ramp that fills the
server to steady state.  After the window the server is shut down, its
arrays freed, and the plain reference judges a sample of the finished
requests drawn from the seed, the longest among them.
"""
from __future__ import annotations

import gc
import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np

from harness import flops, stats, traffic, xtrace

CALIBRATE = "serving"

LOADGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "loadgen.py")


def program_config(m: dict):
    """The program's ModelConfig for a `dense_lm` configuration: the
    named architecture with every size taken from the benchmark's file."""
    from repro.common.types import AttnConfig, FFNConfig
    from repro.configs import registry
    base = registry.get_config(m["arch"])
    return base.with_(
        n_layers=m["n_layers"], d_model=m["d_model"],
        vocab_size=m["vocab_size"], norm_eps=m["norm_eps"],
        dtype=m["dtype"],
        attn=AttnConfig(kind="gqa", n_heads=m["n_heads"],
                        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
                        rope_theta=m["rope_theta"]),
        ffn=FFNConfig(d_ff=m["d_ff"], mlp_type="swiglu"))


def model_numbers(cfg: dict) -> dict:
    keys = ("arch", "members", "n_layers", "d_model", "n_heads",
            "n_kv_heads", "head_dim", "d_ff", "vocab_size", "rope_theta",
            "norm_eps", "dtype")
    return {k: cfg[k] for k in keys}


class Window:
    """What the parent sees of the window: the child's open/closed
    marks, the engine's step counter at each, and the profiler."""

    def __init__(self, engine, trace_dir):
        self.engine = engine
        self.trace_dir = trace_dir
        self.marks = {}
        self.steps = {}
        self.result = None
        self.done = threading.Event()

    def on_line(self, line: str):
        if line.startswith("open "):
            self.marks["open"] = time.time()
            self.steps["open"] = self.engine.steps_run
            if self.trace_dir:
                xtrace.start(self.trace_dir)
        elif line.startswith("closed "):
            self.marks["closed"] = time.time()
            self.steps["closed"] = self.engine.steps_run
            if self.trace_dir:
                xtrace.stop()
        elif line.startswith("result "):
            self.result = json.loads(line[len("result "):])
            self.done.set()


class Served:
    """The engine behind the HTTP frontend, built and warmed once; each
    `window` drives one load through it from a fresh child process."""

    def __init__(self, ctx):
        import jax

        from repro.serving import EnsembleEngine
        from repro.serving.frontend import Replica, Router, serve_frontend

        self.m = m = model_numbers(ctx.config)
        self.eng_cfg = e = ctx.mix["engine"]
        max_prompt, max_out = traffic.max_lengths(ctx.mix)
        self.cfg = program_config(m)
        params = self._params(ctx.seed)
        self.engine = EnsembleEngine(
            self.cfg, params, n_slots=e["slots"], max_prompt=max_prompt,
            max_out=max_out, paged=True, page_size=e["page_size"],
            n_pages=e["n_pages"], kv_dtype=e["kv_dtype"])
        del params
        # compile the programs the traffic uses: admission, one prefill
        # chunk, the decode step (one shape each), then release the slot
        warm = np.arange(1, 65, dtype=np.int32) % m["vocab_size"]
        self.engine.generate([warm], max_new=2)
        self.engine.update_slots(release=range(self.engine.n_slots))
        jax.block_until_ready(self.engine.state)
        self.srv = serve_frontend(Router([Replica(
            "r0", self.engine, prefill_budget=e.get("prefill_budget"))]),
            port=0, verbose=False)

    def _params(self, seed: int):
        """The benchmark's weights, checked against the program's layout."""
        import jax

        from repro.models import transformer as tf

        from harness import weights
        params = weights.lm_params(self.m, seed)
        want = jax.eval_shape(jax.vmap(lambda k: tf.init(k, self.cfg)),
                              jax.random.split(jax.random.PRNGKey(0),
                                               self.m["members"]))
        if jax.tree.structure(want) != jax.tree.structure(params) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(params))):
            raise RuntimeError("the benchmark's weights do not match the "
                               "program's parameter layout")
        return params

    def reseed(self, seed: int):
        """Install another seed's weights (the old ones freed first: two
        stacks do not fit the chip beside the pool)."""
        self.engine.params = None
        gc.collect()
        self.engine.swap_params(self._params(seed))

    def window(self, mix: dict, seed: int, seconds: float,
               trace_dir=None) -> Window:
        plan = traffic.plan(mix, seed, seconds, self.m["vocab_size"])
        plan.update(url=self.srv.url,
                    first_token_wait_s=mix["first_token_wait_s"],
                    drain_s=mix["drain_s"])
        win = Window(self.engine, trace_dir)
        child = subprocess.Popen(
            [sys.executable, LOADGEN], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, bufsize=1)
        try:
            child.stdin.write(json.dumps(plan) + "\n")
            child.stdin.flush()
            if child.stdout.readline().strip() != "ready":
                raise RuntimeError("load generator did not start")

            def reader():
                for line in child.stdout:
                    win.on_line(line.strip())
                win.done.set()

            threading.Thread(target=reader, daemon=True).start()
            child.stdin.write("go\n")
            child.stdin.flush()
            wait = (plan["ramp_s"] + seconds + mix["first_token_wait_s"]
                    + mix["drain_s"] + 120)
            if not win.done.wait(wait) or win.result is None:
                raise RuntimeError("load generator gave no result")
            child.wait(timeout=60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
            win.engine = None  # the window's record must not keep it alive
        return win

    def close(self) -> dict:
        """Shut the server down, free the engine; -> its page stats."""
        self.srv.shutdown(drain=True, timeout=120)
        stats_ = self.engine.page_stats()
        del self.engine, self.srv
        gc.collect()
        return stats_


def run(ctx) -> dict:
    import jax

    srv = Served(ctx)
    try:
        win = srv.window(ctx.mix, ctx.seed, ctx.seconds, ctx.trace_dir)
    finally:
        dev = jax.devices()[0]
        peak_bytes = int((dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
        page_stats = srv.close()
    res = reduce_records(win.result, srv.m, ctx.mix, srv.eng_cfg)
    res["setup_s"] = win.marks["open"] - ctx.t_start
    res["steps_in_window"] = win.steps["closed"] - win.steps["open"]
    res["memory_peak_bytes"] = peak_bytes
    res["pool_bytes_per_token"] = page_stats.get("bytes_per_token")
    res["checks"] = dict(
        judge(srv.m, ctx.mix, ctx.limits, ctx.seed, ctx.seconds,
              win.result["records"]),
        wrong_answers={"value": res["wrong"], "limit": 0})
    return res


# -- the numbers of one run ---------------------------------------------------


def reduce_records(load: dict, m: dict, mix: dict, eng_cfg: dict) -> dict:
    """End-to-end numbers and the per-layer inputs, from every record."""
    t0, t1 = load["window"]
    W = t1 - t0
    recs = load["records"]
    if load.get("ran_dry"):
        raise RuntimeError("the closed loop used up its requests inside "
                           "the window: raise the mix's max_done_per_s")
    due_in = [r for r in recs if t0 <= r["due"] < t1]
    # failed: refused, broken off by the server, or no first token before
    # the load generator stopped waiting; wrong: the server's stream
    # disagreed with its own final answer or reported an error
    failed = [r for r in due_in if r["status"] not in ("ok", "cut", "open")
              or (r["status"] != "ok" and not r["t"])]
    wrong = [r for r in recs if r["status"] == "error"]
    # time to first token from the due time; a request that never got
    # one counts as waiting until the child stopped waiting
    give_up = t1 + mix["first_token_wait_s"]
    ttft = [(r["t"][0] if r["t"] else give_up) - r["due"] for r in due_in]
    gaps, toks_in, decode_ctx = [], 0, []
    prefill_fl, decode_fl = 0.0, 0.0
    for r in recs:
        ts = r["t"]
        for i, t in enumerate(ts):
            if not t0 <= t < t1:
                continue
            toks_in += 1
            if i > 0:
                gaps.append(t - ts[i - 1])
                ctx_len = r["prompt_len"] + i
                decode_ctx.append(ctx_len)
                decode_fl += flops.decode_token_flops(m, ctx_len)
            else:
                prefill_fl += flops.prefill_flops(m, r["prompt_len"])
    qwait = []
    for r in due_in:
        ev = {e["event"]: e["t"] for e in (r["trace"] or {}).get(
            "events", [])}
        if "admitted" in ev and "enqueued" in ev:
            qwait.append(ev["admitted"] - ev["enqueued"])
    out = {
        "attempted": len(due_in), "failed": len(failed),
        "wrong": len(wrong), "window_s": W,
        "late_max_s": load["late_max_s"], "late_mean_s": load["late_mean_s"],
        "e2e": {"out_tok_s": stats.rate(toks_in, W)},
        "serve": {"queue_wait": qwait, "decode_ctx": decode_ctx,
                  "prefill_flops": prefill_fl, "decode_flops": decode_fl,
                  "decode_tokens": len(decode_ctx), "model": m,
                  "kv_dtype": eng_cfg["kv_dtype"],
                  "page_size": eng_cfg["page_size"]},
    }
    if ttft:
        out["e2e"]["ttft_p90_s"] = stats.percentile(ttft, 90)
    if gaps:
        out["e2e"]["itl_p95_s"] = stats.percentile(gaps, 95)
    return out


# -- correctness --------------------------------------------------------------


def sample_finished(records: list, n: int, seed: int) -> list:
    """The longest finished request and n-1 others drawn from the seed."""
    done = [r for r in records if r["status"] == "ok" and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["id"]))
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_for(seed, 3)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_inputs(sample: list, prompts: dict):
    """(tokens (R, T), rows (N, 2), served (N,)) for the reference: each
    sequence is its prompt and its served tokens but the last, padded at
    the end; row (r, p) is the position whose next token was served."""
    from configs.llama_ref import Q_BLOCK
    seqs = [list(prompts[r["id"]]) + r["tokens"][:-1] for r in sample]
    # lengths padded to whole multiples of 1024: few shapes to compile
    T = 2 * Q_BLOCK * math.ceil(max(len(s) for s in seqs) / (2 * Q_BLOCK))
    tokens = np.zeros((len(seqs), T), np.int32)
    rows, served = [], []
    for i, (r, s) in enumerate(zip(sample, seqs)):
        tokens[i, :len(s)] = s
        for j, tok in enumerate(r["tokens"]):
            rows.append((i, r["prompt_len"] - 1 + j))
            served.append(tok)
    return tokens, np.asarray(rows, np.int32), np.asarray(served, np.int32)


def token_gaps(ref_lp, chosen) -> np.ndarray:
    """How far each chosen token's log-prob lies below the reference's
    best at its position."""
    ref_lp = np.asarray(ref_lp)
    best = ref_lp.max(-1)
    return best - ref_lp[np.arange(len(chosen)), chosen]


def judge(m: dict, mix: dict, lim: dict, seed: int, seconds: float,
          records: list, control: str = "") -> dict:
    """The comparison that decides `correct`: the widest gap between a
    served token's log-prob and the reference's best, over the sample.
    With `control` set, the reference in that lower precision is also
    put in the program's place, and the gap of its own first choice at
    each of the same positions is read as `control_logprob_gap`."""
    import jax.numpy as jnp

    from configs import llama_ref
    prompts = {r["id"]: r["tokens"] for r in traffic.plan(
        mix, seed, seconds, m["vocab_size"])["requests"]}
    sample = sample_finished(records, lim["check_requests"], seed)
    out = {"served_tokens_checked": {"value": 0,
                                     "limit": lim["min_tokens"]}}
    if not sample:
        return out
    tokens, rows, served = reference_inputs(sample, prompts)
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    ref = llama_ref.fused_log_probs(m, seed, tokens, rows)
    out["served_tokens_checked"]["value"] = int(len(served))
    out["max_logprob_gap"] = {"value": float(token_gaps(ref, served).max()),
                              "limit": lim["max_logprob_gap"]}
    if control:
        low = llama_ref.fused_log_probs(m, seed, tokens, rows, quant=control)
        gaps = token_gaps(ref, np.asarray(low).argmax(-1))
        out["control_logprob_gap"] = {"value": float(gaps.max()),
                                      "limit": lim["max_logprob_gap"]}
    return out


def passed(checks: dict) -> bool:
    n = checks["served_tokens_checked"]
    if checks.get("wrong_answers", {"value": 0})["value"]:
        return False
    if n["value"] < n["limit"]:
        return False
    g = checks.get("max_logprob_gap")
    return g is not None and g["value"] <= g["limit"]
