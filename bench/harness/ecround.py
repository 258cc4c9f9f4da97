"""The EC window that every EC-round runner shares: the paper's training
loop, driven through `repro.runtime.trainer.Trainer.run_round` as
`launch/train.py` drives it.

A runner of an EC kind (bench/harness/<kind>.py) supplies only what
belongs to its model:

  METRIC                  the end-to-end rate its cells report
  trainer_for(ctx, fault) -> (trainer, recorders), the trainer built
                          with the benchmark's weights and data (made
                          from the seed on the device) and `recorders`
                          below wrapped round its calls
  round_work(config)      -> (samples, FLOPs per sample) of one round's
                          local and distillation steps
  readings(config, seed, recs[, precision])
                          the compared numbers, against its reference

Set-up builds the trainer and runs the mix's warm rounds: the first
compiles the local step and the relabel, the second the distillation
step.  Three things are recorded as they pass through the trainer's own
calls: the first three local steps of round one (from the weights the
seed makes), round one's relabel (Eqn 6; the parameters it was given,
the inputs it relabelled and the ensemble's distribution it produced),
and the first three distillation steps of round two (the state they
were given, their batches and lambdas).  The window then runs whole
rounds back to back and closes on `block_until_ready`.  Afterwards the
trainer is freed and the runner's plain reference judges the three.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from harness import xtrace


class Recorder:
    """Wraps one of the trainer's jitted steps, (state, batch, *rest) ->
    (state, loss).  For the first `n` calls it keeps the state before
    call 1, each call's inputs and returned loss, the momentum buffer
    after call 1 and the parameters after call n, copied to the host
    before the next call donates them.  `fault`, where tests or
    bench/calibrate.py plant one, replaces the step."""

    def __init__(self, step, n: int = 3):
        self.step, self.n = step, n
        self.state0 = None
        self.args, self.losses = [], []
        self.mu1 = self.p_last = None
        self.fault = None

    def __call__(self, state, *args):
        import jax
        if len(self.args) >= self.n:
            return self.step(state, *args)
        if not self.args:
            self.state0 = jax.device_get(state)
        self.args.append(jax.device_get(args))
        state, loss = (self.fault or self.step)(state, *args)
        self.losses.append(float(loss))
        if len(self.args) == 1:
            self.mu1 = jax.device_get(state["opt"]["mu"])
        if len(self.args) == self.n:
            self.p_last = jax.device_get(state["params"])
        return state, loss

    def release(self):
        self.step = self.fault = None


class RelabelRecorder:
    """Wraps the trainer's relabel; for its first call keeps the
    parameters it relabels with and the pseudo buffer it leaves (the
    inputs and the ensemble's distribution over them, dense or TopM),
    on the host.  `quorum`, where a fault plants one, replaces the
    trainer's."""

    def __init__(self, tr):
        self.tr, self.relabel = tr, tr._relabel
        self.params = self.subset = self.probs = None
        self.quorum = None

    def __call__(self, quorum=None):
        import jax
        first = self.params is None
        if first:
            self.params = jax.device_get(self.tr.state["params"])
        self.relabel(self.quorum if self.quorum is not None else quorum)
        if first:
            self.subset, self.probs = jax.device_get(self.tr.pseudo_buffer)

    def release(self):
        self.tr = self.relabel = None


def recorders(tr, p_steps: int) -> dict:
    """Wrap the trainer's local step, relabel and distillation step."""
    recs = {"local": Recorder(tr._plain_step),
            "relabel": RelabelRecorder(tr),
            "distill": Recorder(tr._distill_step, n=min(3, p_steps))}
    tr._plain_step, tr._relabel = recs["local"], recs["relabel"]
    tr._distill_step = recs["distill"]
    return recs


def run(ctx, kind, fault: str = "") -> dict:
    """Set-up, the timed window and the check of one run of an EC cell
    whose runner module is `kind`."""
    import jax

    tr, recs = kind.trainer_for(ctx, fault)
    losses = [tr.run_round() for _ in range(ctx.mix["warm_rounds"])]
    jax.block_until_ready((tr.state, tr.pseudo_buffer))

    samples, flops_per_sample = kind.round_work(ctx.config)
    setup_s = time.time() - ctx.t_start
    if ctx.trace_dir:
        xtrace.start(ctx.trace_dir)
    t0 = time.time()
    rounds = 0
    while time.time() - t0 < ctx.seconds:
        losses.append(tr.run_round())
        rounds += 1
    jax.block_until_ready((tr.state, tr.pseudo_buffer))
    t1 = time.time()
    if ctx.trace_dir:
        xtrace.stop()

    dev = jax.devices()[0]
    peak_bytes = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    window = t1 - t0
    for r in recs.values():
        r.release()
    del tr
    gc.collect()
    res = {
        "attempted": rounds, "failed": 0, "window_s": window,
        "setup_s": setup_s, "memory_peak_bytes": peak_bytes,
        "round_losses": [x if math.isfinite(x) else repr(x)
                         for x in losses],
        "e2e": {kind.METRIC: rounds * samples / window},
        "ec": {"rounds": rounds, "samples": rounds * samples,
               "train_flops": rounds * samples * flops_per_sample},
    }
    t2 = time.time()
    res["checks"] = judge(ctx.limits,
                          kind.readings(ctx.config, ctx.seed, recs))
    res["reference_s"] = time.time() - t2
    return res


# -- correctness --------------------------------------------------------------

# the numbers `readings` gives; a cell compares those its limits name
CHECKS = ("loss_rel", "grad_gap", "change_gap", "relabel_gap",
          "distill_loss_rel", "distill_grad_gap", "distill_change_gap")


def norms(tree: dict, K: int, sub: dict = None) -> dict:
    """{(leaf, member): float32 norm of tree[leaf][member] (less
    sub[leaf][member])} of trees {leaf: (K, ...)}, whatever the leaf's
    stored type."""
    def f32(a):
        return np.asarray(a, np.float32)

    return {(n, k): float(np.linalg.norm(
        (f32(tree[n][k]) - (f32(sub[n][k]) if sub else 0)).ravel()))
        for n in tree for k in range(K)}


def gaps(got_loss, ref_loss, gr: dict, gp: dict, cr: dict, cp: dict) -> dict:
    """Program (or the lower-precision reference in its place) against
    the reference over the recorded steps, from the losses and from the
    norms {(leaf, member): norm} of the first step's gradient (gr, gp)
    and of the parameters' change over the steps (cr, cp).

    loss_rel     worst step of |loss - ref| / |ref| (mean over members)
    grad_gap     worst leaf of | |g| - |g_ref| | / max(|g_ref|, median
                 leaf's |g_ref|), g the first step's gradient as the
                 optimizer got it (mu after step 1 - momentum * mu before)
    change_gap   the same for the parameters' change over the steps
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of both norms.
    """
    med_g = float(np.median(list(gr.values())))
    keep = [lk for lk, v in gr.items() if v >= 1e-3 * med_g]
    med_c = float(np.median([cr[lk] for lk in keep]))

    def worst(r, g, med):
        return max(abs(g[lk] - r[lk]) / max(r[lk], med) for lk in keep)

    return {"loss_rel": float(np.max(np.abs(np.asarray(got_loss)
                                            - ref_loss)
                                     / np.abs(ref_loss))),
            "grad_gap": worst(gr, gp, med_g),
            "change_gap": worst(cr, cp, med_c),
            "leaves_kept": len(keep), "leaves": len(gr)}


def program_norms(m: dict, rec: Recorder, flat=lambda tree: tree) -> tuple:
    """The program's recorded steps: (losses, norms of the first step's
    gradient, norms of the change over the steps), trees brought to
    {leaf: (K, ...)} by `flat`."""
    K = m["members"]
    p0 = flat(rec.state0["params"])
    mu0, mu1 = flat(rec.state0["opt"]["mu"]), flat(rec.mu1)
    g = {n: mu1[n] - m["momentum"] * mu0[n] for n in mu1}
    return (np.asarray(rec.losses), norms(g, K),
            norms(flat(rec.p_last), K, p0))


def step_gaps(m: dict, rec: Recorder, ref: dict, low: dict = None) -> dict:
    """`gaps` of the program (or, with `low`, the lower-precision
    reference in its place) against the reference, both given as trees
    {loss (S,), g, p}, g and p {leaf: (K, ...)}."""
    K = m["members"]
    p0 = rec.state0["params"]
    if low is None:
        got_l, gp, cp = program_norms(m, rec)
    else:
        got_l, gp, cp = low["loss"], norms(low["g"], K), norms(low["p"], K,
                                                                 p0)
    return gaps(got_l, ref["loss"], norms(ref["g"], K), gp,
                norms(ref["p"], K, p0), cp)


def pseudo_rows(inputs: np.ndarray, batch_inputs: np.ndarray) -> np.ndarray:
    """Where each distillation input (K, S, B, ...) lies in the
    relabelled inputs (K, n, ...), found by its bytes: -> (K, S, B)
    indices, -1 for an input that is not there."""
    K = inputs.shape[0]
    out = np.full(batch_inputs.shape[:3], -1, np.int64)
    for k in range(K):
        at = {inputs[k, i].tobytes(): i for i in range(inputs.shape[1])}
        for s in range(batch_inputs.shape[1]):
            for b in range(batch_inputs.shape[2]):
                out[k, s, b] = at.get(batch_inputs[k, s, b].tobytes(), -1)
    return out


NOT_FOUND = {"distill_loss_rel": math.inf, "distill_grad_gap": math.inf,
             "distill_change_gap": math.inf}


def judge(lim: dict, r: dict) -> dict:
    return {k: {"value": r[k], "limit": lim[k]} for k in CHECKS if k in lim}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
