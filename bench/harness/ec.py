"""EC-round cells: the paper's training loop, driven through
`repro.runtime.trainer.Trainer.run_round` as `launch/train.py` drives it.

Set-up builds one Trainer with the benchmark's weights and data (made
from the seed on the device), and runs two whole rounds through
`run_round`: the first compiles the local step and the relabel, the
second the distillation step (Eqn 9, through the fused loss kernel).
Three things are recorded as they pass through the trainer's own calls:
the first three local steps of round one (from the weights the seed
makes), round one's relabel (Eqn 6; the parameters it was given, the
images it relabelled and the ensemble's distribution it produced), and
the first three distillation steps of round two (the state they were
given, their batches and lambdas).  The window then runs whole rounds
back to back and closes on `block_until_ready`.  Afterwards the plain
reference repeats each of the three and the comparison decides
`correct`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import flops, weights, xtrace


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
    images and the ensemble's distribution over them), on the host.
    `quorum`, where a fault plants one, replaces the trainer's."""

    def __init__(self, tr):
        self.tr, self.relabel = tr, tr._relabel
        self.params = self.images = self.probs = None
        self.quorum = None

    def __call__(self, quorum=None):
        import jax
        first = self.params is None
        if first:
            self.params = jax.device_get(self.tr.state["params"])
        self.relabel(self.quorum if self.quorum is not None else quorum)
        if first:
            subset, probs = jax.device_get(self.tr.pseudo_buffer)
            self.images, self.probs = subset["images"], probs

    def release(self):
        self.tr = self.relabel = None


def trainer_for(ctx, fault: str = ""):
    """-> (trainer, recorders {local, relabel, distill}); with `fault`,
    that fault of bench/harness/faults.py planted."""
    import jax

    from repro.common.types import ECConfig
    from repro.configs import registry
    from repro.models import cnn
    from repro.optim import sgd_momentum
    from repro.runtime.trainer import Trainer

    from harness import faults
    m = ctx.config
    if [list(x) for x in cnn.NIN_SPEC] != [list(x) for x in m["layers"]]:
        raise RuntimeError("the program's NiN layers differ from the "
                           "configuration's")
    mix = ctx.mix
    cfg = registry.get_config(m["arch"]).with_(vocab_size=m["n_classes"])
    train = weights.image_data(m, ctx.seed, m["per_member"])
    test = weights.image_data(m, ctx.seed, mix["test_images"], test=True)
    ec = ECConfig(tau=m["tau_steps"], lam=m["lam"], p_steps=m["p_steps"],
                  relabel_fraction=m["relabel_fraction"], label_mode="dense",
                  aggregator="ec", protocol="allgather")
    opt = sgd_momentum(m["lr"], momentum=m["momentum"])
    tr = Trainer(cfg, ec, opt, m["members"], jax.random.PRNGKey(0), train,
                 test, batch_size=m["batch"], seed=ctx.seed % 2 ** 63)
    params0 = weights.nin_params(m, ctx.seed)
    tr.state = {"params": params0, "opt": jax.vmap(opt.init)(params0)}
    recs = {"local": Recorder(tr._plain_step),
            "relabel": RelabelRecorder(tr),
            "distill": Recorder(tr._distill_step,
                                n=min(3, m["p_steps"]))}
    tr._plain_step, tr._relabel = recs["local"], recs["relabel"]
    tr._distill_step = recs["distill"]
    if fault:
        faults.plant_ec(fault, m, recs)
    return tr, recs


def run(ctx, fault: str = "") -> dict:
    import jax

    m = ctx.config
    tr, recs = trainer_for(ctx, fault)
    for _ in range(ctx.mix["warm_rounds"]):
        tr.run_round()
    jax.block_until_ready((tr.state, tr.pseudo_buffer))

    imgs_per_round = m["tau_steps"] * m["members"] * m["batch"]
    setup_s = time.time() - ctx.t_start
    if ctx.trace_dir:
        xtrace.start(ctx.trace_dir)
    t0 = time.time()
    rounds = 0
    while time.time() - t0 < ctx.seconds:
        tr.run_round()
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
        "e2e": {"ec_img_s": rounds * imgs_per_round / window},
        "ec": {"rounds": rounds, "images": rounds * imgs_per_round,
               "relabel_rows": int(recs["relabel"].images.shape[1]),
               "train_flops": rounds * imgs_per_round
               * flops.nin_train_flops(m["img"], m["channels"],
                                       m["n_classes"])},
    }
    res["checks"] = judge(ctx, recs)
    return res


# -- correctness --------------------------------------------------------------

# the numbers `readings` gives; a cell compares those its limits name
CHECKS = ("loss_rel", "grad_gap", "change_gap", "relabel_gap",
          "distill_loss_rel", "distill_grad_gap", "distill_change_gap")


def _stack(trees: list) -> dict:
    return {n: np.stack([t[n] for t in trees]) for n in trees[0]}


def _member(tree: dict, k: int) -> dict:
    import jax.numpy as jnp
    return {n: jnp.asarray(v[k]) for n, v in tree.items()}


def step_gaps(m: dict, rec: Recorder, ref: dict, low: dict = None) -> dict:
    """Program (or, with `low`, the lower-precision reference in its
    place) against the reference, over the recorded steps.

    loss_rel     worst step of |loss - ref| / |ref| (mean over members)
    grad_gap     worst leaf of | |g| - |g_ref| | / max(|g_ref|, median
                 leaf's |g_ref|), g the first step's gradient as the
                 optimizer got it (mu after step 1 - momentum * mu before)
    change_gap   the same for the parameters' change over the steps
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of both norms.
    """
    K = m["members"]
    p0 = rec.state0["params"]
    if low is None:
        got_l = np.asarray(rec.losses)
        got_g = {n: rec.mu1[n] - m["momentum"] * rec.state0["opt"]["mu"][n]
                 for n in rec.mu1}
        got_p = rec.p_last
    else:
        got_l, got_g, got_p = low["loss"], low["g"], low["p"]

    def norms(tree, sub=None):
        return {(n, k): float(np.linalg.norm(
            (tree[n][k] - (sub[n][k] if sub else 0)).ravel()))
            for n in tree for k in range(K)}

    gr, gp = norms(ref["g"]), norms(got_g)
    med_g = float(np.median(list(gr.values())))
    keep = [lk for lk, v in gr.items() if v >= 1e-3 * med_g]
    cr, cp = norms(ref["p"], p0), norms(got_p, p0)
    med_c = float(np.median([cr[lk] for lk in keep]))

    def worst(r, g, med):
        return max(abs(g[lk] - r[lk]) / max(r[lk], med) for lk in keep)

    return {"loss_rel": float(np.max(np.abs(got_l - ref["loss"])
                                     / np.abs(ref["loss"]))),
            "grad_gap": worst(gr, gp, med_g),
            "change_gap": worst(cr, cp, med_c),
            "leaves_kept": len(keep), "leaves": len(gr)}


def ref_steps(mh, rec: Recorder, start: list, pseudo=None, lams=None,
              precision: str = "") -> dict:
    """The reference's steps from `start` ((params, mu) per member), on
    the recorded batches, member by member: {loss (S,) mean over
    members, g, p}."""
    import jax
    import jax.numpy as jnp

    from configs import nin_ref
    imgs = np.stack([a[0]["images"] for a in rec.args], 1)  # (K, S, ...)
    labs = np.stack([a[0]["labels"] for a in rec.args], 1)
    loss, g, p = [], [], []
    for k in range(mh["members"]):
        lo, g1, pn = nin_ref.sgd_steps(
            *start[k], jnp.asarray(imgs[k]), jnp.asarray(labs[k]),
            None if pseudo is None else jnp.asarray(pseudo[k]),
            None if lams is None else jnp.asarray(lams, jnp.float32),
            m=mh, precision=precision)
        loss.append(np.asarray(lo))
        g.append(jax.device_get(g1))
        p.append(jax.device_get(pn))
    return {"loss": np.mean(loss, 0), "g": _stack(g), "p": _stack(p)}


def pseudo_rows(images: np.ndarray, batch_images: np.ndarray) -> np.ndarray:
    """Where each distillation image (K, S, B, ...) lies in the relabelled
    images (K, n, ...), found by its bytes: -> (K, S, B) indices, -1 for
    an image that is not there."""
    K = images.shape[0]
    out = np.full(batch_images.shape[:3], -1, np.int64)
    for k in range(K):
        at = {images[k, i].tobytes(): i for i in range(images.shape[1])}
        for s in range(batch_images.shape[1]):
            for b in range(batch_images.shape[2]):
                out[k, s, b] = at.get(batch_images[k, s, b].tobytes(), -1)
    return out


def readings(m: dict, seed: int, recs: dict, precision: str = "") -> dict:
    """The compared numbers, the program's recorded calls against the
    plain reference; with `precision` set, the reference in that
    precision stands in for the program.

    Local steps (from the weights the seed makes): loss_rel, grad_gap,
    change_gap, as `step_gaps` says.
    relabel_gap: the widest total-variation distance between the
    ensemble distribution the trainer relabelled an image with and Eqn 6
    worked out by the reference from the same members' parameters.
    Distillation steps (from the state the trainer gave them; the
    reference takes lambda from the schedule and the pseudo labels from
    its own relabel, matched to each batch image): distill_loss_rel,
    distill_grad_gap, distill_change_gap.
    """
    from configs import nin_ref
    from harness.frozen import Frozen
    mh = Frozen(m)
    K = m["members"]
    loc, rl, dis = recs["local"], recs["relabel"], recs["distill"]
    out = {}

    # local steps, from the weights the reference makes from the seed
    import jax
    start = []
    for k in range(K):
        p0 = weights.nin_member(m, seed, k)
        start.append((p0, jax.tree.map(np.zeros_like, p0)))
    ref = ref_steps(mh, loc, start)
    low = ref_steps(mh, loc, start, precision=precision) if precision \
        else None
    out.update(step_gaps(m, loc, ref, low))

    # the relabel, from the members the trainer relabelled with
    members = [_member(rl.params, k) for k in range(K)]
    ref_pr = np.asarray(nin_ref.relabel(mh, members, rl.images))
    got_pr = (np.asarray(nin_ref.relabel(mh, members, rl.images,
                                         precision=precision))
              if precision else np.asarray(rl.probs))
    out["relabel_gap"] = float(0.5 * np.abs(got_pr - ref_pr).sum(-1).max())

    # distillation steps, with the reference's own pseudo labels
    b_imgs = np.stack([a[0]["images"] for a in dis.args], 1)
    rows = pseudo_rows(rl.images, b_imgs)
    if (rows < 0).any():
        out.update(distill_loss_rel=float("inf"),
                   distill_grad_gap=float("inf"),
                   distill_change_gap=float("inf"))
        return out
    pseudo = ref_pr[np.arange(K)[:, None, None], rows]  # (K, S, B, C)
    lams = [nin_ref.lam_at(m, t) for t in range(len(dis.args))]
    start = [(_member(dis.state0["params"], k),
              _member(dis.state0["opt"]["mu"], k)) for k in range(K)]
    ref = ref_steps(mh, dis, start, pseudo, lams)
    low = (ref_steps(mh, dis, start, pseudo, lams, precision=precision)
           if precision else None)
    out.update({"distill_" + k: v
                for k, v in step_gaps(m, dis, ref, low).items()})
    return out


def judge(ctx, recs: dict) -> dict:
    r = readings(ctx.config, ctx.seed, recs)
    lim = ctx.limits
    return {k: {"value": r[k], "limit": lim[k]} for k in CHECKS if k in lim}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
