"""Runner of the `nin_ec` kind: the paper's EC round on its NiN
members (bench/configs/paper-nin-k4.json), through the window every EC
runner shares (bench/harness/ecround.py).  This module holds only what
belongs to NiN: the trainer with the benchmark's NiN weights and
CIFAR-shaped images, the images and FLOPs of a round, and the readings
against bench/configs/nin_ref.py.
"""
from __future__ import annotations

import sys

import numpy as np

from harness import ecround, flops, weights
from harness.ecround import passed  # noqa: F401  (the runner's verdict)

CALIBRATE = "training"
METRIC = "ec_img_s"


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
    recs = ecround.recorders(tr, m["p_steps"])
    if fault:
        faults.plant_ec(fault, tr, recs)
    return tr, recs


def round_work(m: dict) -> tuple:
    """Images of a round's local and distillation steps, and the
    forward and backward FLOPs of one."""
    return (m["tau_steps"] * m["members"] * m["batch"],
            flops.nin_train_flops(m["img"], m["channels"], m["n_classes"]))


def run(ctx, fault: str = "") -> dict:
    return ecround.run(ctx, sys.modules[__name__], fault)


# -- correctness --------------------------------------------------------------


def _stack(trees: list) -> dict:
    return {n: np.stack([t[n] for t in trees]) for n in trees[0]}


def _member(tree: dict, k: int) -> dict:
    import jax.numpy as jnp
    return {n: jnp.asarray(v[k]) for n, v in tree.items()}


def ref_steps(mh, rec: ecround.Recorder, start: list, pseudo=None, lams=None,
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
    out.update(ecround.step_gaps(m, loc, ref, low))

    # the relabel, from the members the trainer relabelled with
    members = [_member(rl.params, k) for k in range(K)]
    images = rl.subset["images"]
    ref_pr = np.asarray(nin_ref.relabel(mh, members, images))
    got_pr = (np.asarray(nin_ref.relabel(mh, members, images,
                                         precision=precision))
              if precision else np.asarray(rl.probs))
    out["relabel_gap"] = float(0.5 * np.abs(got_pr - ref_pr).sum(-1).max())

    # distillation steps, with the reference's own pseudo labels
    b_imgs = np.stack([a[0]["images"] for a in dis.args], 1)
    rows = ecround.pseudo_rows(images, b_imgs)
    if (rows < 0).any():
        out.update(ecround.NOT_FOUND)
        return out
    pseudo = ref_pr[np.arange(K)[:, None, None], rows]  # (K, S, B, C)
    lams = [nin_ref.lam_at(m, t) for t in range(len(dis.args))]
    start = [(_member(dis.state0["params"], k),
              _member(dis.state0["opt"]["mu"], k)) for k in range(K)]
    ref = ref_steps(mh, dis, start, pseudo, lams)
    low = (ref_steps(mh, dis, start, pseudo, lams, precision=precision)
           if precision else None)
    out.update({"distill_" + k: v
                for k, v in ecround.step_gaps(m, dis, ref, low).items()})
    return out
