"""Runner of the `lm_ec` kind: the paper's EC round on the members of a
dense decoder LM (llama layout), through the window every EC runner
shares (bench/harness/ecround.py).  This module holds only what belongs
to the language model: the trainer with the benchmark's weights and
token streams, the tokens and FLOPs of a round, and the readings against
bench/configs/lm_ec_ref.py.

The relabel keeps the ensemble's top-M entries at every position
(`label_mode` "topk"), and the distillation steps train against them.
"""
from __future__ import annotations

import sys

import numpy as np

from harness import ecround, flops, weights
from harness.ecround import passed  # noqa: F401  (the runner's verdict)

CALIBRATE = "training"
METRIC = "ec_tok_s"


def trainer_for(ctx, fault: str = ""):
    """-> (trainer, recorders {local, relabel, distill}); with `fault`,
    that fault of bench/harness/faults.py planted."""
    import jax

    from repro.common.types import ECConfig
    from repro.optim import sgd_momentum
    from repro.runtime.trainer import Trainer

    from harness import faults, serve
    m, mix = ctx.config, ctx.mix
    cfg = serve.program_config(m)
    train = weights.token_data(m, ctx.seed, m["per_member"])
    test = weights.token_data(m, ctx.seed, mix["test_sequences"], test=True)
    ec = ECConfig(tau=m["tau_steps"], lam=m["lam"], p_steps=m["p_steps"],
                  relabel_fraction=m["relabel_fraction"],
                  label_mode=m["label_mode"], top_m=m["top_m"],
                  aggregator="ec", protocol="allgather")
    opt = sgd_momentum(m["lr"], momentum=m["momentum"])
    tr = Trainer(cfg, ec, opt, m["members"], jax.random.PRNGKey(0), train,
                 test, batch_size=m["batch"], seed=ctx.seed % 2 ** 63)
    tr.state = None  # the trainer's own, freed before the seed's is made
    params0 = weights.lm_params(m, ctx.seed)
    tr.state = {"params": params0, "opt": jax.vmap(opt.init)(params0)}
    recs = ecround.recorders(tr, m["p_steps"])
    if fault:
        faults.plant_ec(fault, tr, recs)
    return tr, recs


def round_work(m: dict) -> tuple:
    """Tokens of a round's local and distillation steps, and the
    forward and backward FLOPs of one."""
    return (m["tau_steps"] * m["members"] * m["batch"] * m["seq_len"],
            flops.lm_train_flops_per_token(m, m["seq_len"]))


def run(ctx, fault: str = "") -> dict:
    return ecround.run(ctx, sys.modules[__name__], fault)


# -- correctness --------------------------------------------------------------


def _name(path: tuple) -> str:
    return path[0] if path[-1] == "norm_scale" else path[-1]


def flat(m: dict, tree: dict) -> dict:
    """The program's parameter tree -> {leaf: (K, ...)}, layer leaves
    (K, n_layers, ...), named as the reference names them."""
    out = {}
    for path, _shape, _std, _mean, per_layer, _kind in weights.lm_leaves(m):
        v = tree["segments"][0]["slot_0"] if per_layer else tree
        for p in path:
            v = v[p]
        out[_name(path)] = v
    return out


def _member(m: dict, tree: dict, k: int) -> dict:
    """Member k of a program tree, on the device in float32."""
    import jax.numpy as jnp
    return {n: jnp.asarray(v[k], jnp.float32)
            for n, v in flat(m, tree).items()}


def ref_steps(m: dict, rec: ecround.Recorder, start, pseudo=None,
              lams=None, quant: str = "") -> tuple:
    """The reference's steps on the recorded batches, member by member
    from start(k) -> (weights, momentum): (losses (S,) mean over
    members, gradient norms, change norms {(leaf, member): norm})."""
    from configs import lm_ec_ref
    toks = np.stack([a[0]["tokens"] for a in rec.args], 1)  # (K, S, B, T)
    labs = np.stack([a[0]["labels"] for a in rec.args], 1)
    loss, gn, cn = [], {}, {}
    for k in range(m["members"]):
        w, mu = start(k)
        lo, g1, ch = lm_ec_ref.sgd_steps(
            m, w, mu, toks[k], labs[k],
            None if pseudo is None else (pseudo[0][k], pseudo[1][k]),
            lams, quant)
        del w, mu
        loss.append(lo)
        gn.update({(n, k): v for n, v in g1.items()})
        cn.update({(n, k): v for n, v in ch.items()})
    return np.mean(loss, 0), gn, cn


def topm_tv(va, ia, vb, ib) -> np.ndarray:
    """Total variation between two top-M distributions over the union of
    their kept ids (an id one side did not keep counts 0 there):
    (..., M) values and ids -> (...)."""
    ids = np.concatenate([ia, ib], -1)
    vals = np.concatenate([va, -vb], -1).astype(np.float64)
    order = np.argsort(ids, -1, kind="stable")
    ids = np.take_along_axis(ids, order, -1)
    vals = np.take_along_axis(vals, order, -1)
    # an id kept on both sides sits beside itself: |a| + |b| -> |a - b|
    pair = ids[..., 1:] == ids[..., :-1]
    a, b = vals[..., :-1], vals[..., 1:]
    over = np.where(pair, np.abs(a) + np.abs(b) - np.abs(a + b), 0.0)
    return 0.5 * (np.abs(vals).sum(-1) - over.sum(-1))


def readings(m: dict, seed: int, recs: dict, precision: str = "") -> dict:
    """The compared numbers, the program's recorded calls against the
    plain reference; with `precision` set, the reference in that
    precision stands in for the program.

    Local steps (from the weights the seed makes): loss_rel, grad_gap,
    change_gap, as `ecround.gaps` says.
    relabel_gap: the widest total variation, over the union of the kept
    ids, between the top-M target the trainer relabelled a position with
    and the top M of Eqn 6 worked out by the reference from the same
    members' parameters.
    Distillation steps (from the state the trainer gave them; the
    reference takes lambda from the schedule and the targets from its
    own relabel, matched to each batch sequence): distill_loss_rel,
    distill_grad_gap, distill_change_gap.
    """
    import jax
    import jax.numpy as jnp

    from configs import lm_ec_ref
    K, M = m["members"], m["top_m"]
    loc, rl, dis = recs["local"], recs["relabel"], recs["distill"]

    def fl(tree):
        return flat(m, tree)

    out = {}
    # local steps, from the weights the reference makes from the seed
    def seed_start(k):
        w = lm_ec_ref.seed_member(m, seed, k)
        return w, jax.tree.map(jnp.zeros_like, w)

    ref_l, ref_g, ref_c = ref_steps(m, loc, seed_start)
    if precision:
        got = ref_steps(m, loc, seed_start, quant=precision)
    else:
        got = ecround.program_norms(m, loc, fl)
    out.update(ecround.gaps(got[0], ref_l, ref_g, got[1], ref_c, got[2]))

    # the relabel, from the members the trainer relabelled with
    members = [_member(m, rl.params, k) for k in range(K)]
    tokens = rl.subset["tokens"]
    ref_v, ref_i = lm_ec_ref.relabel(m, members, tokens, M)
    if precision:
        got_v, got_i = lm_ec_ref.relabel(m, members, tokens, M,
                                         quant=precision)
    else:
        got_v, got_i = np.asarray(rl.probs.vals), np.asarray(rl.probs.idx)
    del members
    out["relabel_gap"] = float(topm_tv(got_v, got_i, ref_v, ref_i).max())

    # distillation steps, with the reference's own targets
    rows = ecround.pseudo_rows(tokens, np.stack(
        [a[0]["tokens"] for a in dis.args], 1))
    if (rows < 0).any():
        out.update(ecround.NOT_FOUND)
        return out
    at = (np.arange(K)[:, None, None], rows)
    pseudo = (ref_v[at], ref_i[at])  # (K, S, B, T, M) each
    lams = [lm_ec_ref.lam_at(m, t) for t in range(len(dis.args))]

    def state_start(k):
        return (_member(m, dis.state0["params"], k),
                _member(m, dis.state0["opt"]["mu"], k))

    ref_l, ref_g, ref_c = ref_steps(m, dis, state_start, pseudo, lams)
    if precision:
        got = ref_steps(m, dis, state_start, pseudo, lams, quant=precision)
    else:
        got = ecround.program_norms(m, dis, fl)
    out.update({"distill_" + k: v for k, v in ecround.gaps(
        got[0], ref_l, ref_g, got[1], ref_c, got[2]).items()})
    return out
