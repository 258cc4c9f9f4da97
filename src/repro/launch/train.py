"""Training launcher: EC-DNN / MA-DNN / sync-SGD on any mesh.

On real hardware this is the entry point per host (jax.distributed
initializes from the TPU environment); on CPU it runs reduced configs for
development.  The same Trainer/steps drive both — only mesh + shardings
differ, which is the property the dry-run certifies.

  python -m repro.launch.train --arch gemma3-1b --reduced --rounds 4 \
      --aggregator ec --members 4 --ckpt /tmp/ec_ckpt --resume
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main(argv=None) -> int:
    """Run the rounds; -> 0, or 1 if any round's loss is not finite."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper_nin")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU development)")
    ap.add_argument("--aggregator", default="ec",
                    choices=["ec", "ma", "sync"])
    ap.add_argument("--protocol", default="ring",
                    choices=["ring", "allgather"])
    ap.add_argument("--label-mode", default="dense",
                    choices=["dense", "topk"])
    ap.add_argument("--members", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--tau", type=int, default=16)
    ap.add_argument("--p-steps", type=int, default=8)
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--relabel-fraction", type=float, default=0.7)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--per-member", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--straggler-drop", type=int, default=0,
                    help="simulate N lagging members dropped per round")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import use_compile_cache
    from repro.common.types import ECConfig
    from repro.configs import registry
    from repro.data import image_member_datasets, lm_member_datasets
    from repro.optim import adamw, sgd_momentum
    from repro.runtime.trainer import Trainer

    use_compile_cache()
    cfg = registry.get_config(args.arch, reduced=args.reduced)
    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)

    if cfg.family == "cnn":
        train, test = image_member_datasets(
            key, args.members, args.per_member, n_classes=cfg.vocab_size)
        opt = sgd_momentum(args.lr, momentum=0.9)
    else:
        train, test = lm_member_datasets(
            key, args.members, args.per_member, args.seq_len,
            cfg.vocab_size)
        opt = adamw(args.lr)

    ec = ECConfig(tau=args.tau, lam=args.lam, p_steps=args.p_steps,
                  relabel_fraction=args.relabel_fraction,
                  label_mode=args.label_mode, aggregator=args.aggregator,
                  protocol=args.protocol)
    tr = Trainer(cfg, ec, opt, args.members, key, train, test,
                 batch_size=args.batch, ckpt_dir=args.ckpt, seed=args.seed)
    if args.resume and tr.resume():
        print(f"resumed from round {tr.round}")

    diverged = []
    for r in range(tr.round, args.rounds):
        mask = None
        if args.straggler_drop:
            mask = np.ones(args.members)
            drop = rng.choice(args.members, args.straggler_drop,
                              replace=False)
            mask[drop] = 0.0
            print(f"round {r}: dropping stragglers {sorted(drop)}")
        before = dict(tr.counters)
        loss = tr.run_round(straggler_mask=mask)
        ev = tr.evaluate()
        d = {k: v - before.get(k, 0) for k, v in tr.counters.items()}
        traces = sum(v for k, v in d.items() if k.startswith("trace."))
        print(f"round {r:3d} | train {loss:.4f} | local nll "
              f"{ev['local_loss']:.4f} err {ev['local_err']:.4f} | "
              f"{'ens' if args.aggregator == 'ec' else 'global'} nll "
              f"{ev['global_loss']:.4f} err {ev['global_err']:.4f} | "
              f"steps {d['local_steps']} distill {d['distill_steps']} "
              f"traces {traces}")
        if not np.isfinite([loss, ev["local_loss"], ev["global_loss"]]).all():
            diverged.append(r)
    tr.save()
    if tr.ckpt:
        tr.ckpt.close()
    best, k = tr.best_member()
    print(f"final model: member {k} (EC-DNN_L rule)")
    if diverged:
        print(f"non-finite loss in round(s) {diverged}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
