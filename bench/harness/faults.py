"""Faults planted under the timed path, for the benchmark's tests and for
reading each fault's numbers on the chip (bench/calibrate.py).  A
correctness check that a planted fault does not fail is no check.

Serving (patches of the program, undone by the returned function):
  state_unchanged   the decode step returns the KV pool it was given
  token_altered     every sampled token is moved to the next id

EC training (planted in the trainer's recorded calls):
  state_unchanged     the local step returns its state as it came
  half_batch          the local step's loss is the mean over the first
                      half of the batch
  loss_altered        the loss the local step returns is 1% off
  relabel_one_member  the relabel takes one member's distribution, not
                      the ensemble's
  lam_zero            the distillation steps get lambda 0 (plain CE)
"""
from __future__ import annotations


def serve_fault(name: str):
    """Patch the program; -> a function that undoes the patch."""
    from repro.serving import engine as eng_mod
    from repro.serving import sampling
    if name == "state_unchanged":
        orig = eng_mod.EnsembleEngine._step_impl

        def step(self, params, cache, st, quorum):
            new_st, _ = orig(self, params, cache, st, quorum)
            return new_st, cache
        eng_mod.EnsembleEngine._step_impl = step
        return lambda: setattr(eng_mod.EnsembleEngine, "_step_impl", orig)
    if name == "token_altered":
        orig = sampling.sample_slots

        def sample(keys, lp, temp, topk):
            return (orig(keys, lp, temp, topk) + 1) % lp.shape[-1]
        sampling.sample_slots = sample
        return lambda: setattr(sampling, "sample_slots", orig)
    raise ValueError(f"no serving fault {name!r}")


SERVE_FAULTS = ("state_unchanged", "token_altered")
EC_FAULTS = ("state_unchanged", "half_batch", "loss_altered",
             "relabel_one_member", "lam_zero")


def plant_ec(name: str, config: dict, recs: dict):
    """Plant a fault in the trainer's recorded calls (bench/harness/ec.py
    recorders): the local step built as the trainer builds it (not
    donating) with the fault in it, the relabel's quorum, or the lambda
    the distillation step gets."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.optim import sgd_momentum
    from repro.runtime import steps
    if name == "relabel_one_member":
        one = jnp.zeros((config["members"],), jnp.float32).at[0].set(1.0)
        recs["relabel"].quorum = one
        return
    if name == "lam_zero":
        dis = recs["distill"]
        orig = dis.step
        dis.fault = lambda s, b, ps, lam: orig(s, b, ps, jnp.zeros_like(lam))
        return
    cfg = registry.get_config(config["arch"]).with_(
        vocab_size=config["n_classes"])
    opt = sgd_momentum(config["lr"], momentum=config["momentum"])
    step = steps.make_local_step(cfg, opt)
    if name == "state_unchanged":
        fn = jax.jit(lambda s, b: (s, step(s, b, None, 0.0)[1]))
    elif name == "half_batch":
        def half(b):
            n = b["labels"].shape[1] // 2
            return jax.tree.map(lambda a: a[:, :n], b)
        fn = jax.jit(lambda s, b: step(s, half(b), None, 0.0))
    elif name == "loss_altered":
        def f(s, b):
            s, loss = step(s, b, None, 0.0)
            return s, loss * 1.01
        fn = jax.jit(f)
    else:
        raise ValueError(f"no EC fault {name!r}")
    recs["local"].fault = fn
