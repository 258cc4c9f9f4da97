"""Faults planted under the timed path, for the benchmark's tests and for
reading each fault's numbers on the chip (bench/calibrate.py).  A
correctness check that a planted fault does not fail is no check.

Serving (patches of the program, undone by the returned function):
  state_unchanged   the decode step returns the KV pool it was given
  token_altered     every sampled token is moved to the next id

EC training (planted in the trainer's recorded calls, any EC kind):
  state_unchanged     the local step returns its state as it came
  half_batch          the local step's loss is the mean over the first
                      half of the batch (of one sequence: of its
                      positions)
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


def plant_ec(name: str, tr, recs: dict):
    """Plant a fault in the trainer's recorded calls (the recorders of
    bench/harness/ecround.py): the local step built from the trainer's
    own program configuration and optimizer, with the fault in it, the
    relabel's quorum, or the lambda the distillation step gets."""
    import jax
    import jax.numpy as jnp

    from repro.runtime import steps
    if name == "relabel_one_member":
        one = jnp.zeros((tr.K,), jnp.float32).at[0].set(1.0)
        recs["relabel"].quorum = one
        return
    if name == "lam_zero":
        dis = recs["distill"]
        orig = dis.step
        dis.fault = lambda s, b, ps, lam: orig(s, b, ps, jnp.zeros_like(lam))
        return
    step = steps.make_local_step(tr.cfg, tr.opt)
    if name == "state_unchanged":
        def f(s, b):
            return s, step(s, b, None, 0.0)[1]
    elif name == "half_batch":
        def half(b):
            n = b["labels"].shape[1]
            if n > 1:
                return jax.tree.map(lambda a: a[:, :n // 2], b)
            # one sequence a member: a token-level loss's batch is its
            # positions, so the first half of them
            t = b["labels"].shape[2] // 2
            return jax.tree.map(lambda a: a[:, :, :t], b)

        def f(s, b):
            return step(s, half(b), None, 0.0)
    elif name == "loss_altered":
        def f(s, b):
            s, loss = step(s, b, None, 0.0)
            return s, loss * 1.01
    else:
        raise ValueError(f"no EC fault {name!r}")
    # donating, as the trainer's step does: a state the size of a
    # language model's does not fit the chip twice
    recs["local"].fault = jax.jit(f, donate_argnums=(0,))
