"""The EC-DNN trainer: rounds of (local SGD -> aggregate -> distill).

Algorithm 1 of the paper, generalized over aggregator:

  aggregator="ec"   local tau steps; relabel a fraction of D_k with the
                    ensemble (ring or allgather protocol); next round's
                    first p steps minimize Eqn 9 with lambda annealing to 0.
  aggregator="ma"   local tau steps; params <- mean_k params (MA-DNN).
  aggregator="sync" every step all-reduces gradients over the member axis
                    (sync-SGD reference; tau is ignored).

State is member-stacked (leading K) and the same jitted steps serve
1-device tests and the 512-chip dry-run (sharding comes from the in/out
shardings the launcher attaches, plus constrain() hints in model code).

Fault tolerance: checkpoint every round via CheckpointManager (async,
atomic, keep-N); `Trainer.resume()` restores the newest committed round.
Straggler policy: at aggregation time members listed as lagging are
excluded from the ensemble via the quorum mask (renormalized 1/(K-r));
MA mode uses the same mask for the parameter mean.

Inputs: a step's batch is drawn as indices on the host (`self.rng`) and
gathered from the member shards (or the pseudo buffer) by one program,
`jit_ec_sample`; lambda is worked out on the host (`distill.lam_host`).

Tracing: every program has a stable name (XLA modules `jit_ec_local_step`,
`jit_ec_distill_step`, `jit_ec_sync_step`, `jit_ec_ma_step`,
`jit_ec_relabel`, `jit_ec_sample`), and `run_round` divides the round
into host spans that do not nest (`jax.profiler.TraceAnnotation`,
written into the profiler's own trace and costing about a microsecond
when no profiler runs): `ec.sample` (one index draw and one
`jit_ec_sample` dispatch), `ec.step`, `ec.loss_readback`, `ec.relabel`,
`ec.ma`, `ec.checkpoint`.  Each trace of a program runs its Python body
once, under an `ec.trace.<program>` span, and counts `trace.<program>`
in `Trainer.counters`, beside `local_steps` and `distill_steps`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.common.sharding import layout_ctx
from repro.common.types import ECConfig, ModelConfig
from repro.core import aggregation as agg
from repro.core import distill
from repro.core import ensemble as ens
from repro.data import (batch_indices, gather_members, relabel_indices,
                        sample_batch)
from repro.checkpoint import CheckpointManager
from repro.optim import Optimizer
from repro.runtime import steps


@dataclasses.dataclass
class TrainerMetrics:
    round_idx: List[int] = dataclasses.field(default_factory=list)
    local_loss: List[float] = dataclasses.field(default_factory=list)
    global_loss: List[float] = dataclasses.field(default_factory=list)
    compressed_loss: List[float] = dataclasses.field(default_factory=list)
    local_err: List[float] = dataclasses.field(default_factory=list)
    global_err: List[float] = dataclasses.field(default_factory=list)
    compressed_err: List[float] = dataclasses.field(default_factory=list)


class Trainer:
    def __init__(self, cfg: ModelConfig, ec: ECConfig, opt: Optimizer,
                 n_members: int, key, train_shards: dict, test_set: dict,
                 batch_size: int, mesh=None, ckpt_dir: Optional[str] = None,
                 seed: int = 0, grad_accum: int = 1):
        self.cfg, self.ec, self.opt = cfg, ec, opt
        self.K = n_members
        self.mesh = mesh
        self.shards = train_shards
        self.test = test_set
        self.batch = batch_size
        self.grad_accum = grad_accum
        self.rng = np.random.default_rng(seed)
        self.metrics = TrainerMetrics()
        self.ckpt = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None
        self.pseudo_buffer = None  # (subset_batch, pseudo_targets)
        self.round = 0
        # steps dispatched, and traces of each program ("trace.<name>")
        self.counters: Dict[str, int] = {"local_steps": 0,
                                         "distill_steps": 0}

        keys = jax.random.split(key, self.K)
        params = jax.vmap(lambda k: models.init(k, cfg))(keys)
        opt_state = jax.vmap(opt.init)(params)
        self.state = {"params": params, "opt": opt_state}

        self._build_steps()

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------

    def _logits(self, params, batch):
        return steps.make_logits_fn(self.cfg)(params, batch)

    def _member_loss(self, params, batch, pseudo, lam):
        return steps.make_member_loss(self.cfg)(params, batch, pseudo, lam)

    def _traced(self, program: str):
        """Count one trace of `program` and span it in the profiler's
        trace.  Called from a jitted function's Python body, so it runs
        once per (re)trace, never per call."""
        key = "trace." + program
        self.counters[key] = self.counters.get(key, 0) + 1
        return jax.profiler.TraceAnnotation("ec.trace." + program)

    def _build_steps(self):
        opt = self.opt
        plain = steps.make_local_step(self.cfg, opt,
                                      grad_accum=self.grad_accum)
        syncs = steps.make_local_step(self.cfg, opt,
                                      grad_accum=self.grad_accum, sync=True)

        def ec_local_step(s, b):
            with self._traced("ec_local_step"):
                return plain(s, b, None, 0.0)

        def ec_sync_step(s, b):
            with self._traced("ec_sync_step"):
                return syncs(s, b, None, 0.0)

        def ec_distill_step(s, b, ps, lam):
            with self._traced("ec_distill_step"):
                return plain(s, b, ps, lam)

        def ec_ma_step(s, q):
            with self._traced("ec_ma_step"):
                return {"params": agg.ma_aggregate(s["params"], q),
                        "opt": s["opt"]}

        self._plain_step = jax.jit(ec_local_step, donate_argnums=(0,))
        self._sync_step = jax.jit(ec_sync_step, donate_argnums=(0,))
        self._distill_step = jax.jit(ec_distill_step, donate_argnums=(0,))
        self._ma_step = jax.jit(ec_ma_step)

        def ec_sample(tree, idx):
            with self._traced("ec_sample"):
                return gather_members(tree, idx)

        # the shards and the pseudo buffer are arguments: closed over,
        # they would be baked into the program as constants
        self._sample = jax.jit(ec_sample)

        def eval_members(params, batch):
            with layout_ctx(batch=()):
                logits = jax.vmap(lambda p: self._logits(p, batch))(params)
            member_nll = ens.mean_member_nll(logits, batch["labels"])
            ens_nll = ens.ensemble_nll(logits, batch["labels"])
            preds = logits.argmax(-1)
            member_err = (preds != batch["labels"][None]).mean()
            ens_pred = ens.ensemble_probs(logits).argmax(-1)
            ens_err = (ens_pred != batch["labels"]).mean()
            return member_nll, ens_nll, member_err, ens_err

        self._eval = jax.jit(eval_members)

        def single_eval(params, batch):
            logits = self._logits(params, batch)
            nll = distill.true_ce(logits, batch["labels"])
            err = (logits.argmax(-1) != batch["labels"]).mean()
            return nll, err

        self._single_eval = jax.jit(single_eval)

    # ------------------------------------------------------------------
    # aggregation
    # ------------------------------------------------------------------

    def _relabel_program(self, quorum=None):
        """The jitted Eqn-6 relabel of the allgather protocol, built
        afresh on every call."""
        def ec_relabel(p, b):
            with self._traced("ec_relabel"):
                return agg.allgather_relabel(p, b, self._logits, self.ec,
                                             quorum=quorum)

        return jax.jit(ec_relabel)

    def _relabel(self, quorum=None):
        """Relabel relabel_fraction of each member's shard -> pseudo buffer."""
        with jax.profiler.TraceAnnotation("ec.relabel",
                                          round=self.round) as span:
            subset = self._sample(self.shards, relabel_indices(
                self.rng, self.shards, self.ec.relabel_fraction))
            span.set_metadata(images=int(np.prod(
                jax.tree.leaves(subset)[0].shape[:2])))
            if self.mesh is not None and self.ec.protocol == "ring" \
                    and self.K > 1:
                pseudo = agg.ring_relabel(self.mesh, self.state["params"],
                                          subset, self._logits, self.ec,
                                          axis=self.ec_axis(), quorum=quorum)
            else:
                pseudo = self._relabel_program(quorum)(
                    self.state["params"], subset)
            self.pseudo_buffer = (subset, pseudo)

    def ec_axis(self) -> str:
        return "data"

    # ------------------------------------------------------------------
    # round loop
    # ------------------------------------------------------------------

    def run_round(self, straggler_mask: Optional[np.ndarray] = None):
        """One full round: tau local steps (first p mixed if a pseudo
        buffer exists), then aggregation per the configured method."""
        ec = self.ec
        r = self.round
        for t in range(ec.tau):
            if ec.aggregator == "ec" and self.pseudo_buffer is not None \
                    and t < ec.p_steps:
                lam = distill.lam_host(t, ec.lam, ec.p_steps)
                with jax.profiler.TraceAnnotation("ec.sample", round=r, t=t):
                    batch, pseudo = self._sample_pseudo_batch()
                with jax.profiler.TraceAnnotation("ec.step", round=r, t=t,
                                                  kind="distill"):
                    self.state, loss = self._distill_step(
                        self.state, batch, pseudo, lam)
                self.counters["distill_steps"] += 1
            else:
                with jax.profiler.TraceAnnotation("ec.sample", round=r, t=t):
                    batch = self._sample(self.shards, batch_indices(
                        self.rng, self.shards, self.batch))
                sync = ec.aggregator == "sync"
                step = self._sync_step if sync else self._plain_step
                with jax.profiler.TraceAnnotation(
                        "ec.step", round=r, t=t,
                        kind="sync" if sync else "local"):
                    self.state, loss = step(self.state, batch)
                self.counters["local_steps"] += 1

        quorum = None
        if straggler_mask is not None:
            quorum = jnp.asarray(straggler_mask, jnp.float32)
        if ec.aggregator == "ec":
            self._relabel(quorum)
        elif ec.aggregator == "ma":
            with jax.profiler.TraceAnnotation("ec.ma", round=r):
                self.state = self._ma_step(self.state, quorum)
        self.round += 1
        if self.ckpt is not None:
            with jax.profiler.TraceAnnotation("ec.checkpoint", round=r):
                self.ckpt.save(self.round, self.state)
        with jax.profiler.TraceAnnotation("ec.loss_readback", round=r):
            loss = float(loss)
        return loss

    def _sample_pseudo_batch(self):
        """-> (batch, pseudo targets) at the same rows, in one gather
        (a `TopM` target is a pytree like the batch)."""
        idx = batch_indices(self.rng, self.pseudo_buffer[0], self.batch)
        return self._sample(self.pseudo_buffer, idx)

    # ------------------------------------------------------------------
    # evaluation / reporting (paper Figures 1-3, Table 1)
    # ------------------------------------------------------------------

    def evaluate(self, record: bool = True) -> Dict[str, float]:
        test_b = jax.tree.map(lambda a: a[:256], self.test)
        m_nll, e_nll, m_err, e_err = self._eval(self.state["params"],
                                                test_b)
        out = {"local_loss": float(m_nll), "global_loss": float(e_nll),
               "local_err": float(m_err), "global_err": float(e_err)}
        if self.ec.aggregator == "ma":
            avg = agg.ma_aggregate(self.state["params"])
            one = jax.tree.map(lambda x: x[0], avg)
            nll, err = self._single_eval(one, test_b)
            out["global_loss"], out["global_err"] = float(nll), float(err)
        if record:
            self.metrics.round_idx.append(self.round)
            self.metrics.local_loss.append(out["local_loss"])
            self.metrics.global_loss.append(out["global_loss"])
            self.metrics.local_err.append(out["local_err"])
            self.metrics.global_err.append(out["global_err"])
        return out

    def evaluate_compressed(self) -> Dict[str, float]:
        """After distill steps, members ARE the compressed models."""
        test_b = jax.tree.map(lambda a: a[:256], self.test)
        m_nll, _, m_err, _ = self._eval(self.state["params"], test_b)
        out = {"compressed_loss": float(m_nll),
               "compressed_err": float(m_err)}
        self.metrics.compressed_loss.append(out["compressed_loss"])
        self.metrics.compressed_err.append(out["compressed_err"])
        return out

    def best_member(self):
        """EC-DNN_L: the member with smallest training loss."""
        batch = sample_batch(self.rng, self.shards, min(self.batch, 64))
        with layout_ctx(batch=()):
            losses = jax.vmap(
                lambda p, b: self._member_loss(p, b, None, 0.0))(
                self.state["params"], batch)
        k = int(jnp.argmin(losses))
        return jax.tree.map(lambda x: x[k], self.state["params"]), k

    # ------------------------------------------------------------------
    # fault tolerance / elasticity
    # ------------------------------------------------------------------

    def save(self):
        if self.ckpt is not None:
            self.ckpt.save(self.round, self.state)
            self.ckpt.wait()

    def resume(self) -> bool:
        if self.ckpt is None:
            return False
        latest = self.ckpt.latest()
        if latest is None:
            return False
        self.state = self.ckpt.restore(self.state, latest)
        self.round = latest
        self.pseudo_buffer = None  # relabel happens at next round boundary
        return True

    def reshard(self, k_new: int, key=None):
        from repro.checkpoint import reshard_members
        self.state = reshard_members(self.state, k_new, perturb=1e-3,
                                     key=key)
        self.shards = reshard_members(self.shards, k_new)
        self.K = k_new
        self.pseudo_buffer = None
        self._build_steps()
