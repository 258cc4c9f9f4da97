"""Distributed aggregation protocols for EC-DNN and the MA baseline.

The paper's aggregation step broadcasts all K models to every worker
(K x |params| bytes over InfiniBand) and evaluates the ensemble locally.
On a TPU mesh that cost model inverts: weights are huge (llama3-405b:
810 GB) while the relabel inputs are tokens (~KBs) and the pseudo-label
accumulators are top-M compressed.  So the TPU-native realization rotates
*data* around the ensemble axis instead of weights:

  ring_relabel (shard_map over the ensemble axis, manual; TP stays auto):
    each shard holds its member's params + its relabel batch + an
    accumulator.  K-1 ppermute hops move (batch, accumulator) to the next
    member; each hop the local member scores the visiting batch and merges
    its (compressed) output distribution into the accumulator.  One final
    hop returns the accumulator home.  Per-link traffic:
    K * (batch_tokens * 4B + acc_bytes)   vs   K * |params| for the naive
    broadcast — a ~10^4-10^6x reduction at LM scale (benchmarks/
    aggregation_cost.py quantifies it per arch).  XLA overlaps the
    collective-permute with the member forward pass (async collectives),
    which is the paper's "relabel concurrently with training" mapped to ICI.

  allgather_relabel (pjit, dense): every member scores every batch via an
    implicit all-gather of the (small) batches; the K x K logits then mean
    over members.  Dense-oracle used by tests and for small vocab.

  ma_aggregate: parameter mean over the member axis — one all-reduce of
    |params| bytes (the MA-DNN baseline's cost AND its failure mode).

Straggler policy: a (K,) 0/1 quorum mask; dropped members contribute
nothing and weights renormalize to 1/(K-r) (ensemble of any subset still
carries the Jensen guarantee — DESIGN §3).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.sharding import shard_map as _shard_map
from repro.common.types import ECConfig
from repro.core import compression as comp
from repro.core import ensemble as ens


# ---------------------------------------------------------------------------
# dense oracle (pjit / single-process)
# ---------------------------------------------------------------------------

def allgather_relabel(stacked_params, batches, logits_fn: Callable,
                      ec: ECConfig,
                      quorum: Optional[jax.Array] = None):
    """-> pseudo-label targets for each member's own batch.

    stacked_params: pytree with leading K; batches: pytree with leading K
    (each member's relabel inputs); logits_fn(params, batch) -> (..., V).
    Returns dense probs (K, ..., V) or TopM with leading K.
    """
    K = jax.tree.leaves(batches)[0].shape[0]

    def member_on_all(p):
        return jax.vmap(lambda b: logits_fn(p, b))(batches)  # (K, ..., V)

    all_logits = jax.vmap(member_on_all)(stacked_params)  # (K_member, K_batch, ..., V)
    probs = ens.ensemble_probs(all_logits, weights=quorum,
                               average_probs=ec.average_probs)  # (K_batch, ..., V)
    if ec.label_mode == "topk":
        return comp.from_dense(probs, ec.top_m)
    return probs


# ---------------------------------------------------------------------------
# ring protocol (shard_map over the ensemble mesh axis)
# ---------------------------------------------------------------------------

def _ring_body(local_params, local_batch, logits_fn, ec: ECConfig,
               axis: str, quorum=None, n_vocab_shards: int = 1):
    """Runs on one shard of the ensemble axis. Leading local dim = 1."""
    K = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % K) for i in range(K)]

    p1 = jax.tree.map(lambda x: x[0], local_params)
    b1 = jax.tree.map(lambda x: x[0], local_batch)

    w_me = 1.0 if quorum is None else quorum[me]

    def score(batch):
        """Member's (compressed) output distribution on a visiting batch.

        topk mode scores ONE sequence at a time (lax.map) so the dense
        (m, T, V) f32 distribution never materializes — only the member's
        own (1, T, V) logits are transiently live before the top-M prune.
        At gemma's 262k vocab this is the difference between ~48 GB and
        ~0.3 GB of live relabel state per shard.
        """
        if ec.label_mode != "topk":
            logits = logits_fn(p1, batch).astype(jnp.float32)
            return (jax.nn.softmax(logits, -1) if ec.average_probs
                    else logits) * w_me

        def one(b_seq):
            b1x = jax.tree.map(lambda x: x[None], b_seq)
            lg = logits_fn(p1, b1x).astype(jnp.float32)[0]
            out = (jax.nn.softmax(lg, -1) if ec.average_probs else lg) \
                * w_me
            # distributed top-M: per-vocab-shard top-k, merge candidates
            # (avoids all-gathering the (T, V) distribution)
            return comp.from_dense_sharded(out, ec.top_m, n_vocab_shards)

        return jax.lax.map(one, batch)

    def merge(acc, contribution):
        if ec.label_mode == "topk":
            return comp.merge(acc, contribution)
        return acc + contribution

    # hop 0: score own batch
    acc = score(b1)

    def hop(carry, _):
        batch, acc = carry
        batch = jax.tree.map(
            lambda x: jax.lax.ppermute(x, axis, perm), batch)
        acc = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), acc)
        acc = merge(acc, score(batch))
        return (batch, acc), None

    (b_out, acc), _ = jax.lax.scan(hop, (b1, acc), None, length=K - 1)
    # final hop returns the accumulator home (batch no longer needed)
    acc = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), acc)

    denom = jnp.float32(K) if quorum is None else jnp.maximum(
        quorum.sum(), 1.0)
    if ec.label_mode == "topk":
        out = comp.scale(acc, 1.0 / denom)
        out = comp.TopM(*[x[None] for x in out])  # restore leading local dim
    else:
        out = (acc / denom)[None]
    return out


def ring_relabel(mesh, stacked_params, batches, logits_fn: Callable,
                 ec: ECConfig, axis: str = "data",
                 quorum: Optional[jax.Array] = None,
                 extra_manual_axes=(), model_axis: str = "model"):
    """shard_map-launched ring relabel. Returns per-member pseudo targets
    with leading K, sharded like the inputs over `axis`."""
    n_vocab = mesh.shape.get(model_axis, 1)
    body = functools.partial(_ring_body, logits_fn=logits_fn, ec=ec,
                             axis=axis, quorum=quorum,
                             n_vocab_shards=n_vocab)
    in_specs = (P(axis), P(axis))
    if ec.label_mode == "topk":
        out_specs = comp.TopM(P(axis), P(axis), P(axis))
    else:
        out_specs = P(axis)
    manual = {axis, *extra_manual_axes}
    return _shard_map(
        lambda p, b: body(p, b), mesh, in_specs=in_specs,
        out_specs=out_specs, axis_names=manual, check_vma=False)(
            stacked_params, batches)


# ---------------------------------------------------------------------------
# MA baseline + sync-SGD baseline helpers
# ---------------------------------------------------------------------------

def ma_aggregate(stacked_params, quorum: Optional[jax.Array] = None):
    return ens.ma_average(stacked_params, weights=quorum)


def psum_gradients(grads, axis: str):
    """sync-SGD baseline: all-reduce mean of grads over the member axis."""
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)
