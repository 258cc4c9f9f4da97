"""Dry-run cell construction: (arch x shape x mesh) -> lowerable step.

For every cell this module builds
  - the step function (the same builders the Trainer uses — steps.py),
  - abstract inputs (jax.ShapeDtypeStruct, weak-type-correct, no
    allocation anywhere),
  - in/out shardings (NamedSharding) under the production mesh.

Step per shape kind (DESIGN §5):
  train_4k     ec_local_train_step over member-stacked state (plain-CE
               variant is the roofline row; the distill variant and the
               ring-relabel step are lowered for §Dry-run's protocol
               analysis).
  prefill_32k  single-model forward, last-token logits.
  decode_*     single-model decode_step over a seq_len KV/state cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.sharding import make_param_pspecs
from repro.common.types import (ECConfig, ModelConfig, ParallelConfig,
                                SHAPES, ShapeConfig)
from repro.configs import registry
from repro.optim import adamw
from repro.runtime import steps


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def filter_par(par: ParallelConfig, mesh) -> ParallelConfig:
    """Drop axes the active mesh doesn't have (e.g. 'pod' single-pod)."""
    names = set(mesh.axis_names)
    return dataclasses.replace(
        par,
        batch_axes=tuple(a for a in par.batch_axes if a in names),
        ensemble_axis=par.ensemble_axis if par.ensemble_axis in names
        else ("" if par.ensemble_axis else par.ensemble_axis),
        fsdp_axis=par.fsdp_axis if par.fsdp_axis in names else "",
        seq_axis=par.seq_axis if par.seq_axis in names else "")


def abstract_tree(fn, *args, **kw):
    return jax.eval_shape(fn, *args, **kw)


# ---------------------------------------------------------------------------
# per-arch member counts / batch splits
# ---------------------------------------------------------------------------

def ensemble_k(arch: str, mesh, par: ParallelConfig) -> int:
    if not par.ensemble_axis:
        return max(par.ensemble_size, 1)
    if par.ensemble_size:
        return par.ensemble_size
    return mesh.shape[par.ensemble_axis]


def _grad_accum(arch: str, shape: ShapeConfig, mesh, k: int,
                par: ParallelConfig) -> int:
    """Microbatch so each device step holds ~1-2 sequences of activations."""
    per_member = shape.global_batch // k
    if registry.size_class(arch) == "big":
        data = mesh.shape.get("data", 1)
        return max(1, per_member // data)  # -> microbatch 1/device
    pod = mesh.shape.get("pod", 1)
    # recurrent jnp paths (rwkv) carry fatter per-token state: halve the
    # microbatch for the ssm family
    target = 2 if registry.get_config(arch).family == "ssm" else 4
    return max(1, per_member // (target * pod))


# ---------------------------------------------------------------------------
# batch specs
# ---------------------------------------------------------------------------

def _lm_batch_sds(cfg: ModelConfig, k: int, b: int, t: int) -> Dict:
    batch: Dict[str, Any] = {}
    lead = (k, b, t) if k else (b, t)
    if cfg.family == "vlm":
        # frontend stub: precomputed patch/text embeddings (M-RoPE backbone)
        batch["embeds"] = sds(lead + (cfg.d_model,), jnp.bfloat16)
    else:
        batch["tokens"] = sds(lead, jnp.int32)
    if cfg.enc_dec:
        enc_lead = (k, b) if k else (b,)
        batch["enc_embeds"] = sds(
            enc_lead + (cfg.enc_max_frames, cfg.d_model), jnp.bfloat16)
    batch["labels"] = sds(lead, jnp.int32)
    return batch


def _batch_pspec(cfg: ModelConfig, par: ParallelConfig, k: int) -> Dict:
    ens = par.ensemble_axis or None
    ba = tuple(par.batch_axes) or None
    lead = (ens, ba) if k else (ba,)
    out: Dict[str, P] = {}
    if cfg.family == "vlm":
        out["embeds"] = P(*lead, None, None)
    else:
        out["tokens"] = P(*lead, None)
    if cfg.enc_dec:
        out["enc_embeds"] = P(*lead, None, None)
    out["labels"] = P(*lead, None)
    return out


# ---------------------------------------------------------------------------
# cache pspecs (decode)
# ---------------------------------------------------------------------------

def cache_pspecs(cfg: ModelConfig, cache: Any, par: ParallelConfig,
                 mesh) -> Any:
    """Name+shape-driven layout for KV/state caches.

    full-attn K/V (B,S,kv,dh): kv heads over "model" when divisible, else
    the sequence dim (seq-sharded KV decode).  MLA latents + SSM states
    shard their channel dim; batch always over the batch role axes.
    """
    ba = tuple(par.batch_axes) or None
    msize = mesh.shape[par.model_axis]

    def rule(path, leaf):
        name = ""
        for e in reversed(path):
            if isinstance(e, jax.tree_util.DictKey):
                name = str(e.key)
                break
        nd = leaf.ndim
        if nd == 0:
            return P()
        bspec = ba if (leaf.shape[0] % _axsize(mesh, ba) == 0) else None

        if name in ("k", "v"):  # (B, S, kv, dh)
            if leaf.shape[2] % msize == 0:
                return P(bspec, None, par.model_axis, None)
            if leaf.shape[1] % msize == 0:
                return P(bspec, par.model_axis, None, None)
            return P(bspec, None, None, None)
        if name in ("c_kv", "k_r"):  # (B, S, r)
            return P(bspec, par.model_axis
                     if leaf.shape[1] % msize == 0 else None, None)
        if name == "ssm":  # (B, d_inner, N)
            return P(bspec, par.model_axis, None)
        if name == "conv":  # (B, W-1, d_inner)
            return P(bspec, None, par.model_axis)
        if name == "wkv":  # (B, H, dh, dh)
            return P(bspec, par.model_axis
                     if leaf.shape[1] % msize == 0 else None, None, None)
        if name in ("shift", "cmix_shift", "enc"):  # (B, 1|S, d)
            return P(bspec, None, None)
        if name == "idx":
            return P()
        return P(*([None] * nd))

    def pad_stacked(path, leaf):
        # cache leaves under "segments" have a leading (count,) stack dim
        spec = rule(path, _drop_lead(path, leaf))
        if _is_stacked(path):
            return P(None, *spec)
        return spec

    return jax.tree_util.tree_map_with_path(pad_stacked, cache)


def _axsize(mesh, axes) -> int:
    if not axes:
        return 1
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape.get(a, 1)
    return n


def _is_stacked(path) -> bool:
    for e in path:
        if isinstance(e, jax.tree_util.DictKey) and str(e.key) == "segments":
            return True
    return False


def _drop_lead(path, leaf):
    if _is_stacked(path):
        return jax.ShapeDtypeStruct(leaf.shape[1:], leaf.dtype)
    return leaf


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeConfig
    step_name: str
    fn: Callable
    args: Tuple
    in_shardings: Tuple
    out_shardings: Any
    meta: Dict[str, Any]
    donate: Tuple[int, ...] = ()  # args donated (state / cache buffers)


def _named(mesh, pspecs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def build_train_cell(arch: str, mesh, multi_pod: bool,
                     variant: str = "plain",
                     ec: Optional[ECConfig] = None) -> Cell:
    """variant: plain | distill | relabel."""
    from repro.models import transformer as tf
    shape = SHAPES["train_4k"]
    cfg = registry.get_config(arch)
    par = filter_par(registry.parallel_policy(arch, shape, multi_pod), mesh)
    k = ensemble_k(arch, mesh, par)
    b = shape.global_batch // k
    ec = ec or ECConfig(label_mode="topk", top_m=64)
    accum = _grad_accum(arch, shape, mesh, k, par)

    params = abstract_tree(
        lambda key: jax.vmap(lambda kk: tf.init(kk, cfg))(
            jax.random.split(key, k)), jax.random.PRNGKey(0))
    # bf16 Adam moments for the big archs: optimizer state for a 405B
    # member must fit its 256-chip pod alongside params + activations
    moment_dtype = jnp.bfloat16 \
        if registry.size_class(arch) == "big" else jnp.float32
    opt = adamw(1e-4, moment_dtype=moment_dtype)
    opt_state = abstract_tree(lambda p: jax.vmap(opt.init)(p), params)
    state = {"params": params, "opt": opt_state}

    p_pspec = make_param_pspecs(params, par, ensemble=bool(par.ensemble_axis),
                                mesh=mesh)
    o_pspec = abstract_pspecs_like(opt_state, p_pspec)
    s_pspec = {"params": p_pspec, "opt": o_pspec}
    b_sds = _lm_batch_sds(cfg, k, b, shape.seq_len)
    b_pspec = _batch_pspec(cfg, par, k)

    if variant == "relabel":
        from repro.core import aggregation as agg
        logits_fn = steps.make_logits_fn(cfg)
        m = max(1, int(b * ec.relabel_fraction))
        r_sds = _lm_batch_sds(cfg, k, m, shape.seq_len)

        def fn(p, batch):
            return agg.ring_relabel(mesh, p, batch, logits_fn, ec,
                                    axis=par.ensemble_axis or "data")

        return Cell(arch, shape, "relabel_step", fn,
                    (params, r_sds),
                    (_named(mesh, p_pspec), _named(mesh, b_pspec)),
                    None,
                    {"k": k, "per_member": m, "accum": 1, "par": par})

    step = steps.make_local_step(cfg, opt, par=par, grad_accum=accum)
    if variant == "plain":
        fn = lambda s, bb: step(s, bb, None, 0.0)  # noqa: E731
        args = (state, b_sds)
        in_sh = (_named(mesh, s_pspec), _named(mesh, b_pspec))
        out_sh = (_named(mesh, s_pspec), None)
        return Cell(arch, shape, "train_step[plain]", fn, args, in_sh,
                    out_sh, {"k": k, "per_member": b, "accum": accum,
                             "par": par}, donate=(0,))
    else:  # distill
        from repro.core.compression import TopM
        m_top = ec.top_m
        pseudo = TopM(sds((k, b, shape.seq_len, m_top), jnp.float32),
                      sds((k, b, shape.seq_len, m_top), jnp.int32),
                      sds((k, b, shape.seq_len), jnp.float32))
        ens = par.ensemble_axis or None
        ba = tuple(par.batch_axes) or None
        ps_spec = TopM(P(ens, ba, None, None), P(ens, ba, None, None),
                       P(ens, ba, None))
        fn = lambda s, bb, ps: step(s, bb, ps, 0.25)  # noqa: E731
        args = (state, b_sds, pseudo)
        in_sh = (_named(mesh, s_pspec), _named(mesh, b_pspec),
                 _named(mesh, ps_spec))
        out_sh = (_named(mesh, s_pspec), None)

    return Cell(arch, shape, f"train_step[{variant}]", fn, args, in_sh,
                out_sh, {"k": k, "per_member": b, "accum": accum,
                         "par": par}, donate=(0,))


def build_serve_cell(arch: str, shape_name: str, mesh,
                     multi_pod: bool) -> Cell:
    from repro.models import transformer as tf
    shape = SHAPES[shape_name]
    cfg = registry.get_config(arch)
    par = filter_par(registry.parallel_policy(arch, shape, multi_pod), mesh)
    B = shape.global_batch

    # drop batch axes that don't divide this shape's batch (long_500k B=1)
    if B % _axsize(mesh, tuple(par.batch_axes)) != 0:
        keep = []
        for a in par.batch_axes:
            if B % _axsize(mesh, tuple(keep + [a])) == 0:
                keep.append(a)
        par = dataclasses.replace(par, batch_axes=tuple(keep))

    params = abstract_tree(lambda key: tf.init(key, cfg),
                           jax.random.PRNGKey(0))
    p_pspec = make_param_pspecs(params, par, ensemble=False, mesh=mesh)
    prefill_fn, decode_fn = steps.make_serve_fns(cfg, par)
    ba = tuple(par.batch_axes) or None

    if shape.kind == "prefill":
        b_sds = _lm_batch_sds(cfg, 0, B, shape.seq_len)
        b_sds.pop("labels")
        b_pspec = _batch_pspec(cfg, par, 0)
        b_pspec.pop("labels")
        return Cell(arch, shape, "prefill_step", prefill_fn,
                    (params, b_sds),
                    (_named(mesh, p_pspec), _named(mesh, b_pspec)), None,
                    {"k": 1, "per_member": B, "accum": 1, "par": par})

    # decode: one token against a seq_len cache
    cache = abstract_tree(
        lambda: tf.init_cache(cfg, B, max_seq=shape.seq_len))
    c_pspec = cache_pspecs(cfg, cache, par, mesh)
    tok = sds((B, 1), jnp.int32)
    t_pspec = P(ba, None)
    return Cell(arch, shape, "serve_step", decode_fn,
                (params, cache, tok),
                (_named(mesh, p_pspec), _named(mesh, c_pspec),
                 _named(mesh, t_pspec)),
                (None, _named(mesh, c_pspec)),  # logits free, cache aliased
                {"k": 1, "per_member": B, "accum": 1, "par": par},
                donate=(1,))


def abstract_pspecs_like(opt_state: Any, p_pspec: Any) -> Any:
    """Optimizer-state pspecs: moments mirror their parameter, scalars
    replicate."""
    flat_p, _ = jax.tree_util.tree_flatten(p_pspec)

    def rule(path, leaf):
        # match moment tensors by rank against the param tree by position:
        # m/v/mu subtrees are structurally identical to params.
        for e in path:
            if isinstance(e, jax.tree_util.DictKey) \
                    and str(e.key) in ("m", "v", "mu"):
                sub = jax.tree_util.keystr(path[1:])
                return _lookup_pspec(p_pspec, path[1:], leaf)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(rule, opt_state)


def _lookup_pspec(p_pspec, path, leaf):
    node = p_pspec
    for e in path:
        if isinstance(e, jax.tree_util.DictKey):
            node = node[str(e.key)]
        elif isinstance(e, jax.tree_util.SequenceKey):
            node = node[e.idx]
    return node if isinstance(node, P) else P(*([None] * leaf.ndim))


def build_cell(arch: str, shape_name: str, mesh, multi_pod: bool,
               variant: str = "plain") -> Cell:
    if SHAPES[shape_name].kind == "train":
        return build_train_cell(arch, mesh, multi_pod, variant=variant)
    return build_serve_cell(arch, shape_name, mesh, multi_pod)
