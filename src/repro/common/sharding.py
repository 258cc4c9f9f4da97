"""Path-based PartitionSpec rules for model parameter pytrees.

Every parameter in the framework has a standardized leaf name (see
models/*.py); `pspec_for` maps (leaf-name, rank) -> PartitionSpec under a
ParallelConfig.  `make_param_pspecs` walks an abstract param tree and returns
a matching pytree of NamedShardings/PartitionSpecs.

Conventions (TP = `model` axis, FSDP = optional `fsdp` axis):
  - column-parallel weights (d_model, X): P(fsdp, "model")   [shard output dim]
  - row-parallel weights  (X, d_model):  P("model", fsdp)    [shard input dim]
  - embeddings (V, d): vocab over "model", d over fsdp
  - per-expert weights (E, ...): experts over "model" (EP)
  - norms / small lora mats: replicated
Stacked scan segments add a leading None; the EC ensemble adds a leading
ensemble-axis dim on top of that.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.common.types import ParallelConfig

# leaf-name -> role
_COLUMN = {
    "w_q", "w_k", "w_v", "w_gate", "w_up", "mamba_in", "rwkv_r", "rwkv_k",
    "rwkv_v", "rwkv_g", "cmix_k", "q_up", "kv_up", "w_cross_q",
}
_ROW = {"w_o", "w_down", "mamba_out", "rwkv_o", "cmix_v"}
_EXPERT_COLUMN = {"experts_gate", "experts_up"}
_EXPERT_ROW = {"experts_down"}
_EMBED = {"embed", "head", "enc_embed"}
_REPLICATED_PREFIXES = (
    "norm", "bias", "router", "rwkv_mix", "rwkv_decay", "rwkv_first",
    "mamba_dt", "mamba_A", "mamba_D", "mamba_conv", "q_down", "kv_down",
    "k_rope", "qk_scale", "alibi", "pos",
)


def pspec_for(name: str, ndim: int, par: ParallelConfig) -> P:
    m, f = par.model_axis, (par.fsdp_axis or None)

    def pad(spec_tail):
        # left-pad with None for stacked-segment leading dims
        lead = ndim - len(spec_tail)
        return P(*([None] * lead), *spec_tail)

    if any(name.startswith(p) for p in _REPLICATED_PREFIXES):
        return P(*([None] * ndim))
    if name in _EMBED:
        return pad((m, f))
    if name in _EXPERT_COLUMN:
        return pad((m, f, None))
    if name in _EXPERT_ROW:
        return pad((m, None, f))
    if name in _COLUMN:
        return pad((f, m))
    if name in _ROW:
        return pad((m, f))
    # conservative default: replicate
    return P(*([None] * ndim))


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return str(entry.name)
    return ""


def make_param_pspecs(params: Any, par: ParallelConfig,
                      ensemble: bool = False, mesh=None) -> Any:
    """Pytree of PartitionSpecs matching `params` (abstract or concrete).

    With `mesh`, specs are sanitized: an axis whose size doesn't divide
    the dimension is dropped (jit in_shardings require divisibility —
    e.g. whisper's 51865 vocab can't split 16 ways, so it replicates).
    """
    def axsize(a):
        if isinstance(a, (tuple, list)):
            n = 1
            for x in a:
                n *= mesh.shape.get(x, 1)
            return n
        return mesh.shape.get(a, 1)

    def sanitize(spec, shape):
        if mesh is None:
            return spec
        clean = []
        for dim, a in zip(shape, tuple(spec) + (None,) * len(shape)):
            clean.append(a if (a is None or dim % axsize(a) == 0) else None)
        return P(*clean)

    def rule(path, leaf):
        name = _leaf_name(path)
        ens_axis = par.ensemble_axis if ensemble else None
        spec = pspec_for(name, leaf.ndim - (1 if ensemble else 0), par)
        if ensemble:
            spec = P(ens_axis or None, *spec)
        return sanitize(spec, leaf.shape)

    return jax.tree_util.tree_map_with_path(rule, params)


def make_shardings(mesh, pspecs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _axis_ok(axis, names) -> bool:
    if axis is None:
        return True
    if isinstance(axis, (tuple, list)):
        return all(a in names for a in axis)
    return axis in names


# ---------------------------------------------------------------------------
# layout context: symbolic axes resolved at trace time
# ---------------------------------------------------------------------------
# Model code names *roles* ("batch"); the step function decides what mesh
# axes that role maps to.  EC ensemble training maps "batch" to () because
# the member axis is carried by the stacked leading dim, while single-model
# serving maps it to ("pod", "data").

import contextlib
import threading

BATCH = "batch"  # sentinel usable in constrain() specs
REP = "__replicate__"  # force replication of a dim (None means "free")

_layout = threading.local()


def _layout_map() -> dict:
    return getattr(_layout, "map", {"batch": ("pod", "data"),
                                    "seq": None, "train": False})


def layout_flag(name: str) -> bool:
    return bool(_layout_map().get(name))


@contextlib.contextmanager
def layout_ctx(**roles):
    """layout_ctx(batch=("data",)) remaps symbolic axes inside the block."""
    old = _layout_map()
    _layout.map = {**old, **roles}
    try:
        yield
    finally:
        _layout.map = old


def _resolve(axis):
    if isinstance(axis, str) and axis in _layout_map():
        v = _layout_map()[axis]
        return tuple(v) if isinstance(v, (tuple, list)) else v
    return axis


def ambient_mesh():
    """The (abstract) mesh `jax.sharding.set_mesh` installed, or None
    off-mesh (constrain() then becomes a no-op)."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m


def set_mesh(mesh):
    """jax.sharding.set_mesh: a context manager installing `mesh`."""
    return jax.sharding.set_mesh(mesh)


def make_mesh(axis_shapes, axis_names, auto: bool = True):
    """jax.make_mesh with every axis Auto (or every axis Explicit)."""
    kind = (jax.sharding.AxisType.Auto if auto
            else jax.sharding.AxisType.Explicit)
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(kind,) * len(axis_names))


def shard_map(f, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """jax.shard_map; `axis_names` lists the manual axes (default all)."""
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


# ---------------------------------------------------------------------------
# serving member-axis placement
# ---------------------------------------------------------------------------
# The serving engine's unit of parallelism is the ensemble MEMBER (paper
# Eqn 6: the global model is K independent members, so the member axis is
# embarrassingly parallel at test time).  Stacked params, the KV cache
# pool, and the quorum vector all carry a leading (K,) axis; these
# helpers place that axis over the "member" mesh axis and leave
# everything else replicated ("data" is reserved for slot/batch
# sharding, a ROADMAP follow-up).

MEMBER_AXIS = "member"
DATA_AXIS = "data"


def member_pspec(ndim: int, axis: str = MEMBER_AXIS) -> P:
    """PartitionSpec sharding a leaf's leading member axis, rest replicated."""
    return P(axis, *([None] * (ndim - 1)))


def member_pspecs(tree: Any, axis: str = MEMBER_AXIS) -> Any:
    """Pytree of PartitionSpecs matching `tree`: every leaf's leading
    (K,) member axis shards over `axis`, all other dims replicate.

    This is the serving twin of `make_param_pspecs(..., ensemble=True)`:
    at serving time members never communicate during the forward pass
    (only fused log-probs cross devices, see core.ensemble
    .ensemble_log_probs_psum), so intra-member TP/FSDP axes are left
    unsharded and the member axis carries all the parallelism.
    """
    return jax.tree.map(lambda x: member_pspec(x.ndim, axis), tree)


def replicated_pspecs(tree: Any) -> Any:
    """Pytree of all-None PartitionSpecs (fully replicated leaves)."""
    return jax.tree.map(lambda x: P(*([None] * x.ndim)), tree)


def local_mesh(member: int = 1, data: int = 1,
               axis_names: Tuple[str, str] = (MEMBER_AXIS, DATA_AXIS)):
    """Build a (member, data) mesh from this process's devices,
    degrading gracefully to whatever is available.

    Unlike `make_mesh` (which insists the grid uses every device), this
    takes the FIRST member*data local devices — and when the host has
    fewer, clamps each axis down (member first) so the same shard_map
    code path still runs: a 1-CPU CI box asking for `local_mesh(2, 1)`
    gets a 1x1 mesh and exercises the exact program the 2-device run
    compiles, psum collectives included.  Force N host devices on CPU
    with XLA_FLAGS=--xla_force_host_platform_device_count=N (set before
    jax initializes).
    """
    import numpy as np
    devs = jax.devices()
    member = max(1, min(int(member), len(devs)))
    data = max(1, min(int(data), len(devs) // member))
    grid = np.asarray(devs[: member * data]).reshape(member, data)
    return jax.sharding.Mesh(grid, axis_names)


def parse_mesh_arg(arg: str):
    """'MxD' CLI string -> an M x D (member, data) mesh; '' / '1x1' ->
    None (the unsharded single-device reference path).  Unlike
    local_mesh this never clamps: asking for more devices than the
    process holds raises instead of quietly serving on fewer."""
    if not arg or arg.lower() in ("1x1", "none", "off"):
        return None
    try:
        m, d = (int(x) for x in arg.lower().split("x"))
    except ValueError:
        raise ValueError(f"--mesh wants 'MxD' (e.g. 2x1), got {arg!r}")
    if m * d <= 1:
        return None
    n = len(jax.devices())
    if m * d > n:
        raise ValueError(f"--mesh {arg} needs {m * d} devices, this "
                         f"process has {n}")
    return local_mesh(m, d)


def mesh_axis_size(axis: str) -> int:
    """Size of a mesh axis at trace time (1 off-mesh / absent)."""
    mesh = ambient_mesh()
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.axis_sizes)).get(axis, 1)


def constrain(x, *spec):
    """with_sharding_constraint that degrades to a no-op off-mesh.

    Axes absent from the active mesh are dropped (so model code can always
    name its ideal layout and still run on 1 CPU device in tests), and
    symbolic role axes (BATCH/seq) resolve through layout_ctx.

    Unnamed dims become P.UNCONSTRAINED, NOT None: a None dim in a
    sharding constraint means "force replicated", which silently destroys
    the propagated batch sharding (measured: 30 GiB/device attention
    scores on arctic prefill before this distinction).  Model code that
    says constrain(x, None, None, "model") means "pin TP on this dim,
    leave the rest to propagation" — and that is what this emits.
    """
    mesh = ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    U = P.UNCONSTRAINED
    spec = tuple(_resolve(a) for a in spec)
    clean = tuple(
        None if a == REP
        else (a if (a is not None and a != () and _axis_ok(a, names))
              else U)
        for a in spec)
    if x.ndim < len(clean):  # decode paths reuse prefill constraints
        clean = clean[: x.ndim]
    clean = clean + (U,) * (x.ndim - len(clean))
    if all(c is U for c in clean):
        return x
    return jax.lax.with_sharding_constraint(x, P(*clean))
