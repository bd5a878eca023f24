"""Sharding substrate: who holds which parameters, and what moves on the wire.

The FedGAN mapping (see repro.core.fedgan) stacks every parameter leaf with a
leading (P, A) agent grid sharded over the ("pod", "data") mesh axes; tensor
parallelism over "model" lives *inside* each agent.  This module supplies the
two halves of that story:

  activations  ``batch_axes`` / ``batch_spec`` / ``shard`` — model code
               declares constraints positionally ("batch dims, then these
               trailing entries") and the active :func:`batch_axes` context
               decides which mesh axes the batch dims actually occupy.  The
               same model code therefore serves the agent-sharded train step
               (batch over ("pod","data")), the intra-agent DP plan (batch
               over "model") and the single-device CPU paper runs (no mesh:
               every constraint is a no-op).

  parameters   ``param_specs`` — name-rule tensor parallelism (column-/row-
               parallel by module name, divisibility fallback to replicated),
               with ``lead=`` for the agent-stacked leading dims and
               ``fsdp_axis=`` for additionally sharding weights inside an
               agent.  ``dp_param_specs`` is the ZeRO-style variant for the
               intra-agent DP plan: weights *stored* sharded over "model" and
               gathered at use.

Every public helper funnels through :func:`filter_spec`, which adapts a
requested spec to a concrete mesh: axis names the mesh lacks are dropped,
a dim whose size the remaining axes do not divide falls back to replicated,
and an axis already consumed by an earlier dim is never reused (this is what
lets the DP plan put "model" under the batch and silently disable the
tensor-parallel trailing entries of the very same model code).
"""
from __future__ import annotations

import threading
from contextlib import contextmanager

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# The production default: activation batch dims live on the agent grid.
DEFAULT_BATCH_AXES = ("pod", "data")

_local = threading.local()


# ---------------------------------------------------------------------------
# batch-axes context
# ---------------------------------------------------------------------------


def current_batch_axes() -> tuple:
    """Mesh axes currently carrying activation batch dims."""
    return getattr(_local, "batch_axes", DEFAULT_BATCH_AXES)


@contextmanager
def batch_axes(*axes: str):
    """Rebind the activation batch axes for the enclosed trace.

    ``batch_axes()`` (no arguments) means *no* batch sharding — used for
    per-agent compute whose batch dim is already inside an agent — while
    ``batch_axes("model")`` is the intra-agent DP plan.  Nests and restores
    (the previous binding returns on exit, even on exception).
    """
    prev = current_batch_axes()
    _local.batch_axes = tuple(axes)
    try:
        yield
    finally:
        _local.batch_axes = prev


def batch_spec(*trailing):
    """Positional spec entries: the batch entry, then ``trailing`` verbatim.

    The batch entry is the current :func:`batch_axes` tuple, or None when the
    context is empty.  ``shard(x, *batch_spec(None, "model"))`` therefore
    reads "batch over whatever the plan says, dim1 replicated, dim2 tensor-
    parallel"."""
    axes = current_batch_axes()
    return ((tuple(axes) if axes else None),) + trailing


# ---------------------------------------------------------------------------
# spec filtering (mesh adaptation)
# ---------------------------------------------------------------------------


def mesh_dims(mesh) -> dict:
    """{axis name: size} for a mesh (canonical copy; launch.mesh re-exports)."""
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def current_mesh():
    """The mesh made current by ``jax.set_mesh(mesh)``, or None outside any
    mesh context (single-device CPU paper runs — sharding constraints
    become no-ops there).  Read as the abstract mesh, which is what jax
    exposes inside a trace."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


_mesh_dims = mesh_dims


def filter_spec(mesh, entries, shape) -> P:
    """Adapt requested spec ``entries`` to ``mesh`` and ``shape``.

    Per dim (entry may be an axis name, a tuple of axis names, or None):
      1. drop axis names the mesh does not have (e.g. "pod" on a single-pod
         ("data","model") mesh);
      2. drop axis names already used by an earlier dim (an axis can shard
         at most one dim; first dim wins);
      3. if the surviving axes do not evenly divide the dim size, the whole
         dim falls back to replicated (never uneven shards).
    Returns a PartitionSpec with exactly ``len(entries)`` entries.
    """
    dims = _mesh_dims(mesh)
    if len(entries) > len(shape):
        raise ValueError(f"spec {entries} has more entries than shape {shape}")
    used: set = set()
    out = []
    for entry, size in zip(entries, shape):
        if entry is None:
            out.append(None)
            continue
        names = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        keep = [n for n in names if n in dims and n not in used]
        prod = 1
        for n in keep:
            prod *= dims[n]
        if not keep or prod == 1 or size % prod != 0:
            out.append(None)
            continue
        used.update(keep)
        out.append(tuple(keep) if len(keep) > 1 else keep[0])
    return P(*out)


# The seed's call sites bound this private spelling before the public export
# existed; kept as an alias so both names resolve.
_filter_spec = filter_spec


# ---------------------------------------------------------------------------
# activation constraints
# ---------------------------------------------------------------------------


def shard(x, *entries):
    """Constrain ``x`` to ``entries`` on the current mesh context.

    Entries beyond ``x.ndim`` are rejected; missing trailing entries mean
    replicated.  Outside any mesh context (single-device paper runs, unit
    tests) this is the identity, so model code can call it unconditionally.
    """
    mesh = current_mesh()
    if mesh is None or not entries:
        return x
    spec = filter_spec(mesh, entries, x.shape)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_attn_qkv(q, k, v):
    """Constrain attention projections (B, T, heads, head_dim).

    Batch over the active batch axes; heads over "model" when the head count
    divides, otherwise head_dim (GQA kv heads are often fewer than the model
    axis — sharding head_dim keeps the tensor distributed instead of
    replicating it).  Under the DP plan the batch entry consumes "model" and
    the head entries are dropped by :func:`filter_spec`'s reuse rule.
    """
    mesh = current_mesh()
    if mesh is None:
        return q, k, v
    model = _mesh_dims(mesh).get("model", 1)

    def one(t):
        if t.ndim < 4:
            return shard(t, *batch_spec())
        if model > 1 and t.shape[-2] % model == 0:
            ent = (None, "model", None)
        else:
            ent = (None, None, "model")
        return shard(t, *batch_spec(*ent))

    return one(q), one(k), one(v)


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

# Tensor-parallel name rules (matched against any component of the leaf's
# key path; ROW wins over COL when both appear).
#   COL — output-dim ("column") parallel: shard dim -1 over "model".
#   ROW — input-dim ("row") parallel: shard dim -2 over "model" (their
#         matmul contracts the sharded dim; XLA inserts the one all-reduce
#         the Megatron pattern pays per block).
# Everything unmatched (norm scales/biases, ssd scalars, router aux, ...)
# is replicated within the agent.
COL_PARALLEL = frozenset({
    "embed", "lm_head", "wq", "wk", "wv", "w_gate", "w_up", "router",
    "z_proj", "x_proj", "b_proj", "c_proj", "dt_proj", "proj_in", "head",
    "conv",
})
ROW_PARALLEL = frozenset({"wo", "w_down", "out_proj"})


def _path_names(path) -> tuple:
    names = []
    for e in path:
        key = getattr(e, "key", None)
        if key is None:
            key = getattr(e, "name", None)
        if key is None and hasattr(e, "idx"):
            key = str(e.idx)
        names.append(str(key))
    return tuple(names)


def _rule_entries(names, shape, *, fsdp_axis=None) -> list:
    """Trailing-dim entries for one leaf under the TP name rules."""
    nd = len(shape)
    ent: list = [None] * nd
    if nd == 0:
        return ent
    hit = set(names)
    if hit & ROW_PARALLEL:
        if nd >= 2:
            ent[-2] = "model"
            if fsdp_axis:
                ent[-1] = fsdp_axis
    elif hit & COL_PARALLEL:
        ent[-1] = "model"
        if fsdp_axis and nd >= 2:
            ent[-2] = fsdp_axis
    elif fsdp_axis:
        # unmatched leaves (norms, biases, ssd params): plain FSDP on the
        # trailing dim — pure memory sharding, gathered at use
        ent[-1] = fsdp_axis
    return ent


def param_specs(tree, mesh, *, lead: tuple = (), fsdp_axis: str | None = None):
    """Name-rule PartitionSpec tree for a parameter (or optimizer) pytree.

    ``lead`` names one mesh axis per *leading* dim of every leaf — the
    agent-stacked (P, A) dims of FedGAN state.  The TP rules anchor to the
    *trailing* dims, so the same rules serve stacked (lead + layer-stacked)
    and flat serving params.  ``fsdp_axis`` additionally shards the matmul-
    complement dim of every weight over that axis (weights gathered at use).
    Divisibility fallback is per-dim via :func:`filter_spec`.
    """
    lead = tuple(lead)

    def spec_of(path, leaf):
        shape = tuple(leaf.shape)
        n_lead = min(len(lead), len(shape))
        entries = list(lead[:n_lead]) + _rule_entries(
            _path_names(path), shape[n_lead:], fsdp_axis=fsdp_axis)
        return filter_spec(mesh, tuple(entries), shape)

    return jax.tree_util.tree_map_with_path(spec_of, tree)


def dp_param_specs(tree, mesh, *, lead: tuple = ()):
    """ZeRO-style specs for the intra-agent DP plan (``agents-data-dp``).

    Every leaf is *stored* sharded over "model" along its innermost evenly-
    divisible dim (weights, optimizer moments, norms alike) and gathered at
    use — the per-step wire cost becomes O(params) weight gathers + gradient
    reduce-scatters instead of O(activations·layers) TP all-reduces, which
    is the §Perf win ``test_dp_plan_reduces_collectives`` measures.
    """
    lead = tuple(lead)
    model = _mesh_dims(mesh).get("model", 1)

    def spec_of(path, leaf):
        shape = tuple(leaf.shape)
        n_lead = min(len(lead), len(shape))
        entries = list(lead[:n_lead]) + [None] * (len(shape) - n_lead)
        if model > 1:
            for i in range(len(shape) - 1, n_lead - 1, -1):
                if shape[i] % model == 0:
                    entries[i] = "model"
                    break
        return filter_spec(mesh, tuple(entries), shape)

    return jax.tree_util.tree_map_with_path(spec_of, tree)


# ---------------------------------------------------------------------------
# small utilities
# ---------------------------------------------------------------------------


def named_shardings(mesh, tree):
    """PartitionSpec pytree -> NamedSharding pytree on ``mesh`` (non-spec
    leaves pass through, so mixed spec/None trees stay jit-compatible)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s, tree)


def shape_of(x) -> tuple:
    """Shape of an array, ShapeDtypeStruct, or anything with ``.shape``."""
    return tuple(x.shape)
