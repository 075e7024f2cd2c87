"""Logical-axis sharding: one rule table maps logical axis names (annotated
next to every parameter in ``models/params.py`` and asserted on activations
via :func:`shard_act`) to mesh axes.

This is the GSPMD side of the distribution story (training / prefill):
einsum-heavy graphs lower well under pjit with these constraints. The
serving decode path uses ``shard_map`` instead (serving/engine.py) because
its paged gathers must stay shard-local. Since PR 7 the cache daemon's
sharded-table fan-out is a third client of the :func:`shard_map`
wrapper below: ``core/shards.py`` lowers its per-lane map through it over
the ``launch/mesh.py`` lane mesh, so the wrapper is load-bearing for
serving traffic, not just the model stack.

Rules are *per-arch overridable*: a config may e.g. drop the
``heads -> model`` rule when its head count does not divide the model
axis (the baseline keeps attention replicated over 'model' there; §Perf
hillclimbs re-shard it).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# mesh axis groups
_DP = ("pod", "data")  # batch-parallel axes (outer pod, inner data/fsdp)


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma=True):
    """``jax.shard_map`` with ``axis_names=None`` meaning every mesh axis
    is manual (jax's own default, which takes no ``None``)."""
    kw = {} if axis_names is None else {"axis_names": frozenset(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


# Default logical-axis -> mesh-axis rules (single- and multi-pod; missing
# mesh axes in a rule are silently dropped against the actual mesh).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # activations
    "batch": _DP,
    "seq": (),
    "embed": (),            # d_model replicated (activations & serving params)
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "mlp": ("model",),
    "expert": ("model",),
    "inner": ("model",),     # SSM d_inner
    "inner2": ("model",),    # mamba1 in_proj output (2*d_inner)
    "inner_proj": ("model",),  # mamba2 in_proj output
    "ssm_heads": ("model",),
    "state": (),
    "conv": (),
    "lowrank": (),
    "layers": (),
    "kv_cap": ("data",),     # KV pool capacity rows live on the data axis
    "kv_block": (),
}

# Param tables. Training params are 2-D sharded: FSDP over 'data' on the
# d_model ('embed') dim + TP over 'model' on the tensor dim (ZeRO-3 style;
# XLA's latency-hiding scheduler overlaps the per-layer all-gathers with
# the layer scan). Serving replicates weights over 'data' (per-token
# all-gathers would burn ICI on the latency path) and keeps TP only.
TRAIN_PARAM_RULES: dict[str, tuple[str, ...]] = dict(
    DEFAULT_RULES, embed=("data",)
)
SERVE_PARAM_RULES: dict[str, tuple[str, ...]] = dict(DEFAULT_RULES)
MULTIPOD_RULES = dict(DEFAULT_RULES)

_local = threading.local()


def current_rules() -> Mapping[str, tuple[str, ...]] | None:
    return getattr(_local, "rules", None)


def current_mesh():
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def axis_rules(rules: Mapping[str, Sequence[str]] | None, mesh=None):
    """Install logical->mesh rules for model code running under this scope.

    ``None`` (or outside any scope) disables all constraints — single-device
    tests and benches run the exact same model code unconstrained. Passing
    ``mesh`` makes constraints concrete NamedShardings (no reliance on a
    global mesh context manager)."""
    prev = getattr(_local, "rules", None)
    prev_mesh = getattr(_local, "mesh", None)
    _local.rules = dict(rules) if rules is not None else None
    _local.mesh = mesh
    try:
        yield
    finally:
        _local.rules = prev
        _local.mesh = prev_mesh


def _mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names) if mesh is not None else ()


def spec_for_axes(
    axes: Sequence[str | None],
    rules: Mapping[str, Sequence[str]],
    mesh_axis_names: Sequence[str] = (),
) -> P:
    """Logical axes of one array -> PartitionSpec, dropping mesh axes that
    do not exist on the target mesh and axes already used (a mesh axis may
    shard only one dim)."""
    used: set[str] = set()
    parts = []
    for ax in axes:
        entry: tuple[str, ...] = ()
        if ax is not None:
            entry = tuple(
                m
                for m in rules.get(ax, ())
                if (not mesh_axis_names or m in mesh_axis_names)
                and m not in used
            )
            used.update(entry)
        if len(entry) == 0:
            parts.append(None)
        elif len(entry) == 1:
            parts.append(entry[0])
        else:
            parts.append(tuple(entry))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def specs_for_tree(axes_tree, rules, mesh, sds_tree=None) -> object:
    """Map a tree of logical-axes tuples to NamedShardings on ``mesh``.

    With ``sds_tree`` (matching ShapeDtypeStructs), mesh axes that do not
    divide their dimension are dropped — e.g. a 4-kv-head GQA simply keeps
    its KV projections replicated over a 16-way 'model' axis instead of
    failing (the §Perf page-striped serving path re-parallelizes it).
    """
    names = _mesh_axes(mesh)
    is_axes = (lambda x: isinstance(x, tuple)
               and all(isinstance(a, (str, type(None))) for a in x))

    def trim(axes, shape):
        spec = spec_for_axes(axes, rules, names)
        if shape is None:
            return spec
        parts = []
        for i, entry in enumerate(tuple(spec) + (None,) * (len(shape)
                                                           - len(spec))):
            if entry is None:
                parts.append(None)
                continue
            group = entry if isinstance(entry, tuple) else (entry,)
            n = int(np.prod([mesh.shape[a] for a in group]))
            if shape[i] % n != 0:
                # drop trailing axes until it divides (or give up)
                while group and shape[i] % int(
                        np.prod([mesh.shape[a] for a in group])):
                    group = group[:-1]
            parts.append(tuple(group) if len(group) > 1
                         else (group[0] if group else None))
        return P(*parts)

    if sds_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, trim(axes, None)),
            axes_tree, is_leaf=is_axes)
    return jax.tree.map(
        lambda axes, sds: NamedSharding(mesh, trim(axes, sds.shape)),
        axes_tree, sds_tree, is_leaf=is_axes)


def shard_act(x, *axes: str | None):
    """Constrain an activation's sharding by logical axis names.

    No-op when no rules are installed (tests, single-device benches) so
    model code is identical everywhere.
    """
    rules = current_rules()
    if rules is None:
        return x
    mesh = current_mesh()
    if mesh is not None:
        spec = spec_for_axes(axes, rules, tuple(mesh.axis_names))
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    spec = spec_for_axes(axes, rules)
    return jax.lax.with_sharding_constraint(x, spec)
