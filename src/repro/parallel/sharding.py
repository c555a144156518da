"""Logical-axis sharding rules.

Models annotate activations with *logical* axes, e.g.
``shard(x, ("batch", "seq", "embed"))``; the launcher installs a rule table
mapping logical axes to mesh axes.  Outside any installed context the
annotations are no-ops, so unit tests and single-device runs never touch
device state.

Default rule table (DESIGN.md §4):
  batch    -> ("pod", "data")   activations data-parallel
  embed    -> None              residual stream replicated (SP variant: "seq"
                                logical axis mapped to "model")
  heads    -> "model"           attention TP (archs with heads % tp == 0)
  kv_heads -> None              small; replicated within a model row
  ff       -> "model"           MLP TP
  experts  -> "model"           expert parallelism
  vocab    -> "model"           embedding/LM-head TP
  kv_seq   -> "model"           decode KV caches seq-sharded (flash-decoding)
  fsdp     -> "data"            parameter/optimizer-state sharding
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

_STATE = threading.local()

DEFAULT_RULES: Dict[str, Optional[Tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": ("model",),
    "kv_heads": None,
    "head_dim": None,
    "ff": ("model",),
    "feature": ("model",),  # TP projector output (decorr engine 'tp' mode)
    "experts": ("model",),
    "vocab": ("model",),
    "kv_seq": ("model",),
    "fsdp": ("data",),
    "stack": None,  # stacked-layer leading dim
}


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


def current_rules() -> Dict[str, Optional[Tuple[str, ...]]]:
    return getattr(_STATE, "rules", DEFAULT_RULES)


def _has_explicit_axes(mesh: Mesh) -> bool:
    return any(t == AxisType.Explicit for t in mesh.axis_types)


@contextlib.contextmanager
def sharding_context(mesh: Mesh, rules: Optional[Dict] = None):
    """Install mesh + logical rules for model-internal annotations."""
    prev_mesh = getattr(_STATE, "mesh", None)
    prev_rules = getattr(_STATE, "rules", None)
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    _STATE.mesh = mesh
    _STATE.rules = merged
    # code traced under an Explicit-axis mesh must see the axes its inputs
    # are sharded over
    axes_in_scope = (
        jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
        if _has_explicit_axes(mesh) else contextlib.nullcontext()
    )
    try:
        with axes_in_scope:
            yield
    finally:
        _STATE.mesh = prev_mesh
        if prev_rules is None:
            if hasattr(_STATE, "rules"):
                del _STATE.rules
        else:
            _STATE.rules = prev_rules


def logical_to_spec(axes: Sequence[Optional[str]]) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec under the current rules,
    dropping mesh axes that don't exist in the current mesh (e.g. "pod" on
    the single-pod mesh)."""
    mesh = current_mesh()
    rules = current_rules()
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    spec = []
    used: set = set()
    for ax in axes:
        if ax is None:
            spec.append(None)
            continue
        mapped = rules.get(ax)
        if mapped is None:
            spec.append(None)
            continue
        keep = tuple(m for m in mapped if m in mesh_axes and m not in used)
        used.update(keep)
        if not keep:
            spec.append(None)
        elif len(keep) == 1:
            spec.append(keep[0])
        else:
            spec.append(keep)
    return PartitionSpec(*spec)


def shard(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint by logical axes; no-op without a context.

    Axes whose dimension is not divisible by (or is smaller than) the mapped
    mesh-axis product are dropped per-axis — e.g. an 8-head attention on a
    16-way model axis falls back to replicated heads instead of forcing
    GSPMD into involuntary full rematerialization."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(axes)
    parts = list(spec) + [None] * (x.ndim - len(spec))
    fixed = []
    for dim, part in zip(x.shape, parts):
        if part is None:
            fixed.append(None)
            continue
        mesh_axes = part if isinstance(part, tuple) else (part,)
        n = 1
        for a in mesh_axes:
            n *= mesh.shape[a]
        fixed.append(part if (dim % n == 0 and dim >= n) else None)
    if _has_explicit_axes(mesh):  # a constraint only asserts on Explicit axes
        return jax.sharding.reshard(x, PartitionSpec(*fixed))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*fixed)))


def on_mesh(tree):
    """Under an Explicit-axis mesh, replicate leaves that are on no mesh yet
    (unplaced params), so their gradients have a sharding to land in."""
    mesh = current_mesh()
    if mesh is None or not _has_explicit_axes(mesh):
        return tree
    return jax.tree.map(
        lambda x: jax.sharding.reshard(x, PartitionSpec())
        if jax.typeof(x).sharding.mesh.empty else x,
        tree,
    )


def lookup(table, idx, axes: Sequence[Optional[str]]):
    """``table[idx]`` with the result sharded by logical ``axes``; an
    Explicit-axis mesh cannot infer where a gather by sharded indices lands."""
    mesh = current_mesh()
    if mesh is None or not _has_explicit_axes(mesh):
        return table[idx]
    return table.at[idx].get(out_sharding=logical_to_spec(axes))


def named_sharding(axes: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(axes))
