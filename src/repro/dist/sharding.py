"""PartitionSpec utilities shared by the models, the train step and the
dry-run compiler harness.

Everything here is pure spec surgery plus one runtime helper:

* ``prune_spec``         drop spec entries whose mesh-axis product does not
                         divide the array dim (GSPMD would otherwise pad or
                         reject; we prefer replication of the odd dim).
* ``resolve_spec``       pad a spec to an array's rank, drop axes the mesh
                         doesn't have, then prune.
* ``tree_shardings``     resolve a pytree of specs against a pytree of
                         ShapeDtypeStructs into NamedShardings.
* ``add_data_axis``      FSDP/ZeRO helper: shard the first free dim over the
                         ``data`` axis without ever double-sharding.
* ``tree_add_data_axis`` the same over a (specs, shapes) pytree pair.
* ``shard_hint``         ``with_sharding_constraint`` when an ambient mesh
                         is installed, identity otherwise — so model code can
                         carry layout hints that are inert in CPU unit tests.

Specs may contain tuple entries (``P(("pod", "data"), None)``); a tuple is
kept or dropped atomically — splitting it would change the axis order the
partitioner uses.
"""

from __future__ import annotations

from typing import Iterable, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "prune_spec", "resolve_spec", "tree_shardings",
    "add_data_axis", "tree_add_data_axis", "shard_hint",
]


def _axis_sizes(mesh) -> dict:
    """name -> size for anything mesh-shaped (``Mesh``, ``AbstractMesh``
    or a test double exposing ``axis_names`` and ``axis_sizes``)."""
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.axis_sizes)))


def _entry_axes(entry) -> Tuple:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def _pad(spec, ndim: int) -> Tuple:
    entries = tuple(spec) if spec is not None else ()
    if len(entries) > ndim:
        raise ValueError(f"spec {spec} has rank {len(entries)} > array rank {ndim}")
    return entries + (None,) * (ndim - len(entries))


def _is_spec(leaf) -> bool:
    return isinstance(leaf, P)


def prune_spec(spec, shape: Sequence[int], mesh) -> P:
    """Replace entries whose mesh-axis-size product does not divide the
    corresponding dim with None (replicate that dim)."""
    sizes = _axis_sizes(mesh)
    out = []
    for dim, entry in zip(shape, _pad(spec, len(shape))):
        axes = _entry_axes(entry)
        if not axes:
            out.append(None)
            continue
        total = int(np.prod([sizes.get(a, 1) for a in axes]))
        out.append(entry if total > 0 and dim % total == 0 else None)
    return P(*out)


def resolve_spec(spec, shape: Sequence[int], mesh) -> P:
    """Pad ``spec`` to ``len(shape)``, drop axes absent from ``mesh``, prune
    non-divisible dims.  The result is always safe to wrap in a
    NamedSharding over ``mesh``."""
    sizes = _axis_sizes(mesh)
    entries = []
    for entry in _pad(spec, len(shape)):
        axes = tuple(a for a in _entry_axes(entry) if a in sizes)
        if not axes:
            entries.append(None)
        elif not isinstance(entry, (tuple, list)):
            entries.append(axes[0])
        else:
            entries.append(axes)
    return prune_spec(P(*entries), shape, mesh)


def _zip_spec_tree(specs, shapes):
    """Flatten (specs, shapes) in lockstep; specs leaves are PartitionSpecs
    (tuples — so jax.tree would flatten them without is_leaf)."""
    leaves_sh, treedef = jax.tree.flatten(shapes)
    leaves_sp = jax.tree.flatten(specs, is_leaf=_is_spec)[0]
    if len(leaves_sp) != len(leaves_sh):
        raise ValueError(
            f"spec tree has {len(leaves_sp)} leaves, shape tree has "
            f"{len(leaves_sh)} — the trees must be congruent")
    return leaves_sp, leaves_sh, treedef


def tree_shardings(specs, mesh, shapes):
    """Pytree of PartitionSpecs + pytree of ShapeDtypeStructs ->
    pytree (shape treedef) of NamedShardings with unresolvable axes pruned."""
    leaves_sp, leaves_sh, treedef = _zip_spec_tree(specs, shapes)
    resolved = [NamedSharding(mesh, resolve_spec(sp, sh.shape, mesh))
                for sp, sh in zip(leaves_sp, leaves_sh)]
    return jax.tree.unflatten(treedef, resolved)


def add_data_axis(spec, shape: Sequence[int], dp_size: Optional[int] = None,
                  skip_dims: Iterable[int] = (), axis: str = "data") -> P:
    """Shard the first free (None) dim of ``spec`` over ``axis``.

    Never double-shards: if ``axis`` already appears anywhere in the spec
    (including inside tuple entries) the spec is returned unchanged.  When
    ``dp_size`` is given, only dims divisible by it qualify — non-divisible
    candidates are skipped rather than padded.  ``skip_dims`` excludes dims
    that must stay replicated (e.g. the scan/layer dim of stacked weights).
    """
    entries = list(_pad(spec, len(shape)))
    present = {a for e in entries for a in _entry_axes(e)}
    if axis in present:
        return P(*entries)
    skip = set(skip_dims)
    for d, (dim, entry) in enumerate(zip(shape, entries)):
        if d in skip or entry is not None:
            continue
        if dp_size is not None and (dp_size <= 0 or dim % dp_size):
            continue
        entries[d] = axis
        break
    return P(*entries)


def tree_add_data_axis(specs, shapes, skip_dims: Iterable[int] = (),
                       dp_size: Optional[int] = None, axis: str = "data"):
    """``add_data_axis`` over congruent (specs, shapes) pytrees.  Returns a
    tree of PartitionSpecs with the shapes tree's structure."""
    leaves_sp, leaves_sh, treedef = _zip_spec_tree(specs, shapes)
    out = [add_data_axis(sp, sh.shape, dp_size=dp_size, skip_dims=skip_dims,
                         axis=axis)
           for sp, sh in zip(leaves_sp, leaves_sh)]
    return jax.tree.unflatten(treedef, out)


def shard_hint(x, spec):
    """Best-effort layout hint: constrain ``x`` to ``spec`` on the ambient
    mesh (the one ``jax.set_mesh`` installs); identity when no mesh is
    installed (single-device tests) or when the spec names axes the mesh
    lacks / can't divide."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    resolved = resolve_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, resolved))
