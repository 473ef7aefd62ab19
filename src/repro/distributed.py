"""Ambient mesh context for sharding constraints inside model code.

Model code calls :func:`constrain` on activations; when a mesh has been
installed by the launcher the call lowers to
``jax.lax.with_sharding_constraint`` with a :class:`NamedSharding`, and when
running unsharded (CPU smoke tests) it is a no-op.  Axis names that are not
present in the installed mesh are dropped from the spec, so the same model
code serves the (data, model), (pod, data, model) and single-device cases.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

_state = threading.local()


def make_mesh(shape, axis_names, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types.

    ``devices`` restricts the mesh to a device subset (the shard-count
    clamps in :func:`row_shard_count` / :func:`grid_shard_counts` can pick
    fewer shards than visible devices so tiny batches are not mostly
    padding); ``None`` keeps jax.make_mesh's all-devices default.
    """
    return jax.make_mesh(shape, axis_names,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def shard_map(worker, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (callers use
    collectives the checker cannot type)."""
    return jax.shard_map(worker, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def row_shard_count(n_rows: int) -> int:
    """How many ways a leading batch axis of ``n_rows`` should shard.

    Uses the visible devices (``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` forces N host devices for local testing), clamped to
    ``n_rows`` so a tiny batch never shards wider than it has rows (8
    forced devices and 3 mixes used to build 8 shards whose padding
    outnumbered the real rows).  Returns 1 when a single device is present
    or the batch is empty, which callers treat as "skip shard_map
    entirely".
    """
    if n_rows <= 0:
        return 1
    return max(1, min(n_rows, jax.device_count()))


def shard_rows(worker, n_shards: int, axis_name: str = "mix"):
    """shard_map ``worker(sharded_tree, replicated_tree)`` over rows.

    Builds a 1-D mesh of ``n_shards`` devices (the first ``n_shards`` of
    the visible devices — :func:`row_shard_count` may clamp below the
    device count) and maps the worker with the first argument's leaves
    sharded on their leading axis (every leaf must carry the batch axis,
    padded to a multiple of ``n_shards`` by the caller) and the second
    argument replicated.  This is how the fused Fig. 8 timeline
    (:mod:`repro.sim.timeline_jax`) spreads the mix axis of
    hundreds-of-mixes sweeps across devices.
    """
    devices = None
    if n_shards < jax.device_count():
        devices = jax.devices()[:n_shards]
    mesh = make_mesh((n_shards,), (axis_name,), devices=devices)
    return shard_map(
        worker, mesh,
        in_specs=(PartitionSpec(axis_name), PartitionSpec()),
        out_specs=PartitionSpec(axis_name))


def grid_shard_counts(n_groups: int, n_rows: int) -> Tuple[int, int]:
    """Factor the visible devices into a (group, row) shard grid.

    For the stacked Fig. 8 timelines the grid is (manager, mix): manager
    groups shard on the first mesh axis, mixes on the second, so different
    managers' timelines execute on different devices concurrently.  Each
    axis is clamped to its extent (shards <= rows, like
    :func:`row_shard_count`); among factorizations using the most devices
    the most balanced one wins (maximal ``min(a, b)``, then maximal row
    shards), which keeps per-axis padding small and exercises a genuine
    2-D mesh whenever both axes have room.  ``(1, 1)`` means "skip
    shard_map entirely".
    """
    d = jax.device_count()
    if n_groups <= 0 or n_rows <= 0 or d <= 1:
        return (1, 1)
    best = (1, 1)
    best_key = (1, 1, 1)
    for a in range(1, min(n_groups, d) + 1):
        b = min(n_rows, d // a)
        key = (a * b, min(a, b), b)
        if key > best_key:
            best, best_key = (a, b), key
    return best


def shard_grid(worker, grid_shards: Tuple[int, int],
               axis_names: Tuple[str, str] = ("mgr", "mix"),
               grid_specs=None):
    """shard_map ``worker(grid_tree, group_tree, replicated_tree)`` over a
    2-D (group x row) grid.

    ``grid_tree`` leaves carry two leading batch axes ``(K, M, ...)`` and
    shard on both mesh axes; ``group_tree`` leaves carry only the group
    axis ``(K, ...)`` (per-manager segment tables and knob flags) and
    shard on the first axis alone; ``replicated_tree`` is replicated.
    Callers pad K and M to multiples of the shard counts.  With
    ``grid_shards == (1, n)`` this degenerates to :func:`shard_rows` over
    the row axis (the single-group / single-device fallback); callers skip
    shard_map entirely at ``(1, 1)``.

    ``grid_specs`` optionally overrides the grid tree's partition specs
    with a pytree (prefix) of :class:`PartitionSpec` — for leaves whose
    grid axes are NOT leading (the serving engine's KV cache carries its
    slot axis at position 1, so its leaves use
    ``PartitionSpec(None, g, r)``).  The same specs describe the worker's
    outputs, which must mirror the grid tree's structure.
    """
    a, b = grid_shards
    devices = None
    if a * b < jax.device_count():
        devices = jax.devices()[: a * b]
    mesh = make_mesh((a, b), axis_names, devices=devices)
    g, r = axis_names
    if grid_specs is None:
        grid_specs = PartitionSpec(g, r)
    return shard_map(
        worker, mesh,
        in_specs=(grid_specs, PartitionSpec(g), PartitionSpec()),
        out_specs=grid_specs)


# Logical axis groups: "dp" spreads over every data-parallel mesh axis.
DP_AXES = ("pod", "data")


def set_dp_axes(axes) -> None:
    """Override which mesh axes count as data-parallel ("dp") — e.g.
    ("pod", "data", "model") for pure-DP tiny models."""
    _state.dp_axes = tuple(axes)


def get_dp_axes():
    return getattr(_state, "dp_axes", DP_AXES)


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def _resolve_axis(axis, mesh: Mesh):
    """Map a logical axis (or tuple) to the axes present in ``mesh``."""
    if axis is None:
        return None
    if axis == "dp":
        present = tuple(a for a in get_dp_axes() if a in mesh.axis_names)
        return present if present else None
    if isinstance(axis, tuple):
        present = tuple(a for a in axis if a in mesh.axis_names)
        return present if present else None
    return axis if axis in mesh.axis_names else None


def spec(*axes) -> PartitionSpec:
    """Build a PartitionSpec against the ambient mesh ("dp" = all DP axes).

    Mesh axes already claimed by an earlier entry are dropped from later
    entries (e.g. pure-DP mode resolves "dp" to ("data", "model"), so a
    subsequent explicit "model" entry becomes None)."""
    mesh = get_mesh()
    if mesh is None:
        return PartitionSpec(*([None] * len(axes)))
    used = set()
    out = []
    for a in axes:
        r = _resolve_axis(a, mesh)
        if r is None:
            out.append(None)
            continue
        if isinstance(r, tuple):
            r = tuple(x for x in r if x not in used)
            used.update(r)
            out.append(r if r else None)
        else:
            if r in used:
                out.append(None)
            else:
                used.add(r)
                out.append(r)
    return PartitionSpec(*out)


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint against the ambient mesh (no-op if none)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec(*axes)))
