"""Device-mesh utilities for the sharded execution path (``backend="sharded"``).

The serving tier's micro-batch axis is embarrassingly parallel: B stacked
same-signature queries need no cross-query communication, so the batch axis
of a vmapped plan body can be split over a 1-D device mesh with ``shard_map``
and no operator changes. This module owns the mesh plumbing for that path:

* ``data_mesh``      — a 1-D mesh over the host's devices, batch axis only.
* ``batch_ways``     — total shard count over the mesh's batch axes.
* ``shard_spec``     — the batch PartitionSpec, via the same
                       divisibility-fitting policy the model stack uses
                       (``repro.models.sharding.batch_spec``): shard only
                       when the batch divides the device count, else
                       replicate.
* ``can_shard``      — eligibility predicate the plan cache and the serving
                       executor share: >1 device on the batch axes AND the
                       fitting policy actually sharded.
* ``mesh_signature`` — the mesh's contribution to compiled-plan cache keys.
* ``shard_batch``    — wrap a stacked-batch function in ``shard_map`` over
                       the mesh's batch axes.

It is also the *one* home of the intra-query partition arithmetic the
PartSpec layer uses (``repro.core.physical.PartSpec`` /
``PRepartition``): ``row_block`` / ``padded_capacity`` size the per-device
row blocks of a row-partitioned operator, ``hash_bucket`` is the join-key
bucketing function of hash-partitioned ``PJoin``, and
``shard_replicated`` wraps a whole partitioned plan body in ``shard_map``
with replicated inputs/outputs (the collectives live *inside* the plan as
explicit repartition ops). The production/host mesh builders formerly in
``repro.launch.mesh`` live here too — that module re-exports them — so
every mesh helper has exactly one definition.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.sharding import batch_axes, batch_spec

DATA_AXIS = "data"


def data_mesh(n_devices: Optional[int] = None, *,
              devices: Optional[Sequence] = None,
              axis: str = DATA_AXIS) -> Mesh:
    """A 1-D mesh over (a prefix of) the host's devices.

    The single axis is the micro-batch/data axis; there is no model axis —
    the sharded execution path replicates weights and splits only the
    stacked batch dimension. ``axis`` must be a name the batch-axis policy
    recognizes (``models.sharding.batch_axes``), otherwise the mesh would
    silently never shard anything.
    """
    if axis not in ("pod", DATA_AXIS):
        raise ValueError(
            f"axis {axis!r} is not a recognized batch axis "
            f"('pod'/'{DATA_AXIS}'): can_shard would always be False")
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if n_devices is not None:
        if not 1 <= n_devices <= len(devices):
            raise ValueError(
                f"n_devices={n_devices} out of range for "
                f"{len(devices)} visible device(s)")
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (axis,))


def batch_ways(mesh: Mesh) -> int:
    """Total shard count over the mesh's batch axes (pod x data)."""
    ways = 1
    for a in batch_axes(mesh):
        ways *= mesh.shape[a]
    return ways


def shard_spec(mesh: Mesh, batch_size: int) -> P:
    """Batch-axis PartitionSpec under the divisibility-fitting policy."""
    return batch_spec(mesh, batch_size)


def can_shard(mesh: Optional[Mesh], batch_size: int) -> bool:
    """True iff the mesh would actually split ``batch_size``: more than one
    device on the batch axes and the fitting policy sharded (batch divides
    the device count). Everything else falls back to the single-device
    vmapped program."""
    if mesh is None or batch_ways(mesh) <= 1:
        return False
    return any(ax is not None for ax in shard_spec(mesh, batch_size))


def mesh_signature(mesh: Mesh) -> str:
    """The mesh's contribution to a compiled-plan cache key: axis layout and
    per-axis size (device *identity* doesn't change the traced program)."""
    return "x".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)


def shard_batch(fn: Callable, mesh: Mesh) -> Callable:
    """``shard_map`` a stacked-batch function over the mesh's batch axes.

    ``fn`` takes / returns pytrees whose every leaf has the stacked batch as
    its leading axis; each device runs ``fn`` on its ``batch/ways`` slice.
    Callers must have checked ``can_shard`` — the spec here is
    unconditional. Weights and other closed-over arrays are replicated.
    """
    spec = P(batch_axes(mesh))
    # replication checking off: the plan body is arbitrary jnp code over
    # closed-over (replicated) weights; the checker rejects some primitives
    # it cannot type, and we never rely on its types
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


def shard_replicated(fn: Callable, mesh: Mesh) -> Callable:
    """``shard_map`` a *partitioned plan body* over the mesh: inputs and
    outputs are replicated (every device sees the full catalog tables and
    produces the full result), and all data movement happens through the
    explicit ``PRepartition`` collectives inside ``fn`` (slice /
    all_gather / psum against ``jax.lax.axis_index``). This is the
    single-oversized-query counterpart of ``shard_batch``: there is no
    stacked batch axis to split, the *operators* are partitioned instead.
    """
    spec = P()  # replicated in/out; movement is explicit inside the body
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


# ---------------------------------------------------------------------------
# intra-query partition arithmetic (the PartSpec layer's shared helpers)
# ---------------------------------------------------------------------------

def row_block(capacity: int, ways: int) -> int:
    """Per-device row-block size of a ``ways``-way row partition of a
    ``capacity``-row table: ``ceil(capacity / ways)`` — non-dividing
    capacities pad the tail with invalid rows (``padded_capacity``)."""
    if ways < 1:
        raise ValueError(f"ways must be >= 1, got {ways}")
    return -(-int(capacity) // ways)


def padded_capacity(capacity: int, ways: int) -> int:
    """Smallest multiple of ``row_block`` covering ``capacity``: the shape
    row-partitioned blocks re-concatenate to before the trailing padding
    rows (all invalid, all at the tail) are sliced off."""
    return row_block(capacity, ways) * ways


def hash_bucket(keys, ways: int):
    """Device bucket of each (integer) join key: ``key mod ways``.

    The single bucketing function of hash-partitioned joins — both join
    sides and the cost model must agree on it, so it lives here. ``jnp.mod``
    is non-negative for positive ``ways`` regardless of key sign."""
    return jnp.mod(jnp.asarray(keys, jnp.int32), jnp.int32(ways))


# ---------------------------------------------------------------------------
# production / host mesh builders (canonical home; repro.launch.mesh
# re-exports these — functions, never module-level constants: the dry-run
# must set XLA_FLAGS before any jax device state is touched)
# ---------------------------------------------------------------------------

def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data x 16 model). Multi-pod: 2 x 256."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: Optional[int] = None, model: int = 1):
    """Small mesh over the locally visible devices (tests / CPU runs)."""
    n = jax.device_count()
    data = data if data is not None else max(n // model, 1)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
