"""Device mesh construction for distributed OLAP aggregation.

Reference parity: the reference's "cluster" is Druid's broker + historicals
discovered via ZooKeeper (SURVEY.md §2 ZK-discovery row `[U]`); its
parallelism is one Spark partition per (historical, segment-group).  The
TPU-native equivalent is a `jax.sharding.Mesh` whose axes carry the two ways
an aggregation can be decomposed:

* ``data``   — row/segment shards (the historicals-analog; DP/SP axis).  Each
  device aggregates its rows; partial states merge with `psum`/`pmin`/`pmax`
  over ICI.
* ``groups`` — group-domain shards (the TP-analog).  Each device owns a slice
  of the group-id domain [0, G); useful when G is large enough that the
  one-hot block or the sketch state per group dominates memory.

Discovery is the JAX runtime (`jax.distributed` across hosts) — no ZooKeeper.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The ONLY axis names any mesh in this package declares.  Every
# collective / PartitionSpec in the tree is checked against these by
# graftlint's collective-axis pass (GL8xx) — add an axis here (or as a
# `*_AXIS` constant) before using it in an SPMD body.
DATA_AXIS = "data"
GROUPS_AXIS = "groups"
# Virtual multi-slice topology: the slice axis models the DCN-connected
# dimension of a multi-slice pod (each slice's devices talk over ICI;
# slices talk over DCN).  On a single-slice host it is a *virtual*
# partition of the device set used to exercise the hierarchical merge
# tree (`psum` over SLICE_AXIS is the DCN hop the cost model prices).
SLICE_AXIS = "slice"
AXIS_NAMES = (DATA_AXIS, GROUPS_AXIS)
SLICE_AXIS_NAMES = (SLICE_AXIS, DATA_AXIS)


def make_mesh(
    n_data: Optional[int] = None,
    n_groups: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create a (data, groups) mesh.  Defaults to all devices on the data
    axis.  With multi-host meshes the data axis should map to the
    DCN-connected dimension and groups to ICI (group-state merges are the
    bandwidth-heavy collective)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devs) // n_groups
    if n_data * n_groups != len(devs):
        devs = devs[: n_data * n_groups]
    arr = np.array(devs).reshape(n_data, n_groups)
    return Mesh(arr, AXIS_NAMES)


def make_slice_mesh(
    n_slices: int,
    n_data: Optional[int] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create a (slice, data) mesh — the virtual multi-slice topology.

    The slice axis is outermost so contiguous device ranges form a slice
    (matching how `create_hybrid_device_mesh` granules a real pod: a
    slice's devices are ICI-adjacent, the slice axis is the DCN hop).
    Row shards are placed over BOTH axes — the arena treats the flattened
    (slice*data) product as its row-device count — and the merge tree
    decides whether the partial-state `psum` runs flat over both axes or
    hierarchically (data first, then slice)."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_slices < 1:
        raise ValueError("n_slices must be >= 1")
    if n_data is None:
        n_data = len(devs) // n_slices
    if n_data < 1 or n_slices * n_data > len(devs):
        raise ValueError(
            "slice mesh %dx%d needs %d devices, have %d"
            % (n_slices, n_data, n_slices * n_data, len(devs))
        )
    arr = np.array(devs[: n_slices * n_data]).reshape(n_slices, n_data)
    return Mesh(arr, SLICE_AXIS_NAMES)


def row_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes rows are sharded over: (slice, data) on a slice mesh,
    (data,) on the standard mesh.  Collectives that merge per-device row
    partials reduce over exactly these axes."""
    if SLICE_AXIS in mesh.shape:
        return (SLICE_AXIS, DATA_AXIS)
    return (DATA_AXIS,)


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
