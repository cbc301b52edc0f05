"""Multi-host (multi-slice / DCN) support for the distributed engine.

Reference parity: the reference's communication backend is Apache HttpClient
to Druid nodes plus ZooKeeper/Curator discovery (SURVEY.md §2 communication
row, §5 distributed-backend row `[U]`).  The TPU-native replacement has two
halves:

* **discovery / rendezvous** — `jax.distributed.initialize`: on Cloud TPU
  pods the coordinator and process ids come from the environment, on other
  fleets they are passed explicitly.  This replaces CuratorConnection: after
  it returns, `jax.devices()` spans every host's chips and the runtime owns
  membership (no ZK znodes to watch).
* **data placement** — inside one process `jax.device_put(host, sharding)`
  is enough; across processes each host only holds ITS rows (its
  "historical" segments), so global arrays are assembled with
  `jax.make_array_from_process_local_data` — each process contributes its
  addressable shards and XLA's collectives (ICI within a slice, DCN between
  slices) do the rest at execution time.

The collectives in `parallel/distributed.py` (`psum`/`pmin`/`pmax`/
`all_gather`) are mesh-topology-agnostic: on a multi-slice mesh built by
`hybrid_mesh()` the data axis maps to DCN (cheap per-device partials, one
small merged state crosses slices) and the groups axis to ICI, matching the
bandwidth hierarchy the way SURVEY.md §5 prescribes.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import numpy as np

from ..utils.log import get_logger

log = get_logger("parallel.multihost")

_initialized = False


def _env_names_a_cluster() -> bool:
    """Whether the environment describes a multi-process runtime to join.

    A coordinator address or a cluster launcher's job variables (the ones
    jax auto-detects: SLURM / Open MPI) do.  A Cloud TPU worker id alone
    does NOT: a single TPU host that drives all its chips from one process
    carries `TPU_WORKER_ID=0` too, with one name in its worker list
    (`TPU_WORKER_HOSTNAMES=localhost`, met on the four-chip v5e host).  It
    is a pod only when that list (`TPU_PROCESS_ADDRESSES`, else
    `TPU_WORKER_HOSTNAMES` — the order jax reads them in) names more than
    one worker, or the job spans slices."""
    env = os.environ
    if any(
        k in env
        for k in (
            "COORDINATOR_ADDRESS",
            "JAX_COORDINATOR_ADDRESS",
            "SLURM_JOB_ID",
            "OMPI_COMM_WORLD_SIZE",
        )
    ):
        return True
    if "TPU_WORKER_ID" not in env and "CLOUD_TPU_TASK_ID" not in env:
        return False
    workers = env.get("TPU_PROCESS_ADDRESSES") or env.get(
        "TPU_WORKER_HOSTNAMES", ""
    )
    n_workers = len([w for w in workers.split(",") if w.strip()])
    slices = env.get("MEGASCALE_NUM_SLICES", "")
    return n_workers > 1 or (slices.isdigit() and int(slices) > 1)


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join (or form) the multi-host JAX runtime.  The CuratorConnection
    analog: after this, discovery is done — `jax.devices()` is global.

    Safe to call unconditionally: single-process sessions (everything in
    this repo's tests, and any laptop use) return False without touching
    the runtime; repeated calls are no-ops.  Returns True when a
    multi-process runtime is (already) up.

    MUST run before any other JAX call — `jax.distributed.initialize`
    refuses once the XLA backend exists, so this function deliberately
    avoids `jax.process_count()`/`jax.devices()` until after the
    rendezvous."""
    global _initialized
    if _initialized:
        return True
    # a launcher may have formed the runtime before us; is_initialized()
    # inspects the distributed client without initializing the XLA backend
    if getattr(jax.distributed, "is_initialized", lambda: False)():
        _initialized = True
        return True
    if coordinator_address is None and num_processes is None:
        # no explicit rendezvous and no cluster in the environment: stay
        # single-process rather than hanging on a coordinator that will
        # never answer
        if not _env_names_a_cluster():
            return False
    try:
        # CPU backend: cross-process collectives need the Gloo transport
        # ("Multiprocess computations aren't implemented on the CPU
        # backend" otherwise) — must be set BEFORE the runtime forms.
        # Real TPU/GPU pods ignore it; a jax build without the flag (or
        # without Gloo) keeps the old failure mode at dispatch time.
        if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu"):
            try:
                jax.config.update(
                    "jax_cpu_collectives_implementation", "gloo"
                )
            except Exception:  # fault-ok: older/newer flagless builds
                pass
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
        log.info(
            "joined distributed runtime: process %d/%d, %d global devices",
            jax.process_index(), jax.process_count(), jax.device_count(),
        )
        return True
    except ValueError as err:
        # auto-detection found a cluster marker but not enough of the env
        # to form a rendezvous (e.g. SLURM_JOB_ID inside an interactive
        # salloc shell with no srun task vars): stay single-process — the
        # contract is "safe to call unconditionally"
        if coordinator_address is None and num_processes is None:
            log.info("cluster env not resolvable (%s); staying single-process", err)
            return False
        raise
    except RuntimeError as err:
        # tolerate a launcher that already initialized the distributed
        # runtime; surface "backend already initialized" (caller ran JAX
        # ops before rendezvous) — that one is a real ordering bug
        if "already initialized" in str(err).lower() and "backend" not in str(
            err
        ).lower():
            _initialized = True
            return True
        raise


def hybrid_mesh(n_groups: int = 1):
    """A (data, groups) mesh laid out for the DCN x ICI hierarchy.

    Multi-slice: the data axis spans slices over DCN (each slice aggregates
    its own rows; only the [G, M] partial state crosses DCN once per query
    — the broker-merge shape), the groups axis stays inside a slice on ICI.
    Single-slice / single-host: identical to `mesh.make_mesh`."""
    from jax.sharding import Mesh

    from .mesh import AXIS_NAMES, make_mesh

    if jax.process_count() <= 1:
        return make_mesh(n_groups=n_groups)
    from jax.experimental import mesh_utils

    n_dev = jax.device_count()
    devs = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(n_dev // jax.process_count() // n_groups, n_groups),
        dcn_mesh_shape=(jax.process_count(), 1),
        process_is_granule=True,
    )
    return Mesh(devs, AXIS_NAMES)


def put_sharded(host: np.ndarray, sharding) -> jax.Array:
    """Place a host array laid out GLOBALLY under `sharding`, multi-host
    aware.

    Single-process: plain `jax.device_put` (the fast path every test and
    single-chip session takes).  Multi-process: every process knows the
    global row layout (the catalog is deterministic), but only materializes
    and transfers the shards its own devices address —
    `make_array_from_callback` slices `host` per-device, so no host pays
    H2D for another slice's rows (the DruidRDD
    one-partition-per-historical analog)."""
    if jax.process_count() <= 1:
        return jax.device_put(host, sharding)
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx]
    )


def local_segments(segments) -> list:
    """This process's slice of a datasource's segments (round-robin by
    process index) — which rows each "historical" owns.  Deterministic so
    every process agrees on the global layout without coordination."""
    pc, pi = jax.process_count(), jax.process_index()
    if pc <= 1:
        return list(segments)
    return [s for i, s in enumerate(segments) if i % pc == pi]


def process_info() -> Dict[str, int]:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": jax.device_count(),
    }
