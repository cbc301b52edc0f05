"""The unified executor core's SPMD side: shard_map the arena (ISSUE 15).

PR 12's segment-stacked arena made the single-device path O(1) dispatches;
this module makes the SAME stacked `[B, R]` layout the one program the mesh
lowers too, so the mesh is a *placement strategy* over the arena rather
than a fork of the executor:

* **Device-major permuted stacking** — the datasource's segment blocks
  stack into ONE `[B_pad, R]` array per column, laid out so row-device
  ``d`` owns the cyclic canonical blocks ``{d, ndt+d, 2*ndt+d, ...}`` in
  its contiguous shard.  `B_pad = ndt * L` (zero blocks pad the tail), so
  a `NamedSharding` over the row axes gives every device an equal `[L, R]`
  block-stack with NO per-scope relayout: the layout is keyed on the FULL
  segment signature, never a query's pruned scope.
* **Scope as data, not shape** — a query's pruned uid set arrives as a
  per-block membership vector (a data input) plus a dynamic window start
  `j_lo` (also data).  Only the window LENGTH `Lk` — the scope size
  rounded up to device multiples — is a static program-key component, so
  two disjoint scopes of equal rounded size share one compiled program:
  the SPMD program-cache generality that per-scope shard layouts
  (`local_rows` keyed on the scope) traded away.  Compute still scales
  with the scope (the dynamic slice bounds the scan), keeping the r5->r6
  pruning win.
* **Fold inside the trace, merge at the boundary** — each device runs the
  exec/arena.py fold (`_member_init` / `_fold_block` / `finish_member`,
  imported — ONE fold implementation for both paths) over its local
  in-window blocks in canonical order, then the partial states merge with
  `psum`/`pmin`/`pmax` at the trace boundary.  A member whose blocks all
  live on other devices contributes exact identities (zeros for sums,
  ±inf-forced extrema), so the collective is exact for counts and
  min/max, and bit-exact for integer-valued f32 sums.
* **Merge trees** — on a virtual multi-slice mesh (`mesh.make_slice_mesh`)
  the boundary merge runs either FLAT (one psum over slice x data) or
  HIERARCHICAL (slice-local psum over ICI, then the merged state over the
  DCN slice axis), chosen per query by `plan.cost.choose_merge_tree` from
  the calibrated `collective_bytes_per_us` / `dcn_bytes_per_us` constants.
* **Deadline chunking** — with a wall-clock deadline armed, the scan
  splits into per-local-step chunk programs with the fold carry threaded
  through as a `[ndt, ...]` row-sharded array (per-shard stop-and-merge);
  a final merge program runs the boundary collectives.  Coverage is
  accounted host-side per step (the canonical blocks a step touches are
  known), summed across shards.

Builders here are pure (mesh + lowerings in, jitted program out); the
`DistributedEngine` caches them under structured query keys.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import SCOPE_BOUNDARY_MERGE, device_scope
from ..utils.log import get_logger
from .mesh import DATA_AXIS, SLICE_AXIS, row_axes

log = get_logger("parallel.spmd_arena")


class SpmdArenaLayout:
    """The device-major permuted stacking of one datasource's segments
    over `ndt` row devices.  Scope-independent: keyed on the FULL segment
    signature, it survives any query's pruning unchanged."""

    __slots__ = ("segs", "uids", "B", "R", "L", "B_pad", "ndt", "index")

    def __init__(self, segs, ndt: int):
        self.segs = list(segs)
        self.uids = tuple(s.uid for s in self.segs)
        self.B = len(self.segs)
        # a runt tail block (ingest's append-tail) stacks zero-padded to
        # the full row shape; its validity stack is False past its own
        # rows, so the masked fold is exact over the pad
        self.R = max(
            (s.num_rows_padded for s in self.segs), default=0
        )
        self.ndt = ndt
        self.L = -(-max(self.B, 1) // ndt)
        self.B_pad = ndt * self.L
        # canonical segment index by uid (scope -> membership translation)
        self.index = {s.uid: i for i, s in enumerate(self.segs)}

    def pos(self, b: int) -> int:
        """Stacked position of canonical block `b`: device-major, so
        device `b % ndt` holds it at local step `b // ndt`."""
        return (b % self.ndt) * self.L + b // self.ndt


def plan_spmd_layout(ds, ndt: int) -> Optional[SpmdArenaLayout]:
    """Layout decision for one datasource on `ndt` row devices, or None
    when the stacked layout cannot apply: fewer than two segments, or
    padded row counts that aren't the ingest append-tail pattern (equal
    blocks plus at most one shorter LAST block).  The tail block stacks
    zero-padded with False validity — exact under the masked fold — but
    arbitrary shape mixes would let one giant segment inflate every
    block's pad, so those keep the legacy per-shard path (the same
    shape discipline as exec/arena.plan_for, tail-tolerant)."""
    segs = list(ds.segments)
    if len(segs) < 2:
        return None
    shape0 = segs[0].num_rows_padded
    if any(s.num_rows_padded != shape0 for s in segs[:-1]):
        return None
    if segs[-1].num_rows_padded > shape0:
        return None
    return SpmdArenaLayout(segs, ndt)


def scope_window(
    layout: SpmdArenaLayout, canonical: Sequence[int]
) -> Tuple[int, int]:
    """(j_lo, Lk): the local-step window covering the scope's canonical
    block range.  `j_lo` rides as DATA; only `Lk` keys the program."""
    k0, k1 = min(canonical), max(canonical) + 1
    j_lo = k0 // layout.ndt
    j_hi = -(-k1 // layout.ndt)
    return j_lo, j_hi - j_lo


def membership_matrix(
    layout: SpmdArenaLayout, member_scopes: Sequence[Sequence[int]]
) -> np.ndarray:
    """Permuted `[B_pad, n_members]` block-membership flags from each
    member's canonical in-scope indices.  Pad blocks stay False."""
    memb = np.zeros((layout.B_pad, len(member_scopes)), dtype=bool)
    for i, scope in enumerate(member_scopes):
        for b in scope:
            memb[layout.pos(b), i] = True
    return memb


def stack_column(layout: SpmdArenaLayout, name: str) -> np.ndarray:
    """Host-side permuted `[B_pad, R]` stack of one column (zero blocks
    for the pad tail; their validity is False so they can never fold)."""
    seg0 = layout.segs[0]
    proto = np.asarray(
        seg0.valid if name == "__valid" else seg0.column(name)
    )
    out = np.zeros((layout.B_pad, layout.R), dtype=proto.dtype)
    for b, s in enumerate(layout.segs):
        arr = np.asarray(s.valid if name == "__valid" else s.column(name))
        # runt tail block: rows past the segment stay zero / False-valid
        out[layout.pos(b), : arr.shape[0]] = arr
    return out


def _row_spec_axes(mesh) -> Any:
    """The PartitionSpec element sharding a leading row-device axis."""
    axes = row_axes(mesh)
    return axes if len(axes) > 1 else axes[0]


def _merge_groups(mesh, tree: str) -> List[Tuple[str, ...]]:
    if tree == "hierarchical" and SLICE_AXIS in mesh.shape:
        return [(DATA_AXIS,), (SLICE_AXIS,)]
    return [tuple(row_axes(mesh))]


def _boundary_merge(mesh, tree: str, member_carry):
    """finish_member + the collective merge of one member's carry.
    Returns (sums, mins, maxs, live_count) — live_count is the number of
    shards that folded at least one block (0 => empty scope on every
    shard; the host substitutes `empty_partials`)."""
    import jax.numpy as jnp
    from jax import lax

    from ..exec.arena import finish_member

    s, mn, mx, live = finish_member(member_carry)
    # dead-shard identities: zeros are already exact for sums (the carry
    # is zero-seeded), but the extrema carries hold zeros too — force
    # them to the fold identities so pmin/pmax cannot pull a dead 0.0
    # into a live lane
    if mn.shape[1]:
        mn = jnp.where(live, mn, jnp.inf)
    if mx.shape[1]:
        mx = jnp.where(live, mx, -jnp.inf)
    with device_scope(SCOPE_BOUNDARY_MERGE):
        for axes in _merge_groups(mesh, tree):
            s = lax.psum(s, axes)
            if mn.shape[1]:
                mn = lax.pmin(mn, axes)
            if mx.shape[1]:
                mx = lax.pmax(mx, axes)
        live_n = lax.psum(live.astype(jnp.int32), tuple(row_axes(mesh)))
    return s, mn, mx, live_n


def boundary_merge_bytes(mesh, tree: str, merged) -> int:
    """Bytes a device sends in `_boundary_merge`, from the merged arrays
    it returned (`(sums, mins, maxs, live_n)`, as fetched): each state
    array is allreduced once per merge group of `tree`, the live count
    once over all row devices; an allreduce over n devices moves
    2(n-1)/n of the array (`plan.cost.allreduce_factor`)."""
    from ..plan.cost import allreduce_factor

    def over(axes) -> float:
        return allreduce_factor(int(np.prod([mesh.shape[a] for a in axes])))

    *state, live_n = merged
    state_factor = sum(over(axes) for axes in _merge_groups(mesh, tree))
    return round(
        state_factor * sum(np.asarray(a).nbytes for a in state)
        + over(row_axes(mesh)) * np.asarray(live_n).nbytes
    )


def build_spmd_arena_program(
    mesh,
    lowerings,
    strategies,
    Lk: int,
    tree: str = "flat",
    share=None,
):
    """The single-dispatch unified program: per-shard scanned fold over
    the `[Lk]` local-step window + boundary collective merge, ONE
    compiled XLA program.  Signature::

        fn(cols, j_lo, memb) -> ((sums, mins, maxs, live_n), ...) per member

    `cols` maps name -> `[B_pad, R]` row-sharded stack; `j_lo` is the
    replicated window start (data); `memb` is the `[B_pad, n]`
    row-sharded membership.  Nothing scope-shaped is baked into the
    trace, so one program serves every same-`Lk` scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..exec.arena import _fold_block, _member_init
    from ..exec.engine import _segment_partials

    n = len(lowerings)
    row_el = _row_spec_axes(mesh)
    no_start = np.False_  # plain left fold: no batch boundaries on a shard

    def shard_fn(cols, j_lo, memb):
        win = {
            k: lax.dynamic_slice_in_dim(v, j_lo, Lk, axis=0)
            for k, v in cols.items()
        }
        memb_w = lax.dynamic_slice_in_dim(memb, j_lo, Lk, axis=0)
        carry = tuple(_member_init(lw) for lw in lowerings)

        def body(c, xs):
            cols_b, memb_b = xs
            memo: Dict[Any, Any] = {}
            out = []
            for i in range(n):
                s, mn, mx, _sk = _segment_partials(
                    lowerings[i],
                    strategies[i],
                    dict(cols_b),
                    memo=memo if share is not None else None,
                    share=share[i] + (0,) if share is not None else None,
                )
                out.append(
                    _fold_block(c[i], (s, mn, mx), no_start, memb_b[i])
                )
            return tuple(out), None

        carry, _ = lax.scan(body, carry, (win, memb_w))
        return tuple(_boundary_merge(mesh, tree, c) for c in carry)

    in_specs = (P(row_el, None), P(), P(row_el, None))
    out_specs = tuple((P(), P(), P(), P()) for _ in range(n))
    # graftlint: disable=jit-cache -- caller caches under a query key
    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )


def init_carry_stacked(mesh, lowerings):
    """Zero-seeded `[ndt, ...]`-stacked fold carries for the chunked
    (deadline) mode, placed row-sharded so each device owns its slice."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    ndt = int(np.prod([mesh.shape[a] for a in row_axes(mesh)]))
    row_el = _row_spec_axes(mesh)

    def leaf(x):
        host = np.zeros((ndt,) + np.shape(x), np.asarray(x).dtype)
        return jax.device_put(host, NamedSharding(mesh, P(row_el)))

    out = []
    for lw in lowerings:
        la, G = lw.la, lw.num_groups
        z2 = np.zeros((G, len(la.sum_names)), np.float32)
        zn = np.zeros((G, len(la.min_names)), np.float32)
        zx = np.zeros((G, len(la.max_names)), np.float32)
        zb = np.zeros((), bool)
        member = (z2, zn, zx, zb) + (z2, zn, zx, zb)
        out.append(tuple(leaf(x) for x in member))
    return tuple(out)


def build_spmd_chunk_program(mesh, lowerings, strategies, share=None):
    """One deadline-mode chunk: fold ONE local step into the stacked
    carry.  `fn(carry, cols, j, memb) -> carry` — the carry is a
    `[ndt, ...]` row-sharded pytree threaded across dispatches, so a
    stop-and-merge truncation lands on a per-shard step boundary."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..exec.arena import _donate_carry, _fold_block
    from ..exec.engine import _segment_partials

    n = len(lowerings)
    row_el = _row_spec_axes(mesh)
    no_start = np.False_

    def shard_fn(carry, cols, j, memb):
        local = jax.tree.map(lambda x: x[0], carry)
        cols_b = {
            k: lax.dynamic_slice_in_dim(v, j, 1, axis=0)[0]
            for k, v in cols.items()
        }
        memb_b = lax.dynamic_slice_in_dim(memb, j, 1, axis=0)[0]
        memo: Dict[Any, Any] = {}
        out = []
        for i in range(n):
            s, mn, mx, _sk = _segment_partials(
                lowerings[i],
                strategies[i],
                dict(cols_b),
                memo=memo if share is not None else None,
                share=share[i] + (0,) if share is not None else None,
            )
            out.append(
                _fold_block(local[i], (s, mn, mx), no_start, memb_b[i])
            )
        return jax.tree.map(lambda x: x[None], tuple(out))

    in_specs = (P(row_el), P(row_el, None), P(), P(row_el, None))
    donate = {"donate_argnums": (0,)} if _donate_carry() else {}
    # graftlint: disable=jit-cache -- caller caches under a query key
    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=in_specs, out_specs=P(row_el),
            check_vma=False,
        ),
        **donate,
    )


def build_spmd_merge_program(mesh, lowerings, tree: str = "flat"):
    """Deadline-mode boundary merge: `fn(carry) -> per-member (sums,
    mins, maxs, live_n)` — the same collective merge the single-dispatch
    program fuses after its scan, run once after the chunk loop stops."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = len(lowerings)
    row_el = _row_spec_axes(mesh)

    def shard_fn(carry):
        local = jax.tree.map(lambda x: x[0], carry)
        return tuple(_boundary_merge(mesh, tree, c) for c in local)

    out_specs = tuple((P(), P(), P(), P()) for _ in range(n))
    # graftlint: disable=jit-cache -- caller caches under a query key
    return jax.jit(
        jax.shard_map(
            shard_fn, mesh=mesh, in_specs=(P(row_el),), out_specs=out_specs,
            check_vma=False,
        )
    )
