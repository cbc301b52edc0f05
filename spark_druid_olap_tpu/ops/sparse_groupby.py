"""Sort-compaction GroupBy for high-cardinality group domains.

TPUs hate scatter: above ~4k groups the engine's fallback is
`jax.ops.segment_sum`, whose serialized conflicting updates make it ~5-10x
slower than the dense one-hot kernel (measured on SSB q3_x/q4_3, SURVEY.md
§7 hard-part #1).  But the OLAP reality those queries embody is a *huge
combined domain with few distinct groups actually present* (city x city x
year = 437k cells, ~700 populated after filters).  So: compact first, then
go dense.

    gid in [0, G)  --jnp.unique(size=SLOTS)-->  slot in [0, SLOTS)
                   --dense/Pallas one-hot over SLOTS--> [SLOTS, M] partials
                   + uniq[SLOTS] mapping slot -> original gid

The sort inside `unique` is TPU-friendly (bitonic, no scatter), and the
one-hot matmul over <=4096 slots rides the MXU like any low-cardinality
query.  Partial states stay sparse across segment merges (concat + re-unique
+ tiny scatter over 2*SLOTS rows).  If a block holds more distinct groups
than SLOTS, `unique` would silently truncate — every row whose gid got
dropped maps to a wrong slot — so each kernel also emits an `overflow` flag
(any row whose slot doesn't round-trip to its gid); the engine checks it at
fetch time and reruns the query on the scatter path.  Sparse states use
gid = -1 for empty/trash slots.

The reference has no analog (Druid's historicals do hash aggregation in
JVM); this is the TPU-native replacement for that engine interior.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import SCOPE_SPARSE_SORT, device_scope
from .groupby import partial_aggregate

SPARSE_SLOTS = 4096

# Slot-capacity rungs for the HIGH-POPULATED tier (VERDICT r3 #2: the
# sort-agg half of SURVEY.md §7 hard-part #1).  Up to SPARSE_SLOTS the inner
# aggregation is the dense/Pallas one-hot over slots; past it, the
# segmented-reduce-over-ranks kernel below scales to ~2M genuinely populated
# groups.  Past the top rung the engine falls back to raw scatter.
SLOTS_LADDER = (SPARSE_SLOTS, 1 << 15, 1 << 18, 1 << 21)

# Row capacity of the filter-compaction stage: selective queries (the normal
# OLAP case that reaches the sparse path — think city-level predicates over a
# nation) compact surviving rows into this many slots BEFORE the sort, so the
# bitonic sort network runs over 128K rows instead of the full segment.  A
# multiple of 1024 (ROW_PAD) so the inner one-hot blocks divide evenly.
ROW_CAPACITY = 1 << 17

# Capacity rungs.  The engine picks the INITIAL rung from the planner's
# selectivity estimate (x2 headroom) — a q3_2-class segment with ~700
# survivors sorts 4K slots, not 128K (the fixed 128K floor cost ~35 ms of
# sort PER SEGMENT, which at SF100's ~1000 segments was the whole sparse
# budget).  On overflow the kernel's exact survivor count (`n_rows`) picks
# the smallest adequate rung (full-segment sort only past the top): sort
# cost grows roughly linearly with capacity (an ESTIMATE from the
# O(n log n) sort bound — no committed TPU artifact backs a measured
# number yet).
ROW_CAPACITY_LADDER = (
    1 << 12, 1 << 14, 1 << 17, 1 << 18, 1 << 19, 1 << 20, 1 << 21
)


def compact_rows(
    gid: jnp.ndarray,
    mask: jnp.ndarray,
    sum_values: jnp.ndarray,
    minmax_values: jnp.ndarray,
    minmax_masks: jnp.ndarray,
    capacity: int,
):
    """Pack rows where mask is True into `capacity` slots (stable order).

    TPU-idiomatic: one cumsum + one vectorized binary search + gathers — no
    R-sized scatter, no sort.  Slot i holds the i-th surviving row (the first
    position whose running count reaches i+1).  Slots past the survivor count
    duplicate an arbitrary row with their mask cleared, so downstream
    aggregation ignores them.  Returns (*compacted arrays, row_overflow, n)
    — row_overflow set when survivors exceed capacity (the caller must rerun
    at a bigger capacity; compacted state would silently drop rows), and n
    is the exact survivor count so the engine can pick that capacity from
    ROW_CAPACITY_LADDER without guessing."""
    R = gid.shape[0]
    c = jnp.cumsum(mask.astype(jnp.int32))
    n = c[-1]
    row_overflow = n > capacity
    idx = jnp.searchsorted(
        c, jnp.arange(1, capacity + 1, dtype=jnp.int32), side="left"
    )
    idx = jnp.minimum(idx, R - 1)
    new_mask = jnp.arange(capacity, dtype=jnp.int32) < n
    return (
        gid[idx],
        new_mask,
        sum_values[idx],
        minmax_values[idx],
        minmax_masks[idx],
        row_overflow,
        n,
    )


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "block_rows", "num_min", "num_max"),
)
def segmented_reduce_sorted(
    slot: jnp.ndarray,  # i32[R] run index per SORTED row: nondecreasing, +<=1/row
    mask: jnp.ndarray,  # bool[R]
    sum_values: jnp.ndarray,  # f32[R, Ms] pre-masked
    minmax_values: jnp.ndarray,  # f32[R, Mnx]
    minmax_masks: jnp.ndarray,  # bool[R, Mnx]
    capacity: int,
    block_rows: int,
    num_min: int,
    num_max: int,
):
    """Per-run aggregation over rows already sorted by group — the sort-agg
    tier of SURVEY.md §7 hard-part #1, for group domains too populated for a
    one-hot over slots (> SPARSE_SLOTS distinct present).

    TPU-first: because `slot` (the run index from the caller's sort) is
    nondecreasing and grows by at most 1 per row, any B consecutive rows
    span at most B distinct runs.  So each B-row block one-hot-matmuls
    against its LOCAL run offsets (a [B, B] MXU contraction — no scatter)
    and accumulates into the output window [base, base+B) with a contiguous
    dynamic-slice read-modify-write.  A run straddling two blocks is summed
    by both partial windows — addition/min/max identities make that exact.
    Total MXU work is B FLOPs/row/agg regardless of how many groups exist.

    Returns (sums[capacity, Ms], mins[capacity, Mn], maxs[capacity, Mx]).
    The caller guarantees slot < capacity (clamped); rows whose run was
    clamped land in the last slot, which the caller treats as overflow.
    """
    R = slot.shape[0]
    B = block_rows
    pad_rows = (-R) % B
    if pad_rows:
        # repeat the final slot (keeps the nondecreasing invariant) with
        # mask off so padding never contributes
        slot = jnp.concatenate(
            [slot, jnp.broadcast_to(slot[-1], (pad_rows,))]
        )
        mask = jnp.concatenate([mask, jnp.zeros(pad_rows, jnp.bool_)])
        sum_values = jnp.concatenate(
            [sum_values, jnp.zeros((pad_rows,) + sum_values.shape[1:],
                                   sum_values.dtype)]
        )
        minmax_values = jnp.concatenate(
            [minmax_values,
             jnp.zeros((pad_rows,) + minmax_values.shape[1:],
                       minmax_values.dtype)]
        )
        minmax_masks = jnp.concatenate(
            [minmax_masks,
             jnp.zeros((pad_rows,) + minmax_masks.shape[1:], jnp.bool_)]
        )
        R += pad_rows
    nb = R // B
    Ms = sum_values.shape[1]

    slot_b = slot.reshape(nb, B)
    mask_b = mask.reshape(nb, B)
    sumv_b = sum_values.reshape(nb, B, Ms)
    mmv_b = minmax_values.reshape(nb, B, -1)
    mmm_b = minmax_masks.reshape(nb, B, -1)

    iota = lax.iota(jnp.int32, B)
    padded = capacity + B  # windows near the tail stay in-bounds
    init = (
        jnp.zeros((padded, Ms), jnp.float32),
        jnp.full((padded, num_min), jnp.inf, jnp.float32),
        jnp.full((padded, num_max), -jnp.inf, jnp.float32),
    )

    def body(carry, xs):
        sums, mins, maxs = carry
        s, m, sv, mmv, mmm = xs
        base = s[0]
        z = jnp.zeros((), base.dtype)  # start indices must share one dtype
        local = s - base  # in [0, B): nondecreasing, +<=1 over B rows
        match = (local[:, None] == iota[None, :]) & m[:, None]  # [B, B]
        onehot = match.astype(jnp.float32)
        block_sums = lax.dot(
            onehot.T, sv, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        win = lax.dynamic_slice(sums, (base, z), (B, Ms))
        sums = lax.dynamic_update_slice(sums, win + block_sums, (base, z))
        if num_min:
            v = mmv[:, :num_min]
            mm = m[:, None] & mmm[:, :num_min]
            # dtype-matched inf fill (weak floats promote to f64 under x64
            # — graftlint dtype-x64/GL303)
            w = jnp.where(
                match[:, :, None] & mm[:, None, :], v[:, None, :],
                jnp.asarray(jnp.inf, dtype=v.dtype),
            ).min(axis=0)
            win = lax.dynamic_slice(mins, (base, z), (B, num_min))
            mins = lax.dynamic_update_slice(
                mins, jnp.minimum(win, w), (base, z)
            )
        if num_max:
            v = mmv[:, num_min:]
            mm = m[:, None] & mmm[:, num_min:]
            w = jnp.where(
                match[:, :, None] & mm[:, None, :], v[:, None, :],
                jnp.asarray(-jnp.inf, dtype=v.dtype),
            ).max(axis=0)
            win = lax.dynamic_slice(maxs, (base, z), (B, num_max))
            maxs = lax.dynamic_update_slice(
                maxs, jnp.maximum(win, w), (base, z)
            )
        return (sums, mins, maxs), None

    (sums, mins, maxs), _ = lax.scan(
        body, init, (slot_b, mask_b, sumv_b, mmv_b, mmm_b)
    )
    return sums[:capacity], mins[:capacity], maxs[:capacity]


def sparse_partial_aggregate(
    gid: jnp.ndarray,
    mask: jnp.ndarray,
    sum_values: jnp.ndarray,
    minmax_values: jnp.ndarray,
    minmax_masks: jnp.ndarray,
    *,
    num_groups: int,
    num_min: int,
    num_max: int,
    slots: int = SPARSE_SLOTS,
    inner_strategy: str = "auto",
    row_capacity: Optional[int] = None,
) -> Dict[str, jnp.ndarray]:
    """Compact gids to slots, aggregate dense over slots.

    With `row_capacity`, surviving rows are first packed through
    `compact_rows` so the sort network covers `row_capacity` rows instead of
    R (the selective-filter fast path); `row_overflow` in the result tells
    the engine the capacity was exceeded and the state is unusable.

    Returns {"gids": i32[slots] (-1 = empty/trash), "sums": f32[slots, Ms],
    "mins": f32[slots, Mn], "maxs": f32[slots, Mx], "overflow": bool[],
    "row_overflow": bool[], "n_rows": i32[] exact survivor count}.
    """
    G = num_groups
    gid = gid.astype(jnp.int32)  # no-op guard: see partial_aggregate
    row_overflow = jnp.zeros((), jnp.bool_)
    if row_capacity is not None and row_capacity < gid.shape[0]:
        (
            gid, mask, sum_values, minmax_values, minmax_masks,
            row_overflow, n_rows,
        ) = compact_rows(
            gid, mask, sum_values, minmax_values, minmax_masks,
            row_capacity,
        )
    else:
        n_rows = jnp.sum(mask.astype(jnp.int32))
    R = gid.shape[0]
    n_state = slots + 1  # + 1 so the masked-row trash run never eats a slot
    g = jnp.where(mask, gid, jnp.int32(G))  # trash value for masked rows
    # TPU-idiomatic compaction: one argsort, then ONLY gathers — no R-sized
    # scatter (what jnp.unique's return_inverse would cost us).  The row
    # values ride the permutation instead of the slot ids riding an inverse.
    with device_scope(SCOPE_SPARSE_SORT):
        order = jnp.argsort(g)
        sg = g[order]
    firsts = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sg[1:] != sg[:-1]]
    )
    ranks = jnp.cumsum(firsts.astype(jnp.int32)) - 1  # run index per row
    n_distinct = ranks[-1] + 1
    # the trash run (all gid==G) sorts last, so it never displaces a real
    # group; capacity is `slots` REAL groups exactly
    n_real = n_distinct - (sg[-1] == G).astype(jnp.int32)
    overflow = n_real > slots  # clipped slots hold garbage -> rerun
    slot_sorted = jnp.minimum(ranks, n_state - 1)
    # first sorted position of each run -> that slot's gid
    pos = jnp.nonzero(firsts, size=n_state, fill_value=R)[0]
    uniq = jnp.where(
        pos < R, sg[jnp.minimum(pos, R - 1)], jnp.int32(G)
    )
    if slots > SPARSE_SLOTS and inner_strategy not in ("segment", "scatter"):
        # high-populated tier: a one-hot over `slots` would blow VMEM; the
        # rows are already sorted by run, so segmented-reduce them
        sums, mins, maxs = segmented_reduce_sorted(
            slot_sorted,
            mask[order],
            sum_values[order],
            minmax_values[order],
            minmax_masks[order],
            capacity=n_state,
            block_rows=1024,
            num_min=num_min,
            num_max=num_max,
        )
    else:
        sums, mins, maxs = partial_aggregate(
            slot_sorted,
            mask[order],
            sum_values[order],
            minmax_values[order],
            minmax_masks[order],
            num_groups=n_state,
            num_min=num_min,
            num_max=num_max,
            strategy=inner_strategy,
        )
    gids = jnp.where(uniq >= G, jnp.int32(-1), uniq.astype(jnp.int32))
    return {
        "gids": gids,
        "sums": sums,
        "mins": mins,
        "maxs": maxs,
        "overflow": overflow,
        "row_overflow": row_overflow,
        "n_rows": n_rows,
        # exact distinct-present count (when not overflowed): the engine's
        # slot-ladder rung selector reads it instead of guessing
        "n_real": n_real,
    }


@functools.partial(jax.jit, static_argnames=("num_groups",))
def merge_sparse_states(
    a: Dict[str, jnp.ndarray],
    b: Dict[str, jnp.ndarray],
    num_groups: int,
) -> Dict[str, jnp.ndarray]:
    """Merge two sparse partial states (same slot count) into one.

    concat -> re-unique -> scatter over 2*n_state rows (tiny, scatter is
    fine at this size).  Empty slots carry the merge identities
    (+inf/-inf/0), so they never contaminate a real slot they get co-mapped
    with.  State arrays are slots+1 long (see sparse_partial_aggregate), so
    `slots` real gids plus the shared empty/trash sentinel always fit —
    round-trip mismatch therefore fires exactly when real distinct > slots."""
    n_state = a["gids"].shape[0]
    G = num_groups
    cg = jnp.concatenate([a["gids"], b["gids"]])
    cg = jnp.where(cg < 0, jnp.int32(G), cg)  # sentinel back to sortable form
    uniq, inv = jnp.unique(
        cg, size=n_state, fill_value=jnp.int32(G), return_inverse=True
    )
    inv = inv.reshape(cg.shape)
    overflow = (
        a["overflow"] | b["overflow"] | jnp.any(uniq[inv] != cg)
    )
    sums = (
        jnp.zeros((n_state,) + a["sums"].shape[1:], a["sums"].dtype)
        .at[inv]
        .add(jnp.concatenate([a["sums"], b["sums"]]))
    )
    mins = (
        jnp.full((n_state,) + a["mins"].shape[1:], jnp.inf, a["mins"].dtype)
        .at[inv]
        .min(jnp.concatenate([a["mins"], b["mins"]]))
    )
    maxs = (
        jnp.full((n_state,) + a["maxs"].shape[1:], -jnp.inf, a["maxs"].dtype)
        .at[inv]
        .max(jnp.concatenate([a["maxs"], b["maxs"]]))
    )
    gids = jnp.where(uniq >= G, jnp.int32(-1), uniq.astype(jnp.int32))
    # distinct-present in the merged state: exact from the unique when it
    # fit.  When truncation makes the exact count unknowable, report
    # max(a, b) — a LOWER bound.  (ADVICE r4: the a+b upper bound inflated
    # by up to N over N same-group segments, making the rung selector skip
    # workable SLOTS_LADDER rungs or decline outright; with a lower bound
    # the engine ladders up one rung at a time instead — see
    # exec/sparse_exec.fetch_slot_laddered.)
    exact = jnp.sum((uniq < G).astype(jnp.int32))
    n_real = jnp.where(
        overflow, jnp.maximum(a["n_real"], b["n_real"]), exact
    )
    return {
        "gids": gids,
        "sums": sums,
        "mins": mins,
        "maxs": maxs,
        "overflow": overflow,
        "row_overflow": a["row_overflow"] | b["row_overflow"],
        # max, not sum: capacity is per-segment, so the rung the engine picks
        # must cover the worst single segment
        "n_rows": jnp.maximum(a["n_rows"], b["n_rows"]),
        "n_real": n_real,
    }
