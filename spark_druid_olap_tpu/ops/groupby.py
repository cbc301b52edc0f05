"""Partial GroupBy aggregation on TPU — the engine the reference outsourced.

Reference parity: in spark-druid-olap the GroupBy work happens inside external
Druid historicals (per-segment partial aggregates) and the broker merges
partials (SURVEY.md §2 scatter-gather row, §3.3 `[U]`).  This module is the
per-device *historical*: it computes partial aggregate states for one shard of
rows.  `parallel/merge.py` is the *broker*: it merges partials across devices
with ICI collectives.

TPU-first design (SURVEY.md §7 hard-part #1 — "TPUs hate scatter"):

* **Dense one-hot matmul strategy** (default, the common OLAP case): group
  keys are dictionary codes with known cardinality, so the combined group id
  lives in a dense domain [0, G).  A row-block's one-hot matrix
  ``onehot[B, G] = (gid[:, None] == iota(G))`` contracted with the value block
  ``values[B, M]`` on the MXU gives exact per-group sums — an einsum, not a
  scatter.  `lax.scan` over row blocks keeps peak memory at B*G while XLA
  pipelines HBM reads.  min/max use the same match matrix with a masked
  where+reduce (VPU).  This is the standard TPU trick for segment reductions
  and maps 100% of the FLOPs onto the MXU.
* **Segment-scatter strategy** (fallback for very large G where a B×G block
  would blow VMEM/HBM): `jax.ops.segment_sum/min/max` — XLA scatter; slower
  per-row but memory-linear.  The cost model (plan/cost.py) picks the
  strategy from G; see `choose_block_rows`.

Determinism / parity (SURVEY.md §7 hard-part #2): block order inside the scan
is fixed and the matmul reduction order per block is fixed by XLA, so a given
(shard, block size) always produces bit-identical float sums; cross-device
merge order is fixed by the collective.  Tests compare against a float64 numpy
oracle with tight rtol; counts/min/max are exact.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import SCOPE_PARTIAL_AGG, device_scope

# One f32 VMEM tile is (8, 128); one-hot blocks are multiples of both.
_LANE = 128

# Above this combined cardinality the one-hot block no longer fits comfortably
# and we fall back to scatter.  2^17 groups * 1024 rows * 4B = 512MB/block at
# B=1024 — still too big, so the real bound is applied via choose_block_rows;
# this constant bounds G for the dense strategy overall.
DENSE_MAX_GROUPS = 1 << 17

# The one-hot cap and the high-cardinality tiers' eligibility line; nothing
# measures or replaces it.  Up to it a TPU's dense class runs as the Pallas
# kernel (plan/cost.concrete_kernel), the model offers that class no further
# (cost.dense_class_cap), and "auto" resolves dense; past it a query with
# dimensions may take the adaptive or the sparse tier (cost.tier_takes).
# Below it, dense against scatter is the calibrated model's choice
# (plan/cost.shape_kernel), not this constant's.
SCATTER_CUTOVER = 4096


def combine_group_ids(
    codes: Sequence[jnp.ndarray], cards: Sequence[int]
) -> Tuple[jnp.ndarray, int]:
    """Row-major combine N dictionary-code columns into one dense group id.

    gid = ((c0 * card1) + c1) * card2 + c2 ...   Null codes (-1) are clamped
    into slot 0 and must be masked by the caller (the engine adds a
    `code >= 0` conjunct to the filter mask unless nulls are grouped).
    """
    G = 1
    for c in cards:
        G *= int(c)
    gid = None
    for code, card in zip(codes, cards):
        # width choke point: codes may be STORED at int8/int16
        # (catalog.segment.code_dtype); every combined gid is int32
        c = jnp.maximum(code.astype(jnp.int32), 0)
        gid = c if gid is None else gid * jnp.int32(card) + c
    if gid is None:
        gid = jnp.zeros((), jnp.int32)
    return gid, G


def choose_block_rows(num_rows: int, num_groups: int,
                      vmem_budget_bytes: int = 32 << 20) -> int:
    """Pick the scan block size so the one-hot block fits the VMEM budget.

    B*G*4 bytes <= budget, B a multiple of 1024 (ROW_PAD), clamped to
    [1024, num_rows]."""
    b = vmem_budget_bytes // max(4 * num_groups, 1)
    b = max(1024, (b // 1024) * 1024)
    return int(min(b, max(num_rows, 1024)))


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "block_rows", "num_min", "num_max"),
)
def dense_partial_aggregate(
    gid: jnp.ndarray,  # int32[R]
    mask: jnp.ndarray,  # bool[R] — filter ∧ validity
    sum_values: jnp.ndarray,  # f32[R, Ms] — per-agg masked values (0 if excluded)
    minmax_values: jnp.ndarray,  # f32[R, Mn+Mx] — raw values for min/max aggs
    minmax_masks: jnp.ndarray,  # bool[R, Mn+Mx] — per-agg masks for min/max
    num_groups: int,
    block_rows: int,
    num_min: int,
    num_max: int,
):
    """One-hot-matmul partial aggregation over row blocks.

    Returns (sums[G, Ms], mins[G, Mn], maxs[G, Mx]).  `sum_values` columns are
    pre-masked by the caller (value * mask, and FilteredAgg extra masks), so
    the matmul with the bool one-hot is exact.  Count aggs pass a pre-masked
    ones column.  Empty groups: sums 0, mins +inf, maxs -inf (finalizer maps
    them to null).
    """
    R = gid.shape[0]
    assert R % block_rows == 0, (R, block_rows)
    nb = R // block_rows
    Ms = sum_values.shape[1]
    Mnx = minmax_values.shape[1]

    gid_b = gid.reshape(nb, block_rows)
    mask_b = mask.reshape(nb, block_rows)
    sumv_b = sum_values.reshape(nb, block_rows, Ms)
    mmv_b = minmax_values.reshape(nb, block_rows, Mnx)
    mmm_b = minmax_masks.reshape(nb, block_rows, Mnx)

    iota = lax.iota(jnp.int32, num_groups)

    init = (
        jnp.zeros((num_groups, Ms), jnp.float32),
        jnp.full((num_groups, num_min), jnp.inf, jnp.float32),
        jnp.full((num_groups, num_max), -jnp.inf, jnp.float32),
    )

    def body(carry, xs):
        sums, mins, maxs = carry
        g, m, sv, mmv, mmm = xs
        match = (g[:, None] == iota[None, :]) & m[:, None]  # bool[B, G]
        onehot = match.astype(jnp.float32)
        # MXU: [G, B] @ [B, Ms] with f32 accumulation.
        sums = sums + lax.dot(
            onehot.T, sv, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        if num_min:
            v = mmv[:, :num_min]
            mm = m[:, None] & mmm[:, :num_min]
            # [B, G, Mn] masked-where then reduce rows — VPU, B*G*Mn elems.
            # inf fills are dtype-matched: a weak Python float promotes the
            # select to f64 under x64 (graftlint dtype-x64/GL303)
            w = jnp.where(
                match[:, :, None] & mm[:, None, :], v[:, None, :],
                jnp.asarray(jnp.inf, dtype=v.dtype),
            )
            mins = jnp.minimum(mins, w.min(axis=0))
        if num_max:
            v = mmv[:, num_min:]
            mm = m[:, None] & mmm[:, num_min:]
            w = jnp.where(
                match[:, :, None] & mm[:, None, :], v[:, None, :],
                jnp.asarray(-jnp.inf, dtype=v.dtype),
            )
            maxs = jnp.maximum(maxs, w.max(axis=0))
        return (sums, mins, maxs), None

    (sums, mins, maxs), _ = lax.scan(
        body, init, (gid_b, mask_b, sumv_b, mmv_b, mmm_b)
    )
    return sums, mins, maxs


@functools.partial(
    jax.jit, static_argnames=("num_groups", "num_min", "num_max")
)
def scatter_partial_aggregate(
    gid: jnp.ndarray,
    mask: jnp.ndarray,
    sum_values: jnp.ndarray,
    minmax_values: jnp.ndarray,
    minmax_masks: jnp.ndarray,
    num_groups: int,
    num_min: int = 0,
    num_max: int = 0,
):
    """Fallback strategy: XLA scatter (`segment_sum`) — memory-linear in G.

    Used when G is too large for one-hot blocks (cost model decision,
    the analog of the reference's cost-model broker-vs-historicals choice)."""
    # no-op guard (producers are int32 today): a narrow gid would wrap on
    # this trash-slot write, so widen before it
    seg = jnp.where(mask, gid.astype(jnp.int32), num_groups)
    sums = jax.ops.segment_sum(
        sum_values, seg, num_segments=num_groups + 1
    )[:num_groups]
    mins = jnp.zeros((num_groups, num_min), jnp.float32)
    maxs = jnp.zeros((num_groups, num_max), jnp.float32)
    if num_min + num_max:
        Mn = num_min
        # dtype-matched inf fills (weak floats promote to f64 under x64 —
        # graftlint dtype-x64/GL303)
        pos = jnp.asarray(jnp.inf, dtype=minmax_values.dtype)
        if Mn:
            v = jnp.where(minmax_masks[:, :Mn], minmax_values[:, :Mn], pos)
            mins = jax.ops.segment_min(v, seg, num_segments=num_groups + 1)[
                :num_groups
            ]
        Mx = minmax_values.shape[1] - Mn
        if Mx:
            v = jnp.where(minmax_masks[:, Mn:], minmax_values[:, Mn:], -pos)
            maxs = jax.ops.segment_max(v, seg, num_segments=num_groups + 1)[
                :num_groups
            ]
    return sums, mins, maxs


def resolve_strategy(strategy: str, num_groups: int) -> str:
    """What a bare `partial_aggregate(strategy="auto")` runs: the kernel
    library's own default.  The engines do not call it: which kernel a
    query runs is chosen in plan/cost.py and handed down."""
    if strategy != "auto":
        return strategy
    if num_groups > SCATTER_CUTOVER:
        return "segment"
    from .pallas_groupby import pallas_available

    if pallas_available():
        return "pallas"
    return "dense"


@device_scope(SCOPE_PARTIAL_AGG)
def partial_aggregate(
    gid,
    mask,
    sum_values,
    minmax_values,
    minmax_masks,
    num_groups: int,
    num_min: int,
    num_max: int,
    strategy: str = "auto",
    block_rows: Optional[int] = None,
):
    """Strategy dispatcher.  'auto' uses the Pallas kernel on TPU (dense
    one-hot in VMEM) up to SCATTER_CUTOVER groups (the XLA dense scan on
    non-TPU backends), and the scatter/segment path above it.

    Who masks the sums.  "dense" and "segment" take `sum_values` as
    `f32[R, Ms]` with every column already multiplied by `mask` (a count
    is a column of the mask's 0/1): the one-hot scan contracts it as it
    is, the scatter sends masked rows to a trash slot but adds them there.
    "pallas" masks by the group id inside the kernel and makes a count
    from its match tile: it takes one entry a sum column, an unmasked
    `[R]` row or `None` for a count
    (`exec/lowering.py row_arrays(strategy="pallas")`), and the array
    form as well, masked or not (`ops/pallas_groupby.py`).

    Every current producer (combine_group_ids, the lowering codes_fns)
    already yields int32 gids; the astype below is a free no-op guard so a
    FUTURE narrow-width producer cannot wrap in trash-slot writes like
    `where(mask, gid, num_groups)`."""
    gid = gid.astype(jnp.int32)
    if strategy == "auto":
        strategy = resolve_strategy("auto", num_groups)
    if strategy == "pallas":
        from .pallas_groupby import pallas_available, pallas_partial_aggregate

        # interpret mode is what CPU tests ask for by naming the strategy;
        # on a TPU the kernel is always compiled
        return pallas_partial_aggregate(
            gid, mask, sum_values, minmax_values, minmax_masks,
            num_groups=num_groups, num_min=num_min, num_max=num_max,
            interpret=not pallas_available(),
        )
    if strategy in ("dense", "onehot"):
        br = block_rows or choose_block_rows(gid.shape[0], num_groups)
        # shrink to divide R (segments are ROW_PAD-padded so 1024 always divides)
        R = gid.shape[0]
        while R % br:
            br -= 1024
        br = max(br, 1024)
        return dense_partial_aggregate(
            gid, mask, sum_values, minmax_values, minmax_masks,
            num_groups=num_groups, block_rows=br,
            num_min=num_min, num_max=num_max,
        )
    if strategy in ("segment", "scatter"):
        return scatter_partial_aggregate(
            gid, mask, sum_values, minmax_values, minmax_masks,
            num_groups=num_groups, num_min=num_min, num_max=num_max,
        )
    raise ValueError(f"unknown groupby strategy {strategy!r}")
