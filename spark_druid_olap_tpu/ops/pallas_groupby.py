"""Pallas TPU kernel: fused one-hot GroupBy partial aggregation.

This is the hand-scheduled version of ops/groupby.py's dense strategy — the
hot kernel of the whole framework (the role Druid's historical aggregation
engine plays in the reference, SURVEY.md §2 native-components note `[U]`).

Why Pallas beats the XLA scan here: the scan body materializes each one-hot
block ``(B, G)`` through HBM before the matmul reads it back — for B=1M rows
that is gigabytes of pure intermediate traffic.  The kernel builds each
one-hot tile *in VMEM* with `broadcasted_iota` + compare and feeds the MXU
directly; HBM sees only the raw row data (once) and the [G, M] aggregate
state.  min/max ride the same match tile on the VPU.

Layout choices (pallas_guide.md tiling rules):
  * every per-row operand is lane-dense, rows on the lane axis: the group
    id one ``(1, R)`` row (a bitcast of the dense ``s32[R]`` the lowering's
    fusion writes, `-1` where the row is masked), each sum value its own
    dense ``(R / 128, 128)`` view of the ``[R]`` row the lowering leaves
    (a bitcast too: both are the row's linear order), min/max ``(M, R)``.
    An ``[R, 1]`` column holds 8 useful words in every 4 KB tile; as the
    kernel's operand it was a 128 x padded relayout in front of the kernel
    and 128 x padded reads inside it (PERF.md, PR 28);
  * the match tile is ``(BLOCK_G, rows)``, groups on sublanes: the id row
    broadcasts down the sublanes against a group iota, and the sum is
    ``stack (K, rows) . match (BLOCK_G, rows)^T`` on the MXU;
  * aggregate outputs are stored transposed ``(M, G)`` so the small M axis
    pads to 8 sublanes instead of 128 lanes;
  * grid is (groups-tile, rows-tile) with rows innermost, so each group
    tile's accumulator stays VMEM-resident across the whole row sweep
    (TPU grids execute sequentially — accumulation is race-free); a grid
    step's rows are walked in match tiles small enough for VMEM.

Who masks what.  The id row is the only row mask the kernel knows: a row
whose id is negative matches no group of any tile, so whatever its value
holds reaches no sum, and the kernel zeroes it in VMEM besides (a
non-finite value on a masked row would else meet the tile's 0 as
`0 * inf`).  The sum values therefore come as the lowering leaves them,
NOT multiplied by the row mask (`exec/lowering.py row_arrays`, the form it
returns for this kernel); only an aggregator's own mask (a filtered
aggregator, a null-skipping sum) is multiplied in there, because it is
per column.  A count needs no operand at all: it is the tile's row sum,
one constant row of ones in the stack.  The XLA `dense` scan and the
scatter (`ops/groupby.py`) keep their pre-masked `[R, Ms]` values.

f32 sums on a bf16 MXU: `Precision.HIGHEST` splits both operands into three
bf16 parts and makes six passes.  The 0/1 tile is exact in one part, so
three of the six multiply by zero; the kernel makes the values' exact split
``v = hi + mid + lo`` itself, in VMEM at the start of each grid step, and
contracts the ``(3 Mv + 1, rows)`` stack with the bf16 tile in ONE pass,
accumulating in f32 — the products HIGHEST would form, a sixth of its MXU
time.  The split is by truncation (`_split`): Mosaic has no rule for
`lax.reduce_precision`, and a convert to bf16 and back is what XLA's TPU
pipeline elides as excess precision (PR 28: 1e-3 on the chip where CPU
tests read 1e-7).  Until PR 36 the wrapper made the parts, the mask
multiply and a count's row of 0/1 as four XLA passes over HBM in front of
every call (0.37 s of flights2-4's 1.62 s of device time).

The kernel covers sum-class and min/max aggregations (sketch partials stay in
XLA — scatter-shaped, see ops/hll.py).  `interpret=True` under CPU tests.

The pallas_call <-> kernel contract (grid arity vs index_map signatures,
BlockSpec ranks vs ref indexing, spec count vs kernel refs, dtype-matched
fills) is enforced statically by graftlint's pallas-shape pass (GL7xx),
which resolves `kernel`/`grid`/`*_specs` through local assignments and
`functools.partial` — keep those shapes statically spellable.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -jnp.inf
_POS = jnp.inf

# the widest (BLOCK_G, rows) match tile a kernel step builds at once: its
# iota, compare and bf16 0/1 copies stay inside the default 16 MB of VMEM
_MATCH_TILE_ELEMS = 1 << 20
_TILE_ROWS = 4096  # and its width in rows where the group tile is narrow
_LANES = 128
# rows of one (8, 128) tile of a value's dense view: a grid step's rows
# are whole tiles of it
_DENSE_ROWS = 8 * _LANES


def _top8(x: jnp.ndarray) -> jnp.ndarray:
    """f32 -> f32 with the low 16 bits of the word cleared: the 8 leading
    significant bits, exact as a bfloat16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFF0000), jnp.float32
    )


def _split(v: jnp.ndarray):
    """f32 -> (hi, mid, lo), each exact as a bfloat16, with
    hi + mid + lo == v bit for bit (8 + 8 + 8 significand bits), by
    truncation: every step is a mask of the word or an exact subtraction,
    nothing the TPU pipeline may elide as excess precision.  A value that
    is not finite stays whole in `hi`: inf - inf would make the rest NaN,
    and a NaN whose payload is in the low bits would be cut to inf."""
    finite = jnp.isfinite(v)
    hi = jnp.where(finite, _top8(v), v)
    r = jnp.where(finite, v - hi, jnp.zeros_like(v))
    mid = _top8(r)
    lo = r - mid  # at most 8 significant bits are left
    return hi, mid, lo


def _kernel(
    gid_ref,
    *refs,
    block_g: int,
    tile_r: int,
    counts: Tuple[bool, ...],
    num_min: int,
    num_max: int,
):
    num_vals = len(counts) - sum(counts)
    val_refs, rest = refs[:num_vals], list(refs[num_vals:])
    # a class with no aggregation has no operand
    minv_ref = rest.pop(0) if num_min else None
    maxv_ref = rest.pop(0) if num_max else None
    out_sum_ref, out_min_ref, out_max_ref, stack_ref, acc_ref = rest
    i = pl.program_id(1)  # row tile (inner)
    j = pl.program_id(0)  # group tile (outer)
    step_rows = gid_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # the rows no value part overwrites stay ones: a count's row
        stack_ref[:] = jnp.ones_like(stack_ref)
        if num_min:
            out_min_ref[:] = jnp.full_like(out_min_ref, _POS)
        if num_max:
            out_max_ref[:] = jnp.full_like(out_max_ref, _NEG)

    # The values' parts, made in VMEM once a grid step on the dense
    # (rows / 128, 128) view (whole vregs; a (1, rows) view would put the
    # bitcasts on one sublane in eight), then laid as rows of the stack:
    # the dense view and a (1, rows) row hold the same words in the same
    # vregs, so the reshape moves nothing and the store is one strided
    # write a vreg.
    if num_vals:
        live = gid_ref[:].reshape(step_rows // _LANES, _LANES) >= 0
    for m, ref in enumerate(val_refs):
        v = ref[:]
        if v.dtype != jnp.float32:
            v = v.astype(jnp.float32)  # int32 metrics: round to nearest
        v = jnp.where(live, v, jnp.zeros_like(v))
        for k, part in zip((m, num_vals + m, 2 * num_vals + m), _split(v)):
            stack_ref[k:k + 1, :] = part.reshape(1, step_rows)

    # +/-inf fills AT THE REF DTYPE: a bare Python float is weak-typed, and
    # under x64 the interpret-mode lowering promotes the select to f64
    # ('func.call' operand mismatch) — dtype-matched selects never promote.
    pos = jnp.asarray(_POS, dtype=out_min_ref.dtype)
    neg = jnp.asarray(_NEG, dtype=out_max_ref.dtype)
    groups = jax.lax.broadcasted_iota(jnp.int32, (block_g, tile_r), 0)

    def _tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * tile_r, tile_r), tile_r)
        # (BG, TR) bool, VMEM-only: the (1, TR) id row against the group
        # iota.  Masked rows carry -1 and match no group of any tile.
        match = groups == gid_ref[:, rows] - j * block_g
        # MXU, one bf16 pass: (K, TR) . (BG, TR)^T -> (K, BG) in f32.  The
        # stack is the values' exact three-way split over a row of ones
        # and the tile is exact 0/1, so every product is exact: f32 sums
        # of f32 values, and the tile's row sums as the count.
        acc_ref[:] += jax.lax.dot_general(
            stack_ref[:, rows].astype(jnp.bfloat16),
            match.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # VPU/XLU: masked min/max over the same match tile, one agg row at
        # a time.
        for m in range(num_min):
            w = jnp.where(match, minv_ref[m:m + 1, rows], pos)  # (BG, TR)
            out_min_ref[m:m + 1, :] = jnp.minimum(
                out_min_ref[m:m + 1, :], w.min(axis=1)[None, :]
            )
        for m in range(num_max):
            w = jnp.where(match, maxv_ref[m:m + 1, rows], neg)
            out_max_ref[m:m + 1, :] = jnp.maximum(
                out_max_ref[m:m + 1, :], w.max(axis=1)[None, :]
            )
        return carry

    jax.lax.fori_loop(0, step_rows // tile_r, _tile, 0)

    @pl.when(i == pl.num_programs(1) - 1)
    def _finish():
        # a value's sum from its parts, small parts first (each an f32
        # sum of its own); every count is the row of ones
        m = 0
        for col, count in enumerate(counts):
            if count:
                total = acc_ref[3 * num_vals:3 * num_vals + 1, :]
            else:
                hi, mid, lo = (
                    acc_ref[k:k + 1, :]
                    for k in (m, num_vals + m, 2 * num_vals + m)
                )
                total = (lo + mid) + hi
                m += 1
            out_sum_ref[col:col + 1, :] = total


def _row_blocks(R: int, bg: int, block_rows: int):
    """(rows a grid step, rows a match tile) for R rows (whole dense
    tiles: a multiple of 1024) against a BG-wide group tile: the tile a
    multiple of 128 lanes that divides R and keeps (BG, tile) inside
    `_MATCH_TILE_ELEMS`, the step a multiple of the tile and of 1024 that
    divides R."""
    tile = min(_TILE_ROWS, max(128, _MATCH_TILE_ELEMS // bg // 128 * 128), R)
    while R % tile:
        tile -= 128
    unit = math.lcm(tile, _DENSE_ROWS)  # divides R: both do
    br = unit * max(1, min(block_rows, R) // unit)
    while R % br:
        br -= unit
    return br, tile


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_groups", "num_min", "num_max", "block_rows", "block_groups",
        "interpret",
    ),
)
def pallas_partial_aggregate(
    gid: jnp.ndarray,  # int32[R]
    mask: jnp.ndarray,  # bool[R]
    sum_values,  # a row or None a sum column, or f32[R, Ms]: see below
    minmax_values: jnp.ndarray,  # f32[R, Mn+Mx] raw
    minmax_masks: jnp.ndarray,  # bool[R, Mn+Mx]
    num_groups: int,
    num_min: int,
    num_max: int,
    block_rows: int = 16384,
    block_groups: int = 2048,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Same results as ops.groupby.dense_partial_aggregate, hand-scheduled.

    Returns (sums[G, Ms], mins[G, Mn], maxs[G, Mx]); empty groups are 0 /
    +inf / -inf exactly like the XLA path.

    `sum_values` is one entry a sum column (`exec/lowering.py row_arrays`
    under `strategy="pallas"`): an `[R]` row, float32 or int32, NOT
    multiplied by `mask` (the kernel masks by the id; a stored column
    comes as it lies resident, no fusion in front), or `None` for a
    column that counts the rows `mask` keeps, which gets no operand.  An
    `f32[R, Ms]` array, the other kernels' form, is taken column by
    column as rows; masked there already or not, the sums are the same.

    Block tuning (measured on a v5e, 2^19-row segments, PERF.md PR 28): the
    kernel's time is building the match tile, ~0.05 ms a segment for every
    128 groups whatever the blocks, so none of the three matters much.  A
    grid step of 16384 rows against 1024 .. 65536 moves a segment by under
    3 %; a match tile of 4096 rows against 1024 is 10 % faster at one
    128-group tile and cannot be had past 256 groups (VMEM); group tiles of
    512 .. 2048 are alike, and one of 4096 (measured with a 128-row match
    tile) 10 % slower.  Every extra group tile re-reads the row stream,
    which is small beside the compare."""
    R = gid.shape[0]
    if R % _LANES:
        raise ValueError(
            f"row count {R} must be a multiple of 128 (engine rows are "
            "ROW_PAD=1024-multiples)"
        )
    if not isinstance(sum_values, (tuple, list)):
        sum_values = [sum_values[:, m] for m in range(sum_values.shape[1])]
    counts = tuple(v is None for v in sum_values)
    vals = [
        v if v.dtype in (jnp.float32, jnp.int32) else v.astype(jnp.float32)
        for v in sum_values if v is not None
    ]
    Ms, Mv = len(counts), len(vals)
    bg = min(block_groups, max(128, -(-num_groups // 128) * 128))
    g_pad = -(-num_groups // bg) * bg

    # lane-dense operands, rows on the lane axis: XLA writes each from its
    # producing fusion, no relayout between
    gid_t = jnp.where(mask, gid, -1).reshape(1, R)
    mm_t = minmax_values.T  # (Mn+Mx, R)
    mm_ok = mask[None, :] & minmax_masks.T
    mm_rows = []  # the min rows, then the max rows, where there are any
    if num_min:
        mm_rows.append(jnp.where(
            mm_ok[:num_min], mm_t[:num_min], jnp.asarray(_POS, mm_t.dtype)
        ))
    if num_max:
        mm_rows.append(jnp.where(
            mm_ok[num_min:], mm_t[num_min:], jnp.asarray(_NEG, mm_t.dtype)
        ))
    # rows off the dense tile (no engine's: ROW_PAD is 1024) are filled up
    # with rows no group takes
    pad = -R % _DENSE_ROWS
    if pad:
        R += pad
        gid_t = jnp.pad(gid_t, ((0, 0), (0, pad)), constant_values=-1)
        vals = [jnp.pad(v, (0, pad)) for v in vals]
        # the id takes a filled row out of every min and max
        mm_rows = [jnp.pad(m, ((0, 0), (0, pad))) for m in mm_rows]
    br, tile_r = _row_blocks(R, bg, block_rows)

    grid = (g_pad // bg, R // br)
    # the stack's rows: hi, mid, lo of every value, then ones (a count)
    K = -(-(3 * Mv + 1) // 8) * 8

    kernel = functools.partial(
        _kernel, block_g=bg, tile_r=tile_r, counts=counts,
        num_min=num_min, num_max=num_max,
    )
    out_shapes = (
        jax.ShapeDtypeStruct((max(Ms, 1), g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_min, 1), g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_max, 1), g_pad), jnp.float32),
    )
    in_specs = [
        pl.BlockSpec((1, br), lambda j, i: (0, i)),  # gid, -1 where masked
        *[pl.BlockSpec((br // _LANES, _LANES), lambda j, i: (i, 0))] * Mv,
        *[pl.BlockSpec((m.shape[0], br), lambda j, i: (0, i)) for m in mm_rows],
    ]
    out_specs = (
        pl.BlockSpec((max(Ms, 1), bg), lambda j, i: (0, j)),
        pl.BlockSpec((max(num_min, 1), bg), lambda j, i: (0, j)),
        pl.BlockSpec((max(num_max, 1), bg), lambda j, i: (0, j)),
    )
    # Mosaic cannot legalize the i64 grid-index arithmetic that x64 mode
    # injects (func.return (i32, i64) fails on real TPUs) — trace the kernel
    # in 32-bit mode.  All operands are already concrete i32/f32 arrays, so
    # semantics are unchanged.
    with jax.enable_x64(False):
        sums_t, mins_t, maxs_t = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            scratch_shapes=[
                pltpu.VMEM((K, br), jnp.float32),  # the stack, a grid step
                pltpu.VMEM((K, bg), jnp.float32),  # its sums, a group tile
            ],
            interpret=interpret,
        )(
            gid_t, *[v.reshape(R // _LANES, _LANES) for v in vals],
            *mm_rows,
        )
    sums = sums_t[:Ms, :num_groups].T
    mins = (
        mins_t[:num_min, :num_groups].T
        if num_min
        else jnp.zeros((num_groups, 0), jnp.float32)
    )
    maxs = (
        maxs_t[:num_max, :num_groups].T
        if num_max
        else jnp.zeros((num_groups, 0), jnp.float32)
    )
    return sums, mins, maxs


def pallas_available() -> bool:
    """True on a TPU backend: the kernel is compiled there.  Anywhere else
    it runs only under `interpret=True`, which CPU tests ask for by name
    (`strategy="pallas"`)."""
    return jax.default_backend() == "tpu"
