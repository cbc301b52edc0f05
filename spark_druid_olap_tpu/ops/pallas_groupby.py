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
    fusion writes, `-1` where the row is masked), the values ``(M, R)``.
    An ``[R, 1]`` column holds 8 useful words in every 4 KB tile; as the
    kernel's operand it was a 128 x padded relayout in front of the kernel
    and 128 x padded reads inside it (PERF.md, PR 28);
  * the match tile is ``(BLOCK_G, rows)``, groups on sublanes: the id row
    broadcasts down the sublanes against a group iota, and the sum is
    ``values (M, rows) . match (BLOCK_G, rows)^T`` on the MXU;
  * aggregate outputs are stored transposed ``(M, G)`` so the small M axis
    pads to 8 sublanes instead of 128 lanes;
  * grid is (groups-tile, rows-tile) with rows innermost, so each group
    tile's accumulator stays VMEM-resident across the whole row sweep
    (TPU grids execute sequentially — accumulation is race-free); a grid
    step's rows are walked in match tiles small enough for VMEM.

f32 sums on a bf16 MXU: `Precision.HIGHEST` splits both operands into three
bf16 parts and makes six passes.  The 0/1 tile is exact in one part, so
three of the six multiply by zero; the wrapper makes the values' exact split
``v = hi + mid + lo`` itself and the kernel contracts the ``(3 M, rows)``
stack with the bf16 tile in ONE pass, accumulating in f32 — the products
HIGHEST would form, a sixth of its MXU time (on the v5e the transposed
contraction at HIGHEST took 2.28 ms a 2^19-row segment at 800 groups and
three sum columns, this 0.75, ~0.2 of each being the operands' fusions and
dispatch; PERF.md, PR 28).

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
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -jnp.inf
_POS = jnp.inf

# the widest (BLOCK_G, rows) match tile a kernel step builds at once: its
# iota, compare and bf16 0/1 copies stay inside the default 16 MB of VMEM
_MATCH_TILE_ELEMS = 1 << 20
_TILE_ROWS = 4096  # and its width in rows where the group tile is narrow


def _kernel(
    gid_ref,
    sumv_ref,
    minv_ref,
    maxv_ref,
    out_sum_ref,
    out_min_ref,
    out_max_ref,
    *,
    block_g: int,
    tile_r: int,
    num_min: int,
    num_max: int,
):
    i = pl.program_id(1)  # row tile (inner)
    j = pl.program_id(0)  # group tile (outer)

    @pl.when(i == 0)
    def _init():
        out_sum_ref[:] = jnp.zeros_like(out_sum_ref)
        if num_min:
            out_min_ref[:] = jnp.full_like(out_min_ref, _POS)
        if num_max:
            out_max_ref[:] = jnp.full_like(out_max_ref, _NEG)

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
        # MXU, one bf16 pass: (3 Ms, TR) . (BG, TR)^T -> (3 Ms, BG) in f32.
        # The stack is the values' exact three-way split and the tile is
        # exact 0/1, so every product is exact: f32 sums of f32 values.
        out_sum_ref[:] += jax.lax.dot_general(
            sumv_ref[:, rows], match.astype(jnp.bfloat16),
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

    jax.lax.fori_loop(0, gid_ref.shape[1] // tile_r, _tile, 0)


def _row_blocks(R: int, bg: int, block_rows: int):
    """(rows a grid step, rows a match tile) for R rows against a BG-wide
    group tile: the tile a multiple of 128 lanes that divides R and keeps
    (BG, tile) inside `_MATCH_TILE_ELEMS`, the step a multiple of the tile
    that divides R."""
    if R % 128:
        raise ValueError(
            f"row count {R} must be a multiple of 128 (engine rows are "
            "ROW_PAD=1024-multiples)"
        )
    tile = min(_TILE_ROWS, max(128, _MATCH_TILE_ELEMS // bg // 128 * 128), R)
    while R % tile:
        tile -= 128
    br = max(tile, min(block_rows, R) // tile * tile)
    while R % br:
        br -= tile
    return br, tile


def _bf16_parts(v: jnp.ndarray) -> jnp.ndarray:
    """f32 (M, R) -> bf16 (3 M, R), `hi`, `mid`, `lo` stacked, with
    hi + mid + lo == v exactly (8 + 8 + 8 significand bits).

    Each part is rounded by `reduce_precision`, never by a convert to bf16
    and back: XLA's TPU pipeline elides that round trip as excess precision
    (`xla_allow_excess_precision`), which leaves `mid` and `lo` zero and
    the sums at bf16 (1e-3 on the chip where CPU tests read 1e-7).  A value
    that is not finite stays whole in `hi`: inf - inf would make the rest
    NaN."""
    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    hi = bf16(v)
    r = jnp.where(jnp.isfinite(v), v - hi, jnp.zeros_like(v))
    mid = bf16(r)
    lo = r - mid  # at most 8 significant bits are left: exact in bf16
    return jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16)


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
    sum_values: jnp.ndarray,  # f32[R, Ms] pre-masked
    minmax_values: jnp.ndarray,  # f32[R, Mn+Mx] raw
    minmax_masks: jnp.ndarray,  # bool[R, Mn+Mx]
    num_groups: int,
    num_min: int,
    num_max: int,
    block_rows: int = 16384,
    block_groups: int = 2048,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Same contract as ops.groupby.dense_partial_aggregate, hand-scheduled.

    Returns (sums[G, Ms], mins[G, Mn], maxs[G, Mx]); empty groups are 0 /
    +inf / -inf exactly like the XLA path.

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
    Ms = sum_values.shape[1]
    bg = min(block_groups, max(128, -(-num_groups // 128) * 128))
    g_pad = -(-num_groups // bg) * bg
    br, tile_r = _row_blocks(R, bg, block_rows)

    # lane-dense operands, rows on the lane axis: XLA writes each from its
    # producing fusion, no relayout between
    gid_t = jnp.where(mask, gid, -1).reshape(1, R)
    sum_t = _bf16_parts(sum_values.T)  # (3 Ms, R)
    mm_t = minmax_values.T  # (Mn+Mx, R)
    mm_ok = mask[None, :] & minmax_masks.T
    mn_t = (
        jnp.where(mm_ok[:num_min], mm_t[:num_min], jnp.asarray(_POS, mm_t.dtype))
        if num_min
        else jnp.zeros((1, R), jnp.float32)
    )
    mx_t = (
        jnp.where(mm_ok[num_min:], mm_t[num_min:], jnp.asarray(_NEG, mm_t.dtype))
        if num_max
        else jnp.zeros((1, R), jnp.float32)
    )

    grid = (g_pad // bg, R // br)

    kernel = functools.partial(
        _kernel, block_g=bg, tile_r=tile_r, num_min=num_min, num_max=num_max
    )
    out_shapes = (
        jax.ShapeDtypeStruct((3 * Ms, g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_min, 1), g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_max, 1), g_pad), jnp.float32),
    )
    in_specs = [
        pl.BlockSpec((1, br), lambda j, i: (0, i)),  # gid, -1 where masked
        pl.BlockSpec((3 * Ms, br), lambda j, i: (0, i)),  # sum value parts
        pl.BlockSpec((max(num_min, 1), br), lambda j, i: (0, i)),
        pl.BlockSpec((max(num_max, 1), br), lambda j, i: (0, i)),
    ]
    out_specs = (
        pl.BlockSpec((3 * Ms, bg), lambda j, i: (0, j)),
        pl.BlockSpec((max(num_min, 1), bg), lambda j, i: (0, j)),
        pl.BlockSpec((max(num_max, 1), bg), lambda j, i: (0, j)),
    )
    # Mosaic cannot legalize the i64 grid-index arithmetic that x64 mode
    # injects (func.return (i32, i64) fails on real TPUs) — trace the kernel
    # in 32-bit mode.  All operands are already concrete i32/f32 arrays, so
    # semantics are unchanged.
    with jax.enable_x64(False):
        parts_t, mins_t, maxs_t = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shapes,
            interpret=interpret,
        )(gid_t, sum_t, mn_t, mx_t)
    # small parts first: each is an f32 sum of its own
    sums_t = (parts_t[2 * Ms:] + parts_t[Ms:2 * Ms]) + parts_t[:Ms]
    sums = sums_t[:, :num_groups].T
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
