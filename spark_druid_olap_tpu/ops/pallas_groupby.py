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
  * rows are the sublane dim of ``(BLOCK_R, BLOCK_G)`` match tiles;
  * aggregate outputs are stored transposed ``(M, G)`` so the small M axis
    pads to 8 sublanes instead of 128 lanes;
  * grid is (groups-tile, rows-tile) with rows innermost, so each group
    tile's accumulator stays VMEM-resident across the whole row sweep
    (TPU grids execute sequentially — accumulation is race-free).

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


def _kernel(
    gid_ref,
    mask_ref,
    sumv_ref,
    minv_ref,
    maxv_ref,
    out_sum_ref,
    out_min_ref,
    out_max_ref,
    *,
    block_g: int,
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

    gid = gid_ref[:, 0] - j * block_g  # (BR,) relative to this group tile
    mask = mask_ref[:, 0] != 0
    br = gid.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (br, block_g), 1)
    match = (gid[:, None] == iota) & mask[:, None]  # (BR, BG) bool, VMEM-only

    onehot = match.astype(jnp.float32)
    # MXU: (Ms, BR) @ (BR, BG) -> (Ms, BG); sum values are pre-masked so the
    # bool one-hot contraction is exact.  HIGHEST precision keeps f32 inputs
    # f32 on the MXU (default would truncate to bf16 and break parity with
    # the XLA dense path).
    out_sum_ref[:] += jax.lax.dot(
        sumv_ref[:], onehot,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    # VPU: masked min/max over the same match tile, one agg column at a time.
    # The +/-inf fill is materialized AT THE REF DTYPE: a bare Python float
    # here is weak-typed, and under x64 the interpret-mode lowering
    # promotes the select to f64 ('func.call' operand mismatch) —
    # dtype-matched selects never promote.
    for m in range(num_min):
        pos = jnp.asarray(_POS, dtype=out_min_ref.dtype)
        w = jnp.where(match, minv_ref[m, :][:, None], pos)  # (BR, BG)
        out_min_ref[m, :] = jnp.minimum(out_min_ref[m, :], w.min(axis=0))
    for m in range(num_max):
        neg = jnp.asarray(_NEG, dtype=out_max_ref.dtype)
        w = jnp.where(match, maxv_ref[m, :][:, None], neg)
        out_max_ref[m, :] = jnp.maximum(out_max_ref[m, :], w.max(axis=0))


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
    block_rows: int = 1024,
    block_groups: int = 4096,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Same contract as ops.groupby.dense_partial_aggregate, hand-scheduled.

    Returns (sums[G, Ms], mins[G, Mn], maxs[G, Mx]); empty groups are 0 /
    +inf / -inf exactly like the XLA path.

    Block tuning (ESTIMATED for a v5e-class VMEM budget, not tuned on
    hardware): every extra group tile re-reads the whole row stream, so the group-block default spans all groups up to 4096 (one
    tile); the row block shrinks to 512 when the group block is wide so the
    (BR, BG) match tile stays within VMEM."""
    R = gid.shape[0]
    Ms = sum_values.shape[1]
    bg = min(block_groups, max(128, -(-num_groups // 128) * 128))
    g_pad = -(-num_groups // bg) * bg
    # the row-block size must divide R exactly (same contract as the dense
    # path; engine rows are always ROW_PAD=1024-multiples)
    br = min(block_rows if bg <= 1024 else 512, R)
    while br >= 8 and R % br:
        br -= 8
    if br < 8 or R % br:
        raise ValueError(
            f"row count {R} must be divisible by a multiple-of-8 block size"
        )

    # transpose value blocks to (M, R): M pads to sublanes (8) not lanes (128)
    sum_t = sum_values.T  # (Ms, R)
    mn_t = (
        jnp.where(
            mask[:, None] & minmax_masks[:, :num_min],
            minmax_values[:, :num_min],
            jnp.asarray(_POS, dtype=minmax_values.dtype),
        ).T
        if num_min
        else jnp.zeros((1, R), jnp.float32)
    )
    mx_t = (
        jnp.where(
            mask[:, None] & minmax_masks[:, num_min:],
            minmax_values[:, num_min:],
            jnp.asarray(_NEG, dtype=minmax_values.dtype),
        ).T
        if num_max
        else jnp.zeros((1, R), jnp.float32)
    )

    grid = (g_pad // bg, R // br)

    kernel = functools.partial(
        _kernel, block_g=bg, num_min=num_min, num_max=num_max
    )
    out_shapes = (
        jax.ShapeDtypeStruct((Ms, g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_min, 1), g_pad), jnp.float32),
        jax.ShapeDtypeStruct((max(num_max, 1), g_pad), jnp.float32),
    )
    in_specs = [
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),  # gid
        pl.BlockSpec((br, 1), lambda j, i: (i, 0)),  # mask (int32)
        pl.BlockSpec((Ms, br), lambda j, i: (0, i)),  # sum values (Ms, BR)
        pl.BlockSpec((max(num_min, 1), br), lambda j, i: (0, i)),
        pl.BlockSpec((max(num_max, 1), br), lambda j, i: (0, i)),
    ]
    out_specs = (
        pl.BlockSpec((Ms, bg), lambda j, i: (0, j)),
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
            interpret=interpret,
        )(
            gid.reshape(R, 1),
            mask.astype(jnp.int32).reshape(R, 1),
            sum_t,
            mn_t,
            mx_t,
        )
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
