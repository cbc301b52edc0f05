"""HyperLogLog on TPU — per-group register arrays, max-merge everywhere.

Reference parity: Druid's `hyperUnique` / `cardinality` aggregators, which the
reference's AggregateTransform emits for approx_count_distinct (SURVEY.md §2
`[U]`); Druid historicals build per-segment HLL states and the broker merges
them by register-max — exactly the shape we reproduce: per-device states in
HBM merged with a `pmax` collective (parallel/merge.py), so an ICI allreduce
makes the pod one wide HLL builder (BASELINE.json north star).

Kernel shape (SURVEY.md §7 hard-part #3 — "HLL register update is a
scatter-max by hash bucket"): hash each row (uint32), low p bits pick the
bucket, rho = leading-zero-count of the high window + 1, and the scatter-max
runs as one `segment_max` over combined (group, bucket) indices — a single
XLA scatter of int32, not a per-row loop.  State: int32[G, 2^p] (int8 would
do; int32 avoids TPU sub-word scatter penalties; the state is tiny next to
the row data).

Estimation (classic Flajolet HLL on 32-bit hashes): alpha_m * m² /
sum(2^-M_j), with linear counting below 2.5m and the 32-bit large-range
correction.  The sum runs over a histogram of register values, not over
the registers: `register_histogram` counts, on the device where the
registers lie, how many of a group's m registers hold each value
k = 0 … 33 - p, so the host fetches int32[G, 34 - p] instead of
int32[G, m] (PR 40: 23 columns for 2,048 at p = 11).  The estimate
reads Σ_k hist[k]·2^-k and zeros = hist[0]; every partial sum of either
form is a multiple of 2^-(33-p) no larger than 2^p, at most 34
significant bits, so both are exact in float64 and equal bit for bit.
A float Σ 2^-M made on the device would round (float32 holds 24 bits)
and could move a `rint`ed estimate.  Host-held registers (the result
cache's captured state, states handed to a merger) take the numpy twin
`histogram_np`; `estimate(registers)` is that and the same estimator.
"""

from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..models import aggregations as A
from ..obs import SCOPE_SKETCH_HISTOGRAM, device_scope
from ..utils.hashing import combine_hashes, hash_column


def _rho(h: jnp.ndarray, p: int) -> jnp.ndarray:
    """rho = #leading zeros of the (32-p)-bit window (h >> p) + 1, in [1, 33-p].

    An exact integer count: the 32-bit word's leading zeros less the p
    zero bits the shift put on top.  (A float32 `log2` floor was off by
    one at 2^13, 2^15 and 2^21 - 1 on the CPU: PR 39's exhaustive test.)"""
    w = (h >> p).astype(jnp.uint32)
    return (lax.clz(w) - p + 1).astype(jnp.int32)


def partial_hll(
    agg,
    cols: Mapping[str, jnp.ndarray],
    gid: jnp.ndarray,
    mask: jnp.ndarray,
    num_groups: int,
) -> jnp.ndarray:
    """Partial HLL state int32[num_groups, 2^p] for one row shard."""
    p = agg.precision
    m = 1 << p
    if isinstance(agg, A.CardinalityAgg):
        hs = [hash_column(cols[f], seed=0) for f in agg.field_names]
        h = combine_hashes(hs) if agg.by_row else hs[0]
        if not agg.by_row and len(hs) > 1:
            # non-byRow multi-field: distinct over the union of values —
            # emulate by folding each field separately into the same registers
            states = [
                _fold_registers(hh, gid, mask, num_groups, p) for hh in hs
            ]
            out = states[0]
            for s in states[1:]:
                out = jnp.maximum(out, s)
            return out
    else:
        h = hash_column(cols[agg.field_name], seed=0)
    return _fold_registers(h, gid, mask, num_groups, p)


def _fold_registers(h, gid, mask, num_groups, p):
    m = 1 << p
    bucket = (h & jnp.uint32(m - 1)).astype(jnp.int32)
    rho = _rho(h, p)
    # group-sharded callers pass shifted gids that may fall outside [0, G)
    ok = mask & (gid >= 0) & (gid < num_groups)
    rho = jnp.where(ok, rho, 0)
    idx = jnp.where(ok, gid * m + bucket, num_groups * m)  # trash slot
    regs = jax.ops.segment_max(
        rho, idx, num_segments=num_groups * m + 1
    )[: num_groups * m]
    # segment_max fills empty segments with the dtype min — clamp to 0
    regs = jnp.maximum(regs, 0)
    return regs.reshape(num_groups, m)


def histogram_width(p: int) -> int:
    """Columns of a register histogram at precision p: the register
    values 0 … 33 - p (`_rho`'s range, 0 for an empty bucket)."""
    return 34 - p


@functools.partial(jax.jit, static_argnums=1)
def register_histogram(regs: jnp.ndarray, p: int) -> jnp.ndarray:
    """int32[G, 2^p] registers -> int32[G, 34 - p]: hist[g, k] counts the
    registers of group g equal to k.  One compare-and-sum over the
    register axis (the minor one), fused by XLA: no scatter and no
    [G, K, m] array in memory."""
    with device_scope(SCOPE_SKETCH_HISTOGRAM):
        ks = jnp.arange(histogram_width(p), dtype=regs.dtype)
        return jnp.sum(
            regs[:, None, :] == ks[:, None], axis=-1, dtype=jnp.int32
        )


def histogram_np(registers: np.ndarray, p: int) -> np.ndarray:
    """`register_histogram` on the host, for registers int[..., 2^p]
    already fetched: one `bincount` over (row, value) pairs."""
    regs = np.asarray(registers)
    k = histogram_width(p)
    if regs.size and (regs.min() < 0 or regs.max() >= k):
        raise ValueError(
            f"HLL register outside 0..{k - 1} at precision {p}"
        )
    lead = regs.shape[:-1]
    rows = int(np.prod(lead, dtype=np.int64))
    flat = regs.reshape(rows, regs.shape[-1]).astype(np.int64)
    flat += np.arange(rows, dtype=np.int64)[:, None] * k
    hist = np.bincount(flat.ravel(), minlength=rows * k)
    return hist.astype(np.int32).reshape(*lead, k)


def estimate_from_histogram(hist: np.ndarray, m: int) -> np.ndarray:
    """HLL cardinality estimate per group from its register histogram
    int[..., K] (K = 34 - p, m = 2^p registers a group)."""
    hist = np.asarray(hist)
    if m >= 128:
        alpha = 0.7213 / (1 + 1.079 / m)
    elif m == 64:
        alpha = 0.709
    elif m == 32:
        alpha = 0.697
    else:
        alpha = 0.673
    weights = np.exp2(-np.arange(hist.shape[-1], dtype=np.float64))
    est = alpha * m * m / (hist @ weights)
    zeros = hist[..., 0]
    # small-range: linear counting
    with np.errstate(divide="ignore"):
        lc = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1), 1.0))
    est = np.where((est <= 2.5 * m) & (zeros > 0), lc, est)
    # large-range correction for 32-bit hash space
    two32 = 2.0**32
    est = np.where(
        est > two32 / 30.0, -two32 * np.log1p(-est / two32), est
    )
    return est


def estimate(registers: np.ndarray) -> np.ndarray:
    """HLL cardinality estimate per group.  registers: int[..., m]."""
    m = np.shape(registers)[-1]
    return estimate_from_histogram(
        histogram_np(registers, m.bit_length() - 1), m
    )
