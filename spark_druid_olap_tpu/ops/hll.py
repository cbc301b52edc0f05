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

Estimation (host-side, classic Flajolet HLL on 32-bit hashes): alpha_m * m² /
sum(2^-M_j), with linear counting below 2.5m and the 32-bit large-range
correction.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..models import aggregations as A
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


def estimate(registers: np.ndarray) -> np.ndarray:
    """HLL cardinality estimate per group.  registers: int[..., m]."""
    regs = np.asarray(registers, dtype=np.float64)
    m = regs.shape[-1]
    if m >= 128:
        alpha = 0.7213 / (1 + 1.079 / m)
    elif m == 64:
        alpha = 0.709
    elif m == 32:
        alpha = 0.697
    else:
        alpha = 0.673
    est = alpha * m * m / np.sum(np.exp2(-regs), axis=-1)
    zeros = np.sum(regs == 0, axis=-1)
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
