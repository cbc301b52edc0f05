"""A plain HyperLogLog and Druid topN, numpy and pandas only: the reference
the tier-1 tests hold the engine's sketch path to (PR 39).  It imports
nothing of the program; every step is written out here.

Departures from Druid's `hyperUnique`, where the program departs:

- the hash is murmur3's 32-bit finalizer `fmix32` of the key's int32 bits,
  with the seed mixed in as `utils/hashing.py` does, not Murmur3-128;
- registers are whole int32 values, not Druid's 4-bit registers over an
  offset; the estimator is the classic one (Flajolet et al.) in float64:
  alpha m^2 / sum 2^-M, linear counting below 2.5 m while a register is
  zero, and the 32-bit large-range correction.

bucket = h & (m - 1); rho = (33 - p) - bit_length(h >> p), by integer
shifts, never a float log; a register is the max of rho by (group, bucket).
A topN answer rounds the estimate with `np.rint` and ranks by it
descending, ties by the dimension value ascending; sums are float64.
"""

from __future__ import annotations

import numpy as np


def fmix32(keys, seed: int = 0) -> np.ndarray:
    """murmur3 fmix32 of the keys' int32 bits (uint64 lanes kept to 32
    bits), `seed` mixed in first as `utils/hashing.mix32` does."""
    h = (np.asarray(keys).astype(np.int64) & 0xFFFFFFFF).astype(np.uint64)
    h ^= np.uint64((seed * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    return h


def bit_length(w) -> np.ndarray:
    """Bit length of each value below 2^32, by halving shifts."""
    w = np.asarray(w, dtype=np.uint64).copy()
    out = np.zeros(w.shape, dtype=np.int64)
    for s in (16, 8, 4, 2, 1):
        big = w >= np.uint64(1 << s)
        w = np.where(big, w >> np.uint64(s), w)
        out += big * s
    return out + (w > 0)


def rho(h, p: int) -> np.ndarray:
    return (33 - p) - bit_length(np.asarray(h, dtype=np.uint64) >> np.uint64(p))


def registers(keys, groups, num_groups: int, p: int = 11) -> np.ndarray:
    """int32[num_groups, 2^p]: the max rho of each (group, bucket) over
    the rows given (the caller has already applied every filter)."""
    m = 1 << p
    h = fmix32(keys)
    bucket = (h & np.uint64(m - 1)).astype(np.int64)
    regs = np.zeros(num_groups * m, dtype=np.int64)
    np.maximum.at(regs, np.asarray(groups, np.int64) * m + bucket, rho(h, p))
    return regs.reshape(num_groups, m).astype(np.int32)


def estimate(regs) -> np.ndarray:
    """The classic HLL estimate of each row of `regs` [..., m], float64."""
    regs = np.asarray(regs, dtype=np.float64)
    m = regs.shape[-1]
    alpha = 0.7213 / (1 + 1.079 / m)  # m >= 128
    raw = alpha * m * m / np.sum(np.exp2(-regs), axis=-1)
    zeros = np.sum(regs == 0, axis=-1)
    with np.errstate(divide="ignore"):
        lc = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1), 1.0))
    est = np.where((raw <= 2.5 * m) & (zeros > 0), lc, raw)
    two32 = 2.0 ** 32
    return np.where(est > two32 / 30.0, -two32 * np.log1p(-est / two32), est)


def topn(values, keys, sums, threshold: int = 100, p: int = 11):
    """Druid topN over rows already filtered: `values` the dimension's
    value per row, `keys` the hyperUnique's field, `sums` {name: float
    column}.  A DataFrame of the top `threshold` values by the rounded
    estimate (column `uniq`), ties by value ascending, with the float64
    sums beside it, in rank order."""
    import pandas as pd

    names, codes = np.unique(np.asarray(values).astype(str), return_inverse=True)
    est = np.rint(estimate(registers(keys, codes, len(names), p))).astype(np.int64)
    table = {"value": names, "uniq": est}
    for n, col in sums.items():
        table[n] = np.bincount(
            codes, weights=np.asarray(col, np.float64), minlength=len(names)
        )
    df = pd.DataFrame(table)
    # names are sorted: a stable sort keeps ties by value ascending
    order = np.argsort(-est, kind="stable")[:threshold]
    return df.iloc[order].reset_index(drop=True)
