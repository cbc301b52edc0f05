"""The plain reference of the `ssb_topn` configuration: Druid topN with
`doubleSum` and `hyperUnique` over the SSB data, numpy and pandas only.

It imports nothing of the program.  The data comes from `ssb_data.py`'s
generator beside this file (the same seed gives the same tables and fact
chunks the system ingests); the queries are the traffic file's native JSON,
of which it understands the subset the mix uses (topN, granularity `all`,
one interval, no filter or one `selector`, `doubleSum` and `hyperUnique`)
and refuses anything else.

The HLL is written out here, not imported (the tier-1 tests keep their own
copy in `tests/hll_reference.py`), and departs from Druid's where the
program does:

- the hash is murmur3's 32-bit finalizer `fmix32` of the key's int32 bits
  (seed 0 mixed in as `utils/hashing.py` does), not Druid's Murmur3-128;
- registers are whole int32 values, not Druid's 4-bit registers over an
  offset; the classic estimator (Flajolet et al.) in float64 with linear
  counting below 2.5 m and the 32-bit large-range correction;
- the precision is Druid's fixed 2^11 buckets (`HLL_PRECISION`), whatever a
  query asks: a system folding at another precision is not correct.

bucket = h & (m - 1); rho = (33 - p) - bit_length(h >> p), by integer
shifts, never a float log; registers are the max of rho by (group, bucket).
The estimate is rounded with `np.rint`; the top 100 are taken by estimate
descending, ties by the dimension value ascending.  `revenue` is a float64
sum.  An answer is the long frame `(rank, key, measure, value)`: one row for
`revenue` and one for the hyperUnique of each of the 100.

As a script it is the reference child of `loaders/ssb_topn.py`: it pickles
`{precision: {query: frame}, "seconds": ...}` to `--out`.  The `bfloat16`
control rounds `lo_revenue` only.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import ssb_data  # noqa: E402  (beside this file, found by path)

sys.path.remove(HERE)

HLL_PRECISION = 11  # Druid's hyperUnique: 2^11 buckets
COLUMNS = ["rank", "key", "measure", "value"]


def fmix32(keys: np.ndarray, seed: int = 0) -> np.ndarray:
    """murmur3 fmix32 of the keys' int32 bits, in uint64 lanes kept to 32
    bits; `seed` mixed in first."""
    h = (np.asarray(keys).astype(np.int64) & 0xFFFFFFFF).astype(np.uint64)
    h ^= np.uint64((seed * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    h = (h * np.uint64(0x85EBCA6B)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(13)
    h = (h * np.uint64(0xC2B2AE35)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    return h


def bit_length(w: np.ndarray) -> np.ndarray:
    """Bit length of each value below 2^32, by halving shifts."""
    w = np.asarray(w, dtype=np.uint64).copy()
    out = np.zeros(w.shape, dtype=np.int64)
    for s in (16, 8, 4, 2, 1):
        big = w >= np.uint64(1 << s)
        w = np.where(big, w >> np.uint64(s), w)
        out += big * s
    return out + (w > 0)


def bucket_rho(h: np.ndarray, p: int = HLL_PRECISION):
    bucket = (h & np.uint64((1 << p) - 1)).astype(np.int64)
    rho = (33 - p) - bit_length(h >> np.uint64(p))
    return bucket, rho


def fold(regs: np.ndarray, group: np.ndarray, bucket: np.ndarray,
         rho: np.ndarray) -> None:
    """regs[g, b] = max(regs[g, b], rho) for every row, in place."""
    m = regs.shape[1]
    np.maximum.at(regs.reshape(-1), group * m + bucket, rho)


def estimate(regs: np.ndarray) -> np.ndarray:
    """Classic HLL estimate per row of `regs` [G, m], float64."""
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


def _interval_ms(text: str):
    lo, hi = text.split("/")
    return tuple(
        int(np.datetime64(s.rstrip("Z"), "ms").astype(np.int64))
        for s in (lo, hi)
    )


class Query:
    """One traffic query's native JSON, read for the subset the mix uses."""

    def __init__(self, spec: dict):
        if spec.get("queryType") != "topN" or spec.get("granularity") != "all":
            raise ValueError(f"not a topN over granularity all: {spec}")
        (self.interval,) = [_interval_ms(s) for s in spec["intervals"]]
        self.dim = spec["dimension"]
        self.threshold = int(spec["threshold"])
        self.metric = spec["metric"]
        f = spec.get("filter")
        if f is not None and f.get("type") != "selector":
            raise ValueError(f"unsupported filter {f}")
        self.filter = f and (f["dimension"], f["value"])
        self.sums, self.uniques = {}, {}
        for a in spec["aggregations"]:
            if a["type"] == "doubleSum":
                self.sums[a["name"]] = a["fieldName"]
            elif a["type"] == "hyperUnique":
                self.uniques[a["name"]] = a["fieldName"]
            else:
                raise ValueError(f"unsupported aggregation {a}")


class Partial:
    """One query's running state over the chunks: rows, sums (one set per
    precision) and registers per dimension code."""

    def __init__(self, q: Query, n_groups: int, precisions):
        self.q = q
        self.rows = np.zeros(n_groups, dtype=np.int64)
        self.sums = {
            p: {n: np.zeros(n_groups) for n in q.sums} for p in precisions
        }
        self.regs = {
            n: np.zeros((n_groups, 1 << HLL_PRECISION), dtype=np.int64)
            for n in q.uniques
        }

    def add(self, codes, keep, values, hashed):
        g = codes[keep]
        self.rows += np.bincount(g, minlength=len(self.rows))
        for p, sums in self.sums.items():
            for n, field in self.q.sums.items():
                sums[n] += np.bincount(
                    g, weights=values[p][field][keep], minlength=len(self.rows)
                )
        for n, field in self.q.uniques.items():
            bucket, rho = hashed[field]
            fold(self.regs[n], g, bucket[keep], rho[keep])

    def answer(self, names, precision):
        """The long frame of the top `threshold` groups."""
        import pandas as pd

        q = self.q
        present = np.nonzero(self.rows > 0)[0]
        table = {n: s[present] for n, s in self.sums[precision].items()}
        for n in q.uniques:
            table[n] = np.rint(estimate(self.regs[n][present])).astype(np.int64)
        # codes are in the dimension's sorted order: a stable sort keeps
        # ties by value ascending
        order = np.argsort(-np.asarray(table[q.metric]), kind="stable")
        order = order[: q.threshold]
        rows = []
        for rank, i in enumerate(order):
            key = str(names[present[i]])
            for n in (*q.sums, *q.uniques):
                rows.append((rank, key, n, float(table[n][i])))
        return pd.DataFrame(rows, columns=COLUMNS)


def reference_answers(scale, seed, queries, precisions=("float32",)):
    """{precision: {query name: long frame}} over the fact table that
    (`scale`, `seed`) defines, chunk by chunk: one pass, the registers
    folded once, the sums once per precision."""
    qs = {name: Query(spec) for name, spec in queries.items()}
    tables = ssb_data.gen_dim_tables(scale, np.random.default_rng(seed))
    cats = ssb_data.oracle_categories(tables)
    parts = {
        name: Partial(q, len(cats[q.dim][0]), precisions)
        for name, q in qs.items()
    }
    uniques = {f for q in qs.values() for f in q.uniques.values()}
    sums = {f for q in qs.values() for f in q.sums.values()}
    for lo in ssb_data.fact_chunks(scale, seed, ssb_data.CHUNK_ROWS, tables):
        t = lo["lo_orderdate"]
        hashed = {f: bucket_rho(fmix32(lo[f])) for f in uniques}
        values = {
            p: {f: ssb_data._round_to(lo[f], p) for f in sums}
            for p in precisions
        }
        idx = {}

        def codes_of(attr):
            table, fk = ssb_data.DIM_ATTRS[attr]
            if table not in idx:
                idx[table] = ssb_data._fk_row_index(
                    lo, fk, table, tables["dwdate"]
                )
            return cats[attr][1][idx[table]]

        for name, q in qs.items():
            keep = (t >= q.interval[0]) & (t < q.interval[1])
            if q.filter:
                attr, value = q.filter
                hit = np.nonzero(cats[attr][0] == value)[0]
                keep &= codes_of(attr) == (hit[0] if len(hit) else -1)
            parts[name].add(codes_of(q.dim), keep, values, hashed)
    return {
        p: {
            name: parts[name].answer(cats[q.dim][0], p)
            for name, q in qs.items()
        }
        for p in precisions
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description="SSB topN + hyperUnique reference child")
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", required=True,
                    help="a JSON file: {query name: native query}")
    ap.add_argument("--precision", action="append", required=True,
                    help="one answer set per precision, in this order")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.nice(10)  # the window's client and server threads come first
    t0 = time.perf_counter()
    with open(args.queries) as f:
        queries = json.load(f)
    out = reference_answers(args.scale, args.seed, queries, args.precision)
    out["seconds"] = time.perf_counter() - t0
    tmp = args.out + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(out, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
