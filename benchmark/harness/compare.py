"""The comparison that decides `correct`: a served answer against the
float64 reference's.  COPIED from `chip_smoke.py` (`parity`,
`metrics_faults`) at commit 1f06452 and not to track it; changed to
return the numbers it compares, so that each can be printed beside its
limit, instead of a verdict at a tolerance of its own.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def answer_numbers(got, want) -> Dict[str, float]:
    """`got` (DataFrame of the served rows) against `want` (the
    reference's float, or DataFrame whose last column is the sum):
    `key_mismatch` 1.0 when the row count or a group column differs
    (then nothing else is compared), else 0.0 with
    `sum_rel_err`, the worst |got - want| / |want| over the groups."""
    if isinstance(want, float):
        if len(got) != 1:
            return {"key_mismatch": 1.0}
        g = float(got.iloc[0, -1])
        return {
            "key_mismatch": 0.0,
            "sum_rel_err": abs(g - want) / max(abs(want), 1e-300),
        }
    vcol = want.columns[-1]
    keys = [c for c in want.columns if c != vcol]
    if len(got) != len(want):
        return {"key_mismatch": 1.0}
    if not len(want):  # (an empty answer has no columns to compare)
        return {"key_mismatch": 0.0, "sum_rel_err": 0.0}
    if any(c not in got.columns for c in want.columns):
        return {"key_mismatch": 1.0}
    # keys compare as strings: JSON and pandas disagree on int vs str years
    got = got.assign(**{c: got[c].astype(str) for c in keys})
    want = want.assign(**{c: want[c].astype(str) for c in keys})
    got = got.sort_values(keys).reset_index(drop=True)
    want = want.sort_values(keys).reset_index(drop=True)
    for c in keys:
        if list(got[c]) != list(want[c]):
            return {"key_mismatch": 1.0}
    w = np.asarray(want[vcol], dtype=np.float64)
    g = np.asarray(got[vcol], dtype=np.float64)
    err = np.abs(g - w) / np.maximum(np.abs(w), 1e-300)
    return {"key_mismatch": 0.0, "sum_rel_err": float(np.max(err))}


def metrics_faults(m, distributed: bool = False) -> List[str]:
    """What in a request's QueryMetrics says it left the path the cell
    times: the device, undegraded, first time, on one chip (or the mesh).
    A tier never gives way to another on a device error (it raises into
    the retry machinery), so `retries` covers that too."""
    if m is None:
        return ["no metrics"]
    out = []
    if bool(m.distributed) != distributed:
        out.append(f"distributed={m.distributed} mesh_shape={m.mesh_shape}")
    if m.executor != "device":
        out.append(f"executor={m.executor}")
    if m.degraded:
        out.append("degraded")
    if m.retries:
        out.append(f"retries={m.retries}")
    if m.partial:
        out.append("partial")
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; `ok` where value <= limit.
    A number the cell states no limit for is an error, not a pass."""
    out = {}
    for name, value in values.items():
        if name not in limits:
            raise KeyError(f"the cell states no limit for {name!r}")
        limit = limits[name]
        out[name] = {
            "value": value, "limit": limit,
            "ok": bool(value is not None and value <= limit),
        }
    return out


def verdict(checks: Optional[Dict[str, dict]]) -> bool:
    return bool(checks) and all(c["ok"] for c in checks.values())
