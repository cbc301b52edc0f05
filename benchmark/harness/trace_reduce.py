"""From a `jax.profiler` trace (`*.xplane.pb`) to device busy time, the
operations that took it, and the idle gaps by what the host was doing.

The yardstick for `device.busy_s`, `device.window_s`, `breakdown` and
every per-layer metric whose source is `device_trace`.  Nothing of the
program is imported: only `jax.profiler.ProfileData`.

What a trace holds (looked at by hand on a TPU v5 lite, PR 24): one plane
per chip named `/device:TPU:<n>` with the lines `XLA Modules` (one event
per executed program), `XLA Ops` (one event per HLO operation the core
executed, in sequence), `Async XLA Ops` (copies and slices in flight beside
them) and `TC Overlay`; and a `/host:CPU` plane with one line per host
thread, where the client's `TraceAnnotation("request:<query>")` spans lie.
Both are on one clock: each request span holds its program's module event,
some 6 ms after its start.  Busy is the union of the `XLA Ops` events: what
the core executed.  An operation's name is its whole HLO text; the
breakdown keeps what stands before " = ", less the instance number.  A CPU backend writes no device
plane: `reduce_trace` then returns None and no device metric is reported.
"""

from __future__ import annotations

import glob
import gzip
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
REQUEST_PREFIX = "request:"
BETWEEN = "between requests"
TOP = 10

Interval = Tuple[float, float]


def find_xplane(logdir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged copy of `intervals` (overlaps and touches joined)."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo)
    ]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] that the merged `busy` leaves."""
    out = []
    at = lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(
    idle: Sequence[Interval], requests: Sequence[Tuple[str, float, float]]
) -> Dict[str, float]:
    """Idle nanoseconds by the request annotation the host was in: each
    gap is split among the `request:<query>` spans it overlaps, and what
    no span covers goes to "between requests"."""
    out: Dict[str, float] = {}
    spans = sorted(requests, key=lambda r: r[1])
    for gap in idle:
        left = gap[1] - gap[0]
        for name, lo, hi in spans:
            if lo >= gap[1]:
                break
            ov = _overlap(gap, (lo, hi))
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                left -= ov
        if left > 0:
            out[BETWEEN] = out.get(BETWEEN, 0.0) + left
    return out


def _top(seconds_by_name: Dict[str, float]) -> List[List]:
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, ns / 1e9] for name, ns in ranked[:TOP]]


def op_family(hlo_text: str) -> str:
    """`%pallas_partial_aggregate.4 = (f32[2,128]...) custom-call(...)` ->
    `%pallas_partial_aggregate`: what stands before " = ", without the
    number XLA appends to tell one instance from the next (a program over
    17 segments holds 17 of them, and the breakdown is by kind)."""
    name = hlo_text.split(" = ")[0]
    stem, dot, number = name.rpartition(".")
    return stem if dot and number.isdigit() else name


def read_planes(path: str):
    """(device op events per device plane, request spans) of one trace:
    `{plane: [(name, start_ns, end_ns)]}`, `[(name, start_ns, end_ns)]`."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    requests: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (op_family(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in line.events
                )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                requests.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(REQUEST_PREFIX)
                )
    return devices, requests


def reduce_events(devices, requests) -> Optional[dict]:
    """The reduction proper, on plain tuples (so a test can feed it a
    made-up trace).  The traced window runs from the first request span's
    start to the last one's end; device events are clipped to it.  Returns
    None when there is no device plane or no request span."""
    if not devices or not requests:
        return None
    lo = min(r[1] for r in requests)
    hi = max(r[2] for r in requests)
    busy_ns = 0.0
    ops: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    for events in devices.values():
        merged = union(clip([(a, b) for _, a, b in events], lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in events:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d
        for name, ns in attribute_gaps(gaps(merged, lo, hi), requests).items():
            idle_by[name] = idle_by.get(name, 0.0) + ns
    n = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "devices": n,
        "requests": len(requests),
        # seconds summed over operations of one name / over gaps of one
        # host state, averaged over the chips
        "device_ops": [[k, s / n] for k, s in _top(ops)],
        "idle_gaps": [[k, s / n] for k, s in _top(idle_by)],
    }


def reduce_trace(path: str) -> Optional[dict]:
    devices, requests = read_planes(path)
    return reduce_events(devices, requests)


def describe(path: str, head: int = 5) -> None:
    """Print what a trace holds: planes, lines, event counts and each
    line's first events.  For looking at one by hand."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:head]:
                print(f"    {e.name[:80]!r} start_ns={e.start_ns} "
                      f"duration_ns={e.duration_ns}")


if __name__ == "__main__":
    import json
    import sys

    describe(sys.argv[1])
    print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
