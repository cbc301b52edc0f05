"""Which part of the program is each device operation of a profiler trace?

    python tools/trace_scopes.py <trace.xplane.pb[.gz]>

Reads a `jax.profiler` trace (`exec/metrics.trace(logdir)`, or the
benchmark's `--trace 1 --keep-trace`) and lists the device's `XLA Ops`
by kind with, for each kind: the device scope it was traced under (the
`sdol.*` components of its HLO `op_name`, see `obs.SCOPE_*`), the source
line, its total time, its SELF time (less the operations it holds: a
`%while` holds its body's) and what holds it.  With `request:<query>`
annotations on the host plane (the benchmark's) the same table is also
given per query, and the program's `sdol:<span>` host events are summed
by name and checked to lie inside their request.  A trace of several
chips (the mesh) has a device plane each: the tables sum over them, and
`scopes by chip` gives each plane's self time by innermost scope, so that
the boundary merge (`sdol.boundary_merge`) and the wait in it for the
slowest shard can be read per chip, and per request and chip.

A by-hand tool, not the benchmark's yardstick (`benchmark/harness/
trace_reduce.py` is): it needs the xplane schema for the per-operation
metadata, which `jax.profiler.ProfileData` does not expose, and takes it
from the installed tensorflow (`tensorflow.tsl.profiler.protobuf`; the
package itself does not depend on it).  `tests/test_trace_scopes.py` runs
it on the benchmark's recorded flight1 trace.
"""

from __future__ import annotations

import gzip
import json
import sys
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SCOPE_PREFIX = "sdol."
SPAN_PREFIX = "sdol:"
REQUEST_PREFIX = "request:"
TOP = 12  # rows of the table over all requests (6 per query)


def load(path):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    opener = gzip.open if path.endswith(".gz") else open
    space = xplane_pb2.XSpace()
    with opener(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def family(hlo_text: str) -> str:
    """`%copy.12 = ...` -> `%copy` (as trace_reduce.op_family)."""
    name = hlo_text.split(" = ")[0]
    stem, dot, number = name.rpartition(".")
    return stem if dot and number.isdigit() else name


def scope_of(tf_op: str) -> str:
    parts = [p for p in tf_op.split("/") if p.startswith(SCOPE_PREFIX)]
    return "/".join(parts) or "-"


def _events(line):
    t0 = line.timestamp_ns * 1000  # ps
    for e in line.events:
        yield e.metadata_id, t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps


def _meta(plane):
    """metadata_id -> (family, scope, op_name tail, source)."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for mid, em in plane.event_metadata.items():
        stats = {}
        for st in em.stats:
            key = names.get(st.metadata_id)
            if key in ("tf_op", "source"):
                stats[key] = st.str_value or (
                    names.get(st.ref_value, "") if st.ref_value else ""
                )
        tf_op = stats.get("tf_op", "")
        out[mid] = (
            family(em.name), scope_of(tf_op),
            "/".join(tf_op.rstrip(":").split("/")[-2:]),
            stats.get("source", "").split("/")[-1],
        )
    return out


def host_events(space):
    """(request intervals, sdol span events) of the host plane, in ps."""
    requests, spans = [], []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for mid, a, b in _events(line):
                name = plane.event_metadata[mid].name
                if name.startswith(REQUEST_PREFIX):
                    requests.append((name, a, b))
                elif name.startswith(SPAN_PREFIX):
                    spans.append((name, a, b))
    return sorted(requests, key=lambda r: r[1]), spans


def device_rows(space, requests):
    """One row per device operation event: (request name, family, scope,
    op tail, source, duration ps, self ps, holder family, device plane)."""
    rows = []
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        meta = _meta(plane)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            stack = []  # open holders: [end, row index]
            for mid, a, b in sorted(_events(line), key=lambda e: (e[1], -e[2])):
                while stack and stack[-1][0] <= a:
                    stack.pop()
                holder = rows[stack[-1][1]] if stack else None
                if holder is not None:
                    holder[6] -= b - a  # the holder's self time
                req = next(
                    (n for n, lo, hi in requests if lo <= a < hi), "-"
                )
                fam, scope, tail, src = meta.get(mid, ("?", "-", "", ""))
                rows.append([req, fam, scope, tail, src, b - a, b - a,
                             holder[1] if holder is not None else "-",
                             plane.name])
                stack.append((b, len(rows) - 1))
    return rows


def table(rows, top):
    """Rows summed by (family, scope, holder), ranked by self time."""
    acc = defaultdict(lambda: [0, 0, 0, "", ""])
    for _req, fam, scope, tail, src, dur, self_ps, holder, _plane in rows:
        a = acc[(fam, scope, holder)]
        a[0] += 1
        a[1] += dur
        a[2] += self_ps
        a[3], a[4] = a[3] or tail, a[4] or src
    ranked = sorted(acc.items(), key=lambda kv: -kv[1][2])[:top]
    return [
        {"op": fam, "scope": scope, "held_by": holder, "n": n,
         "total_s": dur / 1e12, "self_s": self_ps / 1e12,
         "op_name": tail, "source": src}
        for (fam, scope, holder), (n, dur, self_ps, tail, src) in ranked
    ]


def scopes_by_chip(rows):
    """Self seconds by innermost scope on each device plane:
    {scope: {plane: seconds}}, with "-" for operations under no scope."""
    acc = defaultdict(lambda: defaultdict(int))
    for r in rows:
        acc[r[2].split("/")[-1]][r[8]] += r[6]
    return {
        scope: {plane: ps / 1e12 for plane, ps in sorted(chips.items())}
        for scope, chips in sorted(acc.items())
    }


def span_summary(requests, spans):
    """`sdol:<span>` host events by name, and how many lie outside every
    `request:<query>` interval (none should, in a benchmark's trace)."""
    by_name = defaultdict(lambda: [0, 0])
    outside = 0
    for name, a, b in spans:
        by_name[name][0] += 1
        by_name[name][1] += b - a
        if requests and not any(lo <= a and b <= hi for _, lo, hi in requests):
            outside += 1
    return {
        "spans": {k: {"n": n, "total_s": ps / 1e12}
                  for k, (n, ps) in sorted(by_name.items())},
        "outside_requests": outside,
    }


def summarize(path):
    space = load(path)
    requests, spans = host_events(space)
    rows = device_rows(space, requests)
    out = {"device_ops": table(rows, TOP), "by_request": {},
           "scopes_by_chip": scopes_by_chip(rows),
           "host": span_summary(requests, spans)}
    chips = len({r[8] for r in rows}) or 1
    for req in sorted({r[0] for r in rows} - {"-"}):
        mine = [r for r in rows if r[0] == req]
        n = sum(1 for name, _, _ in requests if name == req)
        out["by_request"][req] = {
            "requests": n,
            "device_self_s_per_request": sum(r[6] for r in mine) / 1e12 / n,
            "scope_ms_per_request_and_chip": {
                scope: sum(by.values()) * 1e3 / n / chips
                for scope, by in scopes_by_chip(mine).items()
            },
            "ops": table(mine, 6),
        }
    return out


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    out = summarize(argv[0])

    def show(ops):
        for o in ops:
            print(f"  {o['self_s']:9.4f}s self {o['total_s']:9.4f}s total "
                  f"n={o['n']:<6} {o['op']:<28} scope={o['scope']:<44} "
                  f"in={o['held_by']:<10} {o['op_name']} {o['source']}")

    print("device operations (all requests):")
    show(out["device_ops"])
    for req, d in out["by_request"].items():
        print(f"{req}: {d['requests']} requests, "
              f"{d['device_self_s_per_request'] * 1e3:.2f} ms on the device each")
        print("  ms by scope, a request and chip:",
              json.dumps({k: round(v, 4) for k, v in
                          d["scope_ms_per_request_and_chip"].items()}))
        show(d["ops"])
    print("scopes by chip (self s):", json.dumps(out["scopes_by_chip"]))
    print("host spans:", json.dumps(out["host"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
