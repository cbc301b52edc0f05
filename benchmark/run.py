"""One run of one cell of BENCHMARK.json.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that holds the chip.  It fails (non-zero, no
result line) unless JAX shows a TPU with exactly the cell's number of
chips; `--rehearse` lifts that check for a CPU rehearsal at the
configuration's `rehearse_scale`, and the last line then names the
platform it really ran on.  The last line of standard output is the one
JSON object of the contract; everything else is on earlier lines.
See README.md beside this file.
"""

import argparse
import dataclasses
import gc
import json
import os
import shutil
import statistics
import sys
import time

T_START = time.perf_counter()  # set-up is counted from here

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, ROOT]

from harness import cells, compare, stats, trace_reduce  # noqa: E402
from harness.window import Hooks, Window, field_stat  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # listed in .gitignore
TRACE_MIN_S = 3.0  # whole passes are traced until this much is covered
TRACE_CAP_S = 8.0  # and never past this: the trace has to stay small
REFERENCE_WAIT_S = 240.0  # past the window's close, for the reference child
WARM_PASSES = 3  # at most, until one pass compiles and copies nothing
SLOWEST = 10  # requests of the window named on the `slowest` line
PARTS = 3  # equal parts of the window on the `window_parts` line


def say(**line):
    print(json.dumps(line, default=str), flush=True)


class TraceHooks(Hooks):
    """Profiles whole passes from the window's first on, each request
    inside a `request:<query>` annotation on the profiler's clock."""

    def __init__(self, logdir):
        import jax

        self.jax = jax
        self.logdir = logdir
        self.state = "ready"  # -> "on" -> "done"
        self.t_on = 0.0
        self.traced = []  # the requests of the passes the profiler saw

    def pass_begins(self, index):
        if self.state == "ready":
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no Python frames: small trace
            self.jax.profiler.start_trace(self.logdir, profiler_options=opts)
            self.state = "on"
            self.t_on = time.perf_counter()

    def pass_ended(self, index, requests):
        if self.state == "on":
            self.traced.extend(requests)
            if time.perf_counter() - self.t_on >= TRACE_MIN_S:
                self.stop()

    def request(self, query_name):
        if self.state == "on" and time.perf_counter() - self.t_on > TRACE_CAP_S:
            self.stop()
        if self.state == "on":
            return self.jax.profiler.TraceAnnotation("request:" + query_name)
        return super().request(query_name)

    def stop(self):
        if self.state == "on":
            self.jax.profiler.stop_trace()
            self.state = "done"


def warm_up(system, traffic):
    """Every query of the mix, pass after pass, until a pass hits the
    program cache and copies nothing to the device.  Returns the last
    pass's metrics by query and whether it was warm."""
    last = {}
    for n in range(WARM_PASSES):
        cold = []
        for q in traffic["queries"]:
            t0 = time.perf_counter()
            status, body, m = system.send(q)
            ms = (time.perf_counter() - t0) * 1e3
            if status != 200:
                raise RuntimeError(f"warm-up {q['name']}: HTTP {status}: {body}")
            last[q["name"]] = m
            if m.segments and not (m.program_cache_hit and m.h2d_bytes == 0):
                cold.append(q["name"])
            say(phase="warm_up", round=n, query=q["name"], ms=round(ms, 2),
                strategy=m.strategy, segments=m.segments,
                num_groups=m.num_groups, compile_ms=round(m.compile_ms, 2),
                h2d_bytes=m.h2d_bytes, program_cache_hit=m.program_cache_hit)
        if n and not cold:
            return last, True
    return last, False


def check_answers(cell, requests, want, frame_of):
    """The numbers `correct` rests on, over every request of the window:
    requests that failed or left the timed path, answers whose keys
    differ, and the worst relative error of a sum.  Also per query."""
    failed = keys = 0
    worst = 0.0
    by_query = {}
    for r in requests:
        faults = (
            [f"HTTP {r.status}"] if r.status != 200
            else compare.metrics_faults(r.metrics, cell.chips > 1)
        )
        if faults:
            failed += 1
            by_query.setdefault(r.query, {}).setdefault("faults", faults)
            continue
        n = compare.answer_numbers(frame_of(r.body), want[r.query])
        q = by_query.setdefault(r.query, {"sum_rel_err": 0.0, "key_mismatches": 0})
        if n["key_mismatch"]:
            keys += 1
            q["key_mismatches"] = q.get("key_mismatches", 0) + 1
        else:
            worst = max(worst, n["sum_rel_err"])
            q["sum_rel_err"] = max(q.get("sum_rel_err", 0.0), n["sum_rel_err"])
    values = {
        "failed_requests": failed, "key_mismatches": keys,
        "sum_rel_err_max": worst,
    }
    return values, by_query


def control_values(requests, control, want):
    """The control (the reference in the precision below) put in the
    program's place: its answer to each of the window's requests."""
    import pandas as pd

    keys = 0
    worst = 0.0
    for name in {r.query for r in requests}:
        got = control[name]
        got = pd.DataFrame([[got]]) if isinstance(got, float) else got
        n = compare.answer_numbers(got, want[name])
        if n["key_mismatch"]:
            keys += 1
        else:
            worst = max(worst, n["sum_rel_err"])
    return {"failed_requests": 0, "key_mismatches": keys, "sum_rel_err_max": worst}


def per_layer_metrics(cell, window):
    out = {}
    for m in cell.per_layer:
        if m.read is not None:
            value = m.read(window)
        else:
            value = field_stat(window, m.spec["reader"])
        if value is not None:  # nothing to read: the metric is left out
            out[m.name] = {"value": value, "unit": m.unit}
    return out


class GcWatch:
    """Seconds the interpreter's collector ran while this was open: a
    diagnostic for the window line (a long collection stalls every thread
    of the one process that is client and server)."""

    def __init__(self):
        self.seconds = 0.0
        self.longest = 0.0
        self.collections = 0
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            d = time.perf_counter() - self._t0
            self.seconds += d
            self.longest = max(self.longest, d)
            self.collections += 1

    def close(self):
        gc.callbacks.remove(self._on)
        return {"gc_s": self.seconds, "gc_longest_ms": self.longest * 1e3,
                "gc_collections": self.collections}


def process_cpu_s():
    """CPU seconds of this process so far, all threads: over a window it
    says how many cores client, server and runtime kept busy."""
    t = os.times()
    return t.user + t.system


@dataclasses.dataclass
class Driven:
    """What a window leaves once the system is gone."""

    requests: list
    setup_s: float
    memory_peak_bytes: int  # of the fullest chip
    column_bytes: dict
    traced: list  # the requests the profiler saw (a traced run)
    diagnostics: dict  # GcWatch's reading and the CPU seconds of the window


def drive(cell, args, scale, devs):
    """Set-up, the window, and what has to be read before the system goes."""
    config, traffic = cell.config, cell.traffic
    system = cell.loader.start_system(config, args.seed, scale, say)
    try:
        last, warm = warm_up(system, traffic)
        if not warm:
            say(phase="warning", what="warm-up never reached a pass that hit "
                "the program cache with nothing copied: expect compiles_in_window")
        routed = {name: m.strategy for name, m in last.items()}
        expected = cell.spec.get("expect_strategy", {})
        if routed != expected:
            # routing is the cost model's to change; the ledger must show it did
            say(phase="warning", what="routing differs from the cell's file",
                routed=routed, expected=expected)
        hooks = Hooks()
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            hooks = TraceHooks(TRACE_DIR)
        gc_watch = GcWatch()
        cpu_before = process_cpu_s()
        try:
            setup_s = time.perf_counter() - T_START
            requests = cell.loop.run(system, traffic, args.seconds, args.seed, hooks)
        finally:
            diagnostics = {
                **gc_watch.close(),
                "process_cpu_s": process_cpu_s() - cpu_before,
            }
            hooks.stop()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
        return Driven(
            requests, setup_s, int(max(peaks)), system.column_bytes(),
            hooks.traced, diagnostics,
        )
    finally:
        system.close()


def report_window(requests, e2e, diagnostics):
    """The earlier lines a reader wants: the window, its slowest requests,
    each query's medians and counters."""
    t_first = requests[0].sent_s
    between = sum(
        max(0.0, b.sent_s - a.done_s) for a, b in zip(requests, requests[1:])
    )
    say(phase="window", **e2e, between_requests_s=between, **diagnostics)
    # the same statistics over each third of the window: where the thirds
    # agree and runs do not, the spread is between processes, not in the
    # window's length
    cuts = [t_first + e2e["window_s"] * k / PARTS for k in range(PARTS + 1)]
    cuts[-1] = float("inf")
    say(phase="window_parts", parts=[
        stats.window_stats([r.sent_s for r in part], [r.done_s for r in part])
        for lo, hi in zip(cuts, cuts[1:])
        if (part := [r for r in requests if lo <= r.sent_s < hi])
    ])
    slowest = sorted(requests, key=lambda r: -r.wall_ms)[:SLOWEST]
    say(phase="slowest", columns=["query", "wall_ms", "sent_at_s",
                                  "engine_total_ms", "dispatch_ms"],
        requests=[
            [r.query, round(r.wall_ms, 3), round(r.sent_s - t_first, 3),
             r.metrics and round(r.metrics.total_ms, 3),
             r.metrics and round(r.metrics.device_ms, 3)]
            for r in slowest
        ])
    for name in sorted({r.query for r in requests}):
        rs = [r for r in requests if r.query == name]
        ms = [r.metrics for r in rs if r.metrics is not None]
        walls = sorted(r.wall_ms for r in rs)
        say(phase="query", query=name, n=len(rs),
            median_ms=statistics.median(walls), max_ms=walls[-1],
            strategy=ms[-1].strategy if ms else None,
            segments=ms[-1].segments if ms else None,
            rows_scanned=ms[-1].rows_scanned if ms else None,
            num_groups=ms[-1].num_groups if ms else None,
            device_ms=statistics.median(m.device_ms for m in ms) if ms else None)


def traced_metrics(cell, args, dev, driven, device):
    """(per-layer metrics, breakdown or None) of a `--trace 1` run; adds
    `busy_s` and `window_s` to `device` where the trace holds a device
    plane."""
    window = Window(
        requests=driven.requests,
        queries={q["name"]: q for q in cell.traffic["queries"]},
        column_bytes=driven.column_bytes, traced=driven.traced,
    )
    path = trace_reduce.find_xplane(TRACE_DIR)
    trace = trace_reduce.reduce_trace(path) if path else None
    say(phase="trace", file=path,
        bytes=os.path.getsize(path) if path else None,
        traced_requests=len(driven.traced),
        reduced=trace or "no device plane: device metrics not measured")
    if not args.keep_trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    breakdown = None
    if trace is not None:
        window.trace = trace
        window.peaks = cells.read_json(
            os.path.join(BENCH_DIR, "harness", "peaks.json")
        ).get(dev.device_kind)
        if window.peaks is None:
            raise KeyError(f"peaks.json has no entry for {dev.device_kind!r}")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {k: trace[k] for k in ("device_ops", "idle_gaps")}
    return per_layer_metrics(cell, window), breakdown


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU at rehearse_scale (never the driver)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the traced run's .bench_trace/ in place")
    ap.add_argument("--control", action="store_true",
                    help="also judge the low-precision control (never the driver)")
    args = ap.parse_args(argv)

    cell = cells.load_cell(ROOT, BENCH_DIR, args.workload)
    config, traffic = cell.config, cell.traffic

    import jax

    devs = jax.devices()
    dev = devs[0]
    if not args.rehearse and (dev.platform != "tpu" or len(devs) != cell.chips):
        print(
            f"run.py: {cell.name} needs {cell.chips} TPU chip(s); JAX shows "
            f"{len(devs)} x {dev.platform} ({dev.device_kind})", file=sys.stderr,
        )
        return 2
    scale = config["rehearse_scale"] if args.rehearse else config["scale"]
    say(phase="start", workload=cell.name, seed=args.seed, device=str(dev),
        kind=dev.device_kind, count=len(devs), rehearse=args.rehearse,
        scale=scale)

    precisions = ["float32"] + ([config["control_precision"]] if args.control else [])
    reference = cell.loader.start_reference(
        config, traffic["queries"], args.seed, scale, precisions
    )
    try:
        driven = drive(cell, args, scale, devs)
        requests = driven.requests
        e2e = stats.window_stats(
            [r.sent_s for r in requests], [r.done_s for r in requests]
        )
        e2e["setup_s"] = driven.setup_s
        report_window(requests, e2e, driven.diagnostics)
        device = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "memory_peak_bytes": driven.memory_peak_bytes,
        }
        breakdown = None
        if args.trace:
            metrics, breakdown = traced_metrics(cell, args, dev, driven, device)
        else:
            metrics = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end
            }
        answers = reference.join(REFERENCE_WAIT_S)
    finally:
        reference.close()
    say(phase="reference", seconds=answers["seconds"])
    values, by_query = check_answers(
        cell, requests, answers["float32"], cell.loader.to_frame
    )
    for name in sorted(by_query):
        say(phase="checked", query=name, **by_query[name])
    limits = cell.spec["limits"]
    checks = compare.judge(values, limits)
    if args.control:
        control = compare.judge(
            control_values(requests, answers[config["control_precision"]],
                           answers["float32"]),
            limits,
        )
        say(phase="control", precision=config["control_precision"],
            correct=compare.verdict(control), checks=control)
    # the contract's keys, `breakdown` where there is one, and last the
    # numbers compared, each beside its limit
    result = {
        "correct": compare.verdict(checks), "attempted": len(requests),
        "failed": values["failed_requests"] + values["key_mismatches"],
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
