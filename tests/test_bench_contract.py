"""The driver's parse contract for bench.py (VERDICT r3 #4).

Round 3 regression: the single stdout JSON line grew past what the driver
parses (per-query metrics + probe logs), so the round's headline landed as
``parsed: null``.  The contract now under test: ``_emit`` prints ONE compact
JSON line (< 2000 chars, machine-parseable, headline fields present) and
writes the full record to BENCH_<mode>_detail.json.
"""

import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fat_result():
    # a round-3-shaped result: 13 queries x nested metrics + a long probe log
    per_q = {
        "q%d_%d" % (i, j): {
            "tpu_ms": 123.45,
            "pandas_ms": 678.9,
            "max_rel_err": 1e-12,
            "metrics": {k: 1.0 for k in ("scan_bytes", "kernel_ms",
                                         "merge_ms", "scan_bytes_per_sec",
                                         "segments", "rows_scanned")},
        }
        for i in range(1, 5)
        for j in range(1, 4)
    }
    probe = [
        {"t": "2026-07-31T00:00:00Z", "platform": None,
         "error": "probe timeout after 120s " + "x" * 200}
        for _ in range(30)
    ]
    return {
        "metric": "ssb_sf100_q1-q4_p50_latency",
        "value": 5090.0,
        "unit": "ms",
        "vs_baseline": 4.2,
        "degraded": True,
        "device": "TFRT_CPU_0",
        "detail": {
            "rows": 600_037_902,
            "max_rel_err": 3e-9,
            "rows_per_sec_per_chip": 117_906_269,
            "ingest_s": 1344.6,
            "queries": per_q,
            "probe_attempts": probe,
        },
    }


def test_emit_stdout_is_compact_and_parseable(capsys, tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    bench._emit(_fat_result(), "ssb")
    line = capsys.readouterr().out.strip()
    assert "\n" not in line, "must be ONE line"
    assert len(line) < 2000, "headline line must stay driver-parseable"
    parsed = json.loads(line)
    for k in ("metric", "value", "unit", "vs_baseline", "degraded", "device"):
        assert k in parsed, k
    assert parsed["metric"] == "ssb_sf100_q1-q4_p50_latency"
    assert parsed["vs_baseline"] == 4.2
    # absolute path so a consumer can resolve it regardless of its cwd
    assert parsed["detail_artifact"] == str(tmp_path / "BENCH_ssb_detail.json")
    # nested fat maps must NOT be inline
    assert "queries" not in parsed and "probe_attempts" not in parsed

    detail = json.load(open(tmp_path / "BENCH_ssb_detail.json"))
    assert detail["detail"]["queries"]["q1_1"]["tpu_ms"] == 123.45
    assert len(detail["detail"]["probe_attempts"]) == 30


def test_emit_preserves_tpu_detail_from_cpu_overwrite(tmp_path, monkeypatch,
                                                      capsys):
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    tpu = dict(_fat_result(), degraded=False, device="TPU_0(process=0,(0,0,0,0))")
    bench._emit(tpu, "ssb")
    # the headline points at the clobber-proof TPU copy, not the primary
    line = json.loads(capsys.readouterr().out.strip())
    assert line["detail_artifact"] == str(
        tmp_path / "BENCH_tpu_ssb_detail.json"
    )
    # a later degraded CPU rerun must not clobber the TPU sidecar
    bench._emit(_fat_result(), "ssb")
    kept = json.load(open(tmp_path / "BENCH_tpu_ssb_detail.json"))
    assert kept["device"] == "TPU_0(process=0,(0,0,0,0))"
    capsys.readouterr()


def test_production_tag_keys_scale(monkeypatch):
    bench = _load_bench()
    mode, _, arg = bench._parse_args(["ssb", "100"])
    assert "%s_%g" % (mode, arg) == "ssb_100"
    mode, _, arg = bench._parse_args(["tpch_q1", "0.1"])
    assert "%s_%g" % (mode, arg) == "tpch_q1_0.1"
    mode, _, arg = bench._parse_args([])
    assert "%s_%g" % (mode, arg) == "ssb_1"
    # ingest workload (ISSUE 6): millions-of-rows float arg
    mode, fn, arg = bench._parse_args(["ingest", "2"])
    assert "%s_%g" % (mode, arg) == "ingest_2"
    assert fn is bench.bench_ingest
    # deadline sweep (ISSUE 7): SSB scale-factor float arg
    mode, fn, arg = bench._parse_args(["deadline", "1"])
    assert "%s_%g" % (mode, arg) == "deadline_1"
    assert fn is bench.bench_deadline
    # serving-core hammer (ISSUE 8): SSB scale-factor float arg
    mode, fn, arg = bench._parse_args(["hammer", "0.1"])
    assert "%s_%g" % (mode, arg) == "hammer_0.1"
    assert fn is bench.bench_hammer
    # transfer-pipeline counterfactual (ISSUE 10): SSB scale-factor arg
    mode, fn, arg = bench._parse_args(["overlap", "1"])
    assert "%s_%g" % (mode, arg) == "overlap_1"
    assert fn is bench.bench_overlap
    # cold-boot restore vs re-encode (ISSUE 13): SSB scale-factor arg
    mode, fn, arg = bench._parse_args(["boot", "10"])
    assert "%s_%g" % (mode, arg) == "boot_10"
    assert fn is bench.bench_boot
    assert isinstance(bench.MODES["boot"][1], float)
    # one-dispatch arena counterfactual (ISSUE 14): SSB scale-factor arg
    mode, fn, arg = bench._parse_args(["arena", "1"])
    assert "%s_%g" % (mode, arg) == "arena_1"
    assert fn is bench.bench_arena
    # unified-executor mesh counterfactual (ISSUE 15): SSB scale arg
    mode, fn, arg = bench._parse_args(["mesh_unified", "10"])
    assert "%s_%g" % (mode, arg) == "mesh_unified_10"
    assert fn is bench.bench_mesh_unified
    # cluster-tier QPS scaling (ISSUE 16): SSB scale-factor arg
    mode, fn, arg = bench._parse_args(["cluster", "1"])
    assert "%s_%g" % (mode, arg) == "cluster_1"
    assert fn is bench.bench_cluster
    assert isinstance(bench.MODES["cluster"][1], float)
    # graftsan overhead proof (ISSUE 18): SSB scale-factor arg
    mode, fn, arg = bench._parse_args(["sanitize", "0.1"])
    assert "%s_%g" % (mode, arg) == "sanitize_0.1"
    assert fn is bench.bench_sanitize
    assert isinstance(bench.MODES["sanitize"][1], float)


def test_emit_ingest_result_shape(capsys, tmp_path, monkeypatch):
    """The ingest workload's result must satisfy the same one-compact-line
    contract, with the ingest headline fields inline and the fat span
    trees in the detail sidecar only."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    fat_tree = {"name": "ingest", "children": [
        {"name": "ingest_encode", "attrs": {"rows": 128}}
    ] * 50}
    bench._emit(
        {
            "metric": "ingest_sf100shape_2M_bulk_rows_per_sec",
            "value": 4_200_000,
            "unit": "rows/s",
            "vs_baseline": 5.1,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 2_000_000,
                "ingest_s": 0.47,
                "ingest_rows_per_sec": 4_200_000,
                "serial_seed_rows_per_sec": 820_000,
                "append_visible_p50_ms": 12.5,
                "span_tree_append": fat_tree,
                "span_tree_compact": fat_tree,
            },
        },
        "ingest_2",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "ingest_sf100shape_2M_bulk_rows_per_sec"
    assert parsed["vs_baseline"] == 5.1
    assert parsed["ingest_rows_per_sec"] == 4_200_000
    assert "span_tree_append" not in parsed
    detail = json.load(open(tmp_path / "BENCH_ingest_2_detail.json"))
    assert detail["detail"]["append_visible_p50_ms"] == 12.5
    assert detail["detail"]["span_tree_append"] == fat_tree


def test_emit_deadline_result_shape(capsys, tmp_path, monkeypatch):
    """The deadline mode's fat per-(query, deadline) curves + span tree
    live in the detail sidecar; stdout stays one compact line."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    curves = {
        "q%d_%d" % (i, j): [
            {
                "deadline_ms": 1.0 * k,
                "fraction_of_full": 0.1 * k,
                "wellformed": True,
                "partial": k < 2,
                "coverage": min(1.0, 0.5 * k),
                "total_ms": 3.0,
                "oracle_equal": True,
            }
            for k in range(5)
        ]
        for i in range(1, 5)
        for j in range(1, 4)
    }
    bench._emit(
        {
            "metric": "deadline_ssb_sf1_wellformed_pct",
            "value": 100.0,
            "unit": "%",
            "vs_baseline": 1.0,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 6_000_000,
                "runs": 65,
                "wellformed": 65,
                "oracle_equal_all": True,
                "curves": curves,
                "span_tree_tightest_deadline": {
                    "name": "query",
                    "children": [{"name": "partial"}] * 30,
                },
            },
        },
        "deadline_1",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "deadline_ssb_sf1_wellformed_pct"
    assert parsed["value"] == 100.0
    assert "curves" not in parsed
    detail = json.load(open(tmp_path / "BENCH_deadline_1_detail.json"))
    assert detail["detail"]["curves"]["q1_1"][0]["partial"] is True
    assert detail["detail"]["oracle_equal_all"] is True


def test_emit_hammer_result_shape(capsys, tmp_path, monkeypatch):
    """The serving-core hammer's fat sections (per-lane percentiles,
    the cache-hit span tree, scheduler stats) live in the detail
    sidecar; stdout stays one compact driver-parseable line."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    hit_tree = {"name": "query", "children": [
        {"name": "plan"}, {"name": "execute"}
    ] * 40}
    bench._emit(
        {
            "metric": "hammer_fast_lane_p95_under_heavy_storm_ms",
            "value": 42.5,
            "unit": "ms",
            "vs_baseline": 9.3,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 600_000,
                "fusion": {
                    "serial_dispatches_wall_ms": 404.4,
                    "fused_batch_wall_ms": 391.0,
                    "fused_speedup": 1.03,
                },
                "result_cache": {
                    "hit_zero_device_dispatch": True,
                    "hit_span_names": ["query", "plan", "execute"],
                    "delta_refresh_rows_scanned": 3,
                    "hit_span_tree": hit_tree,
                },
                "lanes": {
                    "fast_with_heavy_storm_lanes_on": {"p95_ms": 42.5},
                    "fast_with_heavy_storm_lanes_off": {"p95_ms": 395.0},
                },
                "mixed_hammer": {"total_queries": 240},
            },
        },
        "hammer_0.1",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "hammer_fast_lane_p95_under_heavy_storm_ms"
    assert parsed["vs_baseline"] == 9.3
    assert "result_cache" not in parsed  # fat maps stay in the sidecar
    detail = json.load(open(tmp_path / "BENCH_hammer_0.1_detail.json"))
    assert detail["detail"]["result_cache"]["hit_span_tree"] == hit_tree
    assert detail["detail"]["fusion"]["fused_speedup"] == 1.03


def test_emit_overlap_result_shape(capsys, tmp_path, monkeypatch):
    """The overlap mode's fat per-(query, mode) receipt maps and the
    streaming-rollup section live in the detail sidecar; stdout stays
    one compact driver-parseable line with the headline efficiency and
    the stall-ratio baseline inline."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    per_q = {
        "q%d_%d" % (i, j): {
            "off": {
                "wall_ms": 25.0, "transfer_stall_ms": 3.7,
                "prefetch_ms": 0.0, "overlap_efficiency": 0.84,
                "device_ms": 20.0, "transfer_bytes": 2_700_288,
                "prefetch_bytes": 0,
            },
            "on": {
                "wall_ms": 24.1, "transfer_stall_ms": 1.9,
                "prefetch_ms": 0.8, "overlap_efficiency": 0.92,
                "device_ms": 20.1, "transfer_bytes": 2_359_296,
                "prefetch_bytes": 340_992,
            },
            "identical": True,
        }
        for i in range(1, 5)
        for j in range(1, 4)
    }
    bench._emit(
        {
            "metric": "overlap_ssb_sf1_pipeline_on_efficiency",
            "value": 0.91,
            "unit": "ratio",
            "vs_baseline": 1.7,
            "identical": True,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 6_000_000,
                "transfer_stall_ms_on": 28.7,
                "transfer_stall_ms_off": 48.9,
                "results_identical_on_vs_off": True,
                "stream_identical_on_vs_off": True,
                "streaming_rollup": {
                    "off": {"wall_s": 0.34, "transfer_stall_ms": 10.8},
                    "on": {"wall_s": 0.29, "transfer_stall_ms": 0.0,
                           "prefetch_ms": 8.2},
                },
                "queries": per_q,
            },
        },
        "overlap_1",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "overlap_ssb_sf1_pipeline_on_efficiency"
    assert parsed["value"] == 0.91
    assert parsed["vs_baseline"] == 1.7
    assert "queries" not in parsed and "streaming_rollup" not in parsed
    detail = json.load(open(tmp_path / "BENCH_overlap_1_detail.json"))
    assert detail["detail"]["queries"]["q1_1"]["identical"] is True
    assert (
        detail["detail"]["streaming_rollup"]["on"]["transfer_stall_ms"]
        == 0.0
    )
    assert detail["detail"]["results_identical_on_vs_off"] is True


def test_emit_boot_result_shape(capsys, tmp_path, monkeypatch):
    """The boot mode's headline (restore speedup vs cold re-encode) stays
    one compact line; the per-phase timings and recovery counters live in
    the detail sidecar."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    bench._emit(
        {
            "metric": "boot_ssb_sf10_restore_speedup",
            "value": 118.4,
            "unit": "x",
            "vs_baseline": 118.4,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 59_986_052,
                "reencode_boot_s": 212.4,
                "restore_boot_s": 1.79,
                "restore_replay_boot_s": 2.31,
                "restore_speedup": 118.4,
                "snapshot_disk_bytes": 3_221_225_472,
                "restored_disk_backed": True,
                "wal_replayed_records": 16,
                "wal_replayed_rows": 8192,
                "wal_replay_rows_per_sec": 81_331,
                "queries_identical_across_restart": True,
                "queries_checked": ["q1_1", "q1_2", "q1_3", "q2_1"],
                "oracle": "byte-identical DataFrames across "
                          "kill-and-restart asserted",
            },
        },
        "boot_10",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "boot_ssb_sf10_restore_speedup"
    assert parsed["value"] == 118.4
    assert parsed["vs_baseline"] == 118.4
    detail = json.load(open(tmp_path / "BENCH_boot_10_detail.json"))
    assert detail["detail"]["restored_disk_backed"] is True
    assert detail["detail"]["queries_identical_across_restart"] is True
    assert detail["detail"]["wal_replayed_rows"] == 8192


def test_emit_arena_result_shape(capsys, tmp_path, monkeypatch):
    """The arena mode's per-(query, mode) dispatch/receipt maps live in
    the detail sidecar; stdout stays one compact driver-parseable line
    with the headline dispatch-collapse ratio and the loop-vs-arena
    p50 wall ratio inline."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    per_q = {
        "q%d_%d" % (i, j): {
            "off": {
                "wall_ms": 25.0, "dispatch_count": 8,
                "arena_build_ms": None, "device_ms": 20.0,
                "transfer_ms": 3.7,
            },
            "on": {
                "wall_ms": 14.1, "dispatch_count": 1,
                "arena_build_ms": 2.4, "device_ms": 9.8,
                "transfer_ms": 3.6,
            },
            "identical": True,
        }
        for i in range(1, 5)
        for j in range(1, 4)
    }
    bench._emit(
        {
            "metric": "arena_ssb_sf1_dispatch_collapse",
            "value": 8.0,
            "unit": "ratio",
            "vs_baseline": 1.6,
            "identical": True,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 6_000_000,
                "p50_wall_ms_arena": 14.1,
                "p50_wall_ms_loop": 25.0,
                "dispatches_arena": 12,
                "dispatches_loop": 96,
                "arena_build_ms_mean": 2.4,
                "results_identical_on_vs_off": True,
                "queries": per_q,
            },
        },
        "arena_1",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "arena_ssb_sf1_dispatch_collapse"
    assert parsed["value"] == 8.0
    assert parsed["vs_baseline"] == 1.6
    assert "queries" not in parsed
    detail = json.load(open(tmp_path / "BENCH_arena_1_detail.json"))
    assert detail["detail"]["queries"]["q1_1"]["identical"] is True
    assert detail["detail"]["queries"]["q1_1"]["on"]["dispatch_count"] == 1
    assert detail["detail"]["dispatches_loop"] == 96
    assert detail["detail"]["results_identical_on_vs_off"] is True


def test_emit_mesh_unified_result_shape(capsys, tmp_path, monkeypatch):
    """The unified-executor mesh mode (ISSUE 15): stdout stays one
    compact line whose vs_baseline is the single-over-mesh-arena p50
    ratio (>=1 is the SF10 acceptance bar); the detail sidecar carries
    the three-arm per-query maps, the receipt-verified per-query
    dispatch ceiling, and the multi-slice point with the cost-model's
    merge-tree span event."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    per_q = {
        "q%d_%d" % (i, j): {
            "single_ms": 20.0,
            "mesh_loop_ms": 21.5,
            "mesh_loop_dispatch_count": 1,
            "mesh_loop_device_ms": 17.0,
            "mesh_loop_transfer_ms": 0.0,
            "mesh_arena_ms": 18.4,
            "mesh_arena_dispatch_count": 1,
            "mesh_arena_device_ms": 15.2,
            "mesh_arena_transfer_ms": 0.0,
            "max_rel_err_vs_single": 0.0,
            "mesh_over_single": 0.92,
        }
        for i in range(1, 5)
        for j in range(1, 4)
    }
    bench._emit(
        {
            "metric": "mesh_unified_sf10_mesh8_p50_latency",
            "value": 18.4,
            "unit": "ms",
            "vs_baseline": 1.09,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 60_000_000,
                "n_devices": 8,
                "p50_ms_single": 20.0,
                "p50_ms_mesh_loop": 21.5,
                "p50_ms_mesh_arena": 18.4,
                "dispatches_mesh_loop": 12,
                "dispatches_mesh_arena": 12,
                "arena_dispatches_per_query_max": 1,
                "arena_vs_loop_speedup": 1.17,
                "max_rel_err_vs_single": 0.0,
                "multi_slice": {
                    "n_slices": 2,
                    "n_devices_per_slice": 4,
                    "p50_ms": 17.9,
                    "slice_equivalents": 1.12,
                    "merge_trees_chosen": ["hierarchical"],
                    "merge_tree_event": {
                        "name": "merge_tree",
                        "at_ms": 1.2,
                        "attrs": {
                            "tree": "hierarchical",
                            "flat_us": 44.8,
                            "hier_us": 35.2,
                            "shards": 8,
                            "slices": 2,
                        },
                    },
                },
                "queries": per_q,
            },
        },
        "mesh_unified_10",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "mesh_unified_sf10_mesh8_p50_latency"
    assert parsed["value"] == 18.4
    assert parsed["vs_baseline"] == 1.09
    assert "queries" not in parsed
    detail = json.load(
        open(tmp_path / "BENCH_mesh_unified_10_detail.json")
    )
    d = detail["detail"]
    assert d["arena_dispatches_per_query_max"] == 1
    assert d["queries"]["q1_1"]["mesh_arena_dispatch_count"] == 1
    assert d["multi_slice"]["merge_tree_event"]["attrs"]["tree"] == (
        "hierarchical"
    )
    assert d["multi_slice"]["slice_equivalents"] > 1
    assert d["p50_ms_mesh_arena"] <= d["p50_ms_single"]


def test_emit_cluster_result_shape(capsys, tmp_path, monkeypatch):
    """The cluster-tier mode (ISSUE 16): stdout stays one compact line
    whose value is the 1->4-historical QPS scaling factor; the detail
    sidecar carries the per-phase qps + latency percentiles, the
    kill-and-recover per-query timeline with its event markers, the
    rolling-restart zero-failure count, and the sampled broker receipt
    with per-historical RPC buckets."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    timeline = [
        {"t_ms": 100.0 * i, "ms": 45.0, "ok": True, "partial": False}
        for i in range(30)
    ]
    bench._emit(
        {
            "metric": "cluster_ssb_sf1_qps_scaling_1to4",
            "value": 3.4,
            "unit": "x",
            "vs_baseline": 3.4,
            "degraded": False,
            "device": "TFRT_CPU_0",
            "detail": {
                "rows": 6_000_000,
                "n_historicals": 4,
                "boot_s": {"h0": 8.1, "h1": 8.3, "h2": 8.2, "h3": 8.4},
                "phases": [
                    {"nodes": 1, "replication": 1, "queries": 32,
                     "qps": 4.1, "errors": 0, "partials": 0,
                     "segments_scattered": 12, "p50_ms": 230.0,
                     "p95_ms": 280.0, "p99_ms": 301.0},
                    {"nodes": 2, "replication": 2, "queries": 32,
                     "qps": 7.9, "errors": 0, "partials": 0,
                     "segments_scattered": 12, "p50_ms": 121.0,
                     "p95_ms": 150.0, "p99_ms": 166.0},
                    {"nodes": 4, "replication": 2, "queries": 32,
                     "qps": 13.9, "errors": 0, "partials": 0,
                     "segments_scattered": 12, "p50_ms": 66.0,
                     "p95_ms": 84.0, "p99_ms": 92.0},
                ],
                "receipt": {
                    "scatter_ms": 61.0, "gather_ms": 2.1,
                    "cluster_merge_ms": 0.8,
                    "nodes": {
                        "h0": {"ms": 58.0, "rpcs": 1, "ok": 1,
                               "failed": 0, "segments": 3},
                    },
                },
                "kill_recover": {
                    "events": [
                        {"t_ms": 1000.0, "event": "SIGKILL h3"},
                        {"t_ms": 1800.0, "event": "respawn h3"},
                        {"t_ms": 9800.0, "event": "rejoin h3"},
                    ],
                    "timeline": timeline,
                    "errors": 0,
                    "partials": 0,
                },
                "rolling_restart": {"queries": 16, "failed": 0},
            },
        },
        "cluster_1",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["metric"] == "cluster_ssb_sf1_qps_scaling_1to4"
    assert parsed["value"] == 3.4
    assert "timeline" not in line  # the stream stays in the sidecar
    detail = json.load(open(tmp_path / "BENCH_cluster_1_detail.json"))
    d = detail["detail"]
    assert [p["nodes"] for p in d["phases"]] == [1, 2, 4]
    assert all(p["errors"] == 0 for p in d["phases"])
    assert d["phases"][-1]["qps"] > d["phases"][0]["qps"]
    assert d["kill_recover"]["errors"] == 0
    assert len(d["kill_recover"]["timeline"]) == 30
    assert any(
        e["event"].startswith("SIGKILL")
        for e in d["kill_recover"]["events"]
    )
    assert d["rolling_restart"]["failed"] == 0
    assert d["receipt"]["nodes"]["h0"]["ok"] == 1


def test_emit_error_shape(capsys, tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    bench._emit(
        {
            "metric": "ssb",
            "value": 0.0,
            "unit": "error",
            "vs_baseline": 0.0,
            "degraded": True,
            "device": "unavailable",
            "detail": {"error": "x" * 5000, "probe_attempts": []},
        },
        "ssb",
    )
    line = capsys.readouterr().out.strip()
    assert len(line) < 2000
    parsed = json.loads(line)
    assert parsed["degraded"] is True and parsed["unit"] == "error"


def test_emit_writes_atomically_and_clears_partial(tmp_path, monkeypatch,
                                                   capsys):
    """ISSUE 1 satellite: artifacts land via tmp + os.replace (no truncated
    BENCH files after a mid-write kill), and a completed run removes the
    incremental partial sidecar while a failed run keeps it."""
    bench = _load_bench()
    monkeypatch.setenv("SD_BENCH_DETAIL_DIR", str(tmp_path))
    # simulate a mid-window state: two queries already flushed
    bench._PARTIAL["path"] = bench._partial_path("ssb_1")
    bench._PARTIAL["mode"] = "ssb"
    bench._PARTIAL["items"] = {}
    bench._note_partial("q1_1", {"tpu_ms": 1.0})
    bench._note_partial("q1_2", {"tpu_ms": 2.0})
    partial = json.load(open(tmp_path / "BENCH_ssb_1_partial.json"))
    assert partial["n_completed"] == 2 and partial["final"] is False
    assert partial["completed"]["q1_2"]["tpu_ms"] == 2.0
    # no stray .tmp left behind by the atomic writes
    assert not list(tmp_path.glob("*.tmp"))

    # a FAILED run keeps the partial evidence
    bench._emit(
        {"metric": "ssb", "value": 0.0, "unit": "error", "vs_baseline": 0.0,
         "degraded": True, "device": "unavailable",
         "detail": {"error": "boom", "probe_attempts": []}},
        "ssb_1",
    )
    capsys.readouterr()
    assert (tmp_path / "BENCH_ssb_1_partial.json").exists()

    # a completed run supersedes it
    bench._emit(dict(_fat_result()), "ssb_1")
    capsys.readouterr()
    assert not (tmp_path / "BENCH_ssb_1_partial.json").exists()
    assert (tmp_path / "BENCH_ssb_1_detail.json").exists()
    assert not list(tmp_path.glob("*.tmp"))
    bench._PARTIAL["path"] = None
    bench._PARTIAL["items"] = {}


def test_atomic_write_never_leaves_truncated_file(tmp_path):
    bench = _load_bench()
    p = tmp_path / "BENCH_x.json"
    bench._atomic_write(str(p), json.dumps({"v": 1}))
    assert json.load(open(p)) == {"v": 1}
    # overwrite failure mid-write must leave the OLD content whole: patch
    # os.replace to fail and verify the target is untouched
    import os as _os

    orig = _os.replace
    try:
        def boom(a, b):
            raise OSError("disk gone")

        _os.replace = boom
        try:
            bench._atomic_write(str(p), json.dumps({"v": 2}))
        except OSError:
            pass
        assert json.load(open(p)) == {"v": 1}  # old artifact intact
    finally:
        _os.replace = orig


def test_committed_r5_headline_artifacts_follow_contract():
    """Every committed BENCH_*_r5.json headline must carry the driver's
    parse keys (VERDICT r4 weak #6: BENCH_assist_r4.json silently broke
    the contract the same round it was restored elsewhere)."""
    import glob

    paths = glob.glob(os.path.join(REPO, "BENCH_*_r5.json"))
    assert paths, "round-5 headline artifacts should exist"
    for p in paths:
        with open(p) as f:
            d = json.load(f)
        for k in ("metric", "value", "unit", "vs_baseline", "degraded",
                  "device"):
            assert k in d, (os.path.basename(p), k)
        assert isinstance(d["value"], (int, float)), p
