"""chip_smoke.py rehearsed on the CPU, the compile cache's placement, and
bench.py's exit code.

The rehearsal runs the script's real phases in-process (`--rehearse` lifts
the TPU check and nothing else), so what the driver runs on the chip is
what these tests ran here — on the CPU, and said so in its last line.
"""

import importlib.util
import json
import os

import jax
import pytest

from spark_druid_olap_tpu.resilience import injector
from spark_druid_olap_tpu.utils import compile_cache
from spark_druid_olap_tpu.workloads import ssb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name + "_under_test", os.path.join(REPO, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    """Entry points turn the persistent compile cache on; a test process
    must not (it would keep writing there for every later test)."""
    monkeypatch.setattr(compile_cache, "enable", lambda: str(tmp_path))


@pytest.fixture
def clean_injector():
    injector().disarm()
    yield injector()
    injector().disarm()


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()]


def test_rehearsal_serves_ssb_at_parity_on_cpu(no_cache, capsys):
    smoke = _load("chip_smoke")
    assert smoke.main(["--rehearse", "--scale", "0.01"]) == 0
    lines = _lines(capsys)
    # the last line names the platform it REALLY ran on: a rehearsal can
    # never be read as a chip run
    assert lines[-1] == {
        "ok": True,
        "device": {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
    }
    queries = [l for l in lines if l.get("phase") == "query"]
    assert [l["query"] for l in queries] == list(ssb.QUERIES)
    assert all(l["ok"] and l["checked"] == "pandas float64" for l in queries)
    native = [l for l in lines if l.get("phase") == "native_groupby"]
    assert len(native) == 1 and native[0]["ok"]
    assert [l for l in lines if l.get("phase") == "end"][0]["failures"] == []


def test_refuses_to_run_without_a_tpu(no_cache, capsys):
    smoke = _load("chip_smoke")
    assert smoke.main(["--scale", "0.01"]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no query ran, no result line
    assert "no TPU" in out.err


@pytest.mark.parametrize("argv, n_chips", [([], 4), (["--chips", "4"], 1),
                                           (["--chips", "4"], 8)])
def test_refuses_a_machine_that_is_not_the_phases(
    no_cache, monkeypatch, capsys, argv, n_chips
):
    """On the chip the last line's count is the phase: the served phase
    on a four-chip host (where the planner would take the mesh) or the
    mesh phase on another count is refused before anything runs."""
    import types

    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip] * n_chips)
    smoke = _load("chip_smoke")
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert f"JAX sees {n_chips} device(s)" in out.err


def test_degraded_query_fails_the_run(no_cache, clean_injector, capsys):
    """Every device dispatch fails -> the served answers come from the host
    fallback, still at parity — and the run must NOT pass on that."""
    clean_injector.arm("device_dispatch", "error")
    smoke = _load("chip_smoke")
    assert smoke.main(["--rehearse", "--scale", "0.01"]) != 0
    lines = _lines(capsys)
    assert "ok" not in lines[-1]  # no result line
    failures = lines[-1]["failures"]
    assert any("executor=fallback" in f or "degraded" in f for f in failures)


def test_one_retried_dispatch_fails_the_run(no_cache, clean_injector, capsys):
    """One transient device error: the engine retries, the answer is right
    and comes from the device — and QueryMetrics.retries alone must fail
    the run (nothing is read off the log)."""
    clean_injector.arm("device_dispatch", "error", times=1)
    smoke = _load("chip_smoke")
    assert smoke.main(["--rehearse", "--scale", "0.01"]) != 0
    lines = _lines(capsys)
    assert "ok" not in lines[-1]
    assert any("retries=1" in f for f in lines[-1]["failures"])


def test_served_phase_stays_on_one_device(no_cache, capsys):
    """The suite runs on eight virtual devices; the default phase must
    still prove the single-device engine, not the mesh."""
    assert len(jax.devices()) > 1
    smoke = _load("chip_smoke")
    seen = []
    faults = smoke.metrics_faults
    smoke.metrics_faults = lambda m, **kw: (
        seen.append(m.distributed) or faults(m, **kw)
    )
    assert smoke.main(["--rehearse", "--scale", "0.01"]) == 0
    capsys.readouterr()
    assert seen and not any(seen)


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, no code path sets a directory:
    JAX reads the variable itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable()
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache"
    )
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_bench_main_fails_when_its_mode_fails(no_cache, monkeypatch, capsys):
    """A mode that raises ends the process non-zero: main() lets the error
    through, runs the mode once, and prints no result line."""
    bench = _load("bench")
    calls = []

    def boom(arg):
        calls.append(arg)
        raise RuntimeError("mode failed")

    monkeypatch.setitem(bench.MODES, "tpch_q1", (boom, 1.0))
    monkeypatch.setattr(bench, "_ensure_calibration", lambda: None)
    with pytest.raises(RuntimeError, match="mode failed"):
        bench.main(["tpch_q1", "0.01"])
    assert calls == [0.01]
    assert capsys.readouterr().out == ""
