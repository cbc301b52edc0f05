"""Unit tests for the query-lifecycle resilience primitives
(spark_druid_olap_tpu/resilience.py): error taxonomy, deadlines, circuit
breaker, admission control, fault injector."""

import threading
import time

import pytest

from spark_druid_olap_tpu import resilience as R


@pytest.fixture(autouse=True)
def _clean_injector():
    R.injector().disarm()
    yield
    R.injector().disarm()


# -- error taxonomy ---------------------------------------------------------


def test_classify_error():
    assert R.classify_error(RuntimeError("device blip")) == "transient"
    assert R.classify_error(OSError("link down")) == "transient"
    assert R.classify_error(R.InjectedFault("x")) == "transient"
    assert R.classify_error(R.CircuitOpenError("x")) == "transient"
    assert R.classify_error(NotImplementedError("no such op")) == "static"
    assert R.classify_error(ValueError("bad plan")) == "static"
    assert R.classify_error(KeyError("col")) == "static"
    assert R.classify_error(TypeError("x")) == "static"
    assert R.classify_error(R.DeadlineExceeded("site", 5)) == "deadline"


# -- deadlines --------------------------------------------------------------


def test_deadline_scope_and_checkpoint():
    assert R.current_deadline() is None
    R.checkpoint("nowhere")  # no active deadline: free no-op
    with R.deadline_scope(10_000) as d:
        assert d is not None and R.current_deadline() is d
        R.checkpoint("inside")  # plenty of budget
        assert d.remaining_ms() > 5_000
    assert R.current_deadline() is None


def test_deadline_expiry_raises_with_site():
    with R.deadline_scope(1):
        time.sleep(0.005)
        with pytest.raises(R.DeadlineExceeded) as ei:
            R.checkpoint("engine.segment_loop")
        assert ei.value.site == "engine.segment_loop"
    # zero/None timeouts arm nothing
    with R.deadline_scope(0):
        assert R.current_deadline() is None
    with R.deadline_scope(None):
        assert R.current_deadline() is None


def test_outer_deadline_wins():
    """A server-set wire deadline must not be replaced by the session
    default armed inside ctx.sql."""
    with R.deadline_scope(50) as outer:
        with R.deadline_scope(600_000) as inner:
            assert inner is outer
            assert R.current_deadline() is outer
            assert R.current_deadline().timeout_ms == 50


# -- circuit breaker --------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_breaker_trips_after_threshold():
    br = R.CircuitBreaker(failure_threshold=3, cooldown_ms=1000)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"  # below threshold
    br.record_failure()
    assert br.state == "open"
    assert not br.allow()
    d = br.to_dict()
    assert d["trips"] == 1 and d["consecutive_failures"] == 3


def test_breaker_success_resets_consecutive_count():
    br = R.CircuitBreaker(failure_threshold=2)
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"  # the success broke the streak


def test_breaker_half_open_probe_and_recovery():
    clk = _FakeClock()
    br = R.CircuitBreaker(failure_threshold=1, cooldown_ms=500, clock=clk)
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clk.t += 0.6  # past the cooldown
    assert br.state == "half_open"
    assert br.allow()  # the probe is admitted
    br.record_success()
    assert br.state == "closed"


def test_breaker_half_open_admits_single_probe():
    """Cooldown expiry under queued traffic must release ONE probe, not a
    thundering herd onto the possibly-still-broken device."""
    clk = _FakeClock()
    br = R.CircuitBreaker(failure_threshold=1, cooldown_ms=500, clock=clk)
    br.record_failure()
    clk.t += 0.6
    assert br.allow()  # first caller holds the probe lease
    assert not br.allow()  # everyone else keeps degrading
    assert not br.allow()
    br.record_failure()  # probe reports: re-open, lease released
    assert br.state == "open"
    clk.t += 0.6
    assert br.allow()  # fresh lease after the new cooldown
    br.record_success()
    assert br.state == "closed"
    # a probe that dies without reporting cannot wedge the breaker: the
    # lease goes stale after another cooldown interval
    br.record_failure()
    clk.t += 0.6
    assert br.allow()
    clk.t += 0.6  # lease is now stale
    assert br.allow()


def test_breaker_failed_probe_reopens():
    clk = _FakeClock()
    br = R.CircuitBreaker(failure_threshold=1, cooldown_ms=500, clock=clk)
    br.record_failure()
    clk.t += 0.6
    assert br.allow()
    br.record_failure()  # probe failed
    assert br.state == "open" and not br.allow()
    assert br.to_dict()["trips"] == 2
    clk.t += 0.6  # a fresh cooldown runs from the failed probe
    assert br.allow()
    br.record_success()
    assert br.state == "closed"


def test_breaker_release_probe_returns_lease_without_verdict():
    clk = _FakeClock()
    br = R.CircuitBreaker(failure_threshold=1, cooldown_ms=500, clock=clk)
    br.record_failure()
    clk.t += 0.6
    assert br.allow()  # lease taken
    assert not br.allow()
    br.release_probe()  # e.g. the query was served from the result cache
    assert br.state == "half_open"  # no verdict: state unchanged
    assert br.allow()  # next caller probes immediately, no stale wait
    br.record_success()
    assert br.state == "closed"


# -- admission control ------------------------------------------------------


def test_admission_slots_and_timeout():
    adm = R.AdmissionController(max_concurrent=2, queue_timeout_ms=50)
    assert adm.acquire() and adm.acquire()
    assert adm.in_use == 2
    t0 = time.perf_counter()
    assert not adm.acquire()  # full: rejected after the queue wait
    assert time.perf_counter() - t0 >= 0.04
    assert adm.rejected_total == 1
    adm.release()
    assert adm.acquire()  # a freed slot admits again
    adm.release()
    adm.release()
    assert adm.in_use == 0
    assert adm.retry_after_s() >= 1
    d = adm.to_dict()
    assert d["slots_total"] == 2 and d["admitted_total"] == 3


def test_retry_after_from_observed_hold_time():
    """Retry-After reflects the observed slot hold EWMA, not the
    configured queue wait (ROADMAP resilience follow-up (d))."""
    clk = _FakeClock()
    adm = R.AdmissionController(
        max_concurrent=1, queue_timeout_ms=30000, clock=clk
    )
    # before any observation the configured wait stands in (clamped)
    assert adm.retry_after_s() == 30
    assert adm.acquire()
    clk.t += 2.5  # the query held its slot for 2.5s
    adm.release()
    # idle pool, observed ~2.5s hold: hint is ceil(2.5) = 3, NOT 30
    assert adm.retry_after_s() == 3
    assert adm.to_dict()["hold_ewma_ms"] == pytest.approx(2500.0)


def test_retry_after_scales_with_queue_depth():
    clk = _FakeClock()
    adm = R.AdmissionController(
        max_concurrent=1, queue_timeout_ms=60000, clock=clk
    )
    # observe a 4s hold to seed the EWMA
    assert adm.acquire()
    clk.t += 4.0
    adm.release()
    # occupy the slot and queue two real waiters behind it
    assert adm.acquire()
    started = threading.Barrier(3)

    def waiter():
        started.wait(timeout=5)
        adm.acquire()  # parks until release (60s budget)
        adm.release()

    threads = [threading.Thread(target=waiter) for _ in range(2)]
    for t in threads:
        t.start()
    started.wait(timeout=5)
    deadline = time.perf_counter() + 5
    while adm.queue_depth < 2 and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert adm.queue_depth == 2
    # depth 2 on 1 slot at ~4s/hold: ceil(4 * (2/1 + 1)) = 12s; an
    # unqueued pool with the same EWMA would say 4s
    assert adm.retry_after_s() == 12
    d = adm.to_dict()
    assert d["queue_depth"] == 2
    adm.release()  # drain: each waiter acquires and releases in turn
    for t in threads:
        t.join(timeout=5)
    assert adm.queue_depth == 0
    # hint is clamped to [1, 60] even under absurd observed holds
    clk2 = _FakeClock()
    adm2 = R.AdmissionController(
        max_concurrent=1, queue_timeout_ms=1000, clock=clk2
    )
    assert adm2.acquire()
    clk2.t += 500.0
    adm2.release()
    assert adm2.retry_after_s() == 60


def test_admission_queued_caller_gets_freed_slot():
    adm = R.AdmissionController(max_concurrent=1, queue_timeout_ms=2000)
    assert adm.acquire()
    got = {}

    def waiter():
        got["ok"] = adm.acquire()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    adm.release()
    t.join(timeout=2)
    assert got["ok"] is True
    adm.release()


# -- fault injector ---------------------------------------------------------


def test_injector_error_mode_counts_down():
    inj = R.FaultInjector()
    inj.arm("device_dispatch", "error", times=2)
    with pytest.raises(R.InjectedFault):
        inj.fire("device_dispatch")
    with pytest.raises(R.InjectedFault):
        inj.fire("device_dispatch")
    inj.fire("device_dispatch")  # self-disarmed after N fires
    assert not inj.armed("device_dispatch")
    assert inj.state()["fired"]["device_dispatch"] == 2


def test_injector_delay_and_partial_modes():
    inj = R.FaultInjector()
    inj.arm("h2d", "delay", delay_ms=30)
    t0 = time.perf_counter()
    inj.fire("h2d")  # sleeps, never raises
    assert time.perf_counter() - t0 >= 0.025
    inj.arm("fallback_decode", "partial", fraction=0.5)
    # fire() must NOT consume or trip a partial spec
    inj.fire("fallback_decode")
    assert inj.partial_fraction("fallback_decode") == 0.5
    assert inj.partial_fraction("device_dispatch") is None


def test_injector_custom_error_type_and_disarm_all():
    inj = R.FaultInjector()
    inj.arm("compile", "error", error_type=OSError)
    with pytest.raises(OSError):
        inj.fire("compile")
    inj.arm("h2d", "error")
    inj.disarm()
    inj.fire("compile")
    inj.fire("h2d")


def test_injector_env_arming():
    inj = R.FaultInjector()
    inj.arm_from_env("device_dispatch:error:2, h2d:delay:5, compile:partial:0.25")
    assert inj.armed("device_dispatch")
    assert inj.armed("h2d")
    assert inj.partial_fraction("compile") == 0.25
    with pytest.raises(R.InjectedFault):
        inj.fire("device_dispatch")


def test_global_fire_noop_when_never_armed():
    # the module-level shortcut must stay free when nothing was armed
    R.fire("device_dispatch")
    R.injector().arm("device_dispatch", "error", times=1)
    with pytest.raises(R.InjectedFault):
        R.fire("device_dispatch")
    R.fire("device_dispatch")


# -- resilience state / health ---------------------------------------------


def test_resilience_state_health_shape():
    from spark_druid_olap_tpu.config import SessionConfig

    cfg = SessionConfig()
    cfg.max_concurrent_queries = 3
    cfg.breaker_failure_threshold = 5
    st = R.ResilienceState(cfg)
    st.note_degraded()
    st.note_server_error(ValueError("boom"))
    h = st.health()
    assert h["healthy"] is True
    assert h["breaker"]["state"] == "closed"
    assert h["breaker"]["failure_threshold"] == 5
    assert h["admission"]["slots_total"] == 3
    assert h["counters"]["degraded_total"] == 1
    assert h["counters"]["server_errors_total"] == 1
    assert h["counters"]["last_error"]["errorClass"] == "ValueError"
