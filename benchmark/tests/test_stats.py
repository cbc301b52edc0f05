import pytest

from harness import stats


def test_nearest_rank():
    values = sorted(range(1, 101))
    assert stats.nearest_rank(values, 50) == 50
    assert stats.nearest_rank(values, 95) == 95
    assert stats.nearest_rank([7.0], 95) == 7.0
    assert stats.nearest_rank([1.0, 2.0, 3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)


def _window(latencies_s):
    sent, done, t = [], [], 0.0
    for d in latencies_s:
        sent.append(t)
        t += d
        done.append(t)
    return stats.window_stats(sent, done)


def test_rate_is_over_the_whole_window():
    w = _window([0.01] * 100)
    assert w["queries_per_s"] == pytest.approx(100.0)
    assert w["latency_p50_ms"] == pytest.approx(10.0)
    assert w["latency_p95_ms"] == pytest.approx(10.0)
    assert w["samples"] == 100


def test_a_stall_moves_all_three():
    """A window of 10 ms requests in which 60 of 100 hit a 100 ms stall:
    nothing is a median of passes, so rate, median and tail all show it."""
    calm = _window([0.01] * 100)
    stalled = _window([0.01] * 40 + [0.1] * 60)
    assert stalled["queries_per_s"] < 0.2 * calm["queries_per_s"]
    assert stalled["latency_p50_ms"] == pytest.approx(100.0)
    assert stalled["latency_p95_ms"] == pytest.approx(100.0)
    # and a stall of a few requests moves the rate and the tail at least
    few = _window([0.01] * 90 + [1.0] * 10)
    assert few["queries_per_s"] < 0.1 * calm["queries_per_s"]
    assert few["latency_p95_ms"] == pytest.approx(1000.0)
    assert few["latency_p50_ms"] == pytest.approx(10.0)
