"""The arithmetic of the end-to-end metrics."""

import numpy as np
import pytest

from harness import loadgen, stats


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 0.50, 50),
    (list(range(1, 101)), 0.95, 95),
    ([5.0], 0.95, 5.0),
    ([3, 1, 2], 0.5, 2),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


@pytest.mark.parametrize("n,q,ok", [(200, 0.95, True), (199, 0.95, False),
                                    (20, 0.5, True), (19, 0.5, False)])
def test_a_percentile_wants_ten_samples_beyond_it(n, q, ok):
    assert stats.supports(n, q) is ok


def test_a_failed_request_counts_as_beyond_the_tail():
    recs = [{"due": 0.0, "done": 0.010, "ok": True}] * 99 \
        + [{"due": 0.0, "done": 0.001, "ok": False}]
    lat = stats.latencies_ms(recs, penalty_ms=45_000.0)
    assert stats.percentile(lat, 0.50) == pytest.approx(10.0)
    assert max(lat) == 45_000.0


def test_open_loop_latency_runs_from_the_due_time():
    # sent late: the wait is the server's (or the generator's) to answer for
    rec = {"due": 10.0, "sent": 10.4, "done": 10.5, "ok": True}
    assert stats.latencies_ms([rec], 1e9) == [pytest.approx(500.0)]


def test_whole_batch_clock_stops_at_the_last_whole_batch():
    recs = [{"done": 100.0 + 2.0 * (i + 1), "ok": True, "queries": 64}
            for i in range(6)]          # done at 102 .. 112
    qps, n = stats.completed_qps(recs, t_open=100.0, seconds=11.0,
                                 whole_batches=True)
    assert n == 5 * 64 and qps == pytest.approx(5 * 64 / 10.0)
    qps, n = stats.completed_qps(recs, 100.0, 11.0, whole_batches=False)
    assert qps == pytest.approx(5 * 64 / 11.0)


def test_a_wrong_query_is_not_counted_as_completed():
    recs = [{"done": 1.0, "ok": True, "queries": 1},
            {"done": 2.0, "ok": False, "queries": 1}]
    assert stats.completed_qps(recs, 0.0, 10.0, False) == (0.1, 1)


def test_arrival_gaps_are_one_set_in_a_seeded_order():
    a = loadgen.exponential_gaps(500, 20.0, np.random.default_rng(1))
    b = loadgen.exponential_gaps(500, 20.0, np.random.default_rng(2))
    assert a != b and sorted(a) == sorted(b)
    assert sum(a) == pytest.approx(500 / 20.0, rel=0.02)
