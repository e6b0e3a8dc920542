"""Percentile, spread and the open loop's timing arithmetic."""

import statistics

import numpy as np
import pytest

from lib import loadgen, serve
from lib.stats import percentile, quartile_spread

TRAFFIC = {"zipf_exponent": 1.0, "unknown_share": 0.02, "num": 10,
           "warmup_s": 3.0, "base_seed": 7}


def test_percentile_interpolates_like_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0]
    for q in (0, 25, 50, 90, 99, 100):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([4.0], 99) == 4.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_quartile_spread_is_the_contracts():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert quartile_spread(xs) == pytest.approx((q3 - q1) / 12.5)


def test_every_seed_sends_the_same_gaps_and_ranks_in_another_order():
    a = loadgen.make_plan(TRAFFIC, 1, 5000, 10.0, rate_qps=50.0)
    b = loadgen.make_plan(TRAFFIC, 2**31 + 5, 5000, 10.0, rate_qps=50.0)
    assert len(a.users) == len(b.users) == round(50.0 * 13.0)
    gaps_a, gaps_b = np.diff(a.due), np.diff(b.due)
    assert a.due[0] == 0.0 and a.due[-1] < 13.0
    assert np.sort(gaps_a) == pytest.approx(np.sort(gaps_b), abs=1e-9)
    assert not np.allclose(gaps_a, gaps_b)
    assert (a.rows < 0).sum() == (b.rows < 0).sum() > 0
    assert a.users != b.users
    # as many distinct users asked, as often each
    count = lambda p: sorted(np.unique(p.rows, return_counts=True)[1])  # noqa: E731
    assert count(a) == count(b)


def test_open_loop_latency_runs_from_the_due_time_and_window_excludes_warmup():
    plan = loadgen.make_plan(TRAFFIC, 3, 100, 4.0, rate_qps=10.0)
    n = len(plan.users)
    out = loadgen._new_outcome(n)
    out.sent[:] = plan.due + 0.010        # the generator ran 10 ms late
    out.done[:] = plan.due + 0.250        # answered 250 ms after due
    win = serve.window_of(plan, out, "open")
    assert (plan.due[win["idx"]] >= 3.0).all()
    assert len(win["idx"]) == (plan.due >= 3.0).sum()
    lat = out.done[win["idx"]] - win["clock0"][win["idx"]]
    assert lat == pytest.approx(0.250)
    assert win["t_first"] == 3.0
    assert win["window_s"] == pytest.approx(plan.due[-1] + 0.250 - 3.0)


def test_closed_loop_window_counts_what_ends_inside_it_over_its_own_length():
    """A shorter latency at the same rate of answers reads the same
    rate: the drain is outside, the answers to the warm-up's last
    requests inside."""
    plan = loadgen.make_plan(TRAFFIC, 3, 100, 4.0, rate_qps=None)
    for latency in (0.5, 2.0):
        out = loadgen._new_outcome(len(plan.users))
        out.sent[:70] = np.arange(70) * 0.1          # 10 a second, to 6.9 s
        out.done[:70] = out.sent[:70] + latency
        out.status[:70] = 200
        out.status[69] = 0                           # fails in the drain
        win = serve.window_of(plan, out, "closed")
        assert (win["t_first"], win["t_last"], win["window_s"]) == (
            3.0, 7.0, 4.0)
        assert 39 <= len(win["idx"]) <= 41, latency
        assert serve.late_failures(plan, out, "closed", win) == 1
        assert serve.late_failures(plan, out, "open", win) == 0


def test_well_formed_answers():
    good = [{"item": "i1", "score": 2.0}, {"item": "i2", "score": 1.0}]
    assert serve.well_formed(good, 2, True)
    assert not serve.well_formed(good[::-1], 2, True)      # not descending
    assert not serve.well_formed(good[:1], 2, True)        # short
    assert serve.well_formed([], 2, False)                 # unknown user
    assert not serve.well_formed(good, 2, False)


def sweep_row(rate, p50, p99, first, second, seconds=27.0, failed=0):
    return {"offered": f"{rate} q/s", "seconds": seconds, "failed": failed,
            "p50_ms": p50, "p99_ms": p99, "p50_first_half_ms": first,
            "p50_second_half_ms": second}


def test_knee_refuses_a_growing_backlog_and_a_phase_too_short_to_show_one():
    """The rows of PR 23's first sweep: a rule that lets 480 q/s pass
    (second half 6% over the first) finds no knee."""
    import sweep
    rows = [sweep_row(100, 1567, 1686, 1583, 1555),
            sweep_row(400, 2433, 2565, 2436, 2432),
            sweep_row(440, 2440, 2591, 2451, 2428),
            sweep_row(480, 2696, 2968, 2613, 2771),
            {"offered": "1024 callers"}]
    assert sweep.knee(rows) == "440 q/s"
    rows[2]["seconds"] = 8.0      # 3 latencies: says nothing of growth
    assert sweep.knee(rows) == "400 q/s"
    rows[1]["failed"] = 1
    assert sweep.knee(rows) == "100 q/s"
    assert sweep.phases_of("100:10,400", "open", 27.0) == [
        ("open", 100.0, 10.0), ("open", 400.0, 27.0)]
