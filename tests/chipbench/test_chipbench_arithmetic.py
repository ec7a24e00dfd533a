"""Latency arithmetic from due times, and the arrivals
generator's fixed work per seed."""
import collections

import numpy as np
import pytest

from chipbench import arrivals, stats


def test_tail_is_nearest_rank_with_its_sample_count():
    xs = list(range(1, 201))                   # 200 samples, 1..200
    t = stats.tail(xs, 0.95)
    assert t == dict(value=190, n=200, beyond=10)
    assert stats.tail([5.0], 0.95) == dict(value=5.0, n=1, beyond=0)


def test_latency_runs_from_due_time_to_answer():
    due = np.array([0.0, 0.5, 1.0, 1.2])
    done = np.array([2.0, 2.0, 3.5, 3.5])      # two passes answered them
    lat = list((done - due) * 1e3)
    assert stats.mean(lat) == pytest.approx((2000 + 1500 + 2500 + 2300) / 4)
    assert stats.tail(lat)["value"] == 2500


def test_mean_and_tail_refuse_no_samples():
    with pytest.raises(ValueError):
        stats.mean([])
    with pytest.raises(ValueError):
        stats.tail([])


@pytest.mark.parametrize("rate,seconds", [(6.0, 45.0), (7.3, 10.0)])
def test_schedule_offers_the_same_work_for_every_seed(rate, seconds):
    # the run's seed does not reach the schedule: every run gets this one
    mixes = [[("A", 1.0), ("B", 1.0), ("C", 2.0)], [("A", 3.0), ("D", 1.0)]]
    times, names = arrivals.schedule(mixes, rate, seconds)
    half = round(rate * seconds / 2)
    assert len(names) == len(times) == 2 * half
    first = collections.Counter(names[:half])
    second = collections.Counter(names[half:])
    assert set(first) == {"A", "B", "C"} and set(second) == {"A", "D"}
    assert abs(first["C"] - half / 2) <= 1
    assert abs(second["A"] - 3 * half / 4) <= 1
    assert np.all(np.diff(times) >= 0)
    assert times[0] >= 0 and times[half - 1] < seconds / 2 <= times[half]
    assert times[-1] < seconds
    again = arrivals.schedule(mixes, rate, seconds)
    assert again[1] == names and np.array_equal(again[0], times)


def test_quotas_split_exactly():
    q = arrivals.quotas([1.0] * 24, 270)
    assert q.sum() == 270 and set(q.tolist()) == {11, 12}
    assert q[:6].tolist() == [12] * 6          # ties go to the earlier entry
