"""The reader of ``adapt.profile_hit_share``, on counter deltas as the
harness hands them over, and its entry in ``BENCHMARK.json``."""
import pytest

from chipbench import registry

METRIC = "adapt.profile_hit_share"


@pytest.mark.parametrize("counters, share", [
    ({}, None),                                          # the parent's
    ({"queries.served": 9, "cache.profile_noted": 24}, None),
    ({"cache.profile_hits": 0, "cache.profile_builds": 0}, None),
    ({"cache.profile_hits": 48}, 100.0),
    ({"cache.profile_hits": 48, "cache.profile_builds": 0}, 100.0),
    ({"cache.profile_builds": 10}, 0.0),
    ({"cache.profile_hits": 30, "cache.profile_builds": 10}, 75.0),
])
def test_profile_hit_share(counters, share):
    got = registry.reader(METRIC)(dict(counters=counters))
    assert got == (None if share is None else pytest.approx(share))


def test_profile_hit_share_is_listed_for_the_drift_cell():
    (m,) = [m for m in registry.benchmark()["per_layer"]
            if m["name"] == METRIC]
    assert m == dict(name=METRIC, unit="%", better="higher",
                     source="program_counter", layer="adaptation",
                     moves="query_p95_ms", workloads=["lubm10-exp1-drift"])
