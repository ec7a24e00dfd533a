"""The reader of the planner's join-order metric, ``plan.order_ms``, on
counter deltas as the harness hands them over, including a program
without the ordering span (nothing to read), and its entry in
``BENCHMARK.json``."""
import pytest

from chipbench import registry

CELLS = ["lubm10-exp1-drift", "watdiv100-retail-social-drift"]


@pytest.mark.parametrize("counters, ms", [
    ({}, None),                                          # the parent's
    ({"span.repro.exec.query.calls": 40,
      "span.repro.exec.query.ns": 4_000_000_000}, None),
    ({"plan.stats_builds": 2}, None),                    # no ordering span
    ({"span.repro.plan.order.calls": 0, "span.repro.plan.order.ns": 0}, None),
    ({"span.repro.plan.order.calls": 0, "span.repro.plan.order.ns": 0,
      "span.repro.exec.query.calls": 40}, 0.0),
    ({"span.repro.plan.order.calls": 30,
      "span.repro.plan.order.ns": 6_000_000,
      "span.repro.exec.query.calls": 40}, 0.15),
    ({"span.repro.plan.order.calls": 1,
      "span.repro.plan.order.ns": 70_000_000,
      "span.repro.exec.query.calls": 10}, 7.0),
])
def test_order_ms(counters, ms):
    got = registry.reader("plan.order_ms")(dict(counters=counters))
    assert got == (None if ms is None else pytest.approx(ms))


def test_order_ms_is_listed_for_its_cells():
    bench = registry.benchmark()
    (m,) = [m for m in bench["per_layer"] if m["name"] == "plan.order_ms"]
    assert m == dict(name="plan.order_ms", unit="ms", better="lower",
                     source="program_span", layer="planner",
                     moves="query_mean_ms", workloads=CELLS)
    for cell in CELLS:
        assert "plan.order_ms" in registry.readers(bench, cell)
