"""repro.obs: wall-clock program spans and the metrics registry — unit
behavior (nesting, request ids, export, null twins, kind discipline) plus
the observability acceptance properties: a traced drain records every
adaptation round, migration chunk and window, span structure matches
across runs and executor backends, and the spans reach a profiler
capture's host plane on the Tracer's clock."""
import glob
import json
import os
import time

import pytest

from repro.api import KGService
from repro.obs import (NULL_METRICS, NULL_TRACER, MetricsRegistry,
                       NullTracer, Tracer, ambient_tracer, set_ambient,
                       set_ambient_tracer, span)
from repro.stream import LatencyRecorder, QueryLatency

EXECUTORS = ("numpy", "jax", "jax-pallas")


@pytest.fixture
def tracer():
    """A Tracer installed ambiently for one test, then uninstalled."""
    before = ambient_tracer()
    tr = Tracer()
    set_ambient_tracer(tr)
    yield tr
    set_ambient_tracer(before)


def _structure(tr, keep):
    """``tr.structure()`` of the kept spans only, each at the depth of
    its kept ancestors."""
    by_seq = {e["seq"]: e for e in tr.events}
    out = []
    for e in sorted(tr.events, key=lambda e: e["seq"]):
        if keep(e["name"]):
            depth, p = 0, e["parent"]
            while p is not None:
                depth += keep(by_seq[p]["name"])
                p = by_seq[p]["parent"]
            out.append((depth, e["name"]))
    return out


# --------------------------------------------------------------------------- #
# tracer unit behavior
# --------------------------------------------------------------------------- #

def test_tracer_nesting_and_clock(tracer):
    t0 = time.time_ns()
    with span("window", n=2) as w:
        with span("query", query="Q1"):
            time.sleep(0.002)
        with span("query", query="Q2"):
            pass
        w.annotate(late=True)
    with span("round"):
        pass
    t1 = time.time_ns()
    win, = tracer.find("window")
    q1, q2 = tracer.find("query")
    rnd, = tracer.find("round")
    # parent seq and one request id per outermost span
    assert win["parent"] is None and q1["parent"] == q2["parent"] == win["seq"]
    assert q1["req"] == q2["req"] == win["req"] != rnd["req"]
    assert win["args"] == {"n": 2, "late": True}
    # real time on the wall clock: children inside the parent, in order
    assert t0 <= win["ts_ns"] <= q1["ts_ns"]
    assert q1["dur_ns"] >= 2_000_000
    assert q1["ts_ns"] + q1["dur_ns"] <= q2["ts_ns"]
    assert q2["ts_ns"] + q2["dur_ns"] <= win["ts_ns"] + win["dur_ns"] <= t1
    # depth reflects the open stack; structure is open-order
    assert tracer.structure() == [(0, "window"), (1, "query"), (1, "query"),
                                  (0, "round")]
    assert _structure(tracer, lambda n: n != "window") == [
        (0, "query"), (0, "query"), (0, "round")]


def test_tracer_chrome_export_schema(tracer, tmp_path):
    with span("repro.adapt.round", trigger="explicit") as sp:
        with span("repro.migrate.chunk", bytes=96):
            pass
        sp.annotate(accepted=True)
    raw = tracer.chrome_trace()
    assert raw["displayTimeUnit"] == "ms"
    phases = [e["ph"] for e in raw["traceEvents"]]
    assert phases.count("M") == 1 and phases.count("X") == len(tracer.events)
    rnd, chunk = [e for e in raw["traceEvents"] if e["ph"] == "X"]
    for ev in (rnd, chunk):
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(ev)
        assert ev["dur"] >= 0 and ev["ts"] > 1e15      # epoch microseconds
        assert {"seq", "parent", "req"} <= set(ev["args"])
    assert chunk["args"]["parent"] == rnd["args"]["seq"]
    assert chunk["args"]["req"] == rnd["args"]["req"]
    assert rnd["args"]["accepted"] is True and chunk["args"]["bytes"] == 96
    assert rnd["ts"] <= chunk["ts"]
    p = tmp_path / "t.json"
    assert tracer.export(str(p)) == len(tracer.events) == 2
    assert json.loads(p.read_text()) == json.loads(json.dumps(raw))


def test_tracer_attrs_json_safe(tracer):
    import numpy as np
    with span("x", a=np.int32(3), b=np.float64(0.5), c=(1, np.int64(2)),
              d={"k": np.bool_(True)}, e=None):
        pass
    (ev,) = tracer.events
    assert ev["args"] == {"a": 3, "b": 0.5, "c": [1, 2],
                          "d": {"k": True}, "e": None}
    json.dumps(ev["args"])              # round-trips without a custom encoder


def test_null_tracer_is_inert():
    before = ambient_tracer()
    set_ambient_tracer(NULL_TRACER)
    tr = NULL_TRACER
    assert not tr.enabled
    with span("query", big=list(range(10))) as sp:
        assert not sp.recording
        sp.annotate(x=1)
    assert sp.event is None and "x" not in sp.attrs
    assert len(tr) == 0 and tr.structure() == [] and tr.span_counts() == {}
    assert tr.find("query") == []
    set_ambient_tracer(before)


def test_spans_accumulate_in_the_ambient_registry():
    from repro.obs import ambient
    before = ambient()
    m = MetricsRegistry()
    set_ambient(m)
    with span("repro.exec.scan"):
        time.sleep(0.001)
    with span("repro.exec.scan"):
        pass
    set_ambient(before)
    c = m.snapshot()["counters"]
    assert c["span.repro.exec.scan.calls"] == 2
    assert c["span.repro.exec.scan.ns"] >= 1_000_000


# --------------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------------- #

def test_metrics_registry_snapshot_and_kinds():
    m = MetricsRegistry()
    m.counter("a.hits").inc()
    m.counter("a.hits").inc(4)
    m.gauge("b.level").set(2.0)
    assert m.gauge("b.peak").track_max(3.0) == 3.0
    assert m.gauge("b.peak").track_max(1.0) == 3.0
    for v in (1.0, 2.0, 3.0, 4.0):
        m.histogram("c.lat").observe(v)
    snap = m.snapshot()
    assert snap["counters"] == {"a.hits": 5}
    assert snap["gauges"] == {"b.level": 2.0, "b.peak": 3.0}
    h = snap["histograms"]["c.lat"]
    assert h["n"] == 4 and h["mean"] == 2.5 and h["max"] == 4.0
    assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    # a name is bound to one instrument kind for its lifetime
    with pytest.raises(TypeError, match="counter"):
        m.gauge("a.hits")
    with pytest.raises(TypeError, match="histogram"):
        m.counter("c.lat")


def test_metrics_registry_csv(tmp_path):
    import csv
    m = MetricsRegistry()
    m.counter("z.n").inc(7)
    m.histogram("a.lat").observe(0.5)
    p = tmp_path / "m.csv"
    assert m.to_csv(str(p)) == 2
    rows = list(csv.DictReader(open(p, newline="")))
    assert [r["metric"] for r in rows] == ["a.lat", "z.n"]   # sorted
    assert rows[1]["kind"] == "counter" and rows[1]["value"] == "7"
    assert rows[1]["p95"] == ""                              # restval
    assert rows[0]["kind"] == "histogram" and float(rows[0]["p50"]) == 0.5


def test_null_metrics_is_inert(tmp_path):
    NULL_METRICS.counter("x").inc(5)
    NULL_METRICS.gauge("y").set(1.0)
    NULL_METRICS.histogram("z").observe(1.0)
    assert len(NULL_METRICS) == 0
    assert NULL_METRICS.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}
    p = tmp_path / "null.csv"
    assert NULL_METRICS.to_csv(str(p)) == 0
    assert p.read_text().startswith("metric,kind,value")


# --------------------------------------------------------------------------- #
# recorder queue-time summaries (satellite: queue-vs-execute split)
# --------------------------------------------------------------------------- #

def _rec(i, window=0, queue=0.05, exec_s=0.1):
    t0 = 0.1 * i
    return QueryLatency(seq=i, name=f"Q{i}", window=window, shard=i % 2,
                        arrival_s=t0, start_s=t0 + queue,
                        finish_s=t0 + queue + exec_s, epoch=0, cached=False)


def test_recorder_queue_summaries(tmp_path):
    rec = LatencyRecorder()
    for i in range(8):
        rec.record(_rec(i, window=i // 4, queue=0.01 * (i + 1)))
    s = rec.summary()
    assert s["queue"]["n"] == 8
    assert s["queue"]["max"] == pytest.approx(0.08)
    assert s["queue"]["p50"] < s["p50"]          # queue is a strict subset
    for w, ws in rec.per_window().items():
        assert ws["queue"]["n"] == 4
    rows = rec.window_rows(mode="t", rate_qps=1.0)
    cols = list(rows[0])
    # the legacy header prefix consumers index by, queue columns after
    assert cols[:9] == ["mode", "rate_qps", "window", "n", "p50_ms",
                        "p95_ms", "p99_ms", "mean_ms", "max_ms"]
    assert cols[9:] == ["queue_p50_ms", "queue_p95_ms", "queue_p99_ms"]
    p = tmp_path / "w.csv"
    assert rec.to_csv(str(p), mode="t", rate_qps=1.0) == 2
    assert p.read_text().splitlines()[0] == ",".join(cols)


def test_recorder_empty_summary_well_formed():
    s = LatencyRecorder.empty_summary()
    assert s["n"] == 0 and s["p99"] == 0.0
    assert s["queue"] == dict(n=0, mean=0.0, p50=0.0, p95=0.0, p99=0.0,
                              max=0.0)


# --------------------------------------------------------------------------- #
# service wiring
# --------------------------------------------------------------------------- #

def test_stats_and_tracer_raise_before_ready(small_lubm):
    svc = KGService.from_dataset(small_lubm, n_shards=4)
    with pytest.raises(RuntimeError, match="bootstrap"):
        svc.stats()
    with pytest.raises(RuntimeError, match="trace"):
        svc.tracer()                     # tracing off -> actionable error
    svc.bootstrap(small_lubm.base_workload())
    st = svc.stats()                     # no stream yet: empty but shaped
    assert st["latency"] == LatencyRecorder.empty_summary()
    assert st["latency_per_shard"] == {}
    assert "queries.served" not in st["metrics"]["counters"]


def _traced_drain(ds, executor="numpy"):
    svc = KGService.from_dataset(ds, n_shards=4, executor=executor,
                                 migration_budget=120_000, trace=True)
    svc.bootstrap(ds.base_workload())
    window = ds.extended_workload()
    svc.query_batch(window)
    report = svc.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
    assert report.accepted and svc.session is not None
    windows = 1
    while svc.session is not None:       # drain while serving, traced
        svc.query_batch(window)
        windows += 1
    return svc, windows, len(window)


def _serve_level(name):
    return (name.startswith("repro.serve.")
            or name in ("repro.exec.query", "repro.adapt.round"))


def test_traced_drain_is_complete_and_metered(small_lubm):
    svc, windows, per_window = _traced_drain(small_lubm)
    tr = svc.tracer()
    counts = tr.span_counts()
    served = windows * per_window
    m = svc.stats()["metrics"]
    # every window, round and chunk is recorded; each miss is one query
    assert counts["repro.serve.window"] == windows
    assert counts["repro.adapt.round"] == 1
    assert counts["repro.migrate.chunk"] == m["counters"]["migrate.chunks"]
    assert counts["repro.migrate.chunk"] >= 3
    misses = served - m["counters"].get("queries.result_cache_hits", 0)
    assert counts["repro.exec.query"] == misses
    assert sum(w["args"]["misses"] for w in tr.find("repro.serve.window")) \
        == misses
    (rnd,) = tr.find("repro.adapt.round")
    assert rnd["args"]["accepted"] is True
    assert rnd["args"]["trigger"] == "explicit"
    assert rnd["args"]["reason"] in ("amortized", "improved")
    assert rnd["args"]["t_new"] < rnd["args"]["t_base"]
    assert counts["repro.adapt.measure"] >= 2     # baseline + candidate
    by_seq = {e["seq"]: e for e in tr.events}
    for e in tr.events:                 # children nest inside their parent
        if e["parent"] is not None:
            p = by_seq[e["parent"]]
            assert e["req"] == p["req"]
            assert p["ts_ns"] <= e["ts_ns"]
            assert e["ts_ns"] + e["dur_ns"] <= p["ts_ns"] + p["dur_ns"]
    for w in tr.find("repro.serve.window"):      # one request per window
        kids = [e for e in tr.events if e["req"] == w["req"]]
        assert all(e["name"] != "repro.serve.window" or e is w for e in kids)
    q = tr.find("repro.exec.query")[0]["args"]
    assert q["modeled_s"] > 0 and q["query"] and q["rows"] >= 0
    assert m["counters"]["queries.served"] == served
    assert m["counters"]["adapt.accepted"] == 1
    assert m["histograms"]["query.modeled_s"]["n"] == misses
    assert m["gauges"]["migrate.progress"] == 1.0
    assert m["counters"]["federation.bytes_shipped"] > 0
    assert m["counters"]["span.repro.serve.window.calls"] == windows
    # kernel dispatch tier picks landed in the ambient registry
    assert any(k.startswith("kernels.dispatch.jaccard.distance.")
               for k in m["counters"])


def test_trace_byte_identical_same_seed(small_lubm):
    """Two same-seed runs open the same spans in the same nesting; the
    times differ, so the files no longer match byte for byte."""
    a, _, _ = _traced_drain(small_lubm)
    b, _, _ = _traced_drain(small_lubm)
    assert a.tracer().structure() == b.tracer().structure()
    assert [e["req"] for e in a.tracer().events] == \
        [e["req"] for e in b.tracer().events]


def test_trace_structure_identical_across_executors(small_lubm):
    traces = {}
    for name in EXECUTORS:
        svc, _, _ = _traced_drain(small_lubm, executor=name)
        traces[name] = svc.tracer()
    ref = _structure(traces["numpy"], _serve_level)
    assert ref.count((0, "repro.adapt.round")) == 1
    for name in EXECUTORS[1:]:
        assert _structure(traces[name], _serve_level) == ref, name


def test_untraced_service_records_nothing(small_lubm):
    svc = KGService.from_dataset(small_lubm, n_shards=4)
    svc.bootstrap(small_lubm.base_workload())
    svc.query_batch(small_lubm.extended_workload())
    assert isinstance(svc._tracer, NullTracer)
    assert ambient_tracer() is svc._tracer
    assert len(svc._tracer) == 0
    # ...but the metrics registry is always live, span totals included
    counters = svc.stats()["metrics"]["counters"]
    assert counters["queries.served"] > 0
    assert counters["span.repro.serve.window.calls"] == 1


def test_spans_reach_the_profilers_host_plane(small_lubm, tmp_path):
    """A tiny service served under ``jax.profiler.trace``: the capture's
    host plane holds ``repro.serve.window`` with ``repro.exec.scan`` nested
    inside it, on the clock the Tracer records (CLOCK_REALTIME, offset by
    the capture's ``profile_start_time``)."""
    import jax
    from jax.profiler import ProfileData

    svc = KGService.from_dataset(small_lubm, n_shards=4, executor="jax",
                                 trace=True)
    svc.bootstrap(small_lubm.base_workload())
    with jax.profiler.trace(str(tmp_path)):
        svc.serve_window([small_lubm.queries["Q1"]])
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    env = dict(next(p for p in pd.planes
                    if p.name == "Task Environment").stats)
    events = [(e.name, e.start_ns, e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("repro.")]
    (_, w0, wd), = [e for e in events if e[0] == "repro.serve.window"]
    scans = [e for e in events if e[0] == "repro.exec.scan"]
    assert scans and all(w0 <= s and s + d <= w0 + wd for _, s, d in scans)
    mine, = svc.tracer().find("repro.serve.window")
    # the two clocks agree to well under a millisecond
    assert abs(env["profile_start_time"] + w0 - mine["ts_ns"]) < 1e6
    assert abs(wd - mine["dur_ns"]) < 1e6


def test_traced_flash_crowd_scenario():
    """A traced drift replay captures the reaction end-to-end: the round
    the controller fires, its drain, and every served window — and two
    same-seed replays open the same spans at the serving level."""
    from repro import scenario as drift
    from repro.graph import watdiv

    ds = watdiv.load(1, seed=0)
    scn = drift.flash_crowd(ds, warm=2, spike=2, cool=1,
                            queries_per_window=6, seed=3)

    def run():
        svc = KGService.from_dataset(ds, n_shards=4,
                                     migration_budget=1 << 20,
                                     replica_budget=1 << 20, trace=True)
        svc.bootstrap(scn.bootstrap_workload(ds))
        rep = drift.run_scenario(svc, scn, ds, adapt=True,
                                 mode="awapart/adaptive", warmup_phases=1)
        return svc, rep

    svc, rep = run()
    assert any(w.adapted for w in rep.windows)
    counts = svc.tracer().span_counts()
    # every reacted window is covered by a recorded round (warm-up and
    # rejected rounds may add more)
    assert counts["repro.adapt.round"] >= sum(1 for w in rep.windows
                                              if w.adapted)
    assert counts["repro.exec.query"] > 0 and counts["repro.serve.window"] > 0
    rounds = svc.tracer().find("repro.adapt.round")
    assert all(r["args"]["trigger"] in ("degradation", "write_drift",
                                        "no_baseline", "explicit")
               for r in rounds)
    svc2, _ = run()
    assert _structure(svc2.tracer(), _serve_level) == \
        _structure(svc.tracer(), _serve_level)
