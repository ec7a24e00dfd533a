"""The program's spans in the benchmark: the readers of the
``program_span`` metrics, the reduction of a capture's program spans to
calls, self time and device-idle time per span, and the recorded TPU
slices, which still reduce to the values they gave before."""
import json
from pathlib import Path

import pytest

from chipbench import registry, roofline, spans, trace

MS = 1_000_000  # ns
DATA = Path(__file__).parent / "data"


def small():
    """Device busy 10-20 and 30-40 ms of a 100 ms window; a served
    request 5-60 ms and an adaptation round 60-100 ms."""
    return {
        "devices": [{"name": "/device:TPU:0",
                     "ops": [["fusion.1", 10 * MS, 10 * MS],
                             ["fusion.2", 30 * MS, 10 * MS]],
                     "modules": []}],
        "host": [["chipbench.window", 0, 100 * MS],
                 ["chipbench.serve", 5 * MS, 55 * MS],
                 ["chipbench.adapt", 60 * MS, 40 * MS]],
    }


PROGRAM = [["repro.serve.window", 6 * MS, 52 * MS],
           ["repro.exec.query", 8 * MS, 42 * MS],
           ["repro.exec.scan", 8 * MS, 4 * MS],
           ["repro.exec.join", 15 * MS, 30 * MS],
           ["repro.join.sort", 16 * MS, 9 * MS],
           ["repro.adapt.round", 61 * MS, 38 * MS],
           ["repro.adapt.measure", 62 * MS, 28 * MS],
           ["repro.exec.scan", 120 * MS, 1 * MS]]       # after the window


def test_by_span_self_and_idle_time():
    r = spans.by_span(small(), PROGRAM)
    ms = 1e-3
    expect = {  # calls, seconds, self, idle (ms)
        "repro.serve.window": (1, 52, 10, 10),
        "repro.exec.query": (1, 42, 8, 5),
        "repro.exec.scan": (1, 4, 4, 2),
        "repro.exec.join": (1, 30, 21, 10),
        "repro.join.sort": (1, 9, 9, 5),
        "repro.adapt.round": (1, 38, 10, 10),
        "repro.adapt.measure": (1, 28, 28, 28),
    }
    assert set(r) == set(expect)
    for name, (calls, sec, own, idle) in expect.items():
        assert r[name] == dict(calls=calls, seconds=pytest.approx(sec * ms),
                               self_seconds=pytest.approx(own * ms),
                               idle_seconds=pytest.approx(idle * ms)), name
    ranked = spans.idle_by_span(r)
    assert [n for n, _ in ranked] == [
        "repro.adapt.measure", "repro.serve.window", "repro.exec.join",
        "repro.adapt.round", "repro.exec.query", "repro.join.sort",
        "repro.exec.scan"]
    assert spans.idle_by_span(r, top=2)[0][1] == pytest.approx(0.028)


def test_attributed_share_of_the_harness_idle_time():
    serve = spans.attributed(small(), PROGRAM, "chipbench.serve",
                             ("repro.serve.window",))
    # 55 ms less 20 busy; query 5 + scan 2 + join 10 + sort 5 attributed
    assert serve["idle_s"] == pytest.approx(0.035)
    assert serve["share"] == pytest.approx(22 / 35)
    adapt = spans.attributed(small(), PROGRAM, "chipbench.adapt",
                             ("repro.adapt.round",))
    assert adapt == dict(idle_s=pytest.approx(0.040),
                         share=pytest.approx(0.7))
    assert spans.attributed(small(), PROGRAM, "chipbench.step")["share"] \
        is None


NEW_READERS = {  # metric: (span, per)
    "exec.scan_ms": ("repro.exec.scan", "repro.exec.query"),
    "exec.join_ms": ("repro.exec.join", "repro.exec.query"),
    "join.sort_ms": ("repro.join.sort", "repro.exec.query"),
    "exec.federation_ms": ("repro.exec.federation", "repro.exec.query"),
    "serve.plan_ms": ("repro.serve.plan", "repro.exec.query"),
    "adapt.measure_ms": ("repro.adapt.measure", "repro.adapt.round"),
    "adapt.cluster_ms": ("repro.adapt.cluster", "repro.adapt.round"),
}


@pytest.mark.parametrize("metric", sorted(NEW_READERS))
def test_span_readers(metric):
    name, per = NEW_READERS[metric]
    read = registry.reader(metric)
    counters = {f"span.{per}.calls": 4, f"span.{per}.ns": 90 * MS,
                f"span.{name}.calls": 7, f"span.{name}.ns": 10 * MS}
    assert read(dict(counters=counters)) == pytest.approx(2.5)
    # a span the window never opened reads 0; a program without the
    # spans (no per-call counter) reads nothing
    del counters[f"span.{name}.ns"]
    assert read(dict(counters=counters)) == 0.0
    assert read(dict(counters={"queries.served": 9})) is None


def test_span_metrics_are_listed_for_the_drift_cell():
    bench = registry.benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW_READERS:
        m = listed[metric]
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["workloads"] == ["lubm10-exp1-drift"]


RECORDED = DATA / "tpu_v5e_trace_slice.json"


def test_recorded_tpu_slice_reduces_as_before():
    rec = json.loads(RECORDED.read_text())["trace"]
    r = trace.reduce(rec, roofline.KERNELS)
    approx = pytest.approx
    assert r["window_s"] == approx(0.06, rel=1e-12)
    assert r["busy_s"] == approx(0.019091071, rel=1e-12)
    assert r["idle_share"] == approx(0.6818154833333334, rel=1e-12)
    assert r["device_ops"] == [
        ["jit_expand_pairs_pallas", approx(0.002748655, rel=1e-12)],
        ["jit_probe_sorted_pallas", approx(0.002397296, rel=1e-12)],
        ["jit__lambda", approx(0.000725292, rel=1e-12)],
        ["jit_pack_keys_pallas", approx(2.0854e-05, rel=1e-12)],
        ["jit_dynamic_slice", approx(2.942e-06, rel=1e-9)]]
    assert r["idle_gaps"] == [["chipbench.serve",
                               approx(0.0409089289999999, rel=1e-12)]]
    assert r["kernels"] == {
        "pack_keys_pallas": dict(calls=8, seconds=approx(2.0854e-05)),
        "probe_sorted_pallas": dict(calls=4, seconds=approx(0.002397296)),
        "expand_pairs_pallas": dict(calls=3, seconds=approx(0.002748655))}
    ctx = dict(trace=r)
    assert registry.reader("join.fallback_share")(ctx) == 0.0
    assert registry.reader("device.idle_share")(ctx) == approx(
        68.18154833333334, rel=1e-12)


def test_program_spans_of_a_cpu_capture(small_lubm, tmp_path):
    """A tiny service served under ``jax.profiler.trace``: the program
    spans read from the capture hold ``repro.serve.window`` with
    ``repro.exec.scan`` nested inside it, and reduce against a window."""
    import jax

    from repro.api import KGService

    svc = KGService.from_dataset(small_lubm, n_shards=4, executor="jax")
    svc.bootstrap(small_lubm.base_workload())
    with jax.profiler.trace(str(tmp_path)):
        svc.serve_window([small_lubm.queries["Q2"]])
    prog = spans.program(str(tmp_path))
    (_, w0, wd), = [p for p in prog if p[0] == "repro.serve.window"]
    scans = [p for p in prog if p[0] == "repro.exec.scan"]
    assert scans and all(w0 <= s and s + d <= w0 + wd for _, s, d in scans)
    fake = dict(devices=[dict(name="/device:TPU:0", ops=[], modules=[])],
                host=[["chipbench.window", w0, wd]])
    r = spans.by_span(fake, prog)
    assert r["repro.serve.window"]["calls"] == 1
    assert r["repro.exec.scan"]["calls"] == len(scans)
    assert r["repro.serve.window"]["self_seconds"] < \
        r["repro.serve.window"]["seconds"]
    assert r["repro.exec.scan"]["idle_seconds"] == pytest.approx(
        r["repro.exec.scan"]["self_seconds"])


PROGRAM_SLICE = DATA / "tpu_v5e_program_slice.json"


def test_recorded_program_slice():
    """60 ms of a traced TPU v5e run with the program's spans: the join
    pipeline's jitted stages show under their own names, and the spans
    reduce to calls, self time and device-idle time."""
    rec = json.loads(PROGRAM_SLICE.read_text())["trace"]
    approx = pytest.approx
    r = trace.reduce(rec, roofline.KERNELS)
    assert r["idle_share"] == approx(0.8949213166666666, rel=1e-12)
    ops = dict(r["device_ops"])
    assert ops["jit_sort_take"] == approx(0.00207394, rel=1e-9)
    assert ops["jit_pair_gather"] == approx(6.569e-06, rel=1e-9)
    assert not any("lambda" in name for name in r["modules"])
    s = spans.by_span(rec, rec["program"])
    for r_ in s.values():
        assert 0 <= r_["idle_seconds"] <= r_["self_seconds"] * (1 + 1e-12)
        assert r_["self_seconds"] <= r_["seconds"] * (1 + 1e-12)
    assert s["repro.serve.window"] == dict(
        calls=1, seconds=approx(0.030425601), self_seconds=approx(2.311e-05),
        idle_seconds=approx(2.311e-05))
    assert s["repro.join.sort"] == dict(
        calls=2, seconds=approx(0.0095707), self_seconds=approx(0.0095707),
        idle_seconds=approx(0.007162035))
    assert s["repro.exec.join"]["self_seconds"] == approx(0.000122052)
    assert spans.idle_by_span(s, 3) == [
        ["repro.serve.plan", approx(0.017190629)],
        ["repro.exec.federation", approx(0.012325048)],
        ["repro.join.sort", approx(0.007162035)]]
    serve = spans.attributed(rec, rec["program"], "chipbench.serve",
                             ("repro.serve.window",))
    assert serve == dict(idle_s=approx(0.053637399),
                         share=approx(0.9754233235657083))
