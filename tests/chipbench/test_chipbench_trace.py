"""Reduction of a profiler trace (compact form) to busy time, idle share,
the longest device operations and idle gaps, and kernel time; checked on a
small hand-built trace and on a slice recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from chipbench import registry, roofline, trace

MS = 1_000_000  # ns


def small():
    return {
        "devices": [{
            "name": "/device:TPU:0",
            "ops": [["fusion.1", 10 * MS, 5 * MS],      # 10-15
                    ["fusion.2", 12 * MS, 6 * MS],      # overlaps: 10-18
                    ["custom-call", 50 * MS, 10 * MS],  # 50-60
                    ["fusion.3", 95 * MS, 20 * MS]],    # clipped at 100
            "modules": [["jit_pack_keys_pallas(3)", 10 * MS, 8 * MS],
                        ["jit_probe_sorted_pallas(4)", 50 * MS, 10 * MS],
                        ["jit_pack_keys_pallas(3)", 95 * MS, 2 * MS],
                        ["jit_early(1)", 1 * MS, 1 * MS]]}],
        "host": [["chipbench.window", 5 * MS, 95 * MS],          # 5-100
                 ["chipbench.adapt", 5 * MS, 40 * MS],           # 5-45
                 ["chipbench.serve", 45 * MS, 55 * MS]],         # 45-100
    }


def test_busy_idle_and_gaps():
    r = trace.reduce(small(), roofline.KERNELS)
    assert r["window_s"] == pytest.approx(0.095)
    assert r["busy_s"] == pytest.approx(0.008 + 0.010 + 0.005)
    assert r["idle_share"] == pytest.approx(1 - 0.023 / 0.095)
    gaps = r["idle_gaps"]
    # 5-10 and 18-50 inside the round's span, 60-95 inside a serve span
    assert gaps == [["chipbench.adapt", pytest.approx(0.037)],
                    ["chipbench.serve", pytest.approx(0.035)]]
    assert sum(g[1] for g in gaps) == pytest.approx(0.095 - 0.023)


def test_modules_and_kernels_inside_the_window():
    r = trace.reduce(small(), roofline.KERNELS)
    ops = dict(r["device_ops"])
    assert ops == {"jit_pack_keys_pallas": pytest.approx(0.010),
                   "jit_probe_sorted_pallas": pytest.approx(0.010)}
    assert r["kernels"]["pack_keys_pallas"] == dict(
        calls=2, seconds=pytest.approx(0.010))
    assert r["kernels"]["expand_pairs_pallas"]["calls"] == 0


def test_no_window_is_an_error():
    t = small()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(t)


def test_kernel_bytes_follow_the_shapes():
    assert roofline.pack_keys_bytes((1000, 2)) == 1000 * 2 * 4 + 8 * 1000
    assert roofline.probe_sorted_bytes(300, 1000) == 8 * 300 + 16 * 1000
    assert roofline.expand_pairs_bytes(1000, 4096) == 12 * 1000 + 8 * 4096


RECORDED = Path(__file__).parent / "data" / "tpu_v5e_trace_slice.json"


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over start/end points, clipped to [lo, hi)."""
    points = sorted([(max(s, lo), 1) for _, s, d in events if s < hi
                     and s + d > lo and d > 0]
                    + [(min(s + d, hi), -1) for _, s, d in events if s < hi
                       and s + d > lo and d > 0])
    depth, busy, last = 0, 0.0, lo
    for x, step in points:
        if depth > 0:
            busy += x - last
        depth += step
        last = x
    return busy


def test_recorded_tpu_slice():
    rec = json.loads(RECORDED.read_text())["trace"]
    r = trace.reduce(rec, roofline.KERNELS)
    (_, w0, wd), = [h for h in rec["host"] if h[0] == "chipbench.window"]
    dev = rec["devices"][0]
    assert r["window_s"] == pytest.approx(wd * 1e-9)
    assert r["busy_s"] == pytest.approx(
        _busy_by_sweep(dev["ops"], w0, w0 + wd) * 1e-9, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    for k in roofline.KERNELS:
        mine = [m for m in dev["modules"]
                if k in m[0] and w0 <= m[1] < w0 + wd]
        assert r["kernels"][k]["calls"] == len(mine) > 0
        assert r["kernels"][k]["seconds"] == pytest.approx(
            sum(m[2] for m in mine) * 1e-9)
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_fallback_share_reads_the_jnp_stages_modules():
    read = registry.reader("join.fallback_share")
    modules = {"jit_probe_sorted": 3.0, "jit_expand_pairs": 1.0,
               "jit_pack_keys_pallas": 0.5, "jit_probe_sorted_pallas": 0.25,
               "jit_expand_pairs_pallas": 0.25, "jit_gather": 9.0}
    assert read(dict(trace=dict(modules=modules))) == pytest.approx(80.0)
    assert read(dict(trace=dict(modules={"jit_gather": 1.0}))) is None
    rec = json.loads(RECORDED.read_text())["trace"]
    share = read(dict(trace=trace.reduce(rec, roofline.KERNELS)))
    assert share is not None and 0 <= share <= 100


def test_roofline_share_refuses_disagreeing_call_counts():
    tr = trace.reduce(small(), roofline.KERNELS)
    peaks = dict(hbm_bytes_per_s=1e9)
    ctx = dict(trace=tr, peaks=peaks,
               kernel_bytes={"pack_keys_pallas": [1_000_000, 1_000_000]})
    # 2 MB at 1 GB/s is 2 ms of the kernels' 10 ms
    assert roofline.share(ctx, "pack_keys_pallas") == pytest.approx(20.0)
    assert roofline.share(ctx, "expand_pairs_pallas") is None
    ctx["kernel_bytes"]["pack_keys_pallas"].append(1)
    with pytest.raises(ValueError):
        roofline.share(ctx, "pack_keys_pallas")
    ctx["kernel_bytes"]["expand_pairs_pallas"] = [8]
    with pytest.raises(ValueError):
        roofline.share(ctx, "expand_pairs_pallas")
