"""Profiler trace: capture, compact form, and reduction to metrics.

The compact form keeps what the reduction reads: per device plane, the
events of its ``XLA Ops`` and ``XLA Modules`` lines, and the host spans the
harness opened (``chipbench.*`` trace annotations), each as
``[name, start_ns, duration_ns]``. Tests reduce a small recorded one.

* busy: the union of a device's op intervals inside the window span
  (``chipbench.window``), averaged over the devices; idle share is one less
  busy over the window;
* modules: device time per executable (module), and device_ops, the
  longest of them first;
* idle_gaps: idle time between busy intervals by what the host was doing,
  each gap labelled by the innermost host span open at its midpoint
  (``outside_calls`` when the loop was between calls: waiting for a due
  request, or bookkeeping), summed per label, longest first;
* kernels: calls and device time of the modules whose name holds a kernel's
  name.
"""
from __future__ import annotations

import glob
import os
import re
from typing import List, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"


def compact(log_dir: str) -> dict:
    """Compact form of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: dict = {"devices": [], "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: Sequence[Sequence[float]], lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(trace: dict, kernels: Sequence[str] = (), top: int = 10) -> dict:
    win = [h for h in trace["host"] if h[0] == WINDOW_SPAN]
    if not win or not trace["devices"]:
        raise ValueError("trace has no window span or no device plane")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    spans = [h for h in trace["host"] if h[0] != WINDOW_SPAN]
    busy, gaps, per_module = [], {}, {}
    kern = {k: [0, 0.0] for k in kernels}
    for i, dev in enumerate(trace["devices"]):
        events = dev["ops"] or dev["modules"]
        merged = _union([(s, s + d) for _, s, d in events], w0, w1)
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in dev["modules"]:
            if not w0 <= s < w1:
                continue
            m = _module(name)
            per_module[m] = per_module.get(m, 0.0) + d
            for k in kernels:
                if k in m:
                    kern[k][0] += 1
                    kern[k][1] += d * 1e-9
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    mid = (s + e) / 2
                    open_ = [h for h in spans if h[1] <= mid < h[1] + h[2]]
                    label = (min(open_, key=lambda h: h[2])[0] if open_
                             else "outside_calls")
                    gaps[label] = gaps.get(label, 0.0) + (e - s) * 1e-9
    window_s = (w1 - w0) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    ops = sorted(per_module.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        window_s=window_s, busy_s=busy_s,
        idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
        modules={n: d * 1e-9 for n, d in per_module.items()},
        device_ops=[[n, d * 1e-9] for n, d in ops],
        idle_gaps=sorted(([k, v] for k, v in gaps.items()),
                         key=lambda g: -g[1])[:top],
        kernels={k: dict(calls=c, seconds=s) for k, (c, s) in kern.items()})
