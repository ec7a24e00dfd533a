"""The program's spans (``repro.obs.span``) in the benchmark.

The program adds each closed span's calls and nanoseconds to its metrics
registry (``span.<name>.calls``, ``span.<name>.ns``), so the readers of
the ``program_span`` metrics divide two counter deltas over the window
(:func:`per_call`). A program without those counters reads nothing.

A profiler capture also holds the spans on its host plane, on the clock
of the device's operations. :func:`program` reads them from a capture,
and :func:`by_span` reduces them against the compact trace
(``trace.py``): for each span name inside the window, its calls, seconds,
self seconds (less its children's) and the device-idle seconds inside
that self time. :func:`attributed` is the share of the device-idle time
inside a harness span (``chipbench.serve``) that falls in the self time
of a program span.

    python3 -m chipbench.spans --workload <cell> --seed <n> --seconds <s>

runs a cell as ``python3 -m chipbench.run --trace 1`` does, keeping the
program's spans of the capture, and prints its result with
``idle_by_span`` (the ten spans with the most idle seconds), ``spans`` and
``attributed`` added. ``--slice PATH`` also writes 60 ms of the capture
from a join in mid-window, in the compact form, for the tests.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import sys
from typing import Dict, List, Sequence

PREFIX = "repro."
SLICE_MS = 60.0


def per_call(ctx: dict, name: str, per: str):
    """Milliseconds of span ``name`` per call of span ``per`` over the
    window, from the registry's counter deltas; None when the program
    reports no ``per`` span."""
    c = ctx["counters"]
    calls = c.get(f"span.{per}.calls", 0)
    if not calls:
        return None
    return c.get(f"span.{name}.ns", 0) * 1e-6 / calls


def program(log_dir: str) -> List[list]:
    """``[name, start_ns, duration_ns]`` of every program span on the host
    planes of the newest capture under ``log_dir``, on the clock of
    ``trace.compact``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    pd = ProfileData.from_file(paths[-1])
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


class _Busy:
    """Device-busy time inside any interval, from the merged busy
    intervals of the first device inside the window."""

    def __init__(self, trace: dict, w0: float, w1: float):
        from chipbench.trace import _union

        dev = trace["devices"][0]
        events = dev["ops"] or dev["modules"]
        self.merged = _union([(s, s + d) for _, s, d in events], w0, w1)
        self.starts = [s for s, _ in self.merged]
        self.cum = [0.0]
        for s, e in self.merged:
            self.cum.append(self.cum[-1] + e - s)

    def _before(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        total = self.cum[i]
        if i and self.merged[i - 1][1] > x:
            total -= self.merged[i - 1][1] - x
        return total

    def idle(self, a: float, b: float) -> float:
        return (b - a) - (self._before(b) - self._before(a))


def _window(trace: dict):
    from chipbench.trace import WINDOW_SPAN

    (_, w0, wd), = [h for h in trace["host"] if h[0] == WINDOW_SPAN][:1]
    return w0, w0 + wd


def _records(program_spans: Sequence[list], w0: float, w1: float):
    """``[start, end, name, self_intervals]`` of each span starting inside
    the window (clipped to its end), nested by a stack: spans of one
    thread either nest or follow each other."""
    evs = sorted(((s, min(s + d, w1), n) for n, s, d in program_spans
                  if w0 <= s < w1), key=lambda e: (e[0], -e[1]))
    records, stack = [], []
    for s, e, n in evs:
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, n, []]
        if stack:
            stack[-1][3].append((s, min(e, stack[-1][1])))
        stack.append(rec)
        records.append(rec)
    for rec in records:
        s, e, _, kids = rec
        gaps, at = [], s
        for ks, ke in kids:
            if ks > at:
                gaps.append((at, ks))
            at = max(at, ke)
        if e > at:
            gaps.append((at, e))
        rec[3] = gaps
    return records


def by_span(trace: dict, program_spans: Sequence[list]) -> Dict[str, dict]:
    """Per program span name inside the window: ``calls``, ``seconds``,
    ``self_seconds`` and ``idle_seconds`` (device idle inside its self
    time)."""
    w0, w1 = _window(trace)
    busy = _Busy(trace, w0, w1)
    out: Dict[str, dict] = {}
    for s, e, name, gaps in _records(program_spans, w0, w1):
        r = out.setdefault(name, dict(calls=0, seconds=0.0,
                                      self_seconds=0.0, idle_seconds=0.0))
        r["calls"] += 1
        r["seconds"] += (e - s) * 1e-9
        r["self_seconds"] += sum(b - a for a, b in gaps) * 1e-9
        r["idle_seconds"] += sum(busy.idle(a, b) for a, b in gaps) * 1e-9
    return out


def idle_by_span(spans: Dict[str, dict], top: int = 10) -> List[list]:
    return sorted(([n, r["idle_seconds"]] for n, r in spans.items()),
                  key=lambda kv: -kv[1])[:top]


def attributed(trace: dict, program_spans: Sequence[list], harness: str,
               skip: Sequence[str] = ()) -> dict:
    """Device-idle seconds inside the harness span ``harness`` and the
    share of them in the self time of a program span not in ``skip``."""
    w0, w1 = _window(trace)
    busy = _Busy(trace, w0, w1)
    outer = sorted((max(s, w0), min(s + d, w1)) for n, s, d in trace["host"]
                   if n == harness and s < w1 and s + d > w0)
    starts = [s for s, _ in outer]
    idle = sum(busy.idle(a, b) for a, b in outer)
    mine = 0.0
    for s, e, name, gaps in _records(program_spans, w0, w1):
        i = bisect.bisect_right(starts, s) - 1
        if name in skip or i < 0 or e > outer[i][1]:
            continue
        mine += sum(busy.idle(a, b) for a, b in gaps)
    return dict(idle_s=idle * 1e-9,
                share=mine / idle if idle > 0 else None)


def cut(trace: dict, program_spans: Sequence[list], start: float,
        ms: float) -> dict:
    """A compact trace of ``ms`` milliseconds from ``start``: the window
    span cut to it, the events starting inside it, op names cut to 40
    characters, and the program spans under ``program``."""
    from chipbench.trace import WINDOW_SPAN

    lo, hi = start, start + ms * 1e6

    def keep(events, width=None):
        return [[n[:width] if width else n, s, d] for n, s, d in events
                if lo <= s < hi]

    host = [[WINDOW_SPAN, lo, hi - lo]] + [
        h for h in trace["host"]
        if h[0] != WINDOW_SPAN and h[1] < hi and h[1] + h[2] > lo]
    devices = [dict(name=d["name"], ops=keep(d["ops"], 40),
                    modules=keep(d["modules"])) for d in trace["devices"]]
    return dict(devices=devices, host=host,
                program=keep(program_spans))


def main(argv=None) -> int:
    from chipbench import registry
    from chipbench import run as runmod
    from chipbench import trace as tracemod

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice", metavar="PATH", default=None)
    args = ap.parse_args(argv)

    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    sys.path.insert(0, str(registry.CHECKOUT / "src"))
    devs = runmod.device_or_exit(int(cell["chips"]))
    runmod.log(f"[setup] compile cache {runmod.compile_cache()}")
    watch = runmod.CompileWatch()
    # chipbench.run reduces the capture and deletes it; keep the program's
    # spans of it on the way
    kept: dict = {}
    compact = tracemod.compact

    def compact_keeping_spans(log_dir):
        kept["trace"] = compact(log_dir)
        kept["program"] = program(log_dir)
        return kept["trace"]

    tracemod.compact = compact_keeping_spans
    try:
        result = runmod.run_cell(bench, args.workload, args.seed,
                                 args.seconds, True, devs, watch)
    finally:
        tracemod.compact = compact
    trace, prog = kept["trace"], kept["program"]
    spans = by_span(trace, prog)
    result["idle_by_span"] = idle_by_span(spans)
    result["spans"] = spans
    result["attributed"] = {
        h: attributed(trace, prog, h, skip)
        for h, skip in (("chipbench.serve", ("repro.serve.window",)),
                        ("chipbench.adapt", ("repro.adapt.round",)))}
    if args.slice:
        joins = sorted(s for n, s, _ in prog if n == "repro.join.sort")
        start = joins[len(joins) // 2] - 1e6 if joins else _window(trace)[0]
        with open(args.slice, "w") as f:
            json.dump(dict(trace=cut(trace, prog, start, SLICE_MS)), f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
