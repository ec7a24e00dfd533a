"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it names its device and exits non-zero, printing no result,
without a TPU or with fewer chips than the cell asks for. It builds the
deployment from the seed and warms up (``setup_s``, from process start to
the window's open), serves the window, reads the device's memory peak,
frees the program's state, checks every answer against the plain
reference (``check.py``), and prints, as the last line of standard output,
one JSON object. With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the profiler records the window and the
metrics are the cell's per-layer ones. The numbers compared, each with its
limit, are the last lines of standard error and the ``checks`` key, which
comes last in the result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from chipbench import registry  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Counts JAX's backend compile events while ``on`` is set."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name=None, **_):
        if self.on and event == BACKEND_COMPILE:
            self.count += 1
            self.seconds += duration
            self.names[fun_name] = self.names.get(fun_name, 0) + 1

    def _event(self, event, **_):
        if self.on and event == CACHE_HIT:
            self.cache_hits += 1


def device_or_exit(chips: int):
    # libtpu logs under /tmp unless told otherwise; a run writes nothing
    # outside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"[device] {d.platform} {d.device_kind} x{len(devs)} | "
        f"jax {jax.__version__}")
    if d.platform != "tpu":
        sys.exit(f"chipbench: no TPU (JAX platform {d.platform!r}); "
                 "the benchmark runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs


def compile_cache() -> str:
    """The checkout's own persistent compilation cache, at a fixed path,
    handed to the program through ``JAX_COMPILATION_CACHE_DIR``; every
    executable is cached, however short its compile."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(registry.CHECKOUT
                                                  / ".jax_cache")
    from repro.launch.serve import setup_compile_cache

    path = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def counter_delta(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             traced: bool, devs, watch: CompileWatch,
             root=registry.HERE) -> dict:
    from chipbench import arrivals, check, deploy, peaks, roofline, stats
    from chipbench import trace as tracemod
    from chipbench.reference import Reference

    cell = registry.cell(bench, cell_name)
    cfg = registry.config(cell["config"], root)
    traffic = registry.traffic(cell["traffic"], root)
    kind = registry.loop(traffic["kind"])
    readers = registry.readers(bench, cell_name, root) if traced else {}

    t = time.perf_counter()
    dep = deploy.build(cfg, seed)
    log(f"[setup] data: {time.perf_counter() - t:.3f} s, "
        f"{len(dep.triples)} triples, fingerprint "
        f"{'as configured' if dep.fingerprint_ok else 'MOVED'}")
    svc = kind.prepare(dep, traffic, log)
    due, names = arrivals.schedule(kind.mixes(traffic),
                                   float(traffic["rate_qps"]), seconds)
    gc.collect()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    kernel_bytes: dict = {}
    if traced:
        import jax

        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - T0
    log(f"[setup] setup_s {setup_s:.3f} s ({len(names)} requests due over "
        f"{seconds} s at {traffic['rate_qps']} q/s)")

    before = svc.metrics.snapshot()["counters"]
    watch.on = True
    if traced:
        with roofline.recording(kernel_bytes):
            run = kind.serve(svc, dep, traffic, due, names, seconds, log)
    else:
        run = kind.serve(svc, dep, traffic, due, names, seconds, log)
    watch.on = False
    counters = counter_delta(before, svc.metrics.snapshot()["counters"])
    if traced:
        import jax

        jax.profiler.stop_trace()
    mem = devs[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    log(f"[window] {len(run.windows)} passes, {len(run.chunk_ms)} chunks, "
        f"drained at {[r.drained_s for r in run.rounds]}, compiles in "
        f"window {watch.count} ({watch.seconds:.3f} s, {watch.cache_hits} "
        f"from the persistent cache): {watch.names}")
    for w in run.windows:
        log(f"[pass] t={w.start_s:.3f} n={w.n} misses={w.misses} "
            f"epoch={w.epoch} round={w.round} step_ms={w.step_ms} "
            f"serve_ms={w.serve_ms:.1f}")

    # the program's state goes before the reference runs
    digests = check.program_digests(run)
    run.answers = [None] * len(run.answers)
    del svc
    dep.store = None
    gc.collect()
    t = time.perf_counter()
    ref = Reference(dep.triples, int(cfg["shards"]))
    patterns = {n: q.patterns for n, q in dep.queries.items()}
    numbers = check.compare(run, ref, patterns,
                            bool(cfg["guarantees"]["round_accepted"]),
                            digests)
    log(f"[check] reference: {time.perf_counter() - t:.3f} s")

    lat = [float(x) for x in run.latencies_ms if x == x]
    failed = int(sum(1 for x in run.latencies_ms if x != x))
    result = dict(correct=check.verdict(numbers), attempted=len(names),
                  failed=failed)
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs), memory_peak_bytes=memory_peak)
    if not traced:
        p95 = stats.tail(lat, 0.95) if lat else None
        if p95:
            log(f"[latency] n={p95['n']} p95={p95['value']:.3f} ms with "
                f"{p95['beyond']} beyond, mean={stats.mean(lat):.3f} ms")
        values = dict(setup_s=setup_s,
                      query_p95_ms=p95["value"] if p95 else None,
                      query_mean_ms=stats.mean(lat) if lat else None)
        units = {m["name"]: m["unit"]
                 for m in registry.end_to_end(bench, cell_name)}
        metrics = {k: dict(value=values[k], unit=u) for k, u in units.items()
                   if values.get(k) is not None}
    else:
        reduced = tracemod.reduce(tracemod.compact(trace_dir),
                                  roofline.KERNELS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(counters=counters, run=run, compiles=watch.count,
                   trace=reduced, kernel_bytes=kernel_bytes,
                   peaks=peaks.peaks(devs[0].device_kind))
        units = {m["name"]: m["unit"]
                 for m in registry.per_layer(bench, cell_name)}
        metrics = {}
        for name, read in readers.items():
            v = read(ctx)
            if v is None:
                log(f"[metric] {name}: nothing to read")
            else:
                metrics[name] = dict(value=float(v), unit=units[name])
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                   idle_gaps=reduced["idle_gaps"])
        log(f"[trace] kernels {reduced['kernels']} | recorded calls "
            f"{ {k: len(v) for k, v in kernel_bytes.items()} }")
    result.update(metrics=metrics, device=dev)
    for k, v in metrics.items():
        log(f"[metric] {k} = {v['value']} {v['unit']}")
    result["checks"] = {k: dict(value=v, limit=check.LIMITS[k])
                        for k, v in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    src = registry.CHECKOUT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chipbench: the program is not in this checkout ({src})")
    sys.path.insert(0, str(src))
    devs = device_or_exit(int(cell["chips"]))
    log(f"[setup] compile cache {compile_cache()}")
    watch = CompileWatch()
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), devs, watch)
    for k, v in result["checks"].items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
