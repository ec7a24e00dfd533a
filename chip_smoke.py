#!/usr/bin/env python3
"""Chip smoke test: serve LUBM(10) over 8 shards through the ``jax-pallas``
executor on one TPU, and check every answer against ``NumpyExecutor``.

    python chip_smoke.py [--universities 10] [--shards 8] [--seed 0]

It drives the public serving path (``repro.launch.serve.build_system`` ->
``KGService``) through these phases, each printed with its wall seconds:

* ``load``       — generate LUBM(universities) from ``--seed``;
* ``bootstrap``  — partition on the base workload, materialize shard views;
* ``cold_batch`` — one ``query_batch`` of the extended workload (24
  queries), first compilation included;
* ``warm_batch`` — the same batch again (a same-epoch repeat, which the
  service's result cache serves);
* ``adapt``      — the EQ-workload adaptation round of experiment 1, then
  ``drain_<k>``: one ``query_batch`` per migration chunk until the session
  has drained;
* ``write``      — one batch of synthetic inserts, then ``final_batch``.

The seconds time the chip service's own call (the twin's replay is
outside them) and include first compilation: they are first-run
observations, not benchmark numbers. After every ``query_batch`` each
query's bindings must equal those of a ``NumpyExecutor`` twin that replays
the same operations on a copy of the same data. The kernel tier counters (``kernels.dispatch.*``) are
printed, and the run fails unless the Pallas join pipeline served and no
join fell back to the host.

The script exits non-zero, without a result line, when JAX finds no TPU.
The last line of its output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the guard's default amortization window refuses the EQ round at
# LUBM(10)/8 shards; over 1000 windows it pays off and is accepted
AMORTIZE_WINDOW = 1000
MIGRATION_BUDGET = 1 << 20          # bytes of migration traffic per chunk
WRITE_ROWS = 1024                   # synthetic inserts in the write phase


def canon(bindings):
    """Order-insensitive form of an executor's bindings ({var: column}):
    the sorted variable names and the rows sorted lexicographically."""
    import numpy as np

    keys = tuple(sorted(bindings))
    if not keys:
        return keys, np.empty((0, 0), np.int64)
    rows = np.stack([np.asarray(bindings[k], np.int64) for k in keys], 1)
    return keys, rows[np.lexsort(rows.T[::-1])]


def _same(a, b) -> bool:
    (ka, ra), (kb, rb) = canon(a), canon(b)
    return ka == kb and ra.shape == rb.shape and bool((ra == rb).all())


def serve_and_compare(universities: int = 10, shards: int = 8,
                      seed: int = 0, log=print) -> dict:
    """Run every phase on a ``jax-pallas`` service and its ``NumpyExecutor``
    twin. Returns the bindings check (``mismatches``, ``batches``), the
    adaptation report, the drain and write outcome, the phase seconds and
    the chip service's ``kernels.dispatch.*`` counters."""
    import numpy as np

    from repro.api import AWAPartitioner, KGService
    from repro.core.adaptive import AdaptConfig
    from repro.graph import lubm
    from repro.graph.triples import TripleStore
    from repro.launch.serve import build_system, synthetic_writes
    from repro.obs import set_ambient

    phases: dict = {}

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        phases[name] = time.perf_counter() - t0
        log(f"[phase] {name}: {phases[name]:.3f} s")
        return out

    config = AdaptConfig(amortize_window=AMORTIZE_WINDOW)
    ds = timed("load", lambda: lubm.load(universities, seed))
    log(f"[data] LUBM({universities}) seed {seed}: {ds.store.n_triples} "
        f"triples, {shards} shards")
    # the twin first: a service installs its registry as the ambient sink
    # of the kernel-dispatch counters, and the chip service must own them
    twin = KGService(TripleStore(ds.store.triples.copy(),
                                 ds.store.dictionary),
                     shards, AWAPartitioner(config),
                     type_predicate=ds.dictionary.lookup("rdf:type"),
                     executor="numpy", migration_budget=MIGRATION_BUDGET)
    _, svc = build_system(universities, shards, seed, config=config,
                          executor="jax-pallas",
                          migration_budget=MIGRATION_BUDGET)

    def on_twin(fn):
        set_ambient(twin.metrics)
        try:
            return fn()
        finally:
            set_ambient(svc.metrics)

    out = dict(mismatches=[], batches=0, phases=phases)

    def batch(name, queries):
        hits0 = svc.metrics.counter("queries.result_cache_hits").value
        got = timed(name, lambda: svc.query_batch(queries))
        hits = svc.metrics.counter("queries.result_cache_hits").value - hits0
        ref = on_twin(lambda: twin.query_batch(queries))
        bad = [q.name for q, (b, _), (r, _) in zip(queries, got, ref)
               if not _same(b, r)]
        out["mismatches"] += [f"{name}:{q}" for q in bad]
        out["batches"] += 1
        rows = sum(st.rows for _, st in got)
        log(f"[check] {name}: {len(queries) - len(bad)}/{len(queries)} "
            f"queries equal NumpyExecutor, {rows} result rows, "
            f"{hits} served from the result cache")

    base, extended = ds.base_workload(), ds.extended_workload()
    eq = ds.workload([f"EQ{i}" for i in range(1, 11)])
    timed("bootstrap", lambda: svc.bootstrap(base))
    on_twin(lambda: twin.bootstrap(base))
    batch("cold_batch", extended)
    batch("warm_batch", extended)

    report = timed("adapt", lambda: svc.adapt(eq))
    twin_report = on_twin(lambda: twin.adapt(eq))
    session = svc.session
    n_chunks = session.n_chunks if session is not None else 0
    log(f"[adapt] accepted={report.accepted} reason={report.reason} "
        f"dj {report.dj_before:.0f}->{report.dj_after:.0f} | "
        f"{report.plan.summary()} | {n_chunks} chunks")
    if twin_report.accepted != report.accepted:
        out["mismatches"].append("adapt:accepted")
    k = 0
    while svc.session is not None:
        batch(f"drain_{k}", extended)          # applies one chunk, serves
        k += 1
    if twin.session is not None:
        out["mismatches"].append("drain:twin_not_drained")
    log(f"[drain] {k} chunks applied, "
        f"{session.bytes_applied if session else 0} bytes migrated")

    rep = timed("write", lambda: synthetic_writes(
        svc, WRITE_ROWS, np.random.default_rng(seed)))
    twin_rep = on_twin(lambda: synthetic_writes(
        twin, WRITE_ROWS, np.random.default_rng(seed)))
    log(f"[write] {rep.n_inserted} rows inserted on shards "
        f"{rep.touched_shards}")
    if twin_rep.n_inserted != rep.n_inserted:
        out["mismatches"].append("write:n_inserted")
    batch("final_batch", extended)

    counters = svc.metrics.snapshot()["counters"]
    out.update(
        accepted=report.accepted, chunks=n_chunks, drained_chunks=k,
        drained=svc.session is None, inserted=rep.n_inserted,
        counters={n: v for n, v in counters.items()
                  if n.startswith("kernels.dispatch.")})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--universities", type=int, default=10)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})"
                 "; this check runs on the chip only")
    from repro.launch.serve import setup_compile_cache

    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" | jax {jax.__version__} | compile cache {setup_compile_cache()}")
    res = serve_and_compare(args.universities, args.shards, args.seed)

    tiers = res["counters"]
    for name, value in tiers.items():
        print(f"[tier] {name} = {value}")
    failures = list(res["mismatches"])
    if not res["accepted"]:
        failures.append("the adaptation round was refused")
    if not res["drained"] or res["drained_chunks"] != res["chunks"]:
        failures.append("the migration session did not drain")
    if res["inserted"] <= 0:
        failures.append("the write batch inserted nothing")
    if tiers.get("kernels.dispatch.join.pipeline.pallas", 0) <= 0:
        failures.append("no join was served by the Pallas pipeline")
    failures += [f"host tier served: {n}" for n in tiers
                 if n.startswith("kernels.dispatch.join.pipeline.host")]
    if failures:
        sys.exit("chip_smoke FAILED: " + "; ".join(failures))
    print(f"[ok] {res['batches']} batches equal NumpyExecutor; "
          f"{res['drained_chunks']} chunks drained; "
          f"{res['inserted']} rows written")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
