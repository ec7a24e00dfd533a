#!/usr/bin/env bash
# Tier-1 gate + end-to-end smoke of the public repro.api surface.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# CI runs the whole suite at full property-test profiles; the default
# developer `pytest -x -q` skips @slow tests and runs reduced profiles
export REPRO_FULL_TESTS=1

echo "== tier-1: pytest (full profiles, slow tests included) =="
python -m pytest -x -q

echo "== smoke: examples/quickstart.py (KGService + all strategies) =="
python examples/quickstart.py

echo "== smoke: query_batch on LUBM(1) under every executor backend =="
python - <<'EOF'
from repro.api import KGService
from repro.graph import lubm

ds = lubm.load(1, seed=0)
window = ds.extended_workload()
rows = {}
for name in ("numpy", "jax", "jax-pallas"):
    svc = KGService.from_dataset(ds, n_shards=4, executor=name)
    kg = svc.bootstrap(ds.base_workload())
    results = svc.query_batch(window)
    assert len(results) == len(window)
    assert kg.plan_builds == len(window), kg.plan_builds
    rows[name] = [st.rows for _, st in results]
    print(f"[ci] query_batch x{len(window)} executor={name}: "
          f"{sum(rows[name])} total rows")
assert rows["numpy"] == rows["jax"] == rows["jax-pallas"], \
    "executor backends disagree"
EOF

echo "== smoke: fused join pipeline forced (pallas-interpret) == numpy =="
python - <<'EOF'
import numpy as np
from repro.api import JaxExecutor, KGService
from repro.graph import lubm
import repro.query.exec as qexec

def canon(b):
    return sorted(map(tuple, np.stack(
        [b[k] for k in sorted(b)], axis=1).tolist())) if b else []

ds = lubm.load(1, seed=0)
window = ds.extended_workload()
ref_svc = KGService.from_dataset(ds, n_shards=4, executor="numpy")
ref_svc.bootstrap(ds.base_workload())
ref = ref_svc.query_batch(window)

# probe_kernel=True under pallas forces every fused-pipeline stage through
# the Pallas kernels (interpret mode on this CPU container)
svc = KGService.from_dataset(
    ds, n_shards=4, executor=JaxExecutor(pallas=True, probe_kernel=True))
svc.bootstrap(ds.base_workload())
got = svc.query_batch(window)
assert [canon(b) for b, _ in got] == [canon(b) for b, _ in ref], \
    "fused pipeline bindings diverge from the numpy reference"
for (_, st), (_, rst) in zip(got, ref):
    for f in qexec.ExecStats.COMPARABLE:
        assert getattr(st, f) == getattr(rst, f), (f, st, rst)
exp = sum(st.expanded_rows for _, st in got)
print(f"[ci] fused pipeline (forced kernels, interpret) == numpy: "
      f"{len(window)} queries byte-identical, {exp} expanded rows")
EOF

echo "== smoke: throttled migration drain on LUBM(1) =="
python - <<'EOF'
import numpy as np
from repro.api import KGService
from repro.graph import lubm

def canon(b):
    return sorted(map(tuple, np.stack(
        [b[k] for k in sorted(b)], axis=1).tolist())) if b else []

ds = lubm.load(1, seed=0)
svc = KGService.from_dataset(ds, n_shards=4, migration_budget=120_000)
svc.bootstrap(ds.base_workload())
window = ds.extended_workload()
# bindings are layout-invariant: the pre-adapt results are the reference
ref = {q.name: canon(b)
       for q, (b, _) in zip(window, svc.query_batch(window))}
report = svc.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
assert report.accepted, "cost-aware guard rejected the smoke round"
sess = svc.session
assert sess is not None and sess.n_chunks >= 3, \
    f"expected a >=3-step drain, got {sess and sess.n_chunks}"
steps = 0
while svc.session is not None:                # query between every chunk
    for q, (b, _) in zip(window, svc.query_batch(window)):
        assert canon(b) == ref[q.name], (q.name, svc.kg.epoch)
    steps += 1
assert steps >= 3, steps
assert np.array_equal(svc.kg.state.feature_to_shard,
                      sess.target.feature_to_shard)
print(f"[ci] throttled migration: {sess.n_chunks} chunks drained over "
      f"{steps} serving windows, {sess.bytes_applied} B, "
      f"final epoch {svc.kg.epoch}")
EOF

echo "== smoke: replicated serving (LUBM(1), replica_budget>0, all executors) =="
python - <<'EOF'
import numpy as np
from repro.api import KGService
from repro.graph import lubm
from repro.query import exec as qexec

def canon(b):
    return sorted(map(tuple, np.stack(
        [b[k] for k in sorted(b)], axis=1).tolist())) if b else []

ds = lubm.load(1, seed=0)
window = ds.extended_workload()

svc0 = KGService.from_dataset(ds, n_shards=4)          # primary-only twin
svc0.bootstrap(ds.base_workload())
svc0.query_batch(window)
rep0 = svc0.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
assert rep0.accepted
bytes0 = sum(st.bytes_shipped for _, st in svc0.query_batch(window))

svc = KGService.from_dataset(ds, n_shards=4, migration_budget=120_000,
                             replica_budget=256_000)
svc.bootstrap(ds.base_workload())
svc.query_batch(window)
report = svc.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
assert report.accepted and report.plan.replica_adds, \
    "replica smoke needs an accepted round with promotions"
while svc.session is not None:                         # drain while serving
    assert not svc.should_adapt()                      # mid-drain guard
    svc.query_batch(window)
kg = svc.kg
assert kg.replicas.has_replicas and kg.replicas == report.replicas
plans = [kg.plan(q) for q in window]
ref = qexec.NumpyExecutor().run_batch(plans, kg)
for name in ("jax", "jax-pallas"):
    got = qexec.get_executor(name).run_batch(plans, kg)
    for q, (rb, rs), (gb, gs) in zip(window, ref, got):
        assert canon(rb) == canon(gb), (q.name, name)
        for f in qexec.ExecStats.COMPARABLE:
            assert getattr(rs, f) == getattr(gs, f), (q.name, name, f)
bytes1 = sum(st.bytes_shipped for st in (s for _, s in ref))
assert bytes1 < bytes0, (bytes1, bytes0)
print(f"[ci] replicated serving: {len(kg.replicas.replicated())} features "
      f"replicated, {bytes1} B shipped/window < {bytes0} B primary-only, "
      f"executors byte-identical")
EOF

echo "== smoke: mixed read/write serving (LUBM(1), writes mid-drain, all executors) =="
python - <<'EOF'
import numpy as np
from repro import write as kgwrite
from repro.api import KGService
from repro.graph import lubm
from repro.query import exec as qexec

def canon(b):
    return sorted(map(tuple, np.stack(
        [b[k] for k in sorted(b)], axis=1).tolist())) if b else []

ds = lubm.load(1, seed=0)
window = ds.extended_workload()
svc = KGService.from_dataset(ds, n_shards=4, migration_budget=120_000,
                             replica_budget=256_000)
svc.bootstrap(ds.base_workload())
svc.query_batch(window)
report = svc.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
assert report.accepted and svc.session is not None
rng = np.random.default_rng(0)
t = ds.store.triples
windows = 0
while svc.session is not None:       # writes land between every chunk
    rows = t[rng.integers(0, len(t), 48)].copy()
    rows[:, 0] = svc.fresh_ids(len(rows)).astype(np.int32)
    rep = svc.insert(rows)
    assert rep.effective and rep.n_inserted == 48
    svc.delete(rows[:16])
    svc.query_batch(window)
    windows += 1
assert windows >= 2 and svc.write_log.n_inserted > svc.write_log.n_deleted
kg = svc.kg
twin = kgwrite.rebuild_from_scratch(kg)
plans = [kg.plan(q) for q in window]
ref = qexec.NumpyExecutor().run_batch(
    [twin.plan(q) for q in window], twin)
for name in ("numpy", "jax", "jax-pallas"):
    got = qexec.get_executor(name).run_batch(plans, kg)
    for q, (rb, rs), (gb, gs) in zip(window, ref, got):
        assert canon(rb) == canon(gb), (q.name, name)
        for f in qexec.ExecStats.COMPARABLE:
            assert getattr(rs, f) == getattr(gs, f), (q.name, name, f)
print(f"[ci] mixed read/write serving: {svc.write_log.n_inserted} inserts/"
      f"{svc.write_log.n_deleted} deletes over {windows} drain windows, "
      f"epoch {kg.epoch}, all executors == rebuild-from-scratch twin")
EOF

echo "== smoke: streaming admission == query_batch (LUBM(1), all executors) =="
python - <<'EOF'
import numpy as np
from repro.api import KGService, WriteBatch
from repro.graph import lubm
from repro.graph.triples import TripleStore

def canon(b):
    return sorted(map(tuple, np.stack(
        [b[k] for k in sorted(b)], axis=1).tolist())) if b else []

ds = lubm.load(1, seed=0)
window = ds.extended_workload()
# each twin gets its own store copy: the write path mutates in place
def build(executor):
    svc = KGService(TripleStore(ds.store.triples.copy(), ds.store.dictionary),
                    4, executor=executor, migration_budget=120_000,
                    type_predicate=ds.dictionary.lookup("rdf:type"))
    svc.bootstrap(ds.base_workload())
    svc.query_batch(window)
    report = svc.adapt(ds.workload([f"EQ{i}" for i in range(1, 11)]))
    assert report.accepted and svc.session is not None
    return svc

rng = np.random.default_rng(0)
t = ds.store.triples
batches = []                         # identical writes for every replay
for w in range(3):
    rows = t[rng.integers(0, len(t), 32)].copy()
    rows[:, 0] = (1 << 22) + np.arange(w * 32, (w + 1) * 32, dtype=np.int32)
    batches.append(rows)

per_exec = {}
for name in ("numpy", "jax", "jax-pallas"):
    # synchronous baseline: write, then one query_batch per admission window
    svc = build(name)
    sync = []
    for rows in batches:
        svc.write(WriteBatch(inserts=rows.copy()))
        sync += [canon(b) for b, _ in svc.query_batch(window)]
    # streamed replay of the same admission order, migration in flight
    svc = build(name)
    stream = svc.stream(pipeline=True, max_window=len(window))
    at = 0.0
    for rows in batches:
        stream.submit_write(WriteBatch(inserts=rows.copy()), at=at)
        for q in window:
            stream.submit(q, at=at)
        at += 0.25
    stream.run_until_idle()
    got = [canon(r.bindings) for r in stream.poll()]
    assert got == sync, f"stream != query_batch under executor {name}"
    assert svc.session is None and svc.write_log.n_inserted == 96
    per_exec[name] = got
    s = stream.stats()
    assert s["latency"]["n"] == len(window) * 3
    assert s["latency"]["p50"] <= s["latency"]["p95"] <= s["latency"]["p99"]
    print(f"[ci] streaming executor={name}: {len(got)} queries over "
          f"{stream.n_windows} windows byte-identical to query_batch, "
          f"p95={s['latency']['p95'] * 1e3:.2f} ms")
assert per_exec["numpy"] == per_exec["jax"] == per_exec["jax-pallas"], \
    "executor backends disagree on streamed results"
EOF

echo "== smoke: drift scenario replay (WatDiv flash crowd, adaptive vs frozen) =="
python - <<'EOF'
from repro import scenario as drift
from repro.api import AWAPartitioner, KGService
from repro.graph import watdiv

ds = watdiv.load(1, seed=0)
scn = drift.flash_crowd(ds, warm=2, spike=2, cool=1,
                        queries_per_window=6, seed=3)

def build(executor):
    svc = KGService.from_dataset(ds, n_shards=4,
                                 partitioner=AWAPartitioner(),
                                 executor=executor,
                                 migration_budget=1 << 20,
                                 replica_budget=1 << 20)
    svc.bootstrap(scn.bootstrap_workload(ds))
    return svc

reports = {}
for mode, adapt in (("adaptive", True), ("frozen", False)):
    per_exec = {}
    for name in ("numpy", "jax", "jax-pallas"):
        rep = drift.run_scenario(build(name), scn, ds, adapt=adapt,
                                 mode=f"awapart/{mode}", warmup_phases=1)
        # modeled costs derive from ExecStats, pinned identical across
        # executors — the whole telemetry series must match exactly
        per_exec[name] = [(w.window_ms, w.stall_bytes, w.epoch, w.adapted)
                          for w in rep.windows]
    assert per_exec["numpy"] == per_exec["jax"] == per_exec["jax-pallas"], \
        f"executors disagree on the {mode} replay"
    reports[mode] = rep

spike = next(i for i, w in enumerate(reports["adaptive"].windows) if w.onset)
assert any(w.adapted for w in reports["adaptive"].windows[spike:]), \
    "adaptive arm never reacted to the flash crowd"
assert not any(w.adapted for w in reports["frozen"].windows[2:]), \
    "frozen arm adapted after its warm-up phase"
a, f = reports["adaptive"].summary(), reports["frozen"].summary()
assert a["recovered"] >= f["recovered"]
print(f"[ci] drift smoke: {int(a['windows'])} windows, "
      f"adaptive recovered {int(a['recovered'])}/{int(a['onsets'])} "
      f"(frozen {int(f['recovered'])}), executors identical")
EOF

echo "== smoke: benchmarks/bench_drift.py --dry-run =="
python benchmarks/bench_drift.py --dry-run

echo "== smoke: benchmarks/bench_streaming.py --dry-run =="
python benchmarks/bench_streaming.py --dry-run

echo "== smoke: benchmarks/bench_writes.py --dry-run =="
python benchmarks/bench_writes.py --dry-run

echo "== smoke: benchmarks/bench_replication.py --dry-run =="
python benchmarks/bench_replication.py --dry-run

echo "== smoke: benchmarks/bench_migration.py --dry-run =="
python benchmarks/bench_migration.py --dry-run

echo "== smoke: benchmarks/bench_kernels.py --dry-run (join kernel) =="
python benchmarks/bench_kernels.py --dry-run

echo "== smoke: traced serve run (--trace/--metrics-csv, schema-validated) =="
python -m repro.launch.serve --universities 1 --shards 4 --experiment 1 \
    --migration-budget 120000 --trace /tmp/ci_trace.json \
    --metrics-csv /tmp/ci_metrics.csv
python - <<'EOF'
import json

raw = json.load(open("/tmp/ci_trace.json"))
events = raw["traceEvents"]
assert events and raw.get("displayTimeUnit") == "ms"
for ev in events:                 # Chrome trace-event schema (Perfetto)
    assert ev["ph"] in ("X", "M"), ev
    assert {"name", "ph", "pid", "tid"} <= set(ev), ev
    if ev["ph"] == "X":
        assert ev["dur"] >= 0 and ev["ts"] >= 0, ev
names = [ev["name"] for ev in events if ev["ph"] == "X"]
for needed in ("repro.adapt.round", "repro.migrate.chunk",
               "repro.serve.window", "repro.serve.plan", "repro.exec.query",
               "repro.exec.scan", "repro.exec.join",
               "repro.exec.federation"):
    assert needed in names, f"missing {needed} spans in the trace"
assert all({"seq", "parent", "req"} <= set(ev["args"])
           for ev in events if ev["ph"] == "X"), "span without its parent"
n_rounds = names.count("repro.adapt.round")
assert n_rounds >= 1, "no adaptation-round span recorded"
print(f"[ci] trace schema ok: {len(events)} events, {n_rounds} adaptation "
      f"round(s), {names.count('repro.migrate.chunk')} migration chunks, "
      f"{names.count('repro.exec.query')} executed queries")
EOF
python results/make_table.py /tmp/ci_metrics.csv
python results/make_table.py /tmp/ci_metrics.csv --md > /dev/null

echo "== smoke: kernels.autotune --quick (empirical dispatch profile) =="
python -m repro.kernels.autotune --quick --out /tmp/ci_dispatch_profile.json
python - <<'EOF'
from repro.kernels import dispatch
from repro.kernels.autotune import PROBE_CAP, DispatchProfile

prof = DispatchProfile.load("/tmp/ci_dispatch_profile.json")
try:
    prof.install()
    got = dispatch.envelope(PROBE_CAP, 123)
    assert got == prof.envelopes[PROBE_CAP], (got, prof.envelopes)
finally:
    dispatch.clear_profile()
print(f"[ci] autotune profile round-trip: backend={prof.backend} "
      f"envelopes={prof.envelopes}")
EOF

echo "== docs drift guard: run every <!-- ci:run --> fenced snippet =="
python - <<'EOF'
import pathlib
import re
import subprocess
import sys

MARK = "<!-- ci:run -->"
# the fence must immediately follow its marker (whitespace only between),
# so the guard can never wander off and run some unrelated later fence
FENCE = re.compile(r"\s*```python\n(.*?)```", re.DOTALL)
ran = 0
for doc in sorted(pathlib.Path("docs").glob("*.md")):
    text = doc.read_text()
    for pos in (m.end() for m in re.finditer(re.escape(MARK), text)):
        fence = FENCE.match(text, pos)
        assert fence is not None, \
            f"{doc}: {MARK} not followed by a python fence"
        proc = subprocess.run([sys.executable, "-"],
                              input=fence.group(1), text=True)
        if proc.returncode != 0:
            sys.exit(f"[ci] snippet from {doc} FAILED — the doc has "
                     "drifted from the code")
        ran += 1
        print(f"[ci] docs snippet ok: {doc} (#{ran})")
assert ran >= 3, f"expected >=3 marked snippets across docs/, found {ran}"
EOF

echo "== deprecation: no in-repo caller of the shimmed engine entry points =="
# the shims live in src/repro/query/engine.py and are exercised (with
# pytest.warns) only by tests/test_executors.py
hits=$(grep -rnE \
  "engine\.(execute|run_workload|workload_average_time|profile_query|stats_from_profile)\(|from repro\.query\.engine import .*(execute|run_workload|workload_average_time|profile_query|stats_from_profile)" \
  src examples benchmarks tests --include='*.py' \
  | grep -v "src/repro/query/engine.py" \
  | grep -v "tests/test_executors.py" || true)
if [ -n "$hits" ]; then
  echo "deprecated engine entry points still used in-repo:"
  echo "$hits"
  exit 1
fi

echo "CI OK"
