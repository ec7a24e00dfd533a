"""Query profiles as a by-product of serving: the ``JaxExecutor`` hands each
query's matched row ids and join counts to the facade
(``PartitionedKG.note_profile``), and those equal what
``profile_from_plan`` derives with its own host execution — so an
adaptation round prices the same layouts with the same integers, without
executing the served queries again."""
import numpy as np
import pytest

from repro.api import KGService, PartitionedKG, ReplicaMap
from repro.graph.triples import TripleStore
from repro.query import exec as qexec
from repro.query import plan as qplan
from repro.query.pattern import Query, var

EXECUTORS = ("jax", "jax-pallas")


def _counter(svc, name):
    return svc.metrics.snapshot()["counters"].get(name, 0)


def _empty_join(ds):
    """Three patterns whose first join empties the table (no entity is both
    a course and a university), so the executor breaks before the third."""
    d = ds.dictionary
    t = d.lookup("rdf:type")
    return Query(name="empty_join", patterns=(
        (var(0), t, d.lookup("ub:Course")),
        (var(0), t, d.lookup("ub:University")),
        (var(0), d.lookup("ub:name"), var(1))))


def _replicate(kg, seed=3):
    """Pin read copies of a random tenth of the features onto other shards
    (a facade over the same store with those copies)."""
    rng = np.random.default_rng(seed)
    rmap = ReplicaMap.primary_only(kg.state)
    for f in range(len(kg.state.feature_to_shard)):
        if rng.random() < 0.1:
            rmap.add(f, int(rng.integers(kg.n_shards)))
    assert rmap.has_replicas
    return PartitionedKG(kg.store, kg.space, kg.state, kg.owners,
                         max_join_rows=kg.max_join_rows, replicas=rmap,
                         metrics=kg.metrics)


def _assert_same_profile(got, ref, name):
    assert len(got.pattern_rows) == len(ref.pattern_rows), name
    for a, b in zip(got.pattern_rows, ref.pattern_rows):
        assert np.array_equal(a, b), name
    for f in ("join_rows", "rows", "n_patterns", "cartesian_rows",
              "expanded_rows"):
        assert getattr(got, f) == getattr(ref, f), (name, f)


@pytest.mark.parametrize("replicated", [False, True],
                         ids=["primary", "replicated"])
@pytest.mark.parametrize("executor", EXECUTORS)
def test_noted_profiles_equal_profile_from_plan(small_lubm, executor,
                                                replicated):
    svc = KGService.from_dataset(small_lubm, n_shards=4, executor=executor)
    kg = svc.bootstrap(small_lubm.base_workload())
    if replicated:
        kg = svc.kg = _replicate(kg)
    queries = list(small_lubm.queries.values()) + [_empty_join(small_lubm)]
    svc.serve_window(queries)             # one batch: every query a miss
    assert _counter(svc, "cache.profile_noted") == len(queries)

    builds = _counter(svc, "cache.profile_builds")
    shared = {}
    for q in queries:
        prof = kg.profile(q)
        ref = qexec.profile_from_plan(kg.plan(q), kg.store, kg.max_join_rows)
        _assert_same_profile(prof, ref, q.name)
        for op, idx in zip(kg.plan(q).ops, prof.pattern_rows):
            assert not idx.flags.writeable, q.name
            # one array per pattern in a batch, shared, never copied
            assert shared.setdefault(op.pattern, idx) is idx, q.name
    assert _counter(svc, "cache.profile_builds") == builds == 0
    assert _counter(svc, "cache.profile_hits") == len(queries)

    empty = kg.profile(_empty_join(small_lubm))
    assert empty.rows == 0 and len(empty.pattern_rows) == 2 < 3


def test_numpy_executor_notes_nothing(small_lubm):
    svc = KGService.from_dataset(small_lubm, n_shards=4, executor="numpy")
    kg = svc.bootstrap(small_lubm.base_workload())
    svc.serve_window(small_lubm.extended_workload())
    assert _counter(svc, "cache.profile_noted") == 0
    q = small_lubm.queries["Q9"]
    kg.profile(q)
    assert _counter(svc, "cache.profile_builds") == 1
    kg.profile(q)
    assert _counter(svc, "cache.profile_hits") == 1


@pytest.mark.parametrize("executor", EXECUTORS)
def test_failed_or_foreign_runs_note_nothing(small_lubm, executor):
    """A query whose execution raised, a plan the facade does not serve,
    and an executor whose cap the profiler's would refuse leave no
    profile behind."""
    svc = KGService.from_dataset(small_lubm, n_shards=4, executor=executor)
    kg = svc.bootstrap(small_lubm.base_workload())
    q = small_lubm.queries["Q9"]                 # a three-way join
    tight = qexec.get_executor(executor)
    tight.max_join_rows = 0                      # refuses any join
    with pytest.raises(qexec.JoinCapExceeded):
        tight.run(kg.plan(q), kg)
    loose = qexec.get_executor(executor)
    loose.run(qplan.plan(q, kg), kg)             # not the facade's plan
    loose.max_join_rows = kg.max_join_rows + 1
    loose.run(kg.plan(q), kg)                    # looser than the profiler
    assert _counter(svc, "cache.profile_noted") == 0
    kg.profile(q)
    assert _counter(svc, "cache.profile_builds") == 1


def _round_pair(ds, executor):
    """The served service and a twin on the numpy executor, whose profiles
    all come from ``profile_from_plan``; both see the same requests. Each
    holds a copy of the store, since writes mutate it in place."""
    return [KGService(TripleStore(ds.store.triples.copy(), ds.dictionary), 4,
                      type_predicate=ds.dictionary.lookup("rdf:type"),
                      executor=ex)
            for ex in (executor, "numpy")]


def _assert_same_round(a, b):
    assert a.accepted == b.accepted and a.reason == b.reason
    for f in ("t_base", "t_new", "chosen_cut"):
        assert getattr(a, f) == getattr(b, f), f
    assert a.plan.moves == b.plan.moves and a.plan.bytes == b.plan.bytes


@pytest.mark.parametrize("executor", EXECUTORS)
def test_round_prices_noted_profiles_like_built_ones(small_lubm, executor):
    ds = small_lubm
    d = ds.dictionary
    served, twin = _round_pair(ds, executor)
    for svc in (served, twin):
        svc.bootstrap(ds.base_workload())
        svc.serve_window(ds.extended_workload())
    builds = _counter(served, "cache.profile_builds")
    eq = ds.workload([f"EQ{i}" for i in range(1, 11)])
    a, b = served.adapt(eq), twin.adapt(eq)
    assert _counter(served, "cache.profile_builds") == builds == 0
    assert _counter(twin, "cache.profile_builds") > 0
    assert _counter(served, "cache.profile_hits") > 0
    _assert_same_round(a, b)

    # an effective write: nothing profiled before it is served after it,
    # whether the executor noted it or profile_from_plan built it
    kg = served.kg
    noted = {q.name: kg.profile(q) for q in ds.base_workload()}
    built = kg.profile(_empty_join(ds))
    assert _counter(served, "cache.profile_builds") == 1
    tp, take = d.lookup("rdf:type"), d.lookup("ub:takesCourse")
    for svc in (served, twin):
        s = int(svc.fresh_ids(1)[0])
        rep = svc.insert([[s, tp, d.lookup("ub:GraduateStudent")],
                          [s, take, ds.named.grad_course0]])
        assert rep.effective
    assert kg.profile(_empty_join(ds)) is not built
    for q in ds.base_workload():
        prof = kg.profile(q)
        assert prof is not noted[q.name]
        _assert_same_profile(prof, qexec.profile_from_plan(
            kg.plan(q), kg.store, kg.max_join_rows), q.name)
    assert kg.profile(ds.queries["Q1"]).rows == noted["Q1"].rows + 1

    # served again after the write, the executor notes fresh profiles, so
    # the next round builds none either
    for svc in (served, twin):
        svc.serve_window(ds.extended_workload())
    builds = _counter(served, "cache.profile_builds")
    base = ds.base_workload()
    _assert_same_round(served.adapt(base), twin.adapt(base))
    assert _counter(served, "cache.profile_builds") == builds
