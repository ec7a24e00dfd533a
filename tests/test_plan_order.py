"""Join order by estimated join size (``query.plan._join_order``) and the
store's per-predicate statistics it reads (``TripleStore.predicate_stats``):
the statistics are exact and follow the store's writes, the cycle
query C3 of the WatDiv-style generator (``graph/watdiv.py``) no longer
joins its two ``nationality`` patterns on their shared country, every
answer equals the answer under the previous greedy order (fewest
matches first, then the connected pattern with the fewest matches), and
the plan invariants hold."""
import numpy as np
import pytest

from conftest import canon_bindings
from repro.api import HashPartitioner, KGService
from repro.core.features import FeatureSpace
from repro.core.partition import hash_partition
from repro.graph import watdiv
from repro.graph.triples import Dictionary, TripleStore
from repro.query import exec as qexec
from repro.query import plan as qplan
from repro.query.engine import ShardedStore
from repro.query.pattern import is_var

EXECUTORS = {"numpy": qexec.NumpyExecutor,
             "jax": qexec.JaxExecutor,
             "jax-pallas": lambda **kw: qexec.JaxExecutor(pallas=True, **kw)}


@pytest.fixture(scope="module")
def watdiv1():
    return watdiv.load(1, seed=0)


@pytest.fixture(scope="module")
def datasets(small_lubm, watdiv1):
    return {"lubm": small_lubm, "watdiv": watdiv1}


def _sharded(ds, n_shards=4):
    space = FeatureSpace(ds.store,
                         type_predicate=ds.dictionary.lookup("rdf:type"))
    space.track_workload(ds.base_workload())
    state = hash_partition(space.feature_sizes(), n_shards, seed=0)
    return ShardedStore(ds.store, space, state)


def _counts(store, patterns):
    return {p: store.count(*(None if is_var(x) else x for x in p))
            for p in patterns}


def _previous_order(patterns, counts, _stats):
    """The greedy order the planner used before estimated join sizes."""
    remaining, bound, order = list(patterns), set(), []
    while remaining:
        connected = [p for p in remaining
                     if any(is_var(s) and s in bound for s in p)]
        pool = connected if connected and bound else remaining
        pick = min(pool, key=lambda p: counts[p])
        order.append(pick)
        remaining.remove(pick)
        bound.update(s for s in pick if is_var(s))
    return order, [0.0] * len(order)


def _previous_plan(q, source, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(qplan, "_join_order", _previous_order)
        return qplan.plan(q, source)


# --------------------------------------------------------------------------- #
# statistics
# --------------------------------------------------------------------------- #

def _random_store(seed):
    rng = np.random.default_rng(seed)
    n = 2_000
    t = np.stack([rng.integers(0, 60, n), rng.integers(100, 108, n),
                  rng.integers(0, 40, n)], 1).astype(np.int32)
    return TripleStore(np.unique(t, axis=0), Dictionary())


def _distinct_by_predicate(t):
    out = {}
    for p in np.unique(t[:, 1]).tolist():
        rows = t[t[:, 1] == p]
        out[p] = (len(np.unique(rows[:, 0])), len(np.unique(rows[:, 2])))
    return out


@pytest.mark.parametrize("source", ["random0", "random1", "lubm", "watdiv"])
def test_predicate_stats_are_exact(source, datasets):
    store = (_random_store(int(source[-1])) if source.startswith("random")
             else datasets[source].store)
    assert store.predicate_stats() == _distinct_by_predicate(store.triples)


@pytest.mark.parametrize("write, rebuilt", [
    ("insert", True), ("delete", True), ("insert_present", False),
    ("none", False)])
def test_stats_rebuilt_after_effective_write_only(small_lubm, write, rebuilt):
    store = TripleStore(small_lubm.store.triples.copy(),
                        small_lubm.dictionary)
    svc = KGService(store, 4, HashPartitioner(), executor="numpy",
                    type_predicate=small_lubm.dictionary.lookup("rdf:type"))
    svc.bootstrap(small_lubm.base_workload())
    builds = svc.metrics.counter("plan.stats_builds")
    svc.query(small_lubm.queries["Q1"])
    assert builds.value == 1
    for name in ("Q2", "Q9"):           # more plans reuse the statistics
        svc.query(small_lubm.queries[name])
    assert builds.value == 1
    first = store.predicate_stats()

    row = store.triples[:1].copy()
    if write == "insert":
        row[0, 0] = svc.fresh_ids(1)[0]
        svc.insert(row)
    elif write == "delete":
        svc.delete(row)
    elif write == "insert_present":
        svc.insert(row)
    assert builds.value == 1            # built lazily, not by the write
    svc.query(small_lubm.queries["Q9"])
    assert builds.value == 1 + rebuilt
    again = store.predicate_stats()
    assert (again is first) == (not rebuilt)
    assert again == _distinct_by_predicate(store.triples)
    # a plan is built (and ordered) again only after an effective write
    assert svc.metrics.counter("span.repro.plan.order.calls").value == \
        3 + rebuilt


# --------------------------------------------------------------------------- #
# order
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("executor", sorted(EXECUTORS))
def test_c3_skips_the_nationality_cross_join(watdiv1, executor, monkeypatch):
    """The generator's C3 is ``X follows Y . Y follows Z . X nationality W
    . Z nationality W``. The two ``nationality`` patterns have the fewest matches, and W
    has a handful of values, so joining them first on W is nearly a
    cross product: the previous order expanded it first. The estimated
    order reaches the second ``nationality`` pattern with X and Z bound,
    and runs under a cap the previous order breaks."""
    q = watdiv1.queries["C3"]
    nat = watdiv1.dictionary.lookup("sorg:nationality")
    W = q.patterns[2][2]
    sharded = _sharded(watdiv1)
    p = qplan.plan(q, sharded)
    nat_ops = [op for op in p.ops if op.pattern[1] == nat]
    assert len(nat_ops) == 2
    assert set(nat_ops[1].join_vars) > {W}

    prof = qexec.profile_from_plan(p, watdiv1.store)
    cap = prof.expanded_rows
    old = _previous_plan(q, sharded, monkeypatch)
    assert [op.pattern[1] for op in old.ops[:2]] == [nat, nat]
    with pytest.raises(qexec.JoinCapExceeded):
        qexec.profile_from_plan(old, watdiv1.store, max_join_rows=cap)

    bindings, stats = EXECUTORS[executor](max_join_rows=cap).run(p, sharded)
    assert stats.expanded_rows == prof.expanded_rows
    assert stats.join_rows == prof.join_rows
    want, _ = qexec.NumpyExecutor().run(old, sharded)
    assert canon_bindings(bindings) == canon_bindings(want)


@pytest.mark.parametrize("executor", sorted(EXECUTORS))
@pytest.mark.parametrize("family", ["lubm", "watdiv"])
def test_bags_equal_previous_order(datasets, family, executor, monkeypatch):
    ds = datasets[family]
    sharded = _sharded(ds)
    run = EXECUTORS[executor]().run
    for name, q in ds.queries.items():
        got, _ = run(qplan.plan(q, sharded), sharded)
        want, _ = qexec.NumpyExecutor().run(
            _previous_plan(q, sharded, monkeypatch), sharded)
        assert canon_bindings(got) == canon_bindings(want), name


@pytest.mark.parametrize("family", ["lubm", "watdiv"])
def test_plan_invariants_hold(datasets, family):
    """For every query: the first op has the fewest matches, each later op
    joins on a bound variable or is marked cartesian (and a cartesian op
    comes only when no connected pattern is left), and the estimated table
    sizes are those ``explain`` shows."""
    ds = datasets[family]
    for name, q in ds.queries.items():
        p = qplan.plan(q, ds.store)
        counts = _counts(ds.store, q.patterns)
        assert p.ops[0].est_rows == min(counts.values()), name
        assert p.ops[0].est_table == p.ops[0].est_rows
        bound = set(p.ops[0].new_vars)
        left = [op.pattern for op in p.ops[1:]]
        for op in p.ops[1:]:
            left.remove(op.pattern)
            assert bool(op.join_vars) != op.cartesian, name
            assert set(op.join_vars) <= bound
            if op.cartesian:
                assert not any(is_var(s) and s in bound
                               for pat in left for s in pat), name
            bound |= set(op.new_vars)
        text = p.explain()
        for op in p.ops:
            assert op.est_table >= 0
            assert f"~{op.est_table} table rows" in text


def test_estimate_follows_distinct_counts():
    """The estimate on a hand-made store: ``a`` links 100 subjects to one
    hub, ``b`` 100 subjects to 100 distinct objects. From the one-row
    pattern, joining ``b`` (its 100 distinct subjects and objects make it
    0.01 rows) beats joining ``a`` on the shared hub (100 rows), though
    both have 100 matches and the previous order took ``a``."""
    hub, a, b, c = 500, 901, 902, 903
    rows = [(s, a, hub) for s in range(100)]
    rows += [(s, b, 200 + s) for s in range(100)]
    rows += [(7, c, hub)]
    store = TripleStore(np.asarray(rows, np.int32), Dictionary())
    X, Y, Z = -1, -2, -3
    pats = ((Y, a, X), (Z, b, X), (Z, c, X))
    counts = _counts(store, pats)
    stats = store.predicate_stats()
    assert stats[a] == (100, 1) and stats[b] == (100, 100)
    order, sizes = qplan._join_order(pats, counts, stats)
    assert order == [(Z, c, X), (Z, b, X), (Y, a, X)]
    assert sizes == pytest.approx([1.0, 0.01, 1.0])
    assert _previous_order(pats, counts, stats)[0][1] == (Y, a, X)
