"""Each configuration file's data set at generator seed 0 matches the
triple count and the checksums of its triples and queries that it records,
so a changed generator shows as a moved yardstick; and the seed's
relabelling keeps every size."""
import numpy as np
import pytest

from chipbench import deploy, registry


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (registry.HERE / "configs").glob("*.json")))
def test_fingerprint_at_seed_zero(name):
    cfg = registry.config(name)
    ds = deploy.generate(cfg)
    assert deploy.fingerprint(ds.store.triples,
                              ds.queries) == cfg["fingerprint"]


def test_relabelling_keeps_sizes_and_changes_values():
    cfg = dict(registry.config("lubm10-8shard"),
               dataset=dict(generator="lubm", scale=1, seed=0))
    ds = deploy.generate(cfg)
    a, qa = deploy.relabel(ds, 1)
    b, qb = deploy.relabel(ds, 2 ** 31 + 1)
    assert a.shape == b.shape == ds.store.triples.shape
    assert not np.array_equal(a, b)
    # predicates keep their ids; the triple sets are images of the original
    assert np.array_equal(np.sort(a[:, 1]), np.sort(ds.store.triples[:, 1]))
    assert len(np.unique(a, axis=0)) == len(a)
    for name, q in ds.queries.items():
        for pat, pa in zip(q.patterns, qa[name].patterns):
            assert [s < 0 for s in pat] == [s < 0 for s in pa]
            assert pat[1] == pa[1]
