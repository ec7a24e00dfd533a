"""Build a configuration's deployment from the run's seed.

The data set is the configuration's: its generator at generator seed 0,
whose triple count and checksum the configuration file records. The run's
seed relabels every entity id by a permutation (schema ids, the predicates
and classes, keep theirs) and shuffles the rows. Every join, scan and
shard then has the same sizes under every seed, so one seed's compiled
programs serve all of them, while the values that flow through the joins,
and the order of the rows, change with the seed.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
import json
from typing import Dict

import numpy as np

from chipbench.arrivals import rng_for


def fingerprint(triples: np.ndarray, queries: Dict[str, object]
                ) -> Dict[str, object]:
    """Triple count and checksums of the triples and of every query's
    patterns, as the generator makes them at its own seed."""
    t = np.ascontiguousarray(triples, dtype=np.int32)
    q = json.dumps({n: [list(map(int, p)) for p in queries[n].patterns]
                    for n in sorted(queries)})
    return {"triples": int(t.shape[0]),
            "sha256": hashlib.sha256(t.tobytes()).hexdigest(),
            "queries_sha256": hashlib.sha256(q.encode()).hexdigest()}


def generate(cfg: dict):
    """The configuration's data set as the program's generator makes it."""
    data = cfg["dataset"]
    mod = importlib.import_module(f"repro.graph.{data['generator']}")
    return mod.generate(int(data["scale"]), int(data.get("seed", 0)))


@dataclasses.dataclass
class Deployment:
    cfg: dict
    triples: np.ndarray            # (N, 3) int32, relabelled and shuffled
    queries: Dict[str, object]     # name -> repro Query over the new ids
    dictionary: object
    type_predicate: int
    fingerprint: Dict[str, object]
    store: object = None           # the program's TripleStore, built once

    @property
    def fingerprint_ok(self) -> bool:
        return self.fingerprint == self.cfg.get("fingerprint")


def relabel(ds, seed: int):
    """(triples, queries) of ``ds`` under the seed's permutation of the
    entity ids."""
    t = ds.store.triples
    n_schema = len(ds.dictionary)
    top = int(t.max()) + 1
    rng = rng_for(seed, 0)
    perm = np.arange(top, dtype=np.int64)
    perm[n_schema:] = n_schema + rng.permutation(top - n_schema)
    out = perm[t].astype(np.int32)[rng.permutation(len(t))]

    def slot(x: int) -> int:
        return int(perm[x]) if x >= n_schema else x

    queries = {name: dataclasses.replace(
        q, patterns=tuple(tuple(slot(x) for x in pat) for pat in q.patterns))
        for name, q in ds.queries.items()}
    return out, queries


def build(cfg: dict, seed: int) -> Deployment:
    ds = generate(cfg)
    triples, queries = relabel(ds, seed)
    return Deployment(cfg=cfg, triples=triples, queries=queries,
                      dictionary=ds.dictionary,
                      type_predicate=ds.dictionary.lookup("rdf:type"),
                      fingerprint=fingerprint(ds.store.triples,
                                              ds.queries))


def service(dep: Deployment):
    """A fresh ``KGService`` over the deployment, as the configuration
    states it. It becomes the sink of the kernel-dispatch counters. The
    services of one deployment share one store, a copy of the triples the
    reference reads."""
    from repro.api import AWAPartitioner, KGService
    from repro.core.adaptive import AdaptConfig
    from repro.graph.triples import TripleStore

    cfg = dep.cfg
    if dep.store is None:
        dep.store = TripleStore(dep.triples.copy(), dep.dictionary)
    return KGService(dep.store, int(cfg["shards"]),
                     AWAPartitioner(AdaptConfig(**cfg["adapt_config"])),
                     type_predicate=dep.type_predicate,
                     executor=cfg["executor"],
                     migration_budget=int(cfg["migration_budget"]))
