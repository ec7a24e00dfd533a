"""The comparison that decides ``correct``, and its control.

Each answered request is reduced to a digest: its variables, the sorted
hashes of its rows, and its counted fields (``reference.STATS_FIELDS``).
The reference computes the same digest for the query in the plan's join
order, priced at the layout and primary node of the epoch it was served
at. Every number compared is a count of faults, with the limit 0:

* ``answers_wrong``: requests whose bindings differ from the reference's,
  or whose plan is not a permutation of the query's patterns;
* ``stats_wrong``: requests whose federation or join counts differ;
* ``unanswered``: requests due in the window that errored or never came;
* ``migration_wrong``: chunks that failed or moved another number of rows
  than they carry, and drained sessions whose layout moved another number
  of rows than their plan;
* ``features_split``: served layouts in which triples sharing ``(p, o)``
  live on more than one shard;
* ``round_refused``: rounds whose outcome is not the one the
  configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.reference import (STATS_FIELDS, TRIPLE_BYTES, Reference,
                                 row_hashes, same_multiset)

Digest = Tuple[tuple, np.ndarray, int, Dict[str, int]]


def digest(bindings: dict, stats) -> Digest:
    keys, hashes = row_hashes(bindings)
    rows = len(hashes)
    return keys, hashes, rows, {f: int(getattr(stats, f))
                                for f in STATS_FIELDS}


def _same_rows(got: Digest, ev) -> bool:
    keys, hashes, rows, _ = got
    if ev.rows == 0:
        return rows == 0
    return keys == ev.variables and np.array_equal(hashes, ev.hashes)


def compare(run, ref: Reference, patterns: Dict[str, tuple],
            expect_accepted: bool, digests: List[Optional[Digest]],
            ) -> Dict[str, int]:
    """Fault counts of one window's answers (``digests``, one per request,
    ``None`` where unanswered) against the reference."""
    wrong = stats_wrong = unanswered = 0
    for i, got in enumerate(digests):
        if got is None:
            unanswered += 1
            continue
        name, ep = run.names[i], int(run.epoch[i])
        order, ppn = run.plans[(name, ep)]
        if not same_multiset(order, patterns[name]):
            wrong += 1
            stats_wrong += 1
            continue
        ev = ref.evaluate(order)
        if not _same_rows(got, ev):
            wrong += 1
        want = ref.stats(patterns[name], order, ppn, ep, run.layouts[ep])
        if want != got[3]:
            stats_wrong += 1
    moved_wrong = run.step_errors
    refused = 0
    for r in run.rounds:
        refused += int(r.accepted != expect_accepted)
        for e0, e1, nbytes in r.chunks:
            moved = int(np.count_nonzero(run.layouts[e0] != run.layouts[e1]))
            if moved * TRIPLE_BYTES != nbytes:
                moved_wrong += 1
        if r.drained_s is not None and r.chunks:
            moved = int(np.count_nonzero(run.layouts[r.pre_epoch]
                                         != run.layouts[r.chunks[-1][1]]))
            if moved * TRIPLE_BYTES != r.plan_bytes:
                moved_wrong += 1
    split = sum(ref.split_features(lay) > 0 for lay in run.layouts.values())
    return dict(answers_wrong=wrong, stats_wrong=stats_wrong,
                unanswered=unanswered, migration_wrong=moved_wrong,
                features_split=int(split), round_refused=refused)


def program_digests(run) -> List[Optional[Digest]]:
    """Digests of what the program served (one per distinct answer)."""
    memo: Dict[int, Digest] = {}
    out: List[Optional[Digest]] = []
    for ans in run.answers:
        if ans is None:
            out.append(None)
            continue
        bindings, stats = ans
        key = id(bindings)
        d = memo.get(key)
        if d is None:
            d = digest(bindings, stats)
            memo[key] = d
        # stats objects differ per request (the result cache copies them)
        out.append((d[0], d[1], d[2], {f: int(getattr(stats, f))
                                      for f in STATS_FIELDS}))
    return out


def control_digests(run, ref: Reference,
                    patterns: Dict[str, tuple]) -> List[Optional[Digest]]:
    """The control: the reference in the program's place, with a result
    cache that ignores the layout epoch, so every request is priced at the
    layout before the first round (a stale answer where the configuration
    states an exact one). The bindings are the reference's own."""
    first = run.rounds[0].pre_epoch
    pre = run.layouts[first]
    out: List[Optional[Digest]] = []
    for i, name in enumerate(run.names):
        if np.isnan(run.done_s[i]):
            out.append(None)
            continue
        order, ppn = run.plans[(name, int(run.epoch[i]))]
        ev = ref.evaluate(order)
        st = ref.stats(patterns[name], order, ppn, first, pre)
        out.append((ev.variables, ev.hashes, ev.rows, st))
    return out


LIMITS = dict(answers_wrong=0, stats_wrong=0, unanswered=0,
              migration_wrong=0, features_split=0, round_refused=0)


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)
