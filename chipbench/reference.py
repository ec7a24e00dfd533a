"""Plain reference of the served semantics, and the comparison that decides
``correct``.

It imports nothing of the program. It takes the generated triples and the
queries as plain ``(s, p, o)`` tuples (a negative slot is a variable), and
prices a query at the layout and primary node the program served it under.
Everything it answers it computes itself:

* scans by boolean masks over the whole triple array (no index);
* bag-semantics joins in the order the plan gave, with the order checked to
  be a permutation of the query's own patterns;
* the federation counts: per executed pattern, how many matches live on each
  shard, with the off-node matches shipped to the query's primary node;
* the layout guarantees: triples that share ``(p, o)`` live on one shard
  (a feature is never split), and a migration chunk moves exactly the rows
  it says it carries.

Bindings are compared as multisets through a 64-bit hash of each row,
sorted; two different multisets compare equal only on a hash collision
(about ``rows**2 / 2**64``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

TRIPLE_BYTES = 12          # one (s, p, o) row of int32 ids on the wire
MAX_PAIRS = 200_000_000    # the reference's own guard against a runaway join

STATS_FIELDS = ("scan_rows_critical", "join_rows", "distributed_joins",
                "rows_shipped", "bytes_shipped", "messages", "rows",
                "cartesian_rows", "expanded_rows")


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser over uint64."""
    x = x.astype(np.uint64)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


def row_hashes(bindings: Dict[int, np.ndarray]) -> Tuple[tuple, np.ndarray]:
    """(sorted variable names, sorted 64-bit hash of every row)."""
    keys = tuple(sorted(bindings))
    if not keys:
        return keys, np.empty(0, np.uint64)
    n = len(bindings[keys[0]])
    h = np.full(n, 0x9E3779B97F4A7C15, np.uint64)
    with np.errstate(over="ignore"):
        for i, k in enumerate(keys):
            col = np.asarray(bindings[k]).astype(np.int64).astype(np.uint64)
            h = _mix(h ^ _mix(col + np.uint64(i + 1)))
    return keys, np.sort(h)


@dataclasses.dataclass
class Evaluation:
    """The reference's answer to one query in one join order."""
    variables: tuple
    hashes: np.ndarray
    rows: int
    join_rows: int
    expanded_rows: int
    cartesian_rows: int
    executed: List[tuple]


class Reference:
    """Answers and prices BGP queries over one triple array."""

    def __init__(self, triples: np.ndarray, n_shards: int):
        self.t = np.asarray(triples)
        self.n_shards = int(n_shards)
        self._match: Dict[tuple, np.ndarray] = {}
        self._eval: Dict[tuple, Evaluation] = {}
        self._fed: Dict[tuple, np.ndarray] = {}
        self._po_order: Optional[np.ndarray] = None
        self._po_starts: Optional[np.ndarray] = None

    # -------------------------------------------------------------- scan
    def match(self, pat: tuple) -> np.ndarray:
        """Row ids of the triples a pattern's constants select."""
        hit = self._match.get(pat)
        if hit is None:
            mask = np.ones(len(self.t), bool)
            for col, slot in enumerate(pat):
                if slot >= 0:
                    mask &= self.t[:, col] == slot
            hit = np.flatnonzero(mask)
            self._match[pat] = hit
        return hit

    def _columns(self, pat: tuple) -> Tuple[Dict[int, np.ndarray], int]:
        idx = self.match(pat)
        rows = self.t[idx]
        keep = np.ones(len(idx), bool)
        first: Dict[int, int] = {}
        for col, slot in enumerate(pat):
            if slot < 0:
                if slot in first:                 # (?x, p, ?x)
                    keep &= rows[:, first[slot]] == rows[:, col]
                else:
                    first[slot] = col
        cols = {v: rows[keep, c].astype(np.int64) for v, c in first.items()}
        return cols, len(idx)

    # -------------------------------------------------------------- join
    @staticmethod
    def _keys(left: List[np.ndarray], right: List[np.ndarray]):
        if len(left) == 1:
            return left[0], right[0]
        if len(left) == 2:
            return (left[0] << 31) + left[1], (right[0] << 31) + right[1]
        both = np.concatenate([np.stack(left, 1), np.stack(right, 1)])
        _, inv = np.unique(both, axis=0, return_inverse=True)
        inv = inv.ravel().astype(np.int64)
        return inv[:len(left[0])], inv[len(left[0]):]

    def evaluate(self, order: Sequence[tuple]) -> Evaluation:
        """Join the patterns in ``order``; stop once the table is empty."""
        order = tuple(tuple(int(s) for s in p) for p in order)
        hit = self._eval.get(order)
        if hit is not None:
            return hit
        table: Optional[Dict[int, np.ndarray]] = None
        join_rows = expanded = cartesian = 0
        executed: List[tuple] = []

        def size(tab):
            return len(next(iter(tab.values()))) if tab else 0

        for pat in order:
            cols, n_match = self._columns(pat)
            executed.append(pat)
            before = size(table)
            if table is None:
                table = cols
            else:
                nl, nr = size(table), size(cols)
                shared = [v for v in cols if v in table]
                if not shared:
                    cartesian += nl * nr
                    li = np.repeat(np.arange(nl), nr)
                    ri = np.tile(np.arange(nr), nl)
                else:
                    lk, rk = self._keys([table[v] for v in shared],
                                        [cols[v] for v in shared])
                    perm = np.argsort(rk, kind="stable")
                    rs = rk[perm]
                    lo = np.searchsorted(rs, lk, "left")
                    counts = np.searchsorted(rs, lk, "right") - lo
                    total = int(counts.sum())
                    if total > MAX_PAIRS:
                        raise MemoryError(f"reference join of {total} pairs")
                    expanded += total
                    li = np.repeat(np.arange(nl), counts)
                    ends = np.cumsum(counts)
                    offs = np.arange(total) - np.repeat(ends - counts, counts)
                    ri = perm[np.repeat(lo, counts) + offs]
                out = {v: c[li] for v, c in table.items()}
                for v, c in cols.items():
                    if v not in out:
                        out[v] = c[ri]
                table = out
            after = size(table)
            join_rows += before + n_match + after
            if after == 0:
                break
        table = table or {}
        n = size(table)
        variables, hashes = row_hashes(table)
        ev = Evaluation(variables, hashes, n, join_rows, expanded, cartesian,
                        executed)
        self._eval[order] = ev
        return ev

    # -------------------------------------------------------- federation
    def shard_counts(self, pat: tuple, layout_id: int,
                     layout: np.ndarray) -> np.ndarray:
        key = (pat, layout_id)
        hit = self._fed.get(key)
        if hit is None:
            hit = np.bincount(layout[self.match(pat)],
                              minlength=self.n_shards)[:self.n_shards]
            self._fed[key] = hit
        return hit

    def stats(self, query_patterns: Sequence[tuple], order: Sequence[tuple],
              ppn: int, layout_id: int, layout: np.ndarray) -> Dict[str, int]:
        """Every counted field of one query served at ``ppn`` over
        ``layout`` (the shard of every triple row)."""
        ev = self.evaluate(order)
        out = dict(scan_rows_critical=0, distributed_joins=0, rows_shipped=0,
                   bytes_shipped=0, messages=0, rows=ev.rows,
                   join_rows=ev.join_rows, cartesian_rows=ev.cartesian_rows,
                   expanded_rows=ev.expanded_rows)
        multi = len(query_patterns) > 1
        for pat in ev.executed:
            per = self.shard_counts(pat, layout_id, layout)
            out["scan_rows_critical"] += int(per.max()) if len(per) else 0
            off = per.copy()
            off[ppn] = 0
            nz = int(np.count_nonzero(off))
            out["messages"] += nz
            out["rows_shipped"] += int(off.sum())
            out["bytes_shipped"] += int(off.sum()) * TRIPLE_BYTES
            if multi:
                out["distributed_joins"] += nz
        return out

    # ------------------------------------------------------------ layout
    def split_features(self, layout: np.ndarray) -> int:
        """How many ``(p, o)`` groups of triples span more than one shard."""
        if self._po_order is None:
            key = (self.t[:, 1].astype(np.int64) << 31) + self.t[:, 2]
            self._po_order = np.argsort(key, kind="stable")
            sk = key[self._po_order]
            self._po_starts = np.flatnonzero(
                np.concatenate([[True], sk[1:] != sk[:-1]]))
        sh = layout[self._po_order]
        lo = np.minimum.reduceat(sh, self._po_starts)
        hi = np.maximum.reduceat(sh, self._po_starts)
        return int(np.count_nonzero(lo != hi))


def same_multiset(order: Sequence[tuple], patterns: Sequence[tuple]) -> bool:
    return sorted(map(tuple, order)) == sorted(map(tuple, patterns))
