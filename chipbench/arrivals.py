"""The one traffic generator: open-loop arrivals from a traffic file.

Independent users send queries, so the loop is open. The window is split
evenly between the traffic's phases; a phase of ``w`` seconds at
``rate_qps`` carries exactly ``round(rate_qps * w)`` requests, in its mix's
exact proportions, their order and their due times, uniform over the
phase and sorted (a Poisson process conditioned on its count), drawn from
``SCHEDULE_SEED``. Every run of a cell offers the same requests at the
same instants; the run's seed changes the data they read (``deploy.py``).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

SCHEDULE_SEED = 0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator for one use of the run's seed."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def quotas(weights: Sequence[float], n: int) -> np.ndarray:
    """Largest-remainder split of ``n`` requests over ``weights``; ties go
    to the earlier entry, so the split does not depend on the seed."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    frac = exact - out
    order = np.lexsort((np.arange(len(w)), -frac))
    out[order[:n - int(out.sum())]] += 1
    return out


def schedule(mixes: Sequence[Sequence[Tuple[str, float]]], rate_qps: float,
             seconds: float) -> Tuple[np.ndarray, List[str]]:
    """(sorted due times in seconds from the window's open, query names)
    over the phases' mixes of (query, weight)."""
    if rate_qps <= 0 or seconds <= 0 or not mixes:
        raise ValueError("rate, window and phases must be positive")
    span = float(seconds) / len(mixes)
    times, names = [], []
    for k, mix in enumerate(mixes):
        n = max(1, int(round(rate_qps * span)))
        pool = np.repeat(np.array([m[0] for m in mix], dtype=object),
                         quotas([m[1] for m in mix], n))
        rng = rng_for(SCHEDULE_SEED, 1 + k)
        names.extend(str(x) for x in pool[rng.permutation(n)])
        times.append(np.sort(rng.uniform(k * span, (k + 1) * span, n)))
    return np.concatenate(times), names
