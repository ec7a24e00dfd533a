"""Pure-jnp oracle for the hash-join pack/probe/expand/gather kernel family.

This is the same math the executor's pre-Pallas jitted path runs (and the
numpy reference backend, modulo device): packed int64 keys, binary-search
probe against the sorted build side, plain gather. int64 keys require
``jax.enable_x64(True)`` on the caller's side (the ops layer handles
it); two dictionary ids (< 2^31) pack exactly into one int64.
"""
from __future__ import annotations

import jax.numpy as jnp


def pack_keys(cols: jnp.ndarray) -> jnp.ndarray:
    """(N, K) key columns (each value in ``[0, 2^31)``) -> (N,) int64 keys,
    base-2^31 positional packing. Exact for K <= 2."""
    cols = cols.astype(jnp.int64)
    key = cols[:, 0]
    for c in range(1, cols.shape[1]):
        key = key * jnp.int64(1 << 31) + cols[:, c]
    return key


def probe_sorted(build_sorted: jnp.ndarray, probe: jnp.ndarray,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """searchsorted probe: for every probe key, the ``[lo, hi)`` index range
    of equal keys in the ascending ``build_sorted`` array."""
    lo = jnp.searchsorted(build_sorted, probe, side="left")
    hi = jnp.searchsorted(build_sorted, probe, side="right")
    return lo, hi


def expand_pairs(starts: jnp.ndarray, counts: jnp.ndarray,
                 lo: jnp.ndarray, total: int,
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Segmented ragged expansion of ``(lo, counts)`` match runs into flat
    ``(li, pos)`` pair indices — the jnp form of the executor's former
    ``np.repeat``/``np.cumsum`` addressing arithmetic, with the kernel's
    signature.

    ``starts`` (the exclusive cumsum of ``counts``, computed by the caller)
    partitions ``[0, counts.sum())`` into runs; output ``j``'s owner is the
    *last* segment whose start is ``<= j`` (``searchsorted`` right minus one
    — duplicate starts from zero-count segments resolve to the one segment
    that actually owns ``j``). ``total`` is static for jit; indices past
    ``counts.sum()`` resolve to the last segment and must be sliced off by
    the caller."""
    j = jnp.arange(total, dtype=counts.dtype)
    seg = jnp.searchsorted(starts, j, side="right") - 1
    pos = lo[seg] + j - starts[seg]
    return seg, pos


def gather_rows(values: jnp.ndarray, idx: jnp.ndarray, *,
                fill: int = 0) -> jnp.ndarray:
    """Masked gather: ``values[idx]`` with out-of-range indices -> ``fill``."""
    n = values.shape[0]
    safe = jnp.clip(idx, 0, max(n - 1, 0))
    out = values[safe] if n else jnp.zeros_like(idx, dtype=values.dtype)
    return jnp.where((idx >= 0) & (idx < n), out,
                     jnp.asarray(fill, values.dtype))
