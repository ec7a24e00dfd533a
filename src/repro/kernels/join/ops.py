"""Public ops: hash-join pack/probe/gather with kernel/oracle dispatch.

Three layers, same math (see ``docs/kernels.md`` for the idiom):

* :mod:`repro.kernels.join.kernel` — Pallas kernels, int64 keys split into
  32-bit word pairs (TPU has no int64). Compiled on TPU, ``interpret=True``
  on CPU.
* :mod:`repro.kernels.join.ref` — the jnp oracle (int64 under
  ``enable_x64``). Jitted with power-of-two shape buckets, this *is* the
  ``JaxExecutor``'s original jitted probe path — the baseline the Pallas
  kernels are benchmarked against.
* this module — the dispatch seam the executor calls. The join sits on the
  per-query serving hot path, so the auto policy is ``hot_path=True``
  (``repro.kernels.dispatch``) plus two scaling guards (the quadratic
  probe-work and expand-work caps below): compiled
  kernels on TPU for large-enough in-envelope problems, the jitted oracle
  for the rest of the device cases, and plain host numpy
  (:func:`hash_probe_numpy`) when there is no device at all;
  ``use_kernel=True`` forces the kernel (interpret mode on CPU — how the
  equivalence tests pin bit-equality), ``use_kernel=False`` forces the
  oracle.

:func:`hash_probe` is the staged composite: pack both sides, stable-sort
the build side **on the host** (XLA's CPU sort is comparator-based and
loses badly to ``np.argsort``; on TPU the sort is the one stage left on
the host by design), probe every packed key. Returns ``(order, lo,
counts)`` exactly like the numpy reference's searchsorted probe, so the
executors' ragged pair expansion is backend-agnostic.

:func:`expand_pairs` is the segmented ragged expansion that used to live
as host ``np.repeat``/``np.cumsum`` arithmetic inside the executor: ``(lo,
counts)`` match runs -> flat ``(li, pos)`` pair indices, same three tiers.

:func:`hash_join_pipeline` fuses the whole probe→expand→gather chain:
packed keys, ``lo/counts``, expanded positions, and the gathered
permutation rows stay device-resident between stages — the host sees the
build sort key mid-pipeline (the sort stays on the host by design), the
expansion-total scalar (a data-dependent output size must be known to
allocate), and ONE final ``(li, ri)`` materialization, instead of a full
host round trip after every stage. :func:`track_transfers` counts the
boundary crossings so benchmarks can report them per path.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Sequence, Tuple

import jax
import numpy as np

from repro.kernels import dispatch
from repro.kernels.join import kernel, ref
from repro.obs import span

_INT64_MAX = np.iinfo(np.int64).max
_oracle_cache: dict = {}

# Auto-dispatch scalability guards (forced use_kernel=True bypasses all —
# that's how tests pin the kernels at any shape). Resolved per call through
# dispatch.envelope (env var > recorded autotune profile > default), so env
# overrides and loaded profiles work after import:
#
# * the count-probe kernel does O(nl * nr) word-pair compares — a win over
#   binary search only while the compare budget is small; past the cap the
#   log-depth oracle is asymptotically faster even with its device hops.
# * the expand kernel broadcast-tests O(total * n_segments) ownership
#   pairs (the expansion-total threshold): past the cap the log-depth
#   searchsorted oracle wins, exactly like the probe.

def _probe_work_cap() -> int:
    return dispatch.envelope("REPRO_JOIN_PROBE_WORK_CAP", 1 << 32)


def _expand_work_cap() -> int:
    return dispatch.envelope("REPRO_JOIN_EXPAND_WORK_CAP", 1 << 32)


class ExpansionCapExceeded(RuntimeError):
    """A ragged pair expansion would materialize more rows than the
    caller's ``max_total`` cap (the executor maps this onto its
    ``JoinCapExceeded``, mirroring the cartesian-product cap)."""


# --------------------------------------------------------------------------- #
# host-transfer accounting
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class TransferStats:
    """Host<->device array crossings noted by the ops in this module while a
    :func:`track_transfers` scope is active. Counts are structural (one per
    array materialized across the boundary, scalars included) — the honest,
    platform-independent currency of the fused pipeline's claim, measurable
    even on a CPU container where 'device' is the XLA host backend."""
    h2d: int = 0
    d2h: int = 0

    @property
    def total(self) -> int:
        return self.h2d + self.d2h


_transfer_scopes: List[TransferStats] = []


@contextlib.contextmanager
def track_transfers():
    """Count host<->device crossings performed by ops in this scope."""
    ts = TransferStats()
    _transfer_scopes.append(ts)
    try:
        yield ts
    finally:
        _transfer_scopes.remove(ts)


def _note(h2d: int = 0, d2h: int = 0) -> None:
    for ts in _transfer_scopes:
        ts.h2d += h2d
        ts.d2h += d2h


def _pad_pow2(a: np.ndarray, fill=0, min_size: int = 16) -> np.ndarray:
    """Pad axis 0 to the next power of two (stable jit shape buckets)."""
    n = a.shape[0]
    m = max(min_size, 1 << max(n - 1, 0).bit_length())
    if m == n:
        return a
    out = np.full((m,) + a.shape[1:], fill, a.dtype)
    out[:n] = a
    return out


def _pow2_len(n: int, min_size: int = 16) -> int:
    return max(min_size, 1 << max(n - 1, 0).bit_length())


def _oracle_fns():
    """Jitted oracle pack/search, shared by every join of every batch."""
    import jax

    if not _oracle_cache:
        _oracle_cache.update(pack=jax.jit(ref.pack_keys),
                             search=jax.jit(ref.probe_sorted))
    return _oracle_cache["pack"], _oracle_cache["search"]


_pipe_cache: dict = {}


def _pipe_fns():
    """Jitted device helpers for the fused pipeline (and the oracle tiers
    of the granular expand op) — tiny glue ops that keep intermediates on
    the device between kernel stages instead of punting to host numpy.
    Each is a named function, so its executable is named after its stage
    on a profile (``jit_sort_take``, ``jit_pair_gather``, ...)."""
    import functools

    import jax
    import jax.numpy as jnp

    if not _pipe_cache:
        @functools.partial(jax.jit, static_argnames=("n", "fill"))
        def pad_to(a, *, n, fill):
            if n <= a.shape[0]:
                return a
            return jnp.concatenate(
                [a, jnp.full((n - a.shape[0],), fill, a.dtype)])

        def sort_take(a, order):
            """The build side put in sort order."""
            return a[order]

        def pair_gather(order, pos):
            """Expanded pair positions to build-side row ids."""
            return order[pos]

        def run_counts(hi, lo):
            return hi - lo

        def clamp_runs(x, n):
            return jnp.minimum(x, n)

        def count_total(c):
            return jnp.sum(c.astype(jnp.int64))

        def run_starts(c):
            # a device scan: about a minute to compile for the TPU at ~1M
            # rows, so the kernel pipeline takes its prefix sums from the
            # host
            return jnp.cumsum(c) - c

        def join_words(hi, lo):
            return ((hi.astype(jnp.int64) << 32)
                    | lo.astype(jnp.uint32).astype(jnp.int64))

        _pipe_cache.update(
            {f.__name__: jax.jit(f) for f in (
                sort_take, pair_gather, run_counts, clamp_runs, count_total,
                run_starts, join_words)},
            expand=jax.jit(ref.expand_pairs, static_argnames=("total",)),
            gather=jax.jit(ref.gather_rows, static_argnames=("fill",)),
            pad_to=pad_to,
        )
    return _pipe_cache


def _split_words(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Nonnegative int64 keys < 2^62 -> (hi int32, lo uint32) word pair.

    The bound is the K<=2 base-2^31 packing envelope and what keeps the
    probe kernel's +inf padding sentinel (hi = 2^31-1) strictly above every
    real key; a key at or past 2^62 would compare equal to padding and
    inflate the hi counts past the build length."""
    if keys.size and (keys >> 62).any():
        raise ValueError("word-pair kernels require nonnegative packed keys "
                         "< 2^62 (the K<=2 base-2^31 packing envelope)")
    return ((keys >> 32).astype(np.int32),
            (keys & np.int64(0xFFFFFFFF)).astype(np.uint32))


def _join_words(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (hi.astype(np.int64) << 32) | lo.astype(np.int64)


# --------------------------------------------------------------------------- #
# granular ops (bench / tests / docs surface)
# --------------------------------------------------------------------------- #

def pack_keys(cols: np.ndarray, *, use_kernel: bool | None = None,
              interpret: bool | None = None) -> np.ndarray:
    """(N, K<=2) key columns (values < 2^31) -> (N,) packed int64 keys."""
    cols = np.asarray(cols)
    auto = use_kernel is None
    use_kernel, interpret = dispatch.resolve(use_kernel, interpret,
                                             cols.shape[0], hot_path=True)
    if not use_kernel:
        dispatch.note_tier("join.pack_keys", "oracle",
                           "auto" if auto else "forced_off")
        with jax.enable_x64(True):
            pack, _ = _oracle_fns()
            _note(h2d=1, d2h=1)
            return np.asarray(pack(cols.astype(np.int64)))
    dispatch.note_tier("join.pack_keys", "pallas",
                       "auto" if auto else "forced")
    hi, lo = kernel.pack_keys_pallas(cols.astype(np.int32),
                                     interpret=interpret)
    _note(h2d=1, d2h=2)
    return _join_words(np.asarray(hi), np.asarray(lo))


def probe_sorted(build_sorted: np.ndarray, probe: np.ndarray, *,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """searchsorted left/right of nonnegative int64 ``probe`` keys over the
    ascending ``build_sorted`` keys; returns ``(lo, hi)`` index arrays."""
    build_sorted = np.asarray(build_sorted, np.int64)
    probe = np.asarray(probe, np.int64)
    size = max(build_sorted.shape[0], probe.shape[0])
    auto = use_kernel is None
    use_kernel, interpret = dispatch.resolve(use_kernel, interpret, size,
                                             hot_path=True)
    capped = (use_kernel and auto
              and build_sorted.shape[0] * probe.shape[0] > _probe_work_cap())
    if capped:
        use_kernel = False             # quadratic compare budget exceeded
    if not use_kernel:
        dispatch.note_tier("join.probe_sorted", "oracle",
                           "work_cap" if capped
                           else "auto" if auto else "forced_off")
        with jax.enable_x64(True):
            _, search = _oracle_fns()
            _note(h2d=2, d2h=2)
            lo, hi = search(build_sorted, probe)
            return np.asarray(lo), np.asarray(hi)
    dispatch.note_tier("join.probe_sorted", "pallas",
                       "auto" if auto else "forced")
    bh, bl = _split_words(build_sorted)
    ph, pl_ = _split_words(probe)
    lo, hi = kernel.probe_sorted_pallas(bh, bl, ph, pl_, interpret=interpret)
    _note(h2d=4, d2h=2)
    return np.asarray(lo, np.int64), np.asarray(hi, np.int64)


def gather_rows(values: np.ndarray, idx: np.ndarray, *, fill: int = 0,
                on_device: bool | None = None,
                assume_inbounds: bool = False) -> np.ndarray:
    """Masked gather ``values[idx]`` (out-of-range -> ``fill``). Two tiers
    and no Pallas kernel: Mosaic lowers only 2-D gathers, so a gather over
    a (1, N) lane row cannot compile for the TPU, and XLA's device gather
    already does the job. ``on_device=None`` (auto) runs the device gather
    on TPU and host numpy elsewhere; ``True``/``False`` pin a tier. The
    device tier runs under x64, so int64 tables gather exactly.

    ``assume_inbounds=True`` lets a caller that guarantees valid indices
    (the executor's expansion positions are constructed in range) skip the
    host tier's masking passes; the device tier masks either way (the mask
    is inert for valid indices)."""
    values = np.asarray(values)
    idx = np.asarray(idx)
    auto = on_device is None
    if auto:
        on_device = dispatch.on_tpu()
    if not on_device:
        dispatch.note_tier("join.gather_rows", "host",
                           "cpu_auto" if auto else "forced_off")
        if assume_inbounds:
            return values[idx]
        valid = (idx >= 0) & (idx < len(values))
        out = np.full(idx.shape, fill,
                      values.dtype if len(values) else np.int32)
        if len(values):
            out[valid] = values[np.clip(idx, 0, len(values) - 1)][valid]
        return out
    dispatch.note_tier("join.gather_rows", "xla",
                       "auto" if auto else "forced")
    with jax.enable_x64(True):
        _note(h2d=2, d2h=1)
        return np.asarray(_pipe_fns()["gather"](values, idx, fill=fill))


# --------------------------------------------------------------------------- #
# the executor's composite probe
# --------------------------------------------------------------------------- #

def hash_probe_numpy(lcs: Sequence[np.ndarray], rcs: Sequence[np.ndarray],
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The host probe: the same base-2^31 pack + stable sort + searchsorted
    with no device round trip. This is what auto dispatch serves on CPU —
    per-join jnp dispatches lose to host numpy there (measured ~1.8x on the
    LUBM(3) window), so the device tiers engage only on TPU or when
    forced."""
    lk = _pack_np(lcs)
    rk = _pack_np(rcs)
    order = np.argsort(rk, kind="stable")
    rk_sorted = rk[order]
    lo = np.searchsorted(rk_sorted, lk, side="left")
    hi = np.searchsorted(rk_sorted, lk, side="right")
    return order, lo, hi - lo


def _pack_np(cols: Sequence[np.ndarray]) -> np.ndarray:
    key = cols[0]
    for c in cols[1:]:
        key = key * np.int64(1 << 31) + c
    return key


def hash_probe_oracle(lcs: Sequence[np.ndarray], rcs: Sequence[np.ndarray],
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The jitted-jnp probe (the pre-Pallas ``JaxExecutor`` hot path):
    pow2-padded pack + searchsorted under ``enable_x64``, host build sort.
    Padding keys are int64-max so they never binary-search below a real
    key; results are clamped back to the true build size."""
    nl, nr = len(lcs[0]), len(rcs[0])
    with jax.enable_x64(True):
        pack, search = _oracle_fns()
        _note(h2d=2, d2h=2)
        lk = np.asarray(pack(_pad_pow2(np.stack(lcs, axis=1))))[:nl]
        rk = np.asarray(pack(_pad_pow2(np.stack(rcs, axis=1))))[:nr]
        order = np.argsort(rk, kind="stable")
        _note(h2d=2, d2h=2)
        lo_j, hi_j = search(_pad_pow2(rk[order], fill=_INT64_MAX),
                            _pad_pow2(lk, fill=_INT64_MAX))
    lo = np.minimum(np.asarray(lo_j)[:nl], nr)
    hi = np.minimum(np.asarray(hi_j)[:nl], nr)
    return order, lo, hi - lo


def hash_probe(lcs: Sequence[np.ndarray], rcs: Sequence[np.ndarray], *,
               use_kernel: bool | None = None,
               interpret: bool | None = None,
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Full hash-probe of probe-side key columns ``lcs`` against build-side
    ``rcs`` (each a list of <= 2 int columns with values < 2^31). Returns
    ``(order, lo, counts)``: the build side's stable sort permutation and,
    per probe row, the start/length of its match run in that order."""
    assert len(lcs) <= 2 and len(rcs) <= 2, "reduce key columns first"
    nl, nr = len(lcs[0]), len(rcs[0])
    auto = use_kernel is None
    use_kernel, interpret = dispatch.resolve(use_kernel, interpret,
                                             max(nl, nr), hot_path=True)
    capped = use_kernel and auto and nl * nr > _probe_work_cap()
    if capped:
        use_kernel = False             # quadratic compare budget exceeded
    if not use_kernel:
        # three tiers: auto on CPU stays on the host (no device round trip);
        # the jnp oracle runs when explicitly forced (use_kernel=False) or
        # when a TPU is present but the problem is under the size floor
        if auto and not capped and not dispatch.on_tpu():
            dispatch.note_tier("join.hash_probe", "host", "cpu_auto")
            return hash_probe_numpy(lcs, rcs)
        dispatch.note_tier("join.hash_probe", "oracle",
                           "work_cap" if capped
                           else "below_floor" if auto else "forced_off")
        return hash_probe_oracle(lcs, rcs)
    dispatch.note_tier("join.hash_probe", "pallas",
                       "auto" if auto else "forced")
    lh, ll = kernel.pack_keys_pallas(
        np.stack(lcs, axis=1).astype(np.int32), interpret=interpret)
    rh, rl = kernel.pack_keys_pallas(
        np.stack(rcs, axis=1).astype(np.int32), interpret=interpret)
    _note(h2d=2, d2h=4)
    lh, ll = np.asarray(lh), np.asarray(ll)
    rh, rl = np.asarray(rh), np.asarray(rl)
    # stable build-side sort on the host, by the recombined int64 key
    order = np.argsort(_join_words(rh, rl), kind="stable")
    lo, hi = kernel.probe_sorted_pallas(rh[order], rl[order], lh, ll,
                                        interpret=interpret)
    _note(h2d=4, d2h=2)
    lo = np.asarray(lo, np.int64)
    return order, lo, np.asarray(hi, np.int64) - lo


# --------------------------------------------------------------------------- #
# segmented ragged expansion
# --------------------------------------------------------------------------- #

def expand_pairs_numpy(lo: np.ndarray, counts: np.ndarray,
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """The host expansion — the executor's original addressing arithmetic:
    ``li`` repeats each segment id ``counts[i]`` times; ``pos`` walks
    ``lo[i], lo[i]+1, ...`` within each run."""
    lo = np.asarray(lo, np.int64)
    counts = np.asarray(counts, np.int64)
    n = counts.shape[0]
    total = int(counts.sum())
    li = np.repeat(np.arange(n, dtype=np.int64), counts)
    starts = np.cumsum(counts) - counts
    offs = np.arange(total, dtype=np.int64) - np.repeat(starts, counts)
    pos = np.repeat(lo, counts) + offs
    return li, pos


def _expand_pairs_oracle(lo: np.ndarray, counts: np.ndarray, total: int,
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """The jitted searchsorted expansion, pow2-padded for stable jit
    buckets. Zero-fill padding segments own no output index, and padded
    output indices past ``total`` resolve to the last padding segment —
    both sliced off on the way out."""
    starts = np.cumsum(counts) - counts
    with jax.enable_x64(True):
        fns = _pipe_fns()
        _note(h2d=3, d2h=2)
        li, pos = fns["expand"](_pad_pow2(starts, fill=total),
                                _pad_pow2(counts), _pad_pow2(lo),
                                total=_pow2_len(total))
        return (np.asarray(li)[:total].astype(np.int64),
                np.asarray(pos)[:total].astype(np.int64))


def expand_pairs(lo: np.ndarray, counts: np.ndarray, *,
                 use_kernel: bool | None = None,
                 interpret: bool | None = None,
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Segmented ragged expansion of per-probe-row ``(lo, counts)`` match
    runs into flat ``(li, pos)`` pair indices (``li[j]`` = probe row owning
    output ``j``; ``pos[j]`` = its match's position in the build sort
    order). Same three tiers as the probe; the kernel's ownership test is
    O(total * n_segments), so auto dispatch falls back to the log-depth
    searchsorted oracle past the expand work cap."""
    lo = np.asarray(lo, np.int64)
    counts = np.asarray(counts, np.int64)
    n = counts.shape[0]
    total = int(counts.sum())
    auto = use_kernel is None
    use_kernel, interpret = dispatch.resolve(use_kernel, interpret,
                                             max(total, n), hot_path=True)
    reason = ""
    if use_kernel and auto and total * max(n, 1) > _expand_work_cap():
        use_kernel = False             # ownership-test budget exceeded
        reason = "work_cap"
    if use_kernel:
        # the kernel carries runs as int32; out-of-envelope runs would
        # silently truncate, so auto falls back and forced raises.
        in_envelope = (total < 1 << 31 and n < 1 << 31
                       and (n == 0 or (int((lo + counts).max()) <= 1 << 31
                                       and int(lo.min()) >= 0)))
        if not in_envelope:
            if not auto:
                raise ValueError("expand kernel requires int32-range runs")
            use_kernel = False
            reason = "int32_envelope"
    if not use_kernel:
        if auto and not reason and not dispatch.on_tpu():
            dispatch.note_tier("join.expand_pairs", "host", "cpu_auto")
            return expand_pairs_numpy(lo, counts)
        dispatch.note_tier("join.expand_pairs", "oracle",
                           reason or ("below_floor" if auto
                                      else "forced_off"))
        return _expand_pairs_oracle(lo, counts, total)
    dispatch.note_tier("join.expand_pairs", "pallas",
                       "auto" if auto else "forced")
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    starts = np.cumsum(counts) - counts
    _note(h2d=3, d2h=2)
    li, pos = kernel.expand_pairs_pallas(
        _pad_pow2(starts.astype(np.int32)), _pad_pow2(counts.astype(np.int32)),
        _pad_pow2(lo.astype(np.int32)), total=_pow2_len(total),
        interpret=interpret)
    return (np.asarray(li)[:total].astype(np.int64),
            np.asarray(pos)[:total].astype(np.int64))


def expand_segment_ids(counts: np.ndarray, *, use_kernel: bool | None = None,
                       interpret: bool | None = None) -> np.ndarray:
    """``np.repeat(np.arange(len(counts)), counts)`` through the same
    dispatch seam — the segment-id half of the expansion, used by the
    executor's federation bincount build."""
    counts = np.asarray(counts, np.int64)
    li, _ = expand_pairs(np.zeros_like(counts), counts,
                         use_kernel=use_kernel, interpret=interpret)
    return li


# --------------------------------------------------------------------------- #
# the fused probe -> expand -> gather pipeline
# --------------------------------------------------------------------------- #

def _check_total(total: int, max_total: "int | None") -> None:
    if max_total is not None and total > max_total:
        raise ExpansionCapExceeded(
            f"hash-join ragged expansion would materialize {total} rows, "
            f"above the {max_total}-row cap")


_EMPTY_PAIR = (np.empty(0, np.int64), np.empty(0, np.int64), 0)


def _pipeline_numpy(lcs, rcs, max_total):
    """Pure-host pipeline: zero boundary crossings, what auto serves on
    CPU. The cap check sits between probe and expansion, exactly where the
    device tiers check it — nothing is materialized past the cap."""
    order, lo, counts = hash_probe_numpy(lcs, rcs)
    total = int(counts.sum())
    _check_total(total, max_total)
    if total == 0:
        return _EMPTY_PAIR
    li, pos = expand_pairs_numpy(lo, counts)
    return li, order[pos], total


def _pipeline_oracle(lcs, rcs, max_total):
    """Device-resident jitted-jnp pipeline. Boundary crossings: two key
    uploads, the build sort key down + the order back up (the sort stays
    on the host by design), the expansion-total scalar down, and the final
    ``(li, ri)`` pair down — 7, vs the staged oracle composite's 12 plus
    its full intermediate arrays."""
    import jax.numpy as jnp

    nl, nr = len(lcs[0]), len(rcs[0])
    with jax.enable_x64(True):
        pack, search = _oracle_fns()
        fns = _pipe_fns()
        with span("repro.join.pack"):
            _note(h2d=2)
            lk_d = pack(_pad_pow2(np.stack(lcs, axis=1)))      # (nl pow2,)
            rk_d = pack(_pad_pow2(np.stack(rcs, axis=1)))      # (nr pow2,)
        with span("repro.join.sort"):
            _note(d2h=1)
            rk = np.asarray(rk_d)[:nr]
            order = np.argsort(rk, kind="stable")
            _note(h2d=1)
            order_d = jnp.asarray(order)
            build_d = fns["pad_to"](fns["sort_take"](rk_d[:nr], order_d),
                                    n=_pow2_len(nr), fill=int(_INT64_MAX))
        with span("repro.join.probe") as sp:
            if sp.recording:
                sp.annotate(tier="oracle")
            lo_j, hi_j = search(build_d, lk_d)
            lo_d = fns["clamp_runs"](lo_j[:nl], nr)
            counts_d = fns["run_counts"](fns["clamp_runs"](hi_j[:nl], nr),
                                         lo_d)
        with span("repro.join.counts"):
            _note(d2h=1)
            total = int(fns["count_total"](counts_d))
            _check_total(total, max_total)
        if total == 0:
            return _EMPTY_PAIR
        with span("repro.join.expand") as sp:
            if sp.recording:
                sp.annotate(tier="oracle", total=total)
            mp = _pow2_len(nl)
            counts_p = fns["pad_to"](counts_d, n=mp, fill=0)
            li_d, pos_d = fns["expand"](fns["run_starts"](counts_p),
                                        counts_p,
                                        fns["pad_to"](lo_d, n=mp, fill=0),
                                        total=_pow2_len(total))
        with span("repro.join.gather"):
            ri_d = fns["pair_gather"](order_d, pos_d[:total])
            _note(d2h=2)
            return (np.asarray(li_d[:total]).astype(np.int64),
                    np.asarray(ri_d).astype(np.int64), total)


def _pipeline_pallas(lcs, rcs, use_kernel, interpret, max_total):
    """Kernel pipeline: pack/probe/expand as Pallas kernels and the gather
    as XLA's device gather, with device-resident word-pair intermediates;
    per-stage scaling-envelope fallbacks swap in the jitted jnp form of
    that one stage *on device* instead of dropping the whole join to the
    host. Boundary crossings: two key-column uploads, the recombined sort
    key down + the order back up, the match counts down + their exclusive
    prefix sum back up, the final pair down — 8, vs the staged all-kernel
    composite's 20. The prefix sum runs on the host because the TPU
    compiler takes about a minute over a device scan of ~1M rows, and the
    join sizes change with every write."""
    import jax.numpy as jnp

    nl, nr = len(lcs[0]), len(rcs[0])
    auto = use_kernel is None
    use_kernel, interpret = dispatch.resolve(use_kernel, interpret,
                                             max(nl, nr), hot_path=True)
    if not use_kernel:
        if auto and not dispatch.on_tpu():
            dispatch.note_tier("join.pipeline", "host", "cpu_auto")
            return _pipeline_numpy(lcs, rcs, max_total)
        dispatch.note_tier("join.pipeline", "oracle",
                           "below_floor" if auto else "forced_off")
        return _pipeline_oracle(lcs, rcs, max_total)
    dispatch.note_tier("join.pipeline", "pallas",
                       "auto" if auto else "forced")
    fns = _pipe_fns()
    with span("repro.join.pack"):
        _note(h2d=2)
        lh, ll = kernel.pack_keys_pallas(
            np.stack(lcs, axis=1).astype(np.int32), interpret=interpret)
        rh, rl = kernel.pack_keys_pallas(
            np.stack(rcs, axis=1).astype(np.int32), interpret=interpret)
    # build-side sort on the host by design: the recombined int64 key is
    # the one mid-pipeline materialization, the order the one extra upload
    with span("repro.join.sort"):
        with jax.enable_x64(True):
            rk_d = fns["join_words"](rh, rl)
        _note(d2h=1)
        order = np.argsort(np.asarray(rk_d), kind="stable")
        _note(h2d=1)
        order_d = jnp.asarray(order.astype(np.int32))
        rh_s = fns["sort_take"](rh, order_d)
        rl_s = fns["sort_take"](rl, order_d)
    with span("repro.join.probe") as sp:
        if auto and nl * nr > _probe_work_cap():
            # compare budget exceeded: this stage runs as the device oracle
            dispatch.note_tier("join.pipeline.probe", "oracle", "work_cap")
            tier = "oracle"
            with jax.enable_x64(True):
                _, search = _oracle_fns()
                lo_j, hi_j = search(rk_d[order_d],
                                    fns["join_words"](lh, ll))
            lo_d = lo_j.astype(jnp.int32)
            counts_d = fns["run_counts"](hi_j, lo_j).astype(jnp.int32)
        else:
            tier = "pallas"
            lo_d, hi_d = kernel.probe_sorted_pallas(rh_s, rl_s, lh, ll,
                                                    interpret=interpret)
            counts_d = fns["run_counts"](hi_d, lo_d)
        if sp.recording:
            sp.annotate(tier=tier)
    with span("repro.join.counts"):
        _note(d2h=1)
        counts = np.asarray(counts_d).astype(np.int64)
        total = int(counts.sum())
        _check_total(total, max_total)
        if 0 < total < 1 << 31 and nr < 1 << 31:
            _note(h2d=1)
            starts_d = jnp.asarray(
                (np.cumsum(counts) - counts).astype(np.int32))
    if total == 0:
        return _EMPTY_PAIR
    with span("repro.join.expand") as sp:
        if total >= 1 << 31 or nr >= 1 << 31:
            # past the int32 envelope no device stage can carry the
            # expansion; finish on the host (auto would normally cap out
            # long before this)
            dispatch.note_tier("join.pipeline.expand", "host",
                               "int32_envelope")
            if sp.recording:
                sp.annotate(tier="host", total=total)
            li, pos = expand_pairs_numpy(np.asarray(lo_d).astype(np.int64),
                                         counts)
            return li, order[pos].astype(np.int64), total
        tp = _pow2_len(total)
        if auto and total * nl > _expand_work_cap():
            # ownership-test budget exceeded: searchsorted oracle, on device
            dispatch.note_tier("join.pipeline.expand", "oracle", "work_cap")
            tier = "oracle"
            mp = _pow2_len(nl)
            li_d, pos_d = fns["expand"](
                fns["pad_to"](starts_d, n=mp, fill=total),
                fns["pad_to"](counts_d, n=mp, fill=0),
                fns["pad_to"](lo_d, n=mp, fill=0), total=tp)
        else:
            tier = "pallas"
            li_d, pos_d = kernel.expand_pairs_pallas(starts_d, counts_d,
                                                     lo_d, total=tp,
                                                     interpret=interpret)
        if sp.recording:
            sp.annotate(tier=tier, total=total)
        li_d, pos_d = li_d[:total], pos_d[:total]
    # XLA's device gather: Mosaic has no lowering for a 1-D lane gather
    with span("repro.join.gather"):
        dispatch.note_tier("join.pipeline.gather", "xla")
        ri_d = fns["pair_gather"](order_d, pos_d)
        _note(d2h=2)
        return (np.asarray(li_d).astype(np.int64),
                np.asarray(ri_d).astype(np.int64), total)


def hash_join_pipeline(lcs: Sequence[np.ndarray], rcs: Sequence[np.ndarray],
                       *, mode: str = "auto",
                       use_kernel: bool | None = None,
                       interpret: bool | None = None,
                       max_total: "int | None" = None,
                       ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Fused probe→expand→gather: key columns in, final ``(li, ri, total)``
    pair indices out (``li`` probe-side row ids, ``ri`` build-side row ids,
    both int64). Intermediates stay device-resident between stages on the
    device tiers; ``max_total`` caps the expansion *before* it is
    materialized (:class:`ExpansionCapExceeded`).

    ``mode`` picks the tier: ``"numpy"`` (pure host), ``"oracle"``
    (device-resident jitted jnp), ``"pallas"`` (kernels; per-stage envelope
    fallbacks stay on device), or ``"auto"`` (pallas on TPU, numpy on CPU —
    the same policy the granular ops resolve per stage)."""
    if mode not in ("auto", "numpy", "oracle", "pallas"):
        raise ValueError(f"unknown pipeline mode: {mode!r}")
    assert len(lcs) <= 2 and len(rcs) <= 2, "reduce key columns first"
    nl, nr = len(lcs[0]), len(rcs[0])
    if nl == 0 or nr == 0:
        return _EMPTY_PAIR
    auto_mode = mode == "auto"
    if mode == "auto":
        mode = "pallas" if dispatch.on_tpu() else "numpy"
    if mode == "numpy":
        dispatch.note_tier("join.pipeline", "host",
                           "cpu_auto" if auto_mode else "forced")
        return _pipeline_numpy(lcs, rcs, max_total)
    if mode == "oracle":
        dispatch.note_tier("join.pipeline", "oracle", "forced")
        return _pipeline_oracle(lcs, rcs, max_total)
    return _pipeline_pallas(lcs, rcs, use_kernel, interpret, max_total)
