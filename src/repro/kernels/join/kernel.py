"""Pallas TPU kernels: hash-join key packing, sorted probe, segmented
ragged expansion.

The executor's hash join has four vectorizable stages:

1. **pack** — reduce the (N, K<=2) shared-variable key columns of each side
   to one 62-bit key per row (base-2^31 positional packing; dictionary ids
   are < 2^31).
2. **probe** — for every probe-side key, the ``[lo, hi)`` range of equal
   keys in the sorted build side (``searchsorted`` left/right).
3. **expand** — turn the per-probe-row ``(lo, counts)`` match runs into
   flat ``(li, pos)`` pair-index arrays (the data-dependent ragged
   expansion, formerly host ``np.repeat``/``np.cumsum`` arithmetic). Match
   runs partition the output index space: output ``j`` belongs to exactly
   the segment ``i`` with ``starts[i] <= j < starts[i] + counts[i]``
   (``starts`` = exclusive cumsum of ``counts``), so each (BN, BM) grid
   step broadcast-tests a tile of output indices against a tile of
   segments and accumulates the single owner's ``(i, lo[i] + j -
   starts[i])`` via a masked sum — a segmented scan with no dynamic
   gathers on the VPU. Zero-count segments own nothing and drop out for
   free, which also makes the padding inert.
4. **gather** — index the build side's sort permutation with the expanded
   match positions. This stage has no kernel here: Mosaic lowers only 2-D
   gathers, and a gather over a (1, N) lane row is refused by the TPU
   compiler, so the ops layer runs XLA's device gather instead.

TPUs have no int64, so packed keys travel through the kernels as two 32-bit
words: ``hi = key >> 32`` (int32, < 2^30 for K <= 2) and ``lo = key &
0xffffffff`` (uint32). Lexicographic order on ``(hi, lo-as-unsigned)``
equals int64 order on the packed key, which is what makes the probe exact.

The probe kernel is **sort-free on device**: instead of binary search (a
log-depth chain of dynamic gathers — hostile to the VPU), each (BN, BM)
grid step broadcast-compares a probe panel against a build panel and
accumulates ``lo = #build < probe`` / ``hi = #build <= probe`` counts.
On a sorted build side those counts *are* the searchsorted indices. The
build-side sort itself stays on the host (``np.argsort``), exactly like the
executor's jitted-jnp path.

Grids: pack is 1-D over row tiles; probe is (N/BN, M/BM) with the
output accumulated over the build axis (TPU grids iterate sequentially, so
read-modify-write on the j axis is the standard reduction pattern). All
arrays are carried as (1, N) lane-major panels to respect the 128-lane
tiling constraint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HI_INF = jnp.int32(2**31 - 1)          # > any real hi word (< 2^30)
_LO_INF = jnp.uint32(0xFFFFFFFF)


def _pad_to(x: jnp.ndarray, n: int, fill) -> jnp.ndarray:
    """Pad the last axis to length ``n`` with ``fill``."""
    return jnp.full(x.shape[:-1] + (n,), fill, x.dtype).at[..., :x.shape[-1]] \
        .set(x)


# --------------------------------------------------------------------------- #
# pack
# --------------------------------------------------------------------------- #

def _pack_kernel(cols_ref, hi_ref, lo_ref, *, n_cols: int):
    c0 = cols_ref[0, :].astype(jnp.uint32)            # ids < 2^31
    if n_cols == 1:                                   # key = c0
        hi = jnp.zeros_like(c0, jnp.int32)
        lo = c0
    else:                                             # key = c0 * 2^31 + c1
        c1 = cols_ref[1, :].astype(jnp.uint32)
        hi = (c0 >> 1).astype(jnp.int32)
        lo = ((c0 & jnp.uint32(1)) << 31) | c1
    hi_ref[0, :] = hi
    lo_ref[0, :] = lo


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def pack_keys_pallas(cols: jnp.ndarray, *, block_n: int = 256,
                     interpret: bool = False,
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, K<=2) int32 key columns -> ``(hi int32, lo uint32)`` word pair
    per row, the split representation of the base-2^31 packed int64 key."""
    n, k = cols.shape
    assert k in (1, 2), f"key columns must be reduced to <= 2, got {k}"
    np_ = max(block_n, (n + block_n - 1) // block_n * block_n)
    cols_t = _pad_to(cols.T.astype(jnp.int32), np_, 0)
    hi, lo = pl.pallas_call(
        functools.partial(_pack_kernel, n_cols=k),
        grid=(np_ // block_n,),
        in_specs=[pl.BlockSpec((k, block_n), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i)),
                   pl.BlockSpec((1, block_n), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.int32),
                   jax.ShapeDtypeStruct((1, np_), jnp.uint32)],
        interpret=interpret,
    )(cols_t)
    return hi[0, :n], lo[0, :n]


# --------------------------------------------------------------------------- #
# probe
# --------------------------------------------------------------------------- #

def _probe_kernel(bh_ref, bl_ref, ph_ref, pl_ref, lo_ref, hi_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    bh = bh_ref[0, :]                                 # (BM,) int32
    bl = bl_ref[0, :]                                 # (BM,) uint32
    ph = ph_ref[0, :]                                 # (BN,) int32
    plo = pl_ref[0, :]                                # (BN,) uint32
    # (BN, BM) broadcast compare, lexicographic on the (hi, lo) word pair
    hi_lt = bh[None, :] < ph[:, None]
    hi_eq = bh[None, :] == ph[:, None]
    lt = hi_lt | (hi_eq & (bl[None, :] < plo[:, None]))
    le = lt | (hi_eq & (bl[None, :] == plo[:, None]))
    lo_ref[0, :] += lt.sum(axis=1).astype(jnp.int32)
    hi_ref[0, :] += le.sum(axis=1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def probe_sorted_pallas(build_hi: jnp.ndarray, build_lo: jnp.ndarray,
                        probe_hi: jnp.ndarray, probe_lo: jnp.ndarray, *,
                        block_n: int = 256, block_m: int = 512,
                        interpret: bool = False,
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """searchsorted left/right of every probe key over the ascending build
    keys, both sides as (hi, lo) word pairs. Build padding is +inf word
    pairs, which never compare below a real probe key — the counts need no
    post-hoc clamping."""
    m, n = build_hi.shape[0], probe_hi.shape[0]
    mp = max(block_m, (m + block_m - 1) // block_m * block_m)
    np_ = max(block_n, (n + block_n - 1) // block_n * block_n)
    bh = _pad_to(build_hi[None, :], mp, _HI_INF)
    bl = _pad_to(build_lo[None, :], mp, _LO_INF)
    ph = _pad_to(probe_hi[None, :], np_, _HI_INF)
    plo = _pad_to(probe_lo[None, :], np_, _LO_INF)
    lo, hi = pl.pallas_call(
        _probe_kernel,
        grid=(np_ // block_n, mp // block_m),
        in_specs=[pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
                  pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
                  pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
                  pl.BlockSpec((1, block_n), lambda i, j: (0, i))],
        out_specs=[pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
                   pl.BlockSpec((1, block_n), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.int32),
                   jax.ShapeDtypeStruct((1, np_), jnp.int32)],
        interpret=interpret,
    )(bh, bl, ph, plo)
    return lo[0, :n], hi[0, :n]


# --------------------------------------------------------------------------- #
# expand
# --------------------------------------------------------------------------- #

def _expand_kernel(starts_ref, counts_ref, lo_ref, li_ref, pos_ref, *,
                   block_n: int, block_m: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        li_ref[...] = jnp.zeros_like(li_ref)
        pos_ref[...] = jnp.zeros_like(pos_ref)

    starts = starts_ref[0, :]                         # (BM,) int32
    counts = counts_ref[0, :]                         # (BM,) int32
    lo = lo_ref[0, :]                                 # (BM,) int32
    # (BN, BM) global output indices / segment ids for this grid step
    j = (pl.program_id(0) * block_n
         + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_m), 0))
    seg = (pl.program_id(1) * block_m
           + jax.lax.broadcasted_iota(jnp.int32, (block_n, block_m), 1))
    # exactly one segment owns each real output index (runs partition the
    # output space); zero-count segments — including all padding — own none
    owns = (starts[None, :] <= j) & (j < (starts + counts)[None, :])
    li_ref[0, :] += jnp.where(owns, seg, 0).sum(axis=1)
    pos_ref[0, :] += jnp.where(owns, lo[None, :] + j - starts[None, :],
                               0).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("total", "block_n", "block_m",
                                             "interpret"))
def expand_pairs_pallas(starts: jnp.ndarray, counts: jnp.ndarray,
                        lo: jnp.ndarray, *, total: int, block_n: int = 256,
                        block_m: int = 512, interpret: bool = False,
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Segmented ragged expansion: per-segment ``(starts, counts, lo)``
    match runs -> flat ``(li, pos)`` pair indices of length ``total``
    (``total`` = the static padded output size; callers slice to the true
    ``counts.sum()``). ``li[j]`` is the owning segment, ``pos[j] = lo[li[j]]
    + (j - starts[li[j]])`` its position in the build-side sort order.
    Output indices past the last run (padding included) own nothing and
    come back 0 — callers slice them off."""
    m = starts.shape[0]
    mp = max(block_m, (m + block_m - 1) // block_m * block_m)
    np_ = max(block_n, (total + block_n - 1) // block_n * block_n)
    st = _pad_to(starts.astype(jnp.int32)[None, :], mp, 0)
    ct = _pad_to(counts.astype(jnp.int32)[None, :], mp, 0)
    lp = _pad_to(lo.astype(jnp.int32)[None, :], mp, 0)
    li, pos = pl.pallas_call(
        functools.partial(_expand_kernel, block_n=block_n, block_m=block_m),
        grid=(np_ // block_n, mp // block_m),
        in_specs=[pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
                  pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
                  pl.BlockSpec((1, block_m), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
                   pl.BlockSpec((1, block_n), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, np_), jnp.int32),
                   jax.ShapeDtypeStruct((1, np_), jnp.int32)],
        interpret=interpret,
    )(st, ct, lp)
    return li[0, :total], pos[0, :total]

