"""Kernel microbenches.

On this CPU container the Pallas kernels execute in interpret mode (Python
per-op), so wall-times compare the *reference jnp paths* (which XLA:CPU
compiles) and validate kernels at small shapes; the kernels' TPU performance
story is carried by the roofline analysis, not CPU timings.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import ref as fref
from repro.kernels.jaccard import kernel as jkernel
from repro.kernels.jaccard import ref as jref
from repro.kernels.join import ops as join_ops
from repro.kernels.mamba2_ssd import kernel as skernel
from repro.kernels.mamba2_ssd import ref as sref
from repro.kernels.rwkv6_wkv import kernel as wkernel
from repro.kernels.rwkv6_wkv import ref as wref


def _time(fn, n=3):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / n * 1e6


def _join_fixture(rng, nl: int, nr: int):
    """Probe/build key columns with a 50% hit rate (executor-shaped)."""
    lcs = [rng.integers(0, 2**31 - 1, nl).astype(np.int64) for _ in range(2)]
    rcs = [rng.integers(0, 2**31 - 1, nr).astype(np.int64) for _ in range(2)]
    n = min(nl, nr) // 2
    for c in range(2):
        rcs[c][:n] = lcs[c][:n]
    return lcs, rcs


def join_rows(rng, *, dry_run: bool = False) -> List[Tuple[str, float, str]]:
    """Join-kernel rows: the jitted-jnp oracle (the ``JaxExecutor``
    baseline probe) vs the Pallas word-pair path (interpret on CPU).
    ``--dry-run`` validates the kernel at a tiny shape and skips timings."""
    rows: List[Tuple[str, float, str]] = []
    nl, nr = (64, 64) if dry_run else (4096, 4096)
    lcs, rcs = _join_fixture(rng, nl, nr)
    ref = join_ops.hash_probe_oracle(lcs, rcs)
    got = join_ops.hash_probe(lcs, rcs, use_kernel=True, interpret=True)
    for a, b, name in zip(ref, got, ("order", "lo", "counts")):
        assert np.array_equal(a, b), f"join kernel mismatch: {name}"
    if dry_run:
        rows.append(("kern/join_dry_run_ok", 1.0,
                     f"nl={nl}_nr={nr}_matches={int(ref[2].sum())}"))
        return rows
    t_oracle = _time(lambda: join_ops.hash_probe_oracle(lcs, rcs))
    rows.append((f"kern/join{nl}_probe_jnp_us", t_oracle,
                 "jitted_oracle_2col"))
    rows.append((f"kern/join{nl}_probe_pallas_interp_us", _time(
        lambda: join_ops.hash_probe(lcs, rcs, use_kernel=True,
                                    interpret=True), n=1), "interpret-mode"))
    cols = np.stack(lcs, axis=1)
    rows.append((f"kern/join{nl}_pack_jnp_us", _time(
        lambda: join_ops.pack_keys(cols, use_kernel=False)), ""))
    rows.append((f"kern/join{nl}_pack_pallas_interp_us", _time(
        lambda: join_ops.pack_keys(cols, use_kernel=True, interpret=True),
        n=1), "interpret-mode"))
    return rows


def _staged_host(lcs, rcs):
    """The pre-fusion composite: three granular host ops in sequence."""
    order, lo, counts = join_ops.hash_probe_numpy(lcs, rcs)
    li, pos = join_ops.expand_pairs_numpy(lo, counts)
    return li, order[pos]


def _staged_oracle(lcs, rcs):
    """The staged device tier: every op round-trips host<->device on its
    own, materializing each intermediate on the host between stages."""
    order, lo, counts = join_ops.hash_probe_oracle(lcs, rcs)
    li, pos = join_ops.expand_pairs(lo, counts, use_kernel=False)
    return li, order[pos]


def _lubm_shapes():
    """Record the key columns of every fused-pipeline call in one LUBM(3)/8
    extended-workload window — the acceptance join shapes. Returns the
    captured ``(lcs, rcs)`` pairs (non-empty sides, largest work first) and
    the raw call count."""
    from repro.api import JaxExecutor, KGService
    from repro.graph import lubm

    ds = lubm.load(3, 0)
    svc = KGService.from_dataset(ds, 8)
    kg = svc.bootstrap(ds.base_workload())
    plans = [kg.plan(q) for q in ds.extended_workload()]
    captured = []
    real = join_ops.hash_join_pipeline

    def recording(lcs, rcs, **kw):
        captured.append(([np.asarray(c) for c in lcs],
                         [np.asarray(c) for c in rcs]))
        return real(lcs, rcs, **kw)

    join_ops.hash_join_pipeline = recording
    try:
        JaxExecutor().run_batch(plans, kg)
    finally:
        join_ops.hash_join_pipeline = real
    live = [s for s in captured if len(s[0][0]) and len(s[1][0])]
    live.sort(key=lambda s: len(s[0][0]) * len(s[1][0]), reverse=True)
    return live, len(captured)


def pipeline_rows(rng, *, dry_run: bool = False,
                  ) -> List[Tuple[str, float, str]]:
    """Fused ``hash_join_pipeline`` vs the staged composite it replaced.

    ``--dry-run`` pins all three tiers bit-identical (plus the expand
    kernel alone) at a tiny shape; the full run captures the real LUBM(3)/8
    join shapes, pins oracle parity on every one and pallas-interpret
    parity on the smallest, then times fused-vs-staged on the host and
    device tiers and reports the structural host-transfer counts (fused
    strictly below staged, per the dispatch docs)."""
    rows: List[Tuple[str, float, str]] = []
    if dry_run:
        lcs, rcs = _join_fixture(rng, 64, 48)
        ref_li, ref_ri = _staged_host(lcs, rcs)
        order, lo, counts = join_ops.hash_probe_numpy(lcs, rcs)
        li_k, pos_k = join_ops.expand_pairs(lo, counts, use_kernel=True,
                                            interpret=True)
        li_n, pos_n = join_ops.expand_pairs_numpy(lo, counts)
        assert np.array_equal(li_k, li_n) and np.array_equal(pos_k, pos_n), \
            "expand kernel mismatch"
        for mode, kw in (("numpy", {}), ("oracle", {}),
                         ("pallas", dict(use_kernel=True, interpret=True))):
            li, ri, total = join_ops.hash_join_pipeline(lcs, rcs, mode=mode,
                                                        **kw)
            assert total == len(ref_li), f"fused {mode} total mismatch"
            assert (np.array_equal(li, ref_li)
                    and np.array_equal(ri, ref_ri)), f"fused {mode} mismatch"
        rows.append(("kern/pipeline_dry_run_ok", 1.0,
                     f"modes=3_total={len(ref_li)}"))
        return rows

    # expand microbench at the probe fixture shape
    nl = 4096
    lcs, rcs = _join_fixture(rng, nl, nl)
    _, lo, counts = join_ops.hash_probe_numpy(lcs, rcs)
    total = int(counts.sum())
    rows.append((f"kern/expand{nl}_numpy_us", _time(
        lambda: join_ops.expand_pairs_numpy(lo, counts)), f"total={total}"))
    rows.append((f"kern/expand{nl}_jnp_us", _time(
        lambda: join_ops.expand_pairs(lo, counts, use_kernel=False)),
        "jitted_searchsorted"))
    rows.append((f"kern/expand{nl}_pallas_interp_us", _time(
        lambda: join_ops.expand_pairs(lo, counts, use_kernel=True,
                                      interpret=True), n=1),
        "interpret-mode"))

    shapes, n_calls = _lubm_shapes()
    big = shapes[:6]                    # timing set: the heaviest joins
    rows.append(("kern/pipeline_lubm3_shapes", float(len(big)),
                 f"of_{n_calls}_window_calls_max_nl="
                 f"{max(len(l[0]) for l, _ in big)}"))

    # parity: fused == staged on every timed shape (device oracle tier),
    # and pallas-interpret pinned on the smallest real shapes (interpret
    # runs the grid in Python, so the big shapes stay on the cheap tiers)
    refs = []
    for l, r in big:
        ref = _staged_host(l, r)
        got = join_ops.hash_join_pipeline(l, r, mode="oracle")
        assert (np.array_equal(got[0], ref[0])
                and np.array_equal(got[1], ref[1])), \
            "fused oracle mismatch on LUBM shape"
        refs.append(ref)
    for l, r in shapes[-2:]:
        ref = _staged_host(l, r)
        got = join_ops.hash_join_pipeline(l, r, mode="pallas",
                                          use_kernel=True, interpret=True)
        assert (np.array_equal(got[0], ref[0])
                and np.array_equal(got[1], ref[1])), \
            "fused pallas-interpret mismatch on LUBM shape"

    t_staged = sum(_time(lambda l=l, r=r: _staged_host(l, r))
                   for l, r in big)
    t_fused = sum(_time(lambda l=l, r=r: join_ops.hash_join_pipeline(
        l, r, mode="numpy")) for l, r in big)
    rows.append(("kern/pipeline_staged_host_us", t_staged,
                 "probe+expand+gather_numpy"))
    rows.append(("kern/pipeline_fused_host_us", t_fused,
                 f"speedup_vs_staged={t_staged / t_fused:.2f}x"))
    t_staged_o = sum(_time(lambda l=l, r=r: _staged_oracle(l, r))
                     for l, r in big)
    t_fused_o = sum(_time(lambda l=l, r=r: join_ops.hash_join_pipeline(
        l, r, mode="oracle")) for l, r in big)
    rows.append(("kern/pipeline_staged_jnp_us", t_staged_o,
                 "per-stage_round_trips"))
    rows.append(("kern/pipeline_fused_jnp_us", t_fused_o,
                 f"speedup_vs_staged={t_staged_o / t_fused_o:.2f}x"
                 "_device_resident"))

    # structural host-transfer accounting: fused strictly below staged
    with join_ops.track_transfers() as tf_f:
        for l, r in big:
            join_ops.hash_join_pipeline(l, r, mode="oracle")
    with join_ops.track_transfers() as tf_s:
        for l, r in big:
            _staged_oracle(l, r)
    assert tf_f.total < tf_s.total, \
        "fused pipeline must cross the boundary strictly less than staged"
    rows.append(("kern/pipeline_fused_transfers", float(tf_f.total),
                 f"h2d={tf_f.h2d}_d2h={tf_f.d2h}_staged={tf_s.total}"
                 f"(h2d={tf_s.h2d}_d2h={tf_s.d2h})"))
    l, r = big[0]
    with join_ops.track_transfers() as t_p:
        order, lo, counts = join_ops.hash_probe_oracle(l, r)
    with join_ops.track_transfers() as t_e:
        _, pos = join_ops.expand_pairs(lo, counts, use_kernel=False)
    with join_ops.track_transfers() as t_g:
        order[pos]
    for name, t in (("probe", t_p), ("expand", t_e), ("gather", t_g)):
        rows.append((f"kern/pipeline_staged_{name}_transfers",
                     float(t.total), f"h2d={t.h2d}_d2h={t.d2h}"))

    # kernel tier at a small shape: fused keeps word pairs device-resident
    lcs, rcs = _join_fixture(rng, 64, 48)
    with join_ops.track_transfers() as kf:
        join_ops.hash_join_pipeline(lcs, rcs, mode="pallas",
                                    use_kernel=True, interpret=True)
    with join_ops.track_transfers() as ks:
        order, lo, counts = join_ops.hash_probe(lcs, rcs, use_kernel=True,
                                                interpret=True)
        _, pos = join_ops.expand_pairs(lo, counts, use_kernel=True,
                                       interpret=True)
        join_ops.gather_rows(order, pos, on_device=True)
    assert kf.total < ks.total, \
        "fused kernel tier must cross the boundary strictly less than staged"
    rows.append(("kern/pipeline_pallas_transfers", float(kf.total),
                 f"h2d={kf.h2d}_d2h={kf.d2h}_staged={ks.total}"
                 f"(h2d={ks.h2d}_d2h={ks.d2h})"))
    return rows


def run(*, dry_run: bool = False) -> List[Tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    if dry_run:
        return join_rows(rng, dry_run=True) + pipeline_rows(rng,
                                                            dry_run=True)
    rows = join_rows(rng)
    rows += pipeline_rows(rng)

    # jaccard: jnp oracle vs pallas-interpret (correctness-checked timing)
    bm = jnp.asarray(rng.integers(0, 2 ** 32, (256, 32), dtype=np.uint32))
    f_ref = jax.jit(lambda a: jref.jaccard_distance(a, a))
    rows.append(("kern/jaccard256_jnp_us", _time(lambda: f_ref(bm)), ""))
    rows.append(("kern/jaccard256_pallas_interp_us", _time(
        lambda: jkernel.jaccard_distance_pallas(bm, bm, interpret=True),
        n=1), "interpret-mode"))

    # flash attention reference path (jit) at a prefill-ish tile
    q = jnp.asarray(rng.normal(size=(1, 512, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 64)), jnp.float32)
    f_attn = jax.jit(lambda q, k, v: fref.attention(q, k, v, causal=True))
    rows.append(("kern/attn512_gqa_jnp_us", _time(lambda: f_attn(q, k, v)),
                 "b1_s512_h8_kv2_d64"))

    # wkv: scan vs chunked kernel (interpret) at small shape
    b, s, h, hd = 1, 128, 2, 32
    r = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(rng.normal(size=(b, s, h, hd)) - 2)),
                    jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, hd)), jnp.float32)
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    f_wkv = jax.jit(lambda *a: wref.wkv(*a))
    rows.append(("kern/wkv128_scan_us", _time(
        lambda: f_wkv(r, kk, vv, w, u, s0)), ""))

    # ssd: scan vs chunked kernel at small shape
    x = jnp.asarray(rng.normal(size=(1, 256, 2, 32)), jnp.float32)
    bmat = jnp.asarray(rng.normal(size=(1, 256, 16)), jnp.float32)
    cmat = jnp.asarray(rng.normal(size=(1, 256, 16)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(1, 256, 2))) * 0.1 + 1e-3,
                     jnp.float32)
    a = jnp.asarray([-1.0, -2.0], jnp.float32)
    d = jnp.asarray([1.0, 1.0], jnp.float32)
    ss0 = jnp.zeros((1, 2, 16, 32), jnp.float32)
    f_ssd = jax.jit(lambda: sref.ssd(x[:, :, 0], bmat, cmat, dt[:, :, 0],
                                     a[0], d[0], ss0[:, 0]))
    rows.append(("kern/ssd256_scan_us", _time(f_ssd), "per-head"))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dry-run", action="store_true",
                    help="validate the join kernel at a tiny shape and exit")
    ap.add_argument("--csv", default=None,
                    help="also write the rows to this CSV path "
                         "(e.g. results/exp_kernels.csv)")
    args = ap.parse_args()
    rows = run(dry_run=args.dry_run)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.csv:
        with open(args.csv, "w") as fh:
            fh.write("name,us_per_call,derived\n")
            for name, us, derived in rows:
                fh.write(f"{name},{us:.1f},{derived}\n")


if __name__ == "__main__":
    main()
