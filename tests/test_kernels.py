"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import autotune, dispatch
from repro.kernels.flash_attention import kernel as fk
from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_attention import ref as fref
from repro.kernels.join import ops as jops
from repro.kernels.mamba2_ssd import kernel as sk
from repro.kernels.mamba2_ssd import ref as sref
from repro.kernels.rwkv6_wkv import kernel as wk
from repro.kernels.rwkv6_wkv import ref as wref


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,s,t,h,kh,d,causal,off,valid", [
    (2, 64, 64, 4, 2, 32, True, 0, None),       # causal GQA
    (2, 64, 64, 4, 4, 32, False, 0, None),      # bidirectional MHA
    (1, 40, 40, 2, 1, 16, True, 0, None),       # padding path
    (2, 1, 128, 4, 2, 32, True, 96, 97),        # decode vs cache
    (1, 16, 128, 2, 2, 64, True, 112, 128),     # chunked prefill tail
])
def test_flash_matches_ref(rng, b, s, t, h, kh, d, causal, off, valid):
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kh, d)), jnp.float32)
    o_ref = fref.attention(q, k, v, causal=causal, q_offset=off,
                           kv_valid_len=valid)
    o_ker = fk.flash_attention_fwd(q, k, v, causal=causal, q_offset=off,
                                   kv_valid_len=valid, block_q=32,
                                   block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ker),
                               atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(rng, dtype):
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 16)), dtype)
    o_ref = fref.attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True)
    o_ker = fk.flash_attention_fwd(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_ref),
                               np.asarray(o_ker).astype(np.float32),
                               atol=tol)


def test_flash_gradients_match_reference(rng):
    b, s, h, kh, d = 2, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kh, d)), jnp.float32)
    g1 = jax.grad(lambda *a: (fops.flash_attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (fref.attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4)


# --------------------------------------------------------------------------- #
# rwkv6 wkv
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,s,h,hd,c", [
    (2, 32, 2, 16, 8), (1, 64, 4, 32, 16), (2, 128, 1, 64, 64),
])
def test_wkv_matches_scan(rng, b, s, h, hd, c):
    r = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32) * 0.5
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(rng.normal(size=(b, s, h, hd)) - 2.0)),
                    jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, hd)), jnp.float32) * 0.1
    s0 = jnp.asarray(rng.normal(size=(b, h, hd, hd)), jnp.float32) * 0.1
    y0, f0 = wref.wkv(r, k, v, w, u, s0)
    y1, f1 = wk.wkv_pallas(r, k, v, w, u, s0, chunk=c, interpret=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-3)
    np.testing.assert_allclose(np.asarray(f0), np.asarray(f1), atol=1e-3)


def test_wkv_strong_decay_no_overflow(rng):
    """w as small as 0.03: the factorized form overflows f32; ours must not."""
    b, s, h, hd = 1, 64, 2, 16
    shapes = (b, s, h, hd)
    r = jnp.asarray(rng.normal(size=shapes), jnp.float32)
    k = jnp.asarray(rng.normal(size=shapes), jnp.float32)
    v = jnp.asarray(rng.normal(size=shapes), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(rng.normal(size=shapes) + 0.2)),
                    jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, hd)), jnp.float32)
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    y0, _ = wref.wkv(r, k, v, w, u, s0)
    y1, _ = wk.wkv_pallas(r, k, v, w, u, s0, chunk=32, interpret=True)
    assert np.isfinite(np.asarray(y1)).all()
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-2)


# --------------------------------------------------------------------------- #
# mamba2 ssd
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bb,s,h,hd,n,c", [
    (2, 32, 2, 16, 8, 8), (1, 64, 3, 32, 16, 16), (2, 128, 1, 64, 64, 128),
])
def test_ssd_matches_scan(rng, bb, s, h, hd, n, c):
    x = jnp.asarray(rng.normal(size=(bb, s, h, hd)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(bb, s, n)), jnp.float32) * 0.5
    cm = jnp.asarray(rng.normal(size=(bb, s, n)), jnp.float32) * 0.5
    dt = jnp.asarray(np.abs(rng.normal(size=(bb, s, h))) * 0.1 + 1e-3,
                     jnp.float32)
    a = jnp.asarray(-np.exp(rng.normal(size=(h,))), jnp.float32)
    d = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(bb, h, n, hd)), jnp.float32) * 0.1
    ys, fs = [], []
    for hi in range(h):
        y, f = sref.ssd(x[:, :, hi], b, cm, dt[:, :, hi], a[hi], d[hi],
                        s0[:, hi])
        ys.append(y)
        fs.append(f)
    y0, f0 = jnp.stack(ys, 2), jnp.stack(fs, 1)
    y1, f1 = sk.ssd_pallas(x, b, cm, dt, a, d, s0, chunk=c, interpret=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-4)
    np.testing.assert_allclose(np.asarray(f0), np.asarray(f1), atol=2e-4)


# --------------------------------------------------------------------------- #
# join (pack / sorted-probe / gather) + the shared dispatch policy
# --------------------------------------------------------------------------- #

MAXID = 2**31 - 1


@pytest.mark.parametrize("nl,nr,k", [
    (0, 17, 1),         # empty probe side
    (23, 0, 2),         # empty build side
    (5, 5, 1),          # below every block size
    (300, 513, 2),      # straddles the probe block boundaries
    (1, 1000, 2),       # single probe key against a large build
])
def test_join_hash_probe_matches_oracle(rng, nl, nr, k):
    """(order, lo, counts) from the Pallas word-pair path == jitted oracle,
    including empty sides and block-boundary straddles."""
    lcs = [rng.integers(0, MAXID, nl).astype(np.int64) for _ in range(k)]
    rcs = [rng.integers(0, MAXID, nr).astype(np.int64) for _ in range(k)]
    n_common = min(nl, nr) // 2
    for c in range(k):                       # force real matches + dup keys
        rcs[c][:n_common] = lcs[c][:n_common]
        if nr > 2:
            rcs[c][-1] = rcs[c][0]
    ref = jops.hash_probe_oracle(lcs, rcs)
    got = jops.hash_probe(lcs, rcs, use_kernel=True, interpret=True)
    for a, b, name in zip(ref, got, ("order", "lo", "counts")):
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_join_probe_zero_matches(rng):
    """Disjoint key ranges: every count is zero on both paths."""
    lcs = [rng.integers(0, 1000, 64).astype(np.int64)]
    rcs = [rng.integers(2000, 3000, 64).astype(np.int64)]
    for kw in ({"use_kernel": False}, {"use_kernel": True, "interpret": True}):
        _, lo, counts = jops.hash_probe(lcs, rcs, **kw)
        assert counts.sum() == 0
        assert (lo >= 0).all() and (lo <= 64).all()


def test_join_pack_word_split_is_exact(rng):
    """The kernel's (hi, lo) 32-bit word pair recombines to exactly the
    oracle's base-2^31 int64 key, including the extreme ids."""
    cols = rng.integers(0, MAXID, (300, 2)).astype(np.int64)
    cols[0] = [0, 0]
    cols[1] = [MAXID - 1, MAXID - 1]
    cols[2] = [1, 0]
    ref = jops.pack_keys(cols, use_kernel=False)
    got = jops.pack_keys(cols, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(ref, got)
    # one-column packing is the identity
    one = cols[:, :1]
    np.testing.assert_array_equal(
        jops.pack_keys(one, use_kernel=True, interpret=True), one[:, 0])


def test_join_probe_sorted_duplicates_and_misses(rng):
    """searchsorted semantics: [lo, hi) spans full duplicate runs; missing
    keys get empty ranges at the insertion point."""
    build = np.sort(np.repeat(rng.integers(0, 2**40, 50), 3))     # dup runs
    probe = np.concatenate([build[::5], rng.integers(2**41, 2**42, 20)])
    ref = jops.probe_sorted(build, probe, use_kernel=False)
    got = jops.probe_sorted(build, probe, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(ref[0], got[0])
    np.testing.assert_array_equal(ref[1], got[1])
    assert ((got[1] - got[0])[: len(build[::5])] == 3).all()
    assert ((got[1] - got[0])[len(build[::5]):] == 0).all()


@pytest.mark.parametrize("on_device", [True, None])
def test_join_gather_rows_masks_out_of_range(rng, on_device):
    """The device XLA gather and auto dispatch mask out-of-range indices
    exactly like the host tier."""
    vals = rng.integers(0, 10_000, 97)
    idx = np.array([-5, -1, 0, 50, 96, 97, 10_000])
    ref = jops.gather_rows(vals, idx, fill=-3, on_device=False)
    got = jops.gather_rows(vals, idx, fill=-3, on_device=on_device)
    np.testing.assert_array_equal(ref, got)
    assert (ref[[0, 1, 5, 6]] == -3).all()


def test_dispatch_policy_and_env_override(monkeypatch):
    """The shared dispatch helper: explicit flags pass through; the auto
    size threshold comes from REPRO_KERNEL_THRESHOLD; hot-path ops never
    auto-select interpret mode on CPU."""
    assert dispatch.resolve(True, False, 1) == (True, False)
    assert dispatch.resolve(False, None, 10**9)[0] is False
    on_tpu = dispatch.on_tpu()
    # analysis policy (jaccard): big problems use the kernel even on CPU
    assert dispatch.resolve(None, None, 10**6, hot_path=False)[0] is True
    # hot-path policy (join): kernel only on TPU, oracle on CPU
    assert dispatch.resolve(None, None, 10**6, hot_path=True)[0] is on_tpu
    monkeypatch.setenv("REPRO_KERNEL_THRESHOLD", "7")
    assert dispatch.kernel_threshold() == 7
    assert dispatch.resolve(None, None, 8, hot_path=False)[0] is True
    assert dispatch.resolve(None, None, 6, hot_path=False)[0] is on_tpu
    assert dispatch.kernel_threshold(31) == 31


def test_jaccard_dispatch_uses_shared_threshold(rng, monkeypatch):
    """jaccard's old hard-coded >=256 floor now honors the shared policy:
    a tiny problem forced over the threshold still matches the oracle."""
    from repro.kernels.jaccard import ops as jacc
    bm = jnp.asarray(rng.integers(0, 2**32, (12, 4), dtype=np.uint32))
    monkeypatch.setenv("REPRO_KERNEL_THRESHOLD", "8")   # 12 >= 8 -> kernel
    got = jacc.jaccard_distance(bm)
    ref = jacc.jaccard_distance(bm, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_join_probe_tiers_agree(rng):
    """All three probe tiers (host numpy / jitted oracle / Pallas kernels)
    return identical (order, lo, counts); auto dispatch off-TPU serves the
    host tier."""
    lcs, rcs = ([rng.integers(0, MAXID, 200).astype(np.int64)],
                [rng.integers(0, MAXID, 300).astype(np.int64)])
    rcs[0][:100] = lcs[0][:100]
    a = jops.hash_probe_numpy(lcs, rcs)
    b = jops.hash_probe_oracle(lcs, rcs)
    c = jops.hash_probe(lcs, rcs, use_kernel=True, interpret=True)
    d = jops.hash_probe(lcs, rcs)                      # auto (host on CPU)
    for got in (b, c, d):
        for x, y in zip(a, got):
            np.testing.assert_array_equal(x, y)


def test_join_auto_guards_respect_scaling_envelopes(rng, monkeypatch):
    """Auto dispatch falls back past the kernels' scaling envelopes (the
    O(nl*nr) probe compare budget) while forced use_kernel=True still pins
    the kernel path; on a TPU the gather serves its device tier. Results
    agree."""
    from repro.kernels.join import ops as live_ops

    lcs = [rng.integers(0, MAXID, 40).astype(np.int64)]
    rcs = [rng.integers(0, MAXID, 50).astype(np.int64)]
    rcs[0][:20] = lcs[0][:20]
    monkeypatch.setenv("REPRO_KERNEL_THRESHOLD", "10")        # over the floor
    monkeypatch.setenv("REPRO_JOIN_PROBE_WORK_CAP", "100")    # 40*50 > 100
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)     # auto -> kernel
    # without the guards these autos would now try to compile the kernels
    # for a backend that doesn't exist — the fallbacks must engage first
    try:
        ref = live_ops.hash_probe_numpy(lcs, rcs)
        # capped auto path must not run the quadratic kernel; on this CPU
        # "TPU" stub the fallback is the jitted oracle — same results
        got = live_ops.hash_probe(lcs, rcs)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        vals = rng.integers(0, 100, 40)
        idx = rng.integers(0, 40, 30)
        np.testing.assert_array_equal(
            live_ops.gather_rows(vals, idx, assume_inbounds=True),
            vals[idx])
    finally:
        monkeypatch.undo()


def test_join_gather_assume_inbounds_matches_masked(rng):
    vals = rng.integers(0, 1000, 64)
    idx = rng.integers(0, 64, 200)
    a = jops.gather_rows(vals, idx)
    b = jops.gather_rows(vals, idx, assume_inbounds=True)
    c = jops.gather_rows(vals, idx, on_device=True, assume_inbounds=True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_join_kernel_contract_guards(rng):
    """Public-op contract enforcement: the word-pair kernels reject packed
    keys past the 2^62 envelope, and every gather tier keeps tables whose
    values pass int32 exact (the device gather runs under x64)."""
    big = np.array([1 << 62], np.int64)
    ok = np.array([5, (1 << 62) - 1], np.int64)
    with pytest.raises(ValueError, match="2\\^62"):
        jops.probe_sorted(np.sort(ok), big, use_kernel=True, interpret=True)
    lo, hi = jops.probe_sorted(np.sort(ok), ok[:1], use_kernel=True,
                               interpret=True)
    assert (lo[0], hi[0]) == (0, 1)

    wide = np.array([1 << 40, 7], np.int64)
    idx = np.array([0, 1])
    for on_device in (None, False, True):
        got = jops.gather_rows(wide, idx, on_device=on_device)
        assert got.dtype == np.int64, on_device
        np.testing.assert_array_equal(got, wide, err_msg=str(on_device))
    # device-tier output keeps the table's dtype
    small = rng.integers(0, 100, 16).astype(np.int16)
    got = jops.gather_rows(small, idx, on_device=True)
    assert got.dtype == small.dtype
    np.testing.assert_array_equal(got, small[idx])


def test_join_probe_keys_in_padded_tail():
    """Regression for the pow2-pad clip (`lo/hi` clamped to nr): probe keys
    sorting past every real build key — including the maximum legal packed
    key (2^62-1), the closest a key gets to the oracle's int64-max fill and
    the word-pair +inf sentinel — must come back with lo == hi == nr on all
    three tiers, never a phantom match against the padding."""
    top = MAXID                                       # max per-column id
    # non-pow2 build size -> real padding tail on the jitted/pallas tiers
    rcs = [np.arange(3, 20, dtype=np.int64),
           np.arange(17, dtype=np.int64)]
    nr = len(rcs[0])
    # probe keys strictly above every build key, up to the (2^62)-1 envelope
    lcs = [np.array([top, top, MAXID // 2 + 1], np.int64),
           np.array([top, 0, 0], np.int64)]
    ref_order, ref_lo, ref_counts = jops.hash_probe_numpy(lcs, rcs)
    assert (ref_counts == 0).all() and (ref_lo == nr).all()
    for tier, got in (
            ("oracle", jops.hash_probe_oracle(lcs, rcs)),
            ("pallas", jops.hash_probe(lcs, rcs, use_kernel=True,
                                       interpret=True)),
            ("auto", jops.hash_probe(lcs, rcs))):
        order, lo, counts = got
        np.testing.assert_array_equal(order, ref_order, err_msg=tier)
        np.testing.assert_array_equal(lo, ref_lo, err_msg=tier)
        np.testing.assert_array_equal(counts, ref_counts, err_msg=tier)
    # a probe key *equal* to a build key that sits at the padded boundary
    # still matches exactly once
    lcs_eq = [rcs[0][-1:].copy(), rcs[1][-1:].copy()]
    for kw in ({}, {"use_kernel": True, "interpret": True}):
        _, lo, counts = jops.hash_probe(lcs_eq, rcs, **kw)
        assert counts[0] == 1 and lo[0] == nr - 1


# --------------------------------------------------------------------------- #
# segmented ragged expansion + the fused pipeline
# --------------------------------------------------------------------------- #

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**20))
def test_expand_tiers_bit_identical(seed):
    """Property: the expansion kernel (interpret), the jitted searchsorted
    oracle, and host numpy return bit-identical int64 (li, pos) for random
    ragged runs. The pinned seeds cover the degenerate shapes: an empty run
    list, all-zero counts, and a single owning run; random draws from
    [0, 4) keep interior zero-count segments frequent."""
    rng = np.random.default_rng(seed)
    sel = seed % 5
    if sel == 0:
        counts = np.zeros(0, np.int64)                 # empty run list
    elif sel == 1:
        counts = np.zeros(6, np.int64)                 # all-zero counts
    elif sel == 2:
        counts = np.array([0, 0, 9, 0], np.int64)      # single-run total
    else:
        counts = rng.integers(0, 4, int(rng.integers(1, 40)))
    counts = np.asarray(counts, np.int64)
    lo = rng.integers(0, 1000, len(counts)).astype(np.int64)
    ref_li, ref_pos = jops.expand_pairs_numpy(lo, counts)
    for kw in ({}, {"use_kernel": False},
               {"use_kernel": True, "interpret": True}):
        li, pos = jops.expand_pairs(lo, counts, **kw)
        assert li.dtype == np.int64 and pos.dtype == np.int64, kw
        np.testing.assert_array_equal(li, ref_li, err_msg=str(kw))
        np.testing.assert_array_equal(pos, ref_pos, err_msg=str(kw))


def test_expand_segment_ids_matches_repeat(rng):
    lens = rng.integers(0, 9, 23).astype(np.int64)
    np.testing.assert_array_equal(jops.expand_segment_ids(lens),
                                  np.repeat(np.arange(23), lens))


def test_expand_kernel_contract_guards():
    """Forced kernel rejects out-of-int32-envelope runs (positions would
    truncate); auto serves a fallback tier instead."""
    lo = np.array([1 << 33], np.int64)
    counts = np.array([2], np.int64)
    with pytest.raises(ValueError, match="int32"):
        jops.expand_pairs(lo, counts, use_kernel=True, interpret=True)
    li, pos = jops.expand_pairs(lo, counts)            # auto -> host tier
    np.testing.assert_array_equal(pos, [1 << 33, (1 << 33) + 1])
    np.testing.assert_array_equal(li, [0, 0])


def _pipeline_fixture(rng, nl=257, nr=190):
    lcs = [rng.integers(0, 40, nl).astype(np.int64),
           rng.integers(0, 5, nl).astype(np.int64)]
    rcs = [rng.integers(0, 40, nr).astype(np.int64),
           rng.integers(0, 5, nr).astype(np.int64)]
    order, lo, counts = jops.hash_probe_numpy(lcs, rcs)
    li, pos = jops.expand_pairs_numpy(lo, counts)
    return lcs, rcs, (li, order[pos], int(counts.sum()))


def test_join_pipeline_tiers_match_staged_reference(rng):
    """Every fused-pipeline tier reproduces the staged probe+expand+gather
    reference bit-exactly (pair enumeration order included)."""
    lcs, rcs, (ref_li, ref_ri, ref_total) = _pipeline_fixture(rng)
    assert ref_total > 0
    for mode, kw in (("numpy", {}), ("oracle", {}), ("auto", {}),
                     ("pallas", {"use_kernel": True, "interpret": True})):
        li, ri, total = jops.hash_join_pipeline(lcs, rcs, mode=mode, **kw)
        assert total == ref_total, mode
        assert li.dtype == np.int64 and ri.dtype == np.int64, mode
        np.testing.assert_array_equal(li, ref_li, err_msg=mode)
        np.testing.assert_array_equal(ri, ref_ri, err_msg=mode)
    with pytest.raises(ValueError, match="unknown pipeline mode"):
        jops.hash_join_pipeline(lcs, rcs, mode="cuda")


def test_join_pipeline_empty_sides(rng):
    empty = [np.empty(0, np.int64), np.empty(0, np.int64)]
    full = [rng.integers(0, 9, 8).astype(np.int64),
            rng.integers(0, 9, 8).astype(np.int64)]
    for lcs, rcs in ((empty, full), (full, empty), (empty, empty)):
        for mode in ("numpy", "oracle", "pallas"):
            li, ri, total = jops.hash_join_pipeline(lcs, rcs, mode=mode)
            assert total == 0 and len(li) == 0 and len(ri) == 0


def test_join_pipeline_transfers_strictly_below_staged(rng):
    """The fused pipeline's claim, measured: fewer host<->device crossings
    than running the same tier staged (probe op + host expand + gather op),
    on both device tiers."""
    lcs, rcs, _ = _pipeline_fixture(rng)

    def staged(probe_fn, gather_kw):
        order, lo, counts = probe_fn()
        li, pos = jops.expand_pairs_numpy(lo, counts)
        jops.gather_rows(order, pos, assume_inbounds=True, **gather_kw)

    for label, fused_kw, probe_fn, gather_kw in (
            ("oracle", {"mode": "oracle"},
             lambda: jops.hash_probe_oracle(lcs, rcs), {}),
            ("pallas", {"mode": "pallas", "use_kernel": True,
                        "interpret": True},
             lambda: jops.hash_probe(lcs, rcs, use_kernel=True,
                                     interpret=True),
             {"on_device": True})):
        with jops.track_transfers() as fused:
            jops.hash_join_pipeline(lcs, rcs, **fused_kw)
        with jops.track_transfers() as stag:
            staged(probe_fn, gather_kw)
        assert fused.total < stag.total, (label, fused, stag)
        assert fused.d2h <= stag.d2h, (label, fused, stag)
    # the host tier never crosses the boundary at all
    with jops.track_transfers() as host:
        jops.hash_join_pipeline(lcs, rcs, mode="numpy")
    assert host.total == 0


def test_join_pipeline_cap_fires_before_materialization(rng):
    lcs, rcs, (_, _, total) = _pipeline_fixture(rng)
    for mode, kw in (("numpy", {}), ("oracle", {}),
                     ("pallas", {"use_kernel": True, "interpret": True})):
        li, ri, got = jops.hash_join_pipeline(lcs, rcs, mode=mode,
                                              max_total=total, **kw)
        assert got == total
        with pytest.raises(jops.ExpansionCapExceeded, match=f"{total} rows"):
            jops.hash_join_pipeline(lcs, rcs, mode=mode,
                                    max_total=total - 1, **kw)


def test_join_pipeline_per_stage_envelope_fallbacks(rng, monkeypatch):
    """Past the probe/expand envelopes the pallas pipeline swaps
    single stages for their device oracles (never the whole join to host):
    results stay bit-identical and no kernel compile for a fake TPU is
    attempted."""
    lcs, rcs, (ref_li, ref_ri, ref_total) = _pipeline_fixture(rng)
    monkeypatch.setenv("REPRO_KERNEL_THRESHOLD", "10")
    monkeypatch.setenv("REPRO_JOIN_PROBE_WORK_CAP", "100")
    monkeypatch.setenv("REPRO_JOIN_EXPAND_WORK_CAP", "100")
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    # interpret pinned: the un-guarded pack stage still runs its kernel,
    # which must not try to compile for the faked TPU on this CPU host
    li, ri, total = jops.hash_join_pipeline(lcs, rcs, mode="pallas",
                                            interpret=True)
    assert total == ref_total
    np.testing.assert_array_equal(li, ref_li)
    np.testing.assert_array_equal(ri, ref_ri)


# --------------------------------------------------------------------------- #
# the empirical dispatch autotuner
# --------------------------------------------------------------------------- #

def _m(work, kernel_us, fallback_us):
    return autotune.Measurement("probe", work, kernel_us, fallback_us)


def test_autotune_crossover_logic():
    """Synthetic sweeps pin the envelope arithmetic: never-wins -> 0,
    always-wins -> default, bracketed -> geometric midpoint."""
    default = 1 << 32
    assert autotune.crossover_cap(
        [_m(100, 9, 1), _m(10_000, 90, 1)], default=default) == 0
    assert autotune.crossover_cap(
        [_m(100, 1, 9), _m(10_000, 1, 90)], default=default) == default
    cap = autotune.crossover_cap(
        [_m(100, 1, 2), _m(10_000, 5, 2), _m(10**6, 50, 2)],
        default=default)
    assert cap == int(np.sqrt(100 * 10_000))           # 1000
    # noise below the last win doesn't truncate the envelope
    assert autotune.crossover_cap(
        [_m(10, 9, 1), _m(100, 1, 2), _m(10_000, 5, 2)],
        default=default) == int(np.sqrt(100 * 10_000))
    assert autotune.crossover_cap([], default=default) == 0


def test_autotune_tune_join_with_synthetic_timer():
    """tune_join sweeps kernel-vs-fallback per stage through an injectable
    timer; a clock that always favors the fallback pins every cap to 0, one
    that favors the kernel keeps the analytical defaults."""
    slow_kernel = iter([5.0, 1.0] * 100)
    prof = autotune.tune_join(quick=True,
                              timer=lambda fn: next(slow_kernel))
    assert all(v == 0 for v in prof.envelopes.values())
    assert {m.stage for m in prof.measurements} == {"probe", "expand"}
    fast_kernel = iter([1.0, 5.0] * 100)
    prof = autotune.tune_join(quick=True,
                              timer=lambda fn: next(fast_kernel))
    assert prof.envelopes[autotune.PROBE_CAP] == 1 << 32
    assert prof.envelopes[autotune.EXPAND_CAP] == 1 << 32


def test_autotune_profile_roundtrip_and_resolution_order(tmp_path,
                                                         monkeypatch):
    """A recorded profile survives JSON save/load; dispatch resolves
    env var > installed profile > hard-coded default."""
    from repro.kernels.join import ops as live_ops

    prof = autotune.DispatchProfile(
        envelopes={autotune.PROBE_CAP: 123, autotune.EXPAND_CAP: 456},
        backend="tpu",
        measurements=[_m(100, 1.0, 2.0)])
    path = tmp_path / "profile.json"
    prof.save(str(path))
    back = autotune.DispatchProfile.load(str(path))
    assert back.envelopes == prof.envelopes
    assert back.backend == "tpu"
    assert back.measurements[0].work == 100

    try:
        # default, then profile, then env var — later layers win
        dispatch.clear_profile()
        assert live_ops._probe_work_cap() == 1 << 32
        back.install()
        assert live_ops._probe_work_cap() == 123
        assert live_ops._expand_work_cap() == 456
        assert (dispatch.kernel_threshold()                  # not recorded
                == dispatch.DEFAULT_KERNEL_THRESHOLD)
        monkeypatch.setenv(autotune.PROBE_CAP, "77")
        assert live_ops._probe_work_cap() == 77
        assert live_ops._expand_work_cap() == 456            # env only wins
        monkeypatch.delenv(autotune.PROBE_CAP)

        # the REPRO_DISPATCH_PROFILE env var names a profile JSON
        dispatch.clear_profile()
        monkeypatch.setenv("REPRO_DISPATCH_PROFILE", str(path))
        assert live_ops._probe_work_cap() == 123
    finally:
        monkeypatch.undo()
        dispatch.clear_profile()
