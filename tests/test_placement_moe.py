"""AWAPart-in-the-framework: expert/vocab placement + MoE dispatch parity.

The multi-device MoE dispatch equivalence runs in a subprocess (it needs 8
host devices, and device count is locked at first jax init)."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ArchConfig
from repro.core import placement
from repro.models import moe

REPO = Path(__file__).resolve().parent.parent


def _moe_cfg(**kw):
    base = dict(arch_id="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                n_kv_heads=2, d_ff=32, vocab_size=64, n_experts=8, top_k=2,
                capacity_factor=8.0, param_dtype="float32",
                compute_dtype="float32")
    base.update(kw)
    return ArchConfig(**base)


def test_placement_reduces_dispatch_bytes(rng):
    e, r, t, k = 32, 4, 512, 4
    topics = rng.permutation(e).reshape(8, 4)
    req_topic = rng.integers(0, 8, t)
    routing = np.stack([rng.permutation(topics[ti])[:k] for ti in req_topic])
    e2r, rep = placement.plan_expert_placement(routing, e, r)
    assert rep.accepted
    assert rep.ranks_after < rep.ranks_before
    assert rep.bytes_saved_frac > 0.3
    assert (np.bincount(e2r, minlength=r) == e // r).all()   # balance


def test_placement_reverts_when_no_gain(rng):
    """Uniform random routing: clustering can't help -> guard reverts."""
    e, r = 16, 4
    routing = rng.integers(0, e, (256, 4))
    old = np.repeat(np.arange(r), e // r).astype(np.int32)
    e2r, rep = placement.plan_expert_placement(routing, e, r,
                                               old_expert_to_rank=old)
    if not rep.accepted:
        assert (e2r == old).all()
        assert rep.moved_experts == 0


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=15, deadline=None)
def test_placement_is_valid_permutation(seed):
    rng = np.random.default_rng(seed)
    e, r = 16, 4
    routing = rng.integers(0, e, (64, 3))
    e2r, _ = placement.plan_expert_placement(routing, e, r)
    perm = placement.rank_map_to_perm(e2r)
    assert sorted(perm.tolist()) == list(range(e))
    assert (np.bincount(e2r, minlength=r) == e // r).all()


def test_apply_placement_preserves_function(rng):
    cfg = _moe_cfg()
    p, _ = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16))
    y0, _ = moe.moe_apply_dense(p, x, cfg)
    e2r = placement.plan_expert_placement(
        rng.integers(0, 8, (64, 2)), 8, 2)[0]
    p2 = placement.apply_expert_placement(p, e2r)
    y1, _ = moe.moe_apply_dense(p2, x, cfg)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)


def test_vocab_permutation_balances_bpe_order():
    v = 4096
    counts = 1.0 / (np.arange(v) + 100.0) ** 0.9   # BPE-like: hot ids first
    ident = placement.shard_gather_imbalance(
        counts, np.arange(v, dtype=np.int32), 16)
    perm = placement.vocab_permutation(counts, 16)
    placed = placement.shard_gather_imbalance(counts, perm, 16)
    assert sorted(perm.tolist()) == list(range(v))
    assert ident > 2.0
    assert placed < 1.05


_MOE_SUBPROCESS = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import ArchConfig
from repro.models import moe
from repro.core import placement

cfg = ArchConfig(arch_id="t", family="moe", n_layers=1, d_model=32,
                 n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                 n_experts=8, top_k=2, capacity_factor=8.0,
                 param_dtype="float32", compute_dtype="float32")
p, _ = moe.moe_init(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
y_dense, _ = moe.moe_apply_dense(p, x, cfg)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
ctx = moe.ShardCtx(mesh=mesh, dp_axes=("data",))
with jax.set_mesh(mesh):
    y_e, _ = moe.moe_apply(p, x, cfg, ctx)
    y_r, _ = moe.moe_apply(p, x,
                           dataclasses.replace(cfg, moe_dispatch="rank"), ctx)
assert float(jnp.abs(y_e - y_dense).max()) < 1e-5, "expert dispatch"
assert float(jnp.abs(y_r - y_dense).max()) < 1e-5, "rank dispatch"
# migrated placement preserves function in both modes
rng = np.random.default_rng(0)
e2r = placement.plan_expert_placement(rng.integers(0, 8, (64, 2)), 8, 4)[0]
p2 = placement.apply_expert_placement(p, e2r)
with jax.set_mesh(mesh):
    y_e2, _ = moe.moe_apply(p2, x, cfg, ctx)
    y_r2, _ = moe.moe_apply(p2, x,
                            dataclasses.replace(cfg, moe_dispatch="rank"), ctx)
assert float(jnp.abs(y_e2 - y_dense).max()) < 1e-5, "expert post-migration"
assert float(jnp.abs(y_r2 - y_dense).max()) < 1e-5, "rank post-migration"
print("MOE-SHARDED-OK")
"""


@pytest.mark.slow
def test_moe_sharded_dispatch_parity_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", _MOE_SUBPROCESS],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=600)
    assert "MOE-SHARDED-OK" in res.stdout, res.stderr[-2000:]
