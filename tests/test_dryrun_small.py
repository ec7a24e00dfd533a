"""Small-mesh dry-run: lower + compile reduced cells on 8 fake devices.

Runs in a subprocess because the placeholder device count must be set before
jax initializes (the main test process keeps the single real CPU device)."""
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax
import repro.configs as configs
from repro.launch import hlo_analysis, sharding
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.models import lm, transformer
from repro.models.moe import ShardCtx
from repro.optim import AdamWConfig, adamw_init

mesh = make_host_mesh(data=2, model=4)
for arch in ("smollm-360m", "olmoe-1b-7b", "rwkv6-3b", "zamba2-7b"):
    cfg = dataclasses.replace(
        configs.get(arch).reduced(),
        d_model=128, d_ff=256,
        n_heads=4 if configs.get(arch).n_heads else 0,
        n_kv_heads=4 if configs.get(arch).n_kv_heads else 0,
        head_dim=0)
    ctx = ShardCtx(mesh=mesh, dp_axes=dp_axes(mesh))
    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda: transformer.init_params(key, cfg)[0])
    _, axes = transformer.init_params(key, cfg)
    p_sh = sharding.tree_shardings(axes, params_sds, mesh, kind="param")
    opt_sds = jax.eval_shape(adamw_init, params_sds)
    o_sh = sharding.opt_state_shardings(axes, params_sds, opt_sds, mesh)
    batch_sds = {"tokens": jax.ShapeDtypeStruct((4, 32), jax.numpy.int32)}
    if cfg.embedding_inputs:
        continue
    b_sh = sharding.batch_specs(batch_sds, mesh)
    opt_cfg = AdamWConfig()

    def step(params, opt_state, batch, cfg=cfg, ctx=ctx):
        return lm.train_step(params, opt_state, batch, cfg, ctx, opt_cfg)

    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh)).lower(
            params_sds, opt_sds, batch_sds)
        compiled = lowered.compile()
    cost = dict(compiled.cost_analysis())
    coll = hlo_analysis.collective_bytes(compiled.as_text())
    assert cost.get("flops", 0) > 0, arch
    assert coll["total_count"] > 0, arch    # DP grads must sync
    assert compiled.memory_analysis() is not None or True
    print(f"{arch}: OK flops={cost['flops']:.2e} "
          f"coll={coll['total_bytes']:.2e}")
print("DRYRUN-SMALL-OK")
"""


@pytest.mark.slow
def test_small_mesh_dryrun_subprocess():
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        capture_output=True, text=True, timeout=900)
    assert "DRYRUN-SMALL-OK" in res.stdout, (res.stdout[-1000:],
                                             res.stderr[-2000:])
