import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed
on the 16×16 single-pod mesh and the 2×16×16 two-pod mesh for every supported
cell, and the compiled artifact yields memory/cost/collective statistics for
the roofline analysis (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch smollm-360m --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

import repro.configs as configs
from repro.configs.base import SHAPES, ArchConfig, shape_supported
from repro.launch import hlo_analysis, roofline, sharding
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import lm, transformer
from repro.models.moe import ShardCtx
from repro.optim import AdamWConfig, adamw_init


def _shape_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def lower_cell(cfg: ArchConfig, shape_name: str, mesh, *,
               donate: bool = True):
    """Build (lowered, compiled, meta) for one cell."""
    kind = SHAPES[shape_name]["kind"]
    ctx = ShardCtx(mesh=mesh, dp_axes=dp_axes(mesh))
    opt_cfg = AdamWConfig()
    key = jax.random.PRNGKey(0)

    params_sds = jax.eval_shape(lambda: transformer.init_params(key, cfg)[0])
    # the logical-axes tree contains strings (not jax types), so it cannot be
    # eval_shape'd; a reduced config has the identical tree structure and
    # identical axis names — materialize it cheaply from there.
    _, axes = transformer.init_params(jax.random.PRNGKey(0), cfg.reduced())
    profile = cfg.sharding_profile
    p_sh = sharding.tree_shardings(axes, params_sds, mesh, profile=profile,
                                   kind="param")

    batch_sds = lm.input_specs(cfg, shape_name)
    b_sh = sharding.batch_specs(batch_sds, mesh, profile=profile)

    if kind == "train":
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        o_sh = sharding.opt_state_shardings(axes, params_sds, opt_sds, mesh)

        def step(params, opt_state, batch):
            return lm.train_step(params, opt_state, batch, cfg, ctx, opt_cfg)

        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1) if donate else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sds, opt_sds, batch_sds)
    elif kind == "prefill":
        def step(params, batch):
            return lm.prefill_step(params, batch, cfg, ctx)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sds, batch_sds)
    else:  # decode
        cache_sds = lm.cache_specs(cfg, shape_name)
        c_axes = sharding.cache_axes(cfg)
        c_sh = sharding.tree_shardings(
            {k: c_axes[k] for k in cache_sds}, cache_sds, mesh,
            profile=profile)

        def step(params, caches, batch):
            return lm.decode_step(params, caches, batch, cfg, ctx)

        jitted = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,) if donate else ())
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sds, cache_sds, batch_sds)

    compiled = lowered.compile()
    return lowered, compiled


def extrapolated_costs(cfg: ArchConfig, shape_name: str, mesh) -> dict:
    """Per-layer cost extrapolation.

    XLA's HLO cost analysis counts a while-loop body once, so the scanned
    L-layer artifact under-reports flops/bytes/collectives by ~L. We compile
    two small *unrolled* variants (L_a, L_b layers) and extrapolate linearly:
    total(L) = cost(L_a) + (L - L_a) * (cost(L_b) - cost(L_a)) / (L_b - L_a).
    For zamba2 a third 1-layer point with ``attn_every=1`` isolates the
    shared attention block's per-application cost, since the L=1/2 points
    contain exactly one application each."""
    from repro.models.transformer import n_shared_apps

    def measure(l_small: int, attn_every: int | None = None) -> dict:
        over = dict(n_layers=l_small, scan_layers=False)
        if attn_every is not None:
            over["attn_every"] = attn_every
        cfg_s = dataclasses.replace(cfg, **over)
        _, compiled = lower_cell(cfg_s, shape_name, mesh, donate=False)
        cost = dict(compiled.cost_analysis())
        coll = hlo_analysis.collective_bytes(compiled.as_text())
        return dict(flops=float(cost.get("flops", 0.0)),
                    bytes=float(cost.get("bytes accessed", 0.0)),
                    coll=coll)

    # MoE cells: the L=1 point is unstable (dispatch-buffer layouts differ
    # between 1- and 2-layer modules), so use the (2, 4) pair instead.
    la_, lb_ = (2, 4) if cfg.is_moe else (1, 2)
    a = measure(la_)    # base + la layers (+1 shared app for hybrids)
    b = measure(lb_)    # base + lb layers (+1 shared app)
    l_full = cfg.n_layers
    extra_apps = 0
    c = None
    if cfg.attn_every:
        # apps(L=1) == apps(L=2) == 1; full model has n_shared_apps(cfg)
        extra_apps = n_shared_apps(cfg) - 1
        c = measure(2, attn_every=1)   # 2 layers + 2 shared apps

    def extrap(ka: float, kb: float, kc: float | None) -> float:
        per_layer = max((kb - ka) / (lb_ - la_), 0.0)
        total = ka + (l_full - la_) * per_layer
        if kc is not None and extra_apps:
            per_app = max(kc - kb, 0.0)
            total += extra_apps * per_app
        return max(total, 0.0)

    def coll_key(k):
        return extrap(a["coll"][k], b["coll"][k],
                      c["coll"][k] if c else None)

    out = dict(
        flops=extrap(a["flops"], b["flops"], c["flops"] if c else None),
        bytes=extrap(a["bytes"], b["bytes"], c["bytes"] if c else None),
        collectives={k: int(coll_key(k)) for k in a["coll"]},
        points=dict(l_a=la_, l_b=lb_, a=a, b=b, c=c, extra_apps=extra_apps))
    return out


def analyze_cell(cfg: ArchConfig, shape_name: str, mesh_name: str,
                 lowered, compiled, extrap: dict | None = None) -> dict:
    info = SHAPES[shape_name]
    kind = info["kind"]
    n_chips = 512 if mesh_name == "multi" else 256
    n_tokens = (info["global_batch"] * info["seq_len"]
                if kind in ("train", "prefill") else info["global_batch"])

    cost = dict(compiled.cost_analysis())
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    if extrap is not None:
        cost["flops"] = extrap["flops"]
        cost["bytes accessed"] = extrap["bytes"]
        coll = extrap["collectives"]
    mf = roofline.model_flops(cfg, shape_name, n_tokens, kind)
    rf = roofline.build(cost, coll, n_chips, mf)

    mem_stats = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            mem_stats[attr] = getattr(mem, attr, None)

    return dict(
        arch=cfg.arch_id, shape=shape_name, mesh=mesh_name, kind=kind,
        n_chips=n_chips, n_tokens=n_tokens,
        n_params=cfg.n_params(), n_active_params=cfg.n_active_params(),
        cost={k: v for k, v in cost.items()
              if k in ("flops", "bytes accessed", "transcendentals")},
        memory=mem_stats, collectives=coll, roofline=rf.to_dict(),
    )


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path,
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = configs.get(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    ok, reason = shape_supported(cfg, shape_name)
    name = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    out_path = out_dir / f"{name}.json"
    if not ok:
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                   skipped=True, reason=reason)
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[skip] {name}: {reason}")
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        lowered, compiled = lower_cell(cfg, shape_name, mesh)
        extrap = extrapolated_costs(cfg, shape_name, mesh)
        rec = analyze_cell(cfg, shape_name, mesh_name, lowered, compiled,
                           extrap)
        rec["extrapolation"] = extrap["points"]
        rec["compile_s"] = round(time.time() - t0, 1)
        rec["skipped"] = False
        out_path.write_text(json.dumps(rec, indent=1))
        r = rec["roofline"]
        print(f"[ok]   {name}: compile={rec['compile_s']}s "
              f"dominant={r['dominant']} "
              f"t=(c {r['t_compute']*1e3:.2f} | m {r['t_memory']*1e3:.2f} | "
              f"x {r['t_collective']*1e3:.2f}) ms "
              f"useful={r['useful_flops_ratio']:.2f} "
              f"frac={r['roofline_fraction']:.3f}")
        return rec
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                   skipped=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.time() - t0, 1))
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[FAIL] {name}: {type(e).__name__}: {str(e)[:200]}")
        return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--set", action="append", default=[],
                    help="config overrides, e.g. --set remat=dots")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        field_types = {f.name: f.type for f in
                       dataclasses.fields(ArchConfig)}
        if v in ("True", "False"):
            v = v == "True"
        elif v.isdigit():
            v = int(v)
        else:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v

    archs = configs.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                run_cell(arch, shape_name, mesh_name, out_dir,
                         overrides or None, args.tag)


if __name__ == "__main__":
    main()
