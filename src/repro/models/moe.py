"""Mixture-of-Experts with expert-parallel dispatch over the ``model`` axis.

Two dispatch modes (``cfg.moe_dispatch``):

* ``"expert"`` — GShard-style baseline: every (token, routed-expert) pair is
  shipped to the expert's rank in per-expert capacity buffers.
* ``"rank"`` — **AWAPart-placed dispatch**: the paper's insight mapped to MoE.
  Experts are placed on ranks by workload-aware clustering (see
  ``core/placement.py``); a token is shipped **once per distinct rank**
  owning any of its top-k experts (the federated-query SERVICE-call dedup),
  so co-locating co-activated experts directly cuts all-to-all bytes —
  exactly as co-locating query features cuts distributed joins.

The logical→physical expert map lives in ``params["inv_perm"]`` (int32, not
trained); migration = permuting the stacked expert weights + updating the map
(the analogue of exchanging triples between shards + updating PMeta).

A dense reference path (``moe_apply_dense``) computes the identical function
without collectives for unit tests and 1-device smoke runs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import Axes, Params, _dtype, dense_init


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context threaded through model apply fns."""
    mesh: Any                       # jax.sharding.Mesh
    dp_axes: Tuple[str, ...]        # batch axes, e.g. ("pod", "data")
    tp_axis: str = "model"
    seq_shard_moe: bool = True      # shard tokens over tp for dispatch

    @property
    def tp(self) -> int:
        return self.mesh.shape[self.tp_axis]


def moe_init(key, cfg: ArchConfig) -> Tuple[Params, Axes]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: Params = {}
    a: Axes = {}
    p["wr"], a["wr"] = dense_init(ks[0], (d, e), ("embed", None), jnp.float32)
    p["wg"], a["wg"] = dense_init(ks[1], (e, d, f), ("experts", "embed", None), dt, fan_in=d)
    p["wi"], a["wi"] = dense_init(ks[2], (e, d, f), ("experts", "embed", None), dt, fan_in=d)
    p["wo"], a["wo"] = dense_init(ks[3], (e, f, d), ("experts", None, "embed"), dt, fan_in=f)
    # logical expert -> physical slot (identity until AWAPart placement runs)
    p["inv_perm"], a["inv_perm"] = jnp.arange(e, dtype=jnp.int32), (None,)
    return p, a


def _router(p: Params, x2d: jnp.ndarray, cfg: ArchConfig):
    """Top-k routing in f32. x2d: (T, d) -> weights/ids (T, k), aux loss."""
    logits = (x2d.astype(jnp.float32) @ p["wr"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)                 # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    frac = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
        1.0 / topi.size)
    aux = e * (frac * probs.mean(0)).sum()
    return topw, topi, aux


def _expert_ffn(wg, wi, wo, x, cfg: ArchConfig):
    """x: (E_loc, C, d) grouped tokens -> (E_loc, C, d)."""
    cd = _dtype(cfg.compute_dtype)
    h = jnp.einsum("ecd,edf->ecf", x, wi.astype(cd))
    if cfg.activation == "silu":
        g = jnp.einsum("ecd,edf->ecf", x, wg.astype(cd))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(cd))


# --------------------------------------------------------------------------- #
# dense reference (no collectives)
# --------------------------------------------------------------------------- #

def moe_apply_dense(p: Params, x: jnp.ndarray, cfg: ArchConfig,
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    cd = _dtype(cfg.compute_dtype)
    b, s, d = x.shape
    x2 = x.reshape(-1, d).astype(cd)
    topw, topi, aux = _router(p, x2, cfg)
    y = jnp.zeros_like(x2)
    for e in range(cfg.n_experts):          # fine for reduced test configs
        w_e = (topw * (topi == e)).sum(-1)                     # (T,)
        slot = p["inv_perm"][e]             # logical expert -> physical slot
        h = x2 @ p["wi"][slot].astype(cd)
        if cfg.activation == "silu":
            h = jax.nn.silu(x2 @ p["wg"][slot].astype(cd)) * h
        else:
            h = jax.nn.gelu(h)
        y = y + (h @ p["wo"][slot].astype(cd)) * w_e[:, None].astype(cd)
    return y.reshape(b, s, d).astype(x.dtype), aux


# --------------------------------------------------------------------------- #
# sharded dispatch helpers
# --------------------------------------------------------------------------- #

def _positions_in_group(group_ids: jnp.ndarray, n_groups: int):
    """Stable sort pair ids by group; return order, sorted ids and intra-group
    positions (all static shapes)."""
    order = jnp.argsort(group_ids, stable=True)
    sorted_ids = group_ids[order]
    counts = jnp.zeros((n_groups,), jnp.int32).at[group_ids].add(
        1, mode="drop")
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(group_ids.shape[0], dtype=jnp.int32) - starts[sorted_ids]
    return order, sorted_ids, pos


def _capacity(tokens: int, k: int, n_groups: int, cf: float) -> int:
    c = int(np.ceil(tokens * k * cf / n_groups))
    return max(8, (c + 7) // 8 * 8)


def _moe_expert_dispatch_block(p: Params, x_loc: jnp.ndarray,
                               cfg: ArchConfig, tp: int, tp_axis: str):
    """Inside-shard_map body, expert-granularity (GShard baseline)."""
    cd = _dtype(cfg.compute_dtype)
    t_loc, d = x_loc.shape
    e, e_loc = cfg.n_experts, cfg.n_experts // tp
    topw, topi, aux = _router(p, x_loc, cfg)
    slots = p["inv_perm"][topi]                                   # physical
    cap = _capacity(t_loc, cfg.top_k, e, cfg.capacity_factor)

    pair_slot = slots.reshape(-1)
    pair_tok = jnp.repeat(jnp.arange(t_loc, dtype=jnp.int32), cfg.top_k)
    order, sorted_slot, pos = _positions_in_group(pair_slot, e)
    sorted_tok = pair_tok[order]
    keep = pos < cap
    scat_e = jnp.where(keep, sorted_slot, e)                      # drop rows
    buf = jnp.zeros((e, cap, d), cd).at[scat_e, jnp.minimum(pos, cap - 1)] \
        .set(x_loc[sorted_tok].astype(cd), mode="drop")

    # ship: (E, C, d) -> all_to_all over tp -> (tp, E_loc, C, d) source-major
    recv = jax.lax.all_to_all(buf, tp_axis, split_axis=0, concat_axis=0,
                              tiled=True)
    recv = recv.reshape(tp, e_loc, cap, d).transpose(1, 0, 2, 3) \
        .reshape(e_loc, tp * cap, d)
    out = _expert_ffn(p["wg"], p["wi"], p["wo"], recv, cfg)
    out = out.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3) \
        .reshape(e, cap, d)
    back = jax.lax.all_to_all(out, tp_axis, split_axis=0, concat_axis=0,
                              tiled=True)

    vals = back[jnp.minimum(sorted_slot, e - 1), jnp.minimum(pos, cap - 1)]
    vals = jnp.where(keep[:, None], vals, 0)
    w_sorted = topw.reshape(-1)[order].astype(cd)
    y = jnp.zeros((t_loc, d), cd).at[sorted_tok].add(vals * w_sorted[:, None])
    return y, aux


def _moe_rank_dispatch_block(p: Params, x_loc: jnp.ndarray,
                             cfg: ArchConfig, tp: int, tp_axis: str):
    """AWAPart mode: one shipment per distinct destination *rank* per token."""
    cd = _dtype(cfg.compute_dtype)
    t_loc, d = x_loc.shape
    e, e_loc = cfg.n_experts, cfg.n_experts // tp
    k = cfg.top_k
    topw, topi, aux = _router(p, x_loc, cfg)
    slots = p["inv_perm"][topi]                                   # (T, k)
    ranks = slots // e_loc

    # distinct destination ranks per token
    rank_hit = jnp.zeros((t_loc, tp), bool).at[
        jnp.repeat(jnp.arange(t_loc), k), ranks.reshape(-1)].set(
        True, mode="drop")
    cap_r = _capacity(t_loc, min(k, tp), tp, cfg.capacity_factor)
    pos2d = jnp.cumsum(rank_hit.astype(jnp.int32), axis=0) - 1    # (T, tp)
    keep = rank_hit & (pos2d < cap_r)

    tok_ids = jnp.broadcast_to(jnp.arange(t_loc, dtype=jnp.int32)[:, None],
                               (t_loc, tp))
    r_ids = jnp.broadcast_to(jnp.arange(tp, dtype=jnp.int32)[None, :],
                             (t_loc, tp))
    scat_r = jnp.where(keep, r_ids, tp)
    scat_c = jnp.minimum(pos2d, cap_r - 1)
    xbuf = jnp.zeros((tp, cap_r, d), cd).at[scat_r, scat_c].set(
        jnp.broadcast_to(x_loc[:, None, :].astype(cd), (t_loc, tp, d)),
        mode="drop")
    slotbuf = jnp.full((tp, cap_r, k), -1, jnp.int32).at[scat_r, scat_c].set(
        jnp.broadcast_to(slots[:, None, :], (t_loc, tp, k)), mode="drop")
    wbuf = jnp.zeros((tp, cap_r, k), jnp.float32).at[scat_r, scat_c].set(
        jnp.broadcast_to(topw[:, None, :], (t_loc, tp, k)), mode="drop")
    tokbuf = jnp.full((tp, cap_r), -1, jnp.int32).at[scat_r, scat_c].set(
        tok_ids, mode="drop")

    a2a = functools.partial(jax.lax.all_to_all, axis_name=tp_axis,
                            split_axis=0, concat_axis=0, tiled=True)
    xr, slotr, wr_ = a2a(xbuf), a2a(slotbuf), a2a(wbuf)
    r_tot = tp * cap_r
    xr = xr.reshape(r_tot, d)
    my_rank = jax.lax.axis_index(tp_axis)
    local_slot = slotr.reshape(r_tot, k) - my_rank * e_loc
    wr2 = wr_.reshape(r_tot, k)
    valid = (local_slot >= 0) & (local_slot < e_loc) & (wr2 > 0)

    # second-level (local) dispatch: jobs = (received token, local expert)
    job_e = jnp.where(valid, local_slot, e_loc).reshape(-1)       # (R*k,)
    job_tok = jnp.repeat(jnp.arange(r_tot, dtype=jnp.int32), k)
    cap_e = _capacity(t_loc * tp, k, e, cfg.capacity_factor)      # jobs per expert
    order, sorted_e, pos = _positions_in_group(job_e, e_loc + 1)
    sorted_tok = job_tok[order]
    keep_j = (sorted_e < e_loc) & (pos < cap_e)
    scat_e = jnp.where(keep_j, sorted_e, e_loc)
    xe = jnp.zeros((e_loc, cap_e, d), cd).at[
        scat_e, jnp.minimum(pos, cap_e - 1)].set(
        xr[sorted_tok], mode="drop")
    he = _expert_ffn(p["wg"], p["wi"], p["wo"], xe, cfg)
    # local combine back to received-token rows, weighted
    w_sorted = wr2.reshape(-1)[order].astype(cd)
    vals = he[jnp.minimum(sorted_e, e_loc - 1), jnp.minimum(pos, cap_e - 1)]
    vals = jnp.where(keep_j[:, None], vals, 0)
    yr = jnp.zeros((r_tot, d), cd).at[sorted_tok].add(
        vals * w_sorted[:, None])

    ybuf = a2a(yr.reshape(tp, cap_r, d))                          # back to sources
    flat_tok = tokbuf.reshape(-1)
    y = jnp.zeros((t_loc, d), cd).at[jnp.where(flat_tok >= 0, flat_tok, t_loc)] \
        .add(ybuf.reshape(-1, d), mode="drop")
    return y, aux


# --------------------------------------------------------------------------- #
# public sharded apply
# --------------------------------------------------------------------------- #

def moe_apply(p: Params, x: jnp.ndarray, cfg: ArchConfig,
              ctx: Optional[ShardCtx]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss). With a ShardCtx, runs the expert-parallel
    path under shard_map; without, the dense reference."""
    if ctx is None or ctx.tp * int(np.prod([ctx.mesh.shape[a] for a in ctx.dp_axes])) == 1:
        return moe_apply_dense(p, x, cfg)

    b, s, d = x.shape
    tp = ctx.tp
    block = (_moe_rank_dispatch_block if cfg.moe_dispatch == "rank"
             else _moe_expert_dispatch_block)

    # token sharding for dispatch: seq over tp when divisible (train/prefill),
    # else batch-only (decode)
    seq_tp = ctx.seq_shard_moe and (s % tp == 0) and s >= tp
    x_spec = (P(ctx.dp_axes, ctx.tp_axis, None) if seq_tp
              else P(ctx.dp_axes, None, None))
    w_spec = {"wr": P(None, None), "wg": P(ctx.tp_axis, None, None),
              "wi": P(ctx.tp_axis, None, None), "wo": P(ctx.tp_axis, None, None),
              "inv_perm": P(None)}

    def body(p_loc, x_loc):
        bl, sl, _ = x_loc.shape
        y, aux = block(p_loc, x_loc.reshape(bl * sl, d), cfg, tp, ctx.tp_axis)
        aux = jax.lax.pmean(aux, ctx.tp_axis)
        for ax in ctx.dp_axes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(bl, sl, d).astype(x.dtype), aux

    # check_vma=False: in decode (batch-only sharding) the tokens are
    # replicated over the tp axis; every rank reconstructs the identical
    # combined output after the return all_to_all, which the static
    # replication checker cannot infer.
    y, aux = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p, x)
    return y, aux
