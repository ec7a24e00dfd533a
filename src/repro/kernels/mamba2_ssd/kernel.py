"""Pallas TPU kernel: chunked Mamba2 SSD (state-space dual) scan.

Grid = (B*H, S/C); the chunk axis is sequential and carries the (N, hd)
state in VMEM scratch. Within a chunk everything is matmuls (MXU):

    cum      = cumsum(dt * a)                      (C,)   a < 0 ⇒ cum ↓
    att[t,j] = (c_t · b_j) e^{cum_t − cum_j} dt_j   (tril, incl. diagonal)
    y        = att @ x + (c e^{cum}) @ S_in + D x
    S_out    = e^{cum_last} S_in + (b · dt e^{cum_last − cum})ᵀ @ x

All exponents are of non-positive values (uniform-sign decay), so unlike
RWKV6 there is no overflow hazard and chunks can be large (256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, s0_ref,
                y_ref, sout_ref, state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    x = x_ref[0].astype(jnp.float32)                   # (C, hd)
    bm = b_ref[0].astype(jnp.float32)                  # (C, N)
    cm = c_ref[0].astype(jnp.float32)                  # (C, N)
    dt = dt_ref[0].astype(jnp.float32)                 # (C,)
    a = a_ref[0].astype(jnp.float32)                   # scalar (per head)
    d = d_ref[0].astype(jnp.float32)
    s_in = state_ref[...]                              # (N, hd)

    da = dt * a                                        # (C,) <= 0
    cum = jnp.cumsum(da)
    seg = cum[:, None] - cum[None, :]                  # (C, C), tril <= 0
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.where(ti >= tj, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    att = scores * lmat * dt[None, :]
    y = jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + jax.lax.dot_general(cm * jnp.exp(cum)[:, None], s_in,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + d * x
    y_ref[0] = y.astype(y_ref.dtype)

    decay_to_end = jnp.exp(cum[-1] - cum) * dt         # (C,)
    contrib = jax.lax.dot_general(bm * decay_to_end[:, None], x,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    s_new = jnp.exp(cum[-1]) * s_in + contrib
    state_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _finalize():
        sout_ref[0] = s_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, b, c, dt, a, d, s0, *, chunk: int = 128,
               interpret: bool = False):
    """x: (B, S, H, hd); b/c: (B, S, N) (single group, shared across heads);
    dt: (B, S, H) post-softplus; a/d: (H,); s0: (B, H, N, hd)."""
    bb, s, h, hd = x.shape
    n = b.shape[-1]
    cs = min(chunk, s)
    assert s % cs == 0, (s, cs)
    bh = bb * h

    xf = x.transpose(0, 2, 1, 3).reshape(bh, s, hd).astype(jnp.float32)
    dtf = dt.transpose(0, 2, 1).reshape(bh, s).astype(jnp.float32)
    af = jnp.broadcast_to(a[None], (bb, h)).reshape(bh).astype(jnp.float32)
    df = jnp.broadcast_to(d[None], (bb, h)).reshape(bh).astype(jnp.float32)
    s0f = s0.reshape(bh, n, hd).astype(jnp.float32)

    y, s_out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=cs, n_chunks=s // cs),
        grid=(bh, s // cs),
        in_specs=[
            pl.BlockSpec((1, cs, hd), lambda i, j: (i, j, 0)),
            # b/c are per-batch (group-shared): index i // H
            pl.BlockSpec((1, cs, n), lambda i, j, h_=h: (i // h_, j, 0)),
            pl.BlockSpec((1, cs, n), lambda i, j, h_=h: (i // h_, j, 0)),
            pl.BlockSpec((1, cs), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, n, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cs, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, hd), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bh, n, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, b.astype(jnp.float32), c.astype(jnp.float32), dtf, af, df, s0f)
    return (y.reshape(bb, h, s, hd).transpose(0, 2, 1, 3),
            s_out.reshape(bb, h, n, hd))
