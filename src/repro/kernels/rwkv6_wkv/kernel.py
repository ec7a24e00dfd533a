"""Pallas TPU kernel: chunked RWKV6 WKV with data-dependent decay.

The recurrence ``S_t = diag(w_t) S_{t-1} + k_t v_t^T`` is sequential per
channel, but within a chunk of C steps it closes to matmuls (the same
duality mamba2's SSD exploits):

    L      = inclusive cumsum of log w              (C, hd)
    A[t,j] = Σ_c r[t,c]·k[j,c]·exp(L[t-1,c] − L[j,c]),  j < t   (strict tril)
    y      = (A + diag-bonus(u)) @ V + (r·exp(L_ex)) @ S_in
    S_out  = exp(L_last) ∘ S_in + (k·exp(L_last − L))ᵀ @ V

Grid = (B*H, S/C); the chunk axis is sequential ("arbitrary") and carries the
(hd, hd) state in VMEM scratch. All math is f32 — ``exp(−L)`` grows like
``exp(0.7·C)`` for typical decays, so C ≤ 64 keeps it far from f32 overflow
(documented bound; the sweep tests assert it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)                   # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                   # (hd,)
    s_in = state_ref[...]                              # (hd, hd)

    logw = jnp.log(jnp.maximum(w, 1e-30))
    l_inc = jnp.cumsum(logw, axis=0)                   # L_t inclusive
    l_ex = l_inc - logw                                # L_{t-1} (exclusive)

    rr = r * jnp.exp(l_ex)                             # (C, hd); l_ex <= 0
    # Intra-chunk matrix via the bounded segment form: the factorized
    # (r e^{L_ex}) @ (k e^{-L_inc})^T overflows f32 for strong decays
    # (|log w|*C > 88); L_ex[t]-L_inc[j] <= 0 for j < t, so exponentiate
    # the (C, C, hd) difference directly — VPU-bound but overflow-free.
    d3 = l_ex[:, None, :] - l_inc[None, :, :]          # (C, C, hd)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where((ti > tj)[:, :, None], jnp.exp(d3), 0.0)
    a = (r[:, None, :] * k[None, :, :] * seg).sum(-1)  # (C, C), strict tril
    diag = ((r * u) * k).sum(axis=1)                   # (C,) bonus term
    y = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + diag[:, None] * v
    y = y + jax.lax.dot_general(rr, s_in, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    l_last = l_inc[-1]                                 # (hd,)
    k_tail = k * jnp.exp(l_last[None, :] - l_inc)      # (C, hd)
    s_new = jnp.exp(l_last)[:, None] * s_in + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _finalize():
        sout_ref[0] = s_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(r, k, v, w, u, s0, *, chunk: int = 64,
               interpret: bool = False):
    """r/k/v/w: (B, S, H, hd) f32; u: (H, hd); s0: (B, H, hd, hd)."""
    b, s, h, hd = r.shape
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    bh = b * h

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(bh, s, hd)

    rf, kf, vf, wf = (flat(x.astype(jnp.float32)) for x in (r, k, v, w))
    uf = jnp.broadcast_to(u[None], (b, h, hd)).reshape(bh, hd)
    s0f = s0.reshape(bh, hd, hd).astype(jnp.float32)

    seq_spec = pl.BlockSpec((1, c, hd), lambda i, j: (i, j, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=c, n_chunks=s // c),
        grid=(bh, s // c),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, hd), lambda i, j: (i, 0)),
                  pl.BlockSpec((1, hd, hd), lambda i, j: (i, 0, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, hd, hd), lambda i, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bh, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0f)
    y = y.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return y, s_out.reshape(b, h, hd, hd)
