"""Pallas TPU flash attention (forward), GQA-aware, cache/offset-aware.

Tiling: grid = (B, H, S/BQ, T/BK); the last (KV) grid axis is sequential and
carries the online-softmax state in VMEM scratch (acc (BQ, D) f32, plus row
max m and row sum l). Each program loads a (BQ, D) query tile and a (BK, D)
key/value tile for its head — MXU-aligned when BQ/BK/D are multiples of 128
(D=64 archs still lower; the MXU pads). KV tiles fully beyond the causal
horizon are skipped with ``pl.when`` so causal attention does half the work.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, q_offset: int, kv_valid_len: Optional[int],
                  bq: int, bk: int, n_kv_blocks: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq + q_offset
    k_start = ki * bk
    # skip KV tiles entirely above the causal diagonal
    needed = jnp.logical_or(not causal, k_start <= q_start + bq - 1)
    if kv_valid_len is not None:
        needed = jnp.logical_and(needed, k_start < kv_valid_len)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (BQ, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (BK, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), bool)
        if causal:
            ok = qpos >= kpos
        if kv_valid_len is not None:
            ok = jnp.logical_and(ok, kpos < kv_valid_len)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, :, 0, :] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_offset", "kv_valid_len",
                              "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, q_offset: int = 0,
                        kv_valid_len: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: (B, S, H, D); k/v: (B, T, K, D). Returns (B, S, H, D)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(block_q, s)
    bk = min(block_k, t)
    # pad S and T to block multiples
    sp = (s + bq - 1) // bq * bq
    tp = (t + bk - 1) // bk * bk
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    if tp != t:
        k = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        # padded kv slots must be masked out
        kv_valid_len = t if kv_valid_len is None else min(kv_valid_len, t)
    n_kv = tp // bk

    kernel = functools.partial(
        _flash_kernel, causal=causal, q_offset=q_offset,
        kv_valid_len=kv_valid_len, bq=bq, bk=bk, n_kv_blocks=n_kv,
        scale=1.0 / np.sqrt(d))

    out = pl.pallas_call(
        kernel,
        grid=(b, h, sp // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, 1, d), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec((1, bk, 1, d),
                         lambda b_, h_, qi, ki, g_=g: (b_, ki, h_ // g_, 0)),
            pl.BlockSpec((1, bk, 1, d),
                         lambda b_, h_, qi, ki, g_=g: (b_, ki, h_ // g_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, d),
                               lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sp, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),      # acc
            pltpu.VMEM((bq,), jnp.float32),        # running max
            pltpu.VMEM((bq,), jnp.float32),        # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s]
