"""Pallas TPU flash attention (train/prefill) and flash decode (serve).

TPU-native tiling: Q blocks × KV blocks staged through VMEM, online softmax
carried in VMEM scratch across the (sequential) KV grid dimension, MXU matmuls
at (block_q × dh) @ (dh × block_k). Block sizes default to 128 — the MXU
systolic width — and must divide the padded sequence lengths.

The dissimilarity hot loop of the bi-metric tower (the expensive D encoder)
spends >90% of its time here at prefill_32k shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      sm_scale: float, causal: bool, block_q: int,
                      block_k: int, kv_len: int, causal_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (block_q, dh)
    k = k_ref[0].astype(jnp.float32)  # (block_k, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (block_q, block_k)

    q_pos = (qi * block_q + causal_offset
             + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)  # (block_k, dv)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    sm_scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> Array:
    """q (B, H, Sq, dh); k, v (B, H, Skv, dh|dv) -> (B, H, Sq, dv)."""
    b, h, sq, dh = q.shape
    skv, dv = k.shape[2], v.shape[3]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)

    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sqp, skp = sq + pad_q, skv + pad_k

    qp = qp.reshape(b * h, sqp, dh)
    kp = kp.reshape(b * h, skp, dh)
    vp = vp.reshape(b * h, skp, dv)
    grid = (b * h, sqp // block_q, skp // block_k)

    kernel = functools.partial(
        _flash_fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=skv,
        causal_offset=skv - sq,  # queries sit at the end of the KV window
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out.reshape(b, h, sqp, dv)[:, :, :sq]


def _flash_decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, sm_scale: float,
                         block_k: int):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # (1, dh) — one (batch*head) row
    k = k_ref[0].astype(jnp.float32)  # (block_k, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # (1, block_k)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < len_ref[0]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q: Array, k: Array, v: Array, *, length: Array | int,
                 sm_scale: float | None = None, block_k: int = 512,
                 interpret: bool = False) -> Array:
    """q (B, H, dh); k, v (B, S, H, dh) -> (B, H, dh). One token vs KV cache."""
    b, h, dh = q.shape
    s = k.shape[1]
    dv = v.shape[3]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)
    block_k = min(block_k, s)
    pad = (-s) % block_k
    kp = jnp.moveaxis(k, 2, 1).reshape(b * h, s, dh)
    vp = jnp.moveaxis(v, 2, 1).reshape(b * h, s, dv)
    if pad:
        kp = jnp.pad(kp, ((0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(vp, ((0, 0), (0, pad), (0, 0)))
    qp = q.reshape(b * h, 1, dh)
    lens = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1, 1), (b, h)
    ).reshape(b * h, 1)
    grid = (b * h, (s + pad) // block_k)

    out = pl.pallas_call(
        functools.partial(_flash_decode_kernel, sm_scale=sm_scale, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, dh), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, dh), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1), lambda bh, ki: (bh, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, dv), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(qp, kp, vp, lens)
    return out.reshape(b, h, dv)
