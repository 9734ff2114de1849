"""Pallas TPU flash attention (prefill): GQA, causal, sliding-window,
always-visible prefix.

TPU-native tiling: grid (batch, q_heads, q_blocks, kv_blocks) with the
kv_blocks dim sequential ("arbitrary"); online-softmax state (m, l, acc)
lives in VMEM scratch across kv iterations.  Block shapes default to
(128, 128) — MXU-aligned (multiples of 128 on both matmul dims) and small
enough that q/k/v tiles + scratch fit VMEM:
    bq*hd + 2*bk*hd (bf16) + bq*bk + bq*hd + 2*bq (f32) ~ 0.25 MB << 16 MB.

Validated against ``ref.flash_attention_ref`` with interpret=True on CPU
(shape/dtype sweeps in tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale: float, block_q: int, block_k: int,
                  causal: bool, window: int, prefix: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kv_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # skip fully-masked blocks (beyond the causal frontier / window)
    run = True
    if causal:
        run = (ik * block_k) <= (iq * block_q + block_q - 1)
    if causal and window > 0:
        # block entirely left of the window AND not prefix-visible
        left_edge = iq * block_q - window
        in_reach = (ik * block_k + block_k - 1) > left_edge
        has_prefix = (ik * block_k) < prefix
        run = jnp.logical_and(run, jnp.logical_or(in_reach, has_prefix)) \
            if prefix > 0 else jnp.logical_and(run, in_reach)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, bk)
        if causal:
            mask = kv_pos <= q_pos
            if window > 0:
                inwin = kv_pos > q_pos - window
                if prefix > 0:
                    inwin = jnp.logical_or(inwin, kv_pos < prefix)
                mask = jnp.logical_and(mask, inwin)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)          # (bq, 1)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                              # (bq, bk)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (bq, hd)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    prefix: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, K, Skv, hd) with H % K == 0.
    Returns (B, H, Sq, hd)."""
    b, h, sq, hd = q.shape
    _, nkv, skv, _ = k.shape
    g = h // nkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    grid = (b, h, sq // block_q, skv // block_k)
    sm_scale = hd ** -0.5

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, causal=causal, window=window, prefix=prefix)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g_=g: (bi, hi // g_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki, g_=g: (bi, hi // g_, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
