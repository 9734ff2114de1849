"""Pallas TPU flash-decode: one new token attends to a long KV cache.

The cache's sequence dim is tiled into blocks; grid
(batch, kv_heads, kv_blocks) with kv_blocks sequential, carrying the
partial-softmax state (m, l, acc) for the G=H/K query heads of this kv head
in VMEM scratch.  Per-sequence valid length arrives via scalar prefetch
(`pos`, (B,) int32) — the SMEM-resident scalar drives block masking, so
ragged batches (continuous batching!) don't waste MXU work on dead blocks:
blocks entirely past pos[b] are skipped.

This kernel is the distributed flash-decode building block: when the cache
is sequence-sharded across chips, each chip runs it over its shard and the
(m, l, acc) partials are combined with a tiny LSE all-reduce
(`ops.decode_attention_sharded`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   sm_scale: float, block_k: int, window: int,
                   prefix: int):
    bi = pl.program_id(0)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[bi]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip blocks entirely beyond the valid cache region (ragged batch)
    blk_start = ik * block_k
    run = blk_start <= pos
    if window > 0:
        in_reach = (blk_start + block_k - 1) > (pos - window)
        if prefix > 0:
            in_reach = jnp.logical_or(in_reach, blk_start < prefix)
        run = jnp.logical_and(run, in_reach)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # (G, bk)
        kv_pos = blk_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kv_pos <= pos
        if window > 0:
            inwin = kv_pos > pos - window
            if prefix > 0:
                inwin = jnp.logical_or(inwin, kv_pos < prefix)
            mask = jnp.logical_and(mask, inwin)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1,
                                                 keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # (G, hd)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     prefix: int = 0, block_k: int = 256,
                     interpret: bool = False, return_lse: bool = False):
    """q: (B, K, G, hd) — new-token queries grouped by kv head;
    k_cache/v_cache: (B, K, S, hd); pos: (B,) int32 (current token index).
    Returns (B, K, G, hd) [+ (m, l) partials when return_lse]."""
    b, nkv, g, hd = q.shape
    s = k_cache.shape[2]
    block_k = min(block_k, s)
    assert s % block_k == 0
    grid = (b, nkv, s // block_k)
    kernel = functools.partial(
        _decode_kernel, sm_scale=hd ** -0.5, block_k=block_k,
        window=window, prefix=prefix)

    out_shapes = [jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype)]
    # NOTE: with scalar prefetch, index maps receive the scalar ref as an
    # extra trailing argument.
    out_specs = [pl.BlockSpec((1, 1, g, hd),
                              lambda bi, hi, ki, _p: (bi, hi, 0, 0))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, hi, ki, _p: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, ki, _p: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, ki, _p: (bi, hi, ki, 0)),
        ],
        out_specs=out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos, q, k_cache, v_cache)
    return out
