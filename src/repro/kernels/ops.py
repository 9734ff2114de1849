"""Jit'd public wrappers for the Pallas kernels, including the distributed
flash-decode combine (sequence-sharded KV + LSE merge via shard_map) — the
TPU-native answer to serving long contexts across chips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8_mm
from repro.kernels.paged_attention import (
    paged_decode_attention as _paged_decode,
    paged_decode_attention_ref as _paged_decode_ref,
    paged_suffix_attention as _paged_suffix,
    paged_suffix_attention_ref as _paged_suffix_ref)


def interpret_mode() -> bool:
    """True where no TPU backs the computation: Pallas kernels then run in
    interpret mode (and the paged wrappers take their jnp references).
    Asked at trace time, never at import, so importing this module
    initialises no backend."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "prefix",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, prefix=0,
                    block_q=128, block_k=128):
    return _flash(q, k, v, causal=causal, window=window, prefix=prefix,
                  block_q=block_q, block_k=block_k,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("window", "prefix", "block_k"))
def decode_attention(q, k_cache, v_cache, pos, *, window=0, prefix=0,
                     block_k=256):
    return _decode(q, k_cache, v_cache, pos, window=window, prefix=prefix,
                   block_k=block_k, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "block_k"))
def int8_matmul(x, w_q, scale, *, block_m=128, block_n=128, block_k=128):
    return _int8_mm(x, w_q, scale, block_m=block_m, block_n=block_n,
                    block_k=block_k, interpret=interpret_mode())


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           window=0, prefix=0):
    """Page-table-direct decode attention.  Routes to the Pallas kernel
    on TPU; the jittable fori_loop reference runs the identical schedule
    elsewhere and whenever `window` is traced (hymba's per-layer
    global/local mix)."""
    if interpret_mode() or not isinstance(window, int):
        return _paged_decode_ref(q, k_pool, v_pool, page_table, pos,
                                 window=window, prefix=prefix)
    return _paged_decode(q, k_pool, v_pool, page_table, pos,
                         window=window, prefix=prefix)


def paged_suffix_attention(q, k_pool, v_pool, page_table, pos):
    """Multi-query paged attention for speculative verify (plain causal):
    q (B, Q, H, hd) holds Q consecutive tokens per slot, the first at
    `pos` (B,).  Pallas kernel on TPU, the jnp reference elsewhere."""
    if interpret_mode():
        q_pos = pos[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)
        return _paged_suffix_ref(q, k_pool, v_pool, page_table, q_pos)
    return _paged_suffix(q, k_pool, v_pool, page_table, pos)


# --------------------------------------------------------------------- #
# Distributed flash-decode: KV sequence-sharded over `axis`, partial
# (num, denom, max) merged with tiny all-reduces — the collective-optimal
# decode for GQA models whose kv_heads don't divide the TP axis.

def _lse_partials(q, k_shard, v_shard, pos, kv_offset, *, window, prefix):
    """Single-shard partial attention with explicit (m, l, num) outputs,
    computed in pure jnp (the Pallas kernel's per-shard analogue)."""
    b, nkv, g, hd = q.shape
    s = k_shard.shape[2]
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    kf = k_shard.astype(jnp.float32)
    scores = jnp.einsum("bkgd,bksd->bkgs", qf, kf)
    slot = kv_offset + jnp.arange(s)
    valid = slot[None, :] <= pos[:, None]
    if window > 0:
        vis = slot[None, :] > (pos[:, None] - window)
        if prefix > 0:
            vis |= (slot < prefix)[None, :]
        valid &= vis
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)                             # (B,K,G)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    num = jnp.einsum("bkgs,bksd->bkgd", p,
                     v_shard.astype(jnp.float32))
    return m, l, num


def decode_attention_sharded(mesh: Mesh, axis: str):
    """Returns fn(q, k_cache, v_cache, pos) with k/v sequence-sharded over
    `axis`; each shard computes flash-decode partials locally, then a pair
    of small all-reduces (max + weighted sums) merges them — wire cost
    O(B*H*hd) instead of O(B*H*S)."""
    def fn(q, k_cache, v_cache, pos):
        b, nkv, g, hd = q.shape
        s = k_cache.shape[2]
        n_shards = mesh.shape[axis]
        shard_len = s // n_shards

        def shard_fn(q_, k_, v_, pos_):
            idx = jax.lax.axis_index(axis)
            m, l, num = _lse_partials(q_, k_, v_, pos_,
                                      idx * shard_len, window=0, prefix=0)
            m_g = jax.lax.pmax(m, axis)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, axis)
            num_g = jax.lax.psum(num * corr[..., None], axis)
            return (num_g / jnp.maximum(l_g[..., None], 1e-30)) \
                .astype(q_.dtype)

        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(None, None, axis, None),
                      P(None, None, axis, None), P()),
            out_specs=P(),
        )(q, k_cache, v_cache, pos)

    return fn
