"""Pallas TPU paged decode attention: queries attend to KV pages through
the slot page table — no gathered logical view ever materializes.

The physical cache is the engine's flat page pool, (n_pages, page_size,
kv_heads, head_dim) per layer; each slot owns one row of the
``(n_slots, pages_per_slot)`` page table whose unused entries hold the
OOB sentinel ``n_pages``.  The kernel grid is (slots, page_blocks) with
the page axis sequential, carrying partial-softmax state (m, l, acc) in
VMEM scratch exactly like ``decode_attention`` — but the K/V BlockSpec
index maps read the *page table* (scalar-prefetch, SMEM-resident) to
pick which physical page streams in next, vLLM PagedAttention-style.
Each step loads the whole page, every KV head of it: the TPU tiling
refuses a one-head slice of the pool's trailing (kv_heads, head_dim)
dims.  Sentinel entries are clamped for the DMA and masked to -inf
in-kernel, so partially-filled tables cost masked lanes, never wrong
output.  ``paged_suffix_attention`` runs the same kernel with a few
query rows per slot for speculative verify.

``paged_decode_attention_ref`` is the jittable ``lax.fori_loop``
reference the tier-1 CPU suite (and the engine on CPU backends) runs:
same page-at-a-time online-softmax schedule, pure jnp, and the only
implementation that supports *traced* windows (hymba's per-layer
global/local mix).  ``paged_suffix_attention_ref`` is the multi-query
variant the speculative-verify dispatch uses: Q draft positions per
slot, causal by absolute position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------------ #
# jittable references (the CPU/tier-1 code path)
# ------------------------------------------------------------------ #
def paged_decode_attention_ref(q, k_pool, v_pool, page_table, pos, *,
                               window=0, prefix: int = 0):
    """One new token per slot attends through its page table.

    q: (B, K, G, hd) grouped queries; k_pool/v_pool: (P, ps, K, hd)
    physical pages; page_table: (B, pps) int32, sentinel == P for
    unmapped entries; pos: (B,) int32 current token index.  `window`
    may be a traced (B,)/scalar array (0 => full causal).  Returns
    (B, K, G, hd).
    """
    b, nkv, g, hd = q.shape
    n_pages, ps, _, _ = k_pool.shape
    pps = page_table.shape[1]
    sm_scale = hd ** -0.5
    qf = q.astype(jnp.float32) * sm_scale
    static_full = isinstance(window, int) and window == 0
    win = None if static_full else jnp.broadcast_to(
        jnp.asarray(window, jnp.int32), (b,))

    def body(j, carry):
        m, l, acc = carry
        ids = page_table[:, j]                              # (B,)
        kp = jnp.take(k_pool, ids, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)     # (B,ps,K,hd)
        vp = jnp.take(v_pool, ids, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)
        s = jnp.einsum("bkgd,bskd->bkgs", qf, kp)           # (B,K,G,ps)
        kv_pos = j * ps + jnp.arange(ps, dtype=jnp.int32)   # (ps,)
        mask = (kv_pos[None, :] <= pos[:, None]) \
            & (ids < n_pages)[:, None]                      # (B, ps)
        if win is not None:
            inwin = kv_pos[None, :] > (pos - win)[:, None]
            inwin = jnp.where((win > 0)[:, None], inwin, True)
            if prefix > 0:
                inwin |= kv_pos[None, :] < prefix
            mask &= inwin
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bkgs,bskd->bkgd", p, vp)
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((b, nkv, g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, 1), jnp.float32)
    a0 = jnp.zeros((b, nkv, g, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, pps, body, (m0, l0, a0))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def paged_suffix_attention_ref(q, k_pool, v_pool, page_table, q_pos):
    """Multi-query paged attention for speculative verify: Q tokens per
    slot at absolute positions ``q_pos`` (B, Q), causal by position.

    q: (B, Q, H, hd); k_pool/v_pool: (P, ps, K, hd); page_table:
    (B, pps) with sentinel == P.  Returns (B, Q, H, hd).  Plain causal
    only (no window/prefix) — the engine gates speculation accordingly.
    """
    b, qn, h, hd = q.shape
    n_pages, ps, nkv, _ = k_pool.shape
    pps = page_table.shape[1]
    grp = h // nkv
    sm_scale = hd ** -0.5
    qf = (q.astype(jnp.float32) * sm_scale).reshape(b, qn, nkv, grp, hd)

    def body(j, carry):
        m, l, acc = carry
        ids = page_table[:, j]
        kp = jnp.take(k_pool, ids, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)     # (B,ps,K,hd)
        vp = jnp.take(v_pool, ids, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)
        s = jnp.einsum("bqkgd,bskd->bqkgs", qf, kp)
        kv_pos = j * ps + jnp.arange(ps, dtype=jnp.int32)
        mask = (kv_pos[None, None, :] <= q_pos[:, :, None]) \
            & (ids < n_pages)[:, None, None]                # (B, Q, ps)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bqkgs,bskd->bqkgd", p, vp)
        return m_new, l_new, acc * corr + pv

    m0 = jnp.full((b, qn, nkv, grp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, qn, nkv, grp, 1), jnp.float32)
    a0 = jnp.zeros((b, qn, nkv, grp, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, pps, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, qn, h, hd).astype(q.dtype)


# ------------------------------------------------------------------ #
# Pallas kernel (one physical page, every KV head, per grid step)
# ------------------------------------------------------------------ #
def _paged_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, sm_scale: float,
                  page_size: int, n_pages: int, window: int, prefix: int,
                  group: int):
    bi = pl.program_id(0)
    ij = pl.program_id(1)
    nj = pl.num_programs(1)
    pos = pos_ref[bi]
    page = table_ref[bi, ij]
    rows = q_ref.shape[1]
    last = pos + (rows // group - 1)        # the latest query position

    @pl.when(ij == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip sentinel pages and pages entirely past the valid region
    blk_start = ij * page_size
    run = jnp.logical_and(page < n_pages, blk_start <= last)
    if window > 0:
        in_reach = (blk_start + page_size - 1) > (pos - window)
        if prefix > 0:
            in_reach = jnp.logical_or(in_reach, blk_start < prefix)
        run = jnp.logical_and(run, in_reach)

    @pl.when(run)
    def _body():
        k = k_ref[0].astype(jnp.float32)                    # (ps, K, hd)
        v = v_ref[0].astype(jnp.float32)
        kv_pos = blk_start + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, k.shape[1], 1), 0)       # (ps, K, 1)
        # decode is one query row per head: scores and the weighted sum
        # are lane reductions on the VPU, so the page block keeps its
        # pool layout and no head is sliced out of the tiled (K, hd) dims
        for r in range(rows):
            q_at = pos + r // group
            q = q_ref[0, r].astype(jnp.float32) * sm_scale  # (K, hd)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # (ps, K, 1)
            mask = kv_pos <= q_at
            if window > 0:
                inwin = kv_pos > q_at - window
                if prefix > 0:
                    inwin = jnp.logical_or(inwin, kv_pos < prefix)
                mask = jnp.logical_and(mask, inwin)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[r]                                # (K, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new[None]), 0.0)
            l_scr[r] = l_scr[r] * corr + jnp.sum(p, axis=0)
            acc_scr[r] = acc_scr[r] * corr + jnp.sum(p * v, axis=0)
            m_scr[r] = m_new

    @pl.when(ij == nj - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, page_table, pos, *, group: int,
                window: int, prefix: int, interpret: bool):
    """q: (B, R, K, hd) query rows; row r sits at position
    pos + r // group.  Returns (B, R, K, hd)."""
    b, rows, nkv, hd = q.shape
    n_pages, ps, _, _ = k_pool.shape
    pps = page_table.shape[1]
    kernel = functools.partial(
        _paged_kernel, sm_scale=hd ** -0.5, page_size=ps,
        n_pages=n_pages, window=window, prefix=prefix, group=group)

    # sentinel entries still drive the DMA index map: clamp them to a
    # real page (the kernel masks the whole block, so the data is dead)
    def kv_map(bi, ij, table_ref, _pos):
        return (jnp.minimum(table_ref[bi, ij], n_pages - 1), 0, 0, 0)

    def q_map(bi, ij, _t, _p):
        return (bi, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pps),
        in_specs=[
            pl.BlockSpec((1, rows, nkv, hd), q_map),
            # the whole page: (ps, K, hd) are the pool's full trailing
            # dims, which the TPU tiling accepts for any K and hd
            pl.BlockSpec((1, ps, nkv, hd), kv_map),
            pl.BlockSpec((1, ps, nkv, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, rows, nkv, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, nkv, 1), jnp.float32),
            pltpu.VMEM((rows, nkv, 1), jnp.float32),
            pltpu.VMEM((rows, nkv, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, nkv, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(page_table, pos, q, k_pool, v_pool)


def paged_decode_attention(q, k_pool, v_pool, page_table, pos, *,
                           window: int = 0, prefix: int = 0,
                           interpret: bool = False):
    """Pallas paged decode attention.  q: (B, K, G, hd); k_pool/v_pool:
    (P, ps, K, hd); page_table: (B, pps) int32 with sentinel == P; pos:
    (B,) int32.  Returns (B, K, G, hd).  `window`/`prefix` must be
    static here — callers with traced windows use the ref."""
    g = q.shape[2]
    out = _paged_call(q.transpose(0, 2, 1, 3), k_pool, v_pool, page_table,
                      pos, group=g, window=window, prefix=prefix,
                      interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def paged_suffix_attention(q, k_pool, v_pool, page_table, pos, *,
                           interpret: bool = False):
    """Pallas multi-query paged attention for speculative verify: q (B,
    Q, H, hd) holds Q consecutive tokens per slot, the first at `pos`
    (B,); causal by absolute position, plain causal only.  Same result as
    ``paged_suffix_attention_ref`` with q_pos = pos + arange(Q)."""
    b, qn, h, hd = q.shape
    nkv = k_pool.shape[2]
    grp = h // nkv
    rows = q.reshape(b, qn, nkv, grp, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(b, qn * grp, nkv, hd)
    out = _paged_call(rows, k_pool, v_pool, page_table, pos, group=grp,
                      window=0, prefix=0, interpret=interpret)
    return out.reshape(b, qn, grp, nkv, hd).transpose(0, 1, 3, 2, 4) \
        .reshape(b, qn, h, hd)
