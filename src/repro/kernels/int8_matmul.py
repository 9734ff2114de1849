"""Pallas TPU dequantizing int8 matmul: x @ (w_q * scale).

Weights stay int8 in HBM (the legacy-VRAM story of the paper: models packed
into 6-8 GB nodes); dequantization happens in VMEM after the integer tile is
loaded, feeding the MXU in bf16/f32.  Per-output-channel scales.

Grid (M/bm, N/bn, K/bk), K sequential, f32 accumulator in VMEM scratch;
scale applied once at the final K block — so the inner loop is a plain
int8-load + f32 FMA, no per-block rescaling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_mm_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, block_k: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)                     # (bm, bk)
    w = w_ref[...].astype(jnp.float32)                     # (bk, bn)
    acc[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc[...] * s_ref[...].astype(jnp.float32)) \
            .astype(o_ref.dtype)


def int8_matmul(x, w_q, scale, *, block_m: int = 128, block_n: int = 128,
                block_k: int = 128, out_dtype=None,
                interpret: bool = False):
    """x: (M, K) float; w_q: (K, N) int8; scale: (1, N) f32.
    Returns (M, N) in out_dtype (defaults to x.dtype)."""
    m, k = x.shape
    _, n = w_q.shape
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0
    out_dtype = out_dtype or x.dtype
    kernel = functools.partial(_int8_mm_kernel, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((block_k, block_n), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, block_n), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_q, scale)
