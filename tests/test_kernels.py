"""Pallas kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp ref.py oracles (interpret=True executes kernel bodies on CPU).
Whether the kernels also compile for the chip is checked in
``tests/test_tpu_compile.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.serving.quantization import quantize_array

rng = np.random.default_rng(7)


def rnd(*s, dt=jnp.float32):
    return jnp.asarray(rng.standard_normal(s), dt)


FLASH_CASES = [
    # B, H, K, Sq, Skv, hd, win, prefix, dtype
    (2, 4, 2, 128, 128, 64, 0, 0, jnp.float32),
    (1, 8, 4, 256, 256, 128, 0, 0, jnp.float32),
    (2, 4, 1, 128, 256, 64, 64, 0, jnp.float32),
    (1, 4, 2, 128, 128, 64, 48, 16, jnp.float32),
    (1, 2, 2, 64, 64, 32, 0, 0, jnp.bfloat16),
    (1, 6, 2, 192, 192, 64, 0, 0, jnp.float32),   # non-pow2 heads
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_oracle(case):
    B, H, K, Sq, Skv, hd, win, pre, dt = case
    q, k, v = rnd(B, H, Sq, hd, dt=dt), rnd(B, K, Skv, hd, dt=dt), \
        rnd(B, K, Skv, hd, dt=dt)
    out = flash_attention(q, k, v, causal=True, window=win, prefix=pre,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True, window=win,
                                     prefix=pre)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_noncausal():
    q, k, v = rnd(1, 4, 128, 64), rnd(1, 2, 128, 64), rnd(1, 2, 128, 64)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


DECODE_CASES = [
    # B, K, G, S, hd, win, block_k
    (2, 2, 4, 512, 64, 0, 128),
    (4, 8, 8, 256, 128, 0, 128),
    (2, 1, 4, 512, 64, 128, 128),
    (1, 4, 2, 1024, 64, 0, 256),
    (3, 2, 8, 256, 32, 0, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention_vs_oracle(case):
    B, K, G, S, hd, win, bk = case
    q = rnd(B, K, G, hd)
    kc, vc = rnd(B, K, S, hd), rnd(B, K, S, hd)
    pos = jnp.asarray(rng.integers(max(win, 1), S, B), jnp.int32)
    out = decode_attention(q, kc, vc, pos, window=win, block_k=bk,
                           interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, pos, window=win)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_decode_ragged_positions():
    """Per-sequence lengths mask correctly (continuous batching)."""
    B, K, G, S, hd = 4, 2, 2, 512, 64
    q = rnd(B, K, G, hd)
    kc, vc = rnd(B, K, S, hd), rnd(B, K, S, hd)
    pos = jnp.asarray([0, 63, 200, 511], jnp.int32)
    out = decode_attention(q, kc, vc, pos, block_k=64, interpret=True)
    expect = ref.decode_attention_ref(q, kc, vc, pos)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


INT8_CASES = [
    (128, 256, 128, jnp.float32),
    (256, 512, 256, jnp.bfloat16),
    (128, 128, 384, jnp.float32),
]


@pytest.mark.parametrize("case", INT8_CASES)
def test_int8_matmul_vs_oracle(case):
    M, K, N, dt = case
    x = rnd(M, K, dt=dt)
    w = rnd(K, N) * 0.1
    qd = quantize_array(w, 8)
    out = int8_matmul(x, qd["__q__"], qd["scale"], interpret=True)
    expect = ref.int8_matmul_ref(x, qd["__q__"], qd["scale"])
    np.testing.assert_allclose(out.astype(np.float32),
                               expect.astype(np.float32),
                               atol=5e-2, rtol=5e-2)


def test_int8_matmul_quantization_error_bound():
    """End-to-end quant error stays within the analytic absmax bound."""
    x = rnd(64, 128)
    w = rnd(128, 64)
    qd = quantize_array(w, 8)
    out = int8_matmul(x, qd["__q__"], qd["scale"], block_m=64, block_n=64,
                      block_k=64, interpret=True)
    exact = x @ w
    # per-element error <= sum_k |x_k| * scale/2
    bound = jnp.sum(jnp.abs(x), axis=1, keepdims=True) * \
        jnp.max(qd["scale"]) * 0.5 + 1e-4
    assert bool(jnp.all(jnp.abs(out - exact) <= bound))


def test_ops_wrappers_jit():
    """Public ops are jit-compiled and match the raw kernels."""
    q, k, v = rnd(1, 4, 128, 64), rnd(1, 2, 128, 64), rnd(1, 2, 128, 64)
    o1 = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    o2 = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(o1, o2, atol=1e-6)


# ------------------- paged decode attention ------------------------ #
def _gather_view(pool, table):
    """Oracle helper: materialize (B, K, pps*ps, hd) logical views from
    the (P, ps, K, hd) page pool; sentinel entries read as zeros (masked
    by pos in the dense oracle)."""
    P = pool.shape[0]
    valid = table < P
    g = jnp.take(pool, jnp.where(valid, table, 0), axis=0)
    g = jnp.where(valid[:, :, None, None, None], g, 0)
    b, pps, ps, k, hd = g.shape
    return g.reshape(b, pps * ps, k, hd).transpose(0, 2, 1, 3)


def _paged_case(B, K, G, n_pages, pps, ps, hd, pos_list):
    kp, vp = rnd(n_pages, ps, K, hd), rnd(n_pages, ps, K, hd)
    pos = jnp.asarray(pos_list, jnp.int32)
    # each slot maps just enough pages to cover pos, sentinel after that
    table = np.full((B, pps), n_pages, np.int32)
    free = iter(rng.permutation(n_pages))
    for i, p in enumerate(pos_list):
        for j in range(p // ps + 1):
            table[i, j] = next(free)
    q = rnd(B, K, G, hd)
    return q, kp, vp, jnp.asarray(table), pos


PAGED_CASES = [
    # B, K, G, n_pages, pps, ps, hd, window
    (3, 2, 4, 24, 6, 8, 64, 0),
    (2, 4, 2, 32, 8, 4, 32, 0),
    (4, 1, 8, 24, 4, 8, 128, 0),
    (3, 2, 4, 24, 6, 8, 64, 16),     # sliding window
]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_vs_gather_oracle(case):
    """Kernel and fori_loop reference both match dense attention over
    the gathered logical view — including sentinel-padded tables and a
    static sliding window."""
    B, K, G, n_pages, pps, ps, hd, win = case
    q, kp, vp, table, pos = _paged_case(
        B, K, G, n_pages, pps, ps, hd,
        [ps - 1, ps * 2 + 3, ps * (pps - 1)][:B] + [5] * max(B - 3, 0))
    expect = ref.decode_attention_ref(q, _gather_view(kp, table),
                                      _gather_view(vp, table), pos,
                                      window=win)
    out_ref = pa.paged_decode_attention_ref(q, kp, vp, table, pos,
                                            window=win)
    np.testing.assert_allclose(out_ref, expect, atol=2e-5, rtol=2e-5)
    out_k = pa.paged_decode_attention(q, kp, vp, table, pos, window=win,
                                      interpret=True)
    np.testing.assert_allclose(out_k, expect, atol=2e-5, rtol=2e-5)


def test_paged_decode_traced_window_ref():
    """The reference also supports per-slot *traced* windows (hymba's
    global/local mix routes through it): window 0 rows stay full-causal
    in the same call as windowed rows."""
    B, K, G, n_pages, pps, ps, hd = 3, 2, 4, 24, 6, 8, 64
    q, kp, vp, table, pos = _paged_case(B, K, G, n_pages, pps, ps, hd,
                                        [ps * 3, ps * 2 + 3, ps * 5 - 1])
    win = jnp.asarray([0, 8, 16], jnp.int32)
    kc, vc = _gather_view(kp, table), _gather_view(vp, table)
    for i in range(B):
        expect = ref.decode_attention_ref(q[i:i + 1], kc[i:i + 1],
                                          vc[i:i + 1], pos[i:i + 1],
                                          window=int(win[i]))
        got = pa.paged_decode_attention_ref(q, kp, vp, table, pos,
                                            window=win)[i:i + 1]
        np.testing.assert_allclose(got, expect, atol=2e-5, rtol=2e-5)


def test_paged_decode_shared_pages():
    """Two slots mapping the *same* physical prefix page (prefix-cache
    COW sharing) read identical keys through their own tables."""
    B, K, G, n_pages, pps, ps, hd = 2, 2, 2, 16, 4, 8, 32
    kp, vp = rnd(n_pages, ps, K, hd), rnd(n_pages, ps, K, hd)
    table = jnp.asarray([[3, 5, 16, 16], [3, 7, 16, 16]], jnp.int32)
    pos = jnp.asarray([ps * 2 - 1, ps * 2 - 1], jnp.int32)
    q = jnp.tile(rnd(1, K, G, hd), (B, 1, 1, 1))
    out = pa.paged_decode_attention_ref(q, kp, vp, table, pos)
    expect = ref.decode_attention_ref(q, _gather_view(kp, table),
                                      _gather_view(vp, table), pos)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)
    outk = pa.paged_decode_attention(q, kp, vp, table, pos,
                                     interpret=True)
    np.testing.assert_allclose(outk, expect, atol=2e-5, rtol=2e-5)


def test_paged_suffix_vs_dense_oracle():
    """Multi-query verify attention (Q draft positions per slot) matches
    dense causal attention over the gathered view at every position."""
    B, Q, K, G, n_pages, pps, ps, hd = 3, 5, 2, 3, 24, 6, 8, 32
    H = K * G
    kp, vp = rnd(n_pages, ps, K, hd), rnd(n_pages, ps, K, hd)
    pos0 = [5, 20, 33]
    table = np.full((B, pps), n_pages, np.int32)
    free = iter(rng.permutation(n_pages))
    for i, p in enumerate(pos0):
        for j in range((p + Q - 1) // ps + 1):
            table[i, j] = next(free)
    table = jnp.asarray(table)
    q = rnd(B, Q, H, hd)
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(Q)[None, :]
    out = pa.paged_suffix_attention_ref(q, kp, vp, table, q_pos)
    outk = pa.paged_suffix_attention(q, kp, vp, table,
                                     jnp.asarray(pos0, jnp.int32),
                                     interpret=True)
    kc, vc = _gather_view(kp, table), _gather_view(vp, table)
    # dense oracle: fold H -> (K, G) K-major, mask kv_pos <= q_pos
    qf = q.reshape(B, Q, K, G, hd).astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqkgd,bksd->bqkgs", qf, kc.astype(jnp.float32))
    kv = jnp.arange(kc.shape[2])
    mask = kv[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    expect = jnp.einsum("bqkgs,bksd->bqkgd", p,
                        vc.astype(jnp.float32)).reshape(B, Q, H, hd)
    np.testing.assert_allclose(out, expect.astype(out.dtype),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(outk, expect.astype(out.dtype),
                               atol=2e-5, rtol=2e-5)
