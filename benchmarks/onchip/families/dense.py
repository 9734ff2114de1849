"""The dense decoder family: MHA or GQA attention, a SwiGLU MLP, one K
and one V per KV head in the cache.  What the benchmark needs of a
family (`spec.load_family`): the program's configuration, the weights'
scales, the plain reference forward and the algorithm's work counts.

Reference, written from the published architecture and independent of
the program: float32 `jax.numpy`, matmuls at `highest` precision, no
cache, no batching, no kernels.  It reads the benchmark's own weights
by name.

    embed -> n_layers x [ h += Attn(Norm(h)); h += SwiGLU(Norm(h)) ]
          -> Norm -> logits = h @ head

Norm is OLMo's non-parametric LayerNorm (eps 1e-5) or RMSNorm with a
learned (1 + scale) (eps 1e-6); attention is causal, multi-head or
grouped-query, with rotary embeddings on the two halves of each head
(theta from the config); the head is the embedding's transpose where
tied.  It runs in blocks so that it fits beside the served weights: one
layer's weights cast to float32 at a time, queries in blocks for
attention, and the vocabulary projection only at the positions asked
for.

Work counts: the operations and bytes the algorithm needs, from the
configuration's sizes and the actual lengths served.  Matmul parameters
per layer:
    d*hd*(h + 2*kv) [q, k, v] + h*hd*d [out] + 3*d*f [gate, up, down]
plus the vocabulary projection d*V (the embedding's transpose when
tied).  A token at context length c (it attends to c positions, itself
included) costs 2 FLOPs per matmul parameter and, per layer,
4*h*hd*c for QK^T and AV.  A decode step reads every matmul weight once
and, per token, the K and V of its c positions.  A prefill of L prompt
tokens attends causally (sum of c = L(L+1)/2) and projects only its last
position onto the vocabulary.  The program's counters are not read: the
dense work follows from the lengths alone.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from reference import Q_BLOCK, V_ROWS, freeze, mm, norm
from weights import EMBED_STD, NORM_STD

BF16 = 2


# ------------------------------------------------------------------ #
# the program's configuration and the weights' scales
# ------------------------------------------------------------------ #
def build_arch(cfg: Dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=cfg["name"], family="dense", **cfg["arch"])


def leaf_std(path: Tuple[str, ...], shape: Tuple[int, ...]) -> float:
    """Matrices N(0, 1/fan_in), embeddings N(0, 0.02^2), RMS-norm
    scales (applied as 1 + s) N(0, 0.1^2)."""
    name = path[-1]
    if name in ("embed", "lm_head"):
        return EMBED_STD
    if name.startswith("ln") or name == "final_norm":
        return NORM_STD
    layer = shape[1:] if path[0] == "layers" else shape
    if name in ("wq", "wk", "wv"):          # (d, heads, hd)
        fan_in = layer[0]
    elif name == "wi":                      # (2, d, f) or (d, f)
        fan_in = layer[-2]
    elif name == "wo" and path[-2] == "attn":   # (heads, hd, d)
        fan_in = layer[0] * layer[1]
    elif name == "wo":                      # (f, d)
        fan_in = layer[0]
    else:
        raise ValueError(f"no rule for weight {'/'.join(path)}")
    return 1.0 / math.sqrt(fan_in)


# ------------------------------------------------------------------ #
# the plain reference
# ------------------------------------------------------------------ #
def _rope(x, theta: float):
    """x: (L, H, hd); rotate the two halves of each head by position."""
    n, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _layer(h, lp, *, arch: Tuple, precision: str):
    """One decoder layer over a right-padded sequence h (L, d) in
    float32 (causal: padding changes no earlier row)."""
    a = dict(arch)
    n, d = h.shape
    nh, nkv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    g = nh // nkv
    lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
    x = norm(h, lp.get("ln1"), a["norm"])
    q = _rope(mm("sd,dhk->shk", x, lp["attn"]["wq"], precision),
              a["rope_theta"])
    k = _rope(mm("sd,dhk->shk", x, lp["attn"]["wk"], precision),
              a["rope_theta"])
    v = mm("sd,dhk->shk", x, lp["attn"]["wv"], precision)
    k = jnp.repeat(k, g, axis=1)              # head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(n)
    outs = []
    for s0 in range(0, n, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        sc = mm("qhk,shk->hqs", qb, k, precision) / np.sqrt(hd)
        qpos = s0 + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        outs.append(mm("hqs,shk->qhk", w, v, precision))
    att = jnp.concatenate(outs, axis=0)
    h = h + mm("shk,hkd->sd", att, lp["attn"]["wo"], precision)
    x = norm(h, lp.get("ln2"), a["norm"])
    wi = lp["mlp"]["wi"]
    gate = mm("sd,df->sf", x, wi[0], precision)
    up = mm("sd,df->sf", x, wi[1], precision)
    act = gate * jax.nn.sigmoid(gate) * up
    return h + mm("sf,fd->sd", act, lp["mlp"]["wo"], precision)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _logits(h, final_scale, head, *, arch: Tuple, precision: str):
    """Final norm and vocabulary projection of rows h (R, d) -> (R, V)."""
    a = dict(arch)
    x = norm(h, final_scale, a["norm"])
    return mm("rd,dv->rv", x, head.astype(jnp.float32), precision)


def hidden(weights: Dict, arch: Dict, tokens: np.ndarray, pad_to: int,
           precision: str = "f32"):
    """Final-layer hidden states (pad_to, d) of `tokens`, right-padded."""
    key = freeze(arch)
    ids = np.zeros((pad_to,), np.int32)
    ids[:len(tokens)] = tokens
    h = jnp.take(weights["embed"], jnp.asarray(ids), axis=0) \
        .astype(jnp.float32)
    layers = weights["layers"]
    for i in range(arch["n_layers"]):
        lp = jax.tree.map(lambda w: w[i], layers)
        h = _layer(h, lp, arch=key, precision=precision)
    return h


def logits(weights: Dict, arch: Dict, tokens: Sequence[int],
           pad_to: int, precision: str = "f32",
           rows: Optional[Sequence[int]] = None) -> np.ndarray:
    """Reference logits (len(rows), V) at positions `rows` of `tokens`
    (every position where not given)."""
    h = hidden(weights, arch, np.asarray(tokens, np.int32), pad_to,
               precision)
    rows = np.arange(len(tokens)) if rows is None else np.asarray(rows)
    # each block to the host before the next, so one (V_ROWS, V) block
    # at a time is on the device
    out = [np.asarray(_logits(h[jnp.asarray(rows[r0:r0 + V_ROWS])],
                              weights.get("final_norm"),
                              _head(weights, arch), arch=freeze(arch),
                              precision=precision))
           for r0 in range(0, len(rows), V_ROWS)]
    return np.concatenate(out)


def _head(weights: Dict, arch: Dict):
    return weights["embed"].T if arch["tie_embeddings"] \
        else weights["lm_head"]


# ------------------------------------------------------------------ #
# the algorithm's work
# ------------------------------------------------------------------ #
def layer_matmul_params(a: Dict) -> int:
    d, hd, f = a["d_model"], a["head_dim"], a["d_ff"]
    h, kv = a["n_heads"], a["n_kv_heads"]
    return d * hd * (h + 2 * kv) + h * hd * d + 3 * d * f


def head_params(a: Dict) -> int:
    return a["d_model"] * a["vocab"]


def matmul_params(a: Dict) -> int:
    return a["n_layers"] * layer_matmul_params(a) + head_params(a)


def weight_bytes(a: Dict) -> int:
    """Bytes of weights one decode step reads (bf16)."""
    return matmul_params(a) * BF16


def kv_bytes_per_token(a: Dict) -> int:
    return a["n_layers"] * 2 * a["n_kv_heads"] * a["head_dim"] * BF16


def attn_flops(a: Dict, context: int) -> int:
    """QK^T and AV of one query over `context` positions, all layers."""
    return a["n_layers"] * 4 * a["n_heads"] * a["head_dim"] * context


def decode_flops(a: Dict, contexts: Iterable[int], counters) -> int:
    """Decode tokens at the given context lengths."""
    per = 2 * matmul_params(a)
    return sum(per + attn_flops(a, c) for c in contexts)


def decode_bytes(a: Dict, steps: int, contexts: Iterable[int],
                 counters) -> int:
    """`steps` decode steps (each reads the weights once) emitting tokens
    at the given context lengths (each reads its KV)."""
    kvb = kv_bytes_per_token(a)
    return steps * weight_bytes(a) + sum(c * kvb for c in contexts)


def prefill_flops(a: Dict, prompt_lens: Iterable[int], counters) -> int:
    layers = 2 * a["n_layers"] * layer_matmul_params(a)
    out = 0
    for n in prompt_lens:
        out += layers * n + attn_flops(a, n * (n + 1) // 2) \
            + 2 * head_params(a)
    return out
