"""Plain reference of the dense decoder the configurations describe,
written from the published architecture and independent of the program:
float32 `jax.numpy`, matmuls at `highest` precision, no cache, no
batching, no kernels.  It reads the benchmark's own weights by name.

    embed -> n_layers x [ h += Attn(Norm(h)); h += SwiGLU(Norm(h)) ]
          -> Norm -> logits = h @ head

Norm is OLMo's non-parametric LayerNorm (eps 1e-5) or RMSNorm with a
learned (1 + scale) (eps 1e-6); attention is causal, multi-head or
grouped-query, with rotary embeddings on the two halves of each head
(theta from the config); the head is the embedding's transpose where
tied.

It runs in blocks so that it fits beside the served weights: one layer's
weights cast to float32 at a time, queries in blocks for attention, and
the vocabulary projection only at the positions compared.

`precision="fp8"` is the control: every matmul's operands rounded to
float8 e4m3 with a per-tensor scale (the next precision below the
configurations' bfloat16), the rest as above.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512          # query rows per attention block
V_ROWS = 256           # positions per vocabulary-projection block
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _round(x, precision: str):
    """Operand rounding of one matmul input."""
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = FP8_MAX / amax
    return (x * scale).astype(FP8).astype(jnp.float32) / scale


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _norm(x, scale, kind: str):
    if kind == "nonparam_ln":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return y * (1.0 + scale.astype(jnp.float32))


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
    x = _norm(h, lp.get("ln1"), a["norm"])
    q = _rope(_mm("sd,dhk->shk", x, lp["attn"]["wq"], precision),
              a["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", x, lp["attn"]["wk"], precision),
              a["rope_theta"])
    v = _mm("sd,dhk->shk", x, lp["attn"]["wv"], precision)
    k = jnp.repeat(k, g, axis=1)              # head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(n)
    outs = []
    for s0 in range(0, n, Q_BLOCK):
        qb = q[s0:s0 + Q_BLOCK]
        sc = _mm("qhk,shk->hqs", qb, k, precision) / np.sqrt(hd)
        qpos = s0 + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        outs.append(_mm("hqs,shk->qhk", w, v, precision))
    att = jnp.concatenate(outs, axis=0)
    h = h + _mm("shk,hkd->sd", att, lp["attn"]["wo"], precision)
    x = _norm(h, lp.get("ln2"), a["norm"])
    wi = lp["mlp"]["wi"]
    gate = _mm("sd,df->sf", x, wi[0], precision)
    up = _mm("sd,df->sf", x, wi[1], precision)
    act = gate * jax.nn.sigmoid(gate) * up
    return h + _mm("sf,fd->sd", act, lp["mlp"]["wo"], precision)


@functools.partial(jax.jit, static_argnames=("arch", "precision"))
def _logits(h, final_scale, head, *, arch: Tuple, precision: str):
    """Final norm and vocabulary projection of rows h (R, d) -> (R, V)."""
    a = dict(arch)
    x = _norm(h, final_scale, a["norm"])
    return _mm("rd,dv->rv", x, head.astype(jnp.float32), precision)


def _arch_key(arch: Dict) -> Tuple:
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "vocab", "norm", "rope_theta", "tie_embeddings")
    return tuple((k, arch[k]) for k in keys)


def hidden(weights: Dict, arch: Dict, tokens: np.ndarray, pad_to: int,
           precision: str = "f32"):
    """Final-layer hidden states (pad_to, d) of `tokens`, right-padded."""
    key = _arch_key(arch)
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
           pad_to: int, precision: str = "f32") -> np.ndarray:
    """Reference logits (len(tokens), V) at every position."""
    h = hidden(weights, arch, np.asarray(tokens, np.int32), pad_to,
               precision)
    out = [_logits(h[r0:min(r0 + V_ROWS, len(tokens))],
                   weights.get("final_norm"), _head(weights, arch),
                   arch=_arch_key(arch), precision=precision)
           for r0 in range(0, len(tokens), V_ROWS)]
    return np.concatenate([np.asarray(x) for x in out])


def _head(weights: Dict, arch: Dict):
    return weights["embed"].T if arch["tie_embeddings"] \
        else weights["lm_head"]


def gaps(weights: Dict, arch: Dict, prompt: Sequence[int],
         served: Sequence[int], pad_to: int, *, control: bool = False):
    """For each served token, by how much its float32 reference logit
    lies below the reference's best logit at that position (0 where it
    is the reference's argmax).  With `control`, also the float32 gap of
    the token the fp8 control puts first at each of those positions."""
    seq = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    first = len(prompt) - 1            # position whose logits chose served[0]
    pos = np.arange(first, first + len(served))
    h32 = hidden(weights, arch, seq, pad_to, "f32")
    h8 = hidden(weights, arch, seq, pad_to, "fp8") if control else None
    fs = weights.get("final_norm")
    head = _head(weights, arch)
    key = _arch_key(arch)
    gaps, ctrl = [], []
    for r0 in range(0, len(pos), V_ROWS):
        rows = jnp.asarray(pos[r0:r0 + V_ROWS])
        lg = _logits(h32[rows], fs, head, arch=key, precision="f32")
        best = jnp.max(lg, axis=-1)
        tok = jnp.asarray(np.asarray(served[r0:r0 + V_ROWS], np.int32))
        mine = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - mine))
        if control:
            lg8 = _logits(h8[rows], fs, head, arch=key, precision="fp8")
            pick = jnp.argmax(lg8, axis=-1)
            theirs = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            ctrl.append(np.asarray(best - theirs))
    return (np.concatenate(gaps),
            np.concatenate(ctrl) if control else None)
