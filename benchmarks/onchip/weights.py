"""Seeded weights, made by the benchmark on the device in one jitted
call, in the type they are served in.

The tree has the layout the program's dense transformer takes (its
`init`'s shapes, read with `jax.eval_shape`, nothing computed); every
leaf's values come from here, so the plain reference reads the same
weights without taking anything the program made.  Scales keep a
random model's activations at unit size: matrices N(0, 1/fan_in),
embeddings N(0, 0.02^2), RMS-norm scales (applied as 1 + s) N(0, 0.1^2)
so that a reference that dropped them would be caught.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.1


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words (traced, so every
    seed runs the same compiled program)."""
    s = seed % 2 ** 64
    return s & 0xFFFFFFFF, s >> 32


def _leaf_std(path: Tuple[str, ...], shape: Tuple[int, ...]) -> float:
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


def _paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _set(tree: Dict, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make(shapes: Any, seed: int, device) -> Dict:
    """Weights for the leaf shapes `shapes` (a dict tree of
    ShapeDtypeStruct), from `seed`, committed to `device`."""
    leaves = list(_paths(shapes))
    stds = [_leaf_std(p, s.shape) for p, s in leaves]

    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        keys = jax.random.split(key, len(leaves))
        out: Dict = {}
        for k, (path, s), std in zip(keys, leaves, stds):
            x = std * jax.random.normal(k, s.shape, jnp.float32)
            _set(out, path, x.astype(s.dtype))
        return out

    lo, hi = seed_words(seed)
    with jax.default_device(device):
        return jax.jit(build)(jnp.uint32(lo), jnp.uint32(hi))
