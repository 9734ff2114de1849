"""Seeded weights, made by the benchmark on the device in one jitted
call, in the type they are served in.

The tree has the layout the program's model takes (its `init`'s shapes,
read with `jax.eval_shape`, nothing computed); every leaf's values come
from here, at the scale the family's rule (`families/<family>.py`,
`leaf_std`) gives it, so the plain reference reads the same weights
without taking anything the program made.  Scales keep a random model's
activations at unit size.  `EMBED_STD` and `NORM_STD` are scales a
family's rule may give embeddings and norm scales (applied as 1 + s);
norm scales away from 0 catch a reference that dropped them.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

EMBED_STD = 0.02
NORM_STD = 0.1


def seed_words(seed: int) -> Tuple[int, int]:
    """A seed of up to 64 bits as two uint32 words (traced, so every
    seed runs the same compiled program)."""
    s = seed % 2 ** 64
    return s & 0xFFFFFFFF, s >> 32


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


def make(shapes: Any, seed: int, device,
         leaf_std: Callable[[Tuple[str, ...], Tuple[int, ...]], float]
         ) -> Dict:
    """Weights for the leaf shapes `shapes` (a dict tree of
    ShapeDtypeStruct), from `seed`, committed to `device`; each leaf
    N(0, leaf_std(path, shape)^2)."""
    leaves = list(_paths(shapes))
    stds = [leaf_std(p, s.shape) for p, s in leaves]

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
