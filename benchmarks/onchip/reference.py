"""What every family's plain reference shares, and the gaps that decide
`correct`, computed from a family's own float32 forward
(`families/<family>.py`, `logits`).

A family's reference is written from the published architecture and
independent of the program: float32 `jax.numpy`, matmuls at `highest`
precision through `mm`, no cache, no batching, no kernels, in blocks
(`Q_BLOCK` query rows for attention, `V_ROWS` positions a vocabulary
projection) so that it fits beside the served weights.

`precision="fp8"` is the control: every matmul's operands rounded to
float8 e4m3 with a per-tensor scale (the next precision below the
configurations' bfloat16), the rest as in float32.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

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


def mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def norm(x, scale, kind: str):
    if kind == "nonparam_ln":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return y * (1.0 + scale.astype(jnp.float32))


def freeze(value: Any):
    """A configuration's sizes as a hashable key for `jax.jit`'s static
    arguments: dicts (nested ones too) as sorted (key, value) tuples,
    lists as tuples; `dict(freeze(d))` gives the top level back."""
    if isinstance(value, dict):
        return tuple((k, freeze(value[k])) for k in sorted(value))
    if isinstance(value, (list, tuple)):
        return tuple(freeze(v) for v in value)
    return value


def gaps(family, weights: Dict, arch: Dict, prompt: Sequence[int],
         served: Sequence[int], pad_to: int, *, control: bool = False):
    """For each served token, by how much its float32 reference logit
    lies below the reference's best logit at that position (0 where it
    is the reference's argmax).  With `control`, also the float32 gap of
    the token the fp8 control puts first at each of those positions."""
    seq = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    first = len(prompt) - 1            # position whose logits chose served[0]
    pos = np.arange(first, first + len(served))
    lg = family.logits(weights, arch, seq, pad_to, "f32", rows=pos)
    best = np.max(lg, axis=-1)
    tok = np.asarray(served, np.int64)
    gap = best - np.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    if not control:
        return gap, None
    pick = np.argmax(family.logits(weights, arch, seq, pad_to, "fp8",
                                   rows=pos), axis=-1)
    return gap, best - np.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
