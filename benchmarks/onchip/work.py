"""Operations and bytes the algorithm needs, from the configuration's
sizes and the actual lengths served: the yardstick of every roofline
and utilization share.  They count the algorithm's work, never what an
implementation happens to move: a program that reads only the pages in
use, or skips padding, leaves these numbers as they are.

Matmul parameters per layer (SwiGLU MLP):
    d*hd*(h + 2*kv) [q, k, v] + h*hd*d [out] + 3*d*f [gate, up, down]
plus the vocabulary projection d*V (the embedding's transpose when
tied).  A token at context length c (it attends to c positions, itself
included) costs 2 FLOPs per matmul parameter and, per layer,
4*h*hd*c for QK^T and AV.  A decode step reads every matmul weight once
and, per token, the K and V of its c positions.  A prefill of L prompt
tokens attends causally (sum of c = L(L+1)/2) and projects only its last
position onto the vocabulary.
"""
from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2


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


def decode_flops(a: Dict, contexts: Iterable[int]) -> int:
    """Decode tokens at the given context lengths."""
    per = 2 * matmul_params(a)
    return sum(per + attn_flops(a, c) for c in contexts)


def decode_bytes(a: Dict, steps: int, contexts: Iterable[int]) -> int:
    """`steps` decode steps (each reads the weights once) emitting tokens
    at the given context lengths (each reads its KV)."""
    kvb = kv_bytes_per_token(a)
    return steps * weight_bytes(a) + sum(c * kvb for c in contexts)


def prefill_flops(a: Dict, prompt_lens: Iterable[int]) -> int:
    layers = 2 * a["n_layers"] * layer_matmul_params(a)
    out = 0
    for n in prompt_lens:
        out += layers * n + attn_flops(a, n * (n + 1) // 2) \
            + 2 * head_params(a)
    return out
