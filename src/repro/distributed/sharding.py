"""Logical-axis sharding: MaxText-style rules with divisibility fallbacks.

Every model tensor (param, activation, cache) is annotated with a tuple of
*logical* axis names.  A `Strategy` maps logical axes to prioritized lists of
mesh-axis tuples; `resolve()` picks, per tensor, the first candidate that
divides the dim and whose mesh axes are still unused in that tensor.  This is
what lets one model definition serve *every* (arch x shape x mesh) cell —
including awkward cases like kv_heads=5 or d_ff=5504 that don't divide a
16-way axis.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[str, ...]
Candidate = Tuple[str, ...]          # tuple of mesh axis names


@dataclasses.dataclass(frozen=True)
class Strategy:
    """Priority-ordered rules: logical axis -> candidate mesh-axis tuples.

    `priority` orders *which logical axes get first pick* of mesh axes when
    several dims of one tensor compete (e.g. kv_heads before seq_kv so head
    sharding wins when divisible).
    """
    rules: Dict[str, List[Candidate]]
    priority: List[str]
    name: str = ""

    def spec_for(self, axes: Axes, shape: Sequence[int],
                 mesh: Mesh) -> P:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        assign: Dict[int, Candidate] = {}
        used: set = set()
        order = [a for a in self.priority if a in axes] + \
                [a for a in axes if a not in self.priority]
        for logical in order:
            if logical not in self.rules:
                continue
            # find the dim index (first unassigned occurrence)
            dim = None
            for i, a in enumerate(axes):
                if a == logical and i not in assign:
                    dim = i
                    break
            if dim is None:
                continue
            for cand in self.rules[logical]:
                if any(c in used for c in cand):
                    continue
                total = int(np.prod([sizes[c] for c in cand]))
                if shape[dim] % total == 0 and total > 1:
                    assign[dim] = cand
                    used.update(cand)
                    break
        parts = []
        for i in range(len(axes)):
            if i in assign:
                cand = assign[i]
                parts.append(cand[0] if len(cand) == 1 else cand)
            else:
                parts.append(None)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def sharding_for(self, axes: Axes, shape: Sequence[int],
                     mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec_for(axes, shape, mesh))


def tree_shardings(axes_tree, specs_tree, mesh: Mesh, strategy: Strategy):
    """Map a tree of logical-axes tuples + ShapeDtypeStructs to shardings."""
    return jax.tree.map(
        lambda ax, spec: strategy.sharding_for(ax, spec.shape, mesh),
        axes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def make_sharder(mesh: Optional[Mesh], strategy: Optional[Strategy]):
    """Returns sh(x, logical_axes) applying a sharding constraint."""
    if mesh is None or strategy is None:
        return lambda x, axes: x

    def sh(x, axes):
        spec = strategy.spec_for(tuple(axes), x.shape, mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return sh


def make_weight_sharder(mesh: Optional[Mesh],
                        strategy: Optional[Strategy]):
    """Returns shw(param_tree, axes_tree) constraining weights to their
    *compute* sharding inside the step.

    This is the explicit-FSDP-gather trick: weights are STORED sharded over
    the DP axis (in_shardings) but CONSTRAINED to a DP-replicated, TP-sharded
    layout at use — so XLA inserts a cheap per-layer weight all-gather
    instead of involuntarily rematerializing (replicating!) the much larger
    activations to match the weight sharding.  Without this, SPMD
    partitioning emits 'involuntary full rematerialization' and the memory/
    collective terms explode by ~2 orders of magnitude (see EXPERIMENTS.md
    §Perf iteration 1).
    """
    if mesh is None or strategy is None:
        return None

    def shw(tree, axes_tree):
        def f(x, ax):
            spec = strategy.spec_for(tuple(ax), x.shape, mesh)
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return jax.tree.map(
            f, tree, axes_tree,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(a, (str, type(None))) for a in t))
    return shw


def make_tp_projector(mesh: Optional[Mesh], act_strategy: Optional[Strategy],
                      w_strategy: Optional[Strategy]):
    """Explicit row-parallel (Megatron) out-projection.

    XLA's SPMD partitioner emits a full ALL-REDUCE for
    `einsum(x, w_contracted_over_tp)` even when the output is constrained
    to a seq-sharded layout (verified by micro-benchmark — no AR->RS
    strength reduction).  This helper wraps the einsum in shard_map with an
    explicit `psum_scatter`, halving the wire bytes.  Falls back to a plain
    einsum whenever the preconditions don't hold (contraction not sharded
    over exactly the TP axis, scatter dim not divisible, decode S=1, ...).

    Returns project(x, w, eq, x_axes, w_axes, out_axes, scatter_axis).
    """
    if mesh is None or act_strategy is None or w_strategy is None:
        return None
    tp = _tp(mesh)[0]
    tp_size = dict(zip(mesh.axis_names, mesh.devices.shape))[tp]

    def project(x, w, eq, x_axes, w_axes, out_axes, scatter_axis):
        out_shape = jax.eval_shape(
            lambda a, b: jnp.einsum(eq, a, b), x, w).shape
        x_spec = act_strategy.spec_for(tuple(x_axes), x.shape, mesh)
        w_spec = w_strategy.spec_for(tuple(w_axes), w.shape, mesh)
        # precondition: w's first (contracted) dim sharded over tp alone,
        # x's matching dim likewise, scatter dim divisible
        x_parts = tuple(x_spec) + (None,) * (len(x.shape) - len(x_spec))
        w_parts = tuple(w_spec) + (None,) * (len(w.shape) - len(w_spec))
        ok = (tp in w_parts and
              out_shape[scatter_axis] % tp_size == 0 and
              x_parts.count(tp) == 1 and w_parts.count(tp) == 1)
        if not ok:
            out = jnp.einsum(eq, x, w)
            return jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, act_strategy.spec_for(
                    tuple(out_axes), out_shape, mesh)))
        out_parts = [None] * len(out_shape)
        out_parts[scatter_axis] = tp
        # keep x's non-tp sharding (e.g. batch over dp) in the out spec
        for i, p in enumerate(x_parts[:len(out_parts)]):
            if p is not None and p != tp and i != scatter_axis:
                out_parts[i] = p

        def body(x_, w_):
            o = jnp.einsum(eq, x_, w_)
            return jax.lax.psum_scatter(o, tp,
                                        scatter_dimension=scatter_axis,
                                        tiled=True)

        return jax.shard_map(body, mesh=mesh,
                             in_specs=(x_spec, w_spec),
                             out_specs=P(*out_parts),
                             check_vma=False)(x, w)

    return project


def make_tp_col_projector(mesh: Optional[Mesh],
                          act_strategy: Optional[Strategy],
                          w_strategy: Optional[Strategy]):
    """Column-parallel (Megatron f-operator) projection with the einsum
    INSIDE the shard_map: fwd = all_gather(x_seq) -> local einsum; bwd =
    one fused psum_scatter.  Composing a standalone gather with an outside
    einsum leaves XLA resolving the partial cotangent with a full
    all-reduce first (measured: 2x wire, §Perf iteration 10).

    Only used when the OUTPUT carries the tp axis (q heads / mlp F) so
    shard_map grads stay exact; falls back to plain einsum + constraint.
    """
    if mesh is None or act_strategy is None or w_strategy is None:
        return None
    tp = _tp(mesh)[0]

    def project(x, w, eq, x_axes, w_axes, out_axes, gather_axis=1):
        out_shape = jax.eval_shape(
            lambda a, b: jnp.einsum(eq, a, b), x, w).shape
        x_spec = act_strategy.spec_for(tuple(x_axes), x.shape, mesh)
        w_spec = w_strategy.spec_for(tuple(w_axes), w.shape, mesh)
        out_spec = act_strategy.spec_for(tuple(out_axes), out_shape, mesh)
        x_parts = tuple(x_spec) + (None,) * (len(x.shape) - len(x_spec))
        w_parts = tuple(w_spec) + (None,) * (len(w.shape) - len(w_spec))
        out_parts = tuple(out_spec) + (None,) * (len(out_shape)
                                                 - len(out_spec))
        ok = (len(x_parts) > gather_axis and
              x_parts[gather_axis] == tp and
              x_parts.count(tp) == 1 and
              tp in out_parts and tp in w_parts)
        if not ok:
            out = jnp.einsum(eq, x, w)
            return jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, out_spec))

        def body(x_, w_):
            x_full = jax.lax.all_gather(x_, tp, axis=gather_axis,
                                        tiled=True)
            return jnp.einsum(eq, x_full, w_)

        return jax.shard_map(body, mesh=mesh, in_specs=(x_spec, w_spec),
                             out_specs=out_spec, check_vma=False)(x, w)

    return project


def make_tp_gather(mesh: Optional[Mesh],
                   act_strategy: Optional[Strategy]):
    """Megatron-SP f-operator: gather the TP(seq)-sharded residual once per
    block, as a shard_map all_gather whose TRANSPOSE is a reduce-scatter.
    (A plain sharding-constraint gather gets a full 2x-wire all-reduce in
    the backward from XLA's partitioner — measured, §Perf iteration 9.)

    Returns gather(x, x_axes, gather_axis=1) -> x with that dim whole.
    """
    if mesh is None or act_strategy is None:
        return None
    tp = _tp(mesh)[0]

    def gather(x, x_axes, gather_axis: int = 1):
        x_spec = act_strategy.spec_for(tuple(x_axes), x.shape, mesh)
        x_parts = tuple(x_spec) + (None,) * (len(x.shape) - len(x_spec))
        if len(x_parts) <= gather_axis or x_parts[gather_axis] != tp:
            return x        # already whole on this dim
        out_parts = list(x_parts)
        out_parts[gather_axis] = None
        while out_parts and out_parts[-1] is None:
            out_parts.pop()

        def body(x_):
            return jax.lax.all_gather(x_, tp, axis=gather_axis,
                                      tiled=True)

        return jax.shard_map(body, mesh=mesh, in_specs=(x_spec,),
                             out_specs=P(*out_parts), check_vma=False)(x)

    return gather


def train_compute_strategy(mesh: Mesh) -> Strategy:
    """Weight layout at *use* time during training: TP dims sharded, the
    FSDP (embed) dim gathered."""
    tp = _tp(mesh)
    rules = {
        "mlp": [tp], "heads": [tp], "kv_heads": [tp], "inner": [tp],
        "vocab": [tp], "experts": [tp],
    }
    return Strategy(rules=rules,
                    priority=["mlp", "heads", "kv_heads", "inner",
                              "vocab", "experts"],
                    name="train_compute")


# --------------------------------------------------------------------- #
# Strategy presets.  DP = data(-parallel) meta axis; TP = model axis.

def _dp(mesh: Mesh) -> Candidate:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _tp(mesh: Mesh) -> Candidate:
    return ("model",)


def train_strategy(mesh: Mesh, name: str = "fsdp_tp") -> Strategy:
    """FSDP over DP + tensor-parallel over TP + sequence-parallel residual.

    Params: embed dim FSDP-sharded over DP; mlp/heads/vocab over TP.
    Activations: batch over DP, seq over TP (Megatron-SP style residual).
    """
    dp, tp = _dp(mesh), _tp(mesh)
    rules = {
        # params
        "embed": [dp],
        "mlp": [tp],
        "heads": [tp],
        "kv_heads": [tp],
        "inner": [tp],
        "vocab": [tp],
        "experts": [tp],        # EP when divisible, else falls through
        # activations
        "batch": [dp],
        "seq": [tp],
        "embed_rs": [tp],       # MoE down-proj reduce-scatter target
    }
    return Strategy(rules=rules,
                    priority=["batch", "embed", "mlp", "heads", "kv_heads",
                              "inner", "vocab", "experts", "embed_rs",
                              "seq"],
                    name=name)


def train_strategy_fsdp(mesh: Mesh) -> Strategy:
    """Pure FSDP: batch over DP+TP flattened; params fully sharded over the
    flattened mesh on their largest logical dim.  Best for small models
    where TP would be latency-bound."""
    dp, tp = _dp(mesh), _tp(mesh)
    all_ = dp + tp
    rules = {
        "embed": [all_, dp, tp],
        "mlp": [all_, tp, dp],
        "vocab": [all_, tp, dp],
        "heads": [tp],
        "kv_heads": [tp],
        "inner": [all_, tp, dp],
        "experts": [tp],
        "batch": [all_, dp],
        "seq": [tp],
        "embed_rs": [tp, dp],   # MoE down-proj reduce-scatter target
    }
    return Strategy(rules=rules,
                    priority=["batch", "mlp", "vocab", "embed", "inner",
                              "heads", "kv_heads", "experts", "embed_rs",
                              "seq"],
                    name="fsdp")


def serve_strategy(mesh: Mesh, name: str = "serve") -> Strategy:
    """Serving: params TP-only (no per-step gathers); batch over DP;
    KV heads over TP when divisible, else KV sequence; long-context batch=1
    spreads KV sequence over every axis."""
    dp, tp = _dp(mesh), _tp(mesh)
    all_ = dp + tp
    rules = {
        # weights TP-only: no per-step gathers on the serving path (the
        # embed/contraction dim stays replicated across DP)
        "mlp": [tp],
        "heads": [tp],
        "kv_heads": [tp],
        "inner": [tp],
        "vocab": [tp],
        "experts": [tp],
        "batch": [dp],
        "seq": [tp],
        "seq_kv": [tp, dp, all_],
    }
    return Strategy(rules=rules,
                    priority=["batch", "kv_heads", "seq_kv", "heads", "mlp",
                              "inner", "vocab", "experts", "seq"],
                    name=name)


STRATEGIES = {
    "fsdp_tp": train_strategy,
    "fsdp": train_strategy_fsdp,
    "serve": serve_strategy,
}


def pick_strategy(kind: str, mesh: Mesh, arch_params: int,
                  override: str = "") -> Strategy:
    """Default policy: big models train with fsdp_tp (SP residual keeps
    activations bounded); small models (<8B) train pure-FSDP; serving is
    always TP-centric."""
    if override:
        return STRATEGIES[override](mesh)
    if kind == "train":
        if arch_params >= 8e9:
            return train_strategy(mesh)
        return train_strategy_fsdp(mesh)
    return serve_strategy(mesh)
