"""Decoder-only transformer family: dense GQA, sliding-window, MoE, VLM
(patch-embedding prefix), and Hymba hybrid (parallel attention + SSM heads,
meta tokens).

Layers are *stacked* (leading ``L`` dim) and iterated with ``lax.scan`` so the
lowered HLO is O(1) in depth — essential for compiling 80-layer models on the
no-hardware dry-run path, and the layout FSDP prefetch wants on real TPUs.

Every function takes ``sh(x, logical_axes)`` — a sharding-constraint hook
provided by the distribution layer (identity on single device).
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

PyTree = Any
Sharder = Callable[[jax.Array, tuple], jax.Array]


def _id_sh(x, axes):
    return x


_row_project = L.row_project


def _dt(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bf16" else jnp.float32


def ssm_inner(cfg: ArchConfig) -> int:
    return cfg.n_heads * cfg.head_dim


# --------------------------------------------------------------------- #
# Parameter init & logical axes

def _layer_init(cfg: ArchConfig, key, cross: bool = False) -> Dict:
    dt = _dt(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    h, k_, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
    keys = iter(jax.random.split(key, 24))
    p: Dict[str, Any] = {}
    p["attn"] = {
        "wq": L.dense_init(next(keys), d, (d, h, hd), dt),
        "wk": L.dense_init(next(keys), d, (d, k_, hd), dt),
        "wv": L.dense_init(next(keys), d, (d, k_, hd), dt),
    }
    if cfg.block != "hymba":
        p["attn"]["wo"] = L.dense_init(next(keys), h * hd, (h, hd, d), dt)
    if cfg.norm == "rms":
        p["ln1"] = jnp.zeros((d,), dt)
        p["ln2"] = jnp.zeros((d,), dt)
    if cross:
        p["xattn"] = {
            "wq": L.dense_init(next(keys), d, (d, h, hd), dt),
            "wk": L.dense_init(next(keys), d, (d, k_, hd), dt),
            "wv": L.dense_init(next(keys), d, (d, k_, hd), dt),
            "wo": L.dense_init(next(keys), h * hd, (h, hd, d), dt),
        }
        if cfg.norm == "rms":
            p["lnx"] = jnp.zeros((d,), dt)
    if cfg.moe:
        e = cfg.moe.num_experts
        wi_shape = (e, 2, d, f) if cfg.act == "swiglu" else (e, d, f)
        p["moe"] = {
            "router": L.dense_init(next(keys), d, (d, e), jnp.float32),
            "wi": L.dense_init(next(keys), d, wi_shape, dt),
            "wo": L.dense_init(next(keys), f, (e, f, d), dt),
        }
    elif f > 0:
        wi_shape = (2, d, f) if cfg.act == "swiglu" else (d, f)
        p["mlp"] = {"wi": L.dense_init(next(keys), d, wi_shape, dt),
                    "wo": L.dense_init(next(keys), f, (f, d), dt)}
    if cfg.block == "hymba":
        inner = ssm_inner(cfg)
        n = cfg.ssm_state
        r = max(8, inner // 64)
        p["ssm"] = {
            "w_in": L.dense_init(next(keys), d, (d, 2, inner), dt),
            "w_dt_a": L.dense_init(next(keys), inner, (inner, r), dt),
            "w_dt_b": L.dense_init(next(keys), r, (r, inner), dt),
            "b_dt": jnp.full((inner,), -4.0, jnp.float32),
            "a_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=jnp.float32), (inner, n))),
            "w_b": L.dense_init(next(keys), inner, (inner, n), dt),
            "w_c": L.dense_init(next(keys), inner, (inner, n), dt),
            "d_skip": jnp.ones((inner,), jnp.float32),
        }
        p["branch_norm_attn"] = jnp.zeros((inner,), dt)
        p["branch_norm_ssm"] = jnp.zeros((inner,), dt)
        p["beta"] = jnp.ones((2,), jnp.float32)
        p["wo_comb"] = L.dense_init(next(keys), inner, (inner, d), dt)
    return p


def _layer_axes(cfg: ArchConfig, cross: bool = False) -> Dict:
    p: Dict[str, Any] = {}
    p["attn"] = {"wq": ("embed", "heads", "head_dim"),
                 "wk": ("embed", "kv_heads", "head_dim"),
                 "wv": ("embed", "kv_heads", "head_dim")}
    if cfg.block != "hymba":
        p["attn"]["wo"] = ("heads", "head_dim", "embed")
    if cfg.norm == "rms":
        p["ln1"] = ("embed",)
        p["ln2"] = ("embed",)
    if cross:
        p["xattn"] = {"wq": ("embed", "heads", "head_dim"),
                      "wk": ("embed", "kv_heads", "head_dim"),
                      "wv": ("embed", "kv_heads", "head_dim"),
                      "wo": ("heads", "head_dim", "embed")}
        if cfg.norm == "rms":
            p["lnx"] = ("embed",)
    if cfg.moe:
        wi = ("experts", "stack", "embed", "mlp") if cfg.act == "swiglu" \
            else ("experts", "embed", "mlp")
        p["moe"] = {"router": ("embed", "experts"), "wi": wi,
                    "wo": ("experts", "mlp", "embed")}
    elif cfg.d_ff > 0:
        wi = ("stack", "embed", "mlp") if cfg.act == "swiglu" \
            else ("embed", "mlp")
        p["mlp"] = {"wi": wi, "wo": ("mlp", "embed")}
    if cfg.block == "hymba":
        p["ssm"] = {"w_in": ("embed", "stack", "inner"),
                    "w_dt_a": ("inner", "rank"),
                    "w_dt_b": ("rank", "inner"),
                    "b_dt": ("inner",), "a_log": ("inner", "state"),
                    "w_b": ("inner", "state"), "w_c": ("inner", "state"),
                    "d_skip": ("inner",)}
        p["branch_norm_attn"] = ("inner",)
        p["branch_norm_ssm"] = ("inner",)
        p["beta"] = ("stack",)
        p["wo_comb"] = ("inner", "embed")
    return p


def init_params(cfg: ArchConfig, key) -> PyTree:
    dt = _dt(cfg)
    keys = iter(jax.random.split(key, 8))
    params: Dict[str, Any] = {
        "embed": L.trunc_normal(next(keys), (cfg.vocab, cfg.d_model),
                                0.02, dt)}
    if cfg.n_meta_tokens:
        params["meta"] = L.trunc_normal(
            next(keys), (cfg.n_meta_tokens, cfg.d_model), 0.02, dt)
    lk = jax.random.split(next(keys), cfg.n_layers)
    params["layers"] = jax.vmap(
        lambda k: _layer_init(cfg, k, cross=cfg.is_encdec))(lk)
    if cfg.is_encdec:
        ek = jax.random.split(next(keys), cfg.encdec.enc_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: _layer_init(cfg, k, cross=False))(ek)
    if cfg.norm == "rms":
        params["final_norm"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.trunc_normal(
            next(keys), (cfg.d_model, cfg.vocab), 0.02, dt)
    return params


def param_axes(cfg: ArchConfig) -> PyTree:
    def stack(tree):
        return jax.tree.map(lambda ax: ("layers",) + ax, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    axes: Dict[str, Any] = {"embed": ("vocab", "embed")}
    if cfg.n_meta_tokens:
        axes["meta"] = ("prefix", "embed")
    axes["layers"] = stack(_layer_axes(cfg, cross=cfg.is_encdec))
    if cfg.is_encdec:
        axes["enc_layers"] = stack(_layer_axes(cfg, cross=False))
    if cfg.norm == "rms":
        axes["final_norm"] = ("embed",)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# --------------------------------------------------------------------- #
# Blocks

def _attention_block(lp, cfg: ArchConfig, x, sh, *, causal, window, prefix,
                     q_offset=0, rope=True, kv=None, impl="auto"):
    """Full-sequence attention sub-block over a (possibly seq-sharded) x.

    Megatron-SP structure: q is a column-parallel projection (fused
    all_gather + einsum, psum_scatter backward); k/v are projected on the
    LOCAL sequence shard (small) and then seq-gathered — so no cotangent
    ever needs a full (B,S,D) all-reduce (§Perf iterations 2/9/10).
    kv: optional (k, v) override for cross-attention (already projected).
    Returns (out, (k, v)).
    """
    q = L.col_project(sh, x, lp["wq"], "bsd,dhk->bshk",
                      ("batch", "seq", "embed"),
                      ("embed", "heads", "head_dim"),
                      ("batch", "seq_attn", "heads", "head_dim"))
    if kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        k = L.seq_gather(sh, k, ("batch", "seq", "kv_heads", "head_dim"))
        v = L.seq_gather(sh, v, ("batch", "seq", "kv_heads", "head_dim"))
        if rope:
            pos = q_offset + jnp.arange(k.shape[1])
            cos, sin = L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
            q = L.apply_rope(q, cos, sin)
            k = L.apply_rope(k, cos, sin)
    else:
        k, v = kv
        if rope:
            pos = q_offset + jnp.arange(q.shape[1])
            cos, sin = L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
            q = L.apply_rope(q, cos, sin)
    # "seq_attn" (unsharded): attention processes the whole sequence per
    # head-shard; sharding seq here would force XLA to reshard O(S^2)
    # score tensors (§Perf iteration 2)
    q = sh(q, ("batch", "seq_attn", "heads", "head_dim"))
    k = sh(k, ("batch", "seq_attn", "kv_heads", "head_dim"))
    v = sh(v, ("batch", "seq_attn", "kv_heads", "head_dim"))
    out = attn_lib.attention(q, k, v, causal=causal, window=window,
                             prefix=prefix, q_offset=q_offset, impl=impl)
    return out, (k, v)


def _hymba_ssm_seq(sp, cfg: ArchConfig, x, h0=None):
    """Hymba SSM branch over a full sequence.  x: (B,S,D)."""
    inner = ssm_inner(cfg)
    b, s, _ = x.shape
    proj = jnp.einsum("bsd,dgi->bsgi", x, sp["w_in"])
    u, z = proj[:, :, 0], proj[:, :, 1]
    dt = jax.nn.softplus(
        jnp.einsum("bsi,ir,rj->bsj", u.astype(jnp.float32),
                   sp["w_dt_a"].astype(jnp.float32),
                   sp["w_dt_b"].astype(jnp.float32)) + sp["b_dt"])
    a = -jnp.exp(sp["a_log"])
    b_t = jnp.einsum("bsi,in->bsn", u, sp["w_b"]).astype(jnp.float32)
    c_t = jnp.einsum("bsi,in->bsn", u, sp["w_c"]).astype(jnp.float32)
    if h0 is None:
        h0 = jnp.zeros((b, inner, cfg.ssm_state), jnp.float32)
    y, h_f = ssm_lib.selective_scan(u.astype(jnp.float32), dt, a, b_t, c_t,
                                    h0)
    y = y + sp["d_skip"] * u.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(x.dtype), h_f


def _hymba_ssm_step(sp, cfg: ArchConfig, x, h):
    """Single decode step.  x: (B, D); h: (B, inner, state)."""
    proj = jnp.einsum("bd,dgi->bgi", x, sp["w_in"])
    u, z = proj[:, 0], proj[:, 1]
    dt = jax.nn.softplus(
        jnp.einsum("bi,ir,rj->bj", u.astype(jnp.float32),
                   sp["w_dt_a"].astype(jnp.float32),
                   sp["w_dt_b"].astype(jnp.float32)) + sp["b_dt"])
    a = -jnp.exp(sp["a_log"])
    b_t = jnp.einsum("bi,in->bn", u, sp["w_b"]).astype(jnp.float32)
    c_t = jnp.einsum("bi,in->bn", u, sp["w_c"]).astype(jnp.float32)
    y, h_new = ssm_lib.selective_step(u.astype(jnp.float32), dt, a, b_t,
                                      c_t, h)
    y = y + sp["d_skip"] * u.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(x.dtype), h_new


def _ffn(lp, cfg: ArchConfig, x, sh):
    """FFN sub-block (dense or MoE) over a possibly seq-sharded x.
    Dense path is fully Megatron: column-parallel up (fused gather),
    row-parallel down (psum_scatter).  Returns (out, aux_loss)."""
    if cfg.moe:
        x = L.seq_gather(sh, x, ("batch", "seq", "embed"))
        y, aux = moe_lib.moe_ffn(x, lp["moe"]["router"], lp["moe"]["wi"],
                                 lp["moe"]["wo"], cfg.moe, cfg.act,
                                 sh=sh)
        return y, aux
    if cfg.d_ff == 0:
        return jnp.zeros_like(x), 0.0
    wi, wo = lp["mlp"]["wi"], lp["mlp"]["wo"]
    if cfg.act == "swiglu":
        h2 = L.col_project(sh, x, wi, "bsd,gdf->bsgf",
                           ("batch", "seq", "embed"),
                           ("stack", "embed", "mlp"),
                           ("batch", "seq_attn", "stack", "mlp"))
        h = jax.nn.silu(h2[:, :, 0].astype(jnp.float32)) \
            .astype(x.dtype) * h2[:, :, 1]
    else:
        h = L.col_project(sh, x, wi, "bsd,df->bsf",
                          ("batch", "seq", "embed"),
                          ("embed", "mlp"),
                          ("batch", "seq_attn", "mlp"))
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out = L.row_project(sh, h, wo, "bsf,fd->bsd",
                        ("batch", "seq_attn", "mlp"),
                        ("mlp", "embed"), ("batch", "seq", "embed"))
    return out, 0.0


def _decoder_layer(lp, cfg: ArchConfig, h, sh, *, is_global, prefix,
                   enc_kv=None, impl="auto"):
    """One full-sequence decoder layer.  Returns (h, (k, v), aux).
    `is_global` may be a traced bool (hymba per-layer flag)."""
    if isinstance(is_global, bool):
        window = 0 if is_global else cfg.swa_window
    else:
        window = jnp.where(is_global, 0, cfg.swa_window)
    x = L.norm(h, lp.get("ln1"), cfg.norm)
    if cfg.block == "hymba":
        inner = ssm_inner(cfg)
        # hymba's SSM branch scans the full sequence: gather once here
        x = L.seq_gather(sh, x, ("batch", "seq", "embed"))
        a_out, kv_pair = _attention_block(
            lp["attn"], cfg, x, sh, causal=True, window=window,
            prefix=prefix, impl=impl)
        a_out = a_out.reshape(*a_out.shape[:2], inner)
        s_out, _ = _hymba_ssm_seq(lp["ssm"], cfg, x)
        a_n = L.rms_norm(a_out, lp["branch_norm_attn"])
        s_n = L.rms_norm(s_out, lp["branch_norm_ssm"])
        comb = (lp["beta"][0] * a_n.astype(jnp.float32)
                + lp["beta"][1] * s_n.astype(jnp.float32)) * 0.5
        proj = jnp.einsum("bsi,id->bsd", comb.astype(h.dtype),
                          lp["wo_comb"])
        h = h + sh(proj, ("batch", "seq", "embed"))
    else:
        a_out, kv_pair = _attention_block(
            lp["attn"], cfg, x, sh, causal=True, window=window,
            prefix=prefix, impl=impl)
        # row-parallel out-projection: explicit reduce-scatter onto the
        # seq-sharded residual (half the wire of XLA's all-reduce),
        # §Perf iterations 6+8
        proj = _row_project(sh, a_out, lp["attn"]["wo"],
                            "bshk,hkd->bsd",
                            ("batch", "seq_attn", "heads", "head_dim"),
                            ("heads", "head_dim", "embed"),
                            ("batch", "seq", "embed"))
        h = h + proj
    aux = 0.0
    if enc_kv is not None:
        x = L.norm(h, lp.get("lnx"), cfg.norm)
        c_out, _ = _attention_block(lp["xattn"], cfg, x, sh, causal=False,
                                    window=0, prefix=0, rope=False,
                                    kv=enc_kv, impl=impl)
        proj = _row_project(sh, c_out, lp["xattn"]["wo"],
                            "bshk,hkd->bsd",
                            ("batch", "seq_attn", "heads", "head_dim"),
                            ("heads", "head_dim", "embed"),
                            ("batch", "seq", "embed"))
        h = h + proj
    x = L.norm(h, lp.get("ln2"), cfg.norm)
    f_out, aux2 = _ffn(lp, cfg, x, sh)
    h = h + sh(f_out, ("batch", "seq", "embed"))
    h = sh(h, ("batch", "seq", "embed"))
    return h, kv_pair, aux + aux2


# --------------------------------------------------------------------- #
# Full-sequence forward (train / prefill)

def _is_global_flags(cfg: ArchConfig):
    flags = jnp.zeros((cfg.n_layers,), bool)
    if cfg.block == "hymba":
        flags = flags.at[jnp.array(cfg.global_attn_layers)].set(True)
    else:
        flags = jnp.ones((cfg.n_layers,), bool) if cfg.swa_window == 0 \
            else flags
    return flags


def _embed_inputs(params, cfg: ArchConfig, tokens, prefix_embeds, sh):
    """Returns (h (B, S_total, D), prefix_len)."""
    h = jnp.take(params["embed"], tokens, axis=0)
    prefix = 0
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        prefix = prefix_embeds.shape[1]
    if cfg.n_meta_tokens:
        meta = jnp.broadcast_to(
            params["meta"][None], (h.shape[0],) + params["meta"].shape)
        h = jnp.concatenate([meta.astype(h.dtype), h], axis=1)
        prefix += cfg.n_meta_tokens
    return sh(h, ("batch", "seq", "embed")), prefix


def _run_encoder(params, cfg: ArchConfig, src_embeds, sh, impl="auto"):
    def body(h, lp):
        h2, _, _ = _decoder_layer(lp, cfg, h, sh, is_global=True, prefix=0,
                                  impl=impl)
        return h2, None
    # encoder is bidirectional: reuse layer with causal=False via wrapper
    def enc_layer(h, lp):
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        a_out, _ = _attention_block(lp["attn"], cfg, x, sh, causal=False,
                                    window=0, prefix=0, impl=impl)
        proj = _row_project(sh, a_out, lp["attn"]["wo"],
                            "bshk,hkd->bsd",
                            ("batch", "seq_attn", "heads", "head_dim"),
                            ("heads", "head_dim", "embed"),
                            ("batch", "seq", "embed"))
        h = h + proj
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = _ffn(lp, cfg, x, sh)
        h = h + sh(f_out, ("batch", "seq", "embed"))
        return sh(h, ("batch", "seq", "embed")), None

    h, _ = jax.lax.scan(enc_layer, src_embeds, params["enc_layers"])
    return L.norm(h, params.get("final_norm"), cfg.norm)


def forward(params, cfg: ArchConfig, tokens, *, prefix_embeds=None,
            src_embeds=None, sh: Sharder = _id_sh, shw=None,
            remat: bool = False, collect_cache: bool = False,
            impl: str = "auto"):
    """Full-sequence forward.  Returns (logits, cache_parts, aux_loss).

    cache_parts is (k_stack, v_stack, enc_out) when collect_cache else None.
    shw(tree, axes_tree): weight compute-sharding hook (explicit FSDP
    gather inside the layer scan).
    """
    if cfg.block == "xlstm":
        from repro.models import xlstm as xl
        return xl.forward(params, cfg, tokens, sh=sh, shw=shw, remat=remat,
                          collect_cache=collect_cache)
    h, prefix = _embed_inputs(params, cfg, tokens, prefix_embeds, sh)
    enc_out = None
    if cfg.is_encdec:
        enc_out = _run_encoder(params, cfg, src_embeds, sh, impl=impl)
    flags = _is_global_flags(cfg) if cfg.block == "hymba" else None
    layer_ax = _layer_axes(cfg, cross=cfg.is_encdec)

    def layer(carry, xs):
        h = carry
        if flags is not None:
            lp, is_glob = xs
        else:
            lp, is_glob = xs, cfg.swa_window == 0
        if shw is not None:
            lp = shw(lp, layer_ax)
        enc_kv = None
        if enc_out is not None:
            ek = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
            ev = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
            enc_kv = (ek, ev)
        h2, kv, aux = _decoder_layer(lp, cfg, h, sh, is_global=is_glob,
                                     prefix=prefix, enc_kv=enc_kv, impl=impl)
        ys = (kv if collect_cache else None, aux)
        return h2, ys

    if remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    xs = (params["layers"], flags) if flags is not None \
        else params["layers"]
    h, (kv_stack, auxs) = jax.lax.scan(layer, h, xs)
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if shw is not None:
        head = shw(head, ("embed", "vocab"))
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    logits = sh(logits, ("batch", "seq", "vocab"))
    aux = jnp.sum(auxs) if cfg.moe else 0.0
    cache_parts = None
    if collect_cache:
        cache_parts = (kv_stack[0], kv_stack[1], enc_out, prefix)
    return logits, cache_parts, aux


# --------------------------------------------------------------------- #
# Loss

def loss_fn(params, cfg: ArchConfig, batch, *, sh: Sharder = _id_sh,
            shw=None, remat: bool = False, aux_weight: float = 0.01):
    """batch: {"tokens", "labels", optional "prefix_embeds"/"src_embeds"}.
    labels == -100 are masked.  Returns (loss, metrics)."""
    logits, _, aux = forward(
        params, cfg, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        src_embeds=batch.get("src_embeds"), sh=sh, shw=shw, remat=remat)
    labels = batch["labels"]
    # logits cover prefix+tokens; labels align with the *token* tail
    n_tok = labels.shape[1]
    logits = logits[:, -n_tok:]
    mask = labels != -100
    lab = jnp.where(mask, labels, 0)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, lab[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1)
    loss = jnp.sum(jnp.where(mask, nll, 0.0)) / denom
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux,
                   "tokens": denom.astype(jnp.float32)}


# --------------------------------------------------------------------- #
# Serving: cache init / prefill / decode

def kv_quantize(x):
    """Per-(position, head) absmax int8 KV quantization.
    x: (..., hd) -> (q int8 (..., hd), scale f32 (...))."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def kv_dequant(q, scale):
    """Dequantize int8 KV.  On TPU this runs inside the Pallas decode
    kernel (int8 HBM reads, VMEM dequant) — tagged so the roofline's
    kernel-adjusted terms treat the f32 expansion as VMEM-local."""
    with jax.named_scope("kv_dequant"):
        return q.astype(jnp.float32) * scale[..., None]


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               src_len: int = 0, dtype=None, kv_quant: bool = False):
    """Dense KV cache pytree (zeros).  max_len includes prefix tokens.
    kv_quant: int8 cache + per-(pos, head) f32 scales (halves at-rest KV
    bytes and HBM read traffic per decode step)."""
    if cfg.block == "xlstm":
        from repro.models import xlstm as xl
        return xl.init_cache(cfg, batch)
    dt = dtype or _dt(cfg)
    lshape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    if kv_quant:
        sshape = lshape[:-1]
        cache = {"k": jnp.zeros(lshape, jnp.int8),
                 "v": jnp.zeros(lshape, jnp.int8),
                 "k_scale": jnp.zeros(sshape, jnp.float32),
                 "v_scale": jnp.zeros(sshape, jnp.float32)}
    else:
        cache = {"k": jnp.zeros(lshape, dt), "v": jnp.zeros(lshape, dt)}
    if cfg.block == "hymba":
        cache["ssm_h"] = jnp.zeros(
            (cfg.n_layers, batch, ssm_inner(cfg), cfg.ssm_state),
            jnp.float32)
    if cfg.is_encdec:
        xshape = (cfg.n_layers, batch, src_len, cfg.n_kv_heads, cfg.head_dim)
        cache["ck"] = jnp.zeros(xshape, dt)
        cache["cv"] = jnp.zeros(xshape, dt)
    return cache


def cache_axes(cfg: ArchConfig, kv_quant: bool = False):
    ax = {"k": ("layers", "batch", "seq_kv", "kv_heads", "head_dim"),
          "v": ("layers", "batch", "seq_kv", "kv_heads", "head_dim")}
    if kv_quant:
        ax["k_scale"] = ("layers", "batch", "seq_kv", "kv_heads")
        ax["v_scale"] = ("layers", "batch", "seq_kv", "kv_heads")
    if cfg.block == "xlstm":
        from repro.models import xlstm as xl
        return xl.cache_axes(cfg)
    if cfg.block == "hymba":
        ax["ssm_h"] = ("layers", "batch", "inner", "state")
    if cfg.is_encdec:
        ax["ck"] = ("layers", "batch", "seq_kv", "kv_heads", "head_dim")
        ax["cv"] = ("layers", "batch", "seq_kv", "kv_heads", "head_dim")
    return ax


def prefill(params, cfg: ArchConfig, tokens, *, prefix_embeds=None,
            src_embeds=None, cache_len: int = 0, sh: Sharder = _id_sh,
            impl: str = "auto", kv_quant: bool = False, lengths=None):
    """Run full-sequence forward, build a decode-ready cache.

    Returns (last_logits (B, V), cache, pos (B,)) — pos = index of the last
    valid cache slot.

    lengths: optional (B,) int32 of *valid* (unpadded) token counts per
    row, for bucketed prefill: prompts right-padded to a shared bucket
    length share one trace, and each row's logits/pos are taken at its own
    last real token.  Sound for causal attention families only — padded
    positions sit beyond `pos` and are masked out of every later decode
    read, then overwritten as the slot advances.  Recurrent families
    (xlstm / hymba SSM states) fold pads into their state, so callers must
    batch those at exact lengths instead.
    """
    if cfg.block == "xlstm":
        from repro.models import xlstm as xl
        return xl.prefill(params, cfg, tokens, sh=sh)
    logits, parts, _ = forward(params, cfg, tokens,
                               prefix_embeds=prefix_embeds,
                               src_embeds=src_embeds, sh=sh,
                               collect_cache=True, impl=impl)
    k_stack, v_stack, enc_out, prefix = parts
    b = tokens.shape[0]
    s_tot = k_stack.shape[2]
    cache_len = max(cache_len, s_tot)
    cache = init_cache(cfg, b, cache_len,
                       src_len=(src_embeds.shape[1] if cfg.is_encdec
                                else 0), kv_quant=kv_quant)
    if kv_quant:
        k_stack, ks = kv_quantize(k_stack)
        v_stack, vs = kv_quantize(v_stack)
        cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, 0, axis=2)
        cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, 0, axis=2)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_stack.astype(cache["k"].dtype), 0, axis=2)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_stack.astype(cache["v"].dtype), 0, axis=2)
    if cfg.is_encdec:
        def xkv(lp_enc):
            ck = jnp.einsum("bsd,dhk->bshk", enc_out, lp_enc["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", enc_out, lp_enc["wv"])
            return ck, cv
        ck, cv = jax.vmap(xkv)(
            {"wk": params["layers"]["xattn"]["wk"],
             "wv": params["layers"]["xattn"]["wv"]})
        cache["ck"], cache["cv"] = ck, cv
    if cfg.block == "hymba":
        # re-run SSM branches to harvest final states (prefill-only cost)
        h, _ = _embed_inputs(params, cfg, tokens, prefix_embeds, sh)
        # states are collected during a light scan over layers
        def body(h, lp):
            x = L.norm(h, lp.get("ln1"), cfg.norm)
            _, h_f = _hymba_ssm_seq(lp["ssm"], cfg, x)
            h2, _, _ = _decoder_layer(lp, cfg, h, sh, is_global=False,
                                      prefix=cfg.n_meta_tokens, impl=impl)
            return h2, h_f
        _, states = jax.lax.scan(body, h, params["layers"])
        cache["ssm_h"] = states
    if lengths is not None:
        prefix = s_tot - tokens.shape[1]
        pos = (prefix + lengths - 1).astype(jnp.int32)
        last = jnp.take_along_axis(logits, pos[:, None, None], axis=1)[:, 0]
        return last, cache, pos
    pos = jnp.full((b,), s_tot - 1, jnp.int32)
    return logits[:, -1], cache, pos


def prefill_suffix(params, cfg: ArchConfig, cache, tokens, offsets,
                   lengths, *, sh: Sharder = _id_sh):
    """Extend per-row caches with a *batch of suffix tokens* in one pass —
    the prefix-cache admission path: rows arrive with `offsets` (B,) cache
    positions already valid (the shared cached prefix), `tokens` (B, S)
    right-padded suffix ids, and `lengths` (B,) valid suffix counts
    (>= 1).  The multi-token generalization of `decode_step`: suffix KV
    is written into the cache view at per-row offsets, attention is
    causal by per-row absolute position, and each row's logits come from
    its own last real token.

    Returns (last_logits (B, V), new_cache, pos (B,)) with
    pos = offsets + lengths - 1 (index of the last valid cache slot).

    Causal decoder-only: recurrent families (xlstm / hymba), enc-dec
    cross-attention, sliding windows, always-visible prefix tokens and
    quantized caches all depend on positions/state the suffix pass does
    not reconstruct — callers gate on those (the engine falls back to
    full prefill).
    """
    if cfg.block in ("xlstm", "hymba") or cfg.is_encdec \
            or cfg.swa_window or cfg.n_meta_tokens \
            or cfg.n_prefix_tokens or "k_scale" in cache:
        raise NotImplementedError(
            "prefill_suffix supports plain causal decoders only")
    b, s = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)              # (B,S,D)
    q_pos = offsets[:, None] + jnp.arange(s)[None, :]          # (B,S)

    def layer(carry, xs):
        h = carry
        lp = xs["lp"]
        kc, vc = xs["k"], xs["v"]
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
        cos, sin = L.rope_cos_sin(q_pos, cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        # scatter the suffix block at each row's absolute positions —
        # per-row scatter (not dynamic_update_slice: its clamped starts
        # would shift a row whose offset+S exceeds the view and overwrite
        # real prefix KV).  Out-of-range positions drop; garbage on
        # padded rows lands beyond `pos`, masked out of every later read
        # and overwritten as the slot advances.
        upd = jax.vmap(lambda c, n, p: c.at[p].set(n, mode="drop"))
        kc = upd(kc, k_new.astype(kc.dtype), q_pos)
        vc = upd(vc, v_new.astype(vc.dtype), q_pos)
        a_out = attn_lib.suffix_attention(q, kc, vc, q_pos)
        h = h + jnp.einsum("bshk,hkd->bsd", a_out, lp["attn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = _ffn(lp, cfg, x, sh)
        h = h + f_out
        return h, {"k": kc, "v": vc}

    xs = {"lp": params["layers"], "k": cache["k"], "v": cache["v"]}
    h, ys = jax.lax.scan(layer, h, xs)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = ys["k"], ys["v"]
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    pos = (offsets + lengths - 1).astype(jnp.int32)
    last_idx = jnp.clip(lengths - 1, 0, s - 1)
    last = jnp.take_along_axis(logits, last_idx[:, None, None],
                               axis=1)[:, 0]
    return last, new_cache, pos


def _scan_kv_carried(layer, h, cache, names, xs):
    """Scan `layer((h, kv), xs) -> ((h, kv), ys)` over the stacked layers
    with the stacked sequence-cache leaves `names` of `cache` in the carry
    (`xs` gains the layer index under "layer").  Each layer writes its new
    entries into the carried stacks and reads its own slice back, so the
    stacks are updated in place; scanned in as `xs` and out as `ys` they
    would be re-stacked into new arrays, a full copy of the cache every
    call.  Returns (h, ys, new_cache)."""
    kv = {n: cache[n] for n in names}
    xs = dict(xs, layer=jnp.arange(cache[names[0]].shape[0]))
    (h, kv), ys = jax.lax.scan(layer, (h, kv), xs)
    return h, ys, {**cache, **kv}


def _write_token(stack, layer, new, pos):
    """Write each row's one new entry `new` (B, 1, ...) at (layer, row,
    pos) of a stacked (L, B, S, ...) cache leaf.  `pos` clamps to S - 1
    as `dynamic_update_slice` clamps a start: only a finished slot reaches
    S, and its write then lands on its own last position."""
    rows = jnp.arange(new.shape[0])
    at = jnp.minimum(pos, stack.shape[2] - 1)
    return stack.at[layer, rows, at].set(new[:, 0])


def decode_step(params, cfg: ArchConfig, cache, token, pos, *,
                sh: Sharder = _id_sh):
    """One decode step.  token: (B,) int32; pos: (B,) int32 — position of
    the *new* token (cache slots [0, pos) are valid; prefix included).
    The stacked KV leaves (and their scales when quantized) ride in the
    layer loop's carry and each layer writes its token into them in
    place, so a step never copies the whole cache.

    Returns (logits (B, V), new_cache).
    """
    if cfg.block == "xlstm":
        from repro.models import xlstm as xl
        return xl.decode_step(params, cfg, cache, token, sh=sh)
    b = token.shape[0]
    h = jnp.take(params["embed"], token, axis=0)[:, None]      # (B,1,D)
    flags = _is_global_flags(cfg) if cfg.block == "hymba" else None
    prefix = cfg.n_meta_tokens + cfg.n_prefix_tokens
    quant = "k_scale" in cache
    names = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")

    def layer(carry, xs):
        h, kv = carry
        lp, i = xs["lp"], xs["layer"]
        hs = xs.get("ssm")
        is_glob = xs.get("flag", cfg.swa_window == 0)
        if isinstance(is_glob, bool):
            window = 0 if is_glob else cfg.swa_window
        else:
            window = jnp.where(is_glob, 0, cfg.swa_window)
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
        cos, sin = L.rope_cos_sin(pos[:, None], cfg.head_dim,
                                  cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        if quant:
            (kq, ks), (vq, vs) = kv_quantize(k_new), kv_quantize(v_new)
            new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            new = {"k": k_new, "v": v_new}
        # write new kv at slot pos (per batch row)
        kv = {n: _write_token(kv[n], i, new[n], pos) for n in names}
        k_at, v_at = kv["k"][i], kv["v"][i]
        if quant:
            k_at = kv_dequant(k_at, kv["k_scale"][i])
            v_at = kv_dequant(v_at, kv["v_scale"][i])
        ys = {}
        a_out = attn_lib.decode_attention(q, k_at, v_at, pos,
                                          window=window, prefix=prefix)
        if cfg.block == "hymba":
            inner = ssm_inner(cfg)
            a_out = a_out.reshape(b, 1, inner)
            s_out, hs_new = _hymba_ssm_step(lp["ssm"], cfg, x[:, 0], hs)
            a_n = L.rms_norm(a_out, lp["branch_norm_attn"])
            s_n = L.rms_norm(s_out[:, None], lp["branch_norm_ssm"])
            comb = (lp["beta"][0] * a_n.astype(jnp.float32)
                    + lp["beta"][1] * s_n.astype(jnp.float32)) * 0.5
            h = h + jnp.einsum("bsi,id->bsd", comb.astype(h.dtype),
                               lp["wo_comb"])
            ys["ssm"] = hs_new
        else:
            h = h + jnp.einsum("bshk,hkd->bsd", a_out, lp["attn"]["wo"])
        if cfg.is_encdec:
            x = L.norm(h, lp.get("lnx"), cfg.norm)
            cq = jnp.einsum("bsd,dhk->bshk", x, lp["xattn"]["wq"])
            src_len = xs["ck"].shape[1]
            c_out = attn_lib.decode_attention(
                cq, xs["ck"], xs["cv"],
                jnp.full((b,), src_len - 1, jnp.int32))
            h = h + jnp.einsum("bshk,hkd->bsd", c_out, lp["xattn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = _ffn(lp, cfg, x, sh)
        h = h + f_out
        return (h, kv), ys

    xs = {"lp": params["layers"]}
    if flags is not None:
        xs["ssm"] = cache["ssm_h"]
        xs["flag"] = flags
    if cfg.is_encdec:
        xs["ck"], xs["cv"] = cache["ck"], cache["cv"]
    h, ys, new_cache = _scan_kv_carried(layer, h, cache, names, xs)
    if cfg.block == "hymba":
        new_cache["ssm_h"] = ys["ssm"]
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)[:, 0]
    return logits, new_cache


def _paged_write(pool, layer, new_kv, write_table, w_pos):
    """Scatter per-row KV into layer `layer` of the stacked physical page
    pool through the write table.  pool: (L, P, ps, K, hd); new_kv:
    (B, ..., K, hd) matching w_pos (B, ...) absolute positions.
    Unmapped / cache-shared / out-of-range positions resolve to the
    sentinel and drop on device — the paged analogue of
    `prefill_suffix`'s mode="drop" scatter."""
    n_pages, ps = pool.shape[1], pool.shape[2]
    pps = write_table.shape[1]
    slot_page = w_pos // ps                                  # (B, Q)
    pid = jnp.take_along_axis(write_table,
                              jnp.minimum(slot_page, pps - 1), axis=1)
    pid = jnp.where(slot_page < pps, pid, n_pages)
    return pool.at[layer, pid, w_pos % ps].set(
        new_kv.astype(pool.dtype), mode="drop")


def decode_step_paged(params, cfg: ArchConfig, cache, token, pos,
                      page_table, write_table, *, sh: Sharder = _id_sh):
    """One decode step directly against the paged physical KV pool — no
    gathered logical view.  token/pos: (B,) int32 as in `decode_step`;
    page_table/write_table: (B, pps) int32 with sentinel == n_pages.
    Paged leaves are the flat (L, n_pages, page_size, K, hd) pools;
    constant-size leaves (ssm states, enc-dec cross KV) stay
    slot-resident.  The pools ride in the layer loop's carry and each
    layer writes its token into them in place, so a step never copies a
    whole pool.  Returns (logits (B, V), new_cache).

    Same family coverage as the paged engine (everything but xlstm);
    quantized KV keeps the gather path — per-page scale layout isn't
    paged yet.
    """
    if cfg.block == "xlstm" or "k_scale" in cache:
        raise NotImplementedError(
            "decode_step_paged: xlstm has no KV to page; quantized KV "
            "uses the gather path")
    b = token.shape[0]
    h = jnp.take(params["embed"], token, axis=0)[:, None]      # (B,1,D)
    flags = _is_global_flags(cfg) if cfg.block == "hymba" else None
    prefix = cfg.n_meta_tokens + cfg.n_prefix_tokens
    nkv = cfg.n_kv_heads

    def layer(carry, xs):
        h, kv = carry
        lp, i = xs["lp"], xs["layer"]
        hs = xs.get("ssm")
        is_glob = xs.get("flag", cfg.swa_window == 0)
        if isinstance(is_glob, bool):
            window = 0 if is_glob else cfg.swa_window
        else:
            window = jnp.where(is_glob, 0, cfg.swa_window)
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
        cos, sin = L.rope_cos_sin(pos[:, None], cfg.head_dim,
                                  cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        kv = {"k": _paged_write(kv["k"], i, k_new, write_table,
                                pos[:, None]),
              "v": _paged_write(kv["v"], i, v_new, write_table,
                                pos[:, None])}
        ys = {}
        qf = q[:, 0].reshape(b, nkv, q.shape[2] // nkv, cfg.head_dim)
        a_out = kernel_ops.paged_decode_attention(
            qf, kv["k"][i], kv["v"][i], page_table, pos, window=window,
            prefix=prefix)
        a_out = a_out.reshape(b, 1, q.shape[2], cfg.head_dim)
        if cfg.block == "hymba":
            inner = ssm_inner(cfg)
            a_out = a_out.reshape(b, 1, inner)
            s_out, hs_new = _hymba_ssm_step(lp["ssm"], cfg, x[:, 0], hs)
            a_n = L.rms_norm(a_out, lp["branch_norm_attn"])
            s_n = L.rms_norm(s_out[:, None], lp["branch_norm_ssm"])
            comb = (lp["beta"][0] * a_n.astype(jnp.float32)
                    + lp["beta"][1] * s_n.astype(jnp.float32)) * 0.5
            h = h + jnp.einsum("bsi,id->bsd", comb.astype(h.dtype),
                               lp["wo_comb"])
            ys["ssm"] = hs_new
        else:
            h = h + jnp.einsum("bshk,hkd->bsd", a_out, lp["attn"]["wo"])
        if cfg.is_encdec:
            x = L.norm(h, lp.get("lnx"), cfg.norm)
            cq = jnp.einsum("bsd,dhk->bshk", x, lp["xattn"]["wq"])
            src_len = xs["ck"].shape[1]
            c_out = attn_lib.decode_attention(
                cq, xs["ck"], xs["cv"],
                jnp.full((b,), src_len - 1, jnp.int32))
            h = h + jnp.einsum("bshk,hkd->bsd", c_out, lp["xattn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = _ffn(lp, cfg, x, sh)
        h = h + f_out
        return (h, kv), ys

    xs = {"lp": params["layers"]}
    if flags is not None:
        xs["ssm"] = cache["ssm_h"]
        xs["flag"] = flags
    if cfg.is_encdec:
        xs["ck"], xs["cv"] = cache["ck"], cache["cv"]
    h, ys, new_cache = _scan_kv_carried(layer, h, cache, ("k", "v"), xs)
    if cfg.block == "hymba":
        new_cache["ssm_h"] = ys["ssm"]
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)[:, 0]
    return logits, new_cache


def spec_verify_paged(params, cfg: ArchConfig, cache, tokens, pos,
                      page_table, write_table, *, sh: Sharder = _id_sh):
    """Speculative-decoding verify: run Q = 1 + n_draft tokens per row in
    one forward against the paged pool, causal by absolute position —
    the multi-token generalization of `decode_step_paged`, exactly as
    `prefill_suffix` generalizes `decode_step`.  tokens: (B, Q) — the
    last accepted token followed by the draft chain; pos: (B,) absolute
    position of tokens[:, 0].  KV for *every* fed position is written
    through the write table (rejected drafts leave garbage beyond the
    accepted position — masked by causality and overwritten when decode
    resumes there).  The pools ride in the layer loop's carry, written in
    place as in `decode_step_paged`, so a verify never copies a whole
    pool.  Returns (logits (B, Q, V), new_cache).

    Plain causal decoders only (recurrent state can't roll back a
    rejected draft; windows/prefix/cross-KV change visibility) — the
    engine gates speculation on the same predicate as the prefix cache.
    """
    if cfg.block in ("xlstm", "hymba") or cfg.is_encdec \
            or cfg.swa_window or cfg.n_meta_tokens \
            or cfg.n_prefix_tokens or "k_scale" in cache:
        raise NotImplementedError(
            "spec_verify_paged supports plain causal decoders only")
    b, qn = tokens.shape
    h = jnp.take(params["embed"], tokens, axis=0)              # (B,Q,D)
    q_pos = pos[:, None] + jnp.arange(qn)[None, :]             # (B,Q)

    def layer(carry, xs):
        h, kv = carry
        lp, i = xs["lp"], xs["layer"]
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
        cos, sin = L.rope_cos_sin(q_pos, cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        kv = {"k": _paged_write(kv["k"], i, k_new, write_table, q_pos),
              "v": _paged_write(kv["v"], i, v_new, write_table, q_pos)}
        a_out = kernel_ops.paged_suffix_attention(
            q, kv["k"][i], kv["v"][i], page_table, pos)
        h = h + jnp.einsum("bshk,hkd->bsd", a_out, lp["attn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = _ffn(lp, cfg, x, sh)
        h = h + f_out
        return (h, kv), {}

    h, _, new_cache = _scan_kv_carried(layer, h, cache, ("k", "v"),
                                       {"lp": params["layers"]})
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", h, head)
    return logits, new_cache
