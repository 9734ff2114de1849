"""The decode layer loop writes each step's K/V in place.

`decode_step`, `decode_step_paged` and `spec_verify_paged` carry the
stacked KV leaves through the layer scan and write the step's tokens
into them.  Two checks:

* structure: the compiled fused decode holds no copy and no
  dynamic-update-slice of the whole KV view (gather path) or pool
  (page-table path) — scanning the cache in as `xs` and out as `ys`
  re-stacked it into a new array every step;
* parity: one call of each, on every family it serves, returns the same
  logits and the same cache, leaf for leaf, as the scanned-in /
  re-stacked formulation kept below as the reference.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.kernels import ops as kernel_ops
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import transformer as tf
from repro.serving import EngineConfig, InferenceEngine

# --------------------------------------------------------------------- #
# structure of the compiled fused decode

_HLO_OP = re.compile(r"=\s*\w+\[([\d,]*)\](?:\{[^}]*\})?\s+([\w\-]+)\(")


def _ops_of_dims(hlo: str, dims):
    """Opcodes of every HLO instruction whose array shape is `dims`
    (any dtype: the CPU backend computes bf16 leaves in f32)."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) == dims:
            found.append(m.group(2))
    return found


@pytest.mark.parametrize("paged_attention", [False, True],
                         ids=["gather", "page_table"])
def test_fused_decode_has_no_full_cache_copy(paged_attention, param_store):
    cfg = ARCHS["olmo-1b"].reduced()
    ns, ml, ps = 2, 64, 16
    eng = InferenceEngine(cfg, param_store(cfg), EngineConfig(
        n_slots=ns, max_len=ml, page_size=ps,
        paged_attention=paged_attention))
    args = (eng.params, eng.cache, eng.last_tok, eng.pos, eng.active,
            eng.remaining, eng.temps, eng.top_ks, eng.top_ps, eng.eos_ids,
            eng._key, eng.pool.page_table(), eng.pool.write_table())
    hlo = eng._fused_decode["greedy"].lower(*args).compile().as_text()
    kv = (cfg.n_kv_heads, cfg.head_dim)
    pool = (cfg.n_layers, eng.pool.n_pages, ps) + kv
    view = (cfg.n_layers, ns, ml) + kv
    assert pool != view
    # the layer loop's cache: the gathered view, or the pool itself
    whole = pool if paged_attention else view
    ops = _ops_of_dims(hlo, whole)
    assert ops, f"no op of shape {whole}: the probe lost the cache"
    assert "copy" not in ops, ops
    assert "dynamic-update-slice" not in ops, ops


# --------------------------------------------------------------------- #
# parity with the scanned-in / re-stacked formulation

def _ref_paged_write(cache_pool, new_kv, write_table, w_pos):
    """One layer's (P, ps, K, hd) pool, written through the write
    table; sentinel pages drop."""
    n_pages, ps = cache_pool.shape[0], cache_pool.shape[1]
    pps = write_table.shape[1]
    slot_page = w_pos // ps
    pid = jnp.take_along_axis(write_table,
                              jnp.minimum(slot_page, pps - 1), axis=1)
    pid = jnp.where(slot_page < pps, pid, n_pages)
    return cache_pool.at[pid, w_pos % ps].set(
        new_kv.astype(cache_pool.dtype), mode="drop")


def _ref_decode(params, cfg, cache, token, pos, tables=None):
    """`decode_step` (tables None) or `decode_step_paged` (tables =
    (page_table, write_table)) with the KV leaves scanned in as `xs` and
    re-stacked from `ys`."""
    b = token.shape[0]
    h = jnp.take(params["embed"], token, axis=0)[:, None]
    flags = tf._is_global_flags(cfg) if cfg.block == "hymba" else None
    prefix = cfg.n_meta_tokens + cfg.n_prefix_tokens
    quant = "k_scale" in cache
    nkv = cfg.n_kv_heads

    def layer(h, xs):
        lp = xs["lp"]
        kc, vc = xs["k"], xs["v"]
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
        ys = {}
        if tables is not None:
            page_table, write_table = tables
            kc = _ref_paged_write(kc, k_new, write_table, pos[:, None])
            vc = _ref_paged_write(vc, v_new, write_table, pos[:, None])
            qf = q[:, 0].reshape(b, nkv, q.shape[2] // nkv, cfg.head_dim)
            a_out = kernel_ops.paged_decode_attention(
                qf, kc, vc, page_table, pos, window=window, prefix=prefix)
            a_out = a_out.reshape(b, 1, q.shape[2], cfg.head_dim)
        else:
            upd = jax.vmap(
                lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(
                    c, n, p, axis=0))
            if quant:
                kq, ks_new = tf.kv_quantize(k_new)
                vq, vs_new = tf.kv_quantize(v_new)
                kc, vc = upd(kc, kq, pos), upd(vc, vq, pos)
                ks = upd(xs["ks"], ks_new, pos)
                vs = upd(xs["vs"], vs_new, pos)
                ys.update(ks=ks, vs=vs)
                k_at, v_at = tf.kv_dequant(kc, ks), tf.kv_dequant(vc, vs)
            else:
                kc, vc = upd(kc, k_new, pos), upd(vc, v_new, pos)
                k_at, v_at = kc, vc
            a_out = attn_lib.decode_attention(q, k_at, v_at, pos,
                                              window=window, prefix=prefix)
        ys.update(k=kc, v=vc)
        if cfg.block == "hymba":
            a_out = a_out.reshape(b, 1, tf.ssm_inner(cfg))
            s_out, hs_new = tf._hymba_ssm_step(lp["ssm"], cfg, x[:, 0],
                                               xs["ssm"])
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
            c_out = attn_lib.decode_attention(
                cq, xs["ck"], xs["cv"],
                jnp.full((b,), xs["ck"].shape[1] - 1, jnp.int32))
            h = h + jnp.einsum("bshk,hkd->bsd", c_out, lp["xattn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = tf._ffn(lp, cfg, x, tf._id_sh)
        return h + f_out, ys

    xs = {"lp": params["layers"], "k": cache["k"], "v": cache["v"]}
    if quant:
        xs["ks"], xs["vs"] = cache["k_scale"], cache["v_scale"]
    if flags is not None:
        xs["ssm"], xs["flag"] = cache["ssm_h"], flags
    if cfg.is_encdec:
        xs["ck"], xs["cv"] = cache["ck"], cache["cv"]
    h, ys = jax.lax.scan(layer, h, xs)
    new_cache = dict(cache, k=ys["k"], v=ys["v"])
    if quant:
        new_cache["k_scale"], new_cache["v_scale"] = ys["ks"], ys["vs"]
    if cfg.block == "hymba":
        new_cache["ssm_h"] = ys["ssm"]
    return _head(params, cfg, h)[:, 0], new_cache


def _ref_verify(params, cfg, cache, tokens, pos, page_table, write_table):
    """`spec_verify_paged` with the pools scanned in and re-stacked."""
    h = jnp.take(params["embed"], tokens, axis=0)
    q_pos = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]

    def layer(h, xs):
        lp = xs["lp"]
        x = L.norm(h, lp.get("ln1"), cfg.norm)
        q = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wq"])
        k_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, lp["attn"]["wv"])
        cos, sin = L.rope_cos_sin(q_pos, cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k_new = L.apply_rope(k_new, cos, sin)
        kc = _ref_paged_write(xs["k"], k_new, write_table, q_pos)
        vc = _ref_paged_write(xs["v"], v_new, write_table, q_pos)
        a_out = kernel_ops.paged_suffix_attention(q, kc, vc,
                                                  page_table, pos)
        h = h + jnp.einsum("bshk,hkd->bsd", a_out, lp["attn"]["wo"])
        x = L.norm(h, lp.get("ln2"), cfg.norm)
        f_out, _ = tf._ffn(lp, cfg, x, tf._id_sh)
        return h + f_out, {"k": kc, "v": vc}

    xs = {"lp": params["layers"], "k": cache["k"], "v": cache["v"]}
    h, ys = jax.lax.scan(layer, h, xs)
    return _head(params, cfg, h), dict(cache, k=ys["k"], v=ys["v"])


def _head(params, cfg, h):
    h = L.norm(h, params.get("final_norm"), cfg.norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, head)


B, MAX_LEN, PS = 3, 32, 8
PPS = MAX_LEN // PS
N_PAGES = B * PPS + 2

_FAMILIES = {
    "dense": lambda: ARCHS["olmo-1b"].reduced(),
    "gqa": lambda: dataclasses.replace(ARCHS["olmo-1b"].reduced(),
                                       name="olmo-1b-reduced-gqa",
                                       n_kv_heads=2),
    "window": lambda: ARCHS["starcoder2-3b"].reduced(),
    "prefix": lambda: ARCHS["internvl2-76b"].reduced(),
    "hymba": lambda: ARCHS["hymba-1.5b"].reduced(),
    "encdec": lambda: ARCHS["seamless-m4t-large-v2"].reduced(),
}

_CASES = ([("decode", f) for f in _FAMILIES] + [("decode_kv_quant", "dense")]
          + [("paged", f) for f in ("dense", "gqa", "window", "hymba",
                                    "encdec")]
          + [("verify", f) for f in ("dense", "gqa")])


def _random_like(tree, key):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for x, k in zip(leaves, keys):
        if x.dtype == jnp.int8:
            out.append(jax.random.randint(k, x.shape, -127, 128, jnp.int8))
        else:
            out.append(jax.random.uniform(k, x.shape, jnp.float32, 0.01, 1)
                       .astype(x.dtype))
    return jax.tree.unflatten(treedef, out)


def _tables(key):
    """A shuffled page table, and a write table whose first page of
    slot 0 is cache-shared (sentinel) so its writes must drop."""
    perm = jax.random.permutation(key, N_PAGES)[:B * PPS]
    page_table = perm.reshape(B, PPS).astype(jnp.int32)
    write_table = page_table.at[0, 0].set(N_PAGES)
    return page_table, write_table


@pytest.mark.parametrize("kind,family", _CASES,
                         ids=[f"{k}-{f}" for k, f in _CASES])
def test_carried_kv_matches_restacked_reference(kind, family, param_store):
    cfg = _FAMILIES[family]()
    params = param_store(cfg)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(7), 3)
    cache = tf.init_cache(cfg, B, MAX_LEN, src_len=8 if cfg.is_encdec else 0,
                          kv_quant=(kind == "decode_kv_quant"))
    if kind in ("paged", "verify"):
        pool = (cfg.n_layers, N_PAGES, PS, cfg.n_kv_heads, cfg.head_dim)
        cache["k"] = jnp.zeros(pool, cache["k"].dtype)
        cache["v"] = jnp.zeros(pool, cache["v"].dtype)
    cache = _random_like(cache, k0)
    if kind == "verify":
        tokens = jax.random.randint(k1, (B, 4), 0, cfg.vocab, jnp.int32)
        # slot 0 writes into its shared first page; slot 2 runs past the
        # last page, whose positions drop
        pos = jnp.array([1, 9, MAX_LEN - 2], jnp.int32)
    else:
        tokens = jax.random.randint(k1, (B,), 0, cfg.vocab, jnp.int32)
        # slot 2 is a finished slot at max_len: the write clamps (gather)
        # or drops (paged)
        pos = jnp.array([5, 17, MAX_LEN], jnp.int32)
    if kind in ("paged", "verify"):
        tables = _tables(k2)
        if kind == "paged":
            got = tf.decode_step_paged(params, cfg, cache, tokens, pos,
                                       *tables)
            want = _ref_decode(params, cfg, cache, tokens, pos, tables)
        else:
            got = tf.spec_verify_paged(params, cfg, cache, tokens, pos,
                                       *tables)
            want = _ref_verify(params, cfg, cache, tokens, pos, *tables)
    else:
        got = tf.decode_step(params, cfg, cache, tokens, pos)
        want = _ref_decode(params, cfg, cache, tokens, pos)
    (got_logits, got_cache), (want_logits, want_cache) = got, want
    np.testing.assert_array_equal(_np(got_logits), _np(want_logits))
    assert got_cache.keys() == want_cache.keys()
    for name in want_cache:
        np.testing.assert_array_equal(_np(got_cache[name]),
                                      _np(want_cache[name]), err_msg=name)
    # the step wrote something: the test would pass vacuously otherwise
    assert not np.array_equal(_np(got_cache["k"]), _np(cache["k"]))


def _np(x):
    return np.asarray(x.astype(jnp.float32) if x.dtype == jnp.bfloat16
                      else x)
