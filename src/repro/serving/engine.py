"""Continuous-batching inference engine — the Ollama analogue each backend
node runs, one per deployed model instance.

Fully GPU/TPU-accelerated path (no CPU fallback, per the paper): the hot
loop is *device-resident*.  Each `step()` issues at most two jitted
dispatches:

* **bucketed prefill** — queued prompts are padded to power-of-two length
  buckets and admitted as one batch per bucket; the jitted call runs the
  forward, scatters every row's cache into its pages (or slot strip),
  samples the first token per row, and updates the persistent per-slot
  state arrays — all on device.  Distinct prompt lengths inside one bucket
  share a single trace (`prefill_traces` counts compiles to prove it).
* **fused K-step decode** — a jitted `lax.scan` runs `decode_block`
  decode+sample steps per dispatch, carrying `(cache, last_tok, pos, key)`
  on device, applying per-slot temperature/top-k/top-p and an on-device
  done mask (EOS or token budget) so finished slots stop advancing
  mid-scan.  Exactly one blocking `device_get` brings back the
  `(K, n_slots)` token block plus emit/done flags.

KV memory is **paged** (`EngineConfig.paged`, default on): the physical
cache is a flat pool of `page_size`-token pages and each slot owns a page
table (`serving.kv_cache.PagedKVPool`).  The fused decode gathers every
slot's logical view through the page table once per dispatch and scatters
it back once — zero extra host syncs — while bucketed prefill lands rows
directly in their pages.  Slots may be *oversubscribed* against the page
budget (`kv_pages` below the contiguous-equivalent `n_slots x
pages_per_slot`): admission is page-aware via the two-level DWRR
scheduler, page tables grow at decode-block boundaries, and on page
exhaustion the engine preempts the lowest-deficit tenant's slot — the
victim re-enters the front of its tenant queue and later *resumes* from
its full context (prompt + generated so far) without re-emitting a token.
`paged=False` restores the contiguous per-slot strips (every slot
reserves its full `max_len` worth of pages up front) for apples-to-apples
studies.

Per-slot sampling params live in persistent device arrays written only on
admission/release/cancel — no host->device uploads or `.at[].set()` loops
inside the hot path.  Weights may be held quantized (int8/int4) at rest
and dequantized on-chip per step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.models import build
from repro.serving import quantization as q_lib
from repro.serving.kv_cache import (PagedKVPool, cache_bytes, gather_pages,
                                    scatter_pages, scatter_prefill_rows,
                                    split_paged, write_slots)
from repro.serving.kv_hierarchy import (HostPagePool, PrefixCache,
                                        drop_handle, swap_in_slot,
                                        swap_out_slot)
from repro.serving.request import (CODE_ENGINE_FAILED, CODE_INVALID_REQUEST,
                                   Request, RequestState)
from repro.serving.sampler import sample_batched
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving import spec_decode as spec_lib


@dataclasses.dataclass
class EngineConfig:
    n_slots: int = 4
    max_len: int = 128
    quantize: str = ""            # "", "int8", "int4"
    top_k: int = 0                # engine-wide default (per-request wins)
    top_p: float = 1.0
    seed: int = 0
    decode_block: int = 4         # K decode steps fused per dispatch
    prefill_bucket_min: int = 8   # smallest power-of-two prompt bucket
    page_size: int = 16           # KV tokens per physical page
    kv_pages: int = 0             # page budget; 0 => n_slots full strips
    paged: bool = True            # False => contiguous per-slot strips
    # hierarchical KV memory (kv_hierarchy): both tiers default OFF so
    # the baseline engine keeps PR 5's exact allocation behavior
    prefix_cache: bool = False    # cross-request prefix page reuse
    prefix_cache_pages: int = 0   # device pages the cache may pin; 0 => no cap
    host_kv_pages: int = 0        # host-DRAM swap-tier pages; 0 => off
    prefix_share_tenants: bool = False  # share prefix blocks across tenants
    # paged attention + on-device speculative decoding
    paged_attention: bool = False  # attend through the page table (no
    #                                per-dispatch gather/scatter copy)
    speculative: bool = False     # n-gram propose + batched greedy verify
    spec_draft: int = 4           # draft tokens proposed per verify
    spec_table: int = 512         # proposer hash-table buckets (pow2)


class EngineFailure(RuntimeError):
    pass


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _identity(p):
    return p


class InferenceEngine:
    """One model instance on one node."""

    def __init__(self, cfg: ArchConfig, params, engine_cfg: EngineConfig,
                 scheduler: Optional[Scheduler] = None,
                 device: Optional[jax.Device] = None, node: str = ""):
        self.cfg = cfg
        self.ecfg = engine_cfg
        # the serving node's id, carried by this engine's spans
        self.node = node
        self._phase = obs.Phases(node=node)
        # every array this engine holds is committed to one device, so
        # its jitted steps run there whichever thread dispatches them
        self.device = device if device is not None else jax.devices()[0]
        self.model = build(cfg)
        self.scheduler = scheduler or Scheduler(SchedulerConfig())
        self._dead = False
        self._key = jax.device_put(jax.random.PRNGKey(engine_cfg.seed),
                                   self.device)
        # recurrent families fold right-pads into their state, so they
        # batch prefills at exact lengths instead of padded buckets
        self._supports_bucket = cfg.block not in ("xlstm", "hymba")
        # meta/vision-prefix tokens occupy cache slots ahead of the prompt
        self._prefix_tokens = (getattr(cfg, "n_meta_tokens", 0)
                               + getattr(cfg, "n_prefix_tokens", 0))
        # state-space caches are constant-size: only KV families run out
        # of cache positions and must stop decoding at max_len
        self._pos_limit = (engine_cfg.max_len if cfg.block != "xlstm"
                           else 2 ** 30)
        # xlstm state has no sequence axis: nothing to page
        self._paged = engine_cfg.paged and cfg.block != "xlstm"
        self.pool = PagedKVPool(engine_cfg.n_slots, engine_cfg.max_len,
                                page_size=engine_cfg.page_size,
                                n_pages=(engine_cfg.kv_pages
                                         if self._paged else 0))
        # page-aware admission: the scheduler charges each queued request
        # its projected page cost against the engine's free page budget
        self.scheduler.pages_for = self._pages_for
        # hierarchical KV memory: prefix reuse needs page-aligned bucketed
        # prefill over a plain causal decoder (recurrent state, enc-dec
        # cross KV, windows and prefix tokens all break block sharing)
        self._prefix_ok = (self._paged and self._supports_bucket
                           and not cfg.is_encdec
                           and self._prefix_tokens == 0
                           and getattr(cfg, "swa_window", 0) == 0)
        # page-table-direct attention: any paged family (recurrent state
        # and enc-dec cross KV stay slot-resident either way)
        self._paged_attn = engine_cfg.paged_attention and self._paged
        # speculative decoding needs the paged-attention verify path and
        # a plain causal decoder (rejected drafts must be erasable by
        # overwrite: recurrent state can't roll back, windows/prefix
        # change visibility) — the same predicate as the prefix cache
        self._spec_ok = (engine_cfg.speculative and self._paged_attn
                         and self._prefix_ok)
        self.host_pool = (HostPagePool(engine_cfg.host_kv_pages)
                          if engine_cfg.host_kv_pages > 0 and self._paged
                          else None)
        self.prefix_cache = (
            PrefixCache(self.pool, host=self.host_pool,
                        max_device_pages=engine_cfg.prefix_cache_pages,
                        share_tenants=engine_cfg.prefix_share_tenants)
            if engine_cfg.prefix_cache and self._prefix_ok else None)
        self._swapped: Dict[int, Any] = {}   # request_id -> SwapHandle

        params = jax.device_put(params, self.device)
        if engine_cfg.quantize:
            bits = 8 if engine_cfg.quantize == "int8" else 4
            self.params = q_lib.quantize_tree(params, bits=bits)
            self._dequant = q_lib.dequant_tree
        else:
            self.params = params
            self._dequant = _identity

        src_len = engine_cfg.max_len if cfg.is_encdec else 0
        self.cache = self._init_cache(src_len)
        self.slot_req: Dict[int, Request] = {}
        # persistent per-slot device state: touched only by jitted
        # admission / fused-decode calls and the (rare) cancel path
        ns = engine_cfg.n_slots
        dev = self.device
        self.pos = jnp.zeros((ns,), jnp.int32, device=dev)
        self.last_tok = jnp.zeros((ns,), jnp.int32, device=dev)
        self.active = jnp.zeros((ns,), bool, device=dev)
        self.remaining = jnp.zeros((ns,), jnp.int32, device=dev)
        self.temps = jnp.zeros((ns,), jnp.float32, device=dev)
        self.top_ks = jnp.zeros((ns,), jnp.int32, device=dev)
        self.top_ps = jnp.ones((ns,), jnp.float32, device=dev)
        self.eos_ids = jnp.full((ns,), -1, jnp.int32, device=dev)
        # speculative-decoding device state: per-slot bigram proposer
        # table plus the token *preceding* last_tok (the chain seed) —
        # wiped on admission/release so a reused slot never proposes
        # from another request's stream
        self.spec_table, self.spec_prev = jax.device_put(
            spec_lib.init_tables(ns, engine_cfg.spec_table), dev)
        # logical KV bytes one fused dispatch moves: the gather path
        # copies every slot's logical view out and back (2x view); the
        # page-table path only writes K new tokens' KV in place
        self._view_bytes = 0
        self._write_token_bytes = 0     # all-slot KV write bytes, 1 step
        if self._paged:
            for leaf in split_paged(self.cache)[0].values():
                per_tok = (leaf.dtype.itemsize * leaf.shape[0]
                           * int(np.prod(leaf.shape[3:])))
                self._view_bytes += (per_tok * ns * self.pool.pages_per_slot
                                     * self.pool.page_size)
                self._write_token_bytes += per_tok * ns
        # decode-boundary page growth must also cover a verify's D+1
        # in-flight writes when speculation is live
        self._growth = max(engine_cfg.decode_block,
                           engine_cfg.spec_draft + 1) if self._spec_ok \
            else engine_cfg.decode_block
        # metrics
        self.total_tokens = 0
        self.total_steps = 0
        self.dispatches = 0       # jitted calls issued
        self.host_syncs = 0       # blocking device->host transfers
        self.prefill_traces = 0   # compile-cache counter: bucketed prefill
        self.decode_traces = 0    # compiles once per decode_block
        self.suffix_traces = 0    # compile-cache counter: suffix prefill
        self.preemptions = 0      # slots evicted on page exhaustion
        self.prefill_dispatch_tokens = 0   # rows x bucket actually forwarded
        self.suffix_prefills = 0  # rows admitted via cached-prefix suffix
        self.swap_outs = 0        # slots parked to the host tier
        self.swap_ins = 0         # slots restored with zero re-prefill
        # paged-attention / speculative-decoding counters
        self.logical_bytes_moved = 0   # KV bytes copied/written per decode
        self.spec_traces = 0      # compile-cache counter: verify dispatch
        self.spec_dispatches = 0  # verify dispatches issued
        self.spec_emitted = 0     # tokens emitted by verify dispatches
        self.spec_slot_accepted = np.zeros((ns,), np.int64)  # drafts/slot
        self._build_steps()

    # ------------------------------------------------------------- #
    def _init_cache(self, src_len: int):
        """Physical cache: paged leaves live as a flat (layers, n_pages,
        page_size, ...) pool; constant-size leaves (ssm states, encoder
        cross-attention KV, the whole xlstm state) stay slot-resident."""
        ns, ml = self.ecfg.n_slots, self.ecfg.max_len
        if not self._paged:
            with jax.default_device(self.device):
                cache = self.model.init_cache(ns, ml, src_len=src_len)
            return jax.device_put(cache, self.device)
        spec = jax.eval_shape(
            lambda: self.model.init_cache(ns, ml, src_len=src_len))
        paged, resident = split_paged(spec)
        cache = {name: jnp.zeros(s.shape, s.dtype, device=self.device)
                 for name, s in resident.items()}
        for name, s in paged.items():
            cache[name] = jnp.zeros(
                (s.shape[0], self.pool.n_pages, self.pool.page_size)
                + s.shape[3:], s.dtype, device=self.device)
        return cache

    def _pages_for(self, req: Request) -> int:
        """Projected page cost of admitting `req` now: its full effective
        context (prompt + already-generated resume tokens + prefix) plus
        one position of decode headroom — *net* of prefix-cache pages it
        would map for free and, for a swap-parked request, net of the
        shared pages its handle already holds on device."""
        if not self._paged:
            return self.pool.pages_per_slot
        handle = self._swapped.get(req.request_id)
        if handle is not None:
            return max(len(handle.host), 1)
        eff = (len(req.prompt) + len(req.output) + self._prefix_tokens)
        need = self.pool.pages_for_tokens(min(eff + 1, self.ecfg.max_len))
        if self.prefix_cache is not None:
            eff0 = len(req.prompt) + len(req.output)
            cached = self.prefix_cache.peek(
                req.tenant, list(req.prompt) + list(req.output),
                eff0 - 1) // self.pool.page_size
            need = max(need - cached, 1)
        return need

    # ------------------------------------------------------------- #
    def _build_steps(self):
        model, ecfg = self.model, self.ecfg
        paged = self._paged
        paged_attn = self._paged_attn

        def prefill_admit(params, cache, last_tok, pos, active, remaining,
                          temps, top_ks, top_ps, eos_ids, key,
                          spec_table, spec_prev,
                          tokens, lengths, slots, row_pages,
                          r_temps, r_topk, r_topp, r_eos, r_budget,
                          r_prev, extra):
            # Python side effect fires at trace time only: counts compiles
            self.prefill_traces += 1
            p = self._dequant(params)
            kw = dict(extra)
            if self._supports_bucket:
                kw["lengths"] = lengths
            if paged:
                # rows at their true (bucketed) length; pages don't need
                # max_len-wide rows
                logits, rows_cache, pos1 = model.prefill(p, tokens, **kw)
                rows_p, rows_r = split_paged(rows_cache)
                pool_p, pool_r = split_paged(cache)
                pool_p = scatter_prefill_rows(pool_p, rows_p, row_pages)
                pool_r = write_slots(pool_r, rows_r, slots)
                cache = {**pool_p, **pool_r}
            else:
                logits, rows_cache, pos1 = model.prefill(
                    p, tokens, cache_len=ecfg.max_len, **kw)
                cache = write_slots(cache, rows_cache, slots)
            key, sk = jax.random.split(key)
            first = sample_batched(logits, sk, r_temps, r_topk, r_topp)
            done0 = ((r_budget <= 1) | ((r_eos >= 0) & (first == r_eos))
                     # prompt fills the cache: no room to decode further
                     | (pos1 + 1 >= self._pos_limit))
            # scatter admission state; padded rows carry slot == n_slots
            # and are dropped on device
            last_tok = last_tok.at[slots].set(first, mode="drop")
            pos = pos.at[slots].set(pos1 + 1, mode="drop")
            active = active.at[slots].set(~done0, mode="drop")
            remaining = remaining.at[slots].set(r_budget - 1, mode="drop")
            temps = temps.at[slots].set(r_temps, mode="drop")
            top_ks = top_ks.at[slots].set(r_topk, mode="drop")
            top_ps = top_ps.at[slots].set(r_topp, mode="drop")
            eos_ids = eos_ids.at[slots].set(r_eos, mode="drop")
            # fresh proposer state: wipe the slot's table row and seed
            # the bigram chain from the last context token
            spec_table = spec_table.at[slots].set(-1, mode="drop")
            spec_prev = spec_prev.at[slots].set(r_prev, mode="drop")
            return (cache, last_tok, pos, active, remaining, temps,
                    top_ks, top_ps, eos_ids, key, spec_table, spec_prev,
                    first, done0)

        def make_fused_decode(mode: str):
            # "greedy": every slot argmax — no PRNG, no sorts.
            # "temp":   temperature only — one categorical, no sorts.
            # "full":   per-slot top-k/top-p filters too.
            def fused_decode(params, cache, last_tok, pos, active,
                             remaining, temps, top_ks, top_ps, eos_ids,
                             key, page_table, write_table):
                self.decode_traces += 1
                p = self._dequant(params)
                if paged and not paged_attn:
                    pool_p, pool_r = split_paged(cache)
                    # one gather per dispatch materializes every slot's
                    # logical view through its page table
                    view = {**gather_pages(pool_p, page_table), **pool_r}
                else:
                    # page-table-direct attention (or contiguous strips):
                    # the physical cache is the working view — no copy
                    view = cache

                def body(carry, _):
                    view, last_tok, pos, active, remaining, key = carry
                    if paged_attn:
                        logits, view = model.decode_paged(
                            p, view, last_tok, pos, page_table,
                            write_table)
                    else:
                        logits, view = model.decode(p, view, last_tok,
                                                    pos)
                    if mode == "greedy":
                        sampled = jnp.argmax(logits, axis=-1) \
                            .astype(jnp.int32)
                    else:
                        key, sk = jax.random.split(key)
                        sampled = sample_batched(
                            logits, sk, temps, top_ks, top_ps,
                            use_top_k=(mode == "full"),
                            use_top_p=(mode == "full"))
                    tok = jnp.where(active, sampled, last_tok)
                    emit = active
                    remaining = jnp.where(active, remaining - 1,
                                          remaining)
                    pos = pos + active.astype(jnp.int32)
                    done = active & (((eos_ids >= 0) & (tok == eos_ids))
                                     | (remaining <= 0)
                                     # out of cache positions: the next
                                     # write would fall past max_len
                                     | (pos >= self._pos_limit))
                    carry = (view, tok, pos, active & ~done, remaining,
                             key)
                    return carry, (tok, emit, done)

                init = (view, last_tok, pos, active, remaining, key)
                carry, (toks, emits, dones) = jax.lax.scan(
                    body, init, None, length=ecfg.decode_block)
                view, last_tok, pos, active, remaining, key = carry
                if paged and not paged_attn:
                    view_p, view_r = split_paged(view)
                    # one scatter per dispatch lands the block's writes
                    # back in the physical page pool — through the
                    # *write* table, whose cache-shared entries hold the
                    # sentinel so shared prefix pages stay immutable
                    cache = {**scatter_pages(pool_p, view_p, write_table),
                             **view_r}
                else:
                    cache = view
                return (cache, last_tok, pos, active, remaining, key,
                        toks, emits, dones)
            return fused_decode

        def suffix_admit(params, cache, last_tok, pos, active, remaining,
                         temps, top_ks, top_ps, eos_ids, key,
                         spec_table, spec_prev,
                         tokens, offsets, lengths, slots, read_tables,
                         write_tables, r_temps, r_topk, r_topp, r_eos,
                         r_budget, r_prev):
            """Prefix-cache hit admission: gather each row's logical view
            through its *full* page table (shared prefix + private
            pages), run the suffix-only forward, and scatter back through
            the *write* table (shared pages masked to the sentinel, so
            nothing ever lands in a cache-shared page).  One dispatch,
            one host sync — same discipline as `prefill_admit`."""
            self.suffix_traces += 1
            p = self._dequant(params)
            pool_p, pool_r = split_paged(cache)
            view = gather_pages(pool_p, read_tables)
            logits, view, pos1 = model.prefill_suffix(
                p, view, tokens, offsets, lengths)
            view_p, _ = split_paged(view)
            cache = {**scatter_pages(pool_p, view_p, write_tables),
                     **pool_r}
            key, sk = jax.random.split(key)
            first = sample_batched(logits, sk, r_temps, r_topk, r_topp)
            done0 = ((r_budget <= 1) | ((r_eos >= 0) & (first == r_eos))
                     | (pos1 + 1 >= self._pos_limit))
            last_tok = last_tok.at[slots].set(first, mode="drop")
            pos = pos.at[slots].set(pos1 + 1, mode="drop")
            active = active.at[slots].set(~done0, mode="drop")
            remaining = remaining.at[slots].set(r_budget - 1, mode="drop")
            temps = temps.at[slots].set(r_temps, mode="drop")
            top_ks = top_ks.at[slots].set(r_topk, mode="drop")
            top_ps = top_ps.at[slots].set(r_topp, mode="drop")
            eos_ids = eos_ids.at[slots].set(r_eos, mode="drop")
            spec_table = spec_table.at[slots].set(-1, mode="drop")
            spec_prev = spec_prev.at[slots].set(r_prev, mode="drop")
            return (cache, last_tok, pos, active, remaining, temps,
                    top_ks, top_ps, eos_ids, key, spec_table, spec_prev,
                    first, done0)

        def restore_slots(last_tok, pos, active, remaining, temps,
                          top_ks, top_ps, eos_ids, spec_table, spec_prev,
                          slots, r_last, r_pos,
                          r_budget, r_temps, r_topk, r_topp, r_eos,
                          r_prev):
            """Swap-in resume: rebuild per-slot decode state host-known
            at park time — no model forward, zero re-prefill.  Padded
            rows carry slot == n_slots and drop on device."""
            last_tok = last_tok.at[slots].set(r_last, mode="drop")
            pos = pos.at[slots].set(r_pos, mode="drop")
            active = active.at[slots].set(True, mode="drop")
            remaining = remaining.at[slots].set(r_budget, mode="drop")
            temps = temps.at[slots].set(r_temps, mode="drop")
            top_ks = top_ks.at[slots].set(r_topk, mode="drop")
            top_ps = top_ps.at[slots].set(r_topp, mode="drop")
            eos_ids = eos_ids.at[slots].set(r_eos, mode="drop")
            spec_table = spec_table.at[slots].set(-1, mode="drop")
            spec_prev = spec_prev.at[slots].set(r_prev, mode="drop")
            return (last_tok, pos, active, remaining, temps, top_ks,
                    top_ps, eos_ids, spec_table, spec_prev)

        def clear_slots(last_tok, pos, active, remaining, temps,
                        spec_table, spec_prev, slots):
            """Release/cancel/preempt: wipe per-slot device state so a
            freed slot can never be decoded or sampled with stale
            values — including the speculative proposer row and chain
            seed, so un-verified drafts from a cancelled request can
            never be proposed into a reused slot."""
            last_tok = last_tok.at[slots].set(0, mode="drop")
            pos = pos.at[slots].set(0, mode="drop")
            active = active.at[slots].set(False, mode="drop")
            remaining = remaining.at[slots].set(0, mode="drop")
            temps = temps.at[slots].set(0.0, mode="drop")
            spec_table = spec_table.at[slots].set(-1, mode="drop")
            spec_prev = spec_prev.at[slots].set(-1, mode="drop")
            return (last_tok, pos, active, remaining, temps, spec_table,
                    spec_prev)

        def spec_decode(params, cache, last_tok, pos, active, remaining,
                        eos_ids, spec_table, spec_prev, page_table,
                        write_table):
            """One speculative step: propose D drafts from the bigram
            table, verify [last_tok, drafts] in ONE batched paged
            forward, emit the longest greedy-matching prefix plus the
            verifier's own next token.  Greedy-only (the host picks
            this path only for all-greedy batches), so emitted tokens
            are provably identical to sequential greedy decode.  Same
            shape discipline as fused_decode: returns (D+1, n_slots)
            token/emit/done blocks consumed by the same host tail."""
            self.spec_traces += 1
            p = self._dequant(params)
            d = ecfg.spec_draft
            drafts = spec_lib.propose(spec_table, spec_prev, last_tok, d)
            # missing proposals (-1) are fed as token 0 but can never be
            # accepted: a -1 draft never equals a real argmax token
            x = jnp.concatenate(
                [last_tok[:, None], jnp.maximum(drafts, 0)], axis=1)
            logits, cache = model.verify_paged(
                p, cache, x, pos, page_table, write_table)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            n_acc = spec_lib.accept_length(drafts, greedy[:, :d])

            def body(carry, xs):
                g_i, i = xs
                last_c, prev_c, pos_c, act_c, rem_c, tab = carry
                emit = act_c & (i <= n_acc)
                tok = jnp.where(emit, g_i, last_c)
                rem_c = jnp.where(emit, rem_c - 1, rem_c)
                pos_c = pos_c + emit.astype(jnp.int32)
                done = emit & (((eos_ids >= 0) & (tok == eos_ids))
                               | (rem_c <= 0)
                               | (pos_c >= self._pos_limit))
                # the table learns each emitted transition on device
                tab = spec_lib.record(tab, prev_c, last_c, tok, emit)
                prev_c = jnp.where(emit, last_c, prev_c)
                carry = (tok, prev_c, pos_c, act_c & ~done, rem_c, tab)
                return carry, (tok, emit, done)

            init = (last_tok, spec_prev, pos, active, remaining,
                    spec_table)
            carry, (toks, emits, dones) = jax.lax.scan(
                body, init, (greedy.T, jnp.arange(d + 1)))
            last_tok, spec_prev, pos, active, remaining, spec_table = \
                carry
            return (cache, last_tok, pos, active, remaining, spec_table,
                    spec_prev, toks, emits, dones)

        self._prefill_admit = jax.jit(
            prefill_admit, donate_argnums=tuple(range(1, 13)))
        self._suffix_admit = jax.jit(
            suffix_admit, donate_argnums=tuple(range(1, 13)))
        self._restore_slots = jax.jit(
            restore_slots, donate_argnums=tuple(range(10)))
        decode_donate = (1, 2, 3, 4, 5, 10)
        # three variants; jax compiles each lazily on first use only
        self._fused_decode = {
            mode: jax.jit(make_fused_decode(mode),
                          donate_argnums=decode_donate)
            for mode in ("greedy", "temp", "full")}
        self._clear_slots = jax.jit(
            clear_slots, donate_argnums=tuple(range(7)))
        self._spec_decode = jax.jit(
            spec_decode, donate_argnums=(1, 2, 3, 4, 5, 7, 8))

    # ------------------------------------------------------------- #
    def _extra_inputs(self, batch: int):
        extra = {}
        dt = jnp.bfloat16 if self.cfg.dtype == "bf16" else jnp.float32
        if self.cfg.frontend == "vision":
            extra["prefix_embeds"] = jnp.zeros(
                (batch, self.cfg.n_prefix_tokens, self.cfg.d_model), dt)
        if self.cfg.is_encdec:
            extra["src_embeds"] = jnp.zeros(
                (batch, self.ecfg.max_len, self.cfg.d_model), dt)
        return extra

    def _bucket_of(self, prompt_len: int) -> int:
        """Power-of-two padded length bucket (attention families); exact
        length for recurrent families, which can't absorb pads.  Capped so
        bucket + prefix (meta/vision) tokens never outgrow the pool
        cache."""
        if not self._supports_bucket:
            return prompt_len
        b = self.ecfg.prefill_bucket_min
        while b < prompt_len:
            b <<= 1
        return min(b, self.ecfg.max_len - self._prefix_tokens)

    # ------------------------------------------------------------- #
    def submit(self, req: Request) -> bool:
        if self._dead:
            req.finish(error="engine dead", code=CODE_ENGINE_FAILED)
            return False
        if len(req.prompt) + self._prefix_tokens > self.ecfg.max_len:
            # malformed input, not a capacity problem: reject at submit
            # time instead of surfacing OVERLOADED after dequeue
            req.finish(
                error=(f"prompt length {len(req.prompt)} (+ "
                       f"{self._prefix_tokens} prefix tokens) exceeds "
                       f"engine max_len {self.ecfg.max_len}"),
                code=CODE_INVALID_REQUEST)
            return False
        return self.scheduler.submit(req)

    def fail(self, reason: str = ""):
        """Take the engine down (node/instance crash, or a step that
        raised): every queued and in-flight request finishes with
        ENGINE_FAILED, carrying `reason` when one is given."""
        self._dead = True
        doomed = list(self.slot_req.values())
        self.slot_req.clear()
        # close-and-drain is atomic: a concurrently racing submit either
        # landed in the queue (doomed below) or is rejected by the closed
        # scheduler with ENGINE_FAILED — the frontend fails it over
        doomed += self.scheduler.close()
        error = f"engine crashed: {reason}" if reason else "engine crashed"
        for req in doomed:
            req.finish(error=error, code=CODE_ENGINE_FAILED)

    def cancel(self, request_id: int):
        """Abort a queued or in-flight request, freeing its slot and
        pages.  Takes effect at the next dispatch boundary: the current
        fused block (if any) has already been emitted.  Returns "queued"
        when the request had never been admitted to a slot (the caller
        refunds its tenant token-bucket charge), "active" when it held a
        slot, False when unknown."""
        if self.scheduler.cancel(request_id):
            handle = self._swapped.pop(request_id, None)
            if handle is not None:       # parked in the host swap tier
                drop_handle(self.pool, self.host_pool, handle)
            if self.prefix_cache is not None:
                self.prefix_cache.unbind(request_id)
            return "queued"
        for slot, req in list(self.slot_req.items()):
            if req.request_id == request_id:
                del self.slot_req[slot]
                if self.prefix_cache is not None:
                    self.prefix_cache.unbind(request_id)
                self.pool.release(slot)
                self._release_device_slot(slot)
                return "active"
        return False

    def _release_device_slot(self, slot: int):
        """Zero the slot's persistent device state (done mask, sampling
        temperature, budget) so the next fused dispatch can't decode or
        sample it with stale values."""
        idx = jnp.asarray([slot], jnp.int32)
        (self.last_tok, self.pos, self.active, self.remaining,
         self.temps, self.spec_table, self.spec_prev) = self._clear_slots(
            self.last_tok, self.pos, self.active, self.remaining,
            self.temps, self.spec_table, self.spec_prev, idx)
        self.dispatches += 1

    @property
    def alive(self) -> bool:
        return not self._dead

    @property
    def n_active(self) -> int:
        return self.pool.n_active

    @property
    def load(self) -> float:
        """Active slots + queue pressure, for least-loaded routing."""
        return self.pool.n_active + self.scheduler.depth

    # ------------------------------------------------------------- #
    def step(self) -> int:
        """One engine iteration: admit one prefill bucket, then one fused
        K-step decode dispatch.  Returns number of decode tokens
        emitted."""
        if self._dead:
            raise EngineFailure("engine is dead")
        t0 = obs.clock()
        step = self.total_steps
        self._phase = obs.Phases(node=self.node, step=step)
        try:
            self._admit()
            emitted = self._decode_block() if self.slot_req else 0
        finally:
            self._phase.end()
        self.total_steps += 1
        obs.record(obs.ENGINE_STEP, t0, obs.clock(), node=self.node,
                   step=step)
        return emitted

    # ---- admissions: one bucketed batch prefill dispatch ---------- #
    def _decode_page_debt(self) -> int:
        """Pages the in-flight slots will need for their next decode
        block — reserved out of the admission budget so a fresh admit
        can't immediately starve running requests into preemption."""
        if not self._paged:
            return 0
        debt = 0
        for slot in self.slot_req:
            target = min(self.pool.lengths[slot] + self._growth,
                         self.ecfg.max_len)
            debt += max(self.pool.pages_for_tokens(target)
                        - len(self.pool.slot_pages[slot]), 0)
        return debt

    def _admit(self):
        self._phase.to(obs.ENGINE_SCHEDULE)
        budget = len(self.pool.free_pages) - self._decode_page_debt()
        if self.prefix_cache is not None:
            # LRU cache pages are reclaimable on demand: count them into
            # the admission budget so the cache never blocks admission
            budget += self.prefix_cache.evictable_device_pages()
        group = self.scheduler.next_prefill_bucket(
            len(self.pool.free_slots), self._bucket_of,
            free_pages=max(budget, 0))
        if not group:
            return
        # partition: swap-parked resumes restore with zero re-prefill;
        # prefix-cache hits prefill only their suffix; the rest take the
        # classic full bucketed prefill.  Admission may issue up to three
        # dispatches when mixed — all off the fused decode hot path.
        swaps = [r for r in group if r.request_id in self._swapped]
        fresh = [r for r in group if r.request_id not in self._swapped]
        if swaps:
            self._admit_swapped(swaps)
        hits, plain = [], fresh
        if self.prefix_cache is not None and fresh:
            hits, plain = [], []
            paged, _ = split_paged(self.cache)
            for req in fresh:
                toks = list(req.prompt) + list(req.output)
                entries, matched, new_paged = self.prefix_cache.match(
                    req.tenant, toks, len(toks) - 1, paged=paged)
                if new_paged is not None:       # host-tier promotion
                    self.cache.update(new_paged)
                    paged = new_paged
                    self.dispatches += 1
                if entries:
                    # pin immediately: a later reclaim (another row's
                    # shortfall or promotion) must not evict these
                    # before the suffix admission maps their pages
                    self.prefix_cache.bind(req.request_id, entries)
                    hits.append((req, entries, matched))
                else:
                    plain.append(req)
        if hits:
            self._admit_suffix(hits)
        if plain:
            self._admit_prefill(plain)

    def _reclaim_shortfall(self, want: int):
        """Feed the free list from LRU refcount-0 cache pages before an
        allocation would block (demoting to the host tier when one is
        attached)."""
        short = want - len(self.pool.free_pages)
        if short > 0 and self.prefix_cache is not None:
            demote = split_paged(self.cache)[0] if self.host_pool \
                else None
            self.prefix_cache.reclaim(short, demote)

    def _admit_prefill(self, group: List[Request]):
        self._phase.to(obs.ENGINE_SCHEDULE)
        admitted: List[Tuple[int, Request]] = []
        for req in group:
            eff = len(req.prompt) + len(req.output)
            need = eff + self._prefix_tokens
            self._reclaim_shortfall(
                self.pool.pages_per_slot if not self._paged
                else self.pool.pages_for_tokens(need))
            slot = self.pool.alloc(
                req.request_id, need,
                reserve_tokens=0 if self._paged else self.ecfg.max_len)
            if slot is None:                    # defensive; the admission
                self.scheduler.requeue(req)     # budget above bounds the
                continue                        # group — never drop it
            self._note_admission(req)
            admitted.append((slot, req))
        if not admitted:
            return
        ecfg = self.ecfg
        bucket = self._bucket_of(max(len(r.prompt) + len(r.output)
                                     for _, r in admitted))
        s_tot = bucket + self._prefix_tokens
        n_row_pages = self.pool.pages_for_tokens(s_tot)
        pad_n = _next_pow2(len(admitted))
        toks = np.zeros((pad_n, bucket), np.int32)
        lengths = np.ones((pad_n,), np.int32)
        slots = np.full((pad_n,), ecfg.n_slots, np.int32)  # OOB => drop
        row_pages = np.full((pad_n, n_row_pages), self.pool.n_pages,
                            np.int32)                      # OOB => drop
        r_temps = np.zeros((pad_n,), np.float32)
        r_topk = np.zeros((pad_n,), np.int32)
        r_topp = np.ones((pad_n,), np.float32)
        r_eos = np.full((pad_n,), -1, np.int32)
        r_budget = np.ones((pad_n,), np.int32)
        r_prev = np.full((pad_n,), -1, np.int32)
        for i, (slot, req) in enumerate(admitted):
            prompt = list(req.prompt) + list(req.output)   # resume ctx
            pl = len(prompt)
            toks[i, :pl] = prompt
            lengths[i] = pl
            slots[i] = slot
            row_pages[i] = self.pool.row_pages(slot, n_row_pages)
            s = req.sampling
            r_temps[i] = s.temperature
            r_topk[i] = s.top_k if s.top_k > 0 else ecfg.top_k
            r_topp[i] = s.top_p if s.top_p < 1.0 else ecfg.top_p
            r_eos[i] = s.eos_id
            r_budget[i] = s.max_tokens - len(req.output)
            r_prev[i] = prompt[-1]      # precedes the sampled first token
        extra = self._extra_inputs(pad_n)
        self._phase.to(obs.PREFILL_DISPATCH)
        (self.cache, self.last_tok, self.pos, self.active, self.remaining,
         self.temps, self.top_ks, self.top_ps, self.eos_ids, self._key,
         self.spec_table, self.spec_prev,
         first, done0) = self._prefill_admit(
            self.params, self.cache, self.last_tok, self.pos, self.active,
            self.remaining, self.temps, self.top_ks, self.top_ps,
            self.eos_ids, self._key, self.spec_table, self.spec_prev,
            toks, lengths, slots, row_pages,
            r_temps, r_topk, r_topp, r_eos, r_budget, r_prev, extra)
        self.dispatches += 1
        self.prefill_dispatch_tokens += pad_n * bucket
        self._phase.to(obs.PREFILL_SYNC)
        first_h, done_h = jax.device_get((first, done0))
        self.host_syncs += 1
        self._post_admit(admitted, first_h, done_h)

    def _note_admission(self, req: Request):
        """A request takes a slot: at its first admission, record how
        long it waited (a preempted request's resume records nothing)."""
        req.state = RequestState.PREFILLING
        if req.admitted_at is None:
            req.admitted_at = obs.clock()
            obs.record(obs.QUEUE_WAIT, req.created_at, req.admitted_at,
                       node=self.node, request=req.request_id)

    def _post_admit(self, admitted: List[Tuple[int, Request]],
                    first_h, done_h):
        """Shared tail of both admission dispatches: emit each row's
        first sampled token, then park it in its slot (or finish it)."""
        self._phase.to(obs.ENGINE_EMIT)
        for i, (slot, req) in enumerate(admitted):
            req.emit(int(first_h[i]))
            req.state = RequestState.DECODING
            self.total_tokens += 1
            if done_h[i]:
                req.finish()
                self._finish_slot(slot, req)
            else:
                self.slot_req[slot] = req

    def _finish_slot(self, slot: int, req: Request):
        """Free a finishing slot — donating its page-aligned prefix
        blocks to the prefix cache first (the cache `retain`s them, so
        the release below leaves the cache holding the last reference)."""
        if self.prefix_cache is not None:
            if not req.error and not req.cancelled:
                n = self.pool.lengths[slot]
                toks = (list(req.prompt) + list(req.output))[:n]
                self.prefix_cache.insert(req.tenant, toks, n,
                                         self.pool.slot_pages[slot])
            self.prefix_cache.unbind(req.request_id)
        self.pool.release(slot)

    # ---- prefix-cache hits: suffix-only bucketed prefill ---------- #
    def _admit_suffix(self, hits):
        self._phase.to(obs.ENGINE_SCHEDULE)
        ecfg = self.ecfg
        pps = self.pool.pages_per_slot
        admitted: List[Tuple[int, Request]] = []
        matched_of: Dict[int, int] = {}
        for req, entries, matched in hits:
            eff = len(req.prompt) + len(req.output)
            shared = [e.page for e in entries]
            self._reclaim_shortfall(
                self.pool.pages_for_tokens(eff) - len(shared))
            slot = self.pool.alloc(req.request_id, eff,
                                   shared_pages=shared)
            if slot is None:            # entries were pinned at match
                self.prefix_cache.unbind(req.request_id)
                self.scheduler.requeue(req)
                continue
            self._note_admission(req)
            admitted.append((slot, req))
            matched_of[slot] = matched
        if not admitted:
            return
        bucket = self._bucket_of(max(
            (len(r.prompt) + len(r.output)) - matched_of[s]
            for s, r in admitted))
        pad_n = _next_pow2(len(admitted))
        toks = np.zeros((pad_n, bucket), np.int32)
        offsets = np.zeros((pad_n,), np.int32)
        lengths = np.ones((pad_n,), np.int32)
        slots = np.full((pad_n,), ecfg.n_slots, np.int32)  # OOB => drop
        read_tables = np.full((pad_n, pps), self.pool.n_pages, np.int32)
        write_tables = np.full((pad_n, pps), self.pool.n_pages, np.int32)
        r_temps = np.zeros((pad_n,), np.float32)
        r_topk = np.zeros((pad_n,), np.int32)
        r_topp = np.ones((pad_n,), np.float32)
        r_eos = np.full((pad_n,), -1, np.int32)
        r_budget = np.ones((pad_n,), np.int32)
        r_prev = np.full((pad_n,), -1, np.int32)
        for i, (slot, req) in enumerate(admitted):
            prompt = list(req.prompt) + list(req.output)
            r_prev[i] = prompt[-1]
            matched = matched_of[slot]
            suffix = prompt[matched:]
            toks[i, :len(suffix)] = suffix
            offsets[i] = matched
            lengths[i] = len(suffix)
            slots[i] = slot
            read_tables[i] = self.pool.row_pages(slot, pps)
            write_tables[i] = read_tables[i]
            # shared prefix blocks are read-only: writes there drop
            write_tables[i, :matched // self.pool.page_size] = \
                self.pool.n_pages
            s = req.sampling
            r_temps[i] = s.temperature
            r_topk[i] = s.top_k if s.top_k > 0 else ecfg.top_k
            r_topp[i] = s.top_p if s.top_p < 1.0 else ecfg.top_p
            r_eos[i] = s.eos_id
            r_budget[i] = s.max_tokens - len(req.output)
        self._phase.to(obs.PREFILL_DISPATCH)
        (self.cache, self.last_tok, self.pos, self.active, self.remaining,
         self.temps, self.top_ks, self.top_ps, self.eos_ids, self._key,
         self.spec_table, self.spec_prev,
         first, done0) = self._suffix_admit(
            self.params, self.cache, self.last_tok, self.pos, self.active,
            self.remaining, self.temps, self.top_ks, self.top_ps,
            self.eos_ids, self._key, self.spec_table, self.spec_prev,
            toks, offsets, lengths, slots,
            read_tables, write_tables, r_temps, r_topk, r_topp, r_eos,
            r_budget, r_prev)
        self.dispatches += 1
        if self._paged:
            # admission gathers/scatters one logical view per padded row
            self.logical_bytes_moved += \
                2 * (self._view_bytes // self.ecfg.n_slots) * pad_n
        self.prefill_dispatch_tokens += pad_n * bucket
        self.suffix_prefills += len(admitted)
        self._phase.to(obs.PREFILL_SYNC)
        first_h, done_h = jax.device_get((first, done0))
        self.host_syncs += 1
        self._post_admit(admitted, first_h, done_h)

    # ---- swap-parked resumes: zero re-prefill restore ------------- #
    def _admit_swapped(self, swaps: List[Request]):
        self._phase.to(obs.ENGINE_SCHEDULE)
        paged, _ = split_paged(self.cache)
        restored: List[Tuple[int, Request]] = []
        for req in swaps:
            handle = self._swapped[req.request_id]
            self._reclaim_shortfall(len(handle.host))
            res = swap_in_slot(self.pool, self.host_pool, paged, handle)
            if res is None:
                # slots/pages short right now: fall back to the classic
                # recompute resume so progress never livelocks on swap
                del self._swapped[req.request_id]
                drop_handle(self.pool, self.host_pool, handle)
                self.scheduler.requeue(req)
                continue
            slot, new_paged = res
            if new_paged is not paged:
                self.cache.update(new_paged)
                paged = new_paged
                self.dispatches += 1        # the swap-in scatter
            del self._swapped[req.request_id]
            self.swap_ins += 1
            restored.append((slot, req))
        if not restored:
            return
        ecfg = self.ecfg
        pad_n = _next_pow2(len(restored))
        slots = np.full((pad_n,), ecfg.n_slots, np.int32)
        r_last = np.zeros((pad_n,), np.int32)
        r_pos = np.zeros((pad_n,), np.int32)
        r_budget = np.zeros((pad_n,), np.int32)
        r_temps = np.zeros((pad_n,), np.float32)
        r_topk = np.zeros((pad_n,), np.int32)
        r_topp = np.ones((pad_n,), np.float32)
        r_eos = np.full((pad_n,), -1, np.int32)
        r_prev = np.full((pad_n,), -1, np.int32)
        for i, (slot, req) in enumerate(restored):
            slots[i] = slot
            r_last[i] = req.output[-1]
            r_prev[i] = req.output[-2] if len(req.output) >= 2 \
                else list(req.prompt)[-1]
            r_pos[i] = self.pool.lengths[slot]
            r_budget[i] = req.sampling.max_tokens - len(req.output)
            s = req.sampling
            r_temps[i] = s.temperature
            r_topk[i] = s.top_k if s.top_k > 0 else ecfg.top_k
            r_topp[i] = s.top_p if s.top_p < 1.0 else ecfg.top_p
            r_eos[i] = s.eos_id
            req.state = RequestState.DECODING
            self.slot_req[slot] = req
        self._phase.to(obs.PREFILL_DISPATCH)
        (self.last_tok, self.pos, self.active, self.remaining, self.temps,
         self.top_ks, self.top_ps, self.eos_ids, self.spec_table,
         self.spec_prev) = self._restore_slots(
            self.last_tok, self.pos, self.active, self.remaining,
            self.temps, self.top_ks, self.top_ps, self.eos_ids,
            self.spec_table, self.spec_prev,
            slots, r_last, r_pos, r_budget, r_temps, r_topk, r_topp,
            r_eos, r_prev)
        self.dispatches += 1

    def _decode_mode(self) -> str:
        """Pick the cheapest compiled decode variant the current batch
        permits — the host knows every slot's sampling params, so sorts
        and PRNG stay out of the program unless actually needed."""
        sampling = [r.sampling for r in self.slot_req.values()
                    if r.sampling.temperature > 0]
        if not sampling:
            return "greedy"
        ecfg = self.ecfg
        if any(s.top_k > 0 or s.top_p < 1.0 or ecfg.top_k > 0
               or ecfg.top_p < 1.0 for s in sampling):
            return "full"
        return "temp"

    # ---- preemption: page exhaustion at a decode-block boundary --- #
    def _pick_victim(self) -> Optional[int]:
        """Preemption victim: the slot whose tenant holds the lowest DWRR
        deficit (most recently over-served), breaking ties toward the
        request with the least progress (cheapest resume)."""
        if not self.slot_req:
            return None
        return min(self.slot_req.items(),
                   key=lambda kv: (self.scheduler.deficit(kv[1].tenant),
                                   len(kv[1].output), -kv[0]))[0]

    def _preempt(self, slot: int):
        """Evict `slot`: park its private KV pages in the host swap tier
        when one is attached (O(pages) moved, zero re-prefill on resume),
        else refund the pages and fall back to the classic recompute
        resume (re-prefill prompt + generated-so-far).  Either way the
        request keeps its emitted tokens and re-enters the front of its
        tenant queue with its remaining budget."""
        req = self.slot_req.pop(slot)
        swapped = False
        if self.host_pool is not None:
            paged, _ = split_paged(self.cache)
            handle = swap_out_slot(self.pool, self.host_pool, paged, slot)
            if handle is not None:
                self._swapped[req.request_id] = handle
                self.swap_outs += 1
                self.dispatches += 1    # the page-gather dispatch
                self.host_syncs += 1    # one device_get moves the blocks
                swapped = True
        if not swapped:
            if self.prefix_cache is not None:
                # recompute resume re-matches and re-binds at admission
                self.prefix_cache.unbind(req.request_id)
            self.pool.release(slot)
        self.pool.preemptions += 1
        self.preemptions += 1
        self._release_device_slot(slot)
        self.scheduler.requeue(req)

    def _ensure_decode_pages(self):
        """Grow every active slot's page table to cover the next fused
        block.  On exhaustion, preempt lowest-deficit slots until the
        growth fits (a sole survivor always fits: the pool holds at least
        one full sequence's pages)."""
        if not self._paged:
            return
        k = self._growth
        for slot in sorted(self.slot_req):
            if slot not in self.slot_req:      # evicted by a prior pass
                continue
            target = min(self.pool.lengths[slot] + k, self.ecfg.max_len)
            while slot in self.slot_req \
                    and not self.pool.grow(slot, target):
                victim = self._pick_victim()
                if victim is None:
                    break
                self._preempt(victim)

    # ---- decode: one fused K-step dispatch, one host sync --------- #
    def _decode_block(self) -> int:
        self._phase.to(obs.DECODE_PAGES)
        self._ensure_decode_pages()
        if not self.slot_req:
            return 0
        mode = self._decode_mode()
        spec = self._spec_ok and mode == "greedy"
        page_table = self.pool.page_table()
        write_table = self.pool.write_table()
        self._phase.to(obs.DECODE_DISPATCH)
        if spec:
            # one verify dispatch proposes + checks D drafts and emits
            # up to D+1 tokens — same single host sync as the fused path
            (self.cache, self.last_tok, self.pos, self.active,
             self.remaining, self.spec_table, self.spec_prev,
             toks, emits, dones) = self._spec_decode(
                self.params, self.cache, self.last_tok, self.pos,
                self.active, self.remaining, self.eos_ids,
                self.spec_table, self.spec_prev, page_table, write_table)
            self.spec_dispatches += 1
            self.logical_bytes_moved += \
                (self.ecfg.spec_draft + 1) * self._write_token_bytes
        else:
            fn = self._fused_decode[mode]
            (self.cache, self.last_tok, self.pos, self.active,
             self.remaining, self._key, toks, emits, dones) = fn(
                self.params, self.cache, self.last_tok, self.pos,
                self.active, self.remaining, self.temps, self.top_ks,
                self.top_ps, self.eos_ids, self._key, page_table,
                write_table)
            if self._paged_attn:
                # page-table-direct: only the block's new KV is written
                self.logical_bytes_moved += \
                    self.ecfg.decode_block * self._write_token_bytes
            elif self._paged:
                # gather + scatter move every slot's full logical view
                self.logical_bytes_moved += 2 * self._view_bytes
        self.dispatches += 1
        self._phase.to(obs.DECODE_SYNC)
        toks_h, emit_h, done_h = jax.device_get((toks, emits, dones))
        self.host_syncs += 1
        self._phase.to(obs.ENGINE_EMIT)
        emitted = 0
        for slot, req in list(self.slot_req.items()):
            col = emit_h[:, slot]
            if not col.any():
                continue
            block = toks_h[:, slot][col].tolist()
            req.emit_many(block)
            self.pool.advance(slot, len(block))
            emitted += len(block)
            self.total_tokens += len(block)
            if spec:
                self.spec_emitted += len(block)
                # tokens beyond the first came from accepted drafts
                self.spec_slot_accepted[slot] += max(len(block) - 1, 0)
            if done_h[:, slot].any():
                req.finish()
                del self.slot_req[slot]
                self._finish_slot(slot, req)
        return emitted

    def run_until_done(self, max_steps: int = 10_000) -> int:
        steps = 0
        while (self.slot_req or self.scheduler.depth) and \
                steps < max_steps:
            self.step()
            steps += 1
        return steps

    # ---- hierarchical KV memory: admin / autoscaler surface ------- #
    def flush_prefix_cache(self) -> Dict[str, int]:
        """Drop every unpinned prefix-cache entry (both tiers) — the
        `/v1/admin/cache/flush` verb."""
        if self.prefix_cache is None:
            return {"flushed": 0, "remaining": 0}
        return self.prefix_cache.flush()

    def page_pressure(self) -> float:
        """Fraction of the device page budget committed to *live* work.
        Cache pages the engine could reclaim on demand are netted out,
        so a warm-but-evictable prefix cache never reads as memory
        pressure to the autoscaler."""
        if not self._paged or self.pool.n_pages == 0:
            return 0.0
        in_use = self.pool.n_pages - len(self.pool.free_pages)
        if self.prefix_cache is not None:
            in_use -= self.prefix_cache.evictable_device_pages()
        return max(in_use, 0) / self.pool.n_pages

    # ------------------------------------------------------------- #
    def memory_report(self) -> Dict[str, int]:
        return {
            "param_bytes": q_lib.tree_bytes(self.params),
            "cache_bytes": cache_bytes(self.cache),
        }

    def perf_stats(self) -> Dict[str, Any]:
        """Dispatch/sync discipline counters (the paper's 'no CPU
        fallback' claim, made measurable) plus the paged-pool VRAM
        metrics."""
        t = max(self.total_tokens, 1)
        stats = {
            "tokens": self.total_tokens,
            "steps": self.total_steps,
            "dispatches": self.dispatches,
            "host_syncs": self.host_syncs,
            "dispatches_per_token": self.dispatches / t,
            "host_syncs_per_token": self.host_syncs / t,
            "prefill_traces": self.prefill_traces,
            "decode_traces": self.decode_traces,
            "decode_block": self.ecfg.decode_block,
            "paged": self._paged,
            "paged_attention": self._paged_attn,
            "speculative": self._spec_ok,
            # logical KV traffic: gather/scatter views vs in-place writes
            "logical_bytes_moved": self.logical_bytes_moved,
            "logical_bytes_moved_per_token": self.logical_bytes_moved / t,
            # speculative decoding acceptance
            "spec_traces": self.spec_traces,
            "spec_dispatches": self.spec_dispatches,
            "spec_emitted": self.spec_emitted,
            "spec_accepted_per_dispatch": (
                self.spec_emitted / self.spec_dispatches
                if self.spec_dispatches else 0.0),
            "spec_slot_accepted": self.spec_slot_accepted.tolist(),
            "preemptions": self.preemptions,
            "queue_enqueued": self.scheduler.enqueued_total,
            "queue_dequeued": self.scheduler.dequeued_total,
            "queue_requeued": self.scheduler.requeued_total,
            "queue_rejected": self.scheduler.rejected,
            "pending_pages": self.scheduler.pending_pages,
            # hierarchical KV memory (kv_hierarchy)
            "suffix_traces": self.suffix_traces,
            "suffix_prefills": self.suffix_prefills,
            "prefill_dispatch_tokens": self.prefill_dispatch_tokens,
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "swapped_requests": len(self._swapped),
            "cache_hit_rate": (self.prefix_cache.hit_rate()
                               if self.prefix_cache is not None else 0.0),
            "host_pages": (self.host_pool.n_pages
                           if self.host_pool is not None else 0),
            "host_pages_in_use": (self.host_pool.in_use
                                  if self.host_pool is not None else 0),
        }
        if self.prefix_cache is not None:
            stats["prefix_cache"] = self.prefix_cache.stats()
        stats.update(self.pool.page_stats())
        return stats
