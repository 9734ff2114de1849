#!/usr/bin/env python3
"""Smoke test on the chip: serve olmo-1b at its published widths through
the Gateway and over HTTP, and check what comes out.

    python chip_smoke.py               # one chip: the served path
    python chip_smoke.py --chips 4     # four one-chip replicas behind the
                                       # frontend, one node killed while
                                       # 16 greedy streams run

Everything runs in this one process, which holds the chip(s) and starts
no child process.  The weights are olmo-1b's random init from `--seed`,
so nothing is downloaded.  The compile cache is JAX_COMPILATION_CACHE_DIR
where that is set, else `.jax_cache/` in the checkout.

Without a TPU, or when any check fails, the script exits non-zero and
does not print its last line.  A passing run ends with exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Earlier lines give the configuration, compile seconds and cache hits per
warmed program, device memory, the engines' counters and agreement
figures.  They give no serving time or rate: this is not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.api import Gateway, StreamEventType  # noqa: E402
from repro.api.http import GatewayHTTPServer, HTTPClient, HTTPConfig  # noqa: E402
from repro.cluster import BackendNode, Fleet  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.core import (AutoscaleConfig, ControllerConfig,  # noqa: E402
                        ModelCatalog, ModelDemand, ReplicaInfo, ReplicaKey,
                        SDAIController)
from repro.core.events import (ENGINE_FAILED, PUMP_ERROR,  # noqa: E402
                               REQUEST_MIGRATED, TICK_ERROR, WATCHDOG_FIRED)
from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.kernels import paged_attention as pa  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serving import Request, SamplingParams  # noqa: E402

MODEL = "olmo-1b"
# bf16 kernel outputs against the float32-accumulating jnp reference:
# bf16 keeps 8 mantissa bits (relative step 2**-8 ~ 0.4%), and the
# outputs are softmax-weighted means of unit-scale values
KERNEL_ATOL = 1e-2
KERNEL_RTOL = 1e-2
# a full forward over prompt + served tokens (teacher-forced) must rank
# every served token among its top few: bf16 prefill/decode and the full
# forward round differently, which can swap near-tied logits, while a
# wrong token from a broken path ranks anywhere in the 50304-token vocab
MAX_FORWARD_RANK = 4
# events that mean a phase failed, whatever the responses say
FAIL_EVENTS = ("deploy_failed", TICK_ERROR, WATCHDOG_FIRED, ENGINE_FAILED,
               PUMP_ERROR)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Engine and traffic sizes of one smoke run."""
    n_slots: int = 8
    max_len: int = 1024
    page_size: int = 16
    max_tokens: int = 32
    # three prefill buckets on the one-chip path: 32, 256 and 1024
    prompt_lens: Tuple[int, ...] = (17, 200, 700)
    # four-chip streams: prompt and migrated context (prompt + up to
    # stream_tokens - 1 emitted) share one prefill bucket (128)
    n_streams: int = 16
    stream_prompt_len: int = 70
    stream_tokens: int = 58
    warm_timeout_s: float = 900.0
    serve_timeout_s: float = 300.0


class SmokeFailure(RuntimeError):
    pass


def say(*parts) -> None:
    print(*parts, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(n_chips: int) -> Dict:
    info = device_info()
    require(info["platform"] == "tpu",
            f"JAX finds no TPU (platform {info['platform']!r})")
    require(info["count"] >= n_chips,
            f"{n_chips} chips asked for, {info['count']} found")
    return dict(info, count=n_chips)


class CompileLog:
    """Persistent-cache hits and misses and backend compile seconds, read
    from JAX's monitoring events."""
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.hits = self.misses = 0
        self.programs: List[Tuple[str, float]] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        with self._lock:
            if event == self.HIT:
                self.hits += 1
            elif event == self.MISS:
                self.misses += 1

    def _on_duration(self, event: str, duration: float, **kw):
        if event == self.COMPILE:
            with self._lock:
                self.programs.append((kw.get("fun_name", "?"), duration))

    def mark(self) -> Tuple[int, int, int]:
        with self._lock:
            return self.hits, self.misses, len(self.programs)

    def since(self, mark: Tuple[int, int, int]) -> Dict:
        with self._lock:
            progs = self.programs[mark[2]:]
            return {"programs": [n for n, _ in progs],
                    "compile_s": sum(d for _, d in progs),
                    "cache_hits": self.hits - mark[0],
                    "cache_misses": self.misses - mark[1]}

    def close(self):
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


# ------------------------------------------------------------------ #
# shared phases
# ------------------------------------------------------------------ #
def seeded_params(cfg: ArchConfig, seed: int):
    """The model's own random init, made on the first device."""
    return jax.jit(build(cfg).init)(jax.random.PRNGKey(seed))


def prompt_tokens(cfg: ArchConfig, n: int, seed: int) -> List[int]:
    rng = np.random.default_rng([seed, n])
    return rng.integers(1, cfg.vocab, size=n).tolist()


def controller(fleet: Fleet, cfg: ArchConfig, *,
               fill_vram: bool) -> SDAIController:
    """A controller that builds real engines for `cfg` (the threshold
    is set to its size) and never autoscales mid-run."""
    catalog = ModelCatalog()
    catalog.register(cfg)
    ctrl = SDAIController(fleet, catalog, ControllerConfig(
        real_param_threshold=cfg.num_params(), fill_vram=fill_vram,
        autoscale=AutoscaleConfig(enabled=False)))
    ctrl.discover()
    return ctrl


def instances(ctrl: SDAIController):
    """(node, instance) of every registered replica."""
    out = []
    for model in ctrl.replicas.models():
        for info in ctrl.replicas.for_model(model):
            node = ctrl.fleet.nodes[info.key.node_id]
            out.append((node, node.instances.get(info.key.instance_id)))
    return out


def check_real_engines(ctrl: SDAIController) -> None:
    """Every replica runs a real engine whose arrays are all committed to
    its node's device."""
    for node, inst in instances(ctrl):
        require(inst is not None and inst.engine is not None,
                f"{node.node_id}: replica has no real engine")
        eng = inst.engine
        leaves = jax.tree.leaves((eng.params, eng.cache, eng.pos,
                                  eng.last_tok, eng.active, eng.remaining,
                                  eng.spec_table, eng._key))
        where = {d for x in leaves for d in x.devices()}
        require(where == {node.device},
                f"{node.node_id}/{inst.model_name}: arrays on {where}, "
                f"node device {node.device}")


def check_bus(ctrl: SDAIController) -> None:
    bad = [e for e in ctrl.bus.events if e.kind in FAIL_EVENTS]
    require(not bad, "failure events on the bus: " + "; ".join(
        f"{e.kind} {e.data}" for e in bad))


def program_traces(ctrl: SDAIController) -> int:
    """Compiles of the engines' step programs so far."""
    n = 0
    for _, inst in instances(ctrl):
        s = inst.engine.perf_stats()
        n += (s["prefill_traces"] + s["decode_traces"] + s["spec_traces"]
              + s["suffix_traces"])
    return n


def check_tokens(cfg: ArchConfig, name: str, tokens: Sequence[int],
                 want: int) -> None:
    require(len(tokens) == want,
            f"{name}: {len(tokens)} tokens, {want} asked for")
    bad = [t for t in tokens if not 0 <= t < cfg.vocab]
    require(not bad, f"{name}: tokens outside the vocab: {bad[:4]}")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


# ------------------------------------------------------------------ #
# one chip: two instances behind the Gateway and HTTP
# ------------------------------------------------------------------ #
def deploy_one_chip(cfg: ArchConfig, params, device,
                    sizes: Sizes) -> Tuple[SDAIController, List[str]]:
    """One v5e-1 node.  The controller places one replica of `cfg`; the
    node then deploys a second instance of the same weights with paged
    attention and speculative decoding, which `ModelDemand` does not
    carry, registered behind the frontend under its own name."""
    node = BackendNode("n0", "v5e-1", param_store=lambda _cfg: params,
                       device=device)
    ctrl = controller(Fleet([node]), cfg, fill_vram=False)
    plan = ctrl.deploy([ModelDemand(
        cfg, min_replicas=1, max_replicas=1, n_slots=sizes.n_slots,
        max_len=sizes.max_len, page_size=sizes.page_size,
        allow_quant=False)])
    require(not plan.unplaced, f"unplaced: {plan.unplaced}")
    paged_cfg = dataclasses.replace(cfg, name=cfg.name + "-paged-spec")
    inst = node.deploy(paged_cfg, n_slots=sizes.n_slots,
                       max_len=sizes.max_len, page_size=sizes.page_size,
                       paged_attention=True, speculative=True)
    ctrl.catalog.register(paged_cfg)
    inst.engine.scheduler.weight_of = ctrl.frontend.tenants.weight
    ctrl.replicas.add(ReplicaInfo(
        ReplicaKey(node.node_id, inst.instance_id), paged_cfg.name, "",
        sizes.n_slots, sizes.max_len, inst.bytes))
    return ctrl, [cfg.name, paged_cfg.name]


def warm(gw: Gateway, models: Sequence[str], prompts: Dict[int, List[int]],
         sizes: Sizes, clog: CompileLog) -> Dict[Tuple[str, int], Tuple]:
    """Hand-pumped (runtime not started): one greedy request per model
    and prompt length compiles every program the served phase runs.
    Returns the warm-up answers."""
    out = {}
    for model in models:
        for n, prompt in prompts.items():
            mark = clog.mark()
            resp = gw.generate(model, prompt,
                               SamplingParams(max_tokens=sizes.max_tokens),
                               timeout_s=sizes.warm_timeout_s)
            got = clog.since(mark)
            require(resp.ok, f"warm-up {model} prompt {n}: {resp.error}")
            say(f"warm {model} prompt {n}: compiled "
                f"{got['programs'] or 'nothing'}, backend compile "
                f"{got['compile_s']:.3f} s, persistent cache "
                f"{got['cache_hits']} hits / {got['cache_misses']} misses")
            out[(model, n)] = resp.tokens
    return out


def serve(gw: Gateway, models: Sequence[str],
          prompts: Dict[int, List[int]],
          sizes: Sizes) -> Dict[Tuple[str, int], Tuple[str, List[int]]]:
    """With the runtime and an HTTP server up, answer one request per
    model and prompt length, all at once: the shortest prompt streams
    over HTTP, the middle one goes through `Gateway.generate`, the rest
    are plain HTTP completions.  Returns (surface, tokens) per request."""
    server = GatewayHTTPServer(gw, HTTPConfig(
        host="127.0.0.1", port=0, default_timeout_s=sizes.serve_timeout_s))
    gw.start()
    server.start()
    lens = sorted(prompts)
    try:
        def http(model, prompt, stream):
            with HTTPClient(server.url(),
                            timeout_s=sizes.serve_timeout_s + 30) as cli:
                if not stream:
                    body = cli.complete(model, prompt,
                                        max_tokens=sizes.max_tokens,
                                        timeout_s=sizes.serve_timeout_s)
                    require("error" not in body, f"{model}: {body}")
                    return list(body["choices"][0]["token_ids"])
                toks = []
                for chunk in cli.complete(model, prompt,
                                          max_tokens=sizes.max_tokens,
                                          stream=True,
                                          timeout_s=sizes.serve_timeout_s):
                    require("error" not in chunk, f"{model}: {chunk}")
                    choice = chunk["choices"][0]
                    if "token" in choice:
                        toks.append(choice["token"])
                return toks

        def generate(model, prompt):
            resp = gw.generate(model, prompt,
                               SamplingParams(max_tokens=sizes.max_tokens),
                               timeout_s=sizes.serve_timeout_s)
            require(resp.ok, f"{model}: {resp.error}")
            return list(resp.tokens)

        jobs = {}
        with ThreadPoolExecutor(max_workers=2 * len(lens) * len(models)) \
                as pool:
            for model in models:
                for i, n in enumerate(lens):
                    if i == 0:
                        surface = "http-stream"
                        fut = pool.submit(http, model, prompts[n], True)
                    elif i == 1:
                        surface = "gateway"
                        fut = pool.submit(generate, model, prompts[n])
                    else:
                        surface = "http"
                        fut = pool.submit(http, model, prompts[n], False)
                    jobs[(model, n)] = (surface, fut)
            served = {k: (s, f.result()) for k, (s, f) in jobs.items()}
    finally:
        server.stop()
        stopped = gw.stop(timeout_s=60)
    require(stopped, "runtime threads did not stop")
    return served


def kernel_in_compiled_decode(ctrl: SDAIController, model: str) -> Dict:
    """Whether the Pallas paged kernel is in the compiled programs of
    `model`'s engine: the speculative verify it serves greedy batches
    with, and its plain paged decode."""
    inst = next(i for _, i in instances(ctrl) if i.model_name == model)
    eng = inst.engine
    table, wtable = eng.pool.page_table(), eng.pool.write_table()
    verify = eng._spec_decode.lower(
        eng.params, eng.cache, eng.last_tok, eng.pos, eng.active,
        eng.remaining, eng.eos_ids, eng.spec_table, eng.spec_prev, table,
        wtable).compile().as_text()
    decode = eng._fused_decode["greedy"].lower(
        eng.params, eng.cache, eng.last_tok, eng.pos, eng.active,
        eng.remaining, eng.temps, eng.top_ks, eng.top_ps, eng.eos_ids,
        eng._key, table, wtable).compile().as_text()
    return {"spec_verify": "tpu_custom_call" in verify,
            "paged_decode": "tpu_custom_call" in decode}


def kernel_vs_reference(cfg: ArchConfig, sizes: Sizes, seed: int, *,
                        spec_draft: int = 4) -> Dict[str, float]:
    """The Pallas paged kernels against their jnp references at the
    served shapes (bf16 pools, scattered pages, ragged positions).
    Returns the largest absolute error of each; raises past tolerance.
    Off the TPU the kernels run in interpret mode, as the served path's
    would."""
    interpret = kernel_ops.interpret_mode()
    b, nkv, hd = sizes.n_slots, cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // nkv
    ps = sizes.page_size
    pps = sizes.max_len // ps
    n_pages = b * pps
    rng = np.random.default_rng(seed)
    pos = rng.integers(spec_draft, sizes.max_len - spec_draft - 1, size=b)
    table = np.full((b, pps), n_pages, np.int32)
    perm = rng.permutation(n_pages)
    for i in range(b):
        used = (int(pos[i]) + spec_draft) // ps + 1
        table[i, :used] = perm[i * pps:i * pps + used]
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf = jnp.bfloat16
    kp = jax.random.normal(keys[0], (n_pages, ps, nkv, hd), bf)
    vp = jax.random.normal(keys[1], (n_pages, ps, nkv, hd), bf)
    q = jax.random.normal(keys[2], (b, nkv, g, hd), bf)
    qs = jax.random.normal(keys[3], (b, spec_draft + 1, nkv * g, hd), bf)
    table_d, pos_d = jnp.asarray(table), jnp.asarray(pos, jnp.int32)
    q_pos = pos_d[:, None] + jnp.arange(spec_draft + 1, dtype=jnp.int32)
    pairs = {
        "paged_decode": (
            jax.jit(lambda *a: pa.paged_decode_attention(
                *a, interpret=interpret))(q, kp, vp, table_d, pos_d),
            jax.jit(pa.paged_decode_attention_ref)(
                q, kp, vp, table_d, pos_d)),
        "paged_suffix": (
            jax.jit(lambda *a: pa.paged_suffix_attention(
                *a, interpret=interpret))(qs, kp, vp, table_d, pos_d),
            jax.jit(pa.paged_suffix_attention_ref)(
                qs, kp, vp, table_d, q_pos)),
    }
    errs = {}
    for name, (got, want) in pairs.items():
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        require(np.isfinite(got).all(), f"{name}: non-finite output")
        errs[name] = float(np.max(np.abs(got - want)))
        require(np.allclose(got, want, atol=KERNEL_ATOL, rtol=KERNEL_RTOL),
                f"{name}: max |kernel - reference| {errs[name]} beyond "
                f"atol {KERNEL_ATOL} rtol {KERNEL_RTOL}")
    return errs


def forward_ranks(cfg: ArchConfig, params,
                  answers: Sequence[Tuple[List[int], List[int]]],
                  max_len: int) -> Tuple[float, int]:
    """One full forward over prompt + output (teacher-forced), all rows
    padded to max_len in one program (causal: padding after a row
    changes none of its logits).  Returns the share of served tokens
    that are the forward's argmax, and the worst rank of a served token
    among the forward's logits (0 = the argmax)."""
    toks = np.zeros((len(answers), max_len), np.int32)
    for i, (prompt, out) in enumerate(answers):
        seq = list(prompt) + list(out)
        toks[i, :len(seq)] = seq
    model = build(cfg)

    def ranks(p, t):
        logits = model.forward(p, t)[0][:, :-1]
        chosen = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)
        return jnp.sum(logits > chosen, axis=-1)

    rank = np.asarray(jax.jit(ranks)(params, jnp.asarray(toks)))
    served = np.concatenate([
        rank[i, len(prompt) - 1:len(prompt) - 1 + len(out)]
        for i, (prompt, out) in enumerate(answers)])
    return float(np.mean(served == 0)), int(served.max())


def run_one_chip(cfg: ArchConfig, sizes: Sizes, seed: int,
                 clog: CompileLog) -> Dict:
    """The one-chip path.  Returns what it measured; raises
    SmokeFailure on the first failed check."""
    dev = jax.devices()[0]
    params = seeded_params(cfg, seed)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    say(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads ({cfg.n_kv_heads} kv), d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, {cfg.dtype}; {n_params} parameters")
    ctrl, models = deploy_one_chip(cfg, params, dev, sizes)
    check_real_engines(ctrl)
    for e in ctrl.bus.of_kind("instance_deployed"):
        say(f"deployed {e.data}")
    say(f"real engines: {[i.model_name for _, i in instances(ctrl)]}")
    gw = Gateway(ctrl)
    prompts = {n: prompt_tokens(cfg, n, seed) for n in sizes.prompt_lens}
    warm(gw, models, prompts, sizes, clog)
    traces = program_traces(ctrl)
    mark = clog.mark()
    served = serve(gw, models, prompts, sizes)
    for (model, n), (surface, toks) in sorted(served.items()):
        check_tokens(cfg, f"{model} prompt {n} via {surface}", toks,
                     sizes.max_tokens)
        say(f"served {model} prompt {n} via {surface}: "
            f"{len(toks)} tokens, first {toks[:6]}")
    check_bus(ctrl)
    check_real_engines(ctrl)
    compiled = clog.since(mark)
    require(program_traces(ctrl) == traces,
            f"step programs compiled while serving: {compiled}")
    say(f"compiles while serving: {compiled['programs'] or 'none'}")
    for _, inst in instances(ctrl):
        s = inst.engine.perf_stats()
        keep = ("tokens", "dispatches", "host_syncs", "prefill_traces",
                "decode_traces", "spec_traces", "spec_dispatches",
                "spec_emitted", "spec_accepted_per_dispatch",
                "preemptions", "paged_attention", "speculative")
        say(f"perf_stats {inst.model_name}: "
            f"{json.dumps({k: s[k] for k in keep})}")
    same = [np.mean(np.asarray(served[(models[0], n)][1])
                    == np.asarray(served[(models[1], n)][1]))
            for n in sizes.prompt_lens]
    say(f"greedy agreement {models[0]} vs {models[1]} per prompt: "
        f"{[float(x) for x in same]}")
    kernels = kernel_in_compiled_decode(ctrl, models[1])
    say(f"tpu_custom_call in compiled programs of {models[1]}: {kernels}")
    errs = kernel_vs_reference(cfg, sizes, seed)
    say(f"kernel vs reference, max abs error: {errs} "
        f"(atol {KERNEL_ATOL}, rtol {KERNEL_RTOL})")
    agree, worst = forward_ranks(
        cfg, params, [(prompts[n], toks)
                      for (_, n), (_, toks) in sorted(served.items())],
        sizes.max_len)
    say(f"teacher-forced full forward: {agree} of served tokens are its "
        f"argmax; worst rank of a served token {worst}")
    require(worst <= MAX_FORWARD_RANK,
            f"a served token ranks {worst} in the full forward's logits, "
            f"beyond {MAX_FORWARD_RANK}")
    return {"kernels": kernels, "kernel_errors": errs, "agreement": agree,
            "worst_rank": worst, "served": served,
            "weight_bytes": sum(int(x.nbytes)
                                for x in jax.tree.leaves(params))}


# ------------------------------------------------------------------ #
# four chips: one replica per chip, a node killed mid-stream
# ------------------------------------------------------------------ #
def deploy_replicas(cfg: ArchConfig, params, devices,
                    sizes: Sizes) -> SDAIController:
    """One v5e-1 node per device; the controller fills each with one
    replica (anti-affinity).  min_replicas=1, so losing a node moves its
    streams to the survivors and places nothing new."""
    nodes = [BackendNode(f"n{i}", "v5e-1",
                         param_store=lambda _cfg: params, device=d)
             for i, d in enumerate(devices)]
    ctrl = controller(Fleet(nodes), cfg, fill_vram=True)
    ctrl.deploy([ModelDemand(
        cfg, min_replicas=1, max_replicas=len(devices),
        n_slots=sizes.n_slots, max_len=sizes.max_len,
        page_size=sizes.page_size, allow_quant=False)])
    per_node = {n.node_id: len(n.instances) for n in nodes}
    require(all(v == 1 for v in per_node.values()),
            f"expected one replica per node, got {per_node}")
    return ctrl


def _drive(node: BackendNode, inst_id: int,
           reqs: Sequence[Request]) -> None:
    """Hand-pump one node until every request settles."""
    for r in reqs:
        require(node.submit(inst_id, r), f"{node.node_id}: submit refused")
    while any(r.finished_at is None for r in reqs):
        node.pump()


def warm_replicas(cfg: ArchConfig, ctrl: SDAIController, sizes: Sizes,
                  clog: CompileLog) -> None:
    """Compile every (admission batch, bucket) prefill the streams can
    cause, and the greedy decode, on every engine, hand-pumped.
    Migrated streams resume as prompt + emitted tokens, which stays in
    the prompt's bucket by the choice of `Sizes`."""
    for node, inst in instances(ctrl):
        # admission batches hold up to max_prefill_per_step rows, padded
        # to a power of two
        cap = min(inst.engine.scheduler.cfg.max_prefill_per_step,
                  sizes.n_slots)
        pads = [1 << k for k in range((cap - 1).bit_length() + 1)]
        mark = clog.mark()
        for pad in pads:
            _drive(node, inst.instance_id, [
                Request(model=cfg.name,
                        prompt=prompt_tokens(cfg, sizes.stream_prompt_len,
                                             10_000 + i),
                        sampling=SamplingParams(max_tokens=2))
                for i in range(pad)])
        got = clog.since(mark)
        say(f"warm {node.node_id} ({node.device}): compiled "
            f"{len(got['programs'])} programs, backend compile "
            f"{got['compile_s']:.3f} s, persistent cache "
            f"{got['cache_hits']} hits / {got['cache_misses']} misses")


def reference_answers(cfg: ArchConfig, ctrl: SDAIController,
                      prompts: Sequence[List[int]],
                      budgets: Sequence[int]) -> List[List[int]]:
    """Fault-free greedy answers from the replica on the first device."""
    node, inst = next((n, i) for n, i in instances(ctrl)
                      if n.node_id == "n0")
    reqs = [Request(model=cfg.name, prompt=list(p),
                    sampling=SamplingParams(max_tokens=n))
            for p, n in zip(prompts, budgets)]
    _drive(node, inst.instance_id, reqs)
    for r in reqs:
        require(not r.error, f"reference: {r.error}")
    return [list(r.output) for r in reqs]


def streams_with_failure(gw: Gateway, model: str,
                         prompts: Sequence[List[int]], sizes: Sizes,
                         victim: str,
                         after_tokens: int) -> List[Tuple[int, List[int]]]:
    """Greedy streams through the running Gateway; `victim` fails once
    one of its streams has `after_tokens` tokens.  Returns each stream's
    last request id (a migrated stream keeps its id) and tokens."""
    fleet = gw.c.fleet
    hit = threading.Event()
    handles = [gw.submit(model, p,
                         SamplingParams(max_tokens=sizes.stream_tokens))
               for p in prompts]

    def consume(h):
        toks = []
        for ev in h.stream(timeout_s=sizes.serve_timeout_s):
            if ev.type is StreamEventType.TOKEN:
                toks.append(ev.token)
                if h.internal.node == victim and len(toks) >= after_tokens:
                    hit.set()
            elif ev.type is StreamEventType.ERROR:
                raise SmokeFailure(f"stream {h.request.prompt[:3]}: "
                                   f"{ev.error}")
        return h.internal.request_id, toks

    with ThreadPoolExecutor(max_workers=len(handles)) as pool:
        futs = [pool.submit(consume, h) for h in handles]
        if not hit.wait(sizes.serve_timeout_s):
            raise SmokeFailure(f"no stream on {victim} reached "
                               f"{after_tokens} tokens")
        fleet.fail_node(victim)
        return [f.result() for f in futs]


def run_four_chips(cfg: ArchConfig, sizes: Sizes, seed: int,
                   clog: CompileLog, devices) -> Dict:
    """The four-chip path.  Returns what it measured; raises
    SmokeFailure on the first failed check."""
    params = seeded_params(cfg, seed)
    ctrl = deploy_replicas(cfg, params, devices, sizes)
    check_real_engines(ctrl)
    where = [(n.node_id, str(n.device)) for n, _ in instances(ctrl)]
    say(f"replicas: {where}")
    warm_replicas(cfg, ctrl, sizes, clog)
    prompts = [prompt_tokens(cfg, sizes.stream_prompt_len, seed + i)
               for i in range(sizes.n_streams)]
    ref = reference_answers(cfg, ctrl, prompts,
                            [sizes.stream_tokens] * len(prompts))
    # the controller drops the failed node's replica: keep the engines
    engines = {n.node_id: i.engine for n, i in instances(ctrl)}
    before = {nid: eng.total_tokens for nid, eng in engines.items()}
    victim = "n1"
    gw = Gateway(ctrl)
    gw.start()
    try:
        outs = streams_with_failure(
            gw, cfg.name, prompts, sizes, victim,
            after_tokens=max(sizes.stream_tokens // 6, 1))
    finally:
        stopped = gw.stop(timeout_s=60)
    require(stopped, "runtime threads did not stop")
    served = {nid: eng.total_tokens - before[nid]
              for nid, eng in engines.items()}
    resumed = {e.data["request_id"]: e.data["tokens_resumed"]
               for e in ctrl.bus.of_kind(REQUEST_MIGRATED)}
    say(f"tokens served per node: {served}")
    say(f"streams migrated: {len(resumed)}, re-routed before their "
        f"first token: {gw.stats.stream_retries}")
    require(all(v > 0 for v in served.values()),
            f"a node served nothing: {served}")
    require(resumed, "the failed node's streams never migrated")
    for i, (_, got) in enumerate(outs):
        check_tokens(cfg, f"stream {i}", got, sizes.stream_tokens)
    # a migrated stream resumes on a survivor as prompt + the `cut`
    # tokens it had emitted, re-prefilled: its fault-free answer is the
    # uninterrupted one up to the cut, then n0's greedy answer to that
    # resumed context.  (Prefill and decode round the emitted tokens' KV
    # differently in bf16, so past the cut a stream may leave the
    # uninterrupted answer at a near-tie; the forward ranks below bound
    # how near.)
    moved = [i for i, (rid, _) in enumerate(outs) if rid in resumed]
    cut = {i: resumed[outs[i][0]] for i in moved}
    tails = reference_answers(
        cfg, ctrl, [prompts[i] + outs[i][1][:cut[i]] for i in moved],
        [sizes.stream_tokens - cut[i] for i in moved])
    want = list(ref)
    for i, tail in zip(moved, tails):
        want[i] = ref[i][:cut[i]] + tail
    differ = [f"stream {i} (migrated after {cut.get(i)} tokens) from "
              f"token {_first_split(got, want[i])}"
              for i, (_, got) in enumerate(outs) if got != want[i]]
    require(not differ, "streams differ from their fault-free answers "
            "from n0: " + "; ".join(differ))
    say(f"all {len(outs)} streams equal their fault-free answers from n0; "
        f"{len(moved)} migrated, after {[cut[i] for i in moved]} tokens")
    split = {i: _first_split(outs[i][1], ref[i]) for i in moved
             if outs[i][1] != ref[i]}
    say(f"migrated streams equal to their uninterrupted answer: "
        f"{len(moved) - len(split)} of {len(moved)}; the others leave it "
        f"at token {split}")
    agree, worst = forward_ranks(
        cfg, params, [(p, got) for p, (_, got) in zip(prompts, outs)],
        sizes.stream_prompt_len + sizes.stream_tokens)
    say(f"teacher-forced full forward: {agree} of streamed tokens are its "
        f"argmax; worst rank of a streamed token {worst}")
    require(worst <= MAX_FORWARD_RANK,
            f"a streamed token ranks {worst} in the full forward's logits, "
            f"beyond {MAX_FORWARD_RANK}")
    check_bus(ctrl)
    check_real_engines(ctrl)
    return {"served": served, "migrated": len(moved),
            "weight_bytes": sum(int(x.nbytes)
                                for x in jax.tree.leaves(params))}


def _first_split(a: Sequence[int], b: Sequence[int]) -> int:
    return next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


# ------------------------------------------------------------------ #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        info = require_tpu(args.chips)
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    say(f"compile cache: {compile_cache.enable()}")
    cfg = ARCHS[MODEL]
    sizes = Sizes()
    clog = CompileLog()
    try:
        devices = jax.devices()[:args.chips]
        if args.chips == 1:
            got = run_one_chip(cfg, sizes, args.seed, clog)
            require(all(got["kernels"].values()),
                    f"Pallas kernel missing from compiled programs: "
                    f"{got['kernels']}")
        else:
            got = run_four_chips(cfg, sizes, args.seed, clog, devices)
        floor = got["weight_bytes"]
        peaks = {str(d): peak_bytes(d) for d in devices}
        say(f"peak_bytes_in_use: {peaks}")
        require(all(v > floor for v in peaks.values()),
                f"peak memory at or below {floor} bytes on a device")
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    finally:
        tot = clog.since((0, 0, 0))
        say(f"persistent cache over the run: {tot['cache_hits']} hits, "
            f"{tot['cache_misses']} misses; backend compile "
            f"{tot['compile_s']:.3f} s in {len(tot['programs'])} programs")
        clog.close()
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
