"""One run of one cell: load, warm up the cell's own shapes, measure for
`--seconds`, check the answers against the plain reference, and print
the result line.

The system under test is the repo's serving stack as a deployment runs
it: `SDAIController` places one real engine per chip (`BackendNode`
committed to its device, the deploy defaults), and every request goes
Gateway -> frontend -> node -> engine from the client side.  The
benchmark makes the weights (`weights.py`), the traffic (`traffic.py`),
the timings (`loadgen.py`), the reduction of the trace (`tracing.py`),
the work counts (`work.py`), the reference (`reference.py`) and the
verdict (`correctness.py`); from the program it takes only the stack,
its counters and its program names.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence

import correctness
import loadgen
import readers
import spec as spec_lib
import traffic as traffic_lib
import tracing
import weights as weights_lib

HERE = spec_lib.HERE
ROOT = spec_lib.ROOT
# one fixed path inside the checkout: the path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"
PEAKS = HERE / "peaks.json"
WARM_TIMEOUT_S = 900.0
# the traced sub-window: it opens a quarter into the window and lasts
# half of it, at most this long (traces are large and slow to read)
TRACE_MAX_S = 10.0


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class RunError(RuntimeError):
    """The run could not be carried out as the cell states."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_compile_cache(path: pathlib.Path = CACHE_DIR) -> None:
    """Every compiled program goes to, and comes from, `path`, whatever
    the environment says: a warm run compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"{chips} chips asked for, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks_of(kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise RunError(f"no peaks for device kind {kind!r} in {PEAKS}")
    return table[kind]


class CompileLog:
    """Backend compiles and persistent-cache hits and misses, read from
    JAX's monitoring events (as `chip_smoke.CompileLog` does)."""
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        self.programs: List = []
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

    def mark(self):
        with self._lock:
            return self.hits, self.misses, len(self.programs)

    def since(self, mark) -> Dict:
        with self._lock:
            progs = self.programs[mark[2]:]
            return {"programs": [n for n, _ in progs],
                    "compile_s": sum(d for _, d in progs),
                    "cache_hits": self.hits - mark[0],
                    "cache_misses": self.misses - mark[1]}

    def close(self):
        import jax
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


class GcPauses:
    """The interpreter's garbage collections while the window runs: each
    one stops every Python thread, the serving loop's included."""

    def __init__(self):
        self.pauses: List = []
        self._t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def summary(self) -> Dict:
        return {"count": len(self.pauses),
                "oldest_generation": max((g for g, _ in self.pauses),
                                         default=-1),
                "longest_s": max((d for _, d in self.pauses), default=0.0),
                "total_s": sum(d for _, d in self.pauses)}


# ------------------------------------------------------------------ #
# the system under test
# ------------------------------------------------------------------ #
def build_arch(cfg: Dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(name=cfg["name"], family="dense", **cfg["arch"])


def make_weights(arch, seed: int, device):
    import jax
    from repro.models import build
    shapes = jax.eval_shape(build(arch).init, jax.random.PRNGKey(0))
    return weights_lib.make(shapes, seed, device)


@dataclasses.dataclass
class Stack:
    gateway: object
    engines: List            # (node, instance) per replica, node order

    @property
    def model(self) -> str:
        return self.engines[0][1].model_name


def deploy(arch, cfg: Dict, params, devices: Sequence) -> Stack:
    """One v5e-1 node per chip; the controller places one replica of the
    model on each, with the deploy defaults and the config's sizes."""
    from repro.api import Gateway
    from repro.cluster import BackendNode, Fleet
    from repro.core import (AutoscaleConfig, ControllerConfig, ModelCatalog,
                            ModelDemand, SDAIController)
    e = cfg["engine"]
    nodes = [BackendNode(f"n{i}", "v5e-1", param_store=lambda _c: params,
                         device=d) for i, d in enumerate(devices)]
    catalog = ModelCatalog()
    catalog.register(arch)
    ctrl = SDAIController(Fleet(nodes), catalog, ControllerConfig(
        real_param_threshold=arch.num_params(), fill_vram=False,
        autoscale=AutoscaleConfig(enabled=False)))
    ctrl.discover()
    plan = ctrl.deploy([ModelDemand(
        arch, min_replicas=len(nodes), max_replicas=len(nodes),
        n_slots=e["n_slots"], max_len=e["max_len"],
        page_size=e["page_size"], allow_quant=False)])
    if plan.unplaced:
        raise RunError(f"unplaced: {plan.unplaced}")
    engines = []
    for node in nodes:
        insts = list(node.instances.values())
        if len(insts) != 1 or insts[0].engine is None:
            raise RunError(f"{node.node_id}: expected one real engine, got "
                           f"{len(insts)} instances")
        ec = insts[0].engine.ecfg
        got = {"n_slots": ec.n_slots, "max_len": ec.max_len,
               "page_size": ec.page_size, "decode_block": ec.decode_block}
        if got != {k: e[k] for k in got}:
            raise RunError(f"engine runs {got}, the config states {e}")
        engines.append((node, insts[0]))
    return Stack(Gateway(ctrl), engines)


def _drive(node, inst_id: int, reqs) -> None:
    """Hand-pump one node until every request settles."""
    for r in reqs:
        if not node.submit(inst_id, r):
            raise RunError(f"{node.node_id}: warm-up submit refused: "
                           f"{r.error}")
    deadline = time.monotonic() + WARM_TIMEOUT_S
    while any(r.finished_at is None for r in reqs):
        if time.monotonic() > deadline:
            raise RunError("warm-up did not finish")
        node.pump()
    bad = [r.error for r in reqs if r.error]
    if bad:
        raise RunError(f"warm-up failed: {bad[:2]}")


def warm(stack: Stack, mix: Dict, arch, clog: CompileLog) -> Dict:
    """Compile, hand-pumped before the runtime starts, every program the
    window can run: a bucketed prefill for each (padded admission batch,
    length bucket) the mix's prompt lengths can form, the greedy fused
    decode, and the slot release a cancel runs.  Nothing else."""
    from repro.serving import Request, SamplingParams
    lens = traffic_lib.prompt_range(mix)
    out = {}
    for node, inst in stack.engines:
        eng = inst.engine
        buckets = sorted({eng._bucket_of(n) for n in lens})
        cap = min(eng.scheduler.cfg.max_prefill_per_step, eng.ecfg.n_slots)
        pads = sorted({1 << (k - 1).bit_length() for k in range(1, cap + 1)})
        mark = clog.mark()
        k = 0
        for b in buckets:
            n = min(b, max(lens))
            for pad in pads:
                reqs = [Request(model=inst.model_name,
                                prompt=[1 + (k + i) % (arch.vocab - 1)] * n,
                                sampling=SamplingParams(max_tokens=2))
                        for i in range(pad)]
                k += pad
                _drive(node, inst.instance_id, reqs)
        # a cancel of an admitted request releases its slot on device
        r = Request(model=inst.model_name, prompt=[1] * min(lens),
                    sampling=SamplingParams(max_tokens=64))
        node.submit(inst.instance_id, r)
        node.pump()
        node.cancel(inst.instance_id, r.request_id)
        got = clog.since(mark)
        out[node.node_id] = {"buckets": buckets, "pads": pads, **got}
        say(f"warm {node.node_id}: buckets {buckets} x pads {pads}; "
            f"{len(got['programs'])} programs, backend compile "
            f"{got['compile_s']:.3f} s, persistent cache "
            f"{got['cache_hits']} hits / {got['cache_misses']} misses")
    return out


def program_traces(stack: Stack) -> int:
    n = 0
    for _, inst in stack.engines:
        e = inst.engine
        n += e.prefill_traces + e.decode_traces + e.suffix_traces \
            + e.spec_traces
    return n


def free_program_state(stack: Stack) -> None:
    """Drop the engines' caches and slot state from the device so the
    reference runs beside the weights alone."""
    import jax
    for _, inst in stack.engines:
        eng = inst.engine
        for leaf in jax.tree.leaves((eng.cache, eng.pos, eng.last_tok,
                                     eng.active, eng.remaining,
                                     eng.spec_table, eng.spec_prev)):
            leaf.delete()
        eng.cache = None
    gc.collect()


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ------------------------------------------------------------------ #
# metrics
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Context:
    """What the metric readers read."""
    arch: Dict
    engine: Dict
    peaks: Dict
    window: loadgen.Window
    setup_s: float
    replicas: List[str]
    trace: Optional[tracing.TraceSummary] = None


def read_metrics(names: Sequence[str], ctx: Context,
                 base: pathlib.Path = HERE) -> Dict:
    out = {}
    for name in names:
        reader = spec_lib.load_reader(name, base)
        value = reader.read(ctx)
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": reader.unit}
    return out


# ------------------------------------------------------------------ #
# one run
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class RunResult:
    line: Dict
    verdict: correctness.Verdict
    context: Context


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             started: float, bench: Optional[spec_lib.Benchmark] = None,
             base: pathlib.Path = HERE, check_device: bool = True,
             control: bool = False,
             mix_over: Optional[Dict] = None) -> RunResult:
    """Run `workload` once; returns the result line and the verdict.
    `check_device=False` lets a test drive the rest of a run on the
    CPU; `control=True` also judges the fp8 control, put in the
    program's place on the same sample (`verdict.control`);
    `mix_over` replaces parameters of the traffic mix (a rate sweep)."""
    import jax
    from repro.serving import SamplingParams

    bench = bench or spec_lib.load_benchmark()
    cell = bench.cell(workload)
    cfg = spec_lib.load_config(cell.config, base)
    mix = dict(spec_lib.load_traffic(cell.traffic, base), **(mix_over or {}))
    if check_device:
        device = require_chips(cell.chips)
        peaks = peaks_of(device["kind"])
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
        peaks = {}
    devices = jax.devices()[:cell.chips]
    arch = build_arch(cfg)
    clog = CompileLog()
    stack = None
    try:
        params = make_weights(arch, seed, devices[0])
        stack = deploy(arch, cfg, params, devices)
        warmed = warm(stack, mix, arch, clog)
        traces_before = program_traces(stack)
        gw = stack.gateway
        sampling = dict(temperature=float(mix.get("temperature", 0.0)))

        def submit(rs):
            return gw.submit(stack.model, rs.prompt.tolist(),
                             SamplingParams(max_tokens=rs.max_tokens,
                                            **sampling))

        def read_program():
            return {
                "head_wait": [i.engine.scheduler.head_wait_s()
                              for _, i in stack.engines],
                "active": [len(i.engine.slot_req) for _, i in stack.engines],
            }

        lead = float(mix.get("lead_s", 0.0))
        trace_dir = tempfile.mkdtemp(prefix="onchip-trace-") if trace \
            else None
        tracer = None
        pauses = GcPauses()
        gw.start()
        mark = clog.mark()
        t_begin = time.perf_counter()
        if trace:
            span = min(seconds / 2.0, TRACE_MAX_S)
            tracer = loadgen.Tracer(t_begin + lead + seconds / 4.0, span,
                                    trace_dir)
        if mix["loop"] == "open":
            window = loadgen.run_open(
                submit, traffic_lib.open_schedule(mix, seed, seconds,
                                                  arch.vocab),
                lead, seconds, read_program, tracer)
        else:
            n_clients = int(mix["clients_per_slot"]) \
                * int(cfg["engine"]["n_slots"]) * len(stack.engines)
            window = loadgen.run_closed(
                submit, traffic_lib.closed_streams(mix, seed, n_clients,
                                                   arch.vocab),
                lead, seconds, read_program, tracer)
        compiled = clog.since(mark)
        compiled["step_traces"] = program_traces(stack) - traces_before
        pauses.close()
        if not gw.stop(timeout_s=60):
            raise RunError("runtime threads did not stop")
        memory = peak_bytes(devices)
        summary = None
        if trace:
            a, b = window.trace_window
            summary = tracing.reduce_dir(pathlib.Path(trace_dir), b - a,
                                         [d.id for d in devices])
            shutil.rmtree(trace_dir, ignore_errors=True)
        # the verdict: after the window, with the program's state freed
        free_program_state(stack)
        judged = _judged(window)
        target = cfg["check"]
        sample = correctness.pick_sample(
            judged, seed, int(target["sample_tokens"]),
            int(target["max_requests"]))
        verdict = correctness.judge(judged, sample, params, cfg["arch"],
                                    cfg, control=control)
        ctx = Context(cfg["arch"], cfg["engine"], peaks, window,
                      window.t0 - started,
                      [n.node_id for n, _ in stack.engines], summary)
        names = [m.name for m in (bench.per_layer_of(workload) if trace
                                  else bench.end_to_end_of(workload))]
        metrics = read_metrics(names, ctx, base)
        device = dict(device, memory_peak_bytes=memory)
        if trace and summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line = {
            "correct": verdict.correct,
            "attempted": len(judged),
            "failed": sum(1 for r in judged if not r.ok),
            "metrics": metrics,
            "device": device,
        }
        if trace and summary is not None:
            line["breakdown"] = {"device_ops": summary.device_ops,
                                 "idle_gaps": summary.idle_gaps}
        line["run"] = {
            "seed": seed, "seconds": seconds, "loop": window.loop,
            "requests_sent": len(window.records),
            "compiled_in_window": compiled,
            "gc_pauses": pauses.summary(),
            "warm": warmed,
            "sample_requests": verdict.sample,
            "sample_tokens": verdict.sample_tokens,
            # where the latency tails sit, for reading their steadiness
            "ttft_ms": _quantiles(readers.ttfts_ms(ctx)),
            "itl_ms": _quantiles(readers.itls_ms(ctx)),
        }
        if control:
            line["run"]["control"] = {"correct": verdict.control.correct,
                                      "checks": verdict.control.checks}
        line["checks"] = verdict.checks
        return RunResult(line, verdict, ctx)
    finally:
        clog.close()
        if stack is not None and stack.gateway.runtime_active:
            stack.gateway.stop(drain=False, timeout_s=60)


def _judged(window: loadgen.Window) -> List[loadgen.Record]:
    """Requests whose answers the run judges: in an open loop those due
    in the window; in a closed loop every request sent, less those the
    benchmark cancelled at the close."""
    if window.loop == "open":
        return [r for r in window.records if r.phase == "window"]
    return [r for r in window.records if not r.cancelled]


def _quantiles(xs: Sequence[float]) -> Dict[str, float]:
    return {f"p{q}": _finite(readers.percentile(xs, q) or 0.0)
            for q in (50, 90, 95, 99)}


def _finite(x: float) -> float:
    return x if math.isfinite(x) else 1e12


def main(argv=None, *, started: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed is a whole number")
    try:
        enable_compile_cache()
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), started=started)
    except (NoAccelerator, RunError, spec_lib.SpecError) as e:
        say(f"FAIL: {e}")
        return 1
    for name, c in res.line["checks"].items():
        c["value"] = _finite(c["value"])
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    for m in res.line["metrics"].values():
        m["value"] = _finite(m["value"])
    print(json.dumps(res.line), flush=True)
    return 0
