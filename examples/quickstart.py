"""Quickstart: AIvailable in ~60 lines, on Gateway API v1 + wire v1.

Build the paper's heterogeneous 6-node testbed, deploy two models through
the SDAI controller (VRAM-aware placement + HAProxy-style frontend), and
talk to everything through ONE unified gateway: sync `generate`, async
`submit` + token streaming, the typed admin snapshot — then the same
fleet over the network, via the OpenAI-compatible HTTP service and its
stdlib client (the old `repro.core.Client` shim is deprecated).

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax

from repro import compile_cache
from repro.api import Gateway
from repro.api.http import GatewayHTTPServer, HTTPClient, HTTPConfig
from repro.cluster import paper_testbed
from repro.configs import ZOO
from repro.core import (ControllerConfig, ModelCatalog, ModelDemand,
                        SDAIController)
from repro.models import build
from repro.serving import SamplingParams

# --- backend nodes pull weights from this store (the Ollama analogue);
#     reduced() models are tiny so the example runs on CPU in seconds
_params = {}


def param_store(cfg):
    if cfg.name not in _params:
        _params[cfg.name] = build(cfg).init(jax.random.PRNGKey(0))
    return _params[cfg.name]


def main():
    compile_cache.enable()
    fleet = paper_testbed(param_store=param_store)
    catalog = ModelCatalog()
    llama = dataclasses.replace(ZOO["llama3.2-1b"].reduced(),
                                name="llama3.2-1b")
    gemma = dataclasses.replace(ZOO["gemma3-1b"].reduced(),
                                name="gemma3-1b")
    catalog.register(llama)
    catalog.register(gemma)

    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    print("discovered nodes:", ctrl.discover())

    # max_len fits a chat-templated prompt (the llama3 header format
    # alone costs ~120 byte-tokens) plus decode budget
    plan = ctrl.deploy([
        ModelDemand(llama, min_replicas=2, n_slots=2, max_len=192),
        ModelDemand(gemma, min_replicas=2, n_slots=2, max_len=192),
    ])
    print(f"deployed {len(plan.assignments)} instances, "
          f"fleet VRAM utilization {ctrl.fleet_utilization():.1%}")

    gw = Gateway(ctrl)
    print("models behind the unified endpoint:", gw.models())

    # sync: one blocking call -> frozen GenerationResponse
    resp = gw.generate("llama3.2-1b", prompt=[1, 2, 3, 4],
                       sampling=SamplingParams(max_tokens=8))
    print(f"  sync   {resp.model:14s} -> {list(resp.tokens)}  "
          f"(via {resp.node}, ttft={resp.ttft*1e3:.0f}ms, "
          f"finish={resp.finish_reason})")

    # async + streaming: tokens arrive as engine decode steps produce them
    handle = gw.submit("gemma3-1b", prompt=[5, 6, 7],
                       sampling=SamplingParams(max_tokens=8))
    toks = []
    for ev in handle.stream():
        if ev.type.value == "token":
            toks.append(ev.token)           # incremental delta
    print(f"  stream {handle.response.model:14s} -> {toks}  "
          f"(via {handle.response.node})")

    snap = gw.admin.snapshot()
    print(f"admin snapshot: {snap.connected}/{snap.total} agents, "
          f"routing={ {m: len(r) for m, r in snap.routing.items()} }")

    # the same fleet over the wire: OpenAI-compatible HTTP + SSE
    server = GatewayHTTPServer(gw, HTTPConfig(port=0)).start()
    client = HTTPClient(server.url(), tenant="quickstart")
    print(f"HTTP service on {server.url()}: models={client.models()}")
    out = client.chat("llama3.2-1b", ["hello fleet"], max_tokens=8)
    choice = out["choices"][0]
    print(f"  chat   {out['model']:14s} -> {choice['token_ids']}  "
          f"(finish={choice['finish_reason']}, "
          f"via {out['metadata']['node']})")
    deltas = sum(1 for c in client.chat("gemma3-1b", ["stream please"],
                                        max_tokens=8, stream=True)
                 if c["choices"][0].get("delta", {}).get("token")
                 is not None)
    print(f"  stream gemma3-1b      -> {deltas} SSE token deltas")
    server.stop()


if __name__ == "__main__":
    main()
