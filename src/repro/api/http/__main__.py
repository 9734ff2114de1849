"""Serve the demo fleet over HTTP:  ``python -m repro.api.http``.

Builds the paper's 6-node heterogeneous testbed, deploys two reduced zoo
models through the SDAI controller, and exposes the Gateway as the
OpenAI-compatible wire service until interrupted.  This is the launch
target CI's http-smoke job (and the README curl examples) run against.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax

from repro import compile_cache
from repro.api import Gateway
from repro.api.http.server import GatewayHTTPServer, HTTPConfig
from repro.cluster import paper_testbed
from repro.configs import ZOO
from repro.core import (ControllerConfig, ModelCatalog, ModelDemand,
                        SDAIController)
from repro.models import build

_params = {}


def _param_store(cfg):
    if cfg.name not in _params:
        _params[cfg.name] = build(cfg).init(jax.random.PRNGKey(0))
    return _params[cfg.name]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m repro.api.http")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--models", default="llama3.2-1b,gemma3-1b",
                   help="comma-separated zoo names (reduced variants "
                        "are deployed so the demo runs on CPU)")
    p.add_argument("--replicas", type=int, default=2)
    args = p.parse_args(argv)
    compile_cache.enable()

    fleet = paper_testbed(param_store=_param_store)
    catalog = ModelCatalog()
    demands = []
    for name in args.models.split(","):
        name = name.strip()
        if name not in ZOO:
            print(f"unknown zoo model {name!r}", file=sys.stderr)
            return 2
        # reduced() shrinks the arch but keeps the name, so chat
        # templates and clients address the paper's model ids
        cfg = dataclasses.replace(ZOO[name].reduced(), name=name)
        catalog.register(cfg)
        # context fits a chat-templated prompt (llama3 headers alone
        # cost ~120 byte-tokens) plus decode budget
        demands.append(ModelDemand(cfg, min_replicas=args.replicas,
                                   n_slots=2, max_len=256))

    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    ctrl.discover()
    plan = ctrl.deploy(demands)
    if plan.unplaced:
        print(f"warning: unplaced {plan.unplaced}", file=sys.stderr)

    server = GatewayHTTPServer(
        Gateway(ctrl), HTTPConfig(host=args.host, port=args.port))
    server.start()
    print(f"serving {ctrl.replicas.models()} on {server.url()}  "
          f"(Ctrl-C to stop)", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        print("draining...", flush=True)
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
