"""The SDAI Configuration Wizard flow (paper §5): Select -> Configure ->
Generate, printing the agent cards, model-capacity panel, configuration
overview, and the rendered HAProxy-style frontend config.

    PYTHONPATH=src python examples/wizard_flow.py
"""
import json

from repro import compile_cache
from repro.cluster import paper_testbed
from repro.configs import ZOO
from repro.core import (ConfigWizard, ControllerConfig, ModelCatalog,
                        SDAIController, WizardConfig, WizardModelChoice,
                        WizardSelection)


def main():
    compile_cache.enable()
    fleet = paper_testbed()
    catalog = ModelCatalog()
    for name in ("deepseek-r1-7b", "qwen3-8b", "llama3.2-1b",
                 "gemma3-1b", "nomic-embed-text", "mxbai-embed-large"):
        catalog.register(ZOO[name])
    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    ctrl.discover()
    wiz = ConfigWizard(ctrl)

    print("=" * 64)
    print("STAGE 1 - SELECT AGENTS")
    for card in wiz.list_agents():
        print(f"  [{card['status']:8s}] {card['node_id']:6s} "
              f"{card['class']:12s} {card['toolkit']:7s} "
              f"({card['year']}) free={card['hbm_free_gb']:.1f} GB")

    print("\n  model capacity on node6 (RX 6800 analogue):")
    cap = wiz.model_capacity("deepseek-r1-7b", "node6")
    for q, b in cap["bytes_per_instance"].items():
        print(f"    deepseek-r1-7b {q or 'bf16':5s}: {b/2**30:.2f} GiB")
    print(f"    -> precision={cap['precision'] or 'bf16'}, "
          f"max_instances={cap['max_instances']}")

    print("\n" + "=" * 64)
    print("STAGE 2 - CONFIGURE (models, replicas, ports)")
    wcfg = WizardConfig(
        selection=WizardSelection(agents=[a["node_id"]
                                          for a in wiz.list_agents()]),
        models=[
            WizardModelChoice("deepseek-r1-7b", replicas=2),
            WizardModelChoice("qwen3-8b", replicas=1),
            WizardModelChoice("llama3.2-1b", replicas=3),
            WizardModelChoice("nomic-embed-text", replicas=2,
                              port=11500),
        ])
    gen = wiz.generate(wcfg)

    print("\n" + "=" * 64)
    print("STAGE 3 - GENERATE: configuration overview")
    ov = gen["overview"]
    print(json.dumps({k: v for k, v in ov.items()
                      if k != "frontend_config"}, indent=2))
    print("\n--- generated frontend config " + "-" * 30)
    print(ov["frontend_config"])

    keys = wiz.apply(gen)
    print(f"\napplied: {len(keys)} instances running; fleet util "
          f"{ctrl.fleet_utilization():.1%}")


if __name__ == "__main__":
    main()
