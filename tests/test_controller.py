"""SDAI controller end-to-end: discovery, deployment, monitoring loop,
node failure -> reallocation, elastic join, wizard flow, unified client."""
import dataclasses

import pytest

from repro.cluster import BackendNode, Fleet, paper_testbed, scale_fleet
from repro.cluster.node import DeployError
from repro.configs import ZOO
from repro.core import (Client, ConfigWizard, ControllerConfig,
                        ModelCatalog, ModelDemand, SDAIController,
                        WizardConfig, WizardModelChoice, WizardSelection)
from repro.serving import SamplingParams


def _catalog_tiny(param_store):
    catalog = ModelCatalog()
    tiny = dataclasses.replace(ZOO["llama3.2-1b"].reduced(),
                               name="llama3.2-1b")
    catalog.register(tiny)
    catalog.register(ZOO["deepseek-r1-7b"])
    catalog.register(ZOO["qwen3-8b"])
    return catalog, tiny


@pytest.fixture()
def stack(param_store):
    fleet = paper_testbed(param_store=param_store)
    catalog, tiny = _catalog_tiny(param_store)
    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    ctrl.discover()
    return fleet, ctrl, tiny


def test_discovery_finds_all_nodes(stack):
    fleet, ctrl, tiny = stack
    assert set(ctrl.nodes.ids()) == set(fleet.nodes)
    payload = ctrl.nodes.payloads["node3"]
    assert payload["legacy"] is True          # GTX 1660S analogue


def test_deploy_and_serve(stack):
    fleet, ctrl, tiny = stack
    plan = ctrl.deploy([
        ModelDemand(tiny, min_replicas=2, n_slots=2, max_len=48),
        ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=2),
    ])
    assert not plan.unplaced
    client = Client(ctrl)
    assert "llama3.2-1b" in client.models()
    req = client.generate("llama3.2-1b", [1, 2, 3],
                          SamplingParams(max_tokens=3))
    assert req.error == "" and len(req.output) == 3
    assert req.ttft is not None and req.latency is not None


def test_failure_reallocation_restores_replicas(stack):
    fleet, ctrl, tiny = stack
    ctrl.deploy([ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=2,
                             max_replicas=2)])
    before = ctrl.frontend.healthy_replicas("deepseek-r1-7b")
    victim = before[0].node_id
    fleet.fail_node(victim)
    ctrl.tick()
    after = ctrl.frontend.healthy_replicas("deepseek-r1-7b")
    assert len(after) >= 2, "reallocation must restore min replicas"
    assert all(k.node_id != victim for k in after)
    kinds = [e.kind for e in ctrl.bus.events]
    assert "node_dead" in kinds and "reallocated" in kinds


def test_elastic_join_rebalances(stack):
    fleet, ctrl, tiny = stack
    ctrl.deploy([ModelDemand(ZOO["qwen3-8b"], min_replicas=1,
                             max_replicas=8)])
    n_before = len(ctrl.frontend.healthy_replicas("qwen3-8b"))
    fleet.add(BackendNode("node7", "v5e-8"))
    ctrl.tick()
    n_after = len(ctrl.frontend.healthy_replicas("qwen3-8b"))
    assert n_after > n_before
    assert "node_joined" in [e.kind for e in ctrl.bus.events]


def test_node_recovery_rejoins_empty(stack):
    fleet, ctrl, tiny = stack
    ctrl.deploy([ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=2,
                             max_replicas=2)])
    victim = ctrl.frontend.healthy_replicas("deepseek-r1-7b")[0].node_id
    fleet.fail_node(victim)
    ctrl.tick()
    fleet.recover_node(victim)
    ctrl.tick()
    assert "node_recovered" in [e.kind for e in ctrl.bus.events]
    dash = ctrl.dashboard()
    assert dash["agents"][victim]["alive"]


def test_dashboard_shape(stack):
    fleet, ctrl, tiny = stack
    ctrl.deploy([ModelDemand(tiny, min_replicas=1, n_slots=2, max_len=32)])
    dash = ctrl.dashboard()
    assert dash["connected"] == 6 and dash["total"] == 6
    assert "llama3.2-1b" in dash["models"]
    assert dash["routing"]["llama3.2-1b"]


def test_wizard_select_configure_generate(stack):
    fleet, ctrl, tiny = stack
    wiz = ConfigWizard(ctrl)
    agents = wiz.list_agents()
    assert len(agents) == 6 and all("hbm_free_gb" in a for a in agents)
    cap = wiz.model_capacity("deepseek-r1-7b", "node6")
    assert cap["max_instances"] >= 1
    gen = wiz.generate(WizardConfig(
        selection=WizardSelection(agents=[a["node_id"] for a in agents],
                                  gpu_enabled={"node3": False}),
        models=[WizardModelChoice("deepseek-r1-7b", replicas=2),
                WizardModelChoice("qwen3-8b", replicas=1, port=12000)],
    ))
    ov = gen["overview"]
    assert ov["system_stats"]["agents"] == 5      # node3 GPU disabled
    assert ov["model_distribution"]["deepseek-r1-7b"] >= 2
    assert ov["ports"]["qwen3-8b"] == 12000
    assert "node3" not in ov["agent_distribution"]       # GPU disabled
    assert "backend bk_deepseek-r1-7b" in ov["frontend_config"]
    keys = wiz.apply(gen)
    assert len(keys) == len(gen["plan"].assignments)


def test_scale_fleet_thousand_nodes():
    """Placement + discovery scale to a 1000-node heterogeneous fleet."""
    fleet = scale_fleet(1000, seed=3)
    catalog = ModelCatalog()
    for name in ("llama3.2-1b", "deepseek-r1-7b", "qwen3-8b"):
        catalog.register(ZOO[name])
    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    found = ctrl.discover()
    assert len(found) == 1000
    plan = ctrl.deploy([
        ModelDemand(ZOO["llama3.2-1b"], min_replicas=100,
                    max_replicas=2000),
        ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=50,
                    max_replicas=800),
        ModelDemand(ZOO["qwen3-8b"], min_replicas=20, max_replicas=400),
    ])
    assert not plan.unplaced
    assert ctrl.fleet_utilization() > 0.5
    # kill 5% of nodes; service must survive
    import random
    rng = random.Random(0)
    fleet.fail_random(rng, 50)
    ctrl.tick()
    for m in ("llama3.2-1b", "deepseek-r1-7b", "qwen3-8b"):
        assert ctrl.frontend.healthy_replicas(m), f"{m} lost all replicas"


def test_real_deploy_without_params_is_reported_not_simulated(param_store):
    """A model under the real-engine threshold on a node with no weights
    for it: the node refuses, the controller reports `deploy_failed`,
    and nothing serves synthetic tokens in its place."""
    tiny = dataclasses.replace(ZOO["llama3.2-1b"].reduced(),
                               name="llama3.2-1b")
    node = BackendNode("bare", "v5e-1")            # no param store
    with pytest.raises(DeployError):
        node.deploy(tiny, n_slots=2, max_len=48)
    assert node.instances == {}
    catalog = ModelCatalog()
    catalog.register(tiny)
    ctrl = SDAIController(Fleet([node]), catalog, ControllerConfig())
    ctrl.discover()
    ctrl.deploy([ModelDemand(tiny, min_replicas=1, max_replicas=1,
                             n_slots=2, max_len=48)])
    assert [e.data["model"] for e in ctrl.bus.of_kind("deploy_failed")] \
        == ["llama3.2-1b"]
    assert not ctrl.bus.of_kind("instance_deployed")
    assert ctrl.replicas.for_model("llama3.2-1b") == []
    assert node.instances == {}
    # with weights, the same deploy reports the engine it built
    node.param_store = param_store
    ctrl.deploy([ModelDemand(tiny, min_replicas=1, max_replicas=1,
                             n_slots=2, max_len=48)])
    [ev] = ctrl.bus.of_kind("instance_deployed")
    assert ev.data["real"] is True
    [inst] = node.instances.values()
    assert inst.engine is not None
