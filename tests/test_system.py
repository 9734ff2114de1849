"""End-to-end behaviour tests for the paper's system: full AIvailable flow
(discover -> wizard -> deploy -> unified client -> failure -> failover ->
reallocation), plus distributed-correctness and dry-run integration tests
that need their own device topology (subprocesses: jax locks the device
count at first init)."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.cluster import paper_testbed
from repro.configs import ZOO
from repro.core import (Client, ControllerConfig, ModelCatalog,
                        ModelDemand, SDAIController)
from repro.serving import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_full_paper_flow(param_store):
    """The complete AIvailable lifecycle on the paper's 6-node testbed."""
    fleet = paper_testbed(param_store=param_store)
    catalog = ModelCatalog()
    tiny = dataclasses.replace(ZOO["llama3.2-1b"].reduced(),
                               name="llama3.2-1b")
    catalog.register(tiny)
    catalog.register(ZOO["deepseek-r1-7b"])
    ctrl = SDAIController(fleet, catalog, ControllerConfig())
    assert len(ctrl.discover()) == 6
    plan = ctrl.deploy([
        ModelDemand(tiny, min_replicas=2, n_slots=2, max_len=48),
        ModelDemand(ZOO["deepseek-r1-7b"], min_replicas=2),
    ])
    assert not plan.unplaced
    assert ctrl.fleet_utilization() > 0.10
    client = Client(ctrl)
    r1 = client.generate("llama3.2-1b", [1, 2, 3],
                         SamplingParams(max_tokens=4))
    assert r1.error == "" and len(r1.output) == 4

    # failure -> transparent failover + reallocation
    victim = r1.node
    fleet.fail_node(victim)
    ctrl.tick()
    r2 = client.generate("llama3.2-1b", [4, 5],
                         SamplingParams(max_tokens=4))
    assert r2.error == "" and r2.node != victim
    # replica count restored to >= min
    assert len(ctrl.frontend.healthy_replicas("llama3.2-1b")) >= 2


@pytest.mark.slow
def test_sharded_train_step_matches_unsharded():
    """Loss from the pjit train step on an 8-device (2,4) mesh equals the
    single-device loss — sharding rules change layout, not math."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS
    from repro.launch.steps import make_train_step
    from repro.distributed.sharding import train_strategy_fsdp
    from repro.training.data import SyntheticLM, DataConfig

    cfg = ARCHS["olmo-1b"].reduced()
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, batch=8)
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticLM(dc).batch_at(0).items()}

    step1, init1 = make_train_step(cfg)
    s1 = init1(jax.random.PRNGKey(0))
    s1b, m1 = jax.jit(step1)(s1, batch)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    strat = train_strategy_fsdp(mesh)
    stepN, initN = make_train_step(cfg, mesh, strat)
    with mesh:
        sN = initN(jax.random.PRNGKey(0))
        sNb, mN = jax.jit(stepN)(sN, batch)
    l1, lN = float(m1["loss"]), float(mN["loss"])
    assert abs(l1 - lN) < 5e-3, (l1, lN)
    # params after one step match too
    for a, b in zip(jax.tree.leaves(s1b["params"]),
                    jax.tree.leaves(sNb["params"])):
        d = float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32))))
        assert d < 5e-2, d
    print("OK")
    """
    r = _run_sub(code, devices=8)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dryrun_cell_multipod():
    """One full multi-pod dry-run cell compiles via the CLI entrypoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "olmo-1b",
         "--shape", "decode_32k", "--mesh", "multi", "--out",
         "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "[ok]" in r.stdout


@pytest.mark.slow
def test_distributed_flash_decode_combine():
    """Sequence-sharded flash-decode (shard_map LSE merge) is exact and
    moves only O(B*H*hd) wire bytes."""
    code = """
    import numpy as np, jax, jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.roofline.analysis import collective_bytes
    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(1)
    B,K,G,S,hd = 2,4,4,512,64
    q = jnp.asarray(rng.standard_normal((B,K,G,hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B,K,S,hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B,K,S,hd)), jnp.float32)
    pos = jnp.asarray([300, 450], jnp.int32)
    fn = ops.decode_attention_sharded(mesh, "model")
    with mesh:
        o = jax.jit(fn)(q, kc, vc, pos)
        txt = jax.jit(fn).lower(q, kc, vc, pos).compile().as_text()
    r = ref.decode_attention_ref(q, kc, vc, pos)
    assert float(jnp.max(jnp.abs(o - r))) < 1e-5
    wire = sum(collective_bytes(txt, 8).values())
    kv_bytes = kc.size * 4
    assert wire < 0.1 * kv_bytes, (wire, kv_bytes)
    print("OK")
    """
    r = _run_sub(code, devices=8)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_fsdp_tp_shard_map_projections_exact():
    """The explicit Megatron-SP machinery (weight gather, row/col
    psum_scatter projections, seq gather) is numerically exact vs the
    single-device step — fsdp_tp strategy on a (2,4) mesh."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS
    from repro.launch.steps import make_train_step
    from repro.distributed.sharding import train_strategy
    from repro.training.data import SyntheticLM, DataConfig

    cfg = ARCHS["olmo-1b"].reduced()
    dc = DataConfig(vocab=cfg.vocab, seq_len=32, batch=4)
    batch = {k: jnp.asarray(v) for k, v in
             SyntheticLM(dc).batch_at(0).items()}

    step1, init1 = make_train_step(cfg)
    s1 = init1(jax.random.PRNGKey(0))
    _, m1 = jax.jit(step1)(s1, batch)

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    strat = train_strategy(mesh)          # fsdp_tp: uses shard_map paths
    stepN, initN = make_train_step(cfg, mesh, strat)
    with mesh:
        sN = initN(jax.random.PRNGKey(0))
        _, mN = jax.jit(stepN)(sN, batch)
    l1, lN = float(m1["loss"]), float(mN["loss"])
    assert abs(l1 - lN) < 5e-3, (l1, lN)
    g1, gN = float(m1["grad_norm"]), float(mN["grad_norm"])
    assert abs(g1 - gN) / max(g1, 1e-6) < 2e-2, (g1, gN)
    print("OK")
    """
    r = _run_sub(code, devices=8)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK" in r.stdout
