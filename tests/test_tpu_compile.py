"""Compile the served path's programs for a described TPU v5e at
olmo-1b's published widths — what the chip's compiler refuses, this file
refuses without a chip.  Nothing runs: these tests say nothing about
results or times.

The topology is described inside a module-scoped fixture (only the
worker that runs this file loads the TPU compiler), the persistent
compilation cache is off around the compiles (entries compiled for a
described chip cannot be read back here), and the kernels' interpret
decision, which asks `jax.default_backend()`, is steered to the compiled
path inside the tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.models import build

HBM_BYTES = 16 * 2 ** 30          # one v5e chip
# the smoke's served sizes (chip_smoke.Sizes)
N_SLOTS, MAX_LEN, PAGE_SIZE, SPEC_DRAFT = 8, 1024, 16, 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_path():
    """Persistent cache off, kernels on their compiled (non-interpret)
    path, for every compile in this module."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "interpret_mode", lambda: False)
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def olmo():
    cfg = ARCHS["olmo-1b"]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (16, 2048, 50304)
    return cfg


def _spec(one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


@pytest.mark.parametrize("kind", ["decode", "suffix"])
def test_paged_kernel_compiles_at_olmo_widths(kind, one_chip, olmo,
                                              compiled_path):
    spec = _spec(one_chip)
    b, k, hd = N_SLOTS, olmo.n_kv_heads, olmo.head_dim
    g = olmo.n_heads // k
    pps = MAX_LEN // PAGE_SIZE
    pool = spec((N_SLOTS * pps, PAGE_SIZE, k, hd), jnp.bfloat16)
    table, pos = spec((b, pps), jnp.int32), spec((b,), jnp.int32)
    if kind == "decode":
        fn, q = pa.paged_decode_attention, spec((b, k, g, hd), jnp.bfloat16)
    else:
        fn = pa.paged_suffix_attention
        q = spec((b, SPEC_DRAFT + 1, k * g, hd), jnp.bfloat16)
    compiled = jax.jit(fn).lower(q, pool, pool, table, pos).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _params_and_pool(olmo, one_chip):
    spec = _spec(one_chip)
    model = build(olmo)
    params = jax.tree.map(
        lambda x: spec(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pps = MAX_LEN // PAGE_SIZE
    pool = spec((olmo.n_layers, N_SLOTS * pps, PAGE_SIZE, olmo.n_kv_heads,
                 olmo.head_dim), jnp.bfloat16)
    return model, params, {"k": pool, "v": pool}


@pytest.mark.parametrize("step", ["decode", "verify"])
def test_olmo_paged_decode_step_fits_one_chip(step, one_chip, olmo,
                                              compiled_path):
    """One paged decode step, and one speculative verify of
    SPEC_DRAFT + 1 tokens per slot, both through the Pallas kernel."""
    spec = _spec(one_chip)
    model, params, cache = _params_and_pool(olmo, one_chip)
    i32 = jnp.int32
    table = spec((N_SLOTS, MAX_LEN // PAGE_SIZE), i32)
    if step == "decode":
        fn, tokens = model.decode_paged, spec((N_SLOTS,), i32)
    else:
        fn, tokens = model.verify_paged, spec((N_SLOTS, SPEC_DRAFT + 1), i32)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, tokens, spec((N_SLOTS,), i32), table,
        table).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_olmo_prefill_bucket_fits_one_chip(one_chip, olmo, compiled_path):
    spec = _spec(one_chip)
    model, params, _ = _params_and_pool(olmo, one_chip)

    def prefill(params, tokens, lengths):
        return model.prefill(params, tokens, lengths=lengths)

    compiled = jax.jit(prefill).lower(
        params, spec((1, MAX_LEN), jnp.int32),
        spec((1,), jnp.int32)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
