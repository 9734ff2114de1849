"""`chip_smoke.py` rehearsed on the CPU: its phase functions on a reduced
olmo-1b, its refusal of a machine without a TPU, its four-chip phase on
four virtual CPU devices (a subprocess: the device count is fixed when a
process first touches JAX), and the import and compile-cache rules the
script relies on.  None of these runs touches the TPU library."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import ARCHS  # noqa: E402

SMALL = chip_smoke.Sizes(
    n_slots=4, max_len=64, page_size=8, max_tokens=8,
    prompt_lens=(5, 12, 40),            # buckets 8, 16 and 64
    n_streams=8, stream_prompt_len=20,  # prompt and migrated context:
    stream_tokens=12,                   # bucket 32
    warm_timeout_s=300.0, serve_timeout_s=120.0)


def _run(code: str, **env) -> subprocess.CompletedProcess:
    """Run `code` in a fresh CPU-only interpreter; an env value of None
    removes that variable."""
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"))
    for k, v in env.items():
        full.pop(k, None)
        if v is not None:
            full[k] = v
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=full, cwd=str(ROOT))


def test_main_refuses_a_machine_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "no TPU" in out


def test_one_chip_phases_on_reduced_olmo():
    cfg = ARCHS["olmo-1b"].reduced()
    clog = chip_smoke.CompileLog()
    try:
        got = chip_smoke.run_one_chip(cfg, SMALL, 0, clog)
    finally:
        clog.close()
    # two instances x three prompt lengths, every answer full length
    assert len(got["served"]) == 6
    assert {s for s, _ in got["served"].values()} == \
        {"http-stream", "gateway", "http"}
    # the CPU takes the jnp reference, never the Pallas kernel
    assert got["kernels"] == {"spec_verify": False, "paged_decode": False}
    assert got["worst_rank"] <= chip_smoke.MAX_FORWARD_RANK
    assert 0.0 < got["agreement"] <= 1.0
    assert set(got["kernel_errors"]) == {"paged_decode", "paged_suffix"}


def test_four_chip_phase_on_virtual_cpu_devices():
    r = _run(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT)!r})
        import jax
        import chip_smoke as cs
        from repro.configs import ARCHS
        assert len(jax.devices()) == 4
        sizes = cs.Sizes(**{json.dumps(SMALL.__dict__)!s})
        clog = cs.CompileLog()
        got = cs.run_four_chips(ARCHS["olmo-1b"].reduced(), sizes, 0, clog,
                                jax.devices()[:4])
        print("RESULT", json.dumps({{"served": got["served"],
                                    "migrated": got["migrated"]}}))
        """, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    got = json.loads(line[len("RESULT "):])
    assert sorted(got["served"]) == ["n0", "n1", "n2", "n3"]
    assert all(v > 0 for v in got["served"].values())
    assert got["migrated"] >= 1
    assert "all 8 streams equal their fault-free answers" in r.stdout
    assert "worst rank of a streamed token" in r.stdout


def test_importing_repro_initialises_no_backend():
    r = _run("""
        import repro.api, repro.cluster, repro.kernels, repro.serving
        from jax._src import xla_bridge
        print("BACKENDS", sorted(xla_bridge._backends))
        """)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "BACKENDS []" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(env_dir, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins where set; else one fixed
    directory inside the checkout."""
    want = str(tmp_path / env_dir) if env_dir else str(ROOT / ".jax_cache")
    r = _run("""
        import jax
        from repro import compile_cache
        used = compile_cache.enable()
        print("DIRS", used, jax.config.jax_compilation_cache_dir)
        """, JAX_COMPILATION_CACHE_DIR=want if env_dir else None)
    assert r.returncode == 0, r.stderr[-2000:]
    used, configured = r.stdout.split("DIRS ")[1].split()
    assert used == configured == want
