"""Without a TPU, or without the program, a run fails and prints no
result."""
import os
import shutil
import subprocess
import sys

import spec

ROOT = spec.ROOT
ARGS = ["--workload", "olmo-1b.chat", "--seed", "3000000000", "--seconds",
        "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_without_a_tpu_fails_and_prints_no_result():
    p = _run(ROOT, ROOT / "benchmarks" / "onchip" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "benchmarks" / "onchip" / "run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
