"""The command refuses to run, and prints no result, without a TPU, and in
a checkout that holds only the benchmark's own files."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench import spec

ARGS = ["--workload", "qwen2-7b-7L.rollout-longtail", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_cpu_backend():
    p = _run(spec.ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_benchmark_files_alone(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
