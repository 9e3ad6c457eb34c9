"""``bench/run.py`` refuses to measure without a TPU, and without the
program's sources."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import tiny

RUN = [sys.executable, "bench/run.py", "--workload", "serve.gpt3s.chat",
       "--seed", "1", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(RUN, cwd=tiny.ROOT, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(RUN, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
