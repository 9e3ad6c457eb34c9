"""Shared harness for multi-device tests on virtual CPU devices.

The main pytest process must keep seeing exactly **1** device (smoke
tests and benches depend on it — see ``tests/conftest.py``), and XLA
only honours ``--xla_force_host_platform_device_count`` before the
first jax import. So every multi-device test runs its body in a
subprocess with the flag set in the environment, and reports its
results back over a one-line JSON protocol:

* the script under test prints **one ``json.dumps(...)`` object as its
  last stdout line** (anything before it — warnings, progress — is
  ignored);
* :func:`run_mesh_script` spawns the subprocess with ``devices``
  virtual CPU devices and the repo's ``src/`` on ``PYTHONPATH``,
  asserts a zero exit (surfacing the stderr tail on failure), and
  returns the decoded JSON.

Used by ``tests/test_distributed.py`` (training-side EP/decode
parity), ``tests/test_serving_sharded.py`` (mesh-sharded serving
token-exactness) and the serving conformance tiers
(``tests/test_serving_conformance.py``, ``tests/test_conformance_*.py``:
the conformance matrix + jit-compile-count regression).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

__all__ = ["SRC_PATH", "run_mesh_script"]

SRC_PATH = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src"))


def run_mesh_script(script: str, *, devices: int = 8,
                    timeout: float = 600, extra_env=None) -> dict:
    """Run ``script`` under ``devices`` virtual CPU devices; return the
    JSON object printed as its final stdout line."""
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=SRC_PATH + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""))
    if extra_env:
        env.update(extra_env)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, \
        f"mesh subprocess failed (exit {out.returncode}):\n" \
        f"{out.stderr[-2000:] or out.stdout[-2000:]}"
    lines = out.stdout.strip().splitlines()
    assert lines, f"mesh subprocess printed nothing:\n{out.stderr[-1000:]}"
    return json.loads(lines[-1])
