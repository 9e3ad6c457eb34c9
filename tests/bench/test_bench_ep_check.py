"""The training check on an expert-parallel mesh of four virtual CPU
devices (in a child process, which alone sees four): a sound run is
correct, and a run whose All-to-All exchange is left out is not. The
cell is the one-chip training cell's file with a ``data=1 x model=4``
mesh, at the tiny size."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import tiny

CHILD = r"""
import json, sys
sys.path.insert(0, {here!r})
import tiny
from bench import spec
spec.program_config = tiny.program_config
if sys.argv[1] == "no_exchange":
    import jax
    jax.lax.all_to_all = lambda x, *a, **k: x
from bench.drivers import train
ctx = tiny.context("train.bertl.1chip", seed=5, seconds=0.5, batch=8,
                   seq=32, mesh={{"data": 1, "model": 4}})
ctx.cell["chips"] = 4
ok = train.run(ctx)
print(json.dumps({{"ok": bool(ok), "devices": len(ctx.devices),
                  "checks": ctx.checks}}))
"""


def _child(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(here=os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code, mode], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=tiny.ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_expert_parallel_run_is_correct():
    out = _child("sound")
    assert out["devices"] == 4 and out["ok"], out


def test_exchange_left_out_is_caught():
    out = _child("no_exchange")
    assert not out["ok"], out
