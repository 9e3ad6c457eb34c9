"""Mesh construction and the CPU virtual-device re-exec.

:func:`make_mesh` builds meshes with ``Auto`` axis types: the sharding
rules (``distributed.sharding``) and the activation constraints
(``DistContext.constrain``) are written for GSPMD propagation, while
``jax.make_mesh`` defaults to ``Explicit`` axes.

:func:`ensure_host_device_count` is the CPU virtual-device shim the
multi-device CLIs (``launch/serve --devices``,
``benchmarks/serving.py --devices``) use to re-exec themselves with
``--xla_force_host_platform_device_count`` when asked for more devices
than are attached.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["ensure_host_device_count", "make_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` over the first ``prod(shape)`` devices, with
    ``Auto`` axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axes))


def ensure_host_device_count(n: int) -> None:
    """Re-exec the current CLI with ``n`` virtual CPU devices.

    XLA only honours ``--xla_force_host_platform_device_count`` before
    the first jax import, which has already happened by the time a CLI
    parses ``--devices N``. When fewer than ``n`` devices are attached
    (and the backend is CPU), re-exec the same argv with the flag added
    to ``XLA_FLAGS`` and exit with the child's status. No-op when
    enough devices already exist; raises if a re-exec already happened.

    The refusal of non-CPU backends is what makes the re-exec safe:
    this process has already initialised its backend, and on an
    accelerator it holds the chip until it exits, so a child that
    needed the chip would fail or hang. Accelerators cannot be
    conjured anyway — the raise names how many are attached.
    """
    import os
    import subprocess
    import sys

    if n <= 1 or jax.device_count() >= n:
        return
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"need {n} devices but only {jax.device_count()} "
            f"{jax.default_backend()} device(s) are attached")
    if os.environ.get("_REPRO_HOST_DEVICE_REEXEC"):
        raise RuntimeError(
            f"still only {jax.device_count()} devices after re-exec "
            f"with --xla_force_host_platform_device_count={n}")
    env = dict(os.environ, _REPRO_HOST_DEVICE_REEXEC="1")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()
    raise SystemExit(
        subprocess.call([sys.executable] + sys.argv, env=env))
