"""Distribution context threaded through model apply functions.

:class:`DistContext` bundles the device mesh with the logical-axis
assignment (which mesh axes carry data, expert and tensor parallelism)
and provides divisibility-aware activation sharding constraints. It is
the single object the model stack consumes — layers never look at the
mesh directly.

Serving: :func:`make_serving_context` builds the dp x ep mesh the
continuous-batching engine (``repro.serve``) runs on — expert-parallel
prefill through ``pipelined_moe``'s ``sharded`` layout, replicated
psum-combine decode. Meshes come from ``repro.compat.make_mesh``
(``Auto`` axis types).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax

__all__ = ["DistContext", "constrain", "ep_split", "make_serving_context"]


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: "jax.sharding.Mesh"
    dp_axes: Tuple[str, ...] = ("data",)
    ep_axis: Optional[str] = "model"      # expert-parallel mesh axis
    tp_axis: Optional[str] = "model"      # tensor-parallel mesh axis
    seq_parallel: bool = False            # residual stream seq-sharded
                                          # over tp (Korthikanti-style)

    @property
    def ep_size(self) -> int:
        if self.ep_axis is None:
            return 1
        return self.mesh.shape[self.ep_axis]

    @property
    def tp_size(self) -> int:
        if self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        out = 1
        for a in self.dp_axes:
            out *= self.mesh.shape[a]
        return out

    def named_sharding(self, *dims: Optional[str]):
        """:class:`NamedSharding` over the mesh for logical per-axis
        roles — one entry per array dimension, each ``'dp' | 'ep' |
        'tp' | None``. This is the placement-side sibling of
        :meth:`constrain` (which hints activations *inside* a jitted
        program): use it for ``device_put`` of step *inputs* so every
        host array enters jit with one committed layout. The serving
        engine's DP-sharded KV pools place through it
        (``serve.paged_kv``: pools ``(None, 'dp')`` over the page axis,
        page tables ``('dp', None)`` over the slot axis)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        entries = []
        for d in dims:
            if d == "dp":
                ax = self.dp_axes
                entries.append(ax if len(ax) > 1 else ax[0])
            elif d == "ep":
                entries.append(self.ep_axis)
            elif d == "tp":
                entries.append(self.tp_axis)
            else:
                entries.append(None)
        return NamedSharding(self.mesh, P(*entries))

    def constrain(self, x, dims: Tuple[Optional[str], ...]):
        """Activation sharding constraint. dims entries: 'dp' | 'tp' |
        None. Drops an entry when the dim isn't divisible (e.g. batch=1
        at long-context decode). Without constraints, GSPMD propagates
        FSDP weight shardings into activations and replicates the batch —
        constraints force gather-at-use (ZeRO) semantics instead."""
        import jax
        from jax.sharding import PartitionSpec as P
        entries = []
        for size, d in zip(x.shape, dims):
            ax = (self.dp_axes if d == "dp"
                  else (self.tp_axis,) if d == "tp" and self.tp_axis
                  else None)
            if ax:
                ext = 1
                for a in ax:
                    ext *= self.mesh.shape[a]
                if ext == 0 or size % ext != 0:
                    ax = None
            entries.append(ax if ax is None or len(ax) > 1 else ax[0])
        return jax.lax.with_sharding_constraint(x, P(*entries))


def constrain(dist: Optional[DistContext], x, dims):
    """Module-level convenience: no-op when ``dist`` is None."""
    return x if dist is None else dist.constrain(x, dims)


def ep_split(devices: int, num_experts: int) -> Tuple[int, int]:
    """Factor ``devices`` into ``(dp, ep)`` for serving.

    ``ep`` is the largest divisor of ``devices`` that also divides
    ``num_experts`` (every device must own the same number of whole
    experts); the rest of the machine becomes data parallelism. Dense
    models (``num_experts == 0``) get ``ep = 1``.
    """
    assert devices >= 1
    ep = 1
    if num_experts > 0:
        for d in range(min(devices, num_experts), 0, -1):
            if devices % d == 0 and num_experts % d == 0:
                ep = d
                break
    return devices // ep, ep


def make_serving_context(devices: int, *,
                         num_experts: int = 0) -> Optional[DistContext]:
    """Mesh + context for mesh-sharded serving (``repro.serve``).

    Builds a ``(data=dp, model=ep)`` mesh over the first ``devices``
    jax devices (``repro.compat.make_mesh``) and returns a
    :class:`DistContext` with ``ep_axis="model"`` (expert parallelism
    only — ``tp_axis`` is None so attention stays unsharded and the
    paged-KV pools replicate). Returns None for ``devices <= 1`` — the
    caller's single-device path.
    """
    if devices <= 1:
        return None
    avail = len(jax.devices())
    if avail < devices:
        raise RuntimeError(
            f"serving mesh needs {devices} devices but jax sees {avail}; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{devices} before the first jax import (the serve CLI and "
            f"benchmarks/serving.py re-exec themselves to do this)")
    from repro.compat import make_mesh
    dp, ep = ep_split(devices, num_experts)
    mesh = make_mesh((dp, ep), ("data", "model"))
    return DistContext(mesh=mesh, dp_axes=("data",),
                       ep_axis="model" if ep > 1 else None,
                       tp_axis=None)
