"""MoE layer: ties router + dispatch + MPipeMoE engine, and owns the
shard_map entry point for expert parallelism.

Layout contract (DESIGN §4): under a mesh, tokens enter sharded over
(dp-axes on batch, EP axis on sequence) — sequence-parallel MoE — so each
device contributes distinct tokens to the All-to-All. At decode (S=1)
tokens are replicated over EP and the combine is a psum instead.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.pipeline_moe import pipelined_moe
from repro.models.module import axes_of
from repro.moe import experts as E
from repro.moe import router as R


def specs(cfg: ArchConfig):
    s = {"router": R.specs(cfg), "experts": E.specs(cfg)}
    if cfg.moe.num_shared_experts:
        s["shared"] = E.shared_specs(cfg)
    return s


def _param_specs(cfg: ArchConfig, ep_axis: Optional[str],
                 dp_axes: Tuple[str, ...] = ()):
    """PartitionSpecs for the shard_map boundary: experts sharded over the
    EP axis on dim 0 AND kept dp-sharded (ZeRO-3) on their output dim —
    the body gathers them explicitly (see ``gather_expert_weights``), so
    the weight-grad reduction is one reduce-scatter. Router/shared stay
    replicated (tiny)."""
    def to_spec(axes, zero3: bool):
        entries = []
        for i, a in enumerate(axes):
            if a == "experts" and i == 0:
                entries.append(ep_axis)
            elif zero3 and dp_axes and i == len(axes) - 1:
                entries.append(dp_axes if len(dp_axes) > 1 else dp_axes[0])
            else:
                entries.append(None)
        return P(*entries)
    tree = axes_of(specs(cfg))
    out = {}
    for key, sub in tree.items():
        zero3 = key == "experts"
        out[key] = jax.tree_util.tree_map(
            lambda ax, z=zero3: to_spec(ax, z), sub,
            is_leaf=lambda x: isinstance(x, tuple))
    return out


def apply(params, x, *, cfg: ArchConfig, dist=None, mode: str = "train",
          use_kernel: bool = False) -> Tuple[jax.Array, dict]:
    """x: [B, S, M] -> ([B, S, M], aux)."""
    b, s, d = x.shape

    if dist is None or dist.ep_axis is None or dist.ep_size == 1:
        out, aux = pipelined_moe(params, x.reshape(b * s, d), cfg=cfg,
                                 ep_size=1, mode=mode,
                                 use_kernel=use_kernel)
        return out.reshape(b, s, d), aux

    mesh = dist.mesh
    ep_axis = dist.ep_axis
    ep_size = dist.ep_size
    dp = dist.dp_axes if b % max(1, dist.dp_size) == 0 else ()
    seq_shardable = mode != "decode" and s % ep_size == 0

    # ZeRO-3 expert weights: only when every expert tensor's last dim
    # divides the dp extent (divisibility fallback: replicate)
    dp_ext = 1
    for a_ in dist.dp_axes:
        dp_ext *= mesh.shape[a_]
    zero3_ok = (mode == "train" and dp_ext > 1
                and cfg.moe.d_expert % dp_ext == 0
                and d % dp_ext == 0)
    zero3_axes = dist.dp_axes if zero3_ok else ()

    x_spec = P(dp if dp else None, ep_axis if seq_shardable else None,
               None)
    p_specs = _param_specs(cfg, ep_axis, zero3_axes)

    # Pin the shard_map boundary: without this, GSPMD propagates the
    # seq-sharded in_spec *backward* into the surrounding layers, and a
    # sequence axis sharded over the EP axis miscompiles the recurrent
    # mixers on jax 0.4.x (the causal conv / chunked SSM scan partition
    # without the needed halo exchange — wrong *values*, not just a bad
    # layout; see tests/test_conformance_arch.py's jamba leg).
    # The explicit replicated constraint keeps the residual stream's
    # layout at the boundary and reshards only inside it.
    if seq_shardable:
        from jax.sharding import NamedSharding
        repl = NamedSharding(mesh, P(None, None, None))
        x = jax.lax.with_sharding_constraint(x, repl)

    # decode uses the replicated-token path: aux is invarying over the EP
    # axis there, so only reduce over the axes the value varies on
    reduce_axes = dp + ((ep_axis,) if seq_shardable else ())

    def body(p, xl):
        bl, sl, _ = xl.shape
        out, aux = pipelined_moe(
            p, xl.reshape(bl * sl, d), cfg=cfg, ep_axis=ep_axis,
            ep_size=ep_size, mode=mode, use_kernel=use_kernel,
            dp_axes=zero3_axes)
        if reduce_axes:
            aux = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, reduce_axes), aux)
        return out.reshape(bl, sl, d), aux

    out, aux = jax.shard_map(
        body, mesh=mesh, in_specs=(p_specs, x_spec),
        out_specs=(x_spec, P()))(params, x)
    if seq_shardable:
        out = jax.lax.with_sharding_constraint(out, repl)
    return out, aux
