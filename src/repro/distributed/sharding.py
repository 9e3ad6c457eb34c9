"""Logical-axis sharding rules with divisibility-aware fallback.

Every parameter spec carries logical axis names ("embed", "mlp",
"experts", ...). Rules map logical names to mesh axes per shape *kind*
(train / prefill / decode); ``spec_for`` drops an axis whenever the dim
size is not divisible by the mesh extent (recorded so the dry-run can log
fallbacks, e.g. arctic's 56 q-heads vs the 16-way model axis).

Layout summary (DESIGN §4):
  train   — batch over dp=(pod,data); experts/mlp/heads/vocab over model;
            d_model dim of params over dp (FSDP / ZeRO-3 gather-at-use).
  prefill — like train, FSDP only for >=30B models.
  decode  — KV-cache sequence over model (flash-decode style); for
            global_batch=1 (long_500k) over (data, model).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import module

__all__ = ["AxisVal", "Rules", "batch_shardings", "cache_shardings",
           "make_rules", "param_shardings"]

AxisVal = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass
class Rules:
    table: Dict[str, AxisVal]
    mesh: Mesh
    fallbacks: List[str] = dataclasses.field(default_factory=list)

    def _extent(self, val: AxisVal) -> int:
        if val is None:
            return 1
        axes = (val,) if isinstance(val, str) else val
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def spec_for(self, shape: Tuple[int, ...],
                 axes: Tuple[Optional[str], ...], tag: str = "") -> P:
        entries: List[AxisVal] = []
        used: set = set()
        for dim, name in zip(shape, axes):
            val = self.table.get(name) if name else None
            if val is not None:
                flat = (val,) if isinstance(val, str) else tuple(val)
                flat = tuple(a for a in flat if a not in used)
                val = flat if len(flat) > 1 else (flat[0] if flat else None)
            if val is not None and dim % self._extent(val) != 0:
                self.fallbacks.append(
                    f"{tag}:{name}={dim} !% {val}({self._extent(val)})")
                val = None
            if val is not None:
                flat = (val,) if isinstance(val, str) else tuple(val)
                used.update(flat)
            entries.append(val)
        return P(*entries)

    def sharding_for(self, shape, axes, tag: str = "") -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(shape, axes, tag))


def make_rules(mesh: Mesh, kind: str, *, fsdp: bool = True,
               seq_shard_cache: AxisVal = "model") -> Rules:
    names = mesh.axis_names
    dp: Tuple[str, ...] = tuple(a for a in names if a in ("pod", "data"))
    tp = "model"
    zero = dp if fsdp else None
    # Role-aware rules: contracting dims are NEVER dp-sharded (that would
    # turn FSDP gathers into activation partial-sum all-reduces — observed
    # 10 GiB all-reduces before this split); output dims carry the dp
    # (ZeRO) component, TP dims carry "model".
    table: Dict[str, AxisVal] = {
        "vocab": tp,
        "mlp": (tp,) + dp if fsdp else tp,       # w_up/w_gate output dim
        "mlp_c": tp,                             # w_down contracting dim
        "expert_mlp": zero,                      # dim0 already uses model
        "expert_mlp_c": None,
        "experts": tp,
        "heads": tp,
        "kv_heads": tp,
        "inner": (tp,) + dp if fsdp else tp,     # mamba/xlstm up outputs
        "inner_c": tp,
        "embed": None,                           # contracting / residual
        "embed_out": zero,                       # w_o/w_down output dim
        "kv_lora": None,
        "head_dim": None,
        "layers": None,
        "batch": dp,
        "seq": None,
        "cache_seq": seq_shard_cache if kind == "decode" else None,
        "enc_seq": None,
    }
    return Rules(table=table, mesh=mesh)


# ---------------------------------------------------------------------------
# Tree-level helpers
# ---------------------------------------------------------------------------

def param_shardings(cfg: ArchConfig, rules: Rules, model):
    """NamedSharding tree matching the abstract param tree."""
    specs = model.specs_tree(cfg)
    flat, treedef = jax.tree_util.tree_flatten(specs,
                                               is_leaf=module.is_spec)
    out = [rules.sharding_for(s.shape, s.axes) for s in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(cfg: ArchConfig, shape: ShapeConfig, rules: Rules,
                    abstract_batch):
    def spec(path_name, leaf):
        nd = len(leaf.shape)
        if path_name == "positions3":           # [3, B, S]
            axes = (None, "batch", None)
        elif nd == 2:                            # tokens/labels [B, S]
            axes = ("batch", "seq")
        elif nd == 3:                            # frames/embeds [B, S, M]
            axes = ("batch", "seq", None)
        else:
            axes = (None,) * nd
        return rules.sharding_for(leaf.shape, axes, path_name)
    return {k: spec(k, v) for k, v in abstract_batch.items()}


def cache_shardings(cfg: ArchConfig, rules: Rules, abstract_cache):
    """Sharding tree for the decode cache (structure-matched)."""
    a = cfg.attn

    def leaf_spec(path: str, leaf):
        nd = len(leaf.shape)
        # stacked caches have leading num_periods dim
        if path.endswith("/len") or path.endswith("pos") or nd == 0:
            return rules.sharding_for(leaf.shape, (None,) * nd, path)
        if "/k" in path or "/v" in path:
            if nd == 5:       # [n, B, T, K, D]
                axes = ("layers", "batch", "cache_seq", "kv_heads", None)
            else:             # cross cache or unstacked
                axes = ("layers", "batch", "enc_seq", "kv_heads", None)[:nd]
            return rules.sharding_for(leaf.shape, axes, path)
        if path.endswith("c_kv") or path.endswith("k_rope"):
            axes = ("layers", "batch", "cache_seq", None)[:nd]
            return rules.sharding_for(leaf.shape, axes, path)
        if path.endswith("conv"):
            axes = ("layers", "batch", None, "inner")[:nd]
            return rules.sharding_for(leaf.shape, axes, path)
        if path.endswith("ssm"):
            axes = ("layers", "batch", "inner", None)[:nd]
            return rules.sharding_for(leaf.shape, axes, path)
        # xlstm states c/n/m/h: replicate heads (small)
        axes = ("layers", "batch") + (None,) * (nd - 2)
        return rules.sharding_for(leaf.shape, axes[:nd], path)

    paths_leaves = jax.tree_util.tree_flatten_with_path(abstract_cache)
    flat, treedef = paths_leaves
    out = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        out.append(leaf_spec(path, leaf))
    return jax.tree_util.tree_unflatten(treedef, out)
