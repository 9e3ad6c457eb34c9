"""AdamW (pure JAX) with optional 8-bit moment quantization.

Moment trees mirror the parameter tree, so GSPMD shards optimizer state
exactly like parameters (ZeRO-style when params are FSDP-sharded). The
8-bit variant stores m/v as int8 with a per-block fp32 scale (block =
last dim) — the 400B-class models (arctic, jamba) cannot fit fp32 Adam on
a single pod (DESIGN §8).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    quantize_moments: bool = False   # int8 moments + per-row scales


class Q8(NamedTuple):
    q: jax.Array       # int8 payload
    scale: jax.Array   # fp32 per-last-dim-block scale


def _quantize(x):
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    return Q8((x / scale).round().astype(jnp.int8), scale)


def _dequantize(q8: Q8):
    return q8.q.astype(jnp.float32) * q8.scale


def init(params, cfg: AdamWConfig):
    def zeros_like(p):
        z = jnp.zeros(p.shape, jnp.float32)
        return _quantize(z) if cfg.quantize_moments and p.ndim >= 2 else z
    return {
        "m": jax.tree_util.tree_map(zeros_like, params),
        "v": jax.tree_util.tree_map(zeros_like, params),
        "count": jnp.zeros((), jnp.int32),
    }


def abstract_state(abstract_params, cfg: AdamWConfig):
    def like(p):
        if cfg.quantize_moments and len(p.shape) >= 2:
            return Q8(jax.ShapeDtypeStruct(p.shape, jnp.int8),
                      jax.ShapeDtypeStruct(p.shape[:-1] + (1,), jnp.float32))
        return jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {
        "m": jax.tree_util.tree_map(like, abstract_params),
        "v": jax.tree_util.tree_map(like, abstract_params),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }


def update(grads, state, params, cfg: AdamWConfig, lr_scale=1.0):
    with jax.named_scope("optimizer"):
        return _update(grads, state, params, cfg, lr_scale)


def _update(grads, state, params, cfg: AdamWConfig, lr_scale):
    count = state["count"] + 1
    b1c = 1 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1 - cfg.b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m_f = _dequantize(m) if isinstance(m, Q8) else m
        v_f = _dequantize(v) if isinstance(v, Q8) else v
        m_new = cfg.b1 * m_f + (1 - cfg.b1) * g
        v_new = cfg.b2 * v_f + (1 - cfg.b2) * g * g
        mhat = m_new / b1c
        vhat = v_new / b2c
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - cfg.lr * lr_scale * step
                 ).astype(p.dtype)
        if isinstance(m, Q8):
            m_new, v_new = _quantize(m_new), _quantize(v_new)
        return p_new, m_new, v_new

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(g, m, v, p)
           for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}
