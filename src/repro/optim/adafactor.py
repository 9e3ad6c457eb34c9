"""Adafactor (factored second moment, no first moment) — the memory-lean
optimizer for the 100B+ configs: state is O(rows + cols) per matrix
instead of O(rows x cols) (~0.5 bytes/param amortized vs 8 for Adam).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdafactorConfig:
    lr: float = 1e-3
    decay: float = 0.8          # beta2_t = 1 - step^-decay
    eps: float = 1e-30
    clip_threshold: float = 1.0
    weight_decay: float = 0.0


def _factored(shape) -> bool:
    return len(shape) >= 2


def init(params, cfg: AdafactorConfig):
    def state_like(p):
        if _factored(p.shape):
            return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                    jnp.float32)}
        return {"v": jnp.zeros(p.shape, jnp.float32)}
    return {"factored": jax.tree_util.tree_map(state_like, params),
            "count": jnp.zeros((), jnp.int32)}


def abstract_state(abstract_params, cfg: AdafactorConfig):
    def like(p):
        if _factored(p.shape):
            return {"vr": jax.ShapeDtypeStruct(p.shape[:-1], jnp.float32),
                    "vc": jax.ShapeDtypeStruct(p.shape[:-2] + p.shape[-1:],
                                               jnp.float32)}
        return {"v": jax.ShapeDtypeStruct(p.shape, jnp.float32)}
    return {"factored": jax.tree_util.tree_map(like, abstract_params),
            "count": jax.ShapeDtypeStruct((), jnp.int32)}


def update(grads, state, params, cfg: AdafactorConfig, lr_scale=1.0):
    with jax.named_scope("optimizer"):
        return _update(grads, state, params, cfg, lr_scale)


def _update(grads, state, params, cfg: AdafactorConfig, lr_scale):
    count = state["count"] + 1
    beta2 = 1.0 - count.astype(jnp.float32) ** (-cfg.decay)

    def upd(g, s, p):
        g = g.astype(jnp.float32)
        g2 = g * g + cfg.eps
        if _factored(p.shape):
            vr = beta2 * s["vr"] + (1 - beta2) * g2.mean(axis=-1)
            vc = beta2 * s["vc"] + (1 - beta2) * g2.mean(axis=-2)
            denom = vr.mean(axis=-1, keepdims=True)
            r = (vr / jnp.maximum(denom, cfg.eps))[..., None]
            u = g * jax.lax.rsqrt(jnp.maximum(r, cfg.eps)) \
                * jax.lax.rsqrt(jnp.maximum(vc[..., None, :], cfg.eps))
            new_s = {"vr": vr, "vc": vc}
        else:
            v = beta2 * s["v"] + (1 - beta2) * g2
            u = g * jax.lax.rsqrt(jnp.maximum(v, cfg.eps))
            new_s = {"v": v}
        # update clipping (RMS)
        rms = jnp.sqrt(jnp.mean(u * u) + 1e-30)
        u = u / jnp.maximum(1.0, rms / cfg.clip_threshold)
        step = cfg.lr * lr_scale * u
        if cfg.weight_decay:
            step = step + cfg.lr * lr_scale * cfg.weight_decay \
                * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - step).astype(p.dtype), new_s

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_s = treedef.flatten_up_to(state["factored"])
    out = [upd(g, s, p) for g, s, p in zip(flat_g, flat_s, flat_p)]
    new_p = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    new_s = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return new_p, {"factored": new_s, "count": count}
