"""RMSNorm / LayerNorm (pre-norm convention, fp32 statistics)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.module import Spec


def specs(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": Spec((d,), ("embed",), "ones")}
    if kind == "layernorm":
        return {"scale": Spec((d,), ("embed",), "ones"),
                "bias": Spec((d,), ("embed",), "zeros")}
    raise ValueError(kind)


def apply(params, x, kind: str, eps: float = 1e-6):
    with jax.named_scope("norm"):
        return _apply(params, x, kind, eps)


def _apply(params, x, kind: str, eps: float):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        # stats in fp32 (stability); the normalized value is cast back to
        # the compute dtype BEFORE the scale so backward keeps one fp32
        # [B,S,M] intermediate instead of a chain of them
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = (x32 * (var + eps) ** -0.5).astype(dtype)
        return y * params["scale"].astype(dtype)
    if kind == "layernorm":
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = ((x32 - mean) * (var + eps) ** -0.5).astype(dtype)
        return (y * params["scale"].astype(dtype)
                + params["bias"].astype(dtype))
    raise ValueError(kind)
