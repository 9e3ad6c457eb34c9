"""Dense feed-forward layers (gated SwiGLU-style or plain MLP).

The tensor names deliberately follow the paper's Fig. 1 dataflow: the
input is ``T_DI``-shaped, the post-GEMM1 hidden is ``T_M``, the output is
``T_DO``. ``checkpoint_name`` tags on ``t_m`` let remat policies drop or
offload exactly the tensors the paper's strategies S1–S4 manage.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.models.module import Spec

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def specs(d_model: int, d_ff: int, gated: bool):
    s = {
        "w_up": Spec((d_model, d_ff), ("embed", "mlp")),
        "w_down": Spec((d_ff, d_model), ("mlp_c", "embed_out")),
    }
    if gated:
        s["w_gate"] = Spec((d_model, d_ff), ("embed", "mlp"))
    return s


def apply(params, x, *, act: str = "silu", gated: bool = True, dist=None):
    with jax.named_scope("ffn"):
        return _apply(params, x, act, gated, dist)


def _apply(params, x, act: str, gated: bool, dist):
    from repro.distributed.context import constrain
    dt = x.dtype
    h = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt))
    h = constrain(dist, h, ("dp",) + (None,) * (h.ndim - 2) + ("tp",))
    if gated:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(dt))
        g = constrain(dist, g, ("dp",) + (None,) * (g.ndim - 2) + ("tp",))
        h = _ACTS[act](g) * h
    else:
        h = _ACTS[act](h)
    h = checkpoint_name(h, "t_m")
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(dt))
