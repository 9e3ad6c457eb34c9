"""Transformer blocks: per-role (mixer x ffn/moe) assembly, pre-norm."""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import attention, ffn, mamba, norm, xlstm
from repro.moe import layer as moe_layer


def block_specs(cfg: ArchConfig, role: Dict, cross: bool = False):
    mixer = role["mixer"]
    s = {"mixer_norm": norm.specs(cfg.d_model, cfg.norm)}
    if mixer == "attn":
        s["mixer"] = attention.specs(cfg)
    elif mixer == "mamba":
        s["mixer"] = mamba.specs(cfg)
    elif mixer == "mlstm":
        s["mixer"] = xlstm.mlstm_specs(cfg)
    elif mixer == "slstm":
        s["mixer"] = xlstm.slstm_specs(cfg)
    else:
        raise ValueError(mixer)
    if cross:
        s["cross_norm"] = norm.specs(cfg.d_model, cfg.norm)
        s["cross"] = attention.cross_specs(cfg)
    if mixer in ("mlstm", "slstm"):
        return s                              # xLSTM blocks embed their FFN
    if role["moe"]:
        s["ffn_norm"] = norm.specs(cfg.d_model, cfg.norm)
        s["moe"] = moe_layer.specs(cfg)
        if cfg.moe.dense_residual and cfg.d_ff:
            s["ffn"] = ffn.specs(cfg.d_model, cfg.d_ff, cfg.gated_ffn)
    elif cfg.d_ff:
        s["ffn_norm"] = norm.specs(cfg.d_model, cfg.norm)
        s["ffn"] = ffn.specs(cfg.d_model, cfg.d_ff, cfg.gated_ffn)
    return s


def block_apply(params, x, *, cfg: ArchConfig, role: Dict, positions,
                mode: str = "train", cache: Optional[dict] = None,
                dist=None, positions3=None, enc_kv=None, causal=True):
    mixer = role["mixer"]
    aux = {"aux_loss": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}

    from repro.distributed.context import constrain
    # seq-parallel: residual stream + norms run sequence-sharded over TP;
    # attention/FFN boundaries gather (AR -> RS+AG, halves live bytes and
    # shrinks fp32 norm-backward chains by 1/tp)
    seq_ax = ("tp" if dist is not None and dist.seq_parallel
              and mode == "train" and x.shape[1] % max(1, getattr(
                  dist, "tp_size", 1)) == 0 else None)
    res_dims = ("dp", seq_ax) + (None,) * (x.ndim - 2)
    x = constrain(dist, x, res_dims)
    h = norm.apply(params["mixer_norm"], x, cfg.norm)
    if mixer == "attn":
        with jax.named_scope("attention"):
            if causal:
                mix, new_cache = attention.apply(
                    params["mixer"], h, cfg=cfg, positions=positions,
                    is_global=role["global_attn"], mode=mode, cache=cache,
                    positions3=positions3, dist=dist)
            else:                              # encoder self-attention
                q, k, v = attention._proj_qkv(params["mixer"], h, cfg)
                out = attention.flash_attention(q, k, v, causal=False)
                mix = jnp.einsum("bshe,hed->bsd", out,
                                 params["mixer"]["w_o"].astype(h.dtype))
                new_cache = None
    elif mixer == "mamba":
        mix, new_cache = mamba.apply(params["mixer"], h, cfg=cfg, mode=mode,
                                     cache=cache)
    elif mixer == "mlstm":
        mix, new_cache = xlstm.mlstm_apply(params["mixer"], h, cfg=cfg,
                                           mode=mode, cache=cache)
    elif mixer == "slstm":
        mix, new_cache = xlstm.slstm_apply(params["mixer"], h, cfg=cfg,
                                           mode=mode, cache=cache)
    else:
        raise ValueError(mixer)
    x = constrain(dist, x + mix, res_dims)

    if enc_kv is not None:                     # enc-dec cross attention
        h = norm.apply(params["cross_norm"], x, cfg.norm)
        with jax.named_scope("attention"):
            x = x + attention.apply_cross(params["cross"], h, enc_kv,
                                          cfg=cfg)

    if mixer in ("mlstm", "slstm"):
        return x, aux, new_cache

    if role["moe"]:
        h = norm.apply(params["ffn_norm"], x, cfg.norm)
        moe_out, moe_aux = moe_layer.apply(params["moe"], h, cfg=cfg,
                                           dist=dist, mode=mode)
        if cfg.moe.dense_residual and cfg.d_ff:
            moe_out = moe_out + ffn.apply(params["ffn"], h, act=cfg.ffn_act,
                                          gated=cfg.gated_ffn, dist=dist)
        x = x + moe_out
        aux = {k: aux[k] + moe_aux[k] for k in aux}
    elif cfg.d_ff:
        h = norm.apply(params["ffn_norm"], x, cfg.norm)
        x = x + ffn.apply(params["ffn"], h, act=cfg.ffn_act,
                          gated=cfg.gated_ffn, dist=dist)
    return constrain(dist, x, res_dims), aux, new_cache
