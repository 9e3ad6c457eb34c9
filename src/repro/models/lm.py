"""Decoder-only language model: init / forward / loss / decode.

Depth is executed as ``lax.scan`` over *layer periods* (the repeating
heterogeneous pattern — e.g. Jamba's 8-layer mamba/attn block, gemma3's
5:1 local:global). HLO size is O(period), not O(depth): an 80-layer model
compiles as fast as a 2-period one — essential for the 512-device dry-run.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks, kv_cache, module
from repro.models.layers import embedding, norm


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def specs_tree(cfg: ArchConfig):
    roles = cfg.layer_roles()
    period = {f"l{i}": blocks.block_specs(cfg, role)
              for i, role in enumerate(roles)}
    return {
        "embed": embedding.specs(cfg),
        "periods": module.stack(period, cfg.num_periods),
        "final_norm": norm.specs(cfg.d_model, cfg.norm),
    }


def init(cfg: ArchConfig, key):
    return module.build(specs_tree(cfg), key)


def abstract_params(cfg: ArchConfig):
    return module.abstract(specs_tree(cfg))


def param_axes(cfg: ArchConfig):
    return module.axes_of(specs_tree(cfg))


def count_params(cfg: ArchConfig, active_only: bool = False) -> int:
    total = module.count(specs_tree(cfg))
    if active_only and cfg.moe is not None:
        from repro.moe import experts as E
        per_layer = module.count(E.specs(cfg))
        n_moe = cfg.num_periods * sum(r["moe"] for r in cfg.layer_roles())
        inactive = 1.0 - cfg.moe.top_k / cfg.moe.num_experts
        total -= int(n_moe * per_layer * inactive)
    return total


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _remat_wrap(fn, cfg: ArchConfig):
    if cfg.remat_policy == "nothing":
        return fn
    if cfg.remat_policy == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    raise ValueError(cfg.remat_policy)


def forward(params, batch, cfg: ArchConfig, *, mode: str = "train",
            cache: Optional[dict] = None, dist=None,
            use_kernel: bool = False):
    """Returns (logits, aux, new_cache)."""
    dt = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    b = tokens.shape[0]
    # paged (serving engine) caches carry a page table + per-sequence
    # lengths instead of a dense [B, max_len] block + scalar pos
    paged = cache is not None and "page_table" in cache

    if mode == "decode":
        if paged:
            positions = cache["lens"][:, None]           # per-slot [B, 1]
        else:
            pos0 = cache["pos"]
            positions = jnp.broadcast_to(pos0[None, None], (b, 1))
    else:
        positions = None  # filled after embeds are known

    with jax.named_scope("embed"):
        x = embedding.embed(params["embed"], tokens, cfg,
                            positions=positions if mode == "decode" else None,
                            dtype=dt)
    if batch.get("embeds") is not None:
        x = jnp.concatenate([batch["embeds"].astype(dt), x], axis=1)
    s = x.shape[1]

    if mode != "decode":
        base = jnp.arange(s)[None, :]
        if paged:                          # chunked prefill at an offset
            positions = cache["lens"][:, None] + base
        else:
            positions = jnp.broadcast_to(base, (b, s))
        if cfg.positional == "learned":
            with jax.named_scope("embed"):
                x = x + params["embed"]["pos"][positions[0]].astype(dt)[None]
    positions3 = batch.get("positions3")

    roles = cfg.layer_roles()
    shared_kv = ({"page_table": cache["page_table"], "lens": cache["lens"],
                  "write_valid": cache.get("write_valid"),
                  "write_sink": cache.get("write_sink"),
                  # trace-static decode attention selector (str) + pool
                  # layout flag (bool) — merged into per-layer caches as
                  # plain Python values, invisible to the scanned pytree
                  "attn_kernel": cache.get("attn_kernel"),
                  "kv_sharded": cache.get("kv_sharded")}
                 if paged else None)
    # serving caches for recurrent mixers are slot-indexed [slots, ...]
    # state (no paging); chunked prefill (B == 1) works on one slot's
    # row, selected by ``cache["slot"]`` ``[1]``
    slot = cache.get("slot") if paged else None

    def period_body(carry, xs):
        x, aux = carry
        pparams, pcache = xs
        new_pcache = {} if pcache is not None else None
        for i, role in enumerate(roles):
            lcache = pcache.get(f"l{i}") if pcache is not None else None
            recurrent = (shared_kv is not None and lcache is not None
                         and role["mixer"] != "attn")
            full = lcache
            if recurrent and slot is not None:   # chunked prefill: B == 1
                lcache = jax.tree_util.tree_map(
                    lambda f: jax.lax.dynamic_slice_in_dim(f, slot[0], 1,
                                                           axis=0), full)
            if shared_kv is not None and lcache is not None:
                lcache = dict(lcache, **{k: v for k, v in shared_kv.items()
                                         if v is not None})
            x, a, nc = blocks.block_apply(
                pparams[f"l{i}"], x, cfg=cfg, role=role,
                positions=positions, mode=mode, cache=lcache, dist=dist,
                positions3=positions3)
            aux = jax.tree_util.tree_map(jnp.add, aux, a)
            if new_pcache is not None:
                if recurrent and nc is not None:
                    if slot is not None:
                        # write the one slot's updated row back in place
                        nc = jax.tree_util.tree_map(
                            lambda f, n: jax.lax.dynamic_update_slice_in_dim(
                                f, n.astype(f.dtype), slot[0], axis=0),
                            full, nc)
                    else:
                        # batched decode over all slots: freeze the state
                        # of inactive (finished / mid-prefill) slots —
                        # the garbage computed for them is finite but
                        # must never stick
                        act = shared_kv.get("write_valid")
                        if act is not None:
                            keep = act[:, 0]
                            nc = {k_: jnp.where(
                                keep.reshape((-1,) + (1,) * (v.ndim - 1)),
                                v.astype(full[k_].dtype), full[k_])
                                for k_, v in nc.items()}
                new_pcache[f"l{i}"] = nc if nc is not None else lcache
        return (x, aux), new_pcache

    aux0 = {"aux_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32)}
    layer_cache = cache["layers"] if cache is not None else None
    body = _remat_wrap(period_body, cfg) if mode == "train" else period_body
    if layer_cache is not None:
        (x, aux), new_layers = jax.lax.scan(
            body, (x, aux0), (params["periods"], layer_cache))
    else:
        (x, aux), _ = jax.lax.scan(
            lambda c, p: (body(c, (p, None))[0], None),
            (x, aux0), params["periods"])
        new_layers = None

    x = norm.apply(params["final_norm"], x, cfg.norm)
    with jax.named_scope("lm_head"):
        logits = embedding.logits(params["embed"], x, cfg)
        if dist is not None:
            logits = dist.constrain(logits, ("dp", None, "tp"))

    new_cache = None
    if cache is not None:
        if paged:
            # page_table / lens are host-managed by the serving engine;
            # only the device pools flow through the step
            new_cache = {"layers": new_layers}
        else:
            new_pos = (cache["pos"] + 1 if mode == "decode"
                       else jnp.asarray(s, jnp.int32))
            new_cache = {"layers": new_layers, "pos": new_pos}
    return logits, aux, new_cache


# ---------------------------------------------------------------------------
# Loss / steps
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels):
    """Sharding-friendly CE: the label logit is extracted with a masked
    reduction (fusible; partial-sums over a model-sharded vocab become one
    tiny [B,S] all-reduce) instead of take_along_axis (whose backward is a
    scatter-add that forced an 8 GiB all-gather of d(logits))."""
    valid = labels >= 0
    lab = jnp.where(valid, labels, 0)
    x = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(x.max(axis=-1, keepdims=True))
    shifted = x - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(vocab_iota == lab[..., None], shifted, 0.0), axis=-1)
    nll = lse - label_logit
    denom = jnp.maximum(valid.sum(), 1)
    return jnp.where(valid, nll, 0.0).sum() / denom


def loss_fn(params, batch, cfg: ArchConfig, dist=None,
            use_kernel: bool = False):
    logits, aux, _ = forward(params, batch, cfg, mode="train", dist=dist,
                             use_kernel=use_kernel)
    labels = batch["labels"]
    # logits cover (embeds + tokens); labels align with the LAST S_text
    # positions (stub-embeds positions carry label -1 = masked anyway)
    with jax.named_scope("lm_head"):
        logits = logits[:, -labels.shape[1]:]
        ce = cross_entropy(logits, labels)
    loss = ce + aux["aux_loss"] + aux["z_loss"]
    return loss, {"ce": ce, "aux_loss": aux["aux_loss"],
                  "z_loss": aux["z_loss"], "loss": loss}


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, abstract: bool = False):
    layers = kv_cache.init_cache(cfg, batch, max_len, dtype,
                                 abstract=abstract)
    cross = None
    if isinstance(layers, dict) and "cross" in layers:
        cross = layers.pop("cross")
    out = {"layers": layers,
           "pos": (jax.ShapeDtypeStruct((), jnp.int32) if abstract
                   else jnp.zeros((), jnp.int32))}
    if cross is not None:
        out["cross"] = cross
    return out


def decode_step(params, cache, tokens, cfg: ArchConfig, dist=None):
    logits, _, new_cache = forward(params, {"tokens": tokens}, cfg,
                                   mode="decode", cache=cache, dist=dist)
    return logits[:, -1], new_cache


def prefill(params, batch, cfg: ArchConfig, max_len: int, dist=None,
            dtype=jnp.bfloat16):
    cache = init_cache(cfg, batch["tokens"].shape[0], max_len, dtype)
    logits, _, new_cache = forward(params, batch, cfg, mode="prefill",
                                   cache=cache, dist=dist)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged KV path (serving engine, repro.serve)
#
# Both steps return *logits* (the last real position's row), leaving the
# token choice — greedy argmax or the masked temperature/top-k/top-p
# sampler in repro.serve.sampling — to the engine's jitted step bodies,
# so one compiled program serves every per-request sampling setting.
#
# ``dist`` (mesh-sharded serving) threads the engine's DistContext down
# to the MoE layers: prefill chunks then run pipelined_moe's sharded
# layout (tokens split over EP, real All-to-Alls) and decode the
# replicated psum-combine layout — selected by mode alone, no separate
# code path.
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, abstract: bool = False):
    """Global page pools shared by every in-flight sequence (stacked over
    periods like :func:`init_cache`)."""
    return kv_cache.init_paged_pools(cfg, num_pages, page_size, dtype,
                                     abstract=abstract)


def decode_step_paged(params, pools, page_table, lens, tokens,
                      cfg: ArchConfig, active=None, dist=None,
                      write_sink=None, attn_kernel=None,
                      kv_sharded=False):
    """One decode step over the whole continuous batch.

    pools: paged cache tree; page_table ``[slots, NP]``; lens ``[slots]``
    (tokens cached per slot); tokens ``[slots, 1]``; ``active`` masks
    finished / mid-prefill slots so their KV writes land in the reserved
    sink page — page 0, or per-slot ``write_sink`` ``[slots]`` when each
    DP shard reserves its own sink. ``attn_kernel`` (trace-static:
    ``"pallas"`` or ``"gather"``/None) selects the fused paged-attention
    kernel vs the gather baseline; ``kv_sharded`` tells the kernel the
    pools are page-sharded over the dp axis. Returns (last-token logits
    ``[slots, vocab]``, new pools).
    """
    cache = {"layers": pools, "page_table": page_table, "lens": lens,
             "attn_kernel": attn_kernel, "kv_sharded": kv_sharded}
    if active is not None:
        cache["write_valid"] = active[:, None]
    if write_sink is not None:
        cache["write_sink"] = write_sink
    logits, _, new_cache = forward(params, {"tokens": tokens}, cfg,
                                   mode="decode", cache=cache, dist=dist)
    return logits[:, -1], new_cache["layers"]


def prefill_chunk_paged(params, pools, page_table, pos0, tokens, valid_len,
                        cfg: ArchConfig, dist=None, write_sink=None,
                        slot=None):
    """One chunked-prefill step for a single sequence.

    tokens ``[1, C]`` (bucket-padded); page_table ``[1, NP]``; pos0
    ``[1]`` = tokens already prefilled; valid_len scalar = real (unpadded)
    tokens in this chunk; ``write_sink`` ``[1]`` = the sink page masked
    writes redirect to (the request's DP shard's own sink under
    ``kv_sharding="dp"``; page 0 otherwise); ``slot`` ``[1]`` = the
    request's slot index — required when the model has recurrent mixers,
    whose slot-indexed state rows this chunk reads and writes in place
    (attention-only models have no per-slot state in the pools and may
    omit it). Pad positions' KV writes are masked and their logits
    discarded. Returns (logits at the last real token ``[1, vocab]``,
    new pools).
    """
    c = tokens.shape[1]
    write_valid = jnp.arange(c)[None, :] < valid_len
    cache = {"layers": pools, "page_table": page_table, "lens": pos0,
             "write_valid": write_valid}
    if write_sink is not None:
        cache["write_sink"] = write_sink
    if slot is not None:
        cache["slot"] = slot
    logits, _, new_cache = forward(params, {"tokens": tokens}, cfg,
                                   mode="prefill", cache=cache, dist=dist)
    last = jax.lax.dynamic_slice_in_dim(
        logits, jnp.maximum(valid_len - 1, 0), 1, axis=1)
    return last[:, 0], new_cache["layers"]
