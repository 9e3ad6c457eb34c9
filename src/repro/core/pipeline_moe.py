"""MPipeMoE execution engine: micro-batch pipelined expert parallelism.

This is the paper's core (§III-B..E), TPU-adapted:

* The local token batch is split into ``n`` micro-batches **along the
  token dimension** (paper Fig. 5b — each chunk's All-to-All remains a
  true all-to-all over the EP axis, never point-to-point).
* Chunks are processed by an *unrolled* Python loop: chunk bodies are
  data-independent, so XLA's latency-hiding scheduler overlaps chunk
  i+1's dispatch collective with chunk i's expert GEMMs (the paper's
  multi-CUDA-stream pipeline, expressed as async HLO collectives).
  ``pipeline_unroll=False`` switches to ``lax.scan`` (serial; useful to
  compare compile size / memory).
* Memory reuse: each chunk is wrapped in the strategy's remat/offload
  policy (``core.strategies``). Residuals ``t_di``/``t_m`` are tagged
  here; dropping them re-runs the dispatch A2A (re-communication) or
  GEMM1 (recompute) in backward — S1–S4 of Table II. With reuse enabled
  the per-chunk buffers are dead after the chunk's combine, so XLA's
  buffer assignment shares one allocation across chunks: the paper's
  m -> m/n "memory bubbles" compression.

Two distributed layouts:
* ``sharded``  (train/prefill): tokens sharded over dp x ep; full
  dispatch-A2A -> grouped FFN -> combine-A2A pipeline.
* ``replicated`` (decode): tokens replicated over the EP axis (batches at
  decode are far smaller than the mesh); each device computes only its
  local experts and the combine is a psum — no A2A on the critical path.

Both layouts are live at inference time: the serving engine
(``repro.serve``, see ``docs/distributed.md``) drives chunked prefill
through the ``sharded`` path and continuous-batch decode through
``replicated``, selected purely by ``mode`` — there is no separate
serving fork of this module.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ArchConfig
from repro.core.strategies import Strategy, wrap_chunk
from repro.moe import dispatch as D
from repro.moe import experts as E
from repro.moe import router as R

__all__ = ["capacity_for", "gather_expert_weights", "pipelined_moe"]


def capacity_for(tokens: int, top_k: int, cf: float, num_experts: int,
                 multiple: int = 8) -> int:
    cap = max(1, math.ceil(tokens * top_k * cf / num_experts))
    return -(-cap // multiple) * multiple


def _resolve_partitions(cfg: ArchConfig, t_local: int, mode: str) -> int:
    if mode == "decode" or not cfg.moe.pipeline:
        return 1
    n = cfg.moe.num_partitions or 4          # 0 = adaptive; default 4
    n = max(1, min(n, t_local))
    while t_local % n:
        n -= 1
    return n


def _chunk_fn(params, chunk, *, cfg: ArchConfig, ep_axis: Optional[str],
              ep_size: int, cap: int, use_kernel: bool):
    """route -> dispatch -> A2A -> expert FFN -> A2A -> combine, each
    stage under its ``moe.*`` device scope."""
    m = cfg.moe
    e_total = m.num_experts
    e_local = e_total // ep_size
    t = chunk.shape[0]

    with jax.named_scope("moe.route"):
        probs, eidx, aux = R.route(params["router"], chunk, cfg)
    with jax.named_scope("moe.dispatch"):
        dest, valid = D.dispatch_plan(eidx, e_total, cap)
        buf = D.dispatch(chunk, dest, e_total, cap)      # [E, cap, M]

    if ep_size > 1:
        with jax.named_scope("moe.a2a_dispatch"):
            buf = buf.reshape(ep_size, e_local, cap, -1)
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                     concat_axis=0)
            buf = buf.reshape(ep_size * e_local, cap, -1)    # src-major
    t_di = checkpoint_name(buf, "t_di")                  # paper's T_DI
    with jax.named_scope("moe.expert_ffn"):
        if ep_size > 1:
            # [ep(src), e_local, cap, M] -> [e_local, ep*cap, M]
            ein = t_di.reshape(ep_size, e_local, cap,
                               -1).transpose(1, 0, 2, 3)
            ein = ein.reshape(e_local, ep_size * cap, -1)
        else:
            ein = t_di
        eout = E.apply_grouped(params["experts"], ein, cfg,
                               use_kernel=use_kernel)    # paper's T_DO

    if ep_size > 1:
        with jax.named_scope("moe.a2a_combine"):
            eout = eout.reshape(e_local, ep_size, cap,
                                -1).transpose(1, 0, 2, 3)
            eout = jax.lax.all_to_all(eout, ep_axis, split_axis=0,
                                      concat_axis=0)
            eout = eout.reshape(e_total, cap, -1)
    with jax.named_scope("moe.combine"):
        out = D.combine(eout, dest, probs, t)

    if m.num_shared_experts:
        # always-on shared experts: dense, independent of the A2As —
        # XLA overlaps this compute with the in-flight collectives.
        with jax.named_scope("moe.expert_ffn"):
            out = out + E.apply_shared(params["shared"], chunk, cfg)
    return out, aux


def _replicated_decode(params, tokens, *, cfg: ArchConfig,
                       ep_axis: Optional[str], ep_size: int,
                       use_kernel: bool):
    """Decode path: tokens replicated over EP; combine via psum."""
    m = cfg.moe
    e_total = m.num_experts
    e_local = e_total // ep_size
    t = tokens.shape[0]
    cap = capacity_for(t, m.top_k, max(m.capacity_factor, 2.0), e_total)

    with jax.named_scope("moe.route"):
        probs, eidx, aux = R.route(params["router"], tokens, cfg)
    with jax.named_scope("moe.dispatch"):
        dest, valid = D.dispatch_plan(eidx, e_total, cap)
        buf = D.dispatch(tokens, dest, e_total, cap)     # [E, cap, M]
        if ep_size > 1:
            my = jax.lax.axis_index(ep_axis)
            local = jax.lax.dynamic_slice_in_dim(buf, my * e_local,
                                                 e_local, 0)
        else:
            local = buf
    with jax.named_scope("moe.expert_ffn"):
        eout = E.apply_grouped(params["experts"], local, cfg,
                               use_kernel=use_kernel)
    # the psum that stands in for the combine All-to-All is the
    # combine's own
    with jax.named_scope("moe.combine"):
        if ep_size > 1:
            full = jnp.zeros_like(buf)
            full = jax.lax.dynamic_update_slice_in_dim(full, eout,
                                                       my * e_local, 0)
            full = jax.lax.psum(full, ep_axis)
        else:
            full = eout
        out = D.combine(full, dest, probs, t)
    if m.num_shared_experts:
        with jax.named_scope("moe.expert_ffn"):
            out = out + E.apply_shared(params["shared"], tokens, cfg)
    return out, aux


def gather_expert_weights(params, dp_axes):
    """Explicit ZeRO-3 gather: expert weights arrive dp-sharded on their
    output dim; one all_gather here (outside the chunk loop) means the
    transpose is ONE reduce-scatter of the accumulated weight gradient —
    instead of one full fp32 psum per pipeline chunk (which dominated the
    collective term at n=16, see EXPERIMENTS §Perf iteration J-ZeRO3)."""
    if not dp_axes:
        return params
    out = dict(params)
    out["experts"] = {
        k: jax.lax.all_gather(v, dp_axes, axis=v.ndim - 1, tiled=True)
        for k, v in params["experts"].items()}
    return out


def pipelined_moe(params, tokens, *, cfg: ArchConfig,
                  ep_axis: Optional[str] = None, ep_size: int = 1,
                  mode: str = "train", use_kernel: bool = False,
                  dp_axes: Tuple[str, ...] = ()
                  ) -> Tuple[jax.Array, dict]:
    """tokens: [T_local, M] -> ([T_local, M], aux losses)."""
    m = cfg.moe
    params = gather_expert_weights(params, dp_axes)
    if mode == "decode" and ep_size > 1:
        return _replicated_decode(params, tokens, cfg=cfg, ep_axis=ep_axis,
                                  ep_size=ep_size, use_kernel=use_kernel)

    t_local = tokens.shape[0]
    n = _resolve_partitions(cfg, t_local, mode)
    chunk_t = t_local // n
    cap = capacity_for(chunk_t, m.top_k, m.capacity_factor, m.num_experts)
    strategy = Strategy(m.memory_reuse_strategy) \
        if m.memory_reuse_strategy != "adaptive" else Strategy.NONE

    def chunk_fn(p, c):
        return _chunk_fn(p, c, cfg=cfg, ep_axis=ep_axis, ep_size=ep_size,
                         cap=cap, use_kernel=use_kernel)

    if mode == "train":
        chunk_fn = wrap_chunk(chunk_fn, strategy)

    if m.pipeline_unroll or n == 1:
        outs, auxes = [], []
        for i in range(n):
            o, a = chunk_fn(params, tokens[i * chunk_t:(i + 1) * chunk_t])
            outs.append(o)
            auxes.append(a)
        out = jnp.concatenate(outs, axis=0) if n > 1 else outs[0]
        aux = jax.tree_util.tree_map(
            lambda *xs: sum(xs) / float(n), *auxes)
    else:
        chunks = tokens.reshape(n, chunk_t, -1)
        _, (outs, auxes) = jax.lax.scan(
            lambda _, c: (0, chunk_fn(params, c)), 0, chunks)
        out = outs.reshape(t_local, -1)
        aux = jax.tree_util.tree_map(lambda x: x.mean(), auxes)
    return out, aux
