"""[B,...]-layout entry points for the paged-decode kernels.

``dist`` (mesh-sharded serving) wraps the kernel in a ``shard_map``
over the dp axis so page reads stay shard-local: slots (q, page table,
lens, output) are slot-sharded; the pools are page-sharded when the
engine runs ``kv_sharding="dp"`` and replicated otherwise. One body
serves both layouts because every page a slot's page-table row names —
allocated pages AND its sink fill — lives on the slot's own shard
(``PagedKVCache`` places slot ``i`` on shard ``i // slots_per_shard``
and allocates only from that shard's free list), so global page ids
localize as ``page_table % local_pages``, which degenerates to the
identity when the pool is replicated. The HLO therefore contains no
all-gather of the page pool — the dissolution of the PR 5 open
question that ``gather_pages`` could not guarantee.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_decode_attention_kernel, paged_mla_decode_kernel)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _run_sharded(fn, dist, kv_sharded, qargs, pools, page_table, lens):
    """shard_map ``fn(*qargs, *pools, page_table, lens)`` over the dp
    axis; output is slot-sharded like ``qargs[0]``."""
    from jax.sharding import PartitionSpec as P

    dp = dist.dp_axes[0]

    def slot_spec(a):
        return P(*((dp,) + (None,) * (a.ndim - 1)))

    def pool_spec(a):
        return slot_spec(a) if kv_sharded else P()

    n_q = len(qargs)

    def body(*args):
        qs, ps = args[:n_q], args[n_q:-2]
        pt, ln = args[-2], args[-1]
        pt = pt % ps[0].shape[0]      # global -> shard-local page ids
        return fn(*qs, *ps, pt, ln)

    wrapped = jax.shard_map(
        body, mesh=dist.mesh,
        in_specs=(tuple(slot_spec(a) for a in qargs)
                  + tuple(pool_spec(a) for a in pools)
                  + (slot_spec(page_table), slot_spec(lens))),
        out_specs=slot_spec(qargs[0]), check_vma=False)
    return wrapped(*qargs, *pools, page_table, lens)


def paged_decode_attention(q, k_pool, v_pool, page_table, lens, *,
                           window: int = 0, dist=None,
                           kv_sharded: bool = False):
    """q: [B, 1, Hq, D]; pools: [P, ps, Kv, D]; page_table: [B, NP];
    lens: [B] — valid cache entries per slot including the token
    scattered this step. Returns [B, 1, Hq, D] (drop-in for
    ``decode_attention`` over gathered pages)."""
    b, s, hq, d = q.shape
    assert s == 1, "paged decode kernel is single-query"
    kv = k_pool.shape[2]
    qe = q.reshape(b, kv, hq // kv, d)
    pt = page_table.astype(jnp.int32)
    ln = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(lens)).astype(jnp.int32), (b,))

    def call(qe, kp, vp, pt, ln):
        return paged_decode_attention_kernel(
            qe, kp, vp, pt, ln, window=window, interpret=_interpret())

    if dist is None:
        out = call(qe, k_pool, v_pool, pt, ln)
    else:
        out = _run_sharded(call, dist, kv_sharded, (qe,),
                           (k_pool, v_pool), pt, ln)
    return out.reshape(b, 1, hq, d)


def paged_mla_decode(q_abs, q_rope, ckv_pool, kr_pool, page_table, lens,
                     *, scale: float, dist=None, kv_sharded: bool = False):
    """q_abs: [B, 1, H, R] (latent-absorbed query); q_rope: [B, 1, H, E];
    ckv_pool: [P, ps, R]; kr_pool: [P, ps, E]; lens: [B] — the slot's
    absolute decode position. Returns the latent context [B, 1, H, R]
    float32 (the caller applies ``w_uv``/``w_o``)."""
    b, s, h, r = q_abs.shape
    assert s == 1, "paged MLA decode kernel is single-query"
    qa, qr = q_abs[:, 0], q_rope[:, 0]
    pt = page_table.astype(jnp.int32)
    ln = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(lens)).astype(jnp.int32), (b,))

    def call(qa, qr, cp, kp, pt, ln):
        return paged_mla_decode_kernel(
            qa, qr, cp, kp, pt, ln, scale=scale, interpret=_interpret())

    if dist is None:
        ctx = call(qa, qr, ckv_pool, kr_pool, pt, ln)
    else:
        ctx = _run_sharded(call, dist, kv_sharded, (qa, qr),
                           (ckv_pool, kr_pool), pt, ln)
    return ctx[:, None]
