"""Paged-attention decode Pallas TPU kernels (plain GQA + MLA latent).

Grid (slots, pages); pages is the innermost (sequential on TPU) axis.
Each step DMAs ONE physical page: the page table is a scalar-prefetch
operand, so the KV BlockSpec index maps route block ``j`` of slot ``b``
straight to ``page_table[b, j]`` — resident KV is never materialized
contiguously in HBM, which is the whole point vs the
``kv_cache.gather_pages`` baseline whose copy grows with context.

Layout: scores are staged with the key position on the sublane axis
(``[T, H]``) and values as ``[T, D]``, so every per-page store lands
at a sublane offset ``j * page_rows`` — the TPU's vector stores need
lane offsets that are multiples of 128, which a ``[H, T]`` staging at
``j * page_size`` is not. Masking / softmax / PV run ONCE over the
staged positions at the last page, the same ops in the same order as
the gather reference (``ref.py``) over the positions that survive the
mask.

Plain GQA folds the kv-head axis into the page rows: a page
``[ps, Kv, D]`` is read as ``[ps*Kv, D]`` (row ``t*Kv + k``) and the
query as ``[Kv*G, D]``, so both contractions are plain 2-D matmuls.
Scores of a query head against another kv head's rows are masked to
``NEG_INF`` per page (``head_mask``), which makes their softmax weight
exactly 0 — the Kv-fold extra score FLOPs are negligible in a
memory-bound decode step, and no per-head batched matmul (whose batch
axis sits in different operand positions) reaches the compiler.
Sink pages and grown-ahead pages (slots holding more pages than
``pages_for(lens)``) need no separate mask: every position ``>= lens``
is cut by the length mask, and the page walk only ever reads pages
named by the slot's own page-table row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38   # matches repro.models.layers.attention.NEG_INF

# contract dim 0 of both operands: [T, H] x [T, D] -> [H, D]
_TN = (((0,), (0,)), ((), ()))
# contract the last dim of both operands: [R, D] x [H, D] -> [R, H]
_NT = (((1,), (1,)), ((), ()))


# ---------------------------------------------------------------------------
# Plain GQA decode
# ---------------------------------------------------------------------------

def _decode_kernel(pt_ref, lens_ref, hm_ref, q_ref, k_ref, v_ref, o_ref,
                   s_scr, v_scr, *, rows: int, kv: int, window: int,
                   scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    npages = pl.num_programs(1)

    s = jax.lax.dot_general(k_ref[0], q_ref[0], _NT,
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(hm_ref[...] != 0, s, NEG_INF)        # [rows, H]
    off = pl.multiple_of(j * rows, rows)
    s_scr[pl.ds(off, rows), :] = s
    v_scr[pl.ds(off, rows), :] = v_ref[0]

    @pl.when(j == npages - 1)
    def _finish():
        t = npages * rows
        cl = lens_ref[b] * kv                          # rows < lens*Kv
        idx = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
        valid = idx < cl
        if window > 0:
            valid = valid & (idx >= cl - window * kv)
        s_all = jnp.where(valid, s_scr[...], NEG_INF)
        m = s_all.max(axis=0, keepdims=True)
        p = jnp.exp(s_all - m)
        l = p.sum(axis=0, keepdims=True)
        out = jax.lax.dot_general(p / jnp.maximum(l, 1e-30),
                                  v_scr[...].astype(jnp.float32), _TN,
                                  preferred_element_type=jnp.float32)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, page_table, lens, *,
                                  window: int = 0,
                                  interpret: bool = False):
    """q: [B, Kv, G, D]; pools: [P, ps, Kv, D]; page_table: [B, NP]
    int32; lens: [B] int32 — valid cache entries per slot INCLUDING the
    token scattered this step. Returns [B, Kv, G, D] in q's dtype.
    """
    b, kv, g, d = q.shape
    n_pool, ps = k_pool.shape[:2]
    npages = page_table.shape[1]
    h, rows = kv * g, ps * kv
    # free row-major views: query head k*G+g, page row t*Kv+k
    qf = q.reshape(b, h, d)
    kf = k_pool.reshape(n_pool, rows, d)
    vf = v_pool.reshape(n_pool, rows, d)
    head_mask = ((jnp.arange(rows)[:, None] % kv)
                 == (jnp.arange(h)[None, :] // g)).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, lens
        grid=(b, npages),
        in_specs=[
            pl.BlockSpec((rows, h), lambda bi, j, pt, ln: (0, 0)),
            pl.BlockSpec((1, h, d), lambda bi, j, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, rows, d),
                         lambda bi, j, pt, ln: (pt[bi, j], 0, 0)),
            pl.BlockSpec((1, rows, d),
                         lambda bi, j, pt, ln: (pt[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bi, j, pt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((npages * rows, h), jnp.float32),
            pltpu.VMEM((npages * rows, d), v_pool.dtype),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, rows=rows, kv=kv, window=window,
                          scale=d ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(page_table, lens, head_mask, qf, kf, vf)
    return out.reshape(b, kv, g, d)


# ---------------------------------------------------------------------------
# MLA latent decode (absorbed formulation)
# ---------------------------------------------------------------------------

def _mla_kernel(pt_ref, lens_ref, qa_ref, qr_ref, ckv_ref, kr_ref, o_ref,
                s_scr, c_scr, *, ps: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    npages = pl.num_programs(1)

    qa = qa_ref[0]                                     # [H, R]
    qr = qr_ref[0]                                     # [H, E]
    ckv = ckv_ref[0]                                   # [ps, R]
    kr = kr_ref[0]                                     # [ps, E]
    s = (jax.lax.dot_general(ckv.astype(qa.dtype), qa, _NT,
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(kr.astype(qr.dtype), qr, _NT,
                               preferred_element_type=jnp.float32))
    off = pl.multiple_of(j * ps, ps)
    s_scr[pl.ds(off, ps), :] = s                       # [ps, H]
    c_scr[pl.ds(off, ps), :] = ckv

    @pl.when(j == npages - 1)
    def _finish():
        t = npages * ps
        ln = lens_ref[b]
        idx = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
        # decode queries sit at absolute position ``lens``; key position
        # t is visible iff t <= lens (the just-written token included)
        s_all = s_scr[...] * scale
        s_all = jnp.where(idx <= ln, s_all, NEG_INF)
        p = jax.nn.softmax(s_all, axis=0)
        o_ref[0] = jax.lax.dot_general(p, c_scr[...].astype(jnp.float32),
                                       _TN,
                                       preferred_element_type=jnp.float32)


def paged_mla_decode_kernel(q_abs, q_rope, ckv_pool, kr_pool, page_table,
                            lens, *, scale: float,
                            interpret: bool = False):
    """q_abs: [B, H, R] (latent-absorbed); q_rope: [B, H, E]; ckv_pool:
    [P, ps, R]; kr_pool: [P, ps, E]; lens: [B] int32 — the slot's
    absolute decode position (visible keys are ``t <= lens``). Returns
    the latent context [B, H, R] float32 (``c_kv`` doubles as K and V,
    so the pages are staged once).
    """
    b, h, r = q_abs.shape
    e = q_rope.shape[-1]
    ps = ckv_pool.shape[1]
    npages = page_table.shape[1]
    t = npages * ps

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, lens
        grid=(b, npages),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda bi, j, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, h, e), lambda bi, j, pt, ln: (bi, 0, 0)),
            pl.BlockSpec((1, ps, r),
                         lambda bi, j, pt, ln: (pt[bi, j], 0, 0)),
            pl.BlockSpec((1, ps, e),
                         lambda bi, j, pt, ln: (pt[bi, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r),
                               lambda bi, j, pt, ln: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t, h), jnp.float32),
            pltpu.VMEM((t, r), ckv_pool.dtype),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_kernel, ps=ps, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        interpret=interpret,
    )(page_table, lens, q_abs, q_rope, ckv_pool, kr_pool)
