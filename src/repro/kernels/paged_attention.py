"""Paged decode-attention kernels (Pallas, TPU target).

One decode token per sequence attends over that sequence's KV pages,
gathered THROUGH the block table inside the kernel grid: the per-slot page
table rides in as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``), so each grid step's k/v BlockSpec
index map reads ``tbl[b, j]`` and DMAs the j-th *logical* page of sequence
``b`` from wherever it physically lives in the pool — no gather
materialization, no contiguous per-slot rows.  Online softmax over pages
mirrors ``kernels/attention.py`` (running m / l / acc, rescaled per tile).

Two variants:

``paged_gqa_attention``
    grid (B * Nkv, pages_per_slot); every program owns one (sequence,
    kv-head) pair and its G = Nq/Nkv query group, so both matmuls are
    MXU-shaped 2-D: scores [G, P] = q [G, H] @ k [P, H]^T and
    acc += p [G, P] @ v [P, H].

``paged_mla_attention``
    grid (B, pages_per_slot); MLA with matrix absorption (the FlashInfer
    MLA trick): the caller absorbs W_kb into the queries so the kernel sees
    latent-rank queries, scores against the concatenated
    [compressed-kv | rope-k] page, and accumulates the *latent* context
    (weighted c_kv) — W_vb is applied outside.

Sentinel block-table entries (unallocated pages) must be clipped into
range by the wrapper; they are always masked off by the position bound.
Layout/padding is the wrapper's job (see ops.py): head dims padded to the
128 lane, query-group/head counts to the 8 sublane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30


def _paged_gqa_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, nkv: int, page: int,
                      scale: float):
    i = pl.program_id(0)               # sequence * kv-head
    j = pl.program_id(1)               # logical page index
    b = i // nkv

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # [G, H]
    k = k_ref[0, 0].astype(jnp.float32)                    # [P, H]
    v = v_ref[0, 0].astype(jnp.float32)                    # [P, H]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    t = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t <= pos_ref[b], s, NEG_INF)

    m_prev = m_scr[...]                                    # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_gqa_attention(q, pool_k, pool_v, tbl, pos, *, scale: float,
                        interpret: bool | None = None):
    """q [B, Nkv, G, H], pools [Nkv, n_pages, P, H] (head-major, padded),
    tbl [B, pps] int32 (CLIPPED into [0, n_pages)), pos [B] int32 ->
    o [B, Nkv, G, H] fp32.  ``interpret=None`` auto-detects the backend
    (interpret only off-TPU)."""
    interpret = resolve_interpret(interpret)
    b, nkv, g, h = q.shape
    n_pages, page = pool_k.shape[1], pool_k.shape[2]
    pps = tbl.shape[1]
    kern = functools.partial(_paged_gqa_kernel, nkv=nkv, page=page,
                             scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * nkv, pps),
        in_specs=[
            pl.BlockSpec((1, 1, g, h),
                         lambda i, j, tbl_ref, pos_ref:
                         (i // nkv, i % nkv, 0, 0)),
            pl.BlockSpec((1, 1, page, h),
                         lambda i, j, tbl_ref, pos_ref:
                         (i % nkv, tbl_ref[i // nkv, j], 0, 0)),
            pl.BlockSpec((1, 1, page, h),
                         lambda i, j, tbl_ref, pos_ref:
                         (i % nkv, tbl_ref[i // nkv, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, h),
                               lambda i, j, tbl_ref, pos_ref:
                               (i // nkv, i % nkv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, h), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, h), jnp.float32),
        interpret=interpret,
    )(tbl, pos, q, pool_k, pool_v)


def _paged_mla_kernel(tbl_ref, pos_ref, q_ref, kc_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, page: int, rank: int,
                      scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [N, R + Hr]
    kc = kc_ref[0].astype(jnp.float32)                     # [P, R + Hr]
    s = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    t = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t <= pos_ref[b], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    # latent accumulation: the "values" are the compressed-kv half of the
    # concatenated page (matrix absorption — W_vb applies after the kernel)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, kc[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def paged_mla_attention(q_cat, pool_cat, tbl, pos, *, rank: int,
                        scale: float, interpret: bool | None = None):
    """q_cat [B, N, R + Hr] (absorbed latent queries || rope queries),
    pool_cat [n_pages, P, R + Hr] (compressed-kv || rope-k pages),
    tbl [B, pps] int32 (clipped), pos [B] int32 -> latent o [B, N, R] fp32.
    ``rank`` is the PADDED latent width R inside the concatenation.
    ``interpret=None`` auto-detects the backend (interpret only off-TPU)."""
    interpret = resolve_interpret(interpret)
    b, n, dcat = q_cat.shape
    page = pool_cat.shape[1]
    pps = tbl.shape[1]
    kern = functools.partial(_paged_mla_kernel, page=page, rank=rank,
                             scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pps),
        in_specs=[
            pl.BlockSpec((1, n, dcat),
                         lambda i, j, tbl_ref, pos_ref: (i, 0, 0)),
            pl.BlockSpec((1, page, dcat),
                         lambda i, j, tbl_ref, pos_ref:
                         (tbl_ref[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, rank),
                               lambda i, j, tbl_ref, pos_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, 1), jnp.float32),
            pltpu.VMEM((n, rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, rank), jnp.float32),
        interpret=interpret,
    )(tbl, pos, q_cat, pool_cat)
