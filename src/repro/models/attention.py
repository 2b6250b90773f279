"""Attention: GQA (full / sliding-window), MLA (DeepSeek-V3), cross-attention.

Pure-jnp reference implementations used by the model builder.  The Pallas
block-attention kernel in ``repro.kernels.attention`` is a drop-in for the
prefill path (enabled via ``use_kernel``; validated against this code in
tests).

Conventions:  x [B, S, D];  q/k/v [B, S, N, H];  caches [B, S_max, Nkv, H].
MLA latent cache: c_kv [B, S_max, R], k_rope [B, S_max, Hr].
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.common import init_norm, apply_norm, scaled_init
from repro.models.rope import apply_positional, apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_gqa(key, cfg):
    d, nq, nkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": scaled_init(ks[0], (d, nq, hd), d),
        "wk": scaled_init(ks[1], (d, nkv, hd), d),
        "wv": scaled_init(ks[2], (d, nkv, hd), d),
        "wo": scaled_init(ks[3], (nq, hd, d), nq * hd),
    }


def init_mla(key, cfg):
    d = cfg.d_model
    nq = cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rph, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": scaled_init(ks[0], (d, qr), d),
        "q_norm": init_norm("rmsnorm", ks[1], qr),
        "wq_b": scaled_init(ks[1], (qr, nq, nope + rph), qr),
        "wkv_a": scaled_init(ks[2], (d, kvr + rph), d),
        "kv_norm": init_norm("rmsnorm", ks[3], kvr),
        "wk_b": scaled_init(ks[3], (kvr, nq, nope), kvr),
        "wv_b": scaled_init(ks[4], (kvr, nq, vh), kvr),
        "wo": scaled_init(ks[5], (nq, vh, d), nq * vh),
    }


def init_attention(key, cfg):
    return init_mla(key, cfg) if cfg.attention == "mla" else init_gqa(key, cfg)


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

def make_mask(q_len: int, kv_len: int, *, causal: bool, window: int = 0,
              q_offset: int = 0):
    """Boolean [q_len, kv_len] mask.  window>0 = sliding window."""
    qi = jnp.arange(q_len)[:, None] + q_offset
    kj = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), bool)
    if causal:
        mask &= kj <= qi
    if window:
        mask &= kj > qi - window
    return mask


def _sdpa(q, k, v, mask, scale):
    """q [B,Sq,Nq,H], k/v [B,Skv,Nkv,H] with Nq = G*Nkv."""
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, sq, nkv, g, h)
    scores = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, nq, h).astype(q.dtype)


# Chunked (flash-style) attention in pure lax: q chunks in a static Python
# loop (so each chunk sees only its causal kv prefix — exact flops), kv
# chunks in a lax.scan carrying online-softmax stats.  The [Sq, Skv] score
# tensor never materializes in HBM — this is what moves the memory roofline
# term down for long-sequence prefill/train (EXPERIMENTS.md §Perf it. 2/5).
#
# Toggle: REPRO_ATTN=chunked enables it (beyond-paper optimized profile);
# default "dense" keeps the baseline implementation the §Roofline table
# measures.
import os as _os


def _env_impl(var: str, default: str, legal: tuple) -> str:
    """Read an impl-selection env toggle, rejecting unknown values at
    import: a typo (REPRO_PAGED_ATTN=kernal) must not silently fall
    through to the default path."""
    val = _os.environ.get(var, default)
    if val not in legal:
        raise ValueError(
            f"{var}={val!r} is not a known implementation; legal values: "
            + ", ".join(repr(v) for v in legal))
    return val


ATTN_IMPL = _env_impl("REPRO_ATTN", "dense", ("dense", "chunked"))
CHUNKED_THRESHOLD = 2048   # use chunked path when Sq*Skv exceeds threshold^2


def _sdpa_chunked(q, k, v, *, causal: bool, window: int, scale: float,
                  q_chunk: int = 1024, kv_chunk: int = 1024):
    b, sq, nq, h = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    assert sq % qc == 0 and skv % kc == 0
    # block inputs stay bf16 (MXU-native); softmax stats fp32 (flash-style)
    kf = k.astype(jnp.bfloat16)
    vf = v.astype(jnp.bfloat16)
    outs = []
    for qi in range(sq // qc):
        q0 = qi * qc
        qg = q[:, q0:q0 + qc].reshape(b, qc, nkv, g, h).astype(jnp.bfloat16)
        # static kv range for this q chunk (causal/window pruning)
        hi = min(skv, (q0 + qc)) if causal else skv
        lo = max(0, q0 - window - kc + 1) if window else 0
        lo = (lo // kc) * kc
        hi = ((hi + kc - 1) // kc) * kc
        nkc = (hi - lo) // kc
        kv_slice_k = kf[:, lo:hi].reshape(b, nkc, kc, nkv, h)
        kv_slice_v = vf[:, lo:hi].reshape(b, nkc, kc, nkv, h)
        q_pos = q0 + jnp.arange(qc)

        def body(carry, inp):
            m_run, l_run, acc = carry
            kb, vb, k0 = inp                      # [B,kc,nkv,h], [], k0 scalar
            s = jnp.einsum("bsngh,btnh->bngst", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            k_pos = k0 + jnp.arange(kc)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bngst,btnh->bngsh", p.astype(jnp.bfloat16), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, nkv, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, nkv, g, qc), jnp.float32)
        a0 = jnp.zeros((b, nkv, g, qc, h), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.moveaxis(kv_slice_k, 1, 0), jnp.moveaxis(kv_slice_v, 1, 0),
             lo + kc * jnp.arange(nkc)))
        o = acc / jnp.maximum(l_f, 1e-30)[..., None]       # [B,nkv,g,qc,h]
        outs.append(jnp.moveaxis(o, 3, 1).reshape(b, qc, nq, h))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA forward (train / prefill)
# ---------------------------------------------------------------------------

def gqa_forward(cfg, params, x, positions, *, causal: bool = True,
                window: int = 0, kv_x: Optional[jnp.ndarray] = None,
                rope_on: bool = True):
    """Full-sequence attention.  kv_x != None -> cross attention (no mask)."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dnh->bsnh", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", src, params["wv"].astype(x.dtype))
    if rope_on and kv_x is None:
        q = apply_positional(q, positions, cfg.rope, cfg.rope_theta)
        k = apply_positional(k, positions, cfg.rope, cfg.rope_theta)
    sq, skv = q.shape[1], k.shape[1]
    if ATTN_IMPL == "chunked" and kv_x is None \
            and sq * skv > CHUNKED_THRESHOLD ** 2 and sq % 1024 == 0 \
            and skv % 1024 == 0:
        out = _sdpa_chunked(q, k, v, causal=causal, window=window,
                            scale=1.0 / math.sqrt(hd))
    else:
        if kv_x is None:
            mask = make_mask(sq, skv, causal=causal, window=window)
        else:
            mask = jnp.ones((sq, skv), bool)
        out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(hd))
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype)), (k, v)


def _decode_positions(position, batch: int):
    """Normalize a decode position ([] scalar or [B] per-slot vector) to [B]."""
    pos = jnp.asarray(position, jnp.int32).reshape(-1)
    return jnp.broadcast_to(pos, (batch,))


def layer_view(arena, layer):
    """Layer ``layer`` of a stacked arena ``[n_layers, ...]``; ``layer``
    None means the arena is unstacked (shared-attention caches and pools).
    Read inside the layer scan, the slice fuses into its consumer."""
    if layer is None:
        return arena
    return jax.lax.dynamic_index_in_dim(arena, layer, 0, keepdims=False)


def write_rows(arena, layer, rows, cols, val):
    """Write one token's KV row per batch row in place:
    ``arena[layer, rows[b], cols[b]] = val[b]``.

    The single KV write of every decode path.  A row index past the end
    of its axis is dropped by the scatter, which is how a masked row
    writes nothing: contiguous arenas route it to row ``B``, paged pools
    to the sentinel page ``n_pages``.  Only the written rows are touched;
    no arena-sized select or copy is built around the write."""
    idx = (rows, cols) if layer is None else (layer, rows, cols)
    return arena.at[idx].set(val.astype(arena.dtype), mode="drop")


def _contiguous_write(cache, layer, slot, val, write_mask):
    """Row ``b`` writes ``val[b]`` at ``slot[b]`` unless ``write_mask[b]``
    is False (None: every row writes)."""
    b = val.shape[0]
    rows = jnp.arange(b)
    if write_mask is not None:
        rows = jnp.where(write_mask, rows, b)
    return write_rows(cache, layer, rows, slot, val)


def gqa_decode(cfg, params, x, cache_k, cache_v, position, *, window: int = 0,
               layer=None, write_mask=None):
    """One-token decode.  x [B,1,D]; caches [B,Smax,Nkv,H], or stacked
    [n_layers,B,Smax,Nkv,H] with ``layer`` the traced layer index; position
    [] int or [B] int (per-slot positions for continuous batching).

    The new K/V rows are written into the caches in place; rows with
    ``write_mask`` False write nothing (their attention then reads the old
    row at their slot; callers discard those rows' outputs).  Returns the
    whole (stacked) caches.

    window>0: the cache is a RING BUFFER of size window (sub-linear memory
    for long_500k); slot = position % window and scores use gathered
    absolute positions for RoPE + masking.
    """
    hd = cfg.resolved_head_dim
    b = x.shape[0]
    smax = cache_k.shape[-3]
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    pos_b = _decode_positions(position, b)                # [B]
    q = apply_positional(q, pos_b[:, None], cfg.rope, cfg.rope_theta)
    k = apply_positional(k, pos_b[:, None], cfg.rope, cfg.rope_theta)
    slot = (pos_b % smax) if window else jnp.minimum(pos_b, smax - 1)
    cache_k = _contiguous_write(cache_k, layer, slot, k[:, 0], write_mask)
    cache_v = _contiguous_write(cache_v, layer, slot, v[:, 0], write_mask)
    ck, cv = layer_view(cache_k, layer), layer_view(cache_v, layer)
    # validity of each cache slot, per batch row
    idx = jnp.arange(smax)
    if window:
        # slot i holds absolute position: the most recent occupant
        age = (slot[:, None] - idx[None, :]) % smax        # 0..smax-1, 0 = newest
        valid = age < jnp.minimum(pos_b + 1, smax)[:, None]
    else:
        valid = idx[None, :] <= pos_b[:, None]             # [B, Smax]
    nq = q.shape[2]
    nkv = ck.shape[2]
    g = nq // nkv
    qg = q.reshape(b, 1, nkv, g, hd)
    scores = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) / math.sqrt(hd)
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", probs, cv.astype(jnp.float32))
    out = out.reshape(b, 1, nq, hd).astype(x.dtype)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, (cache_k, cache_v)


# ---------------------------------------------------------------------------
# Paged decode — KV pools [n_pages, P, ...] + per-slot block tables
# ---------------------------------------------------------------------------
#
# The paged arena stores KV in a global pool of fixed-size pages; each slot
# maps logical cache positions to physical pages through a block table row
# ``tbl [B, pages_per_slot]`` whose sentinel value is ``n_pages``
# (= unallocated).  The jnp path below is BIT-IDENTICAL to the contiguous
# decode above: the gathered view clips sentinel entries to a real page, but
# every clipped position is masked by ``valid`` -> NEG_INF -> exp underflows
# to exactly 0.0 in f32, so garbage pages contribute exactly nothing.
#
# Toggle: REPRO_PAGED_ATTN=kernel routes the score/softmax/context through
# the Pallas paged kernels in repro.kernels.paged_attention (block-table
# gathers via scalar prefetch); default "jnp" keeps the reference path.
PAGED_ATTN_IMPL = _env_impl("REPRO_PAGED_ATTN", "jnp", ("jnp", "kernel"))


class PagedKV:
    """Trace-time bundle for paged decode: block table + write gate.

    ``tbl``: [B, pages_per_slot] int32 device array (sentinel = n_pages).
    ``write_mask``: [B] bool — rows allowed to write their KV this step
    (prefill activity gates, alive & active in decode).  Masked rows route
    their write to the sentinel page id which scatter-drops.
    """

    def __init__(self, tbl, write_mask):
        self.tbl = tbl
        self.write_mask = write_mask


def paged_view(pool, tbl):
    """Gather a slot-contiguous [B, pps*P, ...] view out of the pool.

    Sentinel table entries are clipped to page 0 — callers MUST mask those
    positions (they always can: sentinels only cover positions > pos_b).
    """
    n_pages = pool.shape[0]
    gathered = pool[jnp.clip(tbl, 0, n_pages - 1)]     # [B, pps, P, ...]
    b, pps, psz = gathered.shape[:3]
    return gathered.reshape(b, pps * psz, *gathered.shape[3:])


def paged_write(pool, paged: PagedKV, pos_b, val, layer=None):
    """Scatter one token per row into its block-table page of ``pool``
    ([n_pages, P, ...], or stacked [n_layers, n_pages, P, ...] with
    ``layer`` the traced layer index).

    Rows with write_mask False (and rows whose page is unallocated) are
    routed to the sentinel page id and dropped by the scatter — stale slots
    can never corrupt pages owned by live requests.
    """
    view = pool.shape if layer is None else pool.shape[1:]
    n_pages, psz = view[0], view[1]
    smax = paged.tbl.shape[1] * psz
    slot = jnp.minimum(pos_b, smax - 1)
    page = jnp.take_along_axis(paged.tbl, (slot // psz)[:, None], axis=1)[:, 0]
    page = jnp.where(paged.write_mask, page, n_pages)
    return write_rows(pool, layer, page, slot % psz, val)


def gqa_decode_paged(cfg, params, x, pool_k, pool_v, position, paged: PagedKV,
                     layer=None):
    """One-token GQA decode against paged KV pools [n_pages, P, Nkv, H]
    (stacked [n_layers, ...] with ``layer`` the traced layer index).

    Same math as ``gqa_decode`` on the gathered view — bit-identical for the
    jnp path.  No ring-buffer window support (paged mode asserts window==0
    at scheduler init)."""
    hd = cfg.resolved_head_dim
    b = x.shape[0]
    psz = pool_k.shape[-3]
    smax = paged.tbl.shape[1] * psz
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    pos_b = _decode_positions(position, b)                 # [B]
    q = apply_positional(q, pos_b[:, None], cfg.rope, cfg.rope_theta)
    k = apply_positional(k, pos_b[:, None], cfg.rope, cfg.rope_theta)
    pool_k = paged_write(pool_k, paged, pos_b, k[:, 0], layer)
    pool_v = paged_write(pool_v, paged, pos_b, v[:, 0], layer)
    pk, pv = layer_view(pool_k, layer), layer_view(pool_v, layer)
    nq = q.shape[2]
    nkv = pk.shape[2]
    if PAGED_ATTN_IMPL == "kernel":
        from repro.kernels import ops as kops
        out = kops.paged_gqa_attention(q, pk, pv, paged.tbl, pos_b)
    else:
        cache_k = paged_view(pk, paged.tbl)                # [B, smax, Nkv, H]
        cache_v = paged_view(pv, paged.tbl)
        valid = jnp.arange(smax)[None, :] <= pos_b[:, None]
        g = nq // nkv
        qg = q.reshape(b, 1, nkv, g, hd)
        scores = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32),
                            cache_k.astype(jnp.float32)) / math.sqrt(hd)
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bngst,btnh->bsngh", probs,
                         cache_v.astype(jnp.float32))
        out = out.reshape(b, 1, nq, hd).astype(x.dtype)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))
    return y, (pool_k, pool_v)


def mla_decode_paged(cfg, params, x, pool_ckv, pool_krope, position,
                     paged: PagedKV, layer=None):
    """One-token MLA decode against paged latent pools
    ([n_pages, P, R] / [n_pages, P, Hr], stacked per layer with ``layer``)
    with matrix absorption."""
    b = x.shape[0]
    psz = pool_ckv.shape[-2]
    smax = paged.tbl.shape[1] * psz
    pos_b = _decode_positions(position, b)                 # [B]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, params, x, pos_b[:, None])
    pool_ckv = paged_write(pool_ckv, paged, pos_b, c_kv[:, 0], layer)
    pool_krope = paged_write(pool_krope, paged, pos_b, k_rope[:, 0], layer)
    pc, pr = layer_view(pool_ckv, layer), layer_view(pool_krope, layer)
    if PAGED_ATTN_IMPL == "kernel":
        from repro.kernels import ops as kops
        # absorb wk_b outside the kernel (FlashInfer MLA trick): the kernel
        # sees latent-rank queries only.
        q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope,
                           params["wk_b"].astype(q_nope.dtype))
        nope, rph = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        out = kops.paged_mla_attention(
            q_lat, q_rope, pc, pr, paged.tbl, pos_b,
            scale=1.0 / math.sqrt(nope + rph))
        out = jnp.einsum("bsnr,rnv->bsnv", out.astype(q_nope.dtype),
                         params["wv_b"].astype(q_nope.dtype))
    else:
        cache_ckv = paged_view(pc, paged.tbl)              # [B, smax, R]
        cache_krope = paged_view(pr, paged.tbl)
        valid = jnp.arange(smax)[None, :] <= pos_b[:, None]
        out = mla_scores_ctx(cfg, params, q_nope, q_rope, cache_ckv,
                             cache_krope, valid[:, None, :])
    y = jnp.einsum("bsnv,nvd->bsd", out, params["wo"].astype(x.dtype))
    return y, (pool_ckv, pool_krope)


def cross_decode(cfg, params, x, enc_k, enc_v):
    """Cross-attention decode step against precomputed encoder k/v."""
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    mask = jnp.ones((1, enc_k.shape[1]), bool)
    out = _sdpa(q, enc_k, enc_v, mask, 1.0 / math.sqrt(hd))
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3) — latent-compressed attention with matrix absorption
# ---------------------------------------------------------------------------

def _mla_qkv(cfg, params, x, positions):
    nope, rph = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = jnp.einsum("bsd,dr->bsr", x, params["wq_a"].astype(x.dtype))
    cq = apply_norm("rmsnorm", cq, params["q_norm"])
    q = jnp.einsum("bsr,rnh->bsnh", cq, params["wq_b"].astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"].astype(x.dtype))
    c_kv, k_rope = ckv[..., : cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = apply_norm("rmsnorm", c_kv, params["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_scores_ctx(cfg, params, q_nope, q_rope, c_kv, k_rope, mask):
    """Absorbed-matrix attention: scores & context from the latent cache."""
    nope, rph = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = 1.0 / math.sqrt(nope + rph)
    # absorb wk_b into q:  q_lat [B,Sq,N,R]
    q_lat = jnp.einsum("bsnh,rnh->bsnr", q_nope, params["wk_b"].astype(q_nope.dtype))
    scores = jnp.einsum("bsnr,btr->bnst", q_lat.astype(jnp.float32),
                        c_kv.astype(jnp.float32))
    scores += jnp.einsum("bsnh,bth->bnst", q_rope.astype(jnp.float32),
                         k_rope.astype(jnp.float32))
    m = mask if mask.ndim == 3 else mask[None]             # [B|1, Sq, Skv]
    scores = jnp.where(m[:, None], scores * scale, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_lat = jnp.einsum("bnst,btr->bsnr", probs, c_kv.astype(jnp.float32))
    out = jnp.einsum("bsnr,rnv->bsnv", ctx_lat.astype(q_nope.dtype),
                     params["wv_b"].astype(q_nope.dtype))
    return out


def mla_forward(cfg, params, x, positions, *, causal: bool = True, window: int = 0):
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, params, x, positions)
    mask = make_mask(x.shape[1], x.shape[1], causal=causal, window=window)
    out = mla_scores_ctx(cfg, params, q_nope, q_rope, c_kv, k_rope, mask)
    y = jnp.einsum("bsnv,nvd->bsd", out, params["wo"].astype(x.dtype))
    return y, (c_kv, k_rope)


def mla_decode(cfg, params, x, cache_ckv, cache_krope, position, *,
               window: int = 0, layer=None, write_mask=None):
    """One-token MLA decode against the latent cache (ring buffer if window).

    `position` is a [] scalar or a [B] per-slot vector (continuous batching).
    Stacked caches, ``layer`` and ``write_mask`` as in ``gqa_decode``.
    """
    b = x.shape[0]
    smax = cache_ckv.shape[-2]
    pos_b = _decode_positions(position, b)                 # [B]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(cfg, params, x, pos_b[:, None])
    slot = (pos_b % smax) if window else jnp.minimum(pos_b, smax - 1)
    cache_ckv = _contiguous_write(cache_ckv, layer, slot, c_kv[:, 0],
                                  write_mask)
    cache_krope = _contiguous_write(cache_krope, layer, slot, k_rope[:, 0],
                                    write_mask)
    idx = jnp.arange(smax)
    if window:
        age = (slot[:, None] - idx[None, :]) % smax
        valid = age < jnp.minimum(pos_b + 1, smax)[:, None]
    else:
        valid = idx[None, :] <= pos_b[:, None]             # [B, Smax]
    mask = valid[:, None, :]                               # [B, Sq=1, Skv]
    out = mla_scores_ctx(cfg, params, q_nope, q_rope,
                         layer_view(cache_ckv, layer),
                         layer_view(cache_krope, layer), mask)
    y = jnp.einsum("bsnv,nvd->bsd", out, params["wo"].astype(x.dtype))
    return y, (cache_ckv, cache_krope)
