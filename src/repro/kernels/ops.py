"""jit'd public wrappers around the Pallas kernels.

Handle padding to tile multiples, head-major reshapes, GQA head expansion,
and CPU-vs-TPU dispatch (interpret=True executes the kernel body in Python
on CPU; on a real TPU backend the same call compiles to Mosaic).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels import attention as _attn
from repro.kernels import exit_head as _exit
from repro.kernels import feature_compress as _fc
from repro.kernels import paged_attention as _pattn
from repro.kernels.backend import resolve_interpret as _resolve_interpret


# the exit-head kernel holds an f32 copy of each [D, block_v] weight tile
# in VMEM, whose default scoped limit on v5e is 16 MiB
_EXIT_W_TILE_BYTES = 12 * 2**20


def exit_head_entropy(x, w, *, block_t: int = 128,
                      interpret: bool | None = None,
                      align_128: bool | None = None):
    """x [..., D], w [D, V] -> entropy [...] fp32 (pads T and V).

    ``interpret=None`` auto-detects the backend (interpret only off-TPU).
    ``align_128`` (default: on for the compiled TPU path) forces MXU-legal
    tiling: T is padded to full ``block_t`` tiles and the inner dim to a
    multiple of 128 — zero feature columns/rows contribute nothing to the
    logits, so the entropy is unchanged.  The vocab tile is 512 wide, or
    narrower where its f32 weight tile would overflow the VMEM budget
    (d_model 7168 compiles only at 256).
    """
    interpret = _resolve_interpret(interpret)
    align = (not interpret) if align_128 is None else align_128
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    t = x2.shape[0]
    v = w.shape[1]
    block_v = 512
    while block_v > 128 and d * block_v * 4 > _EXIT_W_TILE_BYTES:
        block_v //= 2
    bt = block_t if align else min(block_t, max(8, t))
    pt = (-t) % bt
    pv = (-v) % block_v
    if pt:
        x2 = jnp.pad(x2, ((0, pt), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, pv))) if pv else w
    if pv:
        # padded vocab columns would distort the softmax; push them to -inf
        # by padding W with zeros and masking via a huge negative bias row?
        # Simplest correct approach: pad with a column of -1e30 * onehot is
        # not expressible in W alone — instead fall back to extending x with
        # a zero feature and W with a bias row: logits_pad = -1e30.
        bias = jnp.zeros((1, v + pv), w.dtype).at[0, v:].set(-1e30)
        x2 = jnp.concatenate([x2, jnp.ones((x2.shape[0], 1), x2.dtype)], axis=1)
        wp = jnp.concatenate([wp, bias.astype(wp.dtype)], axis=0)
    if align and x2.shape[1] % 128:
        pd = (-x2.shape[1]) % 128
        x2 = jnp.pad(x2, ((0, 0), (0, pd)))
        wp = jnp.pad(wp, ((0, pd), (0, 0)))
    ent = _exit.exit_head_entropy(x2, wp, block_t=bt, block_v=block_v,
                                  interpret=interpret)
    return ent[:t].reshape(lead)


def compress_rows(x, *, interpret: bool | None = None):
    """x [..., D] -> (q int8 [..., D], scale fp32 [..., 1]).

    ``interpret=None`` auto-detects the backend (interpret only off-TPU,
    the same way ``exit_head_entropy`` does).  On the compiled TPU path the
    tiling is forced MXU-legal: T is padded to full 256-row tiles and D to
    a multiple of 128.  Zero padding is exact — padded feature columns do
    not move a row's abs-max, so scales and quantized values are unchanged.
    """
    interpret = _resolve_interpret(interpret)
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    t = x2.shape[0]
    bt = 256 if not interpret else min(256, max(8, t))
    pad = (-t) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if not interpret and d % 128:
        x2 = jnp.pad(x2, ((0, 0), (0, (-d) % 128)))
    q, s = _fc.quantize_rows(x2, block_t=bt, interpret=interpret)
    return q[:t, :d].reshape(*lead, d), s[:t].reshape(*lead, 1)


def decompress_rows(q, scale, *, dtype=jnp.bfloat16,
                    interpret: bool | None = None):
    """(q int8 [..., D], scale [..., 1]) -> x [..., D] ``dtype``.

    Backend detection and MXU-legal padding mirror ``compress_rows``
    (padded int8 zeros dequantize to zeros and are sliced off)."""
    interpret = _resolve_interpret(interpret)
    lead = q.shape[:-1]
    d = q.shape[-1]
    q2 = q.reshape(-1, d)
    s2 = scale.reshape(-1, 1)
    t = q2.shape[0]
    bt = 256 if not interpret else min(256, max(8, t))
    pad = (-t) % bt
    if pad:
        q2 = jnp.pad(q2, ((0, pad), (0, 0)))
        s2 = jnp.pad(s2, ((0, pad), (0, 0)))
    if not interpret and d % 128:
        q2 = jnp.pad(q2, ((0, 0), (0, (-d) % 128)))
    x = _fc.dequantize_rows(q2, s2, block_t=bt, dtype=dtype,
                            interpret=interpret)
    return x[:t, :d].reshape(*lead, d)


def paged_gqa_attention(q, pool_k, pool_v, tbl, pos, *,
                        interpret: bool | None = None):
    """Paged GQA decode attention: q [B, 1, Nq, H], pools
    [n_pages, P, Nkv, H], tbl [B, pps] int32 (sentinel entries allowed —
    clipped here, always masked by ``pos``), pos [B] -> [B, 1, Nq, H].

    Layout for the kernel: queries fold to [B, Nkv, G, H] so each grid
    program owns one (sequence, kv-head) query group; pools go head-major
    [Nkv, n_pages, P, H]; the group and head dims are padded MXU/VPU-legal
    (G to the 8 sublane, H to the 128 lane — zero columns add nothing to
    either matmul, padded query rows are sliced off)."""
    interpret = _resolve_interpret(interpret)
    b, s, nq, hd = q.shape
    assert s == 1, "paged attention is a decode (one-token) kernel"
    n_pages, page, nkv, _ = pool_k.shape
    g = nq // nkv
    gp = (-g) % 8
    hp = (-hd) % 128
    qg = q[:, 0].reshape(b, nkv, g, hd)
    if gp or hp:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp), (0, hp)))
    km = pool_k.transpose(2, 0, 1, 3)
    vm = pool_v.transpose(2, 0, 1, 3)
    if hp:
        km = jnp.pad(km, ((0, 0), (0, 0), (0, 0), (0, hp)))
        vm = jnp.pad(vm, ((0, 0), (0, 0), (0, 0), (0, hp)))
    tblc = jnp.clip(tbl, 0, n_pages - 1).astype(jnp.int32)
    out = _pattn.paged_gqa_attention(
        qg, km, vm, tblc, pos.astype(jnp.int32),
        scale=1.0 / math.sqrt(hd), interpret=interpret)
    out = out[:, :, :g, :hd].reshape(b, 1, nq, hd)
    return out.astype(q.dtype)


def paged_mla_attention(q_lat, q_rope, pool_ckv, pool_krope, tbl, pos, *,
                        scale: float, interpret: bool | None = None):
    """Paged MLA decode attention with matrix absorption: q_lat [B, 1, N, R]
    (W_kb already absorbed), q_rope [B, 1, N, Hr], pools
    [n_pages, P, R] / [n_pages, P, Hr], tbl [B, pps] int32, pos [B] ->
    latent context [B, 1, N, R] fp32 (caller applies W_vb).

    The two query/key halves concatenate lane-aligned (each padded to a
    128 multiple) so the kernel scores with ONE [N, R+Hr] @ [P, R+Hr]^T
    matmul; the latent half doubles as the value page."""
    interpret = _resolve_interpret(interpret)
    b, s, n, r = q_lat.shape
    assert s == 1, "paged attention is a decode (one-token) kernel"
    n_pages, page, hr = (pool_krope.shape[0], pool_krope.shape[1],
                         pool_krope.shape[2])
    rp = (-r) % 128
    hrp = (-hr) % 128
    np_ = (-n) % 8
    qc = jnp.concatenate([
        jnp.pad(q_lat[:, 0], ((0, 0), (0, np_), (0, rp))),
        jnp.pad(q_rope[:, 0], ((0, 0), (0, np_), (0, hrp)))], axis=-1)
    pc = jnp.concatenate([
        jnp.pad(pool_ckv, ((0, 0), (0, 0), (0, rp))),
        jnp.pad(pool_krope, ((0, 0), (0, 0), (0, hrp)))], axis=-1)
    tblc = jnp.clip(tbl, 0, n_pages - 1).astype(jnp.int32)
    out = _pattn.paged_mla_attention(
        qc, pc, tblc, pos.astype(jnp.int32), rank=r + rp, scale=scale,
        interpret=interpret)
    return out[:, :n, :r].reshape(b, 1, n, r)


def flash_attention_bshd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool | None = None):
    """q [B, Sq, Nq, H], k/v [B, Skv, Nkv, H] (GQA expanded here)."""
    interpret = _resolve_interpret(interpret, tpu_only=False)
    b, sq, nq, h = q.shape
    nkv = k.shape[2]
    if nkv != nq:
        rep = nq // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * nq, sq, h)
    kf = k.transpose(0, 2, 1, 3).reshape(b * nq, k.shape[1], h)
    vf = v.transpose(0, 2, 1, 3).reshape(b * nq, v.shape[1], h)
    bq = min(block_q, sq)
    pad = (-sq) % bq
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
    o = _attn.flash_attention(qf, kf, vf, causal=causal, window=window,
                              block_q=bq, block_k=block_k,
                              interpret=interpret)
    o = o[:, :sq]
    return o.reshape(b, nq, sq, h).transpose(0, 2, 1, 3)
