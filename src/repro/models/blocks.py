"""Execution plan + block (layer-group) implementations.

Every architecture compiles to a PLAN: an ordered list of steps

    ("scan",  kind, n_units, layer0)   — lax.scan over n_units stacked layers
    ("shared_attn", site_idx)          — zamba2 weight-shared attention block
    ("exit", exit_idx, layer)          — early-exit head / partition boundary

Scan kinds: dense | moe | pair | mamba | mlstm | slstm | decx | enc.
Plan boundaries are exactly the survey's partition points: tier placement,
early exits and failure bypasses all operate on plan steps (core/*).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import apply_norm, init_norm, scaled_init
from repro.models.ffn import ShardCtx, SINGLE


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------

def layer_kind(cfg, i: int) -> str:
    if cfg.family in ("dense", "vlm"):
        return "dense"
    if cfg.family == "moe":
        m = cfg.moe
        if i < m.first_dense_layers:
            return "dense"
        if m.layer_period > 1:
            return "pair"              # grouped (dense, moe) unit
        return "moe"
    if cfg.family == "hybrid":
        return "mamba"
    if cfg.family == "ssm":
        return "slstm" if i in cfg.ssm.slstm_layers else "mlstm"
    if cfg.family == "encdec":
        return "decx"
    raise ValueError(cfg.family)


def shared_attn_sites(cfg) -> Tuple[int, ...]:
    if not cfg.shared_attn_period:
        return ()
    p = cfg.shared_attn_period
    return tuple(i for i in range(cfg.num_layers) if i % p == p - 1)


def build_plan(cfg) -> List[Tuple]:
    """Returns the ordered plan (see module docstring)."""
    L = cfg.num_layers
    exits = set(cfg.exits.exit_layers)
    sa = set(i + 1 for i in shared_attn_sites(cfg))        # boundary AFTER site
    # boundaries where a scan must break
    bounds = {0, L} | exits | sa
    for i in range(1, L):
        if layer_kind(cfg, i) != layer_kind(cfg, i - 1):
            bounds.add(i)
    if cfg.family == "moe" and cfg.moe.layer_period > 1:
        # pair units must not be split mid-unit
        period = cfg.moe.layer_period
        bounds = {b for b in bounds
                  if b <= cfg.moe.first_dense_layers or (b - cfg.moe.first_dense_layers) % period == 0
                  or b == L}
    bl = sorted(bounds)
    plan: List[Tuple] = []
    exit_idx = 0
    sa_idx = 0
    for a, b in zip(bl[:-1], bl[1:]):
        kind = layer_kind(cfg, a)
        n = b - a
        if kind == "pair":
            n = n // cfg.moe.layer_period
        plan.append(("scan", kind, n, a))
        if b in sa:
            plan.append(("shared_attn", sa_idx))
            sa_idx += 1
        if b in exits:
            plan.append(("exit", exit_idx, b))
            exit_idx += 1
    return plan


# ---------------------------------------------------------------------------
# Per-layer init by kind
# ---------------------------------------------------------------------------

def _init_dense_layer(key, cfg):
    ks = jax.random.split(key, 4)
    return {
        "ln1": init_norm(cfg.norm, ks[0], cfg.d_model),
        "attn": attn.init_attention(ks[1], cfg),
        "ln2": init_norm(cfg.norm, ks[2], cfg.d_model),
        "ffn": ffn_mod.init_ffn(ks[3], cfg.d_model, cfg.d_ff, cfg.act),
    }


def _init_moe_layer(key, cfg):
    ks = jax.random.split(key, 4)
    return {
        "ln1": init_norm(cfg.norm, ks[0], cfg.d_model),
        "attn": attn.init_attention(ks[1], cfg),
        "ln2": init_norm(cfg.norm, ks[2], cfg.d_model),
        "moe": ffn_mod.init_moe(ks[3], cfg),
    }


def _init_pair_unit(key, cfg):
    ka, kb = jax.random.split(key)
    return {"a": _init_dense_layer(ka, cfg), "b": _init_moe_layer(kb, cfg)}


def _init_mamba_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln": init_norm(cfg.norm, k1, cfg.d_model),
            "mamba": ssm_mod.init_mamba2(k2, cfg)}


def _init_mlstm_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln": init_norm(cfg.norm, k1, cfg.d_model),
            "mlstm": xlstm_mod.init_mlstm(k2, cfg)}


def _init_slstm_layer(key, cfg):
    k1, k2 = jax.random.split(key)
    return {"ln": init_norm(cfg.norm, k1, cfg.d_model),
            "slstm": xlstm_mod.init_slstm(k2, cfg)}


def _init_decx_layer(key, cfg):
    ks = jax.random.split(key, 6)
    return {
        "ln1": init_norm(cfg.norm, ks[0], cfg.d_model),
        "self_attn": attn.init_gqa(ks[1], cfg),
        "ln2": init_norm(cfg.norm, ks[2], cfg.d_model),
        "cross_attn": attn.init_gqa(ks[3], cfg),
        "ln3": init_norm(cfg.norm, ks[4], cfg.d_model),
        "ffn": ffn_mod.init_ffn(ks[5], cfg.d_model, cfg.d_ff, cfg.act),
    }


_INIT = {
    "dense": _init_dense_layer, "moe": _init_moe_layer, "pair": _init_pair_unit,
    "mamba": _init_mamba_layer, "mlstm": _init_mlstm_layer,
    "slstm": _init_slstm_layer, "decx": _init_decx_layer,
    "enc": _init_dense_layer,
}


def _bf16_once_stacked(a):
    """bf16 for leaves that become matrices when stacked (rank >= 1 per
    layer) — the same leaves ``Model._cast`` casts on the stacked tree."""
    return a.astype(jnp.bfloat16) if a.ndim >= 1 else a


def init_scan_block(key, cfg, kind: str, n_units: int):
    """Stacked params [n_units, ...] for a scanned block.  Each layer is
    cast to bf16 before stacking, so no f32 copy of the whole stack is ever
    held: at published widths that copy is several GB of device memory."""
    keys = jax.random.split(key, n_units)
    layers = [jax.tree.map(_bf16_once_stacked, _INIT[kind](k, cfg))
              for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_shared_attn(key, cfg):
    """zamba2 shared block: attention + FFN with own norms (ONE set of weights)."""
    ks = jax.random.split(key, 4)
    return {
        "ln1": init_norm(cfg.norm, ks[0], cfg.d_model),
        "attn": attn.init_gqa(ks[1], cfg),
        "ln2": init_norm(cfg.norm, ks[2], cfg.d_model),
        "ffn": ffn_mod.init_ffn(ks[3], cfg.d_model, cfg.d_ff, cfg.act),
    }


def init_exit_head(key, cfg):
    k1, k2 = jax.random.split(key)
    hid = cfg.exits.head_hidden
    p = {"norm": init_norm(cfg.norm, k1, cfg.d_model)}
    if hid:
        p["w_h"] = scaled_init(k1, (cfg.d_model, hid), cfg.d_model)
        p["w"] = scaled_init(k2, (hid, cfg.vocab_size), hid)
    else:
        p["w"] = scaled_init(k2, (cfg.d_model, cfg.vocab_size), cfg.d_model)
    return p


def exit_head_hidden(cfg, p, x):
    """The exit head's pre-vocab hidden state (norm + optional gelu MLP) —
    shared by the full-logits head and the fused entropy probe so the two
    paths cannot drift."""
    h = apply_norm(cfg.norm, x, p["norm"])
    if "w_h" in p:
        h = jax.nn.gelu(h @ p["w_h"].astype(h.dtype))
    return h


def exit_head_logits(cfg, p, x):
    h = exit_head_hidden(cfg, p, x)
    return jnp.einsum("...d,dv->...v", h, p["w"].astype(h.dtype)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward (full sequence) per kind
# ---------------------------------------------------------------------------

def _dense_fwd(cfg, lp, x, positions, window, ctx, causal=True):
    h = apply_norm(cfg.norm, x, lp["ln1"])
    if cfg.attention == "mla":
        y, _ = attn.mla_forward(cfg, lp["attn"], h, positions, causal=causal,
                                window=window)
    else:
        y, _ = attn.gqa_forward(cfg, lp["attn"], h, positions, causal=causal,
                                window=window)
    x = x + y
    h = apply_norm(cfg.norm, x, lp["ln2"])
    return x + ffn_mod.ffn_forward(lp["ffn"], h, cfg.act), jnp.float32(0.0)


def _moe_fwd(cfg, lp, x, positions, window, ctx):
    h = apply_norm(cfg.norm, x, lp["ln1"])
    if cfg.attention == "mla":
        y, _ = attn.mla_forward(cfg, lp["attn"], h, positions, window=window)
    else:
        y, _ = attn.gqa_forward(cfg, lp["attn"], h, positions, window=window)
    x = x + y
    h = apply_norm(cfg.norm, x, lp["ln2"])
    y, aux = ffn_mod.moe_ffn(lp["moe"], h, cfg, ctx)
    return x + y, aux


def _pair_fwd(cfg, lp, x, positions, window, ctx):
    x, _ = _dense_fwd(cfg, lp["a"], x, positions, window, ctx)
    return _moe_fwd(cfg, lp["b"], x, positions, window, ctx)


def _mamba_fwd(cfg, lp, x, positions, window, ctx):
    h = apply_norm(cfg.norm, x, lp["ln"])
    y, _ = ssm_mod.mamba2_forward(cfg, lp["mamba"], h)
    return x + y, jnp.float32(0.0)


def _mlstm_fwd(cfg, lp, x, positions, window, ctx):
    h = apply_norm(cfg.norm, x, lp["ln"])
    y, _ = xlstm_mod.mlstm_forward(cfg, lp["mlstm"], h)
    return x + y, jnp.float32(0.0)


def _slstm_fwd(cfg, lp, x, positions, window, ctx):
    h = apply_norm(cfg.norm, x, lp["ln"])
    y, _ = xlstm_mod.slstm_forward(cfg, lp["slstm"], h)
    return x + y, jnp.float32(0.0)


def _make_decx_fwd(enc_out):
    def f(cfg, lp, x, positions, window, ctx):
        h = apply_norm(cfg.norm, x, lp["ln1"])
        y, _ = attn.gqa_forward(cfg, lp["self_attn"], h, positions, causal=True,
                                window=window)
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln2"])
        y, _ = attn.gqa_forward(cfg, lp["cross_attn"], h, positions, kv_x=enc_out)
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln3"])
        return x + ffn_mod.ffn_forward(lp["ffn"], h, cfg.act), jnp.float32(0.0)
    return f


def _enc_fwd(cfg, lp, x, positions, window, ctx):
    return _dense_fwd(cfg, lp, x, positions, window, ctx, causal=False)


def run_scan_block(cfg, kind: str, bparams, x, positions, window, ctx,
                   enc_out=None, remat: bool = False):
    """Scan a stacked block over its layers.  Returns (x, aux_sum).

    remat=True wraps the per-layer body in jax.checkpoint (activation
    rematerialization) — used by the training path so the backward pass
    re-computes layer internals instead of saving them.
    """
    fwd = {
        "dense": _dense_fwd, "moe": _moe_fwd, "pair": _pair_fwd,
        "mamba": _mamba_fwd, "mlstm": _mlstm_fwd, "slstm": _slstm_fwd,
        "decx": _make_decx_fwd(enc_out), "enc": _enc_fwd,
    }[kind]

    def layer(lp, xx):
        return fwd(cfg, lp, xx, positions, window, ctx)

    if remat:
        layer = jax.checkpoint(layer)

    def body(carry, lp):
        xx, aux = layer(lp, carry)
        return xx, aux

    n = jax.tree.leaves(bparams)[0].shape[0]
    if n == 1:
        lp = jax.tree.map(lambda a: a[0], bparams)
        x, aux = layer(lp, x)
        return x, aux
    x, auxs = jax.lax.scan(body, x, bparams)
    return x, jnp.sum(auxs)


def run_shared_attn(cfg, sp, x, positions, window):
    h = apply_norm(cfg.norm, x, sp["ln1"])
    y, _ = attn.gqa_forward(cfg, sp["attn"], h, positions, causal=True,
                            window=window)
    x = x + y
    h = apply_norm(cfg.norm, x, sp["ln2"])
    return x + ffn_mod.ffn_forward(sp["ffn"], h, cfg.act)


# ---------------------------------------------------------------------------
# Decode (single token, cache-carrying) per kind
# ---------------------------------------------------------------------------

# Scan kinds whose decode cache is attention KV (paged-arena eligible).
# State kinds (mamba/mlstm/slstm) keep fixed per-slot rows; decx/enc never
# reach paged decode (scheduler asserts family != "encdec" in paged mode).
PAGED_KINDS = frozenset({"dense", "moe", "pair", "enc"})


def init_layer_cache_paged(cfg, kind: str, batch: int, n_pages: int,
                           page_size: int):
    """Paged decode cache for ONE layer: attention leaves become global
    pools ``[n_pages, P, ...]`` (indexed via the slot block table); state
    kinds keep their per-slot rows unchanged."""
    hd = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads
    kv = lambda: (jnp.zeros((n_pages, page_size, nkv, hd), jnp.bfloat16),
                  jnp.zeros((n_pages, page_size, nkv, hd), jnp.bfloat16))
    if kind in ("dense", "enc", "moe"):
        if cfg.attention == "mla":
            return (jnp.zeros((n_pages, page_size, cfg.kv_lora_rank),
                              jnp.bfloat16),
                    jnp.zeros((n_pages, page_size, cfg.qk_rope_head_dim),
                              jnp.bfloat16))
        return kv()
    if kind == "pair":
        return {"a": init_layer_cache_paged(cfg, "dense", batch, n_pages,
                                            page_size),
                "b": init_layer_cache_paged(cfg, "moe", batch, n_pages,
                                            page_size)}
    if kind in ("mamba", "mlstm", "slstm"):
        return init_layer_cache(cfg, kind, batch, 0)
    raise ValueError(f"kind {kind!r} has no paged decode cache")


def init_layer_cache(cfg, kind: str, batch: int, cache_len: int):
    """Decode cache for ONE layer of the given kind."""
    hd = cfg.resolved_head_dim
    nkv = cfg.num_kv_heads
    kv = lambda: (jnp.zeros((batch, cache_len, nkv, hd), jnp.bfloat16),
                  jnp.zeros((batch, cache_len, nkv, hd), jnp.bfloat16))
    if kind in ("dense", "enc"):
        if cfg.attention == "mla":
            return (jnp.zeros((batch, cache_len, cfg.kv_lora_rank), jnp.bfloat16),
                    jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), jnp.bfloat16))
        return kv()
    if kind == "moe":
        if cfg.attention == "mla":
            return (jnp.zeros((batch, cache_len, cfg.kv_lora_rank), jnp.bfloat16),
                    jnp.zeros((batch, cache_len, cfg.qk_rope_head_dim), jnp.bfloat16))
        return kv()
    if kind == "pair":
        return {"a": init_layer_cache(cfg, "dense", batch, cache_len),
                "b": init_layer_cache(cfg, "moe", batch, cache_len)}
    if kind == "mamba":
        return ssm_mod.init_mamba2_state(cfg, batch)
    if kind == "mlstm":
        return xlstm_mod.init_mlstm_state(cfg, batch)
    if kind == "slstm":
        return xlstm_mod.init_slstm_state(cfg, batch)
    if kind == "decx":
        enc_len = cfg.encdec.encoder_seq_len
        return {"self": kv(),
                "cross": (jnp.zeros((batch, enc_len, nkv, hd), jnp.bfloat16),
                          jnp.zeros((batch, enc_len, nkv, hd), jnp.bfloat16))}
    raise ValueError(kind)


def _attn_decode_dispatch(cfg, lp_attn, h, cache, layer, position, window,
                          paged, write_mask):
    if paged is not None:
        fn = attn.mla_decode_paged if cfg.attention == "mla" \
            else attn.gqa_decode_paged
        return fn(cfg, lp_attn, h, cache[0], cache[1], position, paged, layer)
    fn = attn.mla_decode if cfg.attention == "mla" else attn.gqa_decode
    return fn(cfg, lp_attn, h, cache[0], cache[1], position, window=window,
              layer=layer, write_mask=write_mask)


def _put_state(stacks, layer, new, write_mask):
    """Write one layer's new recurrent state rows ``[B, ...]`` into their
    stacks ``[n_layers, B, ...]``; rows with ``write_mask`` False keep
    their old state (the select covers one layer's small state rows)."""
    def put(stack, n):
        if write_mask is not None:
            old = attn.layer_view(stack, layer)
            n = jnp.where(write_mask.reshape((-1,) + (1,) * (n.ndim - 1)),
                          n, old)
        return jax.lax.dynamic_update_index_in_dim(stack, n, layer, 0)
    return jax.tree.map(put, stacks, new)


def decode_layer(cfg, kind: str, lp, x, caches, layer, position, window, ctx,
                 paged=None, write_mask=None):
    """One-token decode through layer ``layer`` of a stacked block.
    ``caches`` are the block's whole stacked caches ``[n_layers, ...]``;
    the layer's rows are written into them in place.  Returns
    (x, caches, aux).

    ``write_mask`` [B] bool (None: every row writes) gates the writes
    themselves: a masked row's KV write is dropped and its state rows keep
    their old value.  paged != None (an ``attn.PagedKV``): attention caches
    are paged pools gated by ``paged.write_mask``, which callers pass as
    ``write_mask`` too."""
    zero = jnp.float32(0.0)
    if kind in ("dense", "enc"):
        h = apply_norm(cfg.norm, x, lp["ln1"])
        y, new = _attn_decode_dispatch(cfg, lp["attn"], h, caches, layer,
                                       position, window, paged, write_mask)
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln2"])
        return x + ffn_mod.ffn_forward(lp["ffn"], h, cfg.act), new, zero
    if kind == "moe":
        h = apply_norm(cfg.norm, x, lp["ln1"])
        y, new = _attn_decode_dispatch(cfg, lp["attn"], h, caches, layer,
                                       position, window, paged, write_mask)
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln2"])
        y, aux = ffn_mod.moe_ffn(lp["moe"], h, cfg, ctx)
        return x + y, new, aux
    if kind == "pair":
        x, new_a, _ = decode_layer(cfg, "dense", lp["a"], x, caches["a"],
                                   layer, position, window, ctx, paged,
                                   write_mask)
        x, new_b, aux = decode_layer(cfg, "moe", lp["b"], x, caches["b"],
                                     layer, position, window, ctx, paged,
                                     write_mask)
        return x, {"a": new_a, "b": new_b}, aux
    if kind in ("mamba", "mlstm", "slstm"):
        cache = jax.tree.map(lambda a: attn.layer_view(a, layer), caches)
        h = apply_norm(cfg.norm, x, lp["ln"])
        if kind == "mamba":
            y, st, cv = ssm_mod.mamba2_decode(cfg, lp["mamba"], h, cache[0],
                                              cache[1])
            new = (st, cv)
        elif kind == "mlstm":
            y, new = xlstm_mod.mlstm_decode(cfg, lp["mlstm"], h, cache)
        else:
            y, new = xlstm_mod.slstm_decode(cfg, lp["slstm"], h, cache)
        return x + y, _put_state(caches, layer, new, write_mask), zero
    if kind == "decx":
        h = apply_norm(cfg.norm, x, lp["ln1"])
        y, new_self = attn.gqa_decode(
            cfg, lp["self_attn"], h, caches["self"][0], caches["self"][1],
            position, window=window, layer=layer, write_mask=write_mask)
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln2"])
        cross = jax.tree.map(lambda a: attn.layer_view(a, layer),
                             caches["cross"])
        y = attn.cross_decode(cfg, lp["cross_attn"], h, cross[0], cross[1])
        x = x + y
        h = apply_norm(cfg.norm, x, lp["ln3"])
        return (x + ffn_mod.ffn_forward(lp["ffn"], h, cfg.act),
                {"self": new_self, "cross": caches["cross"]}, zero)
    raise ValueError(kind)


def decode_scan_block(cfg, kind: str, bparams, x, caches, position, window, ctx,
                      paged=None, write_mask=None):
    """Decode through a stacked block, scanning its layers.

    The stacked caches ride in the scan's carry and each layer writes its
    rows into them in place (``decode_layer``), so no per-layer cache copy
    and no fresh stacked output is built: with the caches donated, a step
    touches only the rows it writes.  ``write_mask`` [B] gates those
    writes themselves (see ``decode_layer``); callers need no merge.
    Paged attention caches are stacked pools ``[n, n_pages, P, ...]``
    addressed the same way; the block table (inside ``paged``) is shared
    across layers."""
    n = jax.tree.leaves(bparams)[0].shape[0]
    if n == 1:
        lp = jax.tree.map(lambda a: a[0], bparams)
        return decode_layer(cfg, kind, lp, x, caches, 0, position, window,
                            ctx, paged, write_mask)

    def body(carry, inp):
        xx, cc = carry
        lp, i = inp
        xx, cc, aux = decode_layer(cfg, kind, lp, xx, cc, i, position, window,
                                   ctx, paged, write_mask)
        return (xx, cc), aux

    (x, caches), auxs = jax.lax.scan(body, (x, caches),
                                     (bparams, jnp.arange(n)))
    return x, caches, jnp.sum(auxs)


def run_shared_attn_decode(cfg, sp, x, cache, position, window, paged=None,
                           write_mask=None):
    """The shared attention block over its unstacked cache; writes gated as
    in ``decode_layer``."""
    h = apply_norm(cfg.norm, x, sp["ln1"])
    if paged is not None:
        y, new = attn.gqa_decode_paged(cfg, sp["attn"], h, cache[0], cache[1],
                                       position, paged)
    else:
        y, new = attn.gqa_decode(cfg, sp["attn"], h, cache[0], cache[1],
                                 position, window=window,
                                 write_mask=write_mask)
    x = x + y
    h = apply_norm(cfg.norm, x, sp["ln2"])
    return x + ffn_mod.ffn_forward(sp["ffn"], h, cfg.act), new
