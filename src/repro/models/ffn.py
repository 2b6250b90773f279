"""Feed-forward layers: gated dense FFN and expert-parallel MoE.

The MoE layer is a shard_map expert-parallel implementation adapted for TPU
meshes (DESIGN.md §2): tokens are sharded over the ("pod","data") axes and
replicated over "model"; routed experts are sharded over "model".  Each model
shard dispatches the tokens it sees into capacity-bounded buffers for ITS
local experts only (scatter-add, no all-to-all needed because tokens are
replicated along the expert axis), runs the expert FFNs as one batched
matmul, gathers back, and a single psum over "model" combines expert
contributions.  Overflowing tokens beyond capacity are dropped (standard
capacity-factor semantics).

A pure-jnp oracle (`moe_ffn_reference`) implements identical semantics for
tests.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, scaled_init


# ---------------------------------------------------------------------------
# Mesh context threaded through the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Ambient mesh info.  `None` mesh = single-device (tests/smoke)."""
    mesh: Optional[jax.sharding.Mesh] = None

    @property
    def data_axes(self) -> Tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def model_axis(self) -> Optional[str]:
        if self.mesh is None or "model" not in self.mesh.axis_names:
            return None
        return "model"

    @property
    def model_size(self) -> int:
        ax = self.model_axis
        return self.mesh.shape[ax] if ax else 1

    @property
    def data_size(self) -> int:
        if self.mesh is None:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.data_axes)


SINGLE = ShardCtx(None)


# ---------------------------------------------------------------------------
# Dense gated FFN
# ---------------------------------------------------------------------------

def init_ffn(key, d: int, ff: int, act: str):
    ks = jax.random.split(key, 3)
    if act == "silu":
        return {
            "w_gate": scaled_init(ks[0], (d, ff), d),
            "w_up": scaled_init(ks[1], (d, ff), d),
            "w_down": scaled_init(ks[2], (ff, d), ff),
        }
    return {
        "w_in": scaled_init(ks[0], (d, ff), d),
        "w_down": scaled_init(ks[2], (ff, d), ff),
    }


def ffn_forward(params, x, act: str):
    fn = activation(act)
    w = {k: v.astype(x.dtype) for k, v in params.items()}
    if "w_gate" in params:
        h = fn(x @ w["w_gate"]) * (x @ w["w_up"])
    else:
        h = fn(x @ w["w_in"])
    return h @ w["w_down"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(key, cfg):
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": scaled_init(ks[0], (d, m.num_experts), d),
        "wg": scaled_init(ks[1], (m.num_experts, d, fe), d),
        "wu": scaled_init(ks[2], (m.num_experts, d, fe), d),
        "wd": scaled_init(ks[3], (m.num_experts, fe, d), fe),
    }
    if m.num_shared_experts:
        p["shared"] = init_ffn(ks[4], d, fe * m.num_shared_experts, cfg.act)
    return p


def _capacity(tokens_local: int, num_experts: int, top_k: int, cf: float) -> int:
    return max(4, int(math.ceil(tokens_local * top_k * cf / num_experts)))


def _route(x2d, router_w, top_k: int):
    """Router: returns (gates [T,k] fp32, idx [T,k] int32, probs [T,E] fp32)."""
    logits = (x2d.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    if top_k > 1:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32), probs


# ---------------------------------------------------------------------------
# Serving-time W8A8 expert quantization (beyond-paper; EXPERIMENTS.md §Perf).
# The survey's feature-compression idea ([30],[51]) applied INSIDE the model:
# expert weights are stored int8 with per-(expert, out-channel) scales and the
# dispatched activations are quantized per-slot, so the expert matmuls run
# s8 x s8 -> s32 and weight HBM reads halve vs bf16.
# ---------------------------------------------------------------------------

def quantize_expert_weights(moe_params):
    """bf16 expert weights -> int8 + scales.  Keys wg/wu/wd -> *_q, *_s."""
    out = {k: v for k, v in moe_params.items() if k not in ("wg", "wu", "wd")}
    for k in ("wg", "wu", "wd"):
        w = moe_params[k].astype(jnp.float32)      # [..., E, in, out]
        s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-8)
        out[k + "_q"] = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
        out[k + "_s"] = s.astype(jnp.float32)              # [E, 1, out]
    return out


def _quant_rows(x):
    """Per-row symmetric int8: x [T, D] -> (q s8, scale f32 [T, 1])."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s


def _q_expert_matmul(ebuf, wq, ws):
    """W8A8 grouped matmul.  ebuf [E, C, d] float; wq [E, d, f] s8;
    ws [E, 1, f].  Returns fp32 [E, C, f]."""
    e, c, d = ebuf.shape
    aq, as_ = _quant_rows(ebuf.reshape(e * c, d))
    aq = aq.reshape(e, c, d)
    as_ = as_.reshape(e, c, 1)
    acc = jax.lax.dot_general(
        aq, wq, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)                  # [E, C, f]
    return acc.astype(jnp.float32) * as_ * ws


def _dispatch_compute_combine(x2d, gates, idx, weights, e0: int,
                              capacity: int, act: str):
    """Local-expert scatter -> batched expert FFN -> gather-combine.

    x2d [T,d]; gates/idx [T,k]; `weights` holds E_loc experts as either
    {"wg","wu","wd"} bf16 or the W8A8 form {"wg_q","wg_s",...}; e0 = first
    local expert id.  Returns this shard's partial output [T,d].
    """
    t, d = x2d.shape
    k = idx.shape[1]
    quant = "wg_q" in weights
    e_loc = (weights["wg_q"] if quant else weights["wg"]).shape[0]
    fn = activation(act)

    # slot for every (token, k) assignment; non-local / overflow -> trash row
    local = (idx >= e0) & (idx < e0 + e_loc)               # [T,k]
    le = jnp.where(local, idx - e0, e_loc)                 # E_loc = trash bucket
    onehot = jax.nn.one_hot(le, e_loc + 1, dtype=jnp.int32)  # [T,k,E_loc+1]
    # position of each assignment within its expert queue (global order T*k)
    flat_oh = onehot.reshape(t * k, e_loc + 1)
    pos = jnp.cumsum(flat_oh, axis=0) - flat_oh            # exclusive count
    pos_in_e = jnp.sum(pos * flat_oh, axis=-1).reshape(t, k)
    ok = local & (pos_in_e < capacity)
    slot = jnp.where(ok, le * capacity + pos_in_e, e_loc * capacity)  # [T,k]

    nrows = e_loc * capacity + 1
    buf = jnp.zeros((nrows, d), x2d.dtype)
    for j in range(k):                                     # k is small & static
        buf = buf.at[slot[:, j]].add(x2d, mode="drop")
    ebuf = buf[: e_loc * capacity].reshape(e_loc, capacity, d)

    if quant:
        h = fn(_q_expert_matmul(ebuf, weights["wg_q"], weights["wg_s"]))
        h = h * _q_expert_matmul(ebuf, weights["wu_q"], weights["wu_s"])
        out = _q_expert_matmul(h, weights["wd_q"], weights["wd_s"]).astype(x2d.dtype)
    else:
        wg, wu, wd = weights["wg"], weights["wu"], weights["wd"]
        h = fn(jnp.einsum("ecd,edf->ecf", ebuf, wg.astype(ebuf.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", ebuf, wu.astype(ebuf.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wd.astype(ebuf.dtype))
    flat = jnp.concatenate(
        [out.reshape(e_loc * capacity, d), jnp.zeros((1, d), out.dtype)], axis=0)

    y = jnp.zeros((t, d), jnp.float32)
    for j in range(k):
        y = y + flat[slot[:, j]].astype(jnp.float32) * gates[:, j:j + 1]
    return y.astype(x2d.dtype)


def moe_ffn_reference(params, x, cfg, tokens_for_capacity: Optional[int] = None):
    """Pure-jnp single-device oracle with identical dropping semantics."""
    m = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    cap = _capacity(tokens_for_capacity or b * s, m.num_experts, m.top_k,
                    m.capacity_factor)
    gates, idx, probs = _route(x2d, params["router"], m.top_k)
    y = _dispatch_compute_combine(x2d, gates, idx, params, 0, cap, cfg.act)
    if "shared" in params:
        y = y + ffn_forward(params["shared"], x2d, cfg.act)
    aux = _aux_loss(probs, idx, m.num_experts)
    return y.reshape(b, s, d), aux


def _aux_loss(probs, idx, num_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    k = idx.shape[-1]
    f = jnp.mean(
        jax.nn.one_hot(idx, num_experts, dtype=jnp.float32).sum(axis=-2), axis=0) / k
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def quantize_model_moe(params):
    """Walk a model param tree, replacing every MoE expert weight set with
    its W8A8 form (serving-time transform; training params untouched)."""
    def walk(node):
        if isinstance(node, dict):
            if "wg" in node and "router" in node:
                return quantize_expert_weights(
                    {k: walk(v) for k, v in node.items()})
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node
    return walk(params)


def moe_ffn(params, x, cfg, ctx: ShardCtx = SINGLE):
    """Expert-parallel MoE layer.  x [B,S,D] -> (y [B,S,D], aux_loss scalar)."""
    m = cfg.moe
    if ctx.mesh is None:
        return moe_ffn_reference(params, x, cfg)

    b, s, d = x.shape
    # batch not divisible by the data axes (e.g. long_500k batch=1):
    # replicate tokens over data instead of sharding them
    dax = ctx.data_axes if b % max(ctx.data_size, 1) == 0 else ()
    dsize = ctx.data_size if dax else 1
    t_local = (b // dsize) * s
    cap = _capacity(t_local, m.num_experts, m.top_k, m.capacity_factor)
    e_per_shard = m.num_experts // ctx.model_size
    max_ = ctx.model_axis

    wkeys = tuple(k for k in ("wg", "wu", "wd", "wg_q", "wg_s", "wu_q",
                              "wu_s", "wd_q", "wd_s") if k in params)

    def local_fn(xb, router_w, *ws):
        weights = dict(zip(wkeys, ws))
        bl, sl, _ = xb.shape
        x2d = xb.reshape(bl * sl, d)
        gates, idx, probs = _route(x2d, router_w, m.top_k)
        e0 = jax.lax.axis_index(max_) * e_per_shard
        y = _dispatch_compute_combine(x2d, gates, idx, weights, e0, cap, cfg.act)
        y = jax.lax.psum(y, max_)                          # combine expert shards
        aux = _aux_loss(probs, idx, m.num_experts)
        aux = jax.lax.pmean(aux, dax) if dax else aux
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=((P(dax or None, None, None), P(None, None))
                  + tuple(P(max_, None, None) for _ in wkeys)),
        out_specs=(P(dax or None, None, None), P()),
        check_vma=False,
    )(x, params["router"], *[params[k] for k in wkeys])

    if "shared" in params:
        y = y + ffn_forward(params["shared"], x, cfg.act)
    return y, aux
