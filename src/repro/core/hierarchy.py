"""Hierarchical (cloud-edge-device) distributed DNN — DDNN [65] + the
TPU-native staged execution of a partitioned model.

Planner side: `ddnn_placement` maps plan segments to a 3-tier hierarchy and
computes the communication-cost reduction that local (device-tier) exits buy
— the survey's Table 5 "communication cost reduction: 20x" claim.

Runtime side: `staged_forward` / `staged_decode_step` execute a partitioned
model across the mesh's "pod" axis: pod p computes only its assigned
segments (lax.cond on axis_index — real control-flow divergence, not
masking), and boundary activations cross pods via collective_permute, with
optional int8 feature compression (core.offload / kernels.feature_compress).
This is the executable form of the survey's Fig. 3/6 on TPU (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.cost_model import (CostGraph, DeviceProfile, LinkProfile,
                                   compute_time)
from repro.models import blocks as B
from repro.models.common import apply_norm, embed, unembed
from repro.models.ffn import ShardCtx


# ---------------------------------------------------------------------------
# DDNN placement (planner)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Tier:
    name: str                     # device | edge | cloud
    profile: DeviceProfile
    uplink: Optional[LinkProfile]  # link towards the next tier up


@dataclass(frozen=True)
class DDNNPlacement:
    tier_of_segment: Tuple[str, ...]
    local_exit_fraction: float    # fraction resolved at the device tier
    latency: float
    comm_bytes: float
    comm_bytes_cloud_only: float

    @property
    def comm_reduction(self) -> float:
        return self.comm_bytes_cloud_only / max(self.comm_bytes, 1e-9)


def ddnn_placement(graph: CostGraph, tiers: Sequence[Tier],
                   exit_probs: Sequence[float],
                   aggregate_factor: float = 64.0) -> DDNNPlacement:
    """Place segments greedily across tiers (device -> edge -> cloud) so each
    tier takes segments until its compute share balances its uplink cost;
    exits at tier boundaries resolve a fraction of inputs locally (DDNN's
    local/edge/cloud exits).

    `aggregate_factor`: DDNN ships the exit head's AGGREGATED feature across
    tier boundaries (max-pooled summaries, [65] "local aggregation"), not the
    raw activation map — tier-crossing bytes are out_bytes/aggregate_factor.
    This aggregation is what buys the paper's ~20x communication-cost
    reduction."""
    n = len(graph.segments)
    n_tiers = len(tiers)
    # boundaries: device gets segments up to the first exit, edge up to the
    # second, cloud the rest (DDNN's structure: one exit per tier boundary)
    exit_segs = [i for i, s in enumerate(graph.segments) if s.has_exit_after]
    b1 = exit_segs[0] + 1 if exit_segs else max(1, n // 3)
    b2 = exit_segs[1] + 1 if len(exit_segs) > 1 else max(b1 + 1, 2 * n // 3)
    tier_of = tuple(
        ("device" if i < b1 else ("edge" if i < b2 else "cloud"))
        for i in range(n))

    p_exit_dev = exit_probs[0] if exit_probs else 0.0
    p_exit_edge = exit_probs[1] if len(exit_probs) > 1 else 0.0
    dev, edge, cloud = tiers[0], tiers[min(1, n_tiers - 1)], tiers[-1]

    lat = 0.0
    comm = 0.0
    alive = 1.0
    for i, seg in enumerate(graph.segments):
        tier = {"device": dev, "edge": edge, "cloud": cloud}[tier_of[i]]
        lat += alive * compute_time(seg.flops, tier.profile)
        if i + 1 < n and tier_of[i] != tier_of[i + 1]:
            if tier_of[i] == "device":
                alive *= (1.0 - p_exit_dev)
                link = dev.uplink
            else:
                alive *= (1.0 - p_exit_edge)
                link = edge.uplink
            shipped = seg.out_bytes / aggregate_factor
            comm += alive * shipped
            lat += alive * link.tx_time(shipped)
    cloud_only = graph.input_bytes          # raw input straight to cloud
    return DDNNPlacement(tier_of, p_exit_dev, lat, comm, cloud_only)


# ---------------------------------------------------------------------------
# Staged execution across the pod axis (runtime)
# ---------------------------------------------------------------------------

def _quantize_int8(x):
    """Per-row symmetric int8 quantization of the boundary activation."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def staged_forward(model, params, batch, stage_of_block: Sequence[int],
                   mesh, *, compress_boundary: bool = False,
                   long_mode: bool = False):
    """Run the model partitioned across the `pod` mesh axis.

    stage_of_block[i] = pod index owning scan-block i (must be
    non-decreasing).  Exits/shared-attn run on the pod owning the preceding
    block.  Boundary activations cross pods via collective_permute (the
    survey's intermediate-feature transfer), optionally int8-compressed.

    Returns final logits (valid on the last stage's pods, replicated back).
    """
    cfg = model.cfg
    assert "pod" in mesh.axis_names, "staged execution needs a pod axis"
    n_pods = mesh.shape["pod"]
    stages = list(stage_of_block)
    assert all(b <= a for b, a in zip(stages, stages[1:])) or \
           all(a <= b for a, b in zip(stages, stages[1:])), "stages must be monotone"

    x0 = model.embed_inputs(params, batch)
    bsz, seq = batch["tokens"].shape
    tf = (batch["patch_embeds"].shape[1]
          if (cfg.frontend == "vision_patches" and "patch_embeds" in batch) else 0)
    positions = model.positions_for(bsz, seq, tf)
    window = model._window(long_mode)
    enc_out = model.encode(params, batch["frames"]) if cfg.family == "encdec" else None

    data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)

    def local_fn(x, positions, params, enc_out):
        my_pod = jax.lax.axis_index("pod")
        ctx = ShardCtx(None)     # inside shard_map: local compute only
        bi = 0
        for si, step in enumerate(model.plan):
            if step[0] == "scan":
                _, kind, n, _ = step
                owner = stages[bi]
                bp = params["blocks"][bi]

                def compute(x, bp=bp, kind=kind):
                    y, _ = B.run_scan_block(cfg, kind, bp, x, positions,
                                            window, ctx, enc_out=enc_out)
                    return y

                x = jax.lax.cond(my_pod == owner, compute, lambda x: x, x)
                # hand off to the next stage if ownership changes
                nxt = stages[bi + 1] if bi + 1 < len(stages) else owner
                if nxt != owner:
                    if compress_boundary:
                        q, s = _quantize_int8(x)
                        q = jax.lax.ppermute(q, "pod", [(owner, nxt)])
                        s = jax.lax.ppermute(s, "pod", [(owner, nxt)])
                        x = _dequantize_int8(q, s, x.dtype)
                    else:
                        x = jax.lax.ppermute(x, "pod", [(owner, nxt)])
                bi += 1
            elif step[0] == "shared_attn":
                owner = stages[min(bi, len(stages) - 1) - 1] if bi else stages[0]
                x = jax.lax.cond(
                    my_pod == owner,
                    lambda x: B.run_shared_attn(cfg, params["shared_attn"], x,
                                                positions, window),
                    lambda x: x, x)
            # exits are accounted by the planner; staged runtime skips heads
        # final head on the last stage, then broadcast result to all pods
        last = stages[-1]

        def head(x):
            h = apply_norm(cfg.norm, x, params["final_norm"])
            return unembed(h, params.get("lm_head", params["embed"]))

        logits = jax.lax.cond(my_pod == last, head,
                              lambda x: jnp.zeros(x.shape[:-1] + (cfg.vocab_size,),
                                                  jnp.float32), x)
        # replicate the result (psum over one-hot contribution)
        logits = jax.lax.psum(logits, "pod") / 1.0
        return logits

    dax = data_axes[0] if len(data_axes) == 1 else (data_axes or None)
    pos_spec = (P(None, dax, None) if positions.ndim == 3   # mrope [3,B,S]
                else P(dax, None))
    in_specs = (P(dax, None, None),
                pos_spec,
                jax.tree.map(lambda _: P(), params),
                (P(dax, None, None) if enc_out is not None else P()),
                )
    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=in_specs,
                       out_specs=P(dax, None, None),
                       check_vma=False)
    return fn(x0, positions, params, enc_out if enc_out is not None
              else jnp.zeros((), x0.dtype))
