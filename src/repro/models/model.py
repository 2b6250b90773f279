"""The Model: plan-driven multi-family transformer with early exits.

Public surface used by training/serving/launch:

    m = Model(config, ctx=ShardCtx(mesh))
    params  = m.init(rng)                       # or jax.eval_shape(m.init, rng)
    out     = m.forward(params, batch)          # ModelOutputs
    cache   = m.init_decode_cache(batch, cache_len, window=...)
    logits, ee, cache = m.decode_step(params, cache, tokens, position)

Batch dict keys: "tokens" [B,S] int32 (always); "patch_embeds" [B,Tf,D] (vlm);
"frames" [B,Tenc,D] (encdec); "positions" optional.

Depth-segmented decode (the survey's edge-device paradigm made executable):
the plan compiles into ``decode_segments`` — runs of plan steps bounded by
exit heads.  The serving scheduler jits one stage per segment and dispatches
only the segments each token still needs:

    x          = m.embed_decode_tokens(params, tokens)
    x, cache   = m.decode_segment(params, cache, x, seg, pos, alive)
    entropy    = m.exit_probe_entropy(params, seg.exit_index, x)  # fused
    logits     = m.finalize_decode(params, x)

``alive`` [B] gates per-slot work: an exited slot's hidden state is frozen
(passthrough) and its KV/state rows are not written (the mask gates the row
write itself, in place); every slot's token is
produced by ``finalize_decode`` (final norm + LM head) over its — possibly
early-frozen — hidden state, CALM-style, so exit heads act purely as
entropy probes.  With no exits fired the segmented path is bit-identical to
the monolithic ``decode_step``.  Approximation note: a slot that exits at
depth d leaves zero-KV holes at layers deeper than d for that position
(SkipDecode-style); SSM/xLSTM states are simply not advanced there.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import blocks as B
from repro.models.common import apply_norm, embed, init_norm, normal_init, unembed
from repro.models.ffn import SINGLE, ShardCtx


@dataclasses.dataclass(frozen=True)
class DepthSegment:
    """A run of plan steps bounded by exit heads.

    ``steps`` are index-resolved plan entries — ("scan", kind, block_idx) or
    ("shared_attn", site_idx) — so a segment can be executed without
    re-walking the plan.  ``exit_index`` is the exit head probed after this
    segment (None for the final segment).  ``layers`` is the number of
    transformer layers the segment covers (pair units count as
    ``layer_period`` layers); it drives depth-weighted cost accounting.
    """
    index: int
    steps: Tuple[Tuple, ...]
    exit_index: Optional[int]
    layers: int
    layer_frac: float              # layers / num_layers


@dataclasses.dataclass
class ModelOutputs:
    logits: jnp.ndarray                   # [B,S,V] fp32
    exit_logits: List[jnp.ndarray]        # per exit head, [B,S,V] fp32
    aux_loss: jnp.ndarray                 # MoE load-balance scalar
    hidden: jnp.ndarray                   # final hidden [B,S,D]
    mtp_logits: Optional[jnp.ndarray] = None  # [B,S,V] (predicts t+2)


def _entropy(logits):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.exp(logp) * logp, axis=-1)


def _row_where(mask, axis):
    """Per-leaf row select: take ``new`` where ``mask`` along ``axis``
    (cache merging at admission)."""
    def f(new, old):
        shape = [1] * new.ndim
        shape[axis] = -1
        return jnp.where(mask.reshape(shape), new, old)
    return f


class Model:
    def __init__(self, cfg, ctx: ShardCtx = SINGLE, remat: bool = False):
        self.cfg = cfg
        self.ctx = ctx
        self.remat = remat
        self.plan = B.build_plan(cfg)
        # exits that survived plan construction (pair-family drops exits that
        # would split a (dense, moe) unit)
        self.n_exits = sum(1 for s in self.plan if s[0] == "exit")
        self.decode_segments = self._build_decode_segments()

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 8 + len(self.plan))
        params: Dict[str, Any] = {
            "embed": normal_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                 std=0.02, dtype=jnp.bfloat16),
            "final_norm": init_norm(cfg.norm, keys[1], cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = normal_init(
                keys[2], (cfg.vocab_size, cfg.d_model), std=0.02, dtype=jnp.bfloat16)
        blocks = []
        ki = 8
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                blocks.append(self._cast(B.init_scan_block(keys[ki], cfg, kind, n)))
                ki += 1
        params["blocks"] = blocks
        if cfg.shared_attn_period:
            params["shared_attn"] = self._cast(B.init_shared_attn(keys[3], cfg))
        if self.n_exits:
            eks = jax.random.split(keys[4], self.n_exits)
            params["exit_heads"] = [self._cast(B.init_exit_head(k, cfg))
                                    for k in eks]
        if cfg.family == "encdec":
            params["encoder"] = self._cast(
                B.init_scan_block(keys[5], cfg, "enc", cfg.encdec.num_encoder_layers))
            params["enc_norm"] = init_norm(cfg.norm, keys[5], cfg.d_model)
        if cfg.mtp_depth:
            params["mtp"] = self._cast(self._init_mtp(keys[6]))
        return params

    def _cast(self, tree):
        """Matmul weights -> bf16; norms/scalars stay fp32 (rank<=1)."""
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, tree)

    def _init_mtp(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        kind = "moe" if cfg.family == "moe" and cfg.moe.num_experts else "dense"
        return {
            "combine": normal_init(ks[0], (2 * cfg.d_model, cfg.d_model),
                                   std=0.02),
            "norm": init_norm(cfg.norm, ks[1], cfg.d_model),
            "layer": B.init_scan_block(ks[2], cfg, kind, 1),
            "kind_is_moe": jnp.zeros(()) if kind == "dense" else jnp.ones(()),
        }

    # ------------------------------------------------------------------
    # Positions
    # ------------------------------------------------------------------
    def positions_for(self, batch_size: int, seq_len: int,
                      frontend_tokens: int = 0, offset=0):
        cfg = self.cfg
        base = jnp.arange(seq_len, dtype=jnp.int32) + offset
        if cfg.rope != "mrope":
            return jnp.broadcast_to(base[None], (batch_size, seq_len))
        # M-RoPE: patches get (t=0, h,w grid); text continues at g + j
        tf = min(frontend_tokens, seq_len)
        g = int(math.ceil(math.sqrt(max(tf, 1))))
        idx = jnp.arange(seq_len, dtype=jnp.int32)
        is_text = idx >= tf
        t = jnp.where(is_text, g + idx - tf, 0)
        h = jnp.where(is_text, g + idx - tf, idx // max(g, 1))
        w = jnp.where(is_text, g + idx - tf, idx % max(g, 1))
        pos3 = jnp.stack([t, h, w])                        # [3,S]
        pos3 = pos3 + jnp.asarray(offset, jnp.int32)
        return jnp.broadcast_to(pos3[:, None], (3, batch_size, seq_len))

    # ------------------------------------------------------------------
    # Forward (train / prefill)
    # ------------------------------------------------------------------
    def embed_inputs(self, params, batch):
        cfg = self.cfg
        x = embed(batch["tokens"], params["embed"])
        if cfg.frontend == "vision_patches" and "patch_embeds" in batch:
            tf = batch["patch_embeds"].shape[1]
            x = jnp.concatenate(
                [batch["patch_embeds"].astype(x.dtype), x[:, tf:]], axis=1)
        return x

    def encode(self, params, frames):
        """Whisper encoder over stub frame embeddings [B,Tenc,D]."""
        cfg = self.cfg
        pos = self.positions_for(frames.shape[0], frames.shape[1])
        x, _ = B.run_scan_block(cfg, "enc", params["encoder"], frames, pos, 0,
                                self.ctx)
        return apply_norm(cfg.norm, x, params["enc_norm"])

    def forward(self, params, batch, *, long_mode: bool = False) -> ModelOutputs:
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        bsz, seq = batch["tokens"].shape
        window = self._window(long_mode)
        positions = batch.get("positions")
        if positions is None:
            tf = (batch["patch_embeds"].shape[1]
                  if (cfg.frontend == "vision_patches" and "patch_embeds" in batch)
                  else 0)
            positions = self.positions_for(bsz, seq, tf)
        enc_out = None
        if cfg.family == "encdec":
            enc_out = self.encode(params, batch["frames"])

        aux = jnp.float32(0.0)
        exit_logits: List[jnp.ndarray] = []
        bi = 0
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                x, a = B.run_scan_block(cfg, kind, params["blocks"][bi], x,
                                        positions, window, self.ctx,
                                        enc_out=enc_out, remat=self.remat)
                aux = aux + a
                bi += 1
            elif step[0] == "shared_attn":
                x = B.run_shared_attn(cfg, params["shared_attn"], x, positions,
                                      window)
            elif step[0] == "exit":
                _, ei, _ = step
                exit_logits.append(
                    B.exit_head_logits(cfg, params["exit_heads"][ei], x))

        h = apply_norm(cfg.norm, x, params["final_norm"])
        logits = unembed(h, params.get("lm_head", params["embed"]))
        mtp_logits = None
        if cfg.mtp_depth and "mtp" in params:
            mtp_logits = self._mtp_forward(params, h, batch, positions, window)
        return ModelOutputs(logits, exit_logits, aux, h, mtp_logits)

    def _mtp_forward(self, params, h, batch, positions, window):
        """DeepSeek-V3 MTP: combine final hidden with next-token embedding and
        run one extra block to predict token t+2."""
        cfg = self.cfg
        mp = params["mtp"]
        emb_next = embed(batch["tokens"], params["embed"])
        emb_next = jnp.roll(emb_next, -1, axis=1)          # embedding of t+1
        comb = jnp.concatenate([h, emb_next], axis=-1)
        x = comb @ mp["combine"].astype(h.dtype)
        kind = "moe" if cfg.family == "moe" and cfg.moe.num_experts else "dense"
        x, _ = B.run_scan_block(cfg, kind, mp["layer"], x, positions, window,
                                self.ctx)
        x = apply_norm(cfg.norm, x, mp["norm"])
        return unembed(x, params.get("lm_head", params["embed"]))

    def _window(self, long_mode: bool) -> int:
        cfg = self.cfg
        if cfg.attention == "sliding":
            return cfg.sliding_window
        if long_mode:
            return cfg.long_context_window
        return 0

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------
    def cache_len_for(self, seq_len: int, long_mode: bool) -> int:
        """Ring-buffer caches are window-sized; full caches are seq-sized."""
        w = self._window(long_mode)
        if self.cfg.family in ("ssm", "hybrid"):
            return min(seq_len, w) if w else seq_len       # attn sites only
        return min(seq_len, w) if w else seq_len

    def init_decode_cache(self, batch_size: int, seq_len: int,
                          *, long_mode: bool = False):
        cfg = self.cfg
        clen = self.cache_len_for(seq_len, long_mode)
        caches = []
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                per = [B.init_layer_cache(cfg, kind, batch_size, clen)
                       for _ in range(n)]
                caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        cache: Dict[str, Any] = {"blocks": caches}
        if cfg.shared_attn_period:
            n_sites = len(B.shared_attn_sites(cfg))
            hd = cfg.resolved_head_dim
            cache["shared_attn"] = [
                (jnp.zeros((batch_size, clen, cfg.num_kv_heads, hd), jnp.bfloat16),
                 jnp.zeros((batch_size, clen, cfg.num_kv_heads, hd), jnp.bfloat16))
                for _ in range(n_sites)]
        return cache

    def scan_block_kinds(self) -> List[str]:
        """Kind of each stacked block, in ``cache["blocks"]`` order."""
        return [s[1] for s in self.plan if s[0] == "scan"]

    def all_cache_paged(self) -> bool:
        """True iff every decode-cache leaf is pool-backed in paged mode —
        i.e. no SSM/xLSTM state rows.  Prefix-cache page skipping is only
        sound in this case (shared pages fully determine the replay)."""
        return all(k in B.PAGED_KINDS for k in self.scan_block_kinds())

    def init_decode_cache_paged(self, batch_size: int, n_pages: int,
                                page_size: int):
        """Paged decode cache: attention leaves become global pools stacked
        per scanned layer (``[n_layers, n_pages, P, ...]``; shared-attn
        pools are unstacked ``[n_pages, P, Nkv, H]``); SSM/xLSTM state
        leaves keep their per-slot rows.  Slots address the pools through
        the scheduler-owned block table, not a batch axis."""
        cfg = self.cfg
        assert cfg.family != "encdec", "paged decode: encdec unsupported"
        caches = []
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                per = [B.init_layer_cache_paged(cfg, kind, batch_size, n_pages,
                                                page_size)
                       for _ in range(n)]
                caches.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        cache: Dict[str, Any] = {"blocks": caches}
        if cfg.shared_attn_period:
            n_sites = len(B.shared_attn_sites(cfg))
            hd = cfg.resolved_head_dim
            cache["shared_attn"] = [
                (jnp.zeros((n_pages, page_size, cfg.num_kv_heads, hd),
                           jnp.bfloat16),
                 jnp.zeros((n_pages, page_size, cfg.num_kv_heads, hd),
                           jnp.bfloat16))
                for _ in range(n_sites)]
        return cache

    def merge_decode_cache(self, take_new, new_cache, old_cache):
        """Row-wise cache merge: slot b takes `new_cache` where take_new[b].

        Serves admissions only: the scheduler merges a freshly prefilled
        contiguous arena into freed slots once per admission, without
        touching in-flight slots.  Decode and prefill steps never merge:
        their write mask gates each row write in place (``decode_step``,
        ``decode_segment``), and paged arenas prefill straight into the
        pool.  Block caches are stacked [n_layers, B, ...] (batch axis 1);
        shared-attn caches are [B, ...] (batch axis 0).
        """
        out = {"blocks": [jax.tree.map(_row_where(take_new, 1), n, o)
                          for n, o in zip(new_cache["blocks"],
                                          old_cache["blocks"])]}
        if "shared_attn" in old_cache:
            out["shared_attn"] = [
                jax.tree.map(_row_where(take_new, 0), n, o)
                for n, o in zip(new_cache["shared_attn"],
                                old_cache["shared_attn"])]
        return out

    def decode_step(self, params, cache, tokens, position, *,
                    long_mode: bool = False, paged=None, write_mask=None):
        """tokens [B,1] int32; position [] int32 or [B] int32 (per-slot
        positions — continuous batching serves requests at different depths
        in one fixed-shape step).

        ``write_mask`` [B] bool gates every KV and state row write in place:
        a masked row writes nothing and its cache rows keep their values
        (None: every row writes).  paged != None (an
        ``attention.PagedKV``): attention caches are paged pools addressed
        through the bundled block table, and its ``write_mask`` is the mask.

        Returns (logits [B,V] fp32, exit_entropies [n_exits,B] fp32, cache).
        Exit entropies feed the early-exit policy in serving/engine.py.
        """
        cfg = self.cfg
        x = embed(tokens, params["embed"])
        window = self._window(long_mode)
        bsz = tokens.shape[0]
        if paged is not None:
            write_mask = paged.write_mask
        if cfg.rope == "mrope":
            # text token: all three components equal `position`
            pass  # handled inside attention via scalar positions
        aux = jnp.float32(0.0)
        exit_entropies = []
        new_blocks = []
        bi = 0
        sa_i = 0
        new_sa = list(cache.get("shared_attn", []))
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                x, nc, a = B.decode_scan_block(
                    cfg, kind, params["blocks"][bi], x, cache["blocks"][bi],
                    position, window, self.ctx, paged, write_mask)
                new_blocks.append(nc)
                aux = aux + a
                bi += 1
            elif step[0] == "shared_attn":
                x, nkv = B.run_shared_attn_decode(
                    cfg, params["shared_attn"], x, cache["shared_attn"][sa_i],
                    position, window, paged, write_mask)
                new_sa[sa_i] = nkv
                sa_i += 1
            elif step[0] == "exit":
                _, ei, _ = step
                lg = B.exit_head_logits(cfg, params["exit_heads"][ei], x)[:, 0]
                exit_entropies.append(_entropy(lg))
        h = apply_norm(cfg.norm, x, params["final_norm"])
        logits = unembed(h, params.get("lm_head", params["embed"]))[:, 0]
        new_cache = {"blocks": new_blocks}
        if cfg.shared_attn_period:
            new_cache["shared_attn"] = new_sa
        ee = (jnp.stack(exit_entropies) if exit_entropies
              else jnp.zeros((0, bsz), jnp.float32))
        return logits, ee, new_cache

    # ------------------------------------------------------------------
    # Depth-segmented decode (early exits truncate compute)
    # ------------------------------------------------------------------
    def _build_decode_segments(self) -> List[DepthSegment]:
        """Split the plan at exit heads into index-resolved depth segments."""
        cfg = self.cfg
        total = max(1, cfg.num_layers)
        segs: List[DepthSegment] = []
        steps: List[Tuple] = []
        layers = 0
        bi = sa_i = 0
        for step in self.plan:
            if step[0] == "scan":
                _, kind, n, _ = step
                steps.append(("scan", kind, bi))
                bi += 1
                per_unit = cfg.moe.layer_period if kind == "pair" else 1
                layers += n * per_unit
            elif step[0] == "shared_attn":
                steps.append(("shared_attn", sa_i))
                sa_i += 1
            elif step[0] == "exit":
                _, ei, _ = step
                segs.append(DepthSegment(len(segs), tuple(steps), ei,
                                         layers, layers / total))
                steps, layers = [], 0
        segs.append(DepthSegment(len(segs), tuple(steps), None,
                                 layers, layers / total))
        return segs

    def embed_decode_tokens(self, params, tokens):
        """tokens [B,1] int32 -> embeddings [B,1,D] (decode front-end)."""
        return embed(tokens, params["embed"])

    def decode_segment(self, params, cache, x, seg: DepthSegment, position,
                       alive, *, long_mode: bool = False, paged=None,
                       passthrough=None):
        """One-token decode through one depth segment.

        ``alive`` [B] bool gates per-slot effects: rows that already exited
        keep their hidden state (passthrough) and their cache rows are not
        written — ``alive`` is the write mask of every KV and state row
        write, which it gates in place (no select over the cache).  With
        ``alive`` all-true this is exactly the corresponding slice of the
        monolithic ``decode_step`` (bit-identical).  Returns ``(x, cache)``
        where ``cache`` is the full cache dict with only this segment's
        entries replaced.

        paged != None: attention leaves are pools whose writes are gated by
        ``paged.write_mask`` (the scheduler passes ``alive`` there too).
        ``passthrough``
        optionally decouples the HIDDEN-STATE passthrough mask from the
        cache-write mask: the scheduler passes ``alive = alive & active``
        (so stale slots never write pool pages or state rows) but keeps
        ``passthrough = alive`` — every row's hidden compute must stay
        identical to the unpaged path because MoE expert-capacity routing
        couples batch rows (a changed garbage row could evict a live row's
        token from an expert queue).
        """
        cfg = self.cfg
        window = self._window(long_mode)
        x_in = x
        if passthrough is None:
            passthrough = alive
        new_blocks = list(cache["blocks"])
        new_sa = list(cache.get("shared_attn", []))
        for st in seg.steps:
            if st[0] == "scan":
                _, kind, bi = st
                x, new_blocks[bi], _ = B.decode_scan_block(
                    cfg, kind, params["blocks"][bi], x, cache["blocks"][bi],
                    position, window, self.ctx, paged, alive)
            else:
                _, sa_i = st
                x, new_sa[sa_i] = B.run_shared_attn_decode(
                    cfg, params["shared_attn"], x, cache["shared_attn"][sa_i],
                    position, window, paged, alive)
        x = jnp.where(passthrough[:, None, None], x, x_in)
        out: Dict[str, Any] = {"blocks": new_blocks}
        if cfg.shared_attn_period:
            out["shared_attn"] = new_sa
        return x, out

    def exit_probe_entropy(self, params, exit_index: int, x):
        """Entropy of exit head ``exit_index`` over decode hidden x [B,1,D].

        Uses the fused Pallas ``exit_head_entropy`` kernel: the [B,V] exit
        logits are never materialized — vocab tiles stream through online
        softmax statistics and only the [B] entropy comes back.
        """
        from repro.kernels import ops as kops
        p = params["exit_heads"][exit_index]
        h = B.exit_head_hidden(self.cfg, p, x[:, 0, :])
        return kops.exit_head_entropy(h, p["w"])

    def finalize_decode(self, params, x):
        """Final norm + LM head over decode hidden x [B,1,D] -> [B,V] fp32.

        Every slot's token comes from here (CALM-style shared head): slots
        that exited early arrive with their hidden state frozen at the exit
        boundary.
        """
        h = apply_norm(self.cfg.norm, x, params["final_norm"])
        return unembed(h, params.get("lm_head", params["embed"]))[:, 0]

    # ------------------------------------------------------------------
    def prefill(self, params, batch, *, long_mode: bool = False):
        """Run forward and build a decode cache from the processed prompt.

        Used by examples/serving on small models.  Implemented by replaying
        tokens through decode_step (correct for every family, O(S) steps) —
        production prefill for attention archs uses forward() + cache import,
        here we keep the simple universally-correct path.
        """
        tokens = batch["tokens"]
        bsz, seq = tokens.shape
        cache = self.init_decode_cache(bsz, seq, long_mode=long_mode)

        def step(carry, t):
            cache = carry
            logits, _, cache = self.decode_step(
                params, cache, jax.lax.dynamic_slice_in_dim(tokens, t, 1, 1),
                t, long_mode=long_mode)
            return cache, logits

        cache, all_logits = jax.lax.scan(step, cache, jnp.arange(seq))
        return jnp.moveaxis(all_logits, 0, 1), cache
