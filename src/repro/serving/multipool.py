"""Multi-model slot pools: one scheduler multiplexing heterogeneous models.

The survey's tiers are not single-model: an edge node serves a zoo of
heterogeneous DNNs concurrently (§6.3 dynamic task allocation; Zhou et al.'s
multi-tenant edge serving).  This module is that runtime: a ``ModelGroup``
of named ``(model, params)`` entries — e.g. an attention smoke arch, an SSM
smoke arch, and a shared-attention hybrid — served by ONE
``MultiModelScheduler`` behind one queue and one ``poll()`` loop.

Design:

* **Per-model arenas.**  Each named entry owns a full single-model
  ``ContinuousBatchScheduler``: its own fixed-shape KV/state cache arena,
  its own jitted prefill/segment/probe/finalize stages, and its own
  device-side exit counters.  Models never share device buffers, so the
  no-recompile invariant holds *per model*: ``jit_cache_sizes()`` stays
  <= 1 per stage per model under arbitrary slot churn, and each model's
  outputs are bit-identical to a dedicated single-model scheduler fed the
  same requests (greedy and rng-seeded sampling alike — per-arena rng fold
  counters advance exactly as they would alone).
* **One queue, one poll.**  ``submit()`` takes a ``Request`` whose
  ``model`` field names the arena ("" = the group's first entry);
  ``poll()`` rounds over the arenas and returns one unified ``StepReport``
  whose ``per_model`` dict carries the per-arena sub-reports (external
  drivers — the tiered cluster — charge per-model step costs from those).
* **Cross-model prefill fairness.**  ``cfg.max_prefill_chunks_per_step``
  is a POOL-WIDE budget: one poll runs at most that many prefill chunks
  summed over every model, handed out round-robin (rotating first claim),
  so one model's long admission cannot starve another model's decode —
  the same knob that already arbitrates prefill vs decode now also
  arbitrates model vs model.

Typical use::

    group = ModelGroup([("attn", model_a, params_a),
                        ("ssm",  model_b, params_b)])
    pool = MultiModelScheduler(group, SchedulerConfig(n_slots=4))
    pool.submit(Request(tokens=p1, max_new=16, model="attn"))
    pool.submit(Request(tokens=p2, max_new=16, model="ssm"))
    pool.run()
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.serving.scheduler import (ContinuousBatchScheduler, Request,
                                     SchedulerConfig, StageSpec, StepReport)


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """One named model of a group."""
    name: str
    model: Any
    params: Any


class ModelGroup:
    """An ordered, named collection of ``(model, params)`` entries.

    Accepts ``(name, model, params)`` tuples or ``ModelEntry`` instances.
    The first entry is the group's default model (what ``Request.model=""``
    resolves to).
    """

    def __init__(self, entries: Sequence):
        ents: List[ModelEntry] = []
        for e in entries:
            ents.append(e if isinstance(e, ModelEntry) else ModelEntry(*e))
        assert ents, "empty ModelGroup"
        names = [e.name for e in ents]
        assert len(set(names)) == len(names), f"duplicate names: {names}"
        self._entries: Dict[str, ModelEntry] = {e.name: e for e in ents}

    @property
    def names(self) -> List[str]:
        return list(self._entries)

    @property
    def default(self) -> str:
        return next(iter(self._entries))

    def resolve(self, name: str) -> str:
        """Map a request's model key to an entry name ("" = default)."""
        if not name:
            return self.default
        assert name in self._entries, \
            f"unknown model {name!r} (group has {self.names})"
        return name

    def __len__(self) -> int:
        return len(self._entries)

    def __iter__(self) -> Iterator[ModelEntry]:
        return iter(self._entries.values())

    def __getitem__(self, name: str) -> ModelEntry:
        return self._entries[name]

    def __contains__(self, name: str) -> bool:
        return name in self._entries


class MultiModelScheduler:
    """One serving pool multiplexing the arenas of a ``ModelGroup``.

    Mirrors the single-model ``ContinuousBatchScheduler`` surface that
    external drivers use — ``submit`` / ``poll`` / ``run`` / ``has_work`` /
    ``completed`` / ``flush_counters`` / ``exit_stats`` /
    ``jit_cache_sizes`` — so the tiered cluster and the serving engine can
    drive either interchangeably.

    ``slots_per_model`` overrides ``cfg.n_slots`` per entry (the tiered
    cluster derives per-model slot counts from each model's KV arena size);
    ``controllers`` installs an adaptive exit controller per model name.
    """

    def __init__(self, group: ModelGroup,
                 cfg: SchedulerConfig = SchedulerConfig(),
                 slots_per_model: Optional[Dict[str, int]] = None,
                 controllers: Optional[Dict[str, Any]] = None):
        self.group = group
        self.cfg = cfg
        self.pools: Dict[str, ContinuousBatchScheduler] = {}
        for e in group:
            pcfg = cfg
            if slots_per_model and e.name in slots_per_model:
                pcfg = dataclasses.replace(cfg,
                                           n_slots=slots_per_model[e.name])
            self.pools[e.name] = ContinuousBatchScheduler(
                e.model, e.params, pcfg,
                controller=(controllers or {}).get(e.name))
        self.completed: List[Request] = []
        self.n_submitted = 0
        self._rr = 0                   # rotating first claim on the budget

    # ------------------------------------------------------------------
    # public API (drop-in for ContinuousBatchScheduler)
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        """Enqueue one request on its model's arena (``req.model`` names the
        entry; "" = the group's default)."""
        req.model = self.group.resolve(req.model)
        if req.req_id < 0:
            req.req_id = self.n_submitted
        self.n_submitted += 1
        self.pools[req.model].submit(req)

    def set_rng(self, rng):
        """Install one sampling rng into every arena and reset their per-run
        fold counters — each arena then samples exactly as a dedicated
        single-model scheduler given the same rng would."""
        for pool in self.pools.values():
            pool.set_rng(rng)

    @property
    def has_work(self) -> bool:
        return any(p.has_work for p in self.pools.values())

    @property
    def tokens_served(self) -> int:
        return sum(p.tokens_served for p in self.pools.values())

    @property
    def depth_weighted_tokens(self) -> float:
        return sum(p.depth_weighted_tokens for p in self.pools.values())

    @property
    def host_ms_total(self) -> float:
        return sum(p.host_ms_total for p in self.pools.values())

    @property
    def device_ms_total(self) -> float:
        return sum(p.device_ms_total for p in self.pools.values())

    @property
    def peak_tokens_in_flight(self) -> int:
        return max(p.peak_tokens_in_flight for p in self.pools.values())

    def poll(self) -> StepReport:
        """One pool round: each arena admits / prefills / decodes once,
        sharing the pool-wide prefill budget round-robin.  Returns one
        aggregate ``StepReport`` with the per-model sub-reports attached."""
        rep = StepReport()
        budget = self.cfg.max_prefill_chunks_per_step
        names = list(self.pools)
        start = self._rr % len(names)
        self._rr += 1
        used = 0
        active_depth = 0.0
        for name in names[start:] + names[:start]:
            pool = self.pools[name]
            if not pool.has_work:
                continue
            if budget <= 0:            # unbounded per arena (the default)
                sub = pool.poll()
            else:
                sub = pool.poll(prefill_budget=max(0, budget - used))
                used += sub.prefill_chunks
            rep.per_model[name] = sub
            rep.admitted += sub.admitted
            rep.prefill_chunks += sub.prefill_chunks
            rep.prefill_tokens += sub.prefill_tokens
            rep.prefill_done = rep.prefill_done or sub.prefill_done
            rep.decode_stepped = rep.decode_stepped or sub.decode_stepped
            rep.n_active += sub.n_active
            rep.decode_segments_run += sub.decode_segments_run
            # async decode: steps committed is a per-round gauge (max over
            # arenas — they commit in parallel rounds), dispatches/time
            # splits/in-flight tokens are additive device+host work
            rep.decode_steps = max(rep.decode_steps, sub.decode_steps)
            rep.decode_dispatched += sub.decode_dispatched
            rep.host_ms += sub.host_ms
            rep.device_ms += sub.device_ms
            rep.syncs += sub.syncs
            rep.tokens_in_flight += sub.tokens_in_flight
            active_depth += sub.decode_depth_frac * sub.n_active
            rep.completed += sub.completed
        if rep.n_active:               # active-slot-weighted mean depth
            rep.decode_depth_frac = active_depth / rep.n_active
        self.completed += rep.completed
        return rep

    def tick(self) -> bool:
        return self.poll().worked

    def sync(self) -> List[Request]:
        """Drain every arena's async decode pipeline (no-op for sync
        arenas).  Returns the requests the drain completed — like the
        single-pool ``sync()``, the caller must stamp them itself."""
        out: List[Request] = []
        for pool in self.pools.values():
            out += pool.sync()
        self.completed += out
        return out

    # ------------------------------------------------------------------
    # slot migration (delegates to the named arena — snapshots carry their
    # model name, so import routes itself)
    # ------------------------------------------------------------------
    def export_slot(self, slot: int, *, model: str = "",
                    compress: bool = False, skip_keys=frozenset()):
        return self.pools[self.group.resolve(model)].export_slot(
            slot, compress=compress, skip_keys=skip_keys)

    def import_slot(self, snap) -> int:
        return self.pools[self.group.resolve(snap.model)].import_slot(snap)

    def prefix_keys(self, model: str = ""):
        """Prefix-tree digest keys of the named arena (page-granular
        migration: a source skips pages this pool already caches)."""
        return self.pools[self.group.resolve(model)].prefix_keys()

    def slot_payload_bytes(self, slot: int, *, model: str = "") -> int:
        return self.pools[self.group.resolve(model)].slot_payload_bytes(slot)

    def free_slots(self, model: str = ""):
        return self.pools[self.group.resolve(model)].free_slots()

    def active_requests(self):
        """``[(model, slot, request)]`` across every arena."""
        out = []
        for name, pool in self.pools.items():
            out += [(name, slot, r) for _, slot, r in pool.active_requests()]
        return out

    def release_slot(self, slot: int, *, model: str = ""):
        return self.pools[self.group.resolve(model)].release_slot(slot)

    def drain_queue(self):
        out = []
        for pool in self.pools.values():
            out += pool.drain_queue()
        return out

    def cancel_pending(self):
        out = []
        for pool in self.pools.values():
            out += pool.cancel_pending()
        return out

    def run(self, rng=None):
        """Drain the queue and every arena to completion."""
        self.set_rng(rng)
        while self.has_work:
            if not self.poll().worked:  # pragma: no cover - defensive
                break
        self.flush_counters()

    # ------------------------------------------------------------------
    # statistics (per-model isolation is the point — no cross-model sums
    # except the explicit aggregates above)
    # ------------------------------------------------------------------
    def flush_counters(self) -> Dict[str, Any]:
        return {n: p.flush_counters() for n, p in self.pools.items()}

    def reset_stats(self):
        for p in self.pools.values():
            p.reset_stats()
        self.completed.clear()

    def measured_depth_fraction(self) -> float:
        served = self.tokens_served
        if not served:
            return 1.0
        return self.depth_weighted_tokens / served

    def exit_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-model exit statistics (counters are per-arena, on device)."""
        return {n: p.exit_stats() for n, p in self.pools.items()}

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Flattened ``"model/stage" -> compile count``: int values so the
        existing <=1-per-entry assertions work unchanged, per-model bounds
        still visible."""
        out: Dict[str, int] = {}
        for name, pool in self.pools.items():
            for stage, v in pool.jit_cache_sizes().items():
                out[f"{name}/{stage}"] = v
        return out

    def audit_stages(self) -> Dict[str, StageSpec]:
        """Flattened ``"model/stage" -> StageSpec`` over every arena, for
        the jaxpr auditor — same key scheme as ``jit_cache_sizes``."""
        out: Dict[str, StageSpec] = {}
        for name, pool in self.pools.items():
            for stage, spec in pool.audit_stages().items():
                key = f"{name}/{stage}"
                out[key] = dataclasses.replace(spec, name=key)
        return out


class SpecPair(MultiModelScheduler):
    """Speculative-decoding mode of the multi-model pool: a two-entry
    ``ModelGroup`` whose FIRST entry is the draft model and SECOND the
    target.  Every request is served by the target arena; the draft arena
    mirrors it with a shadow request, autoregressively proposes a k-token
    window each round (one gated jitted scan), and the target verifies all
    k positions in one batched dispatch, committing the longest accepted
    prefix + one corrected (or bonus) token.

    Losslessness: commits are the target's own full-depth argmax, so the
    output streams are **bit-identical to target-only greedy decode** —
    speculation changes the schedule, never the tokens.  That contract
    forces two config-time rejections: ``temperature > 0`` (sampled
    streams are not stable under re-batched rng folds — the verify would
    silently degrade to greedy) and ``exit_threshold > 0`` (verify always
    runs full depth, so early-exit outputs would diverge).

    The draft arena is restricted to models whose cache leaves are all
    position-indexed (``all_cache_paged()``): after a rejection the draft's
    stale rows past the accept point are simply overwritten before any
    read reaches them, whereas a sequential SSM/xLSTM state could not be
    rewound.  The target has no such restriction — its verify scan gates
    every write by the on-device accept mask, so rejected positions are
    never written in the first place (no rollback pass, valid for every
    arena kind, paged or contiguous).
    """

    def __init__(self, group: ModelGroup,
                 cfg: SchedulerConfig = SchedulerConfig(),
                 *, k: int = 4,
                 slots_per_model: Optional[Dict[str, int]] = None,
                 controllers: Optional[Dict[str, Any]] = None):
        if len(group) != 2:
            raise ValueError(f"SpecPair needs exactly 2 models "
                             f"(draft, target), got {group.names}")
        if cfg.temperature > 0.0:
            raise ValueError(
                "SpecPair + temperature>0 is rejected at config time: "
                "lossless speculation verifies the target's ARGMAX, so a "
                "sampled stream would silently degrade to greedy instead "
                "of matching the target's rng stream. Use temperature=0, "
                "or serve sampled traffic through a plain pool.")
        if cfg.exit_threshold > 0.0:
            raise ValueError(
                "SpecPair + exit_threshold>0 is rejected at config time: "
                "the verify stage always runs the target at full depth, "
                "so early-exited target-only output would diverge from "
                "the speculative stream. Use exit_threshold=0.")
        if cfg.async_decode:
            raise ValueError(
                "SpecPair + async_decode is rejected at config time: the "
                "propose/verify round is host-lockstep by construction "
                "(the draft window feeds the same round's verify), so "
                "deferred-readback windows cannot overlap it. Speculative "
                "pairs keep the synchronous poll cadence.")
        if k < 2:
            raise ValueError(f"SpecPair window k must be >= 2, got {k}")
        # SpecPair arenas always run the monolithic decode_step: verify is a
        # scan of exactly that step, so the full bit-parity chain (verify
        # scan == step() == target-only reference) holds only on the
        # monolithic path.  Segmentation exists for early exits, which the
        # exit_threshold==0 contract above already forbids — the segmented
        # pipeline's jit-boundary bf16 rounding drifts from the fused scan
        # at the KV-cache bit level, which is why this is forced rather
        # than left to the caller.
        cfg = dataclasses.replace(cfg, segmented=False)
        super().__init__(group, cfg, slots_per_model=slots_per_model,
                         controllers=controllers)
        self.draft_name, self.target_name = group.names
        draft_model = group[self.draft_name].model
        if not draft_model.all_cache_paged():
            raise ValueError(
                f"SpecPair draft model {self.draft_name!r} has sequential "
                "state cache leaves (SSM/conv/xLSTM); a rejected window "
                "cannot rewind them. Use a position-indexed-cache (pure "
                "attention / MLA) draft; the TARGET may be any arch.")
        self.k = k
        for pool in self.pools.values():
            pool.ensure_spec(k)
        # req_id -> (target request, draft shadow request)
        self._pairs: Dict[int, Tuple[Request, Request]] = {}
        # slot-rounds: one per (request, verify round) — the denominator of
        # the acceptance length.  The pool-level round counter alone would
        # inflate acceptance when several slots share a verify dispatch.
        self.slot_rounds = 0

    # ------------------------------------------------------------------
    # submission: every request runs on the target; the draft mirrors it
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        assert req.frames is None, "SpecPair: encdec requests unsupported"
        req.model = self.target_name
        if req.req_id < 0:
            req.req_id = self.n_submitted
        self.n_submitted += 1
        shadow = Request(tokens=np.asarray(req.tokens).reshape(-1),
                         max_new=req.max_new, eos_id=req.eos_id,
                         req_id=req.req_id, model=self.draft_name)
        self._pairs[req.req_id] = (req, shadow)
        self.pools[self.target_name].submit(req)
        self.pools[self.draft_name].submit(shadow)

    # ------------------------------------------------------------------
    # the speculation round
    # ------------------------------------------------------------------
    def _reap(self):
        """Release draft slots whose target request has finished.  A shadow
        still inside a staged prefill cannot be released mid-flight (the
        pending admission would re-activate the freed slot); it is reaped
        on a later poll, once live."""
        drf = self.pools[self.draft_name]
        for rid in list(self._pairs):
            req, shadow = self._pairs[rid]
            if not req.done:
                continue
            if shadow.slot >= 0 and drf.slot_req[shadow.slot] is shadow:
                if not drf.active[shadow.slot]:
                    continue           # staged mid-prefill: reap later
                drf.release_slot(shadow.slot)
            elif shadow in drf.queue:
                drf.queue.remove(shadow)
            del self._pairs[rid]

    def _live_pairs(self) -> List[Tuple[int, int]]:
        """(target_slot, draft_slot) for every request live in BOTH arenas
        — a target slot whose draft mirror is still prefilling waits."""
        tgt = self.pools[self.target_name]
        drf = self.pools[self.draft_name]
        out = []
        for req, shadow in self._pairs.values():
            if (req.slot >= 0 and tgt.active[req.slot]
                    and shadow.slot >= 0 and drf.active[shadow.slot]):
                out.append((req.slot, shadow.slot))
        return out

    def poll(self) -> StepReport:
        """One pool round: both arenas admit/prefill under the shared
        budget, then one speculation round runs — draft proposes its
        window in one jitted scan, target verifies it in one batched
        dispatch and commits.  ``per_model`` carries the draft/target
        sub-reports with the propose/verify accounting split the way
        external drivers (the tiered cluster) charge it."""
        tgt = self.pools[self.target_name]
        drf = self.pools[self.draft_name]
        rep = StepReport()
        budget = self.cfg.max_prefill_chunks_per_step
        sub_t = tgt.prefill_poll(None if budget <= 0 else budget)
        sub_d = drf.prefill_poll(
            None if budget <= 0 else max(0, budget - sub_t.prefill_chunks))
        self._reap()                   # eos on an admission first token
        pairs = self._live_pairs()
        if pairs:
            self.slot_rounds += len(pairs)
            for req, shadow in self._pairs.values():
                if (req.slot >= 0 and tgt.active[req.slot]
                        and shadow.slot >= 0 and drf.active[shadow.slot]):
                    req.spec_rounds += 1
            for tslot, dslot in pairs:
                drf.spec_resync_from(dslot, tgt, tslot)
            win = tgt.spec_window_lens()
            win_t = np.zeros(tgt.cfg.n_slots, np.int32)
            win_d = np.zeros(drf.cfg.n_slots, np.int32)
            for tslot, dslot in pairs:
                win_t[tslot] = win[tslot]
                win_d[dslot] = win[tslot]
            drafts = drf.spec_propose(win_d)
            drafts_t = np.zeros((tgt.cfg.n_slots, self.k - 1), np.int32)
            for tslot, dslot in pairs:
                drafts_t[tslot] = drafts[dslot, :self.k - 1]
            done_before = len(tgt.completed)
            committed = tgt.spec_verify(drafts_t, win_t)
            sub_t.completed += tgt.completed[done_before:]
            self._reap()
            for tslot, dslot in pairs:     # position-agreement invariant
                if drf.active[dslot] and tgt.active[tslot]:
                    drf.spec_resync_from(dslot, tgt, tslot)
            rep.decode_stepped = True
            rep.n_active = len(pairs)
            rep.spec_rounds = 1
            rep.spec_committed = int(committed.sum())
            rep.spec_drafted = int(win_d.sum())
            sub_d.spec_rounds = sub_t.spec_rounds = 1
            sub_d.spec_drafted = rep.spec_drafted
            sub_t.spec_committed = rep.spec_committed
            sub_t.decode_stepped = sub_d.decode_stepped = True
            sub_t.n_active = sub_d.n_active = len(pairs)
            sub_t.decode_depth_frac = sub_d.decode_depth_frac = 1.0
        for name, sub in ((self.draft_name, sub_d), (self.target_name,
                                                     sub_t)):
            rep.per_model[name] = sub
            rep.admitted += sub.admitted
            rep.prefill_chunks += sub.prefill_chunks
            rep.prefill_tokens += sub.prefill_tokens
            rep.prefill_done = rep.prefill_done or sub.prefill_done
            rep.completed += sub.completed
        self.completed += rep.completed
        return rep

    def run(self, rng=None):
        """Drain to completion (greedy only — temperature is 0 by
        construction, so ``rng`` only resets the per-run fold counters)."""
        self.set_rng(rng)
        while self.has_work:
            if not self.poll().worked:
                break
        self.flush_counters()

    # ------------------------------------------------------------------
    # statistics
    # ------------------------------------------------------------------
    def spec_stats(self) -> Dict[str, float]:
        """Measured speculation outcome: verify rounds (pool dispatches),
        slot-rounds (request-round participations), committed tokens, and
        the acceptance length — committed tokens per slot-round, the
        factor by which one request's per-token round trips shrink on a
        cross-tier link."""
        tgt = self.pools[self.target_name]
        return {"k": float(self.k), "rounds": float(tgt.spec_rounds),
                "slot_rounds": float(self.slot_rounds),
                "committed": float(tgt.spec_committed),
                "acceptance_len": (tgt.spec_committed
                                   / max(1, self.slot_rounds))}
