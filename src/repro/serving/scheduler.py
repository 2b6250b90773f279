"""Continuous-batching serving scheduler: request queue + slot-based KV cache.

The survey's edge-device paradigm (§4, Edgent/SPINN) frames early-exit
serving as a throughput/deadline problem, which only becomes measurable once
requests arrive and depart asynchronously.  This module provides that
runtime:

* A FIFO request queue feeding a fixed pool of ``n_slots`` decode slots.
  Each slot owns one row of the (fixed-shape) decode cache; per-slot
  position/length/state live on the host.
* **Batched prefill**: an admitted request's whole prompt is replayed in
  chunked jitted scans (``prefill_chunk`` tokens per dispatch) over a fresh
  cache, then row-merged into the pool — in-flight slots are never touched
  and the prompt is never fed through a host-side token-at-a-time loop.
  ``SchedulerConfig.max_prefill_chunks_per_step`` caps how many chunks one
  ``poll()`` may run, so a long admission interleaves with in-flight decode
  instead of pausing it unboundedly (prefill/decode fairness).
* **Depth-segmented decode** (default): the model's plan compiles into
  per-segment jitted stages bounded by exit heads.  One decode step runs
  ``segment0 -> probe0 -> segment1 -> ... -> finalize``; after each probe
  (the fused Pallas entropy kernel — no [B,V] logits materialized) the
  per-slot ``alive`` mask drops slots whose normalized entropy cleared the
  threshold, gating deeper segments (hidden passthrough + masked KV/state
  writes), and the host short-circuits the remaining stages entirely once
  every active slot has exited.  Early exits therefore *truncate compute*,
  not just counters: the per-step depth fraction (layer-weighted share of
  the stack dispatched) is measured, reported per ``poll()``, and drives
  the adaptive controller and the tiered cluster's virtual clocks.
  ``SchedulerConfig(segmented=False)`` falls back to the monolithic
  one-jit ``decode_step`` (the pre-refactor reference path).
* **Fixed shapes everywhere**: tokens [B,1], per-slot positions [B],
  active/alive masks [B], counters and the entropy threshold are all
  *arguments*, so slot churn (admissions, completions, mixed prompt
  lengths, adaptive-threshold updates) never recompiles.  Each segment
  stage compiles exactly once — ``jit_cache_sizes()`` is bounded by the
  number of depth segments and tests assert every entry stays <= 1.
* **Device-side exit counters**: per-step first-exit histograms accumulate
  in an on-device int32 vector and are flushed to host every
  ``flush_every`` steps (or when the adaptive controller needs them) —
  not synced every token like the old engine.
* **Slot migration**: ``export_slot`` lifts one slot's serving state (cache
  rows truncated to the written prefix, position, pending token, request)
  out of the arena as a ``SlotSnapshot``; ``import_slot`` restores it into
  any same-model arena — even one with a different slot count — and greedy
  decoding continues bit-identically mid-flight, with no prefill replay.
  Both directions are single fixed-shape jitted calls over a traced slot
  index (no per-request recompiles), and the snapshot's measured
  ``payload_bytes`` (optionally int8-quantized through
  ``kernels/feature_compress``) is what external drivers charge link
  transfer time from.  This is the primitive behind the tiered cluster's
  real prefill/decode splits and tier-outage failover.

The scheduler is pool-instantiable and externally steppable: ``run()`` is a
thin drain loop over ``poll()``, which performs one admission/prefill/decode
round and returns a ``StepReport`` describing the work done.  The tiered
serving cluster (``repro.serving.cluster``) instantiates one scheduler per
cloud/edge/device tier and drives all pools via ``poll()``, using the
reports for virtual-time accounting.

This class is the **single-model arena**: one model, one fixed-shape cache
pool, one set of jitted stages.  ``repro.serving.multipool`` multiplexes
several of these arenas — one per named ``(model, params)`` entry of a
``ModelGroup`` — behind one queue and one ``poll()`` loop
(``MultiModelScheduler``), with ``Request.model`` selecting the arena and
``poll(prefill_budget=...)`` sharing the prefill-fairness budget across
models.

Typical use::

    sched = ContinuousBatchScheduler(model, params, SchedulerConfig(
        n_slots=8, max_len=192, exit_threshold=0.6))
    for prompt in prompts:
        sched.submit(Request(tokens=prompt, max_new=32))
    sched.run()                       # drain queue + slots
    outs = [r.out_tokens for r in sched.completed]
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import (Any, Callable, Dict, FrozenSet, List, Optional, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.early_exit import exit_stats_dict, first_exit_index
from repro.models import attention as attn_mod
from repro.models import blocks as blocks_mod
from repro.serving.paged import PageAllocator, RadixPrefixCache, chunk_digests


def _span(name: str):
    """Host span ``sched.<name>`` around scheduler phase ``name``.  It is
    written into a running profiler trace, on the same clock as the
    device's ops, so an idle gap on the chip can be named by the host
    phase that left it; with no trace running it does nothing (about a
    microsecond).  Never opened inside a jitted function or a per-slot
    loop."""
    return jax.profiler.TraceAnnotation("sched." + name)


@dataclasses.dataclass
class Request:
    """One serving request.  ``tokens`` is the prompt [S0] int; ``out_tokens``
    is filled by the scheduler (first token comes from the prompt's last
    logits, like the sequential engine)."""
    tokens: Any                        # [S0] int array (np or jnp)
    max_new: int = 32
    eos_id: Optional[int] = None
    frames: Any = None                 # [Tenc, D] for encdec (whisper) archs
    req_id: int = -1
    # model name for multi-model pools ("" = the pool's default model);
    # a single-model ContinuousBatchScheduler ignores it
    model: str = ""
    # --- filled by the scheduler ---
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    t_done: float = 0.0
    slot: int = -1
    done: bool = False
    # verify rounds this request participated in (SpecPair only): external
    # drivers divide len(out_tokens) by it for per-request speedup
    # attribution vs the one-token-per-round streaming baseline
    spec_rounds: int = 0


@dataclasses.dataclass
class SchedulerConfig:
    n_slots: int = 8
    max_len: int = 256                 # per-slot logical sequence capacity
    prefill_chunk: int = 16            # tokens per jitted prefill dispatch
    exit_threshold: float = 0.5
    temperature: float = 0.0           # 0 = greedy
    flush_every: int = 32              # decode steps between counter flushes
    long_mode: bool = False
    # prefill/decode fairness: max prefill chunks one poll() may run before
    # the pool decode step gets its turn.  0 = unbounded (an admission's
    # whole prompt replays before decode resumes — the old behaviour).
    max_prefill_chunks_per_step: int = 0
    # depth-segmented decode: early exits truncate compute (per-segment
    # jitted stages, short-circuited once every active slot exited).
    # False = monolithic one-jit decode_step, exits counted but not acted on
    # (the pre-refactor reference path, used by parity tests).
    segmented: bool = True
    # paged KV arena: attention caches become a global pool of
    # ``page_size``-token pages addressed through per-slot block tables
    # (serving/paged.py).  n_pages=0 sizes the pool to n_slots full rows
    # (same bytes as the contiguous arena); smaller/larger pools trade slot
    # concurrency against prompt-sharing headroom.  prefix_cache enables the
    # radix prefix tree (auto-disabled for archs with SSM/xLSTM state
    # leaves, where skipping replay would leave states unprimed).
    paged: bool = False
    page_size: int = 16
    n_pages: int = 0
    prefix_cache: bool = True
    # overlapped host-device pipeline: decode runs as jitted WINDOWS of
    # ``readback_interval`` monolithic steps with sampling, token feedback
    # and eos/max_new termination fully on device (per-slot token ring);
    # ``poll()`` double-buffers dispatch (window N+1 is enqueued from the
    # device carry while window N executes) and host readback is deferred
    # to one batched d2h per window, replayed through the exact synchronous
    # commit semantics (bounded-staleness commit — see docs/pipeline.md).
    # Requires segmented=False: the segment pipeline's per-probe host
    # short-circuit is a sync point inside the window.
    async_decode: bool = False
    readback_interval: int = 8


@dataclasses.dataclass
class StepReport:
    """What one ``poll()`` did — consumed by external pool drivers (the
    tiered cluster) for virtual-time accounting and by fairness tests."""
    admitted: List[Request] = dataclasses.field(default_factory=list)
    prefill_chunks: int = 0            # chunks advanced this poll
    prefill_chunk_start: int = 0       # index of the first chunk advanced
    prefill_tokens: int = 0            # real prompt tokens covered this poll
    prefill_done: bool = False         # admission finalized this poll
    decode_stepped: bool = False
    n_active: int = 0                  # active slots during the decode step
    # depth-segmented decode accounting: how many segment stages the decode
    # step dispatched and the layer-weighted fraction of the stack they
    # cover (1.0 = full depth).  External drivers (the tiered cluster)
    # charge their virtual clocks with the *truncated* step cost.
    decode_segments_run: int = 0
    decode_depth_frac: float = 0.0
    # speculative decoding (repro.serving.multipool.SpecPair): verify rounds
    # run this poll, tokens the target committed across them, and draft
    # propose dispatches — external drivers charge draft/verify compute and
    # per-round link costs from these instead of per-token decode steps.
    spec_rounds: int = 0
    spec_committed: int = 0
    spec_drafted: int = 0
    # async decode (cfg.async_decode): decode steps COMMITTED this poll
    # (a whole window's worth at each readback; synchronous polls report 1
    # per stepped poll) and windows DISPATCHED this poll — a dispatch-only
    # poll did real device work even though nothing committed yet, so
    # external drivers must not treat it as idle.
    decode_steps: int = 0
    decode_dispatched: int = 0
    # host/device wall-time split of this poll: ``syncs`` counts its
    # blocking device->host reads (every one goes through ``_sync``: exit
    # probes, token and ring readbacks, the first-token readback, counter
    # flushes), device_ms is the time blocked in them and host_ms the rest
    # of the poll.  tokens_in_flight: tokens still inside
    # dispatched-but-unread windows at poll end.
    host_ms: float = 0.0
    device_ms: float = 0.0
    syncs: int = 0
    tokens_in_flight: int = 0
    completed: List[Request] = dataclasses.field(default_factory=list)
    # multi-model pools (repro.serving.multipool): the per-model sub-reports
    # behind this aggregate, keyed by model name.  Empty for a single-model
    # scheduler.  External drivers that charge per-model costs (the tiered
    # cluster) consume these instead of the aggregate fields.
    per_model: Dict[str, "StepReport"] = dataclasses.field(
        default_factory=dict)

    @property
    def worked(self) -> bool:
        return bool(self.admitted) or self.prefill_chunks > 0 \
            or self.decode_stepped or self.decode_dispatched > 0


@dataclasses.dataclass
class SlotSnapshot:
    """One slot's serving state, lifted out of an arena by ``export_slot``
    and restorable into ANY same-model arena by ``import_slot`` (the two
    arenas may have different slot counts — the payload is one batch row).

    ``payload`` is the flat list of the slot's cache-row leaves (KV rows,
    SSM/conv states, shared-attn rows) with each leaf's time axis truncated
    to the ``filled`` prefix the request has actually written — the bytes a
    migration really ships.  With ``compressed`` the float leaves are int8
    rows + per-row fp32 scales from ``kernels/feature_compress``
    (``scales[i]`` is None for leaves shipped raw).  ``payload_bytes`` is
    the measured size of exactly those arrays: external drivers (the tiered
    cluster) charge link transfer time from it instead of an analytic
    estimate.

    Host-side per-request state rides along (position, pending token,
    decode steps taken, the live ``Request`` with its ``out_tokens``), plus
    provenance: the exporting arena's sampling tick and cumulative exit
    histogram at export time (per-token exit counts accrue in whichever
    arena served the token; they are not transferred twice).

    Parity contract: GREEDY continuation is bit-identical after a raw
    import.  Sampled (temperature > 0) continuation is NOT stream-stable
    across a migration — the rng fold counter is arena-global (every
    pooled request advances it), so the destination arena necessarily
    samples from its own stream; ``rng_tick`` is diagnostic provenance,
    deliberately not restored by ``import_slot``.
    """
    req: Request
    model: str
    position: int
    filled: int                       # time-axis rows actually shipped
    current_tok: int
    steps_taken: int
    compressed: bool
    payload: List[Any]                # np leaves, time axes truncated
    scales: List[Optional[Any]]       # per-leaf fp32 scales (compressed)
    payload_bytes: int
    rng_tick: int = 0                 # exporting arena's sampling tick
    exit_counts: Any = None           # exporting arena's histogram (copy)
    # --- paged arenas: page-granular payloads ---
    # paged exports ship KV PAGES ``[page_skip, page_used)`` instead of
    # token rows: ``page_digests`` is the slot's full prompt digest chain
    # and ``page_skip`` counts leading prompt pages the destination already
    # holds (negotiated via ``export_slot(skip_keys=dst.prefix_keys())``) —
    # those pages are borrowed from the destination's prefix tree on import
    # instead of crossing the link (cold pages only).
    paged: bool = False
    page_skip: int = 0
    page_used: int = 0
    page_digests: List[Any] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PendingPrefill:
    """An admission whose chunked prompt replay is still in flight.  The
    fresh cache is private to the admission, so in-flight decode slots keep
    stepping on the pool cache between chunks."""
    reqs: List[Request]
    slots: List[int]
    tokens: Any                        # np [n_slots, n_chunks*chunk] int32
    lengths: Any                       # np [n_slots] int32
    lengths_d: Any                     # device copy
    admit: Any                         # np [n_slots] bool
    cache: Any                         # fresh decode cache being filled
    last: Any                          # carried last-real-token logits
    next_chunk: int = 0
    n_chunks: int = 0
    # paged arenas: per-row replay start (prefix-cache hit tokens are
    # skipped — their pages are borrowed, not recomputed).  Paged prefill
    # writes pool pages in place (the staged slots own them exclusively),
    # so ``cache`` is None in paged mode.
    start: Any = None                  # np [n_slots] int32
    start_d: Any = None                # device copy


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One registered jitted stage, described abstractly for the jaxpr
    auditor (``repro.analysis.jaxpr_audit``): the jitted callable plus the
    exact abstract argument shapes the serving loop feeds it, so the
    auditor can ``jax.make_jaxpr`` / ``jax.eval_shape`` the stage without
    executing anything on device.

    ``cache_in`` names the argument position holding the cache pytree and
    ``cache_out`` selects the returned cache from the stage's output —
    together they let the auditor prove the cache's leaf dtypes survive
    the stage unchanged (bit-parity: no silent widening).  Stages that
    only *read* the cache (export gathers) leave ``cache_out`` as None."""
    name: str
    fn: Any                                    # the jitted callable
    args: Tuple[Any, ...]                      # ShapeDtypeStruct pytrees
    donate_argnums: Tuple[int, ...] = ()
    cache_in: Optional[int] = None             # argnum of the cache pytree
    cache_out: Optional[Callable[[Any], Any]] = None   # out -> cache pytree


class ContinuousBatchScheduler:
    """Slot-based continuous batching over ``Model.decode_step``.

    Host-side state is tiny numpy vectors (positions, active mask, current
    tokens); everything heavy (cache, counters) stays on device.  An optional
    ``controller`` (AdaptiveExitController) is driven from the flushed
    counters every ``adaptive_every`` served tokens.
    """

    def __init__(self, model, params, cfg: SchedulerConfig = SchedulerConfig(),
                 controller=None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.controller = controller
        self.adaptive_every = 64
        if cfg.async_decode:
            if cfg.segmented:
                raise ValueError(
                    "async_decode requires segmented=False: the segment "
                    "pipeline's per-probe host short-circuit is a sync "
                    "point inside the zero-readback decode window")
            if cfg.readback_interval < 1:
                raise ValueError("readback_interval must be >= 1")

        b = cfg.n_slots
        mcfg = model.cfg
        self._vocab = mcfg.vocab_size
        self._n_exits = model.n_exits
        self._clen = model.cache_len_for(cfg.max_len, cfg.long_mode)

        # --- paged KV arena (cfg.paged): global page pool + block tables ---
        self.page_alloc: Optional[PageAllocator] = None
        self.prefix_cache: Optional[RadixPrefixCache] = None
        self.prefix_hit_tokens = 0
        self.prefill_chunks_skipped = 0
        if cfg.paged:
            assert mcfg.family != "encdec", "paged mode: encdec unsupported"
            assert model._window(cfg.long_mode) == 0, \
                "paged mode: ring-buffer windows unsupported"
            assert cfg.page_size > 0 and cfg.max_len % cfg.page_size == 0, \
                "paged mode: max_len must be a multiple of page_size"
            self._pps = cfg.max_len // cfg.page_size   # pages per slot
            n_pages = cfg.n_pages or b * self._pps
            self.page_alloc = PageAllocator(n_pages, cfg.page_size)
            # prefix skipping replays a SUFFIX of the prompt only — sound
            # iff shared pages fully determine the skipped positions, i.e.
            # every cache leaf is pool-backed (no SSM/xLSTM states to prime)
            if cfg.prefix_cache and model.all_cache_paged():
                self.prefix_cache = RadixPrefixCache(self.page_alloc)
            # host block table, sentinel = n_pages (unallocated); uploaded
            # to device lazily on change (dirty flag) so steady-state polls
            # reuse one upload
            self._tbl = np.full((b, self._pps), n_pages, np.int32)
            self._tbl_device = None
            self._tbl_dirty = True
            self._slot_digests: List[List[bytes]] = [[] for _ in range(b)]

        # --- queue / slot state (host) ---
        self.queue: deque = deque()
        self.completed: List[Request] = []
        self.positions = np.zeros(b, np.int64)     # next decode position
        self.active = np.zeros(b, bool)
        self.current_tok = np.zeros(b, np.int32)   # token each slot feeds next
        self.steps_taken = np.zeros(b, np.int64)   # decode steps this request
        self.slot_req: List[Optional[Request]] = [None] * b
        self.tokens_served = 0
        self.exit_counts = np.zeros(self._n_exits + 1, np.int64)
        # measured truncated compute: sum over served tokens of the
        # layer-weighted depth fraction their decode step dispatched
        self.depth_weighted_tokens = 0.0
        self._depth_since_adapt = 0.0
        self._last_segments_run = 0
        self._last_depth_frac = 0.0
        self.n_admitted = 0
        self.n_submitted = 0
        self._step_idx = 0
        self._tokens_since_adapt = 0
        self._rng = None
        self._pending: Optional[_PendingPrefill] = None
        self._last_step_active = 0
        # per-run fold counters, reset by run() so identical (requests, rng)
        # reproduce identical samples across calls (seed-engine semantics)
        self._rng_tick = 0
        self._admit_tick = 0
        # host scalars fed to jitted stages are uploaded explicitly
        # (jax.device_put) and cached where the value repeats, so poll()
        # runs clean under jax.transfer_guard("disallow") — see
        # analysis.guards.guard_polling and docs/invariants.md
        self._t0_cache: Dict[int, Any] = {}
        self._thr_cache: tuple = (None, None)   # (host value, device scalar)
        # --- async decode pipeline state (cfg.async_decode) ---
        # _win_q: FIFO of dispatched-but-unread windows as (ring handle,
        # participating-slot mask, alive hint); _dev_carry chains the
        # device-side (cur, pos, alive, budget) of the last dispatch so the
        # next window uploads nothing; _carry_valid goes False whenever host
        # state diverges from the carry (admission, import, sync).  Empty /
        # False forever on synchronous schedulers, so shared code paths can
        # consult them unconditionally.
        self._win_q: deque = deque()
        self._dev_carry = None
        self._carry_valid = False
        self._eos_dev = None
        self._flag_cache: Dict[bool, Any] = {}
        # host/device wall-time split accumulators (StepReport.host_ms /
        # device_ms roll up here; reset_stats zeroes them)
        self.host_ms_total = 0.0
        self.device_ms_total = 0.0
        self.peak_tokens_in_flight = 0
        # running totals of _sync (never reset): a poll reports its deltas
        self._dev_s = 0.0
        self._n_syncs = 0

        # --- jitted, fixed-shape device functions ---
        self._counters = jnp.zeros(self._n_exits + 1, jnp.int32)
        self._zero_key = jax.random.PRNGKey(0)
        # fixed per-step initial masks, built once: eager jnp.ones/full
        # upload their fill scalar (an implicit h2d the transfer guard
        # rejects) and re-allocating them every decode step is waste
        self._alive0 = jnp.ones((b,), bool)
        self._first_exit0 = jnp.full((b,), self._n_exits, jnp.int32)
        if cfg.paged:
            self._init_cache = jax.jit(
                lambda: model.init_decode_cache_paged(
                    b, self.page_alloc.n_pages, cfg.page_size))
        else:
            self._init_cache = jax.jit(
                lambda: model.init_decode_cache(b, self._clen,
                                                long_mode=cfg.long_mode))
        # paged arenas prefill IN PLACE: pool pages are freshly allocated per
        # admission, but SSM/xLSTM state rows live per-slot and would carry
        # the previous occupant's final state — zero them at admission (all
        # state initializers are zeros, so this IS the fresh-init row)
        self._reset_states = None
        if cfg.paged and not model.all_cache_paged():
            self._reset_states = jax.jit(self._make_reset_states(),
                                         donate_argnums=(0,))
        # fresh carried-logits buffer per admission, filled ON device: the
        # buffer is donated chunk-to-chunk so it can't be cached, and eager
        # jnp.zeros would implicitly upload its fill scalar every admission
        self._fresh_last = jax.jit(
            lambda: jnp.zeros((b, self._vocab), jnp.float32))
        # donate dead-after-call buffers (caches, counters, carried logits)
        # so XLA aliases them in place instead of copying the KV arena
        # every token; merge donates only the old pool (the output can alias
        # one side, donating both leaves unusable buffers)
        self._merge = jax.jit(model.merge_decode_cache,
                              donate_argnums=(2,))
        # paged prefill takes (params, cache, tokens, t0, lengths, start,
        # last, tbl): donate the pool cache (1) and carried logits (6)
        self._prefill_chunk = jax.jit(self._make_prefill_chunk(),
                                      donate_argnums=(1, 6) if cfg.paged
                                      else (1, 5))
        # decode: either the depth-segmented stage pipeline (default) or the
        # monolithic one-jit step (pre-refactor reference / parity path)
        self._segments = model.decode_segments
        self.stage_calls: Dict[str, int] = {}
        if cfg.segmented:
            self._segment_fns = [
                jax.jit(self._make_segment_stage(seg), donate_argnums=(1,))
                for seg in self._segments]
            self._probe_fns = [jax.jit(self._make_probe(ei))
                               for ei in range(self._n_exits)]
            self._finalize = jax.jit(self._make_finalize(),
                                     donate_argnums=(2,))
            for name in self._stage_names():
                self.stage_calls[name] = 0
        else:
            self._decode = jax.jit(self._make_decode_step(),
                                   donate_argnums=(1, 5))
            if cfg.async_decode:
                # donate the window's whole device carry (cache, cur, pos,
                # alive, budget) plus counters; eos (6) stays undonated so
                # the cached per-chain vector survives carry dispatches
                self._decode_window = jax.jit(
                    self._make_decode_window(),
                    donate_argnums=(1, 2, 3, 4, 5, 7))
        if mcfg.family == "encdec":
            from repro.serving.engine import prime_whisper_cross_cache
            self._prime = jax.jit(
                lambda p, c, f: prime_whisper_cross_cache(model, p, c, f))
        # --- slot migration: fixed-shape export/import (slot is a traced
        # index, so snapshotting/restoring ANY slot reuses one compile).
        # Paged arenas gather/scatter the slot's PAGES through its block
        # table row (also fixed shape: all pages_per_slot entries move,
        # sentinel-routed scatter drops the unshipped ones). ---
        if cfg.paged:
            self._export_rows = jax.jit(self._gather_slot_paged)
            self._import_rows = jax.jit(self._scatter_slot_paged,
                                        donate_argnums=(0,))
        else:
            self._export_rows = jax.jit(self._gather_slot)
            self._import_rows = jax.jit(self._scatter_slot,
                                        donate_argnums=(0,))
        (self._row_struct_flat, self._row_axes_flat,
         self._row_treedef) = self._detect_row_layout()
        self.n_imported = 0
        self.n_exported = 0
        # --- speculative decoding (built lazily by _ensure_spec: the window
        # width k is a shape, so the propose/verify jits exist only once a
        # SpecPair driver fixes it).  Verify-committed tokens are counted on
        # HOST (the device scan cannot know how many committed tokens the
        # commit loop will consume before an eos/max_new finish), so the
        # histogram==tokens_served invariant needs this extra histogram
        # folded in by flush_counters(). ---
        self._spec_k = 0
        self._propose = None
        self._verify = None
        self._host_exit_extra = np.zeros(self._n_exits + 1, np.int64)
        self.spec_rounds = 0
        self.spec_committed = 0
        self.cache = self._init_cache()

    # ------------------------------------------------------------------
    # jitted step builders
    # ------------------------------------------------------------------
    def _make_prefill_chunk(self):
        model, cfg = self.model, self.cfg
        if cfg.paged:
            def chunk(params, cache, tokens, t0, lengths, start, last_logits,
                      tbl):
                """Paged replay directly into the shared pool: rows update
                only while start[b] <= t < lengths[b] (prefix-hit tokens
                below ``start`` are already resident in borrowed pages).
                Staged slots own their pages/state rows exclusively and
                decode polls are serialized with prefill, so writing the
                live pool in place is race-free."""
                n = tokens.shape[1]

                def body(carry, i):
                    cache, last = carry
                    tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
                    t = t0 + i
                    act = (t < lengths) & (t >= start)
                    logits, _, cache = model.decode_step(
                        params, cache, tok, t, long_mode=cfg.long_mode,
                        paged=attn_mod.PagedKV(tbl, act))
                    last = jnp.where((t == lengths - 1)[:, None], logits,
                                     last)
                    return (cache, last), None

                (cache, last), _ = jax.lax.scan(body, (cache, last_logits),
                                                jnp.arange(n))
                return cache, last

            return chunk

        def chunk(params, cache, tokens, t0, lengths, last_logits):
            """Replay ``tokens`` [B,C] at positions t0..t0+C-1; rows write
            their cache rows only while t < lengths[b] (the write mask gates
            each row write in place).  Carries the last real token's logits
            per row so admission can sample the first output token."""
            n = tokens.shape[1]

            def body(carry, i):
                cache, last = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
                t = t0 + i
                logits, _, cache = model.decode_step(
                    params, cache, tok, t, long_mode=cfg.long_mode,
                    write_mask=t < lengths)
                last = jnp.where((t == lengths - 1)[:, None], logits, last)
                return (cache, last), None

            (cache, last), _ = jax.lax.scan(body, (cache, last_logits),
                                            jnp.arange(n))
            return cache, last

        return chunk

    def _sample_and_count(self, logits, first_exit, active, counters, key,
                          step_idx):
        """Token selection + first-exit histogram update, shared by the
        monolithic step and the segmented finalize so their threshold-0
        parity cannot drift.  Both tokens come back so the host can honor
        "greedy unless an rng was provided" (seed-engine semantics) without
        recompiling."""
        cfg = self.cfg
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if cfg.temperature > 0.0:
            k = jax.random.fold_in(key, step_idx)
            nxt = jax.random.categorical(
                k, logits / cfg.temperature).astype(jnp.int32)
        else:
            nxt = greedy
        hist = jax.nn.one_hot(first_exit, self._n_exits + 1, dtype=jnp.int32)
        counters = counters + jnp.sum(
            hist * active.astype(jnp.int32)[:, None], axis=0)
        return greedy, nxt, counters

    def _make_decode_step(self):
        model, cfg = self.model, self.cfg
        n_exits, vocab = self._n_exits, self._vocab

        def step(params, cache, tokens, positions, active, counters,
                 threshold, key, step_idx, *rest):
            # paged arenas pass their block table last, and their pool and
            # state writes gate on ``active``: stale slots must not touch
            # pages they no longer own (a contiguous arena tolerates their
            # garbage writes because each slot has a private row)
            paged = attn_mod.PagedKV(rest[0], active) if rest else None
            logits, ee, cache = model.decode_step(
                params, cache, tokens, positions, long_mode=cfg.long_mode,
                paged=paged)
            if n_exits:
                idx = first_exit_index(ee, threshold, vocab)
            else:
                idx = jnp.zeros((tokens.shape[0],), jnp.int32)
            greedy, nxt, counters = self._sample_and_count(
                logits, idx, active, counters, key, step_idx)
            return greedy, nxt, cache, counters

        return step

    def _make_decode_window(self):
        """Zero-readback decode WINDOW (cfg.async_decode): a jitted
        ``lax.scan`` of ``readback_interval`` monolithic steps with token
        selection, feedback and termination fully on device, emitting a
        per-slot token ring [B, R] the host replays later.

        The on-device commit mirrors ``step()``'s host loop exactly so a
        deferred replay reconstructs identical state: per step the running
        budget (``max_new - steps_taken``) decrements for live rows; a row
        whose budget hits zero freezes WITHOUT taking the trailing token
        (max_new discards the trailing sample, like ``step()``); otherwise
        the token feeds back as ``cur`` and matching ``eos`` (sentinel -1
        = no eos) freezes the row.  Frozen rows keep computing garbage
        exactly like inactive slots under the sync monolithic step —
        private rows in contiguous arenas, ``act``-masked page writes in
        paged ones — so greedy outputs stay bit-identical and freed pages
        are never touched."""
        model, cfg = self.model, self.cfg
        n_exits, vocab = self._n_exits, self._vocab
        R = cfg.readback_interval
        paged = cfg.paged

        def window(params, cache, cur, pos, alive, budget, eos, counters,
                   threshold, key, tick0, use_sampled, *rest):
            tbl = rest[0] if rest else None

            def body(carry, j):
                cache, cur, pos, alive, budget, counters = carry
                act = alive
                tok_in = cur[:, None]
                logits, ee, cache = model.decode_step(
                    params, cache, tok_in, pos, long_mode=cfg.long_mode,
                    paged=attn_mod.PagedKV(tbl, act) if paged else None)
                if n_exits:
                    idx = first_exit_index(ee, threshold, vocab)
                else:
                    idx = jnp.zeros((cur.shape[0],), jnp.int32)
                greedy, nxt, counters = self._sample_and_count(
                    logits, idx, act, counters, key, tick0 + j)
                tok = jnp.where(use_sampled, nxt, greedy)
                pos = pos + act.astype(pos.dtype)
                budget = budget - act.astype(jnp.int32)
                spent = act & (budget <= 0)
                cur = jnp.where(act & ~spent, tok, cur)
                alive = act & ~spent & ~(tok == eos)
                return (cache, cur, pos, alive, budget, counters), tok

            (cache, cur, pos, alive, budget, counters), ring = jax.lax.scan(
                body, (cache, cur, pos, alive, budget, counters),
                jnp.arange(R))
            return cache, cur, pos, alive, budget, counters, ring.T

        return window

    # ------------------------------------------------------------------
    # depth-segmented decode stages (one jit per segment, compiled once)
    # ------------------------------------------------------------------
    def _stage_names(self) -> List[str]:
        names = []
        for seg in self._segments:
            names.append(f"segment{seg.index}")
            if seg.exit_index is not None:
                names.append(f"probe{seg.exit_index}")
        names.append("finalize")
        return names

    def _make_segment_stage(self, seg):
        """Stage for one depth segment.  The first stage embeds the tokens;
        every stage runs its plan steps with ``alive``-masked cache writes
        and hidden passthrough for exited slots."""
        model, cfg = self.model, self.cfg
        first = seg.index == 0
        if cfg.paged:
            def stage(params, cache, x, positions, alive, active, tbl):
                if first:
                    x = model.embed_decode_tokens(params, x)
                # write gates are alive & active (stale slots own no pages)
                # but the HIDDEN passthrough keeps the plain alive mask:
                # every row's compute must match the unpaged path exactly,
                # because MoE expert-capacity routing couples batch rows
                wm = alive & active
                return model.decode_segment(
                    params, cache, x, seg, positions, wm,
                    long_mode=cfg.long_mode,
                    paged=attn_mod.PagedKV(tbl, wm), passthrough=alive)

            return stage

        def stage(params, cache, x, positions, alive):
            if first:
                x = model.embed_decode_tokens(params, x)
            return model.decode_segment(params, cache, x, seg, positions,
                                        alive, long_mode=cfg.long_mode)

        return stage

    def _make_probe(self, exit_index: int):
        """Exit decision after a segment: fused entropy (no [B,V] logits),
        normalized by log(V) so one threshold spans vocab sizes."""
        model, vocab = self.model, self._vocab

        def probe(params, x, alive, first_exit, threshold):
            ent = model.exit_probe_entropy(params, exit_index, x)
            hit = alive & (ent / jnp.log(float(vocab)) < threshold)
            first_exit = jnp.where(hit, jnp.int32(exit_index), first_exit)
            return alive & ~hit, first_exit

        return probe

    def _make_finalize(self):
        """Token selection + counter update from the (possibly early-frozen)
        hidden states, via the same ``_sample_and_count`` the monolithic
        step uses."""
        model = self.model

        def finalize(params, x, counters, first_exit, active, key, step_idx):
            logits = model.finalize_decode(params, x)
            greedy, nxt, counters = self._sample_and_count(
                logits, first_exit, active, counters, key, step_idx)
            return greedy, nxt, counters

        return finalize

    # ------------------------------------------------------------------
    # speculative decoding stages (repro.serving.multipool.SpecPair):
    # a draft arena proposes a k-token window, a target arena verifies it
    # in one batched dispatch.  Both are ok/win-gated lax.scans whose cache
    # writes happen ONLY for positions that end up committed — rejected
    # positions are never written, so there is no rollback pass and the
    # scheme is valid even for sequential state leaves (SSM/conv/xLSTM):
    # the state after the scan equals sequential decode of exactly the
    # accepted tokens.
    # ------------------------------------------------------------------
    def _gated_decode(self, params, cache, tokens, pos, act, tbl):
        """``decode_step`` whose KV and state writes land only in rows
        ``act``; paged arenas (``tbl`` not None) gate through the block
        table's sentinel page."""
        return self.model.decode_step(
            params, cache, tokens, pos, long_mode=self.cfg.long_mode,
            paged=None if tbl is None else attn_mod.PagedKV(tbl, act),
            write_mask=act)

    def _make_propose(self, k: int):
        """Draft-side proposer: ``k`` write-gated greedy decode steps in one
        jitted scan.  Step j feeds the running token at ``pos0 + j`` while
        ``active & (j < win_len)`` and emits the next greedy draft.  The
        k-th dispatch feeds the last draft so its KV row is written — on a
        full accept the resynced draft would otherwise attend to a hole.
        Paged arenas pass their block table as the trailing argument."""
        def propose(params, cache, tok0, pos0, active, win_len, *rest):
            tbl = rest[0] if rest else None

            def body(carry, j):
                cache, cur = carry
                act = active & (j < win_len)
                logits, _, cache = self._gated_decode(
                    params, cache, cur[:, None], pos0 + j, act, tbl)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                cur = jnp.where(act, greedy, cur)
                return (cache, cur), greedy

            (cache, _), drafts = jax.lax.scan(body, (cache, tok0),
                                              jnp.arange(k))
            return cache, drafts.T

        return propose

    def _make_verify(self, k: int):
        """Target-side verifier: run the target over all ``k`` window
        positions in one dispatch (same position handling as the chunked
        prefill scan), comparing target argmax against the next draft token
        on device.  Step i runs while every earlier draft matched
        (``ok``) — so ``acts`` is a per-slot contiguous prefix whose length
        is the committed count: the accepted drafts plus one corrected (or
        bonus) target token.  Rejected positions never write the cache.
        Paged arenas pass their block table as the trailing argument."""
        def verify(params, cache, tokens, pos0, active, win_len, *rest):
            tbl = rest[0] if rest else None

            def body(carry, i):
                cache, ok = carry
                tok = jax.lax.dynamic_slice_in_dim(tokens, i, 1, axis=1)
                act = active & ok & (i < win_len)
                logits, _, cache = self._gated_decode(
                    params, cache, tok, pos0 + i, act, tbl)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jax.lax.dynamic_slice_in_dim(
                    tokens, jnp.minimum(i + 1, k - 1), 1, axis=1)[:, 0]
                ok = ok & (greedy == nxt)
                return (cache, ok), (greedy, act)

            (cache, _), (gs, acts) = jax.lax.scan(
                body, (cache, jnp.ones_like(active)), jnp.arange(k))
            return cache, gs.T, jnp.sum(acts, axis=0).astype(jnp.int32)

        return verify

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, req: Request):
        toks = np.asarray(req.tokens).reshape(-1)
        assert toks.size >= 1, "empty prompt"
        assert req.max_new >= 1, "max_new must be >= 1"
        assert toks.size + req.max_new <= self.cfg.max_len, \
            f"prompt {toks.size} + max_new {req.max_new} exceeds " \
            f"max_len {self.cfg.max_len}"
        req.tokens = toks.astype(np.int32)
        if req.req_id < 0:
            req.req_id = self.n_submitted
        self.n_submitted += 1
        self.queue.append(req)

    def set_rng(self, rng):
        """Install a sampling rng and reset the per-run fold counters, so
        identical (requests, rng) reproduce identical samples — the same
        reset ``run()`` performs, for external pool drivers that step the
        scheduler via ``poll()`` instead."""
        self._rng = rng
        self._rng_tick = 0
        self._admit_tick = 0

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or bool(self.active.any()) \
            or self._pending is not None

    def tick(self) -> bool:
        """One admission/prefill/decode round.  Returns whether any device
        work happened (False = idle)."""
        return self.poll().worked

    def poll(self, prefill_budget: Optional[int] = None) -> StepReport:
        """One scheduler round: begin an admission if slots are free, advance
        at most ``max_prefill_chunks_per_step`` prefill chunks, then run one
        pool decode step.  Returns a ``StepReport`` of the work done — the
        external-driver API the tiered cluster steps pools through.

        ``prefill_budget`` overrides the config cap for this poll only:
        ``None`` uses ``cfg.max_prefill_chunks_per_step``; an int >= 1 runs
        at most that many chunks; 0 runs none (decode still steps, and an
        admission may still be *staged* — chunks replay on a later poll).
        Multi-model pools use this to enforce one prefill-fairness budget
        across every per-model arena.

        With ``cfg.async_decode`` the decode half routes through the
        double-buffered window pipeline (``_poll_async``): a poll either
        dispatches a decode window, commits one (a whole window's worth of
        ``decode_steps`` lands at once), or both — see docs/pipeline.md."""
        with _span("poll"):
            t_poll, dev_s, n_syncs = (time.perf_counter(), self._dev_s,
                                      self._n_syncs)
            if self.cfg.async_decode:
                rep = self._poll_async(prefill_budget)
            else:
                rep = self.prefill_poll(prefill_budget)
                done_before = len(self.completed)
                rep.decode_stepped = self.step()
                rep.decode_steps = 1 if rep.decode_stepped else 0
                rep.n_active = self._last_step_active
                if rep.decode_stepped:
                    rep.decode_segments_run = self._last_segments_run
                    rep.decode_depth_frac = self._last_depth_frac
                rep.completed += self.completed[done_before:]
            rep.syncs = self._n_syncs - n_syncs
            rep.device_ms = (self._dev_s - dev_s) * 1e3
            rep.host_ms = (time.perf_counter() - t_poll) * 1e3 \
                - rep.device_ms
        self.host_ms_total += rep.host_ms
        self.device_ms_total += rep.device_ms
        return rep

    def _sync(self, what: str, x):
        """``jax.device_get(x)``: the scheduler's one way to block on the
        chip.  The wait is a ``sched.sync.<what>`` span, counted in
        ``StepReport.syncs`` and timed into ``device_ms``."""
        t0 = time.perf_counter()
        with _span("sync." + what):
            out = jax.device_get(x)
        self._dev_s += time.perf_counter() - t0
        self._n_syncs += 1
        return out

    def prefill_poll(self, prefill_budget: Optional[int] = None) -> StepReport:
        """Admission + chunked prefill only — no decode step.  Speculative
        drivers (``SpecPair``) own the decode cadence (propose/verify
        rounds), so they advance admissions through this entry instead of
        ``poll()``; ``poll()`` itself is this plus one ``step()``."""
        rep = StepReport()
        done_before = len(self.completed)   # before prefill: an eos on the
        if self._pending is None:           # first sampled token completes
            rep.admitted = self._begin_admit()   # a request at admission
        if self._pending is not None and (prefill_budget is None
                                          or prefill_budget > 0):
            cap = self.cfg.max_prefill_chunks_per_step \
                if prefill_budget is None else prefill_budget
            self._advance_prefill(cap, rep)
        rep.completed = self.completed[done_before:]
        return rep

    def run(self, rng=None):
        """Drain the queue and all slots to completion."""
        self.set_rng(rng)
        while self.has_work:
            if not self.poll().worked:  # pragma: no cover - defensive
                break
        self.flush_counters()

    # ------------------------------------------------------------------
    # admission: chunked batched prefill into freed slots
    # ------------------------------------------------------------------
    def _admit(self) -> bool:
        """Compatibility entry: begin an admission (if possible) and advance
        its prefill by the configured cap (0 = to completion)."""
        began = bool(self._begin_admit())
        if self._pending is None:
            return began
        rep = StepReport()
        self._advance_prefill(self.cfg.max_prefill_chunks_per_step, rep)
        return began or rep.prefill_chunks > 0

    def _reserve_pages(self, slot: int, r: Request) -> Optional[int]:
        """Paged admission: reserve the slot's whole page budget (prompt +
        max_new), borrowing shared prefix pages from the radix tree first.
        Returns the replay start token (prefix-hit tokens are skipped), or
        None when the pool cannot fit the request even after evicting LRU
        trie-only pages — the caller defers the request (head-of-line)."""
        P = self.page_alloc.page_size
        plen = r.tokens.size
        total = -(-(plen + r.max_new) // P)
        digests = chunk_digests(r.tokens, P)
        shared: List[int] = []
        if self.prefix_cache is not None:
            # cap at (plen-1)//P so the LAST prompt token always replays —
            # the carried last-logits stay real, and any divergence after
            # the shared prefix lands in freshly-owned pages (COW by
            # construction: borrowed pages are never written at positions
            # >= start).  match() retains before eviction can run.
            shared = self.prefix_cache.match(digests[:(plen - 1) // P],
                                             r.tokens)
        need = total - len(shared)
        if self.page_alloc.free_count < need and self.prefix_cache is not None:
            self.prefix_cache.evict_until(need)
        if self.page_alloc.free_count < need:
            for pg in shared:
                self.page_alloc.release(pg)
            return None
        row = shared + self.page_alloc.alloc(need)
        self._tbl[slot, :total] = row
        self._tbl[slot, total:] = self.page_alloc.n_pages
        self._tbl_dirty = True
        self._slot_digests[slot] = digests
        self.prefix_hit_tokens += len(shared) * P
        return len(shared) * P

    def _begin_admit(self) -> List[Request]:
        """Reserve free slots for queued requests and stage their prompts as
        a pending chunked prefill over a fresh cache (paged arenas prefill
        straight into their reserved pool pages).  No chunks run here —
        ``_advance_prefill`` replays them, bounded per poll for fairness."""
        free = [i for i in range(self.cfg.n_slots) if self.slot_req[i] is None]
        if not free or not self.queue:
            return []
        with _span("admit"):
            take: List[int] = []
            reqs: List[Request] = []
            starts: Dict[int, int] = {}
            for slot in free:
                if not self.queue:
                    break
                r = self.queue[0]
                if self.page_alloc is not None:
                    st = self._reserve_pages(slot, r)
                    if st is None:
                        break          # pool full: defer, keep FIFO order
                    starts[slot] = st
                self.queue.popleft()
                take.append(slot)
                reqs.append(r)
            if not reqs:
                return []
            b, chunk = self.cfg.n_slots, self.cfg.prefill_chunk
            max_len = max(r.tokens.size for r in reqs)
            n_chunks = -(-max_len // chunk)
            tokens = np.zeros((b, n_chunks * chunk), np.int32)
            lengths = np.zeros(b, np.int32)
            admit = np.zeros(b, bool)
            start = np.zeros(b, np.int32)
            for slot, r in zip(take, reqs):
                tokens[slot, : r.tokens.size] = r.tokens
                lengths[slot] = r.tokens.size
                admit[slot] = True
                start[slot] = starts.get(slot, 0)
                r.slot = slot
                self.slot_req[slot] = r

            if self.page_alloc is not None:
                fresh = None           # paged prefill writes the pool itself
                if self._reset_states is not None:
                    self.cache = self._reset_states(self.cache,
                                                    jnp.asarray(admit))
            else:
                fresh = self._init_cache()
            if self.model.cfg.family == "encdec":
                ec = self.model.cfg.encdec
                frames = np.zeros(
                    (b, ec.encoder_seq_len, self.model.cfg.d_model),
                    np.float32)
                for slot, r in zip(take, reqs):
                    assert r.frames is not None, "encdec request needs frames"
                    frames[slot] = np.asarray(r.frames, np.float32)
                fresh = self._prime(self.params, fresh,
                                    jnp.asarray(frames, jnp.bfloat16))

            self._pending = _PendingPrefill(
                reqs=reqs, slots=take, tokens=tokens, lengths=lengths,
                lengths_d=jnp.asarray(lengths), admit=admit, cache=fresh,
                last=self._fresh_last(),
                next_chunk=0, n_chunks=n_chunks,
                start=start, start_d=jnp.asarray(start))
            return reqs

    def _make_reset_states(self):
        """Jitted row-reset for non-pool cache leaves: admitted slots' state
        rows (axis 1 = batch) go back to zeros, pool leaves pass through."""
        kinds = self.model.scan_block_kinds()

        def reset(cache, admit):
            def zero_rows(a):
                m = admit.reshape((1, admit.shape[0]) + (1,) * (a.ndim - 2))
                return jnp.where(m, jnp.zeros((), a.dtype), a)
            out_blocks = []
            for bi, kind in enumerate(kinds):
                c = cache["blocks"][bi]
                if kind in blocks_mod.PAGED_KINDS:
                    out_blocks.append(c)
                else:
                    out_blocks.append(jax.tree.map(zero_rows, c))
            out = {"blocks": out_blocks}
            if "shared_attn" in cache:
                out["shared_attn"] = cache["shared_attn"]
            return out
        return reset

    def _chunk_t0(self, ci: int):
        """Device scalar for chunk offset ``ci * prefill_chunk``, uploaded
        once per distinct chunk index (explicit h2d; amortized across every
        later admission reusing the same offset)."""
        t0 = self._t0_cache.get(ci)
        if t0 is None:
            t0 = jax.device_put(
                np.asarray(ci * self.cfg.prefill_chunk, np.int32))
            self._t0_cache[ci] = t0
        return t0

    def _thr_device(self, thr: float):
        """Device scalar for the exit threshold, re-uploaded only when the
        adaptive controller actually moves it (explicit h2d; steady-state
        polls reuse the cached upload)."""
        if self._thr_cache[0] != thr:
            self._thr_cache = (thr, jax.device_put(
                np.asarray(thr, np.float32)))
        return self._thr_cache[1]

    def _tbl_dev(self):
        """Device copy of the block table, re-uploaded only when a host-side
        allocation/free actually changed it (explicit h2d; steady-state
        decode polls reuse the cached upload)."""
        if self._tbl_dirty:
            self._tbl_device = jax.device_put(self._tbl)
            self._tbl_dirty = False
        return self._tbl_device

    def _chunk_skippable(self, p: _PendingPrefill, lo: int, hi: int) -> bool:
        """A prefill chunk is skipped when no admitted row has any token to
        replay in [lo, hi) — either the whole span is prefix-cache-resident
        (start >= hi) or past the prompt (lengths <= lo).  Skipped chunks
        cost nothing: no dispatch, no prefill budget."""
        rows = p.admit
        return bool(np.all((p.start[rows] >= hi) | (p.lengths[rows] <= lo)))

    def _advance_prefill(self, max_chunks: int, rep: StepReport):
        """Run up to ``max_chunks`` pending prefill chunks (<=0 = all); merge
        into the pool and activate the slots when the last chunk lands.
        Paged arenas replay straight into the reserved pool pages and skip
        chunks fully covered by prefix-cache hits."""
        p = self._pending
        assert p is not None
        chunk = self.cfg.prefill_chunk
        paged = self.page_alloc is not None
        rep.prefill_chunk_start = p.next_chunk
        budget = max_chunks if max_chunks > 0 else p.n_chunks
        ci = p.next_chunk
        while ci < p.n_chunks and budget > 0:
            lo, hi = ci * chunk, (ci + 1) * chunk
            if paged and self._chunk_skippable(p, lo, hi):
                self.prefill_chunks_skipped += 1
                ci += 1
                continue
            with _span("prefill_chunk"):
                if paged:
                    self.cache, p.last = self._prefill_chunk(
                        self.params, self.cache,
                        jnp.asarray(p.tokens[:, lo:hi]),
                        self._chunk_t0(ci), p.lengths_d, p.start_d, p.last,
                        self._tbl_dev())
                    rep.prefill_tokens += int(np.sum(np.clip(
                        np.minimum(p.lengths, hi) - np.maximum(p.start, lo),
                        0, None)))
                else:
                    p.cache, p.last = self._prefill_chunk(
                        self.params, p.cache,
                        jnp.asarray(p.tokens[:, lo:hi]),
                        self._chunk_t0(ci), p.lengths_d, p.last)
                    rep.prefill_tokens += int(
                        np.sum(np.clip(p.lengths - lo, 0, hi - lo)))
            rep.prefill_chunks += 1
            budget -= 1
            ci += 1
        p.next_chunk = ci
        if p.next_chunk < p.n_chunks:
            return
        # last chunk replayed: merge rows into the pool and go live (paged
        # prefill already wrote the pool in place — nothing to merge)
        with _span("go_live"):
            if not paged:
                self.cache = self._merge(jnp.asarray(p.admit), p.cache,
                                         self.cache)
            # publish the finished prompts' full pages into the prefix tree
            # BEFORE activation (an eos on the first sampled token finishes the
            # slot and releases its table references; trie retention must
            # already be in place so shared pages survive)
            if self.prefix_cache is not None:
                for slot, r in zip(p.slots, p.reqs):
                    n_full = r.tokens.size // self.page_alloc.page_size
                    if n_full:
                        self.prefix_cache.insert(
                            self._slot_digests[slot][:n_full], r.tokens,
                            [int(pg) for pg in self._tbl[slot, :n_full]])
            logits_np = np.asarray(self._sync("first_token", p.last))
            for slot, r in zip(p.slots, p.reqs):
                tok0 = self._sample_first(logits_np[slot])
                r.out_tokens.append(tok0)
                self.positions[slot] = p.lengths[slot]
                self.current_tok[slot] = tok0
                self.steps_taken[slot] = 0
                self.active[slot] = True
                self.n_admitted += 1
                if r.eos_id is not None and tok0 == r.eos_id:
                    self._finish(slot)
            self._pending = None
            rep.prefill_done = True
            # async decode: host state diverged from the device carry (new live
            # slots) — the next window must be a FRESH dispatch.  Device-side
            # ordering already serializes this merge after any in-flight window
            # (both chain through self.cache donation).
            self._carry_valid = False

    def _sample_first(self, logits_row) -> int:
        # seed-engine semantics: sampling needs BOTH temperature>0 and an rng
        if self.cfg.temperature <= 0.0 or self._rng is None:
            return int(np.argmax(logits_row))
        self._admit_tick += 1
        # fold in a 0-d array (a bare python int is an implicit h2d upload)
        # and divide by temperature on host — logits_row is already host-side
        key = jax.random.fold_in(
            self._rng,
            jnp.asarray(np.asarray(1_000_003 + self._admit_tick, np.uint32)))
        scaled = np.asarray(logits_row, np.float32) / self.cfg.temperature
        return int(self._sync(
            "sample", jax.random.categorical(key, jnp.asarray(scaled))))

    # ------------------------------------------------------------------
    # decode: one fixed-shape step over the whole pool
    # ------------------------------------------------------------------
    def _step_segmented(self, tokens, positions, active_d, thr, key):
        """One decode step through the segment pipeline: run a segment,
        probe its exit head, drop exited slots from ``alive``, and stop
        dispatching segments once no *active* slot is still alive — that
        host-side short-circuit is where early exits actually save FLOPs.
        Records the dispatched depth in ``_last_depth_frac``."""
        # alive starts all-true (not `active`): inactive pool rows compute
        # and write garbage exactly like the monolithic step, so threshold-0
        # runs stay bit-identical to it; their probe hits are irrelevant
        # because finalize masks counters by `active` and the short-circuit
        # condition only consults active rows.
        alive = self._alive0
        first_exit = self._first_exit0
        x = tokens
        layers_run = 0
        segs_run = 0
        # normalized entropy is >= 0, so a threshold <= 0 can never fire an
        # exit: skip the probes AND their blocking host syncs entirely (the
        # full-depth path costs zero round-trips per token)
        probing = thr > 0.0
        for seg in self._segments:
            if self.page_alloc is not None:
                x, self.cache = self._segment_fns[seg.index](
                    self.params, self.cache, x, positions, alive, active_d,
                    self._tbl_dev())
            else:
                x, self.cache = self._segment_fns[seg.index](
                    self.params, self.cache, x, positions, alive)
            self.stage_calls[f"segment{seg.index}"] += 1
            layers_run += seg.layers
            segs_run += 1
            if seg.exit_index is None or not probing:
                continue
            alive, first_exit = self._probe_fns[seg.exit_index](
                self.params, x, alive, first_exit, self._thr_device(thr))
            self.stage_calls[f"probe{seg.exit_index}"] += 1
            # the short-circuit is an INTENDED per-probe round-trip: make
            # the d2h sync explicit so guard_polling can vouch for the rest
            if not bool(self._sync("probe", jnp.any(alive & active_d))):
                break
        greedy, sampled, self._counters = self._finalize(
            self.params, x, self._counters, first_exit, active_d, key,
            jax.device_put(np.asarray(self._rng_tick, np.int32)))
        self.stage_calls["finalize"] += 1
        self._last_segments_run = segs_run
        self._last_depth_frac = layers_run / max(1, self.model.cfg.num_layers)
        return greedy, sampled

    def step(self) -> bool:
        assert not self._win_q, \
            "step(): async decode windows in flight — sync() first"
        self._last_step_active = int(self.active.sum())
        if not self.active.any():
            return False
        with _span("step"):
            thr = (self.controller.threshold if self.controller is not None
                   else self.cfg.exit_threshold)
            key = self._rng if self._rng is not None else self._zero_key
            tokens = jnp.asarray(self.current_tok[:, None])
            positions = jnp.asarray(self.positions.astype(np.int32))
            active_d = jnp.asarray(self.active)
            with _span("dispatch"):
                if self.cfg.segmented:
                    greedy, sampled = self._step_segmented(
                        tokens, positions, active_d, thr, key)
                else:
                    args = (self.params, self.cache, tokens, positions,
                            active_d, self._counters, self._thr_device(thr),
                            key, jax.device_put(
                                np.asarray(self._rng_tick, np.int32)))
                    if self.page_alloc is not None:
                        args = args + (self._tbl_dev(),)
                    greedy, sampled, self.cache, self._counters = \
                        self._decode(*args)
                    self._last_segments_run = len(self._segments)
                    self._last_depth_frac = 1.0
            nxt = np.asarray(self._sync(
                "tokens", sampled if self._rng is not None else greedy))
            with _span("commit"):
                self._step_idx += 1
                self._rng_tick += 1
                n_active = int(self.active.sum())
                self.tokens_served += n_active
                self._tokens_since_adapt += n_active
                self.depth_weighted_tokens += self._last_depth_frac * n_active
                self._depth_since_adapt += self._last_depth_frac * n_active
                for slot in np.nonzero(self.active)[0]:
                    r = self.slot_req[slot]
                    self.steps_taken[slot] += 1
                    self.positions[slot] += 1
                    if self.steps_taken[slot] >= r.max_new:
                        self._finish(slot)  # last emitted token just ran;
                        continue            # the trailing sample is dropped
                    tok = int(nxt[slot])
                    r.out_tokens.append(tok)
                    self.current_tok[slot] = tok
                    if r.eos_id is not None and tok == r.eos_id:
                        self._finish(slot)
            self._maybe_flush()
        return True

    # ------------------------------------------------------------------
    # async decode (cfg.async_decode): double-buffered window pipeline
    # ------------------------------------------------------------------
    def _poll_async(self, prefill_budget: Optional[int] = None) -> StepReport:
        """One overlapped scheduler round: admission/prefill as usual, then
        — if a window is already in flight — pre-dispatch window N+1 from
        the device carry BEFORE blocking on window N's ring readback (the
        device computes N+1 while the host replays N's commits), else
        dispatch a fresh window from host state.  Exactly one batched d2h
        (the ring) per committed window; see docs/pipeline.md."""
        rep = self.prefill_poll(prefill_budget)
        # re-capture AFTER prefill_poll: it already stamped its completions
        done_before = len(self.completed)
        if self._win_q:
            if self._carry_valid:
                # the overlap: enqueue N+1 while N's results are read back
                self._dispatch_window(from_carry=True)
                rep.decode_dispatched += 1
            ring, part, _ = self._win_q.popleft()
            self._commit_window(np.asarray(self._sync("ring", ring)), part,
                                rep)
        elif self.active.any():
            self._dispatch_window(from_carry=False)
            rep.decode_dispatched += 1
        rep.completed += self.completed[done_before:]
        rep.tokens_in_flight = self.tokens_in_flight
        self.peak_tokens_in_flight = max(self.peak_tokens_in_flight,
                                         rep.tokens_in_flight)
        return rep

    def _eos_host(self) -> np.ndarray:
        """Per-slot eos vector for the window jit (-1 = no eos: token ids
        are non-negative, so the device compare never fires)."""
        eos = np.full(self.cfg.n_slots, -1, np.int32)
        for slot in np.nonzero(self.active)[0]:
            r = self.slot_req[slot]
            if r.eos_id is not None:
                eos[slot] = r.eos_id
        return eos

    def _flag_dev(self, val: bool):
        """Cached device bool scalar (explicit h2d, uploaded once per
        value) — the window's greedy-vs-sampled selector."""
        flag = self._flag_cache.get(val)
        if flag is None:
            flag = jax.device_put(np.asarray(val, bool))
            self._flag_cache[val] = flag
        return flag

    @property
    def tokens_in_flight(self) -> int:
        """Upper bound on tokens inside dispatched-but-unread windows
        (alive-at-dispatch slots x window length per queued window)."""
        return sum(h * self.cfg.readback_interval
                   for _, _, h in self._win_q)

    def _dispatch_window(self, *, from_carry: bool):
        """Enqueue one decode window.  ``from_carry`` chains the previous
        dispatch's device-side (cur, pos, alive, budget) — zero uploads,
        the same request chain, device-ordered after the previous window.
        A fresh dispatch uploads host state and opens a new chain whose
        participating-slot mask snapshots ``active`` (slots admitted later
        join at the NEXT fresh dispatch, never mid-chain)."""
        with _span("dispatch"):
            thr = (self.controller.threshold if self.controller is not None
                   else self.cfg.exit_threshold)
            key = self._rng if self._rng is not None else self._zero_key
            if from_carry:
                assert self._carry_valid and self._win_q
                cur, pos, alive, budget = self._dev_carry
                part = self._win_q[-1][1]          # same chain, same mask
            else:
                b = self.cfg.n_slots
                budget_h = np.zeros(b, np.int32)
                for slot in np.nonzero(self.active)[0]:
                    budget_h[slot] = (self.slot_req[slot].max_new
                                      - self.steps_taken[slot])
                cur = jnp.asarray(self.current_tok)
                pos = jnp.asarray(self.positions.astype(np.int32))
                alive = jnp.asarray(self.active)
                budget = jnp.asarray(budget_h)
                self._eos_dev = jnp.asarray(self._eos_host())
                part = self.active.copy()
            args = (self.params, self.cache, cur, pos, alive, budget,
                    self._eos_dev, self._counters, self._thr_device(thr), key,
                    jax.device_put(np.asarray(self._rng_tick, np.int32)),
                    self._flag_dev(self._rng is not None))
            if self.page_alloc is not None:
                args = args + (self._tbl_dev(),)
            (self.cache, cur, pos, alive, budget,
             self._counters, ring) = self._decode_window(*args)
            self._dev_carry = (cur, pos, alive, budget)
            self._carry_valid = True
            self._rng_tick += self.cfg.readback_interval
            self._win_q.append((ring, part, int((self.active & part).sum())))

    def _commit_window(self, ring: np.ndarray, part: np.ndarray,
                       rep: StepReport):
        """Replay one window's token ring through the EXACT synchronous
        commit semantics of ``step()`` — same ordering, same max_new
        trailing-sample discard, same eos handling — so host state after
        the replay is bit-identical to R synchronous polls.  ``part``
        masks the replay to the window's own chain: slots admitted while
        it was in flight have no ring tokens and must not replay.

        A chain whose slots all finished mid-replay leaves any still-
        queued successor window permanently dead: drop it (and the carry)
        eagerly so a later admission reusing the slot indices can never
        replay the dead chain's garbage."""
        with _span("commit"):
            R = self.cfg.readback_interval
            replayed = 0
            for j in range(R):
                mask = self.active & part
                if not mask.any():
                    break
                n_active = int(mask.sum())
                self.tokens_served += n_active
                self._tokens_since_adapt += n_active
                self.depth_weighted_tokens += 1.0 * n_active
                self._depth_since_adapt += 1.0 * n_active
                rep.n_active = n_active
                for slot in np.nonzero(mask)[0]:
                    r = self.slot_req[slot]
                    self.steps_taken[slot] += 1
                    self.positions[slot] += 1
                    if self.steps_taken[slot] >= r.max_new:
                        self._finish(slot)  # trailing sample discarded, like
                        part[slot] = False  # the synchronous step(); the slot
                        continue            # leaves the chain PERMANENTLY (a
                    tok = int(ring[slot, j])    # re-admission must not rejoin)
                    r.out_tokens.append(tok)
                    self.current_tok[slot] = tok
                    if r.eos_id is not None and tok == r.eos_id:
                        self._finish(slot)
                        part[slot] = False
                self._step_idx += 1
                replayed += 1
            if replayed:
                self._last_segments_run = len(self._segments)
                self._last_depth_frac = 1.0
                rep.decode_stepped = True
                rep.decode_steps += replayed
                rep.decode_segments_run = self._last_segments_run
                rep.decode_depth_frac = self._last_depth_frac
            if not (self.active & part).any():
                # chain died: any queued successor window is all-dead compute
                # (its act masks are false from step 0 — no counter updates,
                # no page writes) — abandon it without a readback
                self._win_q.clear()
                self._carry_valid = False
            self._maybe_flush(steps=max(1, replayed))

    def sync(self) -> List[Request]:
        """Drain the async pipeline: read back and commit every in-flight
        window, invalidate the carry.  Returns the requests completed BY
        THE DRAIN (they never appear in a later ``poll()`` report — an
        external driver calling ``sync()`` must stamp them itself).  No-op
        on synchronous schedulers; migration entry points (``export_slot``
        / ``release_slot``) and ``reset_stats`` require it first."""
        n0 = len(self.completed)
        while self._win_q:
            ring, part, _ = self._win_q.popleft()
            if not (self.active & part).any():
                continue                # dead chain: no readback needed
            rep = StepReport()
            self._commit_window(np.asarray(self._sync("ring", ring)), part,
                                rep)
        self._carry_valid = False
        return self.completed[n0:]
    def ensure_spec(self, k: int):
        """Fix the speculation window width and build the propose/verify
        jits.  ``k`` is a SHAPE (tokens are [B, k]), so it is fixed per
        arena — each stage then compiles exactly once and
        ``jit_cache_sizes()`` gains one ``propose`` and one ``verify``
        entry bounded by 1 like every other stage."""
        assert not self.cfg.async_decode, \
            "speculative pairs run propose/verify in lockstep — the async " \
            "window pipeline is exempt (SpecPair rejects async_decode)"
        assert k >= 2, f"spec window k must be >= 2, got {k}"
        if self._spec_k == 0:
            self._spec_k = k
            self._propose = jax.jit(self._make_propose(k),
                                    donate_argnums=(1,))
            self._verify = jax.jit(self._make_verify(k),
                                   donate_argnums=(1,))
        assert self._spec_k == k, \
            f"spec window is fixed per arena (have k={self._spec_k}, " \
            f"asked {k}): the propose/verify jits are fixed-shape"

    def spec_window_lens(self) -> np.ndarray:
        """Per-slot verify window ``min(k, max_new - steps_taken)`` (0 for
        idle slots).  Capping at the remaining token budget keeps every
        speculated write inside the slot's admission-reserved page budget:
        positions never exceed ``prompt + max_new - 1``, exactly the normal
        decode bound — no speculative page borrow, nothing to roll back."""
        win = np.zeros(self.cfg.n_slots, np.int32)
        for slot in np.nonzero(self.active)[0]:
            r = self.slot_req[slot]
            win[slot] = min(self._spec_k,
                            int(r.max_new - self.steps_taken[slot]))
        return win

    def spec_propose(self, win_len: np.ndarray) -> np.ndarray:
        """Draft side of one speculation round: autoregressively propose up
        to ``win_len[b]`` greedy tokens per slot in ONE jitted dispatch
        (positions/commit state untouched — the driver resyncs this arena
        from the target after the verify).  Returns the [B, k] greedy
        sequence; column j is the draft for window position j+1, the last
        column is the fed-but-unused tail dispatch."""
        assert self._spec_k, "ensure_spec(k) first"
        run = self.active & (win_len > 0)
        args = (self.params, self.cache, jnp.asarray(self.current_tok),
                jnp.asarray(self.positions.astype(np.int32)),
                jnp.asarray(run), jnp.asarray(win_len.astype(np.int32)))
        if self.page_alloc is not None:
            args = args + (self._tbl_dev(),)
        self.cache, drafts = self._propose(*args)
        return np.asarray(self._sync("draft", drafts))

    def spec_verify(self, drafts: np.ndarray,
                    win_len: np.ndarray) -> np.ndarray:
        """Target side of one speculation round: verify the per-slot window
        ``[current_tok, d_1 .. d_{win-1}]`` in one batched dispatch and
        commit the longest accepted prefix + one corrected (or bonus)
        token per slot, mirroring ``step()``'s per-token commit semantics
        exactly (max_new discards the trailing sample; eos finishes).
        ``drafts`` is [B, >=k-1] (extra columns ignored).  Returns the
        per-slot committed-token counts.

        Committed tokens are full-depth greedy by construction, so they are
        bit-identical to target-only greedy decode; they land in the
        no-exit histogram bucket on HOST (``_host_exit_extra``) because the
        commit loop — not the device scan — decides how many of the
        verified tokens an eos actually serves."""
        assert self._spec_k, "ensure_spec(k) first"
        k, b = self._spec_k, self.cfg.n_slots
        tokens = np.zeros((b, k), np.int32)
        tokens[:, 0] = self.current_tok
        tokens[:, 1:] = np.asarray(drafts, np.int32)[:, :k - 1]
        run = self.active & (win_len > 0)
        args = (self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(self.positions.astype(np.int32)),
                jnp.asarray(run), jnp.asarray(win_len.astype(np.int32)))
        if self.page_alloc is not None:
            args = args + (self._tbl_dev(),)
        self.cache, gs, nv = self._verify(*args)
        gs, nv = self._sync("verify", (gs, nv))
        committed = np.zeros(b, np.int64)
        for slot in np.nonzero(run)[0]:
            r = self.slot_req[slot]
            for j in range(int(nv[slot])):
                tok = int(gs[slot, j])
                self.steps_taken[slot] += 1
                self.positions[slot] += 1
                committed[slot] += 1
                self.tokens_served += 1
                self._tokens_since_adapt += 1
                self.depth_weighted_tokens += 1.0
                self._depth_since_adapt += 1.0
                self._host_exit_extra[self._n_exits] += 1
                if self.steps_taken[slot] >= r.max_new:
                    self._finish(slot)  # trailing sample discarded, like
                    break               # step(); later verified tokens too
                r.out_tokens.append(tok)
                self.current_tok[slot] = tok
                if r.eos_id is not None and tok == r.eos_id:
                    self._finish(slot)
                    break
        self._last_segments_run = len(self._segments)
        self._last_depth_frac = 1.0     # verify always runs full depth
        self.spec_rounds += 1
        self.spec_committed += int(committed.sum())
        self._step_idx += 1
        self._maybe_flush()
        return committed

    def spec_resync_from(self, slot: int, src, src_slot: int):
        """Align this (draft) arena's slot with the target arena's commit
        state after a verify round: position, pending token and step count
        copy over; stale draft rows past the accept point are overwritten
        before they are ever attended to (position-masked reads), which is
        why SpecPair restricts the draft to position-indexed caches."""
        self.positions[slot] = src.positions[src_slot]
        self.current_tok[slot] = src.current_tok[src_slot]
        self.steps_taken[slot] = src.steps_taken[src_slot]

    def _release_slot_pages(self, slot: int):
        """Drop the slot's block-table references (paged arenas).  Pages
        the prefix tree also holds stay resident for future prefix hits;
        slot-exclusive pages return to the free list."""
        if self.page_alloc is None:
            return
        sentinel = self.page_alloc.n_pages
        for pg in self._tbl[slot]:
            if pg != sentinel:
                self.page_alloc.release(int(pg))
        self._tbl[slot] = sentinel
        self._tbl_dirty = True
        self._slot_digests[slot] = []

    def _finish(self, slot: int):
        r = self.slot_req[slot]
        r.done, r.t_done = True, time.time()
        self.completed.append(r)
        self.slot_req[slot] = None
        self.active[slot] = False
        self._release_slot_pages(slot)

    # ------------------------------------------------------------------
    # slot migration: fixed-shape export/import of one slot's serving state
    # ------------------------------------------------------------------
    def _gather_slot(self, cache, slot):
        """Lift slot ``slot``'s batch row out of every cache leaf.  Block
        caches are stacked [n_layers, B, ...] (batch axis 1); shared-attn
        caches are [B, ...] (batch axis 0).  ``slot`` is traced, so one
        compile covers every slot."""
        def take(axis):
            return lambda a: jax.lax.dynamic_index_in_dim(
                a, slot, axis, keepdims=False)
        out = {"blocks": [jax.tree.map(take(1), c)
                          for c in cache["blocks"]]}
        if "shared_attn" in cache:
            out["shared_attn"] = [jax.tree.map(take(0), c)
                                  for c in cache["shared_attn"]]
        return out

    def _scatter_slot(self, cache, rows, slot):
        """Inverse of ``_gather_slot``: write one exported row set into
        slot ``slot`` of this arena (the cache buffer is donated)."""
        def put(axis):
            return lambda a, r: jax.lax.dynamic_update_index_in_dim(
                a, r.astype(a.dtype), slot, axis)
        out = {"blocks": [jax.tree.map(put(1), c, r)
                          for c, r in zip(cache["blocks"], rows["blocks"])]}
        if "shared_attn" in cache:
            out["shared_attn"] = [
                jax.tree.map(put(0), c, r)
                for c, r in zip(cache["shared_attn"], rows["shared_attn"])]
        return out

    def _gather_slot_paged(self, cache, tbl_row, slot):
        """Paged analogue of ``_gather_slot``: pool leaves gather the slot's
        pages through its (traced) block table row — fixed shape: ALL
        ``pages_per_slot`` entries move, sentinel entries clipped to page 0
        (the host slices the shipped range afterwards); state leaves still
        gather the batch row at ``slot``."""
        n_pages = self.page_alloc.n_pages
        tblc = jnp.clip(tbl_row, 0, n_pages - 1)

        def take(axis):
            return lambda a: jax.lax.dynamic_index_in_dim(
                a, slot, axis, keepdims=False)
        out_blocks = []
        for bi, kind in enumerate(self.model.scan_block_kinds()):
            c = cache["blocks"][bi]
            if kind in blocks_mod.PAGED_KINDS:
                # pool leaf [n_layers, n_pages, P, ...] -> [n_layers, pps, P, ...]
                out_blocks.append(jax.tree.map(lambda a: a[:, tblc], c))
            else:
                out_blocks.append(jax.tree.map(take(1), c))
        out = {"blocks": out_blocks}
        if "shared_attn" in cache:
            out["shared_attn"] = [jax.tree.map(lambda a: a[tblc], c)
                                  for c in cache["shared_attn"]]
        return out

    def _scatter_slot_paged(self, cache, rows, idxvec, slot):
        """Inverse of ``_gather_slot_paged``: pool leaves scatter page rows
        to the physical pages in ``idxvec`` [pps] (sentinel = n_pages
        entries are dropped — borrowed prefix pages and the unwritten tail
        never touch the pool); state leaves write the batch row."""
        def put(axis):
            return lambda a, r: jax.lax.dynamic_update_index_in_dim(
                a, r.astype(a.dtype), slot, axis)
        out_blocks = []
        for bi, kind in enumerate(self.model.scan_block_kinds()):
            c = cache["blocks"][bi]
            r = rows["blocks"][bi]
            if kind in blocks_mod.PAGED_KINDS:
                out_blocks.append(jax.tree.map(
                    lambda a, rr: a.at[:, idxvec].set(
                        rr.astype(a.dtype), mode="drop"), c, r))
            else:
                out_blocks.append(jax.tree.map(put(1), c, r))
        out = {"blocks": out_blocks}
        if "shared_attn" in cache:
            out["shared_attn"] = [
                jax.tree.map(lambda a, rr: a.at[idxvec].set(
                    rr.astype(a.dtype), mode="drop"), c, r)
                for c, r in zip(cache["shared_attn"], rows["shared_attn"])]
        return out

    def _detect_row_layout(self):
        """Per-leaf layout of one exported slot row: the full (abstract)
        shapes plus which axis is the time axis, found structurally by
        diffing the row shapes at ``max_len`` vs ``max_len + 1`` — leaves
        whose shape is independent of the context length (SSM/conv states,
        ring-buffer windows, encdec cross caches) get -1 and always ship
        whole; the rest are truncated to the written prefix on export.

        Paged arenas diff the block-table row length instead: the varying
        axis is the PAGE axis, truncated to the shipped ``[skip, used)``
        page range on export."""
        b, lm = self.cfg.n_slots, self.cfg.long_mode

        if self.cfg.paged:
            def rows_struct(pps):
                cache = jax.eval_shape(
                    lambda: self.model.init_decode_cache_paged(
                        b, self.page_alloc.n_pages, self.cfg.page_size))
                return jax.eval_shape(
                    self._gather_slot_paged, cache,
                    jax.ShapeDtypeStruct((pps,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32))

            flat, treedef = jax.tree.flatten(rows_struct(self._pps))
            flat2 = jax.tree.leaves(rows_struct(self._pps + 1))
            axes = []
            for a, c in zip(flat, flat2):
                ax = -1
                for i, (x, y) in enumerate(zip(a.shape, c.shape)):
                    if x != y:
                        ax = i
                        break
                assert ax < a.ndim - 1, "page axis must not be the row axis"
                axes.append(ax)
            return flat, axes, treedef

        def rows_struct(seq_len):
            cache = jax.eval_shape(
                lambda: self.model.init_decode_cache(b, seq_len,
                                                     long_mode=lm))
            return jax.eval_shape(self._gather_slot, cache,
                                  jax.ShapeDtypeStruct((), jnp.int32))

        flat, treedef = jax.tree.flatten(rows_struct(self.cfg.max_len))
        flat2 = jax.tree.leaves(rows_struct(self.cfg.max_len + 1))
        axes = []
        for a, c in zip(flat, flat2):
            ax = -1
            for i, (x, y) in enumerate(zip(a.shape, c.shape)):
                if x != y:
                    ax = i
                    break
            # quantization scales replace the last (feature) axis with 1:
            # a time axis in last position would make them unsliceable
            assert ax < a.ndim - 1, "time axis must not be the row axis"
            axes.append(ax)
        return flat, axes, treedef

    def prefix_keys(self, model: str = "") -> FrozenSet[bytes]:
        """Digest keys of every trie-resident prefix page — a migration
        source intersects these against its slot's digest chain to skip
        shipping pages the destination already holds."""
        del model
        if self.prefix_cache is None:
            return frozenset()
        return self.prefix_cache.keys()

    def export_slot(self, slot: int, *, model: str = "",
                    compress: bool = False,
                    skip_keys: FrozenSet[bytes] = frozenset()
                    ) -> SlotSnapshot:
        """Snapshot one active slot out of the arena as a ``SlotSnapshot``.

        The row gather is one fixed-shape jitted call (traced slot index);
        each leaf's time axis is then truncated on host to the prefix the
        request has actually written, so ``payload_bytes`` measures the
        bytes a migration really ships.  ``compress=True`` routes every
        float leaf through the ``kernels/feature_compress`` int8 row
        quantizer (per-row fp32 scales ride along).  The slot itself is
        left untouched — pair with ``release_slot`` to evict, or discard
        the snapshot to abort a migration.  ``model`` is accepted for
        interface uniformity with ``MultiModelScheduler`` and ignored.

        Paged arenas ship pages, not token rows: the payload slices the
        PAGE axis to ``[skip, used)`` where ``used = ceil(position/P)`` and
        ``skip`` counts the leading prompt pages whose digests appear in
        ``skip_keys`` (the destination's ``prefix_keys()``) — a migration
        between arenas with a shared system prompt moves only cold pages.
        """
        del model                      # single-model arena: one namespace
        from repro.kernels import ops as kops
        assert not self._win_q, \
            "export_slot: async decode windows in flight — sync() first"
        r = self.slot_req[slot]
        assert r is not None and self.active[slot], f"slot {slot} not active"
        position = int(self.positions[slot])
        paged = self.page_alloc is not None
        page_skip = page_used = 0
        page_digests: List[bytes] = []
        if paged:
            P = self.cfg.page_size
            page_used = -(-position // P)
            page_digests = list(self._slot_digests[slot])
            while (page_skip < min(page_used, len(page_digests))
                   and page_digests[page_skip] in skip_keys):
                page_skip += 1
            rows = self._export_rows(
                self.cache, jax.device_put(self._tbl[slot]),
                jax.device_put(np.asarray(slot, np.int32)))
        else:
            rows = self._export_rows(
                self.cache, jax.device_put(np.asarray(slot, np.int32)))
        payload: List[Any] = []
        scales: List[Optional[Any]] = []
        nbytes = 0
        for a, ax in zip(jax.tree.leaves(rows), self._row_axes_flat):
            s = None
            if compress and jnp.issubdtype(a.dtype, jnp.floating):
                # the quantizer wrapper pads eagerly (its fill scalars are
                # implicit uploads); this IS the migration payload boundary,
                # so transfers here are the intended work
                with jax.transfer_guard("allow"):
                    a, s = kops.compress_rows(a)
            ah, sh = self._sync("export", (a, s))
            if ax >= 0:
                cut = [slice(None)] * ah.ndim
                if paged:
                    cut[ax] = slice(page_skip, min(page_used, ah.shape[ax]))
                else:
                    cut[ax] = slice(0, min(position, ah.shape[ax]))
                ah = ah[tuple(cut)]
                if sh is not None:
                    sh = sh[tuple(cut)]
            payload.append(ah)
            scales.append(sh)
            nbytes += ah.nbytes + (0 if sh is None else sh.nbytes)
        self.n_exported += 1
        return SlotSnapshot(
            req=r, model=r.model, position=position,
            filled=min(position, self._clen),
            current_tok=int(self.current_tok[slot]),
            steps_taken=int(self.steps_taken[slot]),
            compressed=compress, payload=payload, scales=scales,
            payload_bytes=int(nbytes), rng_tick=self._rng_tick,
            exit_counts=self.flush_counters().copy(),
            paged=paged, page_skip=page_skip, page_used=page_used,
            page_digests=page_digests)

    def slot_payload_bytes(self, slot: int, *, model: str = "") -> int:
        """Size of the raw payload ``export_slot(slot)`` would ship, from
        the row layout and the slot's position alone (no device work) —
        what a driver feeds ``compression_decision`` BEFORE exporting, so
        choosing int8 doesn't cost a throwaway raw export.  Matches the
        exported snapshot's measured ``payload_bytes`` exactly."""
        del model
        position = int(self.positions[slot])
        if self.page_alloc is not None:
            # raw no-skip estimate: ceil(position / P) whole pages
            cut = -(-position // self.cfg.page_size)
        else:
            cut = position
        total = 0
        for ref, ax in zip(self._row_struct_flat, self._row_axes_flat):
            shape = list(ref.shape)
            if ax >= 0:
                shape[ax] = min(cut, shape[ax])
            total += int(np.prod(shape)) * ref.dtype.itemsize
        return total

    def import_slot(self, snap: SlotSnapshot) -> int:
        """Restore an exported snapshot into a free slot of THIS arena and
        resume decoding mid-flight (no prefill replay).  Truncated time
        axes are zero-padded back to the arena's fixed shape — unwritten
        rows are zero in an unmigrated arena too, and reads are masked by
        position, so a raw-payload import continues bit-identically.
        Compressed payloads are dequantized through the
        ``kernels/feature_compress`` kernel first.  The scatter is one
        fixed-shape jitted call (traced slot index): importing never adds
        per-request recompiles.  Returns the slot used.

        Paged imports rebuild the slot's block table first: pages whose
        digests the snapshot marked skipped are BORROWED from this arena's
        prefix trie (the skip contract — the source consulted our
        ``prefix_keys()``), the rest are freshly allocated; the shipped
        pages then scatter into the fresh pages through a fixed-length
        index vector (sentinel entries dropped)."""
        from repro.kernels import ops as kops
        free = self.free_slots()
        assert free, "import_slot: no free slot in this arena"
        r = snap.req
        assert not r.done and snap.steps_taken < r.max_new, \
            "import_slot: request already finished"
        paged = self.page_alloc is not None
        assert snap.paged == paged, \
            "import_slot: snapshot/arena paging modes differ"

        def pad_full(x, shape):
            if x.shape == tuple(shape):
                return x
            full = np.zeros(shape, x.dtype)
            full[tuple(slice(0, n) for n in x.shape)] = x
            return full

        slot = free[0]
        idxvec = None
        if paged:
            P, pps = self.cfg.page_size, self._pps
            n_pages = self.page_alloc.n_pages
            plen = int(r.tokens.size)
            total = -(-(plen + r.max_new) // P)
            nskip, used = snap.page_skip, snap.page_used
            shared: List[int] = []
            if nskip:
                assert self.prefix_cache is not None, \
                    "import_slot: skipped pages but no prefix cache here"
                shared = self.prefix_cache.match(
                    snap.page_digests[:nskip], r.tokens)
                assert len(shared) == nskip, \
                    "import_slot: prefix pages evicted mid-migration"
            if self.prefix_cache is not None:
                self.prefix_cache.evict_until(total - nskip)
            try:
                fresh = self.page_alloc.alloc(total - nskip)
            except MemoryError:
                for pg in shared:
                    self.page_alloc.release(pg)
                raise
            row = np.full(pps, n_pages, np.int32)
            row[:nskip] = shared
            row[nskip:total] = fresh
            # payload page row j holds physical page row[nskip + j]; rows
            # past the shipped range are zero padding -> sentinel-dropped
            idxvec = np.full(pps, n_pages, np.int32)
            for j in range(used - nskip):
                idxvec[j] = row[nskip + j]
            self._tbl[slot] = row
            self._tbl_dirty = True
            self._slot_digests[slot] = list(snap.page_digests)
        leaves = []
        # restoring the shipped payload is the migration boundary's intended
        # h2d traffic (and the dequantizer wrapper pads eagerly)
        with jax.transfer_guard("allow"):
            for ah, sh, ref in zip(snap.payload, snap.scales,
                                   self._row_struct_flat):
                if sh is not None:
                    a = kops.decompress_rows(
                        jnp.asarray(pad_full(ah, ref.shape)),
                        jnp.asarray(pad_full(sh, ref.shape[:-1] + (1,))),
                        dtype=ref.dtype)
                else:
                    a = jnp.asarray(pad_full(ah, ref.shape))
                leaves.append(a)
        rows = jax.tree.unflatten(self._row_treedef, leaves)
        if paged:
            self.cache = self._import_rows(
                self.cache, rows, jnp.asarray(idxvec),
                jax.device_put(np.asarray(slot, np.int32)))
            if self.prefix_cache is not None and snap.page_digests:
                # publish the imported prompt pages so later admissions
                # (and further migrations) can share them here too
                n_full = len(snap.page_digests)
                self.prefix_cache.insert(
                    snap.page_digests, r.tokens,
                    [int(self._tbl[slot, i]) for i in range(n_full)])
        else:
            self.cache = self._import_rows(
                self.cache, rows, jax.device_put(np.asarray(slot, np.int32)))
        r.slot = slot
        self.slot_req[slot] = r
        self.positions[slot] = snap.position
        self.current_tok[slot] = snap.current_tok
        self.steps_taken[slot] = snap.steps_taken
        self.active[slot] = True
        self.n_imported += 1
        self._carry_valid = False   # async decode: new live slot, fresh
        return slot                 # dispatch required

    def free_slots(self, model: str = "") -> List[int]:
        """Slots with no request bound (staged admissions count as bound)."""
        del model
        return [i for i in range(self.cfg.n_slots)
                if self.slot_req[i] is None]

    def active_requests(self) -> List[tuple]:
        """``[(model, slot, request)]`` for every in-flight decode slot."""
        return [(r.model, i, r) for i, r in enumerate(self.slot_req)
                if r is not None and self.active[i]]

    def release_slot(self, slot: int, *, model: str = "") -> Request:
        """Evict a slot WITHOUT completing its request — the migration
        path: the request continues in another arena from its exported
        snapshot.  The cache rows are left stale; admission merge or
        ``import_slot`` overwrites them before the slot is read again."""
        del model
        assert not self._win_q, \
            "release_slot: async decode windows in flight — sync() first"
        r = self.slot_req[slot]
        assert r is not None, f"slot {slot} empty"
        self.slot_req[slot] = None
        self.active[slot] = False
        self._release_slot_pages(slot)
        r.slot = -1
        return r

    def drain_queue(self) -> List[Request]:
        """Pop every not-yet-admitted request (tier drain on an outage)."""
        out = list(self.queue)
        self.queue.clear()
        return out

    def cancel_pending(self) -> List[Request]:
        """Abandon an in-flight chunked admission and return its requests
        (their prefill restarts wherever they are resubmitted)."""
        if self._pending is None:
            return []
        reqs = list(self._pending.reqs)
        for slot in self._pending.slots:
            self.slot_req[slot] = None
            self._release_slot_pages(slot)
        for r in reqs:
            r.slot = -1
        self._pending = None
        return reqs

    # ------------------------------------------------------------------
    # exit statistics: device counters, periodic flush, adaptive control
    # ------------------------------------------------------------------
    def _maybe_flush(self, steps: int = 1):
        """Periodic counter flush / adaptive update.  ``steps`` is how many
        decode steps landed since the last check (async window commits
        replay a whole window at once): the flush fires iff ``_step_idx``
        crossed a multiple of ``flush_every`` within the last ``steps``
        increments — identical to the per-step check at ``steps=1``."""
        if (self.controller is not None
                and self._tokens_since_adapt >= self.adaptive_every):
            self.flush_counters()
            # one code path: the controller consumes the depth the segment
            # pipeline measured (monolithic mode truthfully reports 1.0 —
            # it never truncates), not a histogram-derived estimate
            self.controller.update_measured(
                self._depth_since_adapt / max(1, self._tokens_since_adapt))
            self._tokens_since_adapt = 0
            self._depth_since_adapt = 0.0
        elif (self._step_idx % self.cfg.flush_every) < steps:
            self.flush_counters()

    def flush_counters(self) -> np.ndarray:
        """Sync the cumulative device-side exit histogram to host (an
        intended d2h round-trip, made explicit for the transfer guard) and
        fold in the host-side histogram of verify-committed tokens."""
        self.exit_counts = np.asarray(self._sync("flush", self._counters),
                                      np.int64) + self._host_exit_extra
        return self.exit_counts

    def reset_stats(self):
        """Zero served-token accounting and exit counters (e.g. after a
        compile-warmup request, so reports cover only the real trace).
        Drains any in-flight async windows first — their committed tokens
        belong to the PRE-reset accounting era."""
        self.sync()
        self._counters = jnp.zeros(self._n_exits + 1, jnp.int32)
        self.exit_counts = np.zeros(self._n_exits + 1, np.int64)
        self._host_exit_extra = np.zeros(self._n_exits + 1, np.int64)
        self.tokens_served = 0
        self._tokens_since_adapt = 0
        self.depth_weighted_tokens = 0.0
        self._depth_since_adapt = 0.0
        self.spec_rounds = 0
        self.spec_committed = 0
        for name in self.stage_calls:
            self.stage_calls[name] = 0
        self.host_ms_total = 0.0
        self.device_ms_total = 0.0
        self.peak_tokens_in_flight = 0
        self.completed.clear()

    def measured_depth_fraction(self) -> float:
        """Layer-weighted fraction of the stack the decode pipeline actually
        dispatched per served token (1.0 = every token ran full depth)."""
        if not self.tokens_served:
            return 1.0
        return self.depth_weighted_tokens / self.tokens_served

    def exit_stats(self) -> Dict[str, float]:
        self.flush_counters()
        st = exit_stats_dict(self.exit_counts, self.tokens_served)
        st["measured_depth"] = self.measured_depth_fraction()
        return st

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compile counts of the hot jitted functions — the no-recompilation
        invariant the tests assert (slot churn must never retrace; every
        entry stays <= 1, and the number of decode entries is bounded by the
        number of depth segments + exit probes + finalize)."""
        sizes = {"prefill": self._prefill_chunk._cache_size(),
                 "export_rows": self._export_rows._cache_size(),
                 "import_rows": self._import_rows._cache_size()}
        if self.cfg.segmented:
            for seg in self._segments:
                sizes[f"segment{seg.index}"] = \
                    self._segment_fns[seg.index]._cache_size()
            for ei in range(self._n_exits):
                sizes[f"probe{ei}"] = self._probe_fns[ei]._cache_size()
            sizes["finalize"] = self._finalize._cache_size()
        else:
            sizes["decode"] = self._decode._cache_size()
            if self.cfg.async_decode:
                sizes["decode_window"] = self._decode_window._cache_size()
        if self._spec_k:
            sizes["propose"] = self._propose._cache_size()
            sizes["verify"] = self._verify._cache_size()
        return sizes

    def audit_stages(self) -> Dict[str, "StageSpec"]:
        """Registry of every jitted stage this arena dispatches, with the
        exact abstract argument shapes the serving loop feeds it — the
        contract the jaxpr auditor (``repro.analysis.jaxpr_audit``) traces
        against.  Mirrors ``jit_cache_sizes()`` (plus the init/merge
        helpers); segment/probe/finalize shapes are chained through
        ``jax.eval_shape`` so hidden-state widths come from the model, not
        a guess.  The encdec cross-cache primer is NOT registered: its
        frames argument is per-request-shaped, so there is no single
        abstract signature to audit."""
        cfg, b = self.cfg, self.cfg.n_slots
        i32, f32 = jnp.int32, jnp.float32
        S = jax.ShapeDtypeStruct
        params_s = jax.tree.map(lambda a: S(jnp.shape(a), a.dtype),
                                self.params)
        cache_s = jax.eval_shape(self._init_cache)
        key_s = S(self._zero_key.shape, self._zero_key.dtype)
        counters_s = S((self._n_exits + 1,), i32)
        bvec_i, bvec_b = S((b,), i32), S((b,), jnp.bool_)
        tok1, last_s = S((b, 1), i32), S((b, self._vocab), f32)
        scalar_i, scalar_f = S((), i32), S((), f32)
        paged = cfg.paged
        tbl_s = S((b, self._pps), i32) if paged else None

        stages: Dict[str, StageSpec] = {
            "init_cache": StageSpec("init_cache", self._init_cache, (),
                                    cache_out=lambda o: o),
            "fresh_last": StageSpec("fresh_last", self._fresh_last, ()),
        }
        if self._reset_states is not None:
            stages["reset_states"] = StageSpec(
                "reset_states", self._reset_states, (cache_s, bvec_b),
                donate_argnums=(0,), cache_in=0, cache_out=lambda o: o)
        if not paged:
            stages["merge"] = StageSpec(
                "merge", self._merge, (bvec_b, cache_s, cache_s),
                donate_argnums=(2,), cache_in=2, cache_out=lambda o: o)
        chunk_s = S((b, cfg.prefill_chunk), i32)
        if paged:
            pf_args = (params_s, cache_s, chunk_s, scalar_i, bvec_i,
                       bvec_i, last_s, tbl_s)
            pf_donate = (1, 6)
        else:
            pf_args = (params_s, cache_s, chunk_s, scalar_i, bvec_i, last_s)
            pf_donate = (1, 5)
        stages["prefill"] = StageSpec(
            "prefill", self._prefill_chunk, pf_args,
            donate_argnums=pf_donate, cache_in=1, cache_out=lambda o: o[0])
        if cfg.segmented:
            x = tok1
            for seg in self._segments:
                fn = self._segment_fns[seg.index]
                args = (params_s, cache_s, x, bvec_i, bvec_b, bvec_b,
                        tbl_s) if paged \
                    else (params_s, cache_s, x, bvec_i, bvec_b)
                stages[f"segment{seg.index}"] = StageSpec(
                    f"segment{seg.index}", fn, args, donate_argnums=(1,),
                    cache_in=1, cache_out=lambda o: o[1])
                x = jax.eval_shape(fn, *args)[0]
                if seg.exit_index is not None:
                    stages[f"probe{seg.exit_index}"] = StageSpec(
                        f"probe{seg.exit_index}",
                        self._probe_fns[seg.exit_index],
                        (params_s, x, bvec_b, bvec_i, scalar_f))
            stages["finalize"] = StageSpec(
                "finalize", self._finalize,
                (params_s, x, counters_s, bvec_i, bvec_b, key_s, scalar_i),
                donate_argnums=(2,))
        else:
            dec_args = (params_s, cache_s, tok1, bvec_i, bvec_b, counters_s,
                        scalar_f, key_s, scalar_i)
            if paged:
                dec_args = dec_args + (tbl_s,)
            stages["decode"] = StageSpec(
                "decode", self._decode, dec_args, donate_argnums=(1, 5),
                cache_in=1, cache_out=lambda o: o[2])
            if cfg.async_decode:
                win_args = (params_s, cache_s, bvec_i, bvec_i, bvec_b,
                            bvec_i, bvec_i, counters_s, scalar_f, key_s,
                            scalar_i, S((), jnp.bool_))
                if paged:
                    win_args = win_args + (tbl_s,)
                stages["decode_window"] = StageSpec(
                    "decode_window", self._decode_window, win_args,
                    donate_argnums=(1, 2, 3, 4, 5, 7),
                    cache_in=1, cache_out=lambda o: o[0])
        if paged:
            exp_args = (cache_s, S((self._pps,), i32), scalar_i)
            rows_s = jax.eval_shape(self._export_rows, *exp_args)
            imp_args = (cache_s, rows_s, S((self._pps,), i32), scalar_i)
        else:
            exp_args = (cache_s, scalar_i)
            rows_s = jax.eval_shape(self._export_rows, *exp_args)
            imp_args = (cache_s, rows_s, scalar_i)
        stages["export_rows"] = StageSpec(
            "export_rows", self._export_rows, exp_args, cache_in=0)
        stages["import_rows"] = StageSpec(
            "import_rows", self._import_rows, imp_args,
            donate_argnums=(0,), cache_in=0, cache_out=lambda o: o)
        if self._spec_k:
            k = self._spec_k
            pro_args = (params_s, cache_s, bvec_i, bvec_i, bvec_b, bvec_i)
            ver_args = (params_s, cache_s, S((b, k), i32), bvec_i, bvec_b,
                        bvec_i)
            if paged:
                pro_args = pro_args + (tbl_s,)
                ver_args = ver_args + (tbl_s,)
            stages["propose"] = StageSpec(
                "propose", self._propose, pro_args, donate_argnums=(1,),
                cache_in=1, cache_out=lambda o: o[0])
            stages["verify"] = StageSpec(
                "verify", self._verify, ver_args, donate_argnums=(1,),
                cache_in=1, cache_out=lambda o: o[0])
        return stages
