"""Runtime invariant guards for the serving stack.

Three guards, all opt-in (tests attach them; production polling pays
nothing):

* :func:`no_recompile` — context manager asserting that a scheduler /
  pool / cluster compiled at most ``bound`` new jit entries inside the
  block (``jit_cache_sizes()`` deltas; the steady-state contract is one
  compile per stage, forever).
* :func:`guard_polling` / :func:`transfer_guard` — make *implicit*
  host<->device transfers inside ``poll()`` hard errors.  Intended syncs
  in the hot loop must be explicit (``jax.device_get`` /
  ``jax.device_put``) so every round-trip is visible in the source.
* :func:`guard_sync_budget` — count the EXPLICIT ``jax.device_get``
  syncs each ``poll()`` performs and assert the count never exceeds
  ``bound``.  The overlapped pipeline's contract is at most one device
  sync per readback window (the batched ring readback); this guard makes
  a regression back to per-token syncs a hard test failure.
* :class:`SlotAudit` — wraps ``poll()`` and re-checks slot-accounting
  invariants after every round: free+staged+live slots partition the
  pool, positions/steps stay in range, booking ledgers balance, and at
  completion the exit-counter histogram equals ``tokens_served`` and no
  orphaned migration state remains.  Speculative draft/target pairs
  (``SpecPair``, standalone or inside a cluster's device/cloud bridge)
  additionally get per-round pair invariants: after every verify round
  the draft shadow's position/pending-token/step state agrees with its
  target slot, finished targets leave no live shadow behind (no orphaned
  draft slots or page refcounts), and every live draft slot belongs to a
  tracked pair.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np


class GuardError(AssertionError):
    """A runtime invariant guard tripped."""


# ---------------------------------------------------------------------------
# no_recompile: jit cache deltas
# ---------------------------------------------------------------------------
def _flat_cache_sizes(target: Any) -> Dict[str, int]:
    """Flatten (possibly nested, e.g. cluster tier -> stage) cache-size
    dicts to ``"tier/stage" -> n``."""
    out: Dict[str, int] = {}

    def rec(prefix: str, d: Dict[str, Any]) -> None:
        for k, v in d.items():
            key = f"{prefix}/{k}" if prefix else str(k)
            if isinstance(v, dict):
                rec(key, v)
            else:
                out[key] = int(v)

    rec("", target.jit_cache_sizes())
    return out


@contextlib.contextmanager
def no_recompile(target: Any, *, bound: int = 0) -> Iterator[None]:
    """Assert ``target`` compiles at most ``bound`` NEW jit entries inside
    the block."""
    before = _flat_cache_sizes(target)
    yield
    after = _flat_cache_sizes(target)
    grown: Dict[str, tuple] = {}
    total = 0
    for key, n_after in after.items():
        n_before = before.get(key, 0)
        delta = n_after - n_before
        if delta > 0:
            grown[key] = (n_before, n_after)
            total += delta
    if total > bound:
        detail = ", ".join(f"{k}: {a}->{b}" for k, (a, b) in sorted(grown.items()))
        raise GuardError(
            f"no_recompile(bound={bound}): {total} new jit compilation(s) "
            f"inside guarded block ({detail}) — a fixed-shape stage retraced")


# ---------------------------------------------------------------------------
# transfer guard: implicit host<->device syncs become hard errors
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def transfer_guard(mode: str = "disallow") -> Iterator[None]:
    """Thin canonical wrapper over ``jax.transfer_guard``: under
    ``"disallow"``, implicit transfers raise while explicit
    ``jax.device_get`` / ``jax.device_put`` stay legal."""
    with jax.transfer_guard(mode):
        yield


@contextlib.contextmanager
def guard_polling(target: Any, mode: str = "disallow") -> Iterator[Any]:
    """Patch ``target.poll`` so every call runs under ``transfer_guard``:
    an implicit sync inside the scheduler/cluster hot loop is a hard
    error, while setup/teardown (submit, flush, result reads) outside
    ``poll()`` stays unrestricted.  Warm the jit caches with one poll
    BEFORE entering — compilation itself may transfer.

    Legal (explicit) sync points inside ``poll()``:

    * synchronous pools — the one ``jax.device_get`` of the step's
      sampled tokens, plus the periodic exit-counter flush;
    * async pools (``cfg.async_decode``) — the one BATCHED
      ``jax.device_get`` of a readback window's token ring (one sync per
      ``readback_interval`` decode steps), the counter flush, and the
      host->device uploads of a fresh window's slot state
      (``jnp.asarray`` on host numpy, an explicit put).

    Everything else — ``.item()``, ``float()``, ``np.asarray`` straight
    on a traced output — is implicit and trips the guard (and the SYN
    analyzer rules flag it statically)."""
    orig = target.poll

    def guarded(*a: Any, **kw: Any):
        with jax.transfer_guard(mode):
            return orig(*a, **kw)

    target.poll = guarded
    try:
        yield target
    finally:
        target.poll = orig


@contextlib.contextmanager
def guard_sync_budget(target: Any, *, bound: int = 1,
                      count_puts: bool = False) -> Iterator[Dict[str, int]]:
    """Patch ``target.poll`` so each call counts its explicit
    ``jax.device_get`` syncs (and ``jax.device_put`` uploads when
    ``count_puts``) and raise :class:`GuardError` the moment one poll
    exceeds ``bound``.

    This is the overlapped pipeline's quantitative contract: at most ONE
    device readback per readback window — the batched token-ring fetch.
    A sync scheduler pays one ``device_get`` per decoded token, so
    attaching this guard with ``bound=1`` to a decode-phase async pool
    both passes and FAILS if someone reintroduces a per-step sync.

    Caveats: the periodic exit-counter flush is itself a ``device_get``,
    so polls where ``flush_every`` fires need ``bound >= 2`` — tests
    should either raise the bound or configure ``flush_every`` past the
    guarded span.  Prefill/admission polls also read back exit probes;
    attach the guard around the DECODE phase (queue drained, prefills
    done) for a tight bound.

    Yields a stats dict (``polls``, ``syncs``, ``max_per_poll``) that
    keeps updating while the guard is attached."""
    orig_poll = target.poll
    stats = {"polls": 0, "syncs": 0, "max_per_poll": 0}

    def counted(*a: Any, **kw: Any):
        real_get, real_put = jax.device_get, jax.device_put
        n = [0]

        def spy_get(x, *ga: Any, **gkw: Any):
            n[0] += 1
            return real_get(x, *ga, **gkw)

        def spy_put(x, *pa: Any, **pkw: Any):
            n[0] += 1
            return real_put(x, *pa, **pkw)

        jax.device_get = spy_get
        if count_puts:
            jax.device_put = spy_put
        try:
            rep = orig_poll(*a, **kw)
        finally:
            jax.device_get = real_get
            jax.device_put = real_put
        stats["polls"] += 1
        stats["syncs"] += n[0]
        stats["max_per_poll"] = max(stats["max_per_poll"], n[0])
        if n[0] > bound:
            raise GuardError(
                f"guard_sync_budget(bound={bound}): poll {stats['polls']} "
                f"performed {n[0]} device sync(s) — the overlapped pipeline "
                f"allows at most {bound} per readback window")
        return rep

    target.poll = counted
    try:
        yield stats
    finally:
        target.poll = orig_poll


# ---------------------------------------------------------------------------
# SlotAudit: slot accounting / booking-ledger invariants after every poll
# ---------------------------------------------------------------------------
class SlotAudit:
    """Re-checks pool invariants after every ``poll()``.

    ``SlotAudit(sched).attach()`` wraps the target's ``poll``; call
    ``detach()`` (or use as a context manager) to restore.  Works on a
    ``ContinuousBatchScheduler``, a ``MultiModelScheduler`` (audits every
    per-model arena), or a ``TieredServingCluster`` (audits every tier's
    pool plus the booking ledgers and migration queues).
    """

    def __init__(self, target: Any):
        self.target = target
        self.polls = 0
        self._orig_poll: Optional[Any] = None

    # -- lifecycle ----------------------------------------------------------
    def attach(self) -> "SlotAudit":
        assert self._orig_poll is None, "already attached"
        orig = self.target.poll

        def audited(*a: Any, **kw: Any):
            rep = orig(*a, **kw)
            self.check()
            return rep

        self._orig_poll = orig
        self.target.poll = audited
        return self

    def detach(self) -> None:
        if self._orig_poll is not None:
            self.target.poll = self._orig_poll
            self._orig_poll = None

    def __enter__(self) -> "SlotAudit":
        return self.attach()

    def __exit__(self, *exc: Any) -> None:
        self.detach()

    # -- checks -------------------------------------------------------------
    def check(self) -> None:
        self.polls += 1
        violations: List[str] = []
        t = self.target
        if hasattr(t, "tiers"):
            self._check_cluster(t, violations)
        elif hasattr(t, "pools"):
            for name, pool in t.pools.items():
                self._check_pool(pool, violations, prefix=f"pool {name}: ")
                if not t.has_work:
                    self._check_pool_idle(pool, violations,
                                          prefix=f"pool {name}: ")
            if hasattr(t, "draft_name"):   # SpecPair pair invariants
                self._check_spec_pair(t, violations)
        else:
            self._check_pool(t, violations)
            if not t.has_work:
                self._check_pool_idle(t, violations)
        if violations:
            raise GuardError(
                "slot audit failed after poll "
                f"{self.polls}:\n  " + "\n  ".join(violations))

    # one ContinuousBatchScheduler arena, between polls -----------------
    @staticmethod
    def _check_pool(s: Any, out: List[str], prefix: str = "") -> None:
        n = s.cfg.n_slots
        staged = set(s._pending.slots) if s._pending is not None else set()
        for i in range(n):
            booked = s.slot_req[i] is not None
            live = bool(s.active[i])
            if live and not booked:
                out.append(f"{prefix}slot {i} active without a request "
                           f"(free+active != slots)")
            if booked and not live and i not in staged:
                out.append(f"{prefix}slot {i} holds a request but is neither "
                           f"live nor staged for prefill (leaked slot)")
            if live and booked:
                r = s.slot_req[i]
                if not (0 <= s.positions[i] <= s.cfg.max_len):
                    out.append(f"{prefix}slot {i} position "
                               f"{int(s.positions[i])} outside "
                               f"[0, {s.cfg.max_len}]")
                if s.steps_taken[i] > r.max_new:
                    out.append(f"{prefix}slot {i} ran {int(s.steps_taken[i])} "
                               f"decode steps > max_new {r.max_new}")
        for r in s.completed:
            if not r.done:
                out.append(f"{prefix}completed request {r.req_id} not "
                           f"marked done")
        if getattr(s, "page_alloc", None) is not None:
            SlotAudit._check_pages(s, out, prefix)

    # paged arena: block tables + prefix tree partition the page pool ----
    @staticmethod
    def _check_pages(s: Any, out: List[str], prefix: str = "") -> None:
        alloc = s.page_alloc
        n_pages = alloc.n_pages
        staged = set(s._pending.slots) if s._pending is not None else set()
        refs = np.zeros(n_pages, np.int64)
        for i in range(s.cfg.n_slots):
            row = s._tbl[i]
            held = row[row < n_pages]
            if s.slot_req[i] is None and i not in staged:
                if held.size:
                    out.append(f"{prefix}freed slot {i} still maps "
                               f"{held.size} page(s) (page leak)")
                continue
            if np.unique(held).size != held.size:
                out.append(f"{prefix}slot {i} maps the same page twice "
                           f"(table corruption)")
            for pg in held:
                refs[int(pg)] += 1
        trie_pages = (s.prefix_cache.pages()
                      if s.prefix_cache is not None else {})
        for pg in trie_pages:
            refs[pg] += 1
        # 1) allocator refcounts == slot references + trie residency
        bad = np.nonzero(refs != alloc.refcount)[0]
        for pg in bad[:8]:
            out.append(f"{prefix}page {int(pg)} refcount "
                       f"{int(alloc.refcount[pg])} != {int(refs[pg])} "
                       f"observed owner(s) (refcount drift)")
        # 2) a page mapped by >1 slot must be prefix-shared (trie-resident):
        # otherwise two requests would write the same physical page
        multi = np.nonzero(refs > 1)[0]
        for pg in multi:
            slot_refs = int(refs[pg]) - (1 if int(pg) in trie_pages else 0)
            if slot_refs > 1 and int(pg) not in trie_pages:
                out.append(f"{prefix}page {int(pg)} shared by {slot_refs} "
                           f"slots without prefix-tree ownership (COW "
                           f"violation)")
        # 3) free list and referenced pages partition the pool exactly
        free = set(alloc._free)
        used = set(np.nonzero(refs)[0].tolist())
        both = free & used
        for pg in sorted(both)[:8]:
            out.append(f"{prefix}page {int(pg)} is simultaneously free and "
                       f"referenced")
        if len(free) + len(used) != n_pages or (free | used) != set(
                range(n_pages)):
            out.append(f"{prefix}page partition broken: {len(free)} free + "
                       f"{len(used)} referenced != {n_pages} pool pages")

    # …and once the pool is fully drained -------------------------------
    @staticmethod
    def _check_pool_idle(s: Any, out: List[str], prefix: str = "") -> None:
        if any(q is not None for q in s.slot_req):
            return                      # not actually idle (defensive)
        # the exit histogram must balance the served-token count exactly;
        # flushing syncs, so explicitly allow the transfer (the audit runs
        # inside guard_polling's disallow scope in tests)
        with jax.transfer_guard("allow"):
            counts = s.flush_counters()
        total = int(np.sum(counts))
        if total != s.tokens_served:
            out.append(f"{prefix}exit-counter histogram sums to {total} but "
                       f"tokens_served is {s.tokens_served} (alive-mask / "
                       f"counter drift)")

    # SpecPair: draft/target agreement + shadow-slot hygiene -------------
    @staticmethod
    def _check_spec_pair(p: Any, out: List[str], prefix: str = "") -> None:
        tgt = p.pools[p.target_name]
        drf = p.pools[p.draft_name]
        shadow_of = {}                 # draft slot -> req_id (live shadows)
        for rid, (req, shadow) in p._pairs.items():
            d_live = (shadow.slot >= 0 and drf.active[shadow.slot]
                      and drf.slot_req[shadow.slot] is shadow)
            if req.done:
                # a finished target must not leave a LIVE shadow behind —
                # its slot (and page refcounts) would leak until the pool
                # drains.  Staged-mid-prefill shadows are reaped later by
                # design and stay tracked in _pairs meanwhile.
                if d_live:
                    out.append(f"{prefix}request {rid} done but its draft "
                               f"shadow still holds live slot "
                               f"{shadow.slot} (orphaned draft slot)")
                continue
            if d_live:
                shadow_of[shadow.slot] = rid
            if not (d_live and req.slot >= 0 and tgt.active[req.slot]):
                continue               # pair not live in both arenas yet
            ts, ds = req.slot, shadow.slot
            # post-round resync contract: the draft mirrors the target's
            # commit state exactly before the next propose reads it
            if int(drf.positions[ds]) != int(tgt.positions[ts]):
                out.append(f"{prefix}pair {rid}: draft position "
                           f"{int(drf.positions[ds])} != target position "
                           f"{int(tgt.positions[ts])} (resync drift)")
            if int(drf.current_tok[ds]) != int(tgt.current_tok[ts]):
                out.append(f"{prefix}pair {rid}: draft pending token "
                           f"{int(drf.current_tok[ds])} != target's "
                           f"{int(tgt.current_tok[ts])} (resync drift)")
            if int(drf.steps_taken[ds]) != int(tgt.steps_taken[ts]):
                out.append(f"{prefix}pair {rid}: draft steps "
                           f"{int(drf.steps_taken[ds])} != target steps "
                           f"{int(tgt.steps_taken[ts])}")
        for i in range(drf.cfg.n_slots):
            r = drf.slot_req[i]
            if r is not None and drf.active[i] and r.req_id not in p._pairs:
                out.append(f"{prefix}draft slot {i} live for request "
                           f"{r.req_id} with no tracked pair (orphaned "
                           f"shadow)")

    # tiered cluster: bookings, ledgers, migration queues ----------------
    def _check_cluster(self, c: Any, out: List[str]) -> None:
        for name, tr in c.tiers.items():
            sched = tr.sched
            pools = sched.pools.values() if hasattr(sched, "pools") \
                else [sched]
            for p in pools:
                self._check_pool(p, out, prefix=f"tier {name}: ")
            for m, sa in tr.slot_avail.items():
                if len(sa) != len(tr.slot_released[m]):
                    out.append(f"tier {name}: slot_avail/{m} and "
                               f"slot_released/{m} ledgers diverged "
                               f"({len(sa)} vs {len(tr.slot_released[m])})")
        for m, pair in getattr(c, "_spec_pairs", {}).items():
            for name, p in pair.pools.items():
                self._check_pool(p, out, prefix=f"spec {m}/{name}: ")
            self._check_spec_pair(pair, out, prefix=f"spec {m}: ")
        for cr in c.requests:
            if cr.done and (cr.booked_slot >= 0 or cr.pf_booked_slot >= 0):
                out.append(f"request {cr.req.req_id} done but still holds a "
                           f"slot booking (ledger leak)")
            if cr.booked_slot >= 0 and cr.booked_tier:
                tr = c.tiers.get(cr.booked_tier)
                if tr is not None and not tr.dead:
                    sa = tr.slot_avail.get(cr.booked_model, [])
                    if not (0 <= cr.booked_slot < len(sa)):
                        out.append(f"request {cr.req.req_id} booked slot "
                                   f"{cr.booked_slot} outside tier "
                                   f"{cr.booked_tier}'s ledger")
        if not c.has_work:
            for cr in c.requests:
                if cr.booked_slot >= 0 or cr.pf_booked_slot >= 0:
                    out.append(f"idle cluster: request {cr.req.req_id} "
                               f"still holds a booking")
            exported = imported = 0
            for name, tr in c.tiers.items():
                if tr.inbound:
                    out.append(f"idle cluster: tier {name} has "
                               f"{len(tr.inbound)} undelivered inbound "
                               f"migration(s) (orphaned snapshots)")
                sched = tr.sched
                pools = sched.pools.values() if hasattr(sched, "pools") \
                    else [sched]
                for p in pools:
                    exported += p.n_exported
                    imported += p.n_imported
                    self._check_pool_idle(p, out, prefix=f"tier {name}: ")
            if exported != imported:
                out.append(f"idle cluster: {exported} slots exported but "
                           f"{imported} imported (orphaned snapshot)")
            if getattr(c, "_spec_waiting", None):
                out.append(f"idle cluster: {len(c._spec_waiting)} "
                           f"speculative request(s) stuck in the bridge "
                           f"admission queue")
            stuck = [cr for cr in getattr(c, "_spec_live", {}).values()
                     if not cr.done]
            if stuck:
                out.append(f"idle cluster: {len(stuck)} speculative "
                           f"request(s) live in the bridge but not done")
            for m, pair in getattr(c, "_spec_pairs", {}).items():
                for name, p in pair.pools.items():
                    self._check_pool_idle(p, out,
                                          prefix=f"spec {m}/{name}: ")
