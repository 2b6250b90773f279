"""Overlapped decode pipeline invariants (cfg.async_decode).

The deferred-readback contract: on-device sampling into a per-slot token
ring, double-buffered window dispatch, ONE batched ``jax.device_get`` per
readback window, and a bounded-staleness commit replay that reproduces the
synchronous ``poll()`` semantics bit-for-bit — EOS and max_new included,
across attention / SSM / shared-attention / MLA arenas, paged and
contiguous, with every jit stage compiled at most once.
"""
import functools

import jax
import numpy as np
import pytest

from repro.analysis import GuardError, guard_sync_budget
from repro.analysis.lint import lint_source
from repro.configs import get_config
from repro.models import Model
from repro.serving import (ContinuousBatchScheduler, ModelGroup, Request,
                           SchedulerConfig, SpecPair)

# one representative arena per attention family: plain GQA attention,
# xLSTM recurrent state, Zamba2 shared-attention hybrid, DeepSeek MLA
ARCHS = ["granite-3-2b-smoke", "xlstm-350m-smoke", "zamba2-1.2b-smoke",
         "deepseek-v3-671b-smoke"]


@functools.lru_cache(maxsize=None)
def _arch(name):
    cfg = get_config(name)
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _make_reqs(vocab, *, n_req=5, prompt_len=6, max_new=7, eos_ids=None):
    rs = np.random.RandomState(7)
    reqs = []
    for j in range(n_req):
        length = int(rs.randint(max(1, prompt_len // 2), prompt_len + 1))
        reqs.append(Request(tokens=rs.randint(0, vocab, length),
                            max_new=max_new, req_id=j,
                            eos_id=None if eos_ids is None
                            else eos_ids.get(j)))
    return reqs


def _run_pool(name, *, async_decode, readback_interval=3, paged=False,
              slots=2, n_req=5, prompt_len=6, max_new=7, eos_ids=None,
              audit=None):
    cfg, m, params = _arch(name)
    max_len = prompt_len + max_new
    if paged:
        max_len += (-max_len) % 16
    sched = ContinuousBatchScheduler(
        m, params,
        SchedulerConfig(n_slots=slots, max_len=max_len, prefill_chunk=4,
                        exit_threshold=0.0, segmented=False, paged=paged,
                        async_decode=async_decode,
                        readback_interval=readback_interval))
    if audit is not None:
        audit(sched)
    reqs = _make_reqs(cfg.vocab_size, n_req=n_req, prompt_len=prompt_len,
                      max_new=max_new, eos_ids=eos_ids)
    for r in reqs:
        sched.submit(r)
    sched.run()
    outs = [list(r.out_tokens) for r in sorted(reqs, key=lambda r: r.req_id)]
    return sched, outs


@pytest.mark.parametrize("arch", ARCHS)
def test_deferred_readback_matches_sync_poll(arch, slot_audit):
    """Greedy outputs under the async window pipeline are bit-identical
    to the synchronous per-token poll() — slot churn, re-admission and a
    readback interval that does not divide max_new included — and the
    window stage compiles exactly once (SlotAudit runs at every readback
    boundary via the audited poll)."""
    s_sync, out_sync = _run_pool(arch, async_decode=False)
    s_async, out_async = _run_pool(arch, async_decode=True,
                                   audit=slot_audit)
    assert out_async == out_sync
    assert s_async.tokens_served == s_sync.tokens_served
    sizes = s_async.jit_cache_sizes()
    if -1 not in sizes.values():
        assert sizes["decode_window"] == 1, sizes
        assert sizes.get("decode", 0) == 0, sizes
        assert all(v <= 1 for v in sizes.values()), sizes


def test_deferred_readback_matches_sync_poll_paged(slot_audit):
    """Same parity through the paged KV arena: the window's act-masked
    paged merge must write exactly the pages the sync path writes."""
    s_sync, out_sync = _run_pool("granite-3-2b-smoke", async_decode=False,
                                 paged=True)
    s_async, out_async = _run_pool("granite-3-2b-smoke", async_decode=True,
                                   paged=True, audit=slot_audit)
    assert out_async == out_sync
    assert s_async.tokens_served == s_sync.tokens_served


def test_eos_inside_window_retro_release():
    """EOS discovered at readback, mid-window: the commit replay truncates
    the stream at the EOS token, the trailing ring entries are discarded
    (tokens_served counts NO wasted slot-steps), and the freed slot is
    re-admitted without replaying the dead chain's ring rows."""
    cfg, _, _ = _arch("granite-3-2b-smoke")
    _, probe = _run_pool("granite-3-2b-smoke", async_decode=False,
                         n_req=5, max_new=7)
    # force request 0's 3rd greedy token to be its EOS: with interval 3
    # the EOS lands inside a window, never at a boundary
    eos_ids = {0: probe[0][2]}
    s_sync, out_sync = _run_pool("granite-3-2b-smoke", async_decode=False,
                                 eos_ids=eos_ids)
    s_async, out_async = _run_pool("granite-3-2b-smoke", async_decode=True,
                                   readback_interval=3, eos_ids=eos_ids)
    assert len(out_sync[0]) == 3 and out_sync[0][-1] == eos_ids[0]
    assert out_async == out_sync
    assert s_async.tokens_served == s_sync.tokens_served


def test_async_config_validation():
    """async_decode is rejected on the segmented decode path and with a
    degenerate readback interval — at construction, not mid-trace."""
    cfg, m, params = _arch("granite-3-2b-smoke")
    with pytest.raises(ValueError, match="segmented"):
        ContinuousBatchScheduler(
            m, params, SchedulerConfig(n_slots=2, max_len=16,
                                       async_decode=True))
    with pytest.raises(ValueError, match="readback_interval"):
        ContinuousBatchScheduler(
            m, params, SchedulerConfig(n_slots=2, max_len=16,
                                       segmented=False, async_decode=True,
                                       readback_interval=0))


def test_spec_pair_rejects_async():
    """Propose/verify is host-lockstep by construction: SpecPair must
    refuse an async config instead of silently serializing it."""
    cfg, m, params = _arch("granite-3-2b-smoke")
    group = ModelGroup([("draft", m, params), ("target", m, params)])
    with pytest.raises(ValueError, match="async"):
        SpecPair(group, SchedulerConfig(n_slots=2, max_len=24,
                                        exit_threshold=0.0, segmented=False,
                                        async_decode=True),
                 k=4)


def test_sync_drains_inflight_windows():
    """sync() pops every queued window, commits the live chains, and
    leaves the pool in a state the migration entry points accept."""
    cfg, m, params = _arch("granite-3-2b-smoke")
    sched = ContinuousBatchScheduler(
        m, params,
        SchedulerConfig(n_slots=2, max_len=20, prefill_chunk=4,
                        exit_threshold=0.0, segmented=False,
                        async_decode=True, readback_interval=4))
    r = Request(tokens=np.arange(4) % cfg.vocab_size, max_new=12, req_id=0)
    sched.submit(r)
    while not sched._win_q:
        sched.poll()
    drained = sched.sync()
    assert not sched._win_q and not sched._carry_valid
    assert all(req.done for req in drained)
    sched.run()
    assert r.done and len(r.out_tokens) == 12


def test_sync_budget_one_readback_per_window():
    """The quantitative pipeline contract: in the decode phase the async
    pool performs at most ONE device_get per poll (the batched ring
    readback), while the sync pool pays one per decoded token — attaching
    the same guard with bound=0 trips on its first decode poll."""
    cfg, m, params = _arch("granite-3-2b-smoke")

    def build(async_decode):
        sched = ContinuousBatchScheduler(
            m, params,
            SchedulerConfig(n_slots=2, max_len=24, prefill_chunk=8,
                            exit_threshold=0.0, segmented=False,
                            flush_every=10 ** 6, async_decode=async_decode,
                            readback_interval=4))
        for j in range(2):
            sched.submit(Request(tokens=(np.arange(6) + j) % cfg.vocab_size,
                                 max_new=16, req_id=j))
        # drain admission + prefill outside the guard: exit probes and
        # uploads there are legal syncs with their own budget
        while sched.queue or sched._pending is not None \
                or not sched.active.any():
            sched.poll()
        return sched

    pool = build(async_decode=True)
    with guard_sync_budget(pool, bound=1) as stats:
        pool.run()
    assert stats["polls"] > 0 and stats["max_per_poll"] <= 1
    assert stats["syncs"] >= 1          # the batched readbacks happened

    pool = build(async_decode=False)
    with pytest.raises(GuardError, match="sync"):
        with guard_sync_budget(pool, bound=0):
            pool.run()


def test_syn_rules_fire_on_seeded_violations():
    """The analyzer's poll-hot-loop pass: implicit concretization, raw
    numpy conversion, and a dispatch barrier all fire; the legal batched
    ``np.asarray(jax.device_get(...))`` idiom and the scheduler's
    ``self._sync`` helper stay silent."""
    seeded = '''
import jax
import numpy as np

class Pool:
    def __init__(self, fn):
        self._decode = jax.jit(fn)
        self.cache = None

    def poll(self):
        out = self._decode(self.cache)
        a = out.item()                          # SYN001
        b = np.asarray(out)                     # SYN002
        out.block_until_ready()                 # SYN003
        return a, b

    def _commit_round(self):
        toks, self.ring = self._decode(self.cache)
        return int(toks)                        # SYN001 (unpack taint)
'''
    rules = sorted(f.rule for f in lint_source(seeded, "seeded.py"))
    assert rules == ["SYN001", "SYN001", "SYN002", "SYN003"], rules

    legal = '''
import jax
import numpy as np

class Pool:
    def __init__(self, fn):
        self._decode = jax.jit(fn)
        self.cache = None

    def poll(self):
        ring = self._decode(self.cache)
        host = np.asarray(jax.device_get(ring))   # batched readback
        go = bool(self._sync("probe", ring))      # the scheduler's helper
        return int(jax.device_get(ring[0]))       # explicit commit read
'''
    assert [f.rule for f in lint_source(legal, "legal.py")] == []
