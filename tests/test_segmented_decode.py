"""Depth-segmented decode: parity with the monolithic path and proof that
early exits truncate compute (not just counters).

Three layers of guarantees:

* **Model-level bit-parity** (eager, no XLA fusion): composing
  ``embed_decode_tokens -> decode_segment* -> finalize_decode`` with an
  all-true alive mask reproduces ``decode_step`` bit-for-bit across an
  attention, an SSM, and a shared-attn (hybrid) config.
* **Scheduler-level parity** at threshold 0: the segmented scheduler emits
  the same tokens and exit counters as the monolithic (pre-refactor)
  scheduler; caches agree to bf16 rounding (different jit boundaries let
  XLA fuse the norm reductions differently, so cross-compilation
  bit-identity is not attainable — the eager test above carries the
  bit-level claim).
* **Compute truncation**: under a permissive threshold the deeper segment
  stages are never dispatched (stage call counts), and the measured
  depth-weighted step cost matches the exit histogram; the tiered cluster's
  virtual clocks charge the truncated cost, so device/edge p50 drops.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.serving import (ClusterConfig, ContinuousBatchScheduler, Request,
                           SchedulerConfig, TieredServingCluster)
from repro.serving.adaptive import AdaptiveExitController

# one attention, one SSM, one shared-attn (hybrid) config — the three cache
# families the alive-masking has to get right
PARITY_ARCHS = ("granite-3-2b-smoke", "xlstm-350m-smoke", "zamba2-1.2b-smoke")


def _model(arch):
    cfg = get_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


# ---------------------------------------------------------------------------
# model-level bit-parity (eager: identical op sequence, no fusion noise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_segment_composition_bit_identical_eager(arch):
    cfg, m, params = _model(arch)
    assert m.n_exits >= 1
    rs = np.random.RandomState(0)
    with jax.disable_jit():
        cache = m.init_decode_cache(2, 16)
        toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 1)), jnp.int32)
        pos = jnp.asarray([3, 5], jnp.int32)
        logits, _, mono_cache = m.decode_step(params, cache, toks, pos)

        x = m.embed_decode_tokens(params, toks)
        alive = jnp.ones((2,), bool)
        seg_cache = cache
        for seg in m.decode_segments:
            x, seg_cache = m.decode_segment(params, seg_cache, x, seg, pos,
                                            alive)
        logits2 = m.finalize_decode(params, x)

    assert (np.asarray(logits) == np.asarray(logits2)).all()
    same = jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        mono_cache, seg_cache)
    assert all(jax.tree.leaves(same))


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_exited_rows_freeze_hidden_and_cache(arch):
    """A dead row's hidden state passes through a segment unchanged and its
    cache rows are not written; alive rows match the all-alive run."""
    cfg, m, params = _model(arch)
    rs = np.random.RandomState(1)
    with jax.disable_jit():
        cache = m.init_decode_cache(2, 16)
        toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 1)), jnp.int32)
        pos = jnp.asarray([2, 2], jnp.int32)
        x0 = m.embed_decode_tokens(params, toks)
        seg = m.decode_segments[0]
        alive = jnp.asarray([True, False])
        x_masked, c_masked = m.decode_segment(params, cache, x0, seg, pos,
                                              alive)
        x_full, c_full = m.decode_segment(params, cache, x0, seg, pos,
                                          jnp.ones((2,), bool))
    # row 1 frozen: hidden passthrough, cache rows untouched
    assert (np.asarray(x_masked)[1] == np.asarray(x0)[1]).all()
    for got, init in zip(jax.tree.leaves(c_masked["blocks"][0]),
                         jax.tree.leaves(cache["blocks"][0])):
        assert (np.asarray(got)[:, 1] == np.asarray(init)[:, 1]).all()
    # row 0 alive: identical to the all-alive run
    assert (np.asarray(x_masked)[0] == np.asarray(x_full)[0]).all()


# ---------------------------------------------------------------------------
# gated in-place row writes: the write mask gates each KV / state row write,
# and the result equals the select formula ``where(mask, new, old)`` that
# used to run over whole stacked cache leaves
# ---------------------------------------------------------------------------

# one GQA, one MLA (+MoE) and one shared-attention + SSM config; at 4 layers
# the GQA and MLA configs' last segment scans a stack of 3 layers
WRITE_ARCHS = ("starcoder2-3b-smoke", "deepseek-v3-671b-smoke",
               "zamba2-1.2b-smoke")


def _deep_model(arch, layers=4):
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _filled_cache(m, b, clen, seed):
    """A contiguous decode cache of random values, so a stray write shows."""
    leaves, tree = jax.tree.flatten(m.init_decode_cache(b, clen))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _select_rows(mask, new, old):
    """Row select over whole leaves: stacked blocks [layers, B, ...] on axis
    1, shared-attention caches [B, ...] on axis 0."""
    def sel(axis):
        def f(n, o):
            shape = [1] * n.ndim
            shape[axis] = -1
            return jnp.where(mask.reshape(shape), n, o)
        return f
    out = {"blocks": [jax.tree.map(sel(1), n, o)
                      for n, o in zip(new["blocks"], old["blocks"])]}
    if "shared_attn" in old:
        out["shared_attn"] = [jax.tree.map(sel(0), n, o)
                              for n, o in zip(new["shared_attn"],
                                              old["shared_attn"])]
    return out


def _assert_trees_equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("arch", WRITE_ARCHS)
def test_segment_gated_writes_match_select_formula(arch):
    """Every segment stage with a mixed ``alive`` mask: masked rows' cache
    stays bitwise unchanged, alive rows' cache and hidden equal the
    all-alive run, i.e. the cache is ``where(alive, new, old)``."""
    cfg, m, params = _deep_model(arch)
    b = 4
    cache = _filled_cache(m, b, 16, seed=1)
    rs = np.random.RandomState(4)
    toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (b, 1)), jnp.int32)
    pos = jnp.asarray([2, 5, 3, 7], jnp.int32)
    alive = jnp.asarray([True, False, True, False])
    x = m.embed_decode_tokens(params, toks)
    for seg in m.decode_segments:
        stage = jax.jit(lambda c, x, al, seg=seg: m.decode_segment(
            params, c, x, seg, pos, al))
        x_full, c_full = stage(cache, x, jnp.ones((b,), bool))
        x_mask, c_mask = stage(cache, x, alive)
        _assert_trees_equal(c_mask, _select_rows(alive, c_full, cache))
        want_x = jnp.where(alive[:, None, None], x_full, x)
        assert (np.asarray(x_mask) == np.asarray(want_x)).all()
        x = x_full


@pytest.mark.parametrize("arch", WRITE_ARCHS)
def test_prefill_chunk_gated_writes_match_replay_merge(arch):
    """The unpaged prefill chunk over ragged lengths gives bitwise the same
    cache and last logits as replaying ``decode_step`` ungated and merging
    each step's cache with ``where(t < lengths, new, old)``.  Eager, like
    the composition test above: two programs may fuse differently."""
    cfg, m, params = _deep_model(arch)
    b, chunk_len = 4, 8
    sched = ContinuousBatchScheduler(m, params, SchedulerConfig(
        n_slots=b, max_len=24, prefill_chunk=chunk_len))
    chunk = sched._make_prefill_chunk()

    def replay_merge(cache, tokens, t0, lengths, last):
        for i in range(tokens.shape[1]):
            t = t0 + i
            logits, _, new = m.decode_step(params, cache, tokens[:, i:i + 1],
                                           t)
            cache = _select_rows(t < lengths, new, cache)
            last = jnp.where((t == lengths - 1)[:, None], logits, last)
        return cache, last

    rs = np.random.RandomState(5)
    lengths = jnp.asarray([13, 3, 8, 16], jnp.int32)
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (b, 2 * chunk_len)),
                         jnp.int32)
    got = want = (_filled_cache(m, b, 24, seed=2),
                  jnp.zeros((b, cfg.vocab_size), jnp.float32))
    with jax.disable_jit():
        for ci in range(2):
            toks = tokens[:, ci * chunk_len:(ci + 1) * chunk_len]
            t0 = jnp.int32(ci * chunk_len)
            got = chunk(params, got[0], toks, t0, lengths, got[1])
            want = replay_merge(want[0], toks, t0, lengths, want[1])
            _assert_trees_equal(got, want)


_ARENA_OP = re.compile(r"= \w+\[([\d,]*)\]\S* (select|broadcast)\(")


@pytest.mark.parametrize("arch", ("starcoder2-3b-smoke", "zamba2-1.2b-smoke"))
def test_decode_stages_build_no_arena_sized_select(arch):
    """Structural guard: the compiled segment stages and unpaged prefill
    chunk hold no select and no broadcast shaped like a whole cache leaf
    (the row write is gated in place, not merged over the arena)."""
    cfg, m, params = _deep_model(arch)
    sched = ContinuousBatchScheduler(m, params, SchedulerConfig(
        n_slots=4, max_len=24, prefill_chunk=8))
    cache_s = jax.eval_shape(sched._init_cache)
    leaf_shapes = {tuple(a.shape) for a in jax.tree.leaves(cache_s)
                   if a.ndim >= 4}
    stages = sched.audit_stages()
    names = [n for n in stages if n.startswith("segment")] + ["prefill"]
    for name in names:
        st = stages[name]
        text = st.fn.lower(*st.args).compile().as_text()
        hits = [ln.strip()[:120] for ln in text.splitlines()
                for mm in [_ARENA_OP.search(ln)]
                if mm and tuple(int(d) for d in mm.group(1).split(",") if d)
                in leaf_shapes]
        assert not hits, f"{name}: arena-sized ops {hits}"


# ---------------------------------------------------------------------------
# scheduler-level parity at threshold 0 (exact tokens/counters)
# ---------------------------------------------------------------------------

def _serve(m, params, prompts, *, segmented, threshold, n_slots=2,
           max_new=6):
    sched = ContinuousBatchScheduler(m, params, SchedulerConfig(
        n_slots=n_slots, max_len=48, prefill_chunk=4,
        exit_threshold=threshold, segmented=segmented))
    reqs = [Request(tokens=p, max_new=max_new) for p in prompts]
    for r in reqs:
        sched.submit(r)
    sched.run()
    return sched, [r.out_tokens for r in reqs]


def _sequential_logits(m, params, prompt, max_new):
    """Batch-1 monolithic greedy reference; returns (tokens, logits rows)."""
    step = jax.jit(lambda p, c, t, pos: m.decode_step(p, c, t, pos))
    s0 = prompt.size
    cache = m.init_decode_cache(1, s0 + max_new)
    toks = jnp.asarray(prompt)[None]
    logits = None
    for t in range(s0):
        logits, _, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
    out, logs = [int(jnp.argmax(logits[0]))], [np.asarray(logits[0])]
    for i in range(max_new - 1):
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        logits, _, cache = step(params, cache, tok, jnp.int32(s0 + i))
        out.append(int(jnp.argmax(logits[0])))
        logs.append(np.asarray(logits[0]))
    return out, logs


def _assert_tie_tolerant_equal(got, want, logs):
    """Token streams must agree except where the reference's top-2 logits
    sit within a bf16 ulp (batch-width rounding can flip such an argmax;
    continuations diverge after a flip, so comparison stops there)."""
    for k, (a, b) in enumerate(zip(got, want)):
        if a == b:
            continue
        gap = float(logs[k][b] - logs[k][a])
        assert 0.0 <= gap < 1e-2, \
            f"token {k}: got {a}, want {b}, ref gap {gap:.3e}"
        return
    assert len(got) == len(want)


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_threshold0_matches_monolithic_scheduler(arch):
    cfg, m, params = _model(arch)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, cfg.vocab_size, l).astype(np.int32)
               for l in (5, 9, 3)]
    s_seg, out_seg = _serve(m, params, prompts, segmented=True, threshold=0.0)
    s_mono, out_mono = _serve(m, params, prompts, segmented=False,
                              threshold=0.0)
    # both pool runs must equal the batch-1 monolithic reference, modulo
    # bf16-ulp argmax ties (the suite-wide tolerance for cross-compilation
    # rounding; the eager test above carries the exact bit-parity claim)
    for p, a, b in zip(prompts, out_seg, out_mono):
        want, logs = _sequential_logits(m, params, p, len(a))
        _assert_tie_tolerant_equal(a, want, logs)
        _assert_tie_tolerant_equal(b, want, logs)
    assert (s_seg.flush_counters() == s_mono.flush_counters()).all()
    assert s_seg.tokens_served == s_mono.tokens_served
    # nothing exited -> full depth everywhere, no stage short-circuited
    assert s_seg.measured_depth_fraction() == 1.0
    assert s_seg.stage_calls["finalize"] == s_seg.stage_calls[
        f"segment{len(m.decode_segments) - 1}"]
    # caches agree to bf16 rounding (different jit boundaries fuse the norm
    # reductions differently; exact bit-parity is the eager test's job).
    # After an argmax tie-flip the flipped token is fed once more, so that
    # slot's cache row legitimately diverges — only comparable flip-free.
    if out_seg == out_mono:
        for a, b in zip(jax.tree.leaves(s_seg.cache),
                        jax.tree.leaves(s_mono.cache)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# compute truncation: deeper stages never dispatched, costs match histogram
# ---------------------------------------------------------------------------

def test_permissive_threshold_truncates_stages():
    cfg, m, params = _model("granite-3-2b-smoke")
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, cfg.vocab_size, l).astype(np.int32)
               for l in (4, 7)]
    # n_slots=1 so batch-level short-circuiting reflects per-token exits
    sched, _ = _serve(m, params, prompts, segmented=True, threshold=1.5,
                      n_slots=1, max_new=8)
    n_steps = sched.stage_calls["finalize"]
    assert n_steps == sched.tokens_served == 16
    # every token cleared the first probe -> the deeper segment never ran
    assert sched.stage_calls["segment0"] == n_steps
    assert sched.stage_calls["probe0"] == n_steps
    assert sched.stage_calls["segment1"] == 0
    st = sched.exit_stats()
    assert st["exit0_frac"] == 1.0 and st["full_depth_frac"] == 0.0
    # depth-weighted step cost == histogram-implied depth (exit after layer
    # 1 of 2 -> 0.5), and the jit cache stays bounded by the segment count
    assert sched.measured_depth_fraction() == pytest.approx(0.5)
    assert sched.depth_weighted_tokens == pytest.approx(
        0.5 * sched.tokens_served)
    sizes = sched.jit_cache_sizes()
    if -1 not in sizes.values():
        # minus the non-stage entries (prefill + slot export/import)
        n_stage_entries = len(sizes) - 3
        assert n_stage_entries == len(m.decode_segments) + m.n_exits + 1
        assert all(v <= 1 for v in sizes.values())
        assert sizes["segment1"] == 0             # never compiled: never ran


def test_step_reports_carry_truncated_depth():
    """External pool drivers consume StepReport: under a permissive
    threshold every decode step must report one dispatched segment and the
    truncated depth fraction (what the cluster charges its virtual clock)."""
    cfg, m, params = _model("granite-3-2b-smoke")
    rs = np.random.RandomState(7)
    sched = ContinuousBatchScheduler(m, params, SchedulerConfig(
        n_slots=1, max_len=32, exit_threshold=1.5))
    sched.submit(Request(tokens=rs.randint(0, cfg.vocab_size, 4), max_new=6))
    reports = []
    while sched.has_work:
        reports.append(sched.poll())
    decs = [r for r in reports if r.decode_stepped]
    assert len(decs) == 6
    assert all(r.decode_segments_run == 1 for r in decs)
    assert all(r.decode_depth_frac == pytest.approx(0.5) for r in decs)


def test_depth_cost_matches_histogram_mixed_exits():
    """With a threshold between the two behaviours, measured depth must
    equal the depth implied by the per-step exit histogram (n_slots=1 makes
    batch-level truncation per-token exact)."""
    cfg, m, params = _model("granite-3-2b-smoke")
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, cfg.vocab_size, 6).astype(np.int32)]
    # measure per-token normalized entropies at head 0 (monolithic ee), then
    # pick a threshold at the median so some tokens exit and some don't
    ents = []
    cache = m.init_decode_cache(1, 32)
    toks = jnp.asarray(prompts[0][:1][None], jnp.int32)
    for t in range(18):
        logits, ee, cache = m.decode_step(params, cache, toks, jnp.int32(t))
        ents.append(float(ee[0, 0]) / np.log(cfg.vocab_size))
        toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    thr = float(np.median(ents))
    sched, _ = _serve(m, params, prompts, segmented=True, threshold=thr,
                      n_slots=1, max_new=12)
    counts = sched.flush_counters()
    total = counts.sum()
    assert total == sched.tokens_served == 12
    seg_fracs = [s.layer_frac for s in m.decode_segments]
    # first-exit at head i -> segments 0..i dispatched
    implied = (counts[0] * seg_fracs[0] + counts[1] * sum(seg_fracs)) / total
    assert sched.measured_depth_fraction() == pytest.approx(implied)


def test_controller_tracks_measured_depth():
    """Satellite fix: the controller consumes the scheduler's measured depth
    (one code path).  A permissive threshold measures depth 0.5 < target
    0.9, so every update must tighten."""
    cfg, m, params = _model("granite-3-2b-smoke")
    rs = np.random.RandomState(5)
    ctrl = AdaptiveExitController(target_depth_fraction=0.9, threshold=1.5,
                                  hi=2.0)
    sched = ContinuousBatchScheduler(
        m, params, SchedulerConfig(n_slots=1, max_len=32, exit_threshold=1.5),
        controller=ctrl)
    sched.adaptive_every = 4
    sched.submit(Request(tokens=rs.randint(0, cfg.vocab_size, 4),
                         max_new=12))
    sched.run()
    assert ctrl.threshold < 1.5


# ---------------------------------------------------------------------------
# tiered cluster: truncated compute moves virtual p50
# ---------------------------------------------------------------------------

def test_cluster_permissive_threshold_lowers_device_p50():
    cfg, m, params = _model("granite-3-2b-smoke")
    plan_cfg = get_config("granite-3-2b")
    from repro.core import Scenario

    def p50(threshold):
        cluster = TieredServingCluster(
            m, params, Scenario.default(), plan_cfg=plan_cfg,
            cfg=ClusterConfig(base_slots=2, max_len=64,
                              exit_threshold=threshold))
        rs = np.random.RandomState(6)
        t = 0.0
        for _ in range(4):   # short + tight deadline -> device/edge tiers
            cluster.submit(rs.randint(0, cfg.vocab_size, 6), max_new=8,
                           deadline=0.05, arrival=t)
            t += 0.01
        cluster.run()
        st = cluster.stats()
        assert st["completed"] == 4
        tiers = [n for n, ts in st["tiers"].items() if ts["routed"]]
        assert set(tiers) <= {"device", "edge"}
        depths = {n: st["tiers"][n]["measured_depth"] for n in tiers}
        return st["p50_latency_s"], depths

    p50_full, depth_full = p50(0.0)
    p50_trunc, depth_trunc = p50(1.5)
    assert all(d == 1.0 for d in depth_full.values())
    assert all(d < 1.0 for d in depth_trunc.values())
    assert p50_trunc < p50_full
