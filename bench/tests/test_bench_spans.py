"""The scheduler's host spans as the benchmark reads them: the span
metrics and the innermost naming of idle gaps on hand-made traces, the
recorded chip trace (no program spans) read as before, and a scheduler
traced by the profiler on the CPU, its spans read back with ``spans.load``
and held to its ``StepReport``s."""
import dataclasses
import os
import sys
import time

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import devtrace  # noqa: E402
import harness  # noqa: E402
import phases  # noqa: E402
import spans  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "starcoder2_decode_3steps.json.gz")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FLUSH_EVERY = 4
# the accepted readers that read the trace
TRACE_READERS = ["decode_step_ms.offline", "exit_head_roofline.offline",
                 "device_idle_share.offline"]


def _ctx(trace, log=None):
    return harness.Context(log=log, setup_s=0.0,
                           model={"hidden_size": 3072, "vocab_size": 49152},
                           cell={}, n_slots=32, peaks=PEAKS, trace=trace)


def read(name, ctx):
    return harness.load_reader(name).read(ctx)


def _hand_made():
    """Two polls: an admission then a probing step, and a bare step."""
    return {
        "host": [["bench.poll", 0, 1000], ["bench.poll", 1000, 1000],
                 ["bench.wait", 2000, 100]],
        "modules": [],
        "ops": [["fusion", 0, 100], ["fusion", 400, 50],
                ["fusion", 600, 450], ["fusion", 1200, 100],
                ["fusion", 1500, 550]],
        "spans": [["sched.poll", 10, 980], ["sched.admit", 20, 30],
                  ["sched.prefill_chunk", 60, 30],
                  ["sched.go_live", 100, 200],
                  ["sched.sync.first_token", 110, 150],
                  ["sched.step", 400, 500], ["sched.dispatch", 410, 200],
                  ["sched.sync.probe", 450, 50],
                  ["sched.sync.probe", 520, 40],
                  ["sched.sync.tokens", 620, 100],
                  ["sched.commit", 730, 100], ["sched.sync.flush", 850, 20],
                  ["sched.poll", 1010, 900], ["sched.step", 1100, 400],
                  ["sched.dispatch", 1110, 100],
                  ["sched.sync.tokens", 1300, 120]]}


def test_span_readers_on_hand_made_trace():
    ctx = _ctx(_hand_made())
    # admit start 20 to go_live end 300
    assert read("admission_ms.offline", ctx) == pytest.approx(280e-6)
    # 4 + 1 reads over 2 steps
    assert read("syncs_per_step.offline", ctx) == pytest.approx(2.5)
    # (500 - 50 - 40 - 100 - 20) and (400 - 120) ns
    assert read("decode_host_ms.offline", ctx) == pytest.approx(285e-6)
    no_step = _hand_made()
    no_step["spans"] = [e for e in no_step["spans"]
                        if e[0] in ("sched.poll", "sched.admit")]
    for trace in (None, devtrace.read(DATA), no_step):
        for name in ("admission_ms.offline", "syncs_per_step.offline",
                     "decode_host_ms.offline"):
            assert read(name, _ctx(trace)) is None


def test_idle_named_by_innermost_span():
    tr = _hand_made()
    # gaps, longest first: 100-400 (first_token 150, go_live 50, poll
    # 100), 1300-1500 (tokens 120, step 80), 450-600 (probes 90,
    # dispatch 60), 1050-1200 (poll 50, step 10, dispatch 90), 2050-2100
    assert spans.idle_gaps(tr) == [
        ["sched.sync.first_token", pytest.approx(300e-9)],
        ["sched.sync.tokens", pytest.approx(200e-9)],
        ["sched.sync.probe", pytest.approx(150e-9)],
        ["sched.dispatch", pytest.approx(150e-9)],
        ["bench.wait", pytest.approx(50e-9)]]
    # each idle instant goes to one name
    by = spans.idle_by_span(tr)
    assert by == pytest.approx({
        "sched.sync.first_token": 150e-9, "sched.go_live": 50e-9,
        "sched.poll": 150e-9, "sched.sync.probe": 90e-9,
        "sched.dispatch": 150e-9, "sched.step": 90e-9,
        "sched.sync.tokens": 120e-9, "bench.wait": 50e-9})
    assert sum(by.values()) == pytest.approx(
        2100e-9 - devtrace.busy_s(tr))
    # no span open over a gap
    tr = {"host": [["bench.poll", 0, 100], ["bench.poll", 900, 100]],
          "ops": [["fusion", 0, 100], ["fusion", 900, 100]], "spans": []}
    assert spans.idle_gaps(tr) == [["host.other", pytest.approx(800e-9)]]
    assert spans.idle_by_span(tr) == {"host.other": pytest.approx(800e-9)}


def test_recorded_trace_reads_as_before():
    """No program span in the recorded chip trace: every reader of the
    benchmark and the gaps' names read the same with a ``spans`` key."""
    old = devtrace.read(DATA)
    new = dict(old, spans=[])
    for name in TRACE_READERS:
        assert read(name, _ctx(new)) == read(name, _ctx(old)) is not None
    assert spans.idle_gaps(new) == devtrace.idle_gaps(old)
    assert spans.idle_gaps(old, 3) == devtrace.idle_gaps(old, 3)
    assert sum(spans.idle_by_span(old).values()) == pytest.approx(
        0.060705505 - devtrace.busy_s(old))


@pytest.fixture(scope="module")
def two_exits():
    """Three layers, exit heads after the first and second (two probes a
    step, as the starcoder2 cell has)."""
    from repro.configs import get_config
    from repro.models import Model
    cfg = get_config("granite-3-2b-smoke")
    cfg = dataclasses.replace(cfg, num_layers=3, exits=dataclasses.replace(
        cfg.exits, exit_layers=(1, 2)))
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _traced(path, two_exits, **kw):
    """Three requests through two slots, every poll inside a bench.poll
    span as in the benchmark's window, compiled before the trace starts."""
    from repro.serving import (ContinuousBatchScheduler, Request,
                               SchedulerConfig)
    cfg, m, params = two_exits
    sched = ContinuousBatchScheduler(m, params, SchedulerConfig(
        n_slots=2, max_len=24, prefill_chunk=4, flush_every=FLUSH_EVERY,
        **kw))
    sched.submit(Request(tokens=np.ones(6, np.int32), max_new=3))
    sched.run()
    rs = np.random.RandomState(0)
    for _ in range(3):
        sched.submit(Request(tokens=rs.randint(0, cfg.vocab_size, 6),
                             max_new=7))
    reps = []
    jax.profiler.start_trace(str(path))
    try:
        while sched.has_work:
            with jax.profiler.TraceAnnotation("bench.poll"):
                rep = sched.poll()
            reps.append((rep, sched._step_idx))
    finally:
        jax.profiler.stop_trace()
    return reps, spans.load(str(path))


def _by_start(tr, name):
    return sorted(spans.named(tr, name), key=lambda e: e[1])


@pytest.mark.parametrize("kw,probes", [({}, 2), ({"exit_threshold": 0.0}, 0)])
def test_traced_synchronous_scheduler(tmp_path, two_exits, kw, probes):
    reps, tr = _traced(tmp_path, two_exits, **kw)
    syncs = [e for e in tr["spans"] if e[0].startswith("sched.sync.")]
    polls = _by_start(tr, "sched.poll")
    assert len(polls) == len(reps)
    # every span of the program nests inside one sched.poll, whose syncs
    # are the poll's StepReport.syncs
    for e in tr["spans"]:
        assert e[0] == "sched.poll" or any(
            p[1] <= e[1] and e[1] + e[2] <= p[1] + p[2] for p in polls), e
    for (rep, _), p in zip(reps, polls):
        assert len(spans.inside(syncs, p)) == rep.syncs
    # per step: its probes, one token readback, a flush every FLUSH_EVERY
    steps = _by_start(tr, "sched.step")
    stepped = [idx for rep, idx in reps if rep.decode_stepped]
    assert len(steps) == len(stepped) == 14
    want = []
    for s, idx in zip(steps, stepped):
        names = sorted(e[0] for e in spans.inside(syncs, s))
        flush = ["sched.sync.flush"] * (idx % FLUSH_EVERY == 0)
        assert names == sorted(["sched.sync.probe"] * probes
                               + ["sched.sync.tokens"] + flush)
        assert len(spans.inside(_by_start(tr, "sched.dispatch"), s)) == 1
        assert len(spans.inside(_by_start(tr, "sched.commit"), s)) == 1
        want.append(len(names))
    # two admissions, each staged then live
    seq = [e[0] for e in sorted(
        spans.named(tr, "sched.admit") + spans.named(tr, "sched.go_live"),
        key=lambda e: e[1])]
    assert seq == ["sched.admit", "sched.go_live"] * 2
    ctx = _ctx(tr)
    assert read("syncs_per_step.offline", ctx) == pytest.approx(
        sum(want) / len(want))
    assert read("admission_ms.offline", ctx) > 0
    step_ms = sum(e[2] for e in steps) / len(steps) * 1e-6
    assert 0 < read("decode_host_ms.offline", ctx) < step_ms


def test_traced_async_scheduler_reads_one_ring_per_window(tmp_path,
                                                          two_exits):
    reps, tr = _traced(tmp_path, two_exits, segmented=False,
                       async_decode=True, readback_interval=3)
    rings = _by_start(tr, "sched.sync.ring")
    commits = _by_start(tr, "sched.commit")
    assert len(rings) == len(commits) == sum(
        1 for rep, _ in reps if rep.decode_steps) >= 3
    assert len(_by_start(tr, "sched.dispatch")) == sum(
        rep.decode_dispatched for rep, _ in reps)
    assert not spans.named(tr, "sched.step")
    assert read("syncs_per_step.offline", _ctx(tr)) is None


def test_phases_runs_a_short_window_on_the_cpu(tmp_path, monkeypatch):
    """``bench/phases.py`` through its own machinery at smoke size: the
    span metrics read, and the gaps take the scheduler's names."""
    # a trace directory of its own: other test workers trace too
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    spec = harness.load_spec("starcoder2-3b.offline_decode")
    spec["traffic"]["prompt_len"] = {"dist": "uniform", "min": 8, "max": 32}
    spec["traffic"]["trace_seconds"] = 3.0
    spec["config"]["scheduler"]["prefill_chunk"] = 16
    spec["cell"]["n_slots"] = 8
    out = phases.run(spec, 2**33 + 5, 3.0, True, time.perf_counter(),
                     smoke=True)
    m = out["metrics"]
    assert out["device"]["platform"] == "cpu" and out["steps"] > 0
    # one exit head at smoke size: a probe and the tokens a step
    assert 2.0 <= m["syncs_per_step"] < 2.5
    assert m["admission_ms"] > 0 and m["decode_host_ms"] > 0
    assert m["decode_occupancy.offline"] > 0
    assert all(m[n] is None for n in TRACE_READERS)
    names = [g[0] for g in out["idle_gaps"]]
    assert sum(n.startswith("sched.") for n in names) >= len(names) // 2
    assert out["decode_polls"] > 0 and out["decode_poll_ms_mean"] > 0
