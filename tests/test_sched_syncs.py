"""The scheduler's one sync path: every blocking device-to-host read goes
through ``_sync``, so ``StepReport.syncs`` counts each poll's reads and
``device_ms`` holds their waits (exit probes included), with ``host_ms``
the rest of the poll."""
import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.serving import ContinuousBatchScheduler, Request, SchedulerConfig

FLUSH_EVERY = 4


@pytest.fixture(scope="module")
def two_exits():
    """Three layers with exit heads after the first and second, so a
    probing decode step reads back two probes and its tokens."""
    cfg = get_config("granite-3-2b-smoke")
    cfg = dataclasses.replace(cfg, num_layers=3, exits=dataclasses.replace(
        cfg.exits, exit_layers=(1, 2)))
    m = Model(cfg)
    return cfg, m, m.init(jax.random.PRNGKey(0))


def _serve(model, params, vocab, **kw):
    """Three requests through two slots; (report, steps so far) per poll."""
    sched = ContinuousBatchScheduler(model, params, SchedulerConfig(
        n_slots=2, max_len=24, prefill_chunk=4, flush_every=FLUSH_EVERY,
        **kw))
    rs = np.random.RandomState(0)
    for _ in range(3):
        sched.submit(Request(tokens=rs.randint(0, vocab, 6), max_new=7))
    out = []
    while sched.has_work:
        rep = sched.poll()
        out.append((rep, sched._step_idx))
    return out


@pytest.mark.parametrize("kw,per_step", [
    ({}, 3),                                   # two probes + the tokens
    ({"exit_threshold": 0.0}, 1),              # probes skipped
    ({"segmented": False}, 1),                 # one monolithic step
])
def test_syncs_per_synchronous_poll(two_exits, kw, per_step):
    cfg, m, params = two_exits
    polls = _serve(m, params, cfg.vocab_size, **kw)
    steps = 0
    for rep, step_idx in polls:
        want = 0
        if rep.prefill_done:
            want += 1                          # first-token readback
        if rep.decode_stepped:
            steps += 1
            want += per_step + (step_idx % FLUSH_EVERY == 0)
        assert rep.syncs == want, (rep, step_idx)
    assert steps == 7 * 2 and any(r.syncs > per_step for r, _ in polls)


def test_async_poll_reads_one_ring_per_committed_window(two_exits):
    cfg, m, params = two_exits
    polls = _serve(m, params, cfg.vocab_size, segmented=False,
                   async_decode=True, readback_interval=3)
    windows = 0
    for rep, step_idx in polls:
        flushed = rep.decode_steps and (
            step_idx % FLUSH_EVERY < rep.decode_steps)
        want = (rep.decode_steps > 0) + bool(flushed) + rep.prefill_done
        windows += rep.decode_steps > 0
        assert rep.syncs == want, (rep, step_idx)
    assert windows >= 3


def test_device_ms_holds_every_wait(two_exits, monkeypatch):
    """Reads made 3 ms slower: each poll's device_ms holds 3 ms a read,
    exit probes included."""
    cfg, m, params = two_exits
    real = jax.device_get

    def slow(x):
        time.sleep(0.003)
        return real(x)

    base = _serve(m, params, cfg.vocab_size)
    monkeypatch.setattr(jax, "device_get", slow)
    polls = _serve(m, params, cfg.vocab_size)
    assert [r.syncs for r, _ in polls] == [r.syncs for r, _ in base]
    for rep, _ in polls:
        assert rep.device_ms >= 3.0 * rep.syncs
