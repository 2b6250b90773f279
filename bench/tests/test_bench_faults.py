"""With the timed path broken underneath, a run's ``correct`` comes out
false: the rest of a run (set-up, the window, the check against the
reference) is driven as on the chip, at the smoke size of a configuration,
with the platform check skipped.  One case per fault a serving cell on one
chip can have: a decode step that returns its cache unchanged, half of the
batch left out of the layers, a token altered where it is produced, and an
exit probe whose entropy is wrong (summed over half the vocabulary, or a
constant).  (A one-chip cell has no exchange between chips to leave
out.)"""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import harness  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.serving.scheduler import ContinuousBatchScheduler  # noqa: E402

WORKLOAD = "starcoder2-3b.offline_decode"
SMOKE_ENTROPY_LIMIT = 1e-3
Sched = ContinuousBatchScheduler


def state_unchanged():
    orig = Sched._make_segment_stage

    def make(self, seg):
        stage = orig(self, seg)

        def broken(params, cache, x, positions, alive):
            x, _ = stage(params, cache, x, positions, alive)
            return x, cache
        return broken
    return Sched, "_make_segment_stage", make


def half_batch_left_out():
    orig = Sched._make_segment_stage

    def make(self, seg):
        stage = orig(self, seg)

        def broken(params, cache, x, positions, alive):
            half = jnp.arange(alive.shape[0]) < alive.shape[0] // 2
            return stage(params, cache, x, positions, alive & half)
        return broken
    return Sched, "_make_segment_stage", make


def token_altered():
    orig = Sched._make_finalize

    def make(self):
        fin = orig(self)

        def broken(params, x, counters, first_exit, active, key, step_idx):
            greedy, nxt, counters = fin(params, x, counters, first_exit,
                                        active, key, step_idx)
            return (greedy + 1) % self._vocab, nxt, counters
        return broken
    return Sched, "_make_finalize", make


def entropy_over_half_the_vocab():
    orig = Model.exit_probe_entropy

    def broken(self, params, exit_index, x):
        heads = [dict(p, w=p["w"][:, :p["w"].shape[1] // 2])
                 for p in params["exit_heads"]]
        return orig(self, dict(params, exit_heads=heads), exit_index, x)
    return Model, "exit_probe_entropy", broken


def entropy_constant():
    def broken(self, params, exit_index, x):
        # what a probe that ignores its logits gives: a uniform softmax
        return jnp.full(x.shape[:1], np.log(self.cfg.vocab_size),
                        jnp.float32)
    return Model, "exit_probe_entropy", broken


# each fault, and the number that has to catch it
FAULTS = {f.__name__: (f, number) for f, number in (
    (state_unchanged, "logit_gap"),
    (half_batch_left_out, "logit_gap"),
    (token_altered, "logit_gap"),
    (entropy_over_half_the_vocab, "exit_entropy_gap"),
    (entropy_constant, "exit_entropy_gap"))}


def _run():
    spec = harness.load_spec(WORKLOAD)
    # short prompts in short chunks: the CPU replays them slowly beside
    # other test workers
    spec["traffic"]["prompt_len"] = {"dist": "uniform", "min": 8, "max": 32}
    spec["config"]["scheduler"]["prefill_chunk"] = 16
    # the smoke model's narrow exit heads round more than the chip
    # cell's: its own limit, still far under a wrong entropy (0.5 or more)
    spec["cell"]["limits"]["exit_entropy_gap"] = SMOKE_ENTROPY_LIMIT
    return harness.run(spec, 2**33 + 99, 3.0, False, time.perf_counter(),
                       smoke=True)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    plant, number = FAULTS[fault]
    monkeypatch.setattr(*plant())
    res = _run()
    assert not res["correct"], res["checks"]
    assert res["checks"][number]["value"] \
        > res["checks"][number]["limit"], res["checks"]
