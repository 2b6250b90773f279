"""The float32 reference agrees with what the serving path serves, at the
smoke sizes of both configurations, and its exit-head entropies with the
serving path's compiled probes; its lower-precision controls miss the
reference by more than the program does."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import weights  # noqa: E402
from reference.dense import Reference  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import Model, ShardCtx  # noqa: E402
from repro.serving.scheduler import (ContinuousBatchScheduler,  # noqa: E402
                                     Request, SchedulerConfig)

T = 96
# (prompt, new tokens): several prefill chunks of 16, decode past them
SHAPES = [(5, 20), (40, 30), (17, 50), (60, 8), (3, 40)]


def _setup(config):
    cfg = get_config(config + "-smoke")
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        m = json.load(f)["model"]
    m.update(harness.describe(cfg))
    model = Model(cfg, ShardCtx(None))
    params = weights.make(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                          2**33 + 5)
    return cfg, m, model, params


def _served(cfg, model, params):
    rng = np.random.default_rng(0)
    sched = ContinuousBatchScheduler(model, params, SchedulerConfig(
        n_slots=4, max_len=T, prefill_chunk=16))
    reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, p,
                                        dtype=np.int32),
                    max_new=n, req_id=i) for i, (p, n) in enumerate(SHAPES)]
    for r in reqs:
        sched.submit(r)
    sched.run()
    tokens = np.zeros((len(reqs), T), np.int32)
    look = np.zeros_like(tokens)
    mask = np.zeros(tokens.shape, bool)
    for i, r in enumerate(reqs):
        assert len(r.out_tokens) == r.max_new
        seq = np.concatenate([r.tokens, np.asarray(r.out_tokens, np.int32)])
        tokens[i, :seq.size] = seq
        p0 = r.tokens.size - 1
        look[i, p0:p0 + r.max_new] = r.out_tokens
        mask[i, p0:p0 + r.max_new] = True
    return sched, tokens, look, mask


@pytest.mark.parametrize("config", ["granite-3-2b", "starcoder2-3b"])
def test_reference_agrees_with_served_path(config):
    cfg, m, model, params = _setup(config)
    sched, tokens, look, mask = _served(cfg, model, params)
    ref = Reference(m, params)
    rp = check.reference_pass(ref, m, tokens, look, mask, 7, 4)
    x, best = rp["x"], rp["best"]
    prog = check.logit_numbers(best, rp["at"], mask)
    # teacher-forced logits of the program's own decode step (the step
    # the prefill chunks replay), against the reference's best logit
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(tokens)})
    prog_best = np.asarray(logits).max(-1)
    scale = float(np.asarray(logits)[mask].std())
    # bf16 activations: logits agree to about 3% of their spread
    assert np.abs(prog_best - best)[mask].max() < 0.03 * scale
    assert prog["logit_gap"] < 0.05 * scale
    # the exit probes the scheduler compiled, on the reference's rows
    stages = sched.audit_stages()
    ent = [check.probe_entropy(stages[f"probe{e}"], params, xe,
                               cfg.vocab_size)
           for e, xe in enumerate(rp["exits"])]
    assert len(ent) == len(cfg.exits.exit_layers) >= 1
    gap = check.entropy_gap(ent, rp["entropy"])
    assert gap < 1e-3 * np.log(cfg.vocab_size)
    # every control misses the reference by more than the program, on the
    # mean gap of the logits and on the exit entropies
    for q in ("int8", "fp8"):
        rq = Reference(m, params, q)
        _, _, arg = rq.head(rq.hidden(tokens), look)
        _, at, _ = ref.head(x, arg)
        ctl = check.logit_numbers(best, at, mask)
        assert ctl["logit_gap_mean"] > prog["logit_gap_mean"]
        assert check.entropy_gap(
            [rq.exit_entropy(xe, e) for e, xe in enumerate(rp["exits"])],
            rp["entropy"]) > gap


@pytest.mark.parametrize("config", ["granite-3-2b", "starcoder2-3b"])
def test_exit_heads_read_after_the_layers_the_reference_taps(config):
    """The program probes exit head e after the segment that ends at layer
    ``exit_layers[e]`` of the configuration file, where the reference reads
    its residual stream for that head (no weights are made)."""
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        m = json.load(f)["model"]
    model = Model(get_config(config), ShardCtx(None))
    depth, reads = 0, {}
    for seg in model.decode_segments:
        depth += seg.layers
        if seg.exit_index is not None:
            reads[seg.exit_index] = depth
    assert [reads[e] for e in sorted(reads)] == m["exit_layers"]
    assert depth == m["num_hidden_layers"]
