"""End-to-end system behaviour: train -> serve -> plan -> sharding specs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config, shape_applicable
from repro.configs.base import InputShape
from repro.core import Scenario, build_cost_graph, plan_all
from repro.data import batch_for_model
from repro.models import Model, ShardCtx
from repro.serving import ServeConfig, ServingEngine
from repro.sharding.specs import ShardingRules
from repro.training import (OptimizerConfig, TrainConfig, init_optimizer,
                            make_train_step)


def test_end_to_end_train_then_serve():
    """The quickstart story: train a tiny model until loss drops, then serve
    it with batched requests and collect early-exit statistics."""
    cfg = get_config("granite-3-2b-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    opt = init_optimizer(params)
    step = jax.jit(make_train_step(
        m, OptimizerConfig(lr=1e-3, warmup_steps=3, total_steps=30)))
    shape = InputShape("t", 64, 4, "train")
    first = last = None
    for i in range(30):
        b = batch_for_model(cfg, shape, i)
        params, opt, metrics = step(params, opt, b, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first

    eng = ServingEngine(m, params, ServeConfig(exit_threshold=0.95))
    prompts = jax.random.randint(jax.random.PRNGKey(9), (4, 8), 0,
                                 cfg.vocab_size)
    out = eng.generate(prompts, max_new=8)
    assert out.shape == (4, 8)
    stats = eng.exit_stats()
    assert stats["tokens"] == 32.0
    fracs = [v for k, v in stats.items() if k.endswith("_frac")]
    assert abs(sum(fracs) - 1.0) < 1e-6


def test_paradigm_planning_on_model_zoo():
    """Every paradigm produces a finite plan for every assigned arch."""
    sc = Scenario.default()
    for arch in ("yi-6b", "zamba2-1.2b", "whisper-base", "qwen2-vl-2b"):
        cfg = get_config(arch)
        g = build_cost_graph(cfg, batch=1, seq_len=256)
        plans = plan_all(g, sc, deadline=1.0)
        assert set(plans) == {"cloud-device", "edge-device",
                              "cloud-edge-device", "device-device"}
        for p in plans.values():
            assert np.isfinite(p.latency) and p.latency > 0
            assert np.isfinite(p.energy)


def test_ssm_partition_boundary_is_cheap():
    """The EI-relevant SSM property: a recurrent arch's partition boundary
    ships O(d_model) state per token vs attention's growing KV — the cost
    graph must reflect smaller boundary-to-compute ratios for SSM archs."""
    g_ssm = build_cost_graph(get_config("xlstm-350m"), 1, 4096)
    g_dense = build_cost_graph(get_config("yi-6b"), 1, 4096)
    r_ssm = g_ssm.segments[0].out_bytes / g_ssm.segments[0].flops
    r_dense = g_dense.segments[0].out_bytes / g_dense.segments[0].flops
    assert r_ssm < r_dense * 10  # same order; boundary is d_model activations


def test_sharding_rules_cover_all_archs():
    """Every param leaf of every full config gets a valid spec on the
    production mesh (divisibility respected)."""
    mesh = AbstractMesh((16, 16), ("data", "model"))
    for arch, cfg in ARCHS.items():
        m = Model(cfg)
        shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        rules = ShardingRules(mesh)
        specs = rules.params_specs(shapes)
        flat_shapes = jax.tree.leaves(shapes)
        flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_shapes) == len(flat_specs)
        for sh, sp in zip(flat_shapes, flat_specs):
            for dim, axis in enumerate(sp):
                if axis is None:
                    continue
                size = 16
                assert sh.shape[dim] % size == 0, (arch, sh.shape, sp)


def test_shape_applicability_matrix():
    """40 pairs: every (arch x shape) is runnable except whisper long_500k."""
    runnable = 0
    skipped = []
    for arch, cfg in ARCHS.items():
        for sname in INPUT_SHAPES:
            if shape_applicable(cfg, sname):
                runnable += 1
            else:
                skipped.append((arch, sname))
    assert skipped == [("whisper-base", "long_500k")]
    assert runnable == 39


def test_zero_opt_spec_adds_data_axis():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    rules = ShardingRules(mesh)
    cfg = get_config("yi-6b")
    m = Model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    from repro.training.optimizer import init_optimizer as io
    opt_shapes = jax.eval_shape(io, shapes)
    ospecs = rules.opt_specs(opt_shapes, shapes)
    flat = jax.tree.leaves(ospecs["m"], is_leaf=lambda x: isinstance(x, P))
    n_data = sum(1 for sp in flat if any(a in ("data", ("pod", "data"))
                                         for a in sp if a))
    assert n_data > len(flat) * 0.5   # most moments are ZeRO-sharded
