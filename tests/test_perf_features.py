"""Tests for the beyond-paper performance features added in §Perf:
chunked attention, W8A8 expert quantization, dp_zero sharding strategy,
context-parallel cache specs, and the HLO cost analyzer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.models import Model
from repro.models import ffn as ffn_mod
from repro.models.attention import _sdpa, _sdpa_chunked, make_mask
from repro.sharding.specs import ShardingRules


# ---------------------------------------------------------------------------
# chunked attention == dense attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (True, 300), (False, 0)])
def test_chunked_attention_matches_dense(causal, window):
    b, sq, nq, nkv, h = 1, 2048, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, nq, h), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sq, nkv, h), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sq, nkv, h), jnp.float32)
    want = _sdpa(q, k, v, make_mask(sq, sq, causal=causal, window=window),
                 1 / h ** 0.5)
    got = _sdpa_chunked(q, k, v, causal=causal, window=window,
                        scale=1 / h ** 0.5, q_chunk=512, kv_chunk=512)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_chunked_attention_grad_finite():
    b, sq, nq, nkv, h = 1, 1024, 2, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, nq, h), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sq, nkv, h), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sq, nkv, h), jnp.float32)
    g = jax.grad(lambda q: jnp.sum(_sdpa_chunked(
        q, k, v, causal=True, window=0, scale=1 / h ** 0.5,
        q_chunk=512, kv_chunk=512) ** 2))(q)
    assert not bool(jnp.isnan(g).any())


# ---------------------------------------------------------------------------
# W8A8 expert quantization
# ---------------------------------------------------------------------------

def test_w8a8_expert_matmul_close_to_bf16():
    cfg = get_config("llama4-maverick-400b-a17b-smoke")
    params = ffn_mod.init_moe(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, params)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                                jnp.bfloat16)
    y_bf, _ = ffn_mod.moe_ffn_reference(params, x, cfg)
    y_q, _ = ffn_mod.moe_ffn_reference(
        ffn_mod.quantize_expert_weights(params), x, cfg)
    rel = float(jnp.linalg.norm((y_q - y_bf).astype(jnp.float32))
                / jnp.linalg.norm(y_bf.astype(jnp.float32)))
    assert rel < 0.05


def test_quantize_model_moe_end_to_end_decode():
    cfg = get_config("deepseek-v3-671b-smoke")
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    pq = ffn_mod.quantize_model_moe(p)
    cache = m.init_decode_cache(2, 16)
    l1, _, _ = m.decode_step(p, cache, jnp.ones((2, 1), jnp.int32), jnp.int32(1))
    l2, _, _ = m.decode_step(pq, cache, jnp.ones((2, 1), jnp.int32), jnp.int32(1))
    assert float(jnp.max(jnp.abs(l1 - l2))) < 0.1 * float(jnp.max(jnp.abs(l1)) + 1.0)
    # int8 weights really are int8 (the byte win is real)
    leaves = jax.tree.leaves(pq)
    assert any(a.dtype == jnp.int8 for a in leaves)
    # non-moe params untouched
    assert set(jax.tree.leaves(p)[0].shape) == set(jax.tree.leaves(pq)[0].shape)


def test_quantize_preserves_dense_archs():
    cfg = get_config("yi-6b-smoke")
    m = Model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    pq = ffn_mod.quantize_model_moe(p)
    assert jax.tree.structure(p) == jax.tree.structure(pq)


# ---------------------------------------------------------------------------
# sharding strategies
# ---------------------------------------------------------------------------

def test_dp_zero_replicates_weights_and_shards_moments():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("granite-3-2b")
    m = Model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    rules = ShardingRules(mesh, strategy="dp_zero")
    specs = rules.params_specs(shapes)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(all(a is None for a in sp) for sp in flat), \
        "dp_zero replicates all params over the mesh"
    from repro.training.optimizer import init_optimizer
    opt_shapes = jax.eval_shape(init_optimizer, shapes)
    ospecs = rules.opt_specs(opt_shapes, shapes)
    mflat = jax.tree.leaves(ospecs["m"], is_leaf=lambda x: isinstance(x, P))
    assert any(any(a is not None for a in sp) for sp in mflat), \
        "ZeRO moments sharded"
    bspec = rules.batch_specs({"tokens": jax.ShapeDtypeStruct((256, 128),
                                                              jnp.int32)})
    assert bspec["tokens"][0] == ("data", "model")


def test_cache_specs_seq_shard_for_mla():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    rules = ShardingRules(mesh)
    cache = {
        "latent": jax.ShapeDtypeStruct((61, 128, 32768, 512), jnp.bfloat16),
        "krope": jax.ShapeDtypeStruct((61, 128, 32768, 64), jnp.bfloat16),
        "kv": jax.ShapeDtypeStruct((40, 128, 32768, 8, 64), jnp.bfloat16),
    }
    specs = rules.cache_specs(cache)
    assert specs["latent"] == P(None, "data", "model", None)
    assert specs["krope"] == P(None, "data", "model", None)
    # kv heads=8 not divisible by 16 -> sequence sharding
    assert specs["kv"] == P(None, "data", "model", None, None)


# ---------------------------------------------------------------------------
# HLO cost analyzer invariants
# ---------------------------------------------------------------------------

def test_hlo_analyzer_scales_scan_bodies():
    from repro.launch.hlo_cost import analyze

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()
        return jax.lax.scan(body, x, w)[0]

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 64), jnp.float32),
        jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)).compile().as_text()
    c = analyze(txt)
    assert abs(c.flops - 7 * 2 * 64 ** 3) / (7 * 2 * 64 ** 3) < 0.01


def test_hlo_analyzer_inplace_dus():
    """Scan residual stacking must not count the whole buffer per step."""
    from repro.launch.hlo_cost import analyze

    def f(x):
        def body(c, _):
            c = jnp.tanh(c)
            return c, c                      # stacks [T, ...] residuals
        return jax.lax.scan(body, x, None, length=100)[1]

    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile().as_text()
    c = analyze(txt)
    full_buffer_per_step = 100 * (100 * 128 * 128 * 4)
    assert c.bytes < full_buffer_per_step * 0.5
