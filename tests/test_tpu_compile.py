"""The main path's Pallas kernels compile for a TPU v5e at published widths.

Each test lowers one kernel wrapper with ``interpret=False`` against a
described (not attached) v5e chip and checks that the compiled program
holds the Mosaic kernel (``tpu_custom_call``).  Interpret-mode tests cannot
see what only the chip's compiler refuses: unaligned tiles, VMEM over the
limit.  Nothing here runs a kernel, so results and times are out of scope.

All compiles stay in this one file: only the process that described the
topology holds the TPU library, so a second file on another worker would
skip.
"""
import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

SLOTS = 8
MAX_LEN = 544                  # 512-token prompts + 32 new tokens
PAGE = 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # no TPU library, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_text(one_chip, no_compile_cache):
    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return go


def _paged_shapes():
    pps = MAX_LEN // PAGE
    return SLOTS * pps, ((SLOTS, pps), jnp.int32), ((SLOTS,), jnp.int32)


@pytest.mark.parametrize("arch", ["granite-3-2b", "deepseek-v3-671b"])
def test_exit_head_entropy_compiles(compile_text, arch):
    cfg = get_config(arch)
    text = compile_text(
        functools.partial(ops.exit_head_entropy, interpret=False),
        ((SLOTS, cfg.d_model), jnp.bfloat16),
        ((cfg.d_model, cfg.vocab_size), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_paged_gqa_attention_compiles(compile_text):
    cfg = get_config("granite-3-2b")
    hd = cfg.resolved_head_dim
    n_pages, tbl, pos = _paged_shapes()
    pool = ((n_pages, PAGE, cfg.num_kv_heads, hd), jnp.bfloat16)
    text = compile_text(
        functools.partial(ops.paged_gqa_attention, interpret=False),
        ((SLOTS, 1, cfg.num_heads, hd), jnp.bfloat16), pool, pool, tbl, pos)
    assert "tpu_custom_call" in text


def test_paged_mla_attention_compiles(compile_text):
    cfg = get_config("deepseek-v3-671b")
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    n_pages, tbl, pos = _paged_shapes()
    text = compile_text(
        functools.partial(ops.paged_mla_attention, interpret=False,
                          scale=1.0 / math.sqrt(cfg.qk_nope_head_dim + rope)),
        ((SLOTS, 1, cfg.num_heads, rank), jnp.bfloat16),
        ((SLOTS, 1, cfg.num_heads, rope), jnp.bfloat16),
        ((n_pages, PAGE, rank), jnp.bfloat16),
        ((n_pages, PAGE, rope), jnp.bfloat16), tbl, pos)
    assert "tpu_custom_call" in text


def test_compress_rows_compiles(compile_text):
    text = compile_text(functools.partial(ops.compress_rows, interpret=False),
                        ((MAX_LEN, 2048), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_decompress_rows_compiles(compile_text):
    text = compile_text(
        functools.partial(ops.decompress_rows, interpret=False),
        ((MAX_LEN, 2048), jnp.int8), ((MAX_LEN, 1), jnp.float32))
    assert "tpu_custom_call" in text
