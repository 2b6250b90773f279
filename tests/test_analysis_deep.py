"""Deep-analysis layers: interprocedural taint (IPC), jaxpr stage audit
(JXP), cost cross-check (CST), plus the CLI/report satellites.

Style mirrors ``tests/test_analysis.py``: seeded-violation sources that
must fire exactly the expected rules, and clean real-repo registries
that must not.
"""
import importlib
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (RULES, check_cost_graphs, lint_source,
                            load_baseline)
from repro.analysis.costcheck import decode_flops_per_token, jaxpr_flops
from repro.analysis.jaxpr_audit import audit_registry, audit_stage
from repro.serving import StageSpec


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# IPC: interprocedural taint
# ---------------------------------------------------------------------------
HELPER_ONLY = """
def _leaf(v):
    return int(v)
"""

ONE_DEEP = """
import jax

@jax.jit
def step(x):
    return _leaf(x) + 1

def _leaf(v):
    return int(v)
"""

TWO_DEEP = """
import jax

@jax.jit
def outer(x):
    return _mid(x)

def _mid(y):
    return _leaf(y * 2)

def _leaf(v):
    return int(v)
"""

IPC_CONTROL_FLOW = """
import jax

@jax.jit
def step(x):
    return _branch(x)

def _branch(v):
    if v > 0:
        return v + 1
    return v
"""

IPC_HOST_LEAK = """
import jax

@jax.jit
def step(x):
    return _scale(x)

def _scale(v):
    return v * len(v)
"""

IPC_METHOD = """
import jax

class Sched:
    def __init__(self):
        self._step = jax.jit(self._make_step())

    def _make_step(self):
        def run(x):
            return self._unpack(x)
        return run

    def _unpack(self, v):
        return v.item()
"""

IPC_CLEAN = """
import jax

@jax.jit
def step(x):
    return _pad(x, x.shape[0])   # .shape launders: static under trace

def _pad(v, n):
    if n > 8:                     # n is static, not traced
        return v
    return v * 2

def _host_side(arr):
    return int(arr)               # never called from traced code
"""


def test_interproc_catches_what_intraproc_misses():
    """The acceptance case: a concretization one call deep.  The helper
    alone is clean under every TRC rule (what the per-function analyzer
    sees), but linked to its traced caller it is an IPC001."""
    assert lint_source(HELPER_ONLY, "helper.py") == []
    found = lint_source(ONE_DEEP, "one_deep.py")
    assert _rules(found) == ["IPC001"]
    assert not any(f.rule.startswith("TRC") for f in found)
    assert "[call chain: step() -> _leaf()]" in found[0].message


def test_interproc_chain_two_deep():
    found = lint_source(TWO_DEEP, "two_deep.py")
    assert _rules(found) == ["IPC001"]
    assert "[call chain: outer() -> _mid() -> _leaf()]" in found[0].message


def test_interproc_control_flow_rule():
    found = lint_source(IPC_CONTROL_FLOW, "cf.py")
    assert _rules(found) == ["IPC002"]
    assert found[0].severity == "error"


def test_interproc_host_leak_rule():
    found = lint_source(IPC_HOST_LEAK, "leak.py")
    assert _rules(found) == ["IPC003"]
    assert found[0].severity == "warning"


def test_interproc_follows_self_methods():
    found = lint_source(IPC_METHOD, "method.py")
    assert _rules(found) == ["IPC001"]
    assert "_unpack()" in found[0].message


def test_interproc_shape_launder_and_dead_helpers_stay_clean():
    assert lint_source(IPC_CLEAN, "clean.py") == []


# ---------------------------------------------------------------------------
# JXP: jaxpr stage audit (seeded stages, one per rule)
# ---------------------------------------------------------------------------
def _spec(fn, args, **kw):
    return StageSpec(name="seeded", fn=fn, args=tuple(args), **kw)


def test_jxp001_callback_primitive():
    def stage(x):
        jax.debug.print("x={x}", x=x)
        return x + 1
    f, _ = audit_stage(_spec(stage, [jax.ShapeDtypeStruct((4,),
                                                          jnp.float32)]),
                       "<jaxpr:seed/callback>")
    assert _rules(f) == ["JXP001"]
    # jax.debug.print lowers to its own debug_print primitive
    assert "'debug_print'" in f[0].message


def test_jxp002_device_put_primitive():
    def stage(x):
        # a device_put of a host constant folds to a literal; one of a
        # traced value stays a primitive in the jaxpr
        return x + jax.device_put(x * 2.0)
    f, _ = audit_stage(_spec(stage, [jax.ShapeDtypeStruct((4,),
                                                          jnp.float32)]),
                       "<jaxpr:seed/device_put>")
    assert _rules(f) == ["JXP002"]


def test_jxp003_large_folded_constant():
    table = jnp.zeros((128, 256), jnp.float32)      # 32768 elements

    def stage(i):
        return table[i]
    f, _ = audit_stage(_spec(stage, [jax.ShapeDtypeStruct((), jnp.int32)]),
                       "<jaxpr:seed/const>")
    assert _rules(f) == ["JXP003"]
    assert "(128, 256)" in f[0].message


def test_jxp003_small_constants_pass():
    iota = jnp.arange(32)

    def stage(i):
        return iota + i
    f, _ = audit_stage(_spec(stage, [jax.ShapeDtypeStruct((), jnp.int32)]),
                       "<jaxpr:seed/smallconst>")
    assert f == []


def test_jxp004_cache_dtype_drift():
    def stage(cache, x):
        return cache.astype(jnp.float32) + x, x
    f, _ = audit_stage(
        _spec(stage, [jax.ShapeDtypeStruct((4, 8), jnp.bfloat16),
                      jax.ShapeDtypeStruct((4, 8), jnp.float32)],
              cache_in=0, cache_out=lambda o: o[0]),
        "<jaxpr:seed/dtype>")
    assert _rules(f) == ["JXP004"]
    assert "bfloat16->float32" in f[0].message


def test_jxp005_donation_violation():
    def stage(cache):
        return cache.sum()
    f, _ = audit_stage(
        _spec(stage, [jax.ShapeDtypeStruct((4, 8), jnp.float32)],
              donate_argnums=(0,)),
        "<jaxpr:seed/donate>")
    assert _rules(f) == ["JXP005"]


def test_jxp_donation_roundtrip_passes():
    def stage(cache, x):
        return cache + x, x.sum()
    f, _ = audit_stage(
        _spec(stage, [jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      jax.ShapeDtypeStruct((4, 8), jnp.float32)],
              donate_argnums=(0,), cache_in=0, cache_out=lambda o: o[0]),
        "<jaxpr:seed/ok>")
    assert f == []


# ---------------------------------------------------------------------------
# real registries audit clean; cost ratios sit in band
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def granite_sched():
    from repro.configs import get_config
    from repro.models import Model
    from repro.serving import ContinuousBatchScheduler, SchedulerConfig
    cfg = get_config("granite-3-2b-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    sched = ContinuousBatchScheduler(
        m, params, SchedulerConfig(n_slots=2, max_len=24, prefill_chunk=4))
    sched.ensure_spec(3)
    return m, sched


def test_real_scheduler_registry_audits_clean(granite_sched):
    _, sched = granite_sched
    stages = sched.audit_stages()
    # the registry mirrors the dispatchable stage set
    assert {"prefill", "finalize", "export_rows", "import_rows",
            "propose", "verify"} <= set(stages)
    findings, jaxprs = audit_registry(stages, "sched")
    assert findings == []
    assert set(jaxprs) == set(stages)


def test_cost_ratio_within_band_and_perturbation_trips(granite_sched,
                                                       monkeypatch):
    m, sched = granite_sched
    stages = sched.audit_stages()
    _, jaxprs = audit_registry(stages, "sched")
    stack = {"sched": sched, "_model": m}
    findings, ratios = check_cost_graphs(stack, {"sched": jaxprs})
    assert findings == []
    assert ratios and all(0.5 <= v["ratio"] <= 2.0
                          for v in ratios.values())
    # decode-path reduction found the segment pipeline
    per = decode_flops_per_token(stages, jaxprs)
    assert per[""]["flops_per_token"] > 0

    # an analytic cost drifting 100x from the compiled stages must trip
    import repro.core.paradigms as paradigms
    real = paradigms.analytic_step_cost

    def drifted(cfg, batch, seq_len):
        c = real(cfg, batch, seq_len)
        import dataclasses
        return dataclasses.replace(
            c, flops_per_token=c.flops_per_token * 100.0)
    monkeypatch.setattr(paradigms, "analytic_step_cost", drifted)
    tripped, _ = check_cost_graphs(stack, {"sched": jaxprs})
    assert _rules(tripped) == ["CST001"]
    assert "tolerance" in tripped[0].message


def test_jaxpr_flops_counts_matmuls():
    def f(a, b):
        return a @ b
    jx = jax.make_jaxpr(f)(jnp.zeros((8, 16)), jnp.zeros((16, 4)))
    assert jaxpr_flops(jx) == 2.0 * 8 * 4 * 16


# ---------------------------------------------------------------------------
# CLI satellites: --explain, corrupt baseline
# ---------------------------------------------------------------------------
def test_every_rule_explains_cleanly(capsys):
    from repro.launch.analyze import main
    for rid in sorted(RULES):
        assert main(["--explain", rid]) == 0
        out = capsys.readouterr().out
        assert rid in out
        assert RULES[rid].description.split()[0] in out
        assert "violates:" in out and "fix:" in out
    assert main(["--explain", "NOPE99"]) == 2


def test_corrupt_baseline_error_is_actionable(tmp_path):
    bad = tmp_path / "analysis_baseline.json"
    bad.write_text('{"findings": [')
    with pytest.raises(ValueError) as e:
        load_baseline(str(bad))
    assert str(bad) in str(e.value)
    assert "--update-baseline" in str(e.value)


# ---------------------------------------------------------------------------
# attention impl env validation
# ---------------------------------------------------------------------------
def test_attention_env_toggles_validated(monkeypatch):
    import repro.models.attention as attention
    monkeypatch.setenv("REPRO_ATTN", "kernal")
    with pytest.raises(ValueError, match="REPRO_ATTN.*legal values"):
        importlib.reload(attention)
    monkeypatch.delenv("REPRO_ATTN")
    monkeypatch.setenv("REPRO_PAGED_ATTN", "pallas")
    with pytest.raises(ValueError, match="REPRO_PAGED_ATTN.*legal values"):
        importlib.reload(attention)
    monkeypatch.undo()
    importlib.reload(attention)
    assert attention.ATTN_IMPL == "dense"
    assert attention.PAGED_ATTN_IMPL == "jnp"
