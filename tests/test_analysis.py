"""Analyzer invariants: the lint rules fire on seeded violations, stay
quiet on the real repo (against the committed baseline), the baseline
gate only trips on NEW findings, and the runtime guards catch seeded
slot leaks / recompiles while passing clean serving runs."""
import json
import os

import jax
import numpy as np
import pytest

from repro.analysis import (Finding, GuardError, SlotAudit, guard_polling,
                            lint_paths, lint_source, load_baseline,
                            new_findings, no_recompile, save_baseline)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# seeded static violations: every rule must fire where planted
# ---------------------------------------------------------------------------
BAD_TRACED = '''
import functools
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def hazards(x):
    if x > 0:                      # TRC004
        x = x + 1
    k = int(x[0])                  # TRC001
    v = x.item()                   # TRC002
    n = len(x)                     # TRC003
    print(f"x={x}")                # TRC005
    h = np.asarray(x)              # TRC007
    return x + k + n

def closure_capture():
    table = jnp.arange(8)
    def lookup(i):
        return table[i]            # TRC006
    return jax.jit(lookup)

def scan_hazard(xs):
    def body(c, x):
        if x.sum() > 0:            # TRC004 inside a scan body
            c = c + 1
        return c, x
    return jax.lax.scan(body, 0, xs)
'''

BAD_PALLAS = '''
import jax
from jax.experimental import pallas as pl

def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(x):
    return pl.pallas_call(                                       # PLT003
        _k,
        grid=(4, 4),
        in_specs=[pl.BlockSpec((100, 100), lambda i: (i, 0))],   # PLT001/2/4
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i,)),     # PLT004
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)

def probe():
    return jax.default_backend()                                 # PLT005
'''


def _rules(findings):
    return sorted({f.rule for f in findings})


CLEAN_TRACED = '''
import functools
import jax

@functools.partial(jax.jit, static_argnames=("flag",))
def clean(x, flag):
    if flag:                       # static arg: no finding
        x = x * 2
    t, d = x.shape
    if d > 4:                      # shape access launders taint: no finding
        x = x[:, :4]
    if x is None:                  # identity vs None: no finding
        return x
    n = len(x.shape)               # len of a static tuple: no finding
    return x


def lookup(cache, key):
    traced = jax.jit(lambda c: c["a"])(cache)
    if "a" in cache:               # constant membership probe: no finding
        return traced
    return key
'''


def test_traced_rules_fire_on_seeded_violations():
    found = lint_source(BAD_TRACED, "bad_traced.py")
    assert _rules(found) == ["TRC001", "TRC002", "TRC003", "TRC004",
                             "TRC005", "TRC006", "TRC007"]
    # TRC004 fires in the jitted fn AND the scan body
    assert sum(1 for f in found if f.rule == "TRC004") == 2


def test_static_args_and_shape_access_stay_clean():
    assert lint_source(CLEAN_TRACED, "clean.py") == []


def test_pallas_rules_fire_on_seeded_violations():
    found = lint_source(BAD_PALLAS, "bad_pallas.py")
    assert _rules(found) == ["PLT001", "PLT002", "PLT003", "PLT004",
                             "PLT005"]
    lane = [f for f in found if f.rule == "PLT001"]
    assert lane and "100" in lane[0].message
    arity = [f for f in found if f.rule == "PLT004"]
    assert len(arity) == 2             # wrong arity AND wrong coord count


def test_unparseable_file_is_reported():
    found = lint_source("def broken(:\n", "oops.py")
    assert [f.rule for f in found] == ["PARSE"]


def test_analyzer_exits_nonzero_on_seeded_violation(tmp_path):
    from repro.launch.analyze import main
    bad = tmp_path / "bad.py"
    bad.write_text(BAD_TRACED)
    empty_baseline = tmp_path / "baseline.json"
    assert main([str(bad), "--baseline", str(empty_baseline)]) == 1
    assert main([str(bad), "--baseline", str(empty_baseline),
                 "--no-gate"]) == 0


# ---------------------------------------------------------------------------
# the repo itself: zero NEW findings against the committed baseline
# ---------------------------------------------------------------------------
def test_repo_is_clean_against_committed_baseline():
    findings = lint_paths([os.path.join(REPO, "src")], repo_root=REPO)
    baseline = load_baseline(os.path.join(REPO, "analysis_baseline.json"))
    fresh = new_findings(findings, baseline)
    assert fresh == [], "new analyzer violations:\n" + "\n".join(
        f.render() for f in fresh)


def test_baseline_gates_only_new_findings(tmp_path):
    old = Finding(rule="TRC001", path="a.py", line=3, col=0,
                  severity="error", message="m", snippet="int(x)")
    new = Finding(rule="TRC001", path="a.py", line=9, col=0,
                  severity="error", message="m", snippet="int(y)")
    bp = str(tmp_path / "b.json")
    save_baseline(bp, [old])
    base = load_baseline(bp)
    # baselined finding survives a line move (fingerprint is rule+path+source)
    moved = Finding(rule="TRC001", path="a.py", line=40, col=0,
                    severity="error", message="m", snippet="int(x)")
    assert new_findings([moved], base) == []
    assert new_findings([moved, new], base) == [new]
    with open(bp) as f:
        assert json.load(f)["findings"][0]["rule"] == "TRC001"


# ---------------------------------------------------------------------------
# runtime guards against a live scheduler
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def granite():
    from repro.configs import get_config
    from repro.models import Model
    cfg = get_config("granite-3-2b-smoke")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _small_sched(granite, **kw):
    from repro.serving import ContinuousBatchScheduler, SchedulerConfig
    cfg, m, params = granite
    base = dict(n_slots=2, max_len=24, prefill_chunk=4)
    base.update(kw)
    return cfg, ContinuousBatchScheduler(m, params, SchedulerConfig(**base))


def test_guarded_poll_runs_clean(granite):
    """A full serve under transfer_guard + SlotAudit + no_recompile: the
    hot loop does no implicit host<->device syncs, never retraces, and
    keeps slot accounting consistent after every poll."""
    from repro.serving import Request
    cfg, sched = _small_sched(granite, exit_threshold=0.85)
    rs = np.random.RandomState(0)
    for l in (4, 7, 3):
        sched.submit(Request(
            tokens=rs.randint(0, cfg.vocab_size, l).astype(np.int32),
            max_new=5))
    sched.set_rng(None)
    sched.poll()                    # warm: compilation may transfer
    audit = SlotAudit(sched).attach()
    with no_recompile(sched), guard_polling(sched):
        while sched.has_work:
            sched.poll()
    audit.detach()
    assert audit.polls > 0
    assert all(r.done for r in sched.completed)


def test_slot_audit_catches_leaked_slot(granite):
    from repro.serving import Request
    cfg, sched = _small_sched(granite)
    sched.submit(Request(tokens=np.arange(4, dtype=np.int32), max_new=3))
    sched.set_rng(None)
    sched.run()
    audit = SlotAudit(sched)
    sched.active[0] = True          # seeded: active without a request
    with pytest.raises(GuardError, match="active without a request"):
        audit.check()
    sched.active[0] = False
    sched.slot_req[1] = Request(tokens=np.arange(3, dtype=np.int32),
                                max_new=2)
    with pytest.raises(GuardError, match="leaked slot"):
        audit.check()


def test_slot_audit_catches_counter_drift(granite):
    from repro.serving import Request
    cfg, sched = _small_sched(granite)
    sched.submit(Request(tokens=np.arange(5, dtype=np.int32), max_new=4))
    sched.set_rng(None)
    sched.run()
    SlotAudit(sched).check()        # balanced after a clean drain
    sched.tokens_served += 1        # seeded drift
    with pytest.raises(GuardError, match="tokens_served"):
        SlotAudit(sched).check()


def test_no_recompile_trips_on_fresh_compile(granite):
    from repro.serving import Request
    cfg, sched = _small_sched(granite)
    sched.submit(Request(tokens=np.arange(4, dtype=np.int32), max_new=2))
    sched.set_rng(None)
    with pytest.raises(GuardError, match="new jit compilation"):
        with no_recompile(sched):
            sched.run()             # first run compiles every stage
