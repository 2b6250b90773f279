"""The trace reduction on a small recorded trace: three decode steps of
starcoder2-3b (32 slots) on one TPU v5e, cut from a profiler trace of the
offline cell and kept as ``devtrace.load`` leaves it (60.7 ms, 4014 ops,
30 programs, 4 host spans)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

import devtrace  # noqa: E402
import harness  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "starcoder2_decode_3steps.json.gz")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MODEL = {"hidden_size": 3072, "vocab_size": 49152}


@pytest.fixture(scope="module")
def ctx():
    return harness.Context(log=None, setup_s=0.0, model=MODEL, cell={},
                           n_slots=32, peaks=PEAKS,
                           trace=devtrace.read(DATA))


def test_window_busy_and_idle(ctx):
    a, b = devtrace.window(ctx.trace)
    assert b - a == 60705505.0
    assert devtrace.busy_s(ctx.trace) == pytest.approx(0.044479399)
    idle = harness.load_reader("device_idle_share.offline").read(ctx)
    assert idle == pytest.approx(100 * (1 - 0.044479399 / 0.060705505))
    gaps = devtrace.idle_gaps(ctx.trace, 3)
    assert [g[0] for g in gaps] == ["bench.poll"] * 3
    assert gaps[0][1] == pytest.approx(0.0028873220)


def test_stage_times(ctx):
    mods = ctx.trace["modules"]
    stages = [e[2] for e in mods if e[0].startswith("jit_stage")]
    probes = [e[2] for e in mods if e[0].startswith("jit_probe")]
    fin = [e[2] for e in mods if e[0].startswith("jit_finalize")]
    assert (len(stages), len(probes), len(fin)) == (9, 6, 3)
    want = (sum(stages) + sum(probes) + sum(fin)) / 3 * 1e-6
    got = harness.load_reader("decode_step_ms.offline").read(ctx)
    assert got == pytest.approx(want) == pytest.approx(14.826362666666665)


def test_kernel_time_and_roofline(ctx):
    ev = devtrace.matching(ctx.trace["ops"], [r"^%exit_head_entropy\b"],
                           devtrace.window(ctx.trace))
    assert [e[2] for e in ev] == [401933.0, 401921.0, 401933.0, 402033.0,
                                  401932.0, 402145.0]
    got = harness.load_reader("exit_head_roofline.offline").read(ctx)
    least = (2 * 3072 * 49152 + 2 * 32 * 3072) / 819e9
    assert got == pytest.approx(100 * least / (sum(e[2] for e in ev) / 6e9))
    assert 0 < got <= 100


def test_top_ops_leave_out_loops(ctx):
    top = devtrace.top_ops(ctx.trace, 5)
    assert top[0][0] == "%bitcast_add_fusion.3"
    assert top[0][1] == pytest.approx(0.009123633)
    assert not any(name.startswith("%while") for name, _ in top)
