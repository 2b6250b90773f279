"""The metric readers on hand-made windows and traces, and the shape-based
FLOP and byte counts on hand-worked cases."""
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH]

import harness  # noqa: E402
from gen.driver import Poll, WindowLog  # noqa: E402
from reference import dense  # noqa: E402

PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
TINY = {"num_hidden_layers": 2, "hidden_size": 4, "intermediate_size": 8,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 2,
        "vocab_size": 10, "hidden_act": "silu"}


def _log():
    """Two requests due at 0 and 1 s in a 10 s window starting at 100."""
    req = lambda i, p, n, done: SimpleNamespace(  # noqa: E731
        req_id=i, tokens=SimpleNamespace(size=p), out_tokens=[0] * n,
        done=done, max_new=n)
    return WindowLog(
        t0=100.0, seconds=10.0, due={0: 100.0, 1: 101.0},
        visible={0: [102.0, 102.0, 102.5, 103.5], 1: [111.0]},
        requests={0: req(0, 3, 4, True), 1: req(1, 5, 1, False)},
        polls=[Poll(100.0, 102.0, 2, 256, True, 1),
               Poll(102.0, 102.5, 0, 0, True, 1),
               Poll(102.5, 103.5, 0, 0, True, 2),
               Poll(103.5, 104.0, 0, 0, False, 0)],
        lateness=[0.0, 0.5])


def _ctx(**kw):
    return harness.Context(log=_log(), setup_s=42.0, model=TINY, cell={},
                           n_slots=4, peaks=PEAKS, reference=dense, **kw)


def read(name, ctx):
    return harness.load_reader(name).read(ctx)


def test_end_to_end_readers():
    ctx = _ctx()
    assert read("setup_s", ctx) == 42.0
    # four tokens shown by the window's end (the fifth at 111 s is past it)
    assert read("output_tok_per_s", ctx) == pytest.approx(0.4)
    assert ctx.log.summary()["completed"] == 1


def test_scheduler_readers():
    ctx = _ctx()
    # decode steps: 1/4, 1/4, 2/4 of the slots
    assert read("decode_occupancy.offline", ctx) == pytest.approx(100 / 3)


def test_a_split_metric_finds_its_quantity_s_reader():
    base = harness.load_reader("device_idle_share")
    for name in ("device_idle_share.offline", "device_idle_share.chat"):
        assert harness.load_reader(name).read.__code__.co_code \
            == base.read.__code__.co_code
    with pytest.raises(FileNotFoundError):
        harness.load_reader("no_such_metric.offline")


def test_mfu_counts_hand_worked():
    mfu = harness.load_reader("step_mfu.offline")
    # per layer: q,o 2*4*2*2=32, k,v 2*4*1*2=16, ffn 3*4*8=96 -> 144;
    # two layers and a 10 x 4 head (the dense reference counts them)
    assert dense.matmul_params(TINY) == 2 * 144 + 40
    n2 = 2 * (2 * 144 + 40)
    # a token at context c adds 4 * L * Nq * H * c = 32 c
    assert dense.attention_flops(TINY, 3) == 96
    assert mfu.token_flops(dense, TINY, 3) == n2 + 96
    # prompt of 3 (contexts 1+2+3) then tokens 2 and 3 at contexts 4, 5
    assert mfu.request_flops(dense, TINY, 3, 1, 3) == 3 * n2 + 32 * 6 \
        + 2 * n2 + 32 * 9
    ctx = _ctx()
    total = mfu.request_flops(dense, TINY, 3, 1, 4)
    assert read("step_mfu.offline", ctx) == pytest.approx(
        100 * total / (10 * PEAKS["bf16_flops_per_s"]))
    ctx.peaks = None
    assert read("step_mfu.offline", ctx) is None


def test_exit_head_least_time_hand_worked():
    r = harness.load_reader("exit_head_roofline.offline")
    # granite: 2 * 2048 * 49155 bytes of weights + 2 * 32 * 2048 of input
    t, bound = r.least_time_s(32, 2048, 49155, PEAKS)
    assert bound == "bytes"
    assert t == pytest.approx((2 * 2048 * 49155 + 2 * 32 * 2048) / 800e9)
    t, bound = r.least_time_s(4096, 2048, 49155, PEAKS)
    assert bound == "flops"
    assert t == pytest.approx(2 * 4096 * 2048 * 49155 / 200e12)


def test_trace_readers_on_hand_made_trace():
    tr = {"host": [["bench.poll", 0, 1000], ["bench.wait", 1000, 1000]],
          "modules": [["jit_chunk(1)", 0, 300], ["jit_chunk(1)", 400, 100],
                      ["jit_stage(2)", 600, 100], ["jit_probe(3)", 700, 50],
                      ["jit_finalize(4)", 750, 50],
                      ["jit_stage(2)", 1600, 200],
                      ["jit_finalize(4)", 1800, 100]],
          "ops": [["fusion", 0, 300], ["fusion", 400, 100],
                  ["%exit_head_entropy.1", 700, 50], ["fusion", 600, 100],
                  ["fusion", 750, 50], ["fusion", 1600, 300]]}
    ctx = _ctx(trace=tr)
    # (100 + 50 + 50 + 200 + 100) ns over 2 finalizes
    assert read("decode_step_ms.offline", ctx) == pytest.approx(250e-6)
    # busy 300 + 100 + 100 + 50 + 50 + 300 = 900 of 2000 ns
    assert read("device_idle_share.offline", ctx) == pytest.approx(55.0)
    roof = read("exit_head_roofline.offline", ctx)
    t, _ = harness.load_reader("exit_head_roofline.offline").least_time_s(
        4, 4, 10, PEAKS)
    assert roof == pytest.approx(100 * t / 50e-9)
    ctx.trace = None
    for name in ("decode_step_ms.offline", "device_idle_share.offline",
                 "exit_head_roofline.offline"):
        assert read(name, ctx) is None
