"""A short window of every cell through the run's own machinery on the
CPU, at the configuration's smoke size (the platform check skipped): set-up,
warm-up, the open-loop window, the reference check and the result line.
Off the chip no device metric is written."""
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import harness  # noqa: E402

SMOKE_ENTROPY_LIMIT = 1e-3

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    WORKLOADS = [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("trace", [False, True])
def test_short_window_of_every_cell(workload, trace, tmp_path, monkeypatch):
    # a trace directory of its own: other test workers trace too
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    spec = harness.load_spec(workload)
    # the CPU replays prompts slowly, the more so beside other test
    # workers: short prompts in short chunks and frequent arrivals, so that
    # a short window serves some tokens whatever the load
    spec["traffic"]["prompt_len"] = {"dist": "uniform", "min": 8, "max": 32}
    spec["config"]["scheduler"]["prefill_chunk"] = 16
    # the smoke model's narrow exit heads round more than the chip
    # cell's: its own limit, still far under a wrong entropy (0.5 or more)
    spec["cell"]["limits"]["exit_entropy_gap"] = SMOKE_ENTROPY_LIMIT
    if "rate_req_s" in spec["cell"]:
        spec["cell"]["rate_req_s"] = 4.0
    res = harness.run(spec, 2**33 + 77, 4.0, trace, time.perf_counter(),
                      smoke=True)
    assert json.loads(json.dumps(res)) == res
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    names = {m["name"] for m in (spec["per_layer"] if trace
                                 else spec["end_to_end"])}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
    for m in ("device_idle_share.offline", "step_mfu.offline",
              "exit_head_roofline.offline"):
        assert m not in res["metrics"]
