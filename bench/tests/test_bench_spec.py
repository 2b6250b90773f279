"""BENCHMARK.json names only files that exist, and keeps to its contract's
shape: the harness finds every configuration, traffic mix, cell and metric
by name, with nothing in run.py that knows a cell."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BJ = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BJ["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_file_of_a_cell_is_found(workload):
    spec = harness.load_spec(workload)
    for key in ("prompt_len", "output_len", "generator", "block",
                "check_requests", "trace_seconds"):
        assert key in spec["traffic"]
    assert spec["cell"]["n_slots"] >= 1
    for k in ("logit_gap", "logit_gap_mean", "exit_entropy_gap"):
        assert spec["cell"]["limits"][k] > 0
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
    moved = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in moved
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def test_names_units_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BJ[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in BJ["configs"]}
    for c in BJ["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in BJ["paths"]))
    for w in BJ["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] == 1
        assert len(w["why"]) <= 200
    layers = set()
    for m in BJ["end_to_end"] + BJ["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in WORKLOADS
        layers.add(m.get("layer"))
    for m in BJ["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BJ["per_layer"]:
        if m["name"].endswith(("_roofline", "mfu")) or "_roofline." in \
                m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_config_files_list_what_differs_from_the_source():
    for c in BJ["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(body["departures"])
        assert set(c["reduced"]) <= set(body["model"])


def test_run_py_knows_no_cell():
    for name in ("run.py", "harness.py"):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        for w in WORKLOADS:
            assert w not in text
        for c in BJ["configs"]:
            assert c["name"] not in text
