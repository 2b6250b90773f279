"""A configuration file brings what its architecture needs: its reference
module by name, rules for drawing its leaves, further shape keys, nested
preset changes and, through its reference module, its FLOP count.

The dense files, which use none of these keys, draw the same weights as
before bit for bit.  Data-only files for a latent-attention expert model
and a Mamba2 hybrid (``data/configs/``, test data and no benchmark
configuration) go through drawing, the shape check, the nested changes and
the warm-up with no other file."""
import copy
import hashlib
import json
import math
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import jax  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import weights  # noqa: E402
from gen.driver import WindowLog  # noqa: E402
from reference import dense  # noqa: E402
from repro.models import Model, ShardCtx  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data", "configs")
HOOKED = ["deepseek-v3-671b", "zamba2-1.2b"]
SEED = 2**33 + 5
# SHA-256 (``digest``) of the smoke trees drawn at SEED on the CPU before
# configuration files could bring weight rules
DIGESTS = {
    "starcoder2-3b":
        "70f336659846c7b48925f83be1d9167bc9daa069f83988922a54352ceaabc420",
    "granite-3-2b":
        "c57aae51de4f9efa89b4c17d7a8d2d99d66e30d769daf741256fe3d3c8167ffe",
}


def _config(name):
    d = DATA if name in HOOKED else os.path.join(BENCH, "configs")
    with open(os.path.join(d, name + ".json")) as f:
        return json.load(f)


def _shapes(c):
    model = Model(harness.model_config(c, smoke=True), ShardCtx(None))
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_dense_weights_are_drawn_as_before(name):
    c = _config(name)
    assert not {"reference", "weight_rules", "shape_keys"} & set(c)
    params = weights.make(_shapes(c), SEED, c.get("weight_rules"))
    assert digest(params) == DIGESTS[name]


@pytest.mark.parametrize("name", HOOKED)
def test_every_leaf_is_drawn_by_its_rule(name):
    c = _config(name)
    rules = c["weight_rules"]
    params = weights.make(_shapes(c), SEED, rules)
    kinds = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        where = weights.path_name(path)
        rule = weights.rule_for(where, leaf.shape, rules)
        a = np.asarray(leaf, np.float64)
        if rule.get("normal") == "fan_in":
            std = 1 / math.sqrt(math.prod(leaf.shape[i]
                                          for i in rule["axes"]))
            assert abs(a.std() / std - 1) < 0.05, where
            kinds.add("fan_in")
        elif "const" in rule:
            assert np.all(a == rule["const"]), where
            kinds.add("const")
        elif "uniform" in rule:
            lo, hi = rule["uniform"]
            if rule.get("log"):
                lo, hi = math.log(lo), math.log(hi)
            # bf16 rounds a draw by at most 2**-8 of its size
            tol = 2.0 ** -8 * max(abs(lo), abs(hi))
            assert lo - tol <= a.min() and a.max() <= hi + tol, where
            assert a.max() - a.min() > (hi - lo) / 2, where
            kinds.add("log" if rule.get("log") else "uniform")
    assert "fan_in" in kinds
    assert kinds >= ({"const"} if "mtp/kind_is_moe" in rules else {"log"})


def test_a_leaf_without_a_rule_or_with_two_fails_naming_it():
    c = _config("deepseek-v3-671b")
    shapes = _shapes(c)
    rules = dict(c["weight_rules"])
    del rules["router"]
    with pytest.raises(ValueError, match="no rule .*moe/router"):
        weights.make(shapes, SEED, rules)
    # a file rule against a built-in one, and two file rules
    dense_shapes = _shapes(_config("starcoder2-3b"))
    with pytest.raises(ValueError, match="conflicting .*attn/wq'"):
        weights.make(dense_shapes, SEED, {"wq": {"normal": 0.02}})
    z = _config("zamba2-1.2b")
    rules = dict(z["weight_rules"], norm={"const": 1.0})
    with pytest.raises(ValueError, match="conflicting .*mamba/norm'"):
        weights.make(_shapes(z), SEED, rules)
    # the same rule twice is no conflict
    weights.make(dense_shapes, SEED, {"wq": {"normal": "fan_in",
                                             "axes": [-3]}})


@pytest.mark.parametrize("name", HOOKED)
def test_shape_keys_are_checked_at_full_size(name):
    c = _config(name)
    cfg, m = harness.checked_model(c, name)
    assert set(c["shape_keys"]) <= set(harness.describe(
        cfg, c["shape_keys"]))
    key = sorted(c["shape_keys"])[0]
    wrong = copy.deepcopy(c)
    wrong["model"][key] += 1
    with pytest.raises(SystemExit, match=key):
        harness.checked_model(wrong, name)
    # a common key is still checked when the file has shape keys
    wrong = copy.deepcopy(c)
    wrong["model"]["hidden_size"] += 1
    with pytest.raises(SystemExit, match="hidden_size"):
        harness.checked_model(wrong, name)
    missing = copy.deepcopy(c)
    missing["shape_keys"][key] = "no_such.attribute"
    with pytest.raises(SystemExit, match=key):
        harness.checked_model(missing, name)
    common = copy.deepcopy(c)
    common["shape_keys"]["hidden_size"] = "num_layers"
    with pytest.raises(SystemExit, match="hidden_size"):
        harness.checked_model(common, name)


def test_nested_preset_changes_set_the_experts_held():
    c = _config("deepseek-v3-671b")
    cfg, m = harness.checked_model(c, "deepseek-v3-671b", smoke=True)
    assert cfg.moe.num_experts == 8 == m["n_routed_experts"]
    assert cfg.moe.top_k == m["num_experts_per_tok"]
    moe = _shapes(c)["blocks"][1]["moe"]
    assert moe["router"].shape[-1] == 8 and moe["wg"].shape[-3] == 8
    c["preset_changes"] = {"moe": {"num_experts": 6},
                           "exits": {"exit_layers": [1]}}
    cfg = harness.model_config(c, smoke=True)
    assert cfg.moe.num_experts == 6 and cfg.exits.exit_layers == (1,)
    assert cfg.moe.top_k == harness.model_config(
        dict(c, preset_changes={}), smoke=True).moe.top_k
    assert _shapes(c)["blocks"][1]["moe"]["wu"].shape[-3] == 6


def _smoke_spec(name):
    workload = harness._json(ROOT, "BENCHMARK.json")["workloads"][0]
    spec = harness.load_spec(workload["name"])
    spec["workload"] = dict(spec["workload"], config=name)
    spec["config"] = _config(name)
    spec["config"]["scheduler"]["prefill_chunk"] = 16
    spec["traffic"]["prompt_len"] = {"dist": "uniform", "min": 8, "max": 32}
    spec["traffic"]["output_len"] = {"dist": "fixed", "value": 8}
    spec["cell"]["n_slots"] = 4
    return spec


@pytest.mark.parametrize("name", HOOKED)
def test_a_data_only_config_builds_and_warms_up(name):
    b = harness.build(_smoke_spec(name), SEED, 2.0, smoke=True)
    sizes = b["sched"].jit_cache_sizes()
    # the warm request went through a prefill chunk and decode steps,
    # each compiled once
    assert sizes["prefill"] == 1 and sizes["finalize"] == 1
    assert all(v == 1 for k, v in sizes.items() if k.startswith("segment"))
    assert b["reqs"] and b["model"]["num_hidden_layers"] == 2


def test_the_reference_module_is_found_by_name():
    assert check.load_reference(_config("starcoder2-3b")) is dense
    assert check.load_reference({"reference": "dense"}) is dense
    for name in ("mla_moe", "../weights"):
        with pytest.raises(SystemExit, match=f"bench/reference/{name}.py"):
            check.load_reference({"reference": name})
    # a run looks for it before any set-up
    spec = _smoke_spec("deepseek-v3-671b")
    with pytest.raises(SystemExit, match="bench/reference/mla_moe.py"):
        harness.run(spec, SEED, 1.0, False, 0.0, smoke=True)


def test_mfu_counts_from_the_reference_module_s_functions():
    mfu = harness.load_reader("step_mfu.offline")
    ref = SimpleNamespace(matmul_params=lambda m: 1000,
                          attention_flops=lambda m, c: 10.0 * c)
    # prompt of 3: 2 * 1000 * 3 + 10 * (1 + 2 + 3); tokens 2 and 3 at
    # contexts 4 and 5: 2000 + 40, 2000 + 50
    assert mfu.request_flops(ref, {}, 3, 1, 3) == 6060 + 2040 + 2050
    assert mfu.request_flops(ref, {}, 3, 2, 2) == 2040
    req = SimpleNamespace(req_id=0, tokens=np.zeros(3), out_tokens=[0] * 3,
                          done=True, max_new=3)
    log = WindowLog(t0=0.0, seconds=2.0, due={0: 0.0},
                    visible={0: [0.5, 1.0, 1.5]}, requests={0: req},
                    polls=[], lateness=[0.0])
    ctx = harness.Context(log=log, setup_s=1.0, model={}, cell={},
                          n_slots=1, peaks={"bf16_flops_per_s": 1e6},
                          reference=ref)
    assert mfu.read(ctx) == pytest.approx(100 * 10150 / (2.0 * 1e6))
