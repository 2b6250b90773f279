"""One run of one benchmark cell: set up, warm up, measure, check.

Everything a cell is made of is found by name, so a new cell, traffic mix
or metric is new files plus ``BENCHMARK.json`` entries:

* ``bench/configs/<config>.json``: the repo preset that runs and its
  ``preset_changes`` (a dict for a sub-config such as ``moe`` changes
  fields of that one), the model as run (in the source's key names, read
  by the reference), the ``SchedulerConfig`` fields it sets, and where the
  model is not a dense decoder: ``reference`` (the module
  ``bench/reference/<name>.py``, ``dense`` by default), ``weight_rules``
  for its leaves (``weights.py``) and ``shape_keys``, the further source
  keys that ``describe`` checks;
* ``bench/traffic/<mix>.json``: the generator (``bench/gen/<name>.py``)
  and its parameters;
* ``bench/cells/<workload>.json``: the load this cell offers (slots, rate)
  and the limits its outputs are held to;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``
  returning a number, or None when the run gave it nothing to read.

The window drives ``ContinuousBatchScheduler.poll()`` (``gen/driver.py``)
with the program built as ``launch/serve.serve_poisson`` builds it, on
weights the benchmark draws from the seed (``weights.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WARM_ID = 1 << 40
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec(workload: str) -> Dict:
    """The cell's entry of BENCHMARK.json with its files and metrics."""
    bj = _json(ROOT, "BENCHMARK.json")
    w = [x for x in bj["workloads"] if x["name"] == workload]
    if len(w) != 1:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json")
    w = w[0]
    e2e = [m for m in bj["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bj["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"workload": w,
            "config": _json(BENCH, "configs", w["config"] + ".json"),
            "traffic": _json(BENCH, "traffic", w["traffic"] + ".json"),
            "cell": _json(BENCH, "cells", workload + ".json"),
            "end_to_end": e2e, "per_layer": per_layer}


def require_chips(n: int):
    """The chips the cell asks for, or exit 2: a measurement path that
    finds no TPU fails, it never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"bench: needs {n} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    return devs


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however small, so only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def describe(cfg, shape_keys: Optional[Dict] = None) -> Dict:
    """The shape keys of a repo ModelConfig, in the config file's names:
    the file must describe the model that runs.  ``shape_keys`` adds
    source keys read from dotted ModelConfig attributes
    (``{"n_routed_experts": "moe.num_experts"}``) to the common ones."""
    out = {"num_hidden_layers": cfg.num_layers,
           "hidden_size": cfg.d_model,
           "intermediate_size": cfg.d_ff,
           "num_attention_heads": cfg.num_heads,
           "num_key_value_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim,
           "vocab_size": cfg.vocab_size,
           "hidden_act": {"silu": "silu",
                          "gelu": "gelu_pytorch_tanh"}[cfg.act],
           "norm": cfg.norm,
           "rope_theta": cfg.rope_theta,
           "sliding_window": cfg.sliding_window,
           "tie_word_embeddings": cfg.tie_embeddings,
           "exit_layers": list(cfg.exits.exit_layers),
           "exit_entropy_threshold": cfg.exits.entropy_threshold,
           "dtype": cfg.dtype}
    for key, attr in (shape_keys or {}).items():
        if key in out:
            raise SystemExit(f"bench: shape key {key!r} is a common key; "
                             "it is always checked as describe() reads it")
        v = cfg
        for part in attr.split("."):
            if not hasattr(v, part):
                raise SystemExit(f"bench: shape key {key!r} names {attr!r}, "
                                 "which the ModelConfig has not")
            v = getattr(v, part)
        out[key] = list(v) if isinstance(v, tuple) else v
    return out


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    log: object                       # gen.driver.WindowLog
    setup_s: float
    model: Dict                       # the config file's model section
    cell: Dict
    n_slots: int
    peaks: Optional[Dict]             # this chip's row of peaks.json
    trace: Optional[Dict] = None      # devtrace.load() of the traced run
    reference: object = None          # the config's bench/reference module

    @property
    def span(self):
        """Host-clock span the per-layer metrics cover: the traced part
        of the window, else the whole window."""
        lg = self.log
        return lg.traced if lg.traced else (lg.t0, lg.end)


def load_reader(name: str):
    """The reader ``bench/metrics/<name>.py``, else the one named without
    the last suffix (``step_mfu.offline`` -> ``step_mfu.py``): one reader
    serves a quantity that is split by the cells that report it."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics",
                            name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def apply_changes(obj, changes: Dict):
    """The frozen dataclass ``obj`` with ``changes``: a dict given for a
    field that holds a dataclass changes fields of that one (``{"moe":
    {"num_experts": 8}}``), a list for a tuple field becomes a tuple."""
    out = {}
    for k, v in changes.items():
        cur = getattr(obj, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(cur):
            v = apply_changes(cur, v)
        elif isinstance(v, list) and isinstance(cur, tuple):
            v = tuple(v)
        out[k] = v
    return dataclasses.replace(obj, **out)


def model_config(c: Dict, smoke: bool = False):
    """The ModelConfig a configuration file runs: its preset (the
    ``-smoke`` variant under ``smoke``) with its ``preset_changes``."""
    from repro.configs import get_config
    cfg = get_config(c["preset"] + ("-smoke" if smoke else ""))
    return apply_changes(cfg, c["preset_changes"])


def checked_model(c: Dict, name: str, smoke: bool = False):
    """The ModelConfig that runs and the file's model section, which must
    describe it; under ``smoke`` the section takes the smoke sizes."""
    cfg = model_config(c, smoke)
    want = describe(cfg, c.get("shape_keys"))
    m = dict(c["model"])
    if smoke:
        m.update(want)
    wrong = {k: (v, m.get(k)) for k, v in want.items() if m.get(k) != v}
    if wrong:
        raise SystemExit(f"bench: {name}.json does not describe the preset "
                         f"that runs: {wrong}")
    return cfg, m


def _peaks(dev) -> Optional[Dict]:
    """The chip's peaks; a TPU missing from the table is an error.  Off
    the TPU (CPU rehearsals only) there are none, so no device metric."""
    if dev.platform != "tpu":
        return None
    table = _json(BENCH, "peaks.json")
    if dev.device_kind not in table:
        raise SystemExit(f"bench: no peaks for {dev.device_kind!r} in "
                         "bench/peaks.json")
    return table[dev.device_kind]


def build(spec: Dict, seed: int, seconds: float, *, smoke: bool = False):
    """Model, weights, traffic and a warmed-up scheduler for one cell.
    ``smoke`` builds the preset's ``-smoke`` variant with a model section
    derived from it (CPU rehearsals and tests only).  Returns a dict."""
    import jax
    from repro.models import Model, ShardCtx
    from repro.serving.scheduler import (ContinuousBatchScheduler, Request,
                                         SchedulerConfig)
    import weights

    c, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    cfg, m = checked_model(c, spec["workload"]["config"], smoke)
    model = Model(cfg, ShardCtx(None))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, seed, c.get("weight_rules"))
    gen = importlib.import_module("gen." + traffic["generator"])
    max_len = gen.max_len(traffic["prompt_len"]) \
        + gen.max_len(traffic["output_len"])
    sched = ContinuousBatchScheduler(model, params, SchedulerConfig(
        n_slots=cell["n_slots"], max_len=max_len, **c["scheduler"]))
    # warm-up: one chunk of prefill, the merge, the first-token readback
    # and two decode steps (segments, probes, finalize): every shape the
    # window uses, since prompts of any length replay the same chunk
    sched.submit(Request(tokens=np.ones(sched.cfg.prefill_chunk, np.int32),
                         max_new=2, req_id=WARM_ID))
    sched.run()
    sched.reset_stats()
    return {"model": m, "shapes": shapes, "params": params, "sched": sched,
            "reqs": gen.generate(traffic, cell, seed, cfg.vocab_size,
                                 seconds),
            "max_len": max_len}


def serve(sched, reqs, seconds: float, **hooks):
    """The measured window (``gen/driver.py``) over prepared requests."""
    from repro.serving.scheduler import Request
    from gen import driver

    def make_request(i, r):
        return Request(tokens=r["tokens"], max_new=r["max_new"], req_id=i)

    return driver.run_window(sched, reqs, seconds,
                             make_request=make_request, **hooks)


def reset(sched, params) -> None:
    """Empty a built scheduler and give it other weights (the calibration
    serves many windows from one warmed-up build)."""
    sched.drain_queue()
    sched.cancel_pending()
    for _, slot, _ in sched.active_requests():
        sched.release_slot(slot)
    sched.reset_stats()
    sched.params = params


def run(spec: Dict, seed: int, seconds: float, trace: bool,
        t_start: float, *, smoke: bool = False) -> Dict:
    """One run; returns the result line's object."""
    import jax
    import check
    import devtrace

    traffic, cell = spec["traffic"], spec["cell"]
    compiles: List[float] = []

    def on_event(event, secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(time.perf_counter())

    reference = check.load_reference(spec["config"])
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        b = build(spec, seed, seconds, smoke=smoke)
        setup_s = time.perf_counter() - t_start
        sched, m = b["sched"], b["model"]
        sizes = sched.jit_cache_sizes()
        hooks = {}
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            hooks = dict(
                trace_start=lambda: jax.profiler.start_trace(TRACE_DIR),
                trace_stop=jax.profiler.stop_trace,
                trace_seconds=traffic["trace_seconds"])
        log = serve(sched, b["reqs"], seconds, **hooks)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    in_window = sum(1 for t in compiles if log.t0 <= t <= log.end)
    dev = jax.devices()[0]
    after = sched.jit_cache_sizes()
    resized = {k: (v, after[k]) for k, v in sizes.items() if after[k] != v}
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    summary = log.summary()
    print("window: " + json.dumps(summary))
    print(f"compiles in window: {in_window}; jit caches changed: {resized}")

    # the compiled exit probes the window ran, checked after it
    stages = sched.audit_stages()
    probes = [stages[f"probe{e}"] for e in range(len(m["exit_layers"]))]
    # the program's state goes before the reference runs
    sched.cache = None
    sched._pending = None
    del sched, b["sched"], stages
    gc.collect()
    checks, failed = check.compare(reference, m, b["params"], log,
                                   traffic, cell, seed, b["max_len"], probes)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    ctx = Context(log=log, setup_s=setup_s, model=m, cell=cell,
                  n_slots=cell["n_slots"], peaks=_peaks(dev),
                  reference=reference)
    out = {}
    if trace:
        ctx.trace = devtrace.load(TRACE_DIR, dev.id)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if ctx.trace["ops"]:
            w = devtrace.window(ctx.trace)
            device["busy_s"] = devtrace.busy_s(ctx.trace)
            device["window_s"] = (w[1] - w[0]) * 1e-9
            out["breakdown"] = {"device_ops": devtrace.top_ops(ctx.trace),
                                "idle_gaps": devtrace.idle_gaps(ctx.trace)}
    metrics = {}
    for md in spec["per_layer"] if trace else spec["end_to_end"]:
        val = load_reader(md["name"]).read(ctx)
        if val is not None:
            metrics[md["name"]] = {"value": val, "unit": md["unit"]}
    res = {"correct": check.correct(checks),
           "attempted": summary["due"], "failed": failed,
           "metrics": metrics, "device": device}
    res.update(out)
    res["checks"] = checks
    return res


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    spec = load_spec(workload)
    require_chips(spec["workload"]["chips"])
    use_compile_cache()
    res = run(spec, seed, seconds, trace, t_start)
    sys.stdout.flush()
    for name, v in res["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0
