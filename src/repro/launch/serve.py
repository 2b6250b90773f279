"""Serving driver: batch generation and open-loop Poisson-arrival serving.

Two modes:

* ``--mode batch`` (default): one batch of identical-shape requests through
  ``ServingEngine`` (continuous-batching scheduler under the hood), printing
  tok/s and early-exit statistics.

      PYTHONPATH=src python -m repro.launch.serve --arch yi-6b-smoke \\
          --batch 4 --prompt-len 16 --max-new 32 --threshold 0.6

* ``--mode poisson``: open-loop load test.  ``--requests`` requests arrive as
  a Poisson process at ``--rate`` req/s (exponential inter-arrival gaps),
  with prompt lengths drawn uniformly from [max(1, prompt_len//4),
  prompt_len]; the continuous-batching scheduler admits them into
  ``--slots`` decode slots as slots free up.  Reports p50/p95 end-to-end
  request latency and sustained decode tok/s.

      PYTHONPATH=src python -m repro.launch.serve --arch yi-6b-smoke \\
          --mode poisson --rate 4 --requests 32 --slots 8 \\
          --prompt-len 16 --max-new 32

  With ``--tiered`` the same trace is instead submitted through the
  paradigm-aware admission router into cloud/edge/device scheduler pools
  (``TieredServingCluster``); arrivals become virtual-clock timestamps and
  the report adds per-tier routed counts, utilization, and p50/p95 latency
  under the chosen ``--scenario`` (default | degraded-wan |
  neurosurgeon-era | high-rtt-access | tier-outage).  ``tier-outage``
  kills the edge tier
  mid-trace: the cluster drains its in-flight slots to the surviving
  tiers via exported KV snapshots (no prefill re-run) and the report adds
  the migration ledger and resilience numbers.  ``--plan-arch``
  names the config the router plans against (defaults to ``--arch`` with a
  ``-smoke`` suffix stripped, so smoke runtimes route like the real model).

      PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b-smoke \\
          --mode poisson --tiered --scenario degraded-wan --requests 32

  With ``--models a,b`` (poisson mode) the trace instead carries requests
  for SEVERAL architectures and one multi-model pool
  (``MultiModelScheduler``) multiplexes them: one arena (cache + jitted
  stages + exit counters) per model behind one queue, requests assigned
  round-robin across models.  Combine with ``--tiered`` to route
  per-(model, request) across the cloud/edge/device pools using per-model
  cost graphs (plan configs default to each arch with ``-smoke`` stripped).

      PYTHONPATH=src python -m repro.launch.serve \\
          --models granite-3-2b-smoke,xlstm-350m-smoke \\
          --mode poisson --rate 8 --requests 32

Flags:
    --arch        architecture name (configs registry; "-smoke" for reduced)
    --models      [poisson] comma-separated archs for a multi-model pool
                  (overrides --arch)
    --mode        batch | poisson
    --batch       [batch] requests per batch
    --prompt-len  max prompt length (poisson draws lengths up to this)
    --max-new     tokens generated per request
    --threshold   early-exit entropy threshold (normalized, 0..1)
    --slots       [poisson] decode slot-pool size (concurrent requests)
    --rate        [poisson] mean arrival rate, requests/second
    --requests    [poisson] total requests in the trace
    --async-decode  [poisson] overlapped decode pipeline (on-device
                  sampling ring, double-buffered dispatch, deferred
                  batched readback; see docs/pipeline.md)
    --readback-interval  [async] decode steps per batched host readback
    --prefill-chunk  tokens per jitted prefill dispatch
    --tiered      [poisson] route through cloud/edge/device pools
    --scenario    [tiered] hardware scenario preset (default |
                  degraded-wan | neurosurgeon-era | high-rtt-access |
                  tier-outage)
    --plan-arch   [tiered] config the admission router plans against
    --deadline    [tiered] per-request deadline in seconds (0 = none)
    --seed        RNG seed for prompts/arrivals
    --long        long-context (ring-buffer KV) mode
    --spec-draft  [tiered multi-model] group entry used as the device-tier
                  speculative draft; enables the cross-tier speculative
                  admission candidate (draft on device, batched verify on
                  cloud)
    --spec-k      [tiered multi-model] draft tokens per speculative round

  Cross-tier speculative decoding example (the speculative candidate wins
  when the client's access link has a high RTT and the plan-size gap
  between draft and target is large):

      PYTHONPATH=src python -m repro.launch.serve \\
          --models granite-3-2b-smoke,deepseek-v3-671b-smoke \\
          --mode poisson --tiered --scenario high-rtt-access \\
          --spec-draft granite-3-2b-smoke --spec-k 6 --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import Scenario
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model, ShardCtx
from repro.serving import (ClusterConfig, ContinuousBatchScheduler,
                           ModelGroup, MultiModelScheduler, Request,
                           ServeConfig, ServingEngine, SchedulerConfig,
                           TieredServingCluster, poisson_trace)

SCENARIOS = {"default": Scenario.default,
             "degraded-wan": Scenario.degraded_wan,
             "neurosurgeon-era": Scenario.neurosurgeon_era,
             "high-rtt-access": Scenario.high_rtt_access,
             "tier-outage": Scenario.tier_outage}


def _print_migration(stats):
    """Migration/resilience lines shared by the tiered drivers."""
    mig = stats.get("migration", {})
    if mig.get("split_handoffs") or mig.get("outage_migrations") \
            or mig.get("requeued"):
        print(f"  migration: splits={mig['split_handoffs']} "
              f"outage={mig['outage_migrations']} "
              f"requeued={mig['requeued']} "
              f"moved={mig['bytes_moved'] / 1024:.0f}KiB "
              f"(raw {mig['bytes_raw'] / 1024:.0f}KiB, "
              f"{mig['compressed']} int8) "
              f"transfer={mig['transfer_s'] * 1e3:.1f}ms")
    res = stats.get("resilience")
    if res is not None:
        print(f"  resilience: dead={stats.get('dead_tiers', [])} "
              f"survive_prob={res['survive_prob']:.2f} "
              f"acc_with_drain={res['expected_accuracy_with_skip']:.2f} "
              f"vs_collapse={res['expected_accuracy_without_skip']:.2f} "
              f"(gain {res['gain']:+.2f})")


def _poisson_trace(rs, rate: float, n_requests: int, prompt_len: int):
    """The shared open-loop trace every Poisson driver replays.  Thin alias
    for ``repro.serving.traces.poisson_trace`` (same draw order, so old
    seeds reproduce old traces bit-for-bit)."""
    return poisson_trace(rs, rate, n_requests, prompt_len)


def _drive_open_loop(sched, reqs, arrivals):
    """Submit each request at its arrival offset and tick the pool until
    every request completes.  Returns (t0, makespan_seconds)."""
    t0 = time.time()
    i = 0
    while len(sched.completed) < len(reqs):
        now = time.time() - t0
        while i < len(reqs) and arrivals[i] <= now:
            sched.submit(reqs[i])
            i += 1
        if sched.has_work:
            sched.tick()
        elif i < len(reqs):
            time.sleep(min(0.002, max(0.0, arrivals[i] - now)))
    return t0, time.time() - t0


def _pctl(vals, q: float) -> float:
    """Percentile, or nan for an empty sample (a model that received no
    requests must not crash or fake a 0.0)."""
    return float(np.percentile(np.asarray(vals), q)) if len(vals) \
        else float("nan")


def serve(arch: str, batch: int, prompt_len: int, max_new: int, *,
          threshold: float = 0.5, long_mode: bool = False, seed: int = 0,
          params=None):
    """Closed one-batch generation (the quickstart path)."""
    cfg = get_config(arch)
    model = Model(cfg, ShardCtx(None))
    rng = jax.random.PRNGKey(seed)
    if params is None:
        params = model.init(rng)
    eng = ServingEngine(model, params,
                        ServeConfig(exit_threshold=threshold,
                                    long_mode=long_mode))
    prompts = jax.random.randint(rng, (batch, prompt_len), 0, cfg.vocab_size)
    frames = None
    if cfg.family == "encdec":
        frames = 0.02 * jax.random.normal(
            rng, (batch, cfg.encdec.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    t0 = time.time()
    out = eng.generate(prompts, max_new=max_new, frames=frames, rng=rng)
    dt = time.time() - t0
    stats = eng.exit_stats()
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({batch * max_new / dt:.1f} tok/s)")
    print("exit stats:", {k: round(v, 3) for k, v in stats.items()})
    return out, stats


def serve_poisson(arch: str, *, rate: float = 4.0, n_requests: int = 32,
                  slots: int = 8, prompt_len: int = 16, max_new: int = 32,
                  threshold: float = 0.5, prefill_chunk: int = 16,
                  long_mode: bool = False, paged: bool = False,
                  async_decode: bool = False, readback_interval: int = 8,
                  seed: int = 0, params=None, quiet: bool = False):
    """Open-loop Poisson-arrival serving through the continuous-batching
    scheduler.  Returns a stats dict (p50/p95 latency, WALL-CLOCK sustained
    tok/s, host/device time split, jit cache sizes — the no-recompile
    invariant — and every request's generated tokens, in trace order).
    ``async_decode`` runs the overlapped pipeline: on-device sampling ring,
    double-buffered window dispatch, one batched readback per
    ``readback_interval`` decode steps."""
    cfg = get_config(arch)
    model = Model(cfg, ShardCtx(None))
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    max_len = prompt_len + max_new
    if paged:                          # page-pool arenas need whole pages
        max_len += (-max_len) % 16
    sched = ContinuousBatchScheduler(
        model, params,
        SchedulerConfig(n_slots=slots, max_len=max_len,
                        prefill_chunk=min(prefill_chunk, max(1, prompt_len)),
                        exit_threshold=threshold, long_mode=long_mode,
                        paged=paged, segmented=not async_decode,
                        async_decode=async_decode,
                        readback_interval=readback_interval))

    rs = np.random.RandomState(seed)
    arrivals, lengths = _poisson_trace(rs, rate, n_requests, prompt_len)
    reqs = [Request(tokens=rs.randint(0, cfg.vocab_size, int(l)),
                    max_new=max_new) for l in lengths]
    if cfg.family == "encdec":
        for r in reqs:
            r.frames = 0.02 * rs.randn(cfg.encdec.encoder_seq_len,
                                       cfg.d_model).astype(np.float32)

    # warm up compiles outside the timed trace (one admit + one step)
    warm = Request(tokens=rs.randint(0, cfg.vocab_size, int(lengths[0])),
                   max_new=1)
    if cfg.family == "encdec":
        warm.frames = reqs[0].frames
    sched.submit(warm)
    sched.run()
    sched.reset_stats()               # warmup must not skew the report

    t0, makespan = _drive_open_loop(sched, reqs, arrivals)
    lat = np.asarray([r.t_done - (t0 + arrivals[j])
                      for j, r in enumerate(reqs)])
    total_tokens = sum(len(r.out_tokens) for r in reqs)
    stats = {
        "requests": n_requests,
        "slots": slots,
        "rate_req_s": rate,
        "makespan_s": makespan,
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p95_latency_s": float(np.percentile(lat, 95)),
        "sustained_tok_s": total_tokens / makespan,
        "tokens": total_tokens,
        "async_decode": async_decode,
        "host_ms": sched.host_ms_total,
        "device_ms": sched.device_ms_total,
        "peak_tokens_in_flight": sched.peak_tokens_in_flight,
        "jit_cache_sizes": sched.jit_cache_sizes(),
        "exit_stats": sched.exit_stats(),
        "out_tokens": [list(r.out_tokens) for r in reqs],
    }
    if paged:
        stats["prefix_hit_tokens"] = sched.prefix_hit_tokens
        stats["prefill_chunks_skipped"] = sched.prefill_chunks_skipped
    if not quiet:
        print(f"arch={cfg.name} poisson rate={rate}/s requests={n_requests} "
              f"slots={slots}" + (" paged" if paged else "")
              + (f" async(r={readback_interval})" if async_decode else ""))
        print(f"  p50={stats['p50_latency_s']*1e3:.0f}ms "
              f"p95={stats['p95_latency_s']*1e3:.0f}ms "
              f"sustained={stats['sustained_tok_s']:.1f} tok/s "
              f"makespan={makespan:.2f}s")
        print(f"  host={stats['host_ms']:.0f}ms device={stats['device_ms']:.0f}ms "
              f"peak-in-flight={stats['peak_tokens_in_flight']} tokens")
        print(f"  jit cache sizes (must stay 1): {stats['jit_cache_sizes']}")
    return stats


def _build_group(archs, seed: int) -> ModelGroup:
    """One (model, params) entry per arch name; params seeded per entry."""
    entries = []
    for i, arch in enumerate(archs):
        cfg = get_config(arch)
        model = Model(cfg, ShardCtx(None))
        entries.append((arch, model,
                        model.init(jax.random.PRNGKey(seed + i))))
    return ModelGroup(entries)


def serve_multi_poisson(archs, *, rate: float = 4.0, n_requests: int = 32,
                        slots: int = 4, prompt_len: int = 16,
                        max_new: int = 32, threshold: float = 0.5,
                        prefill_chunk: int = 16, long_mode: bool = False,
                        async_decode: bool = False,
                        readback_interval: int = 8,
                        seed: int = 0, quiet: bool = False):
    """Open-loop Poisson trace through ONE multi-model pool: requests are
    assigned round-robin across ``archs`` and the ``MultiModelScheduler``
    multiplexes every model's arena in the same poll loop.  Returns a stats
    dict with per-model breakdowns and the flattened per-model jit cache
    sizes (the <=1-per-stage-per-model no-recompile invariant)."""
    group = _build_group(archs, seed)
    sched = MultiModelScheduler(
        group,
        SchedulerConfig(n_slots=slots, max_len=prompt_len + max_new,
                        prefill_chunk=min(prefill_chunk, max(1, prompt_len)),
                        exit_threshold=threshold, long_mode=long_mode,
                        segmented=not async_decode,
                        async_decode=async_decode,
                        readback_interval=readback_interval))

    rs = np.random.RandomState(seed)
    arrivals, lengths = _poisson_trace(rs, rate, n_requests, prompt_len)

    def _frames(cfg):
        if cfg.family != "encdec":
            return None
        return 0.02 * rs.randn(cfg.encdec.encoder_seq_len,
                               cfg.d_model).astype(np.float32)

    reqs = []
    for i, l in enumerate(lengths):
        arch = archs[i % len(archs)]
        cfg = get_config(arch)
        reqs.append(Request(tokens=rs.randint(0, cfg.vocab_size, int(l)),
                            max_new=max_new, model=arch,
                            frames=_frames(cfg)))

    # warm up each arena's compiles outside the timed trace
    for arch in archs:
        cfg = get_config(arch)
        sched.submit(Request(tokens=rs.randint(0, cfg.vocab_size,
                                               int(lengths[0])),
                             max_new=1, model=arch, frames=_frames(cfg)))
    sched.run()
    sched.reset_stats()

    t0, makespan = _drive_open_loop(sched, reqs, arrivals)
    lat = np.asarray([r.t_done - (t0 + arrivals[j])
                      for j, r in enumerate(reqs)])
    total_tokens = sum(len(r.out_tokens) for r in reqs)
    per_model = {}
    for arch in archs:
        ml = [lat[j] for j, r in enumerate(reqs) if r.model == arch]
        per_model[arch] = {
            "requests": len(ml),
            "tokens": sched.pools[arch].tokens_served,
            "p50_latency_s": _pctl(ml, 50),
            "p95_latency_s": _pctl(ml, 95),
        }
    stats = {
        "requests": n_requests,
        "models": per_model,
        "slots": slots,
        "rate_req_s": rate,
        "makespan_s": makespan,
        "p50_latency_s": _pctl(lat, 50),
        "p95_latency_s": _pctl(lat, 95),
        "sustained_tok_s": total_tokens / makespan,
        "tokens": total_tokens,
        "async_decode": async_decode,
        "host_ms": sched.host_ms_total,
        "device_ms": sched.device_ms_total,
        "peak_tokens_in_flight": sched.peak_tokens_in_flight,
        "jit_cache_sizes": sched.jit_cache_sizes(),
    }
    if not quiet:
        print(f"multi-model poisson models={','.join(archs)} rate={rate}/s "
              f"requests={n_requests} slots={slots}/model"
              + (f" async(r={readback_interval})" if async_decode else ""))
        print(f"  p50={stats['p50_latency_s']*1e3:.0f}ms "
              f"p95={stats['p95_latency_s']*1e3:.0f}ms "
              f"sustained={stats['sustained_tok_s']:.1f} tok/s "
              f"makespan={makespan:.2f}s")
        for arch, ms in per_model.items():
            print(f"  {arch:24s} requests={ms['requests']:3d} "
                  f"tokens={ms['tokens']:4d} "
                  f"p95={ms['p95_latency_s']*1e3:.0f}ms")
        print(f"  jit cache sizes (must stay 1 per stage per model): "
              f"{stats['jit_cache_sizes']}")
    return stats


def serve_multi_tiered_poisson(archs, *, rate: float = 4.0,
                               n_requests: int = 32, base_slots: int = 8,
                               prompt_len: int = 16, max_new: int = 32,
                               threshold: float = 0.5,
                               prefill_chunk: int = 16,
                               scenario: str = "default",
                               deadline: float = 0.0,
                               long_mode: bool = False, seed: int = 0,
                               spec_draft: str = "", spec_k: int = 4,
                               async_decode: bool = False,
                               readback_interval: int = 8,
                               quiet: bool = False):
    """Multi-model Poisson trace through the tiered cluster: each request is
    routed per (model, request) using that model's cost graphs (plan config
    = the arch with ``-smoke`` stripped), so heavy and light models can land
    on different tiers within the same trace.

    ``spec_draft`` names a group entry to use as a device-tier draft model:
    the router then also prices a cross-tier *speculative* candidate
    (device drafts k tokens per round, cloud batch-verifies, one uplink of
    k token ids + one downlink of the accept length per round instead of
    one RTT per token), and requests routed speculative execute through a
    device/cloud ``SpecPair`` bridge.  Speculative decode forces greedy
    sampling, so the cluster rejects temperature > 0 at config time."""
    group = _build_group(archs, seed)
    plan_cfgs = {arch: get_config(arch[:-6] if arch.endswith("-smoke")
                                  else arch)
                 for arch in archs}
    cluster = TieredServingCluster(
        group, scenario=SCENARIOS[scenario](), plan_cfg=plan_cfgs,
        cfg=ClusterConfig(base_slots=base_slots,
                          max_len=prompt_len + max_new,
                          prefill_chunk=min(prefill_chunk,
                                            max(1, prompt_len)),
                          exit_threshold=threshold, long_mode=long_mode,
                          spec_draft=spec_draft, spec_k=spec_k,
                          async_decode=async_decode,
                          readback_interval=readback_interval))
    rs = np.random.RandomState(seed)
    arrivals, lengths = _poisson_trace(rs, rate, n_requests, prompt_len)
    for i, (arr, l) in enumerate(zip(arrivals, lengths)):
        arch = archs[i % len(archs)]
        cluster.submit(rs.randint(0, get_config(arch).vocab_size, int(l)),
                       max_new=max_new, arrival=float(arr),
                       deadline=deadline or None, model=arch)
    t0 = time.time()
    cluster.run()
    wall = time.time() - t0
    stats = cluster.stats()
    stats["wall_s"] = wall
    if not quiet:
        print(f"multi-model tiered poisson models={','.join(archs)} "
              f"scenario={scenario} rate={rate}/s requests={n_requests}")
        print(f"  routed: {stats['route_counts']} splits={stats['splits']} "
              f"deadline-hit={stats['deadline_hit_rate']:.2f}")
        print(f"  virtual p50={stats['p50_latency_s']*1e3:.0f}ms "
              f"p95={stats['p95_latency_s']*1e3:.0f}ms (wall {wall:.2f}s)")
        for arch, ms in stats["models"].items():
            print(f"  {arch:24s} routed={ms['routed']:3d} "
                  f"{ms['route_counts']} tokens={ms['tokens']}")
        for name, ts in stats["tiers"].items():
            print(f"  {name:6s} slots={ts['n_slots']} "
                  f"routed={ts['routed']:3d} util={ts['utilization']:.2f} "
                  f"p95={ts['p95_latency_s']*1e3:.0f}ms"
                  + (" DEAD" if ts.get("dead") else ""))
        sp = stats.get("speculative")
        if sp is not None:
            print(f"  speculative: draft={sp['draft']} k={sp['k']} "
                  f"rounds={sp['rounds']} "
                  f"acceptance={sp['acceptance_len']:.2f} "
                  f"requests={sp['requests_completed']} "
                  f"p50={sp['p50_latency_s']*1e3:.0f}ms "
                  f"speedup={sp['mean_speedup_x']:.2f}x")
        _print_migration(stats)
    return stats


def serve_tiered_poisson(arch: str, *, rate: float = 4.0,
                         n_requests: int = 32, base_slots: int = 8,
                         prompt_len: int = 16, max_new: int = 32,
                         threshold: float = 0.5, prefill_chunk: int = 16,
                         scenario: str = "default", plan_arch: str = "",
                         deadline: float = 0.0, long_mode: bool = False,
                         async_decode: bool = False,
                         readback_interval: int = 8,
                         seed: int = 0, params=None, quiet: bool = False):
    """Poisson trace through the tiered cluster: the admission router sends
    each arrival to a cloud/edge/device pool (or a prefill/decode split)
    using the paradigm planners; arrivals and the reported latencies live on
    the tiers' virtual clocks (scenario time), while token generation is
    real execution.  Returns the cluster's stats dict."""
    cfg = get_config(arch)
    model = Model(cfg, ShardCtx(None))
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    plan_cfg = get_config(plan_arch) if plan_arch else \
        get_config(arch[:-6] if arch.endswith("-smoke") else arch)
    cluster = TieredServingCluster(
        model, params, SCENARIOS[scenario](), plan_cfg=plan_cfg,
        cfg=ClusterConfig(base_slots=base_slots,
                          max_len=prompt_len + max_new,
                          prefill_chunk=min(prefill_chunk,
                                            max(1, prompt_len)),
                          exit_threshold=threshold, long_mode=long_mode,
                          async_decode=async_decode,
                          readback_interval=readback_interval))
    rs = np.random.RandomState(seed)
    arrivals, lengths = _poisson_trace(rs, rate, n_requests, prompt_len)
    for arr, l in zip(arrivals, lengths):
        cluster.submit(rs.randint(0, cfg.vocab_size, int(l)),
                       max_new=max_new, arrival=float(arr),
                       deadline=deadline or None)
    t0 = time.time()
    cluster.run()
    wall = time.time() - t0
    stats = cluster.stats()
    stats["wall_s"] = wall
    if not quiet:
        print(f"arch={cfg.name} tiered poisson scenario={scenario} "
              f"rate={rate}/s requests={n_requests} (plan={plan_cfg.name})")
        print(f"  routed: {stats['route_counts']} splits={stats['splits']} "
              f"deadline-hit={stats['deadline_hit_rate']:.2f}")
        print(f"  virtual p50={stats['p50_latency_s']*1e3:.0f}ms "
              f"p95={stats['p95_latency_s']*1e3:.0f}ms (wall {wall:.2f}s)")
        for name, ts in stats["tiers"].items():
            print(f"  {name:6s} slots={ts['n_slots']} routed={ts['routed']:3d} "
                  f"util={ts['utilization']:.2f} "
                  f"occupancy={ts['slot_occupancy']:.2f} "
                  f"depth={ts['measured_depth']:.2f} "
                  f"p95={ts['p95_latency_s']*1e3:.0f}ms"
                  + (" DEAD" if ts.get("dead") else ""))
        _print_migration(stats)
        print(f"  jit cache sizes (must stay 1 per pool): "
              f"{stats['jit_cache_sizes']}")
    return stats


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--models", default="",
                    help="comma-separated archs for a multi-model pool "
                         "(poisson mode; overrides --arch)")
    ap.add_argument("--mode", default="batch", choices=["batch", "poisson"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--threshold", type=float, default=0.5)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--rate", type=float, default=4.0)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--tiered", action="store_true")
    ap.add_argument("--scenario", default="default", choices=sorted(SCENARIOS))
    ap.add_argument("--plan-arch", default="")
    ap.add_argument("--deadline", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--long", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV arena + radix prefix cache "
                         "(poisson single-pool mode)")
    ap.add_argument("--async-decode", action="store_true",
                    help="overlapped decode pipeline: on-device sampling "
                         "ring, double-buffered window dispatch, batched "
                         "readback every --readback-interval steps")
    ap.add_argument("--readback-interval", type=int, default=8,
                    help="[async] decode steps per batched host readback")
    ap.add_argument("--spec-draft", default="",
                    help="[tiered multi-model] group entry to use as the "
                         "device-tier speculative draft model")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="[tiered multi-model] draft tokens per "
                         "speculative round")
    args = ap.parse_args()
    assert args.arch or args.models, "need --arch or --models"
    if args.models:
        assert args.mode == "poisson", "--models needs --mode poisson"
        archs = [a.strip() for a in args.models.split(",") if a.strip()]
        if args.spec_draft:
            assert args.tiered and args.spec_draft in archs, \
                "--spec-draft needs --tiered and must name a --models entry"
        if args.tiered:
            serve_multi_tiered_poisson(
                archs, rate=args.rate, n_requests=args.requests,
                base_slots=args.slots, prompt_len=args.prompt_len,
                max_new=args.max_new, threshold=args.threshold,
                prefill_chunk=args.prefill_chunk, scenario=args.scenario,
                deadline=args.deadline, long_mode=args.long, seed=args.seed,
                spec_draft=args.spec_draft, spec_k=args.spec_k,
                async_decode=args.async_decode,
                readback_interval=args.readback_interval)
        else:
            serve_multi_poisson(
                archs, rate=args.rate, n_requests=args.requests,
                slots=args.slots, prompt_len=args.prompt_len,
                max_new=args.max_new, threshold=args.threshold,
                prefill_chunk=args.prefill_chunk, long_mode=args.long,
                async_decode=args.async_decode,
                readback_interval=args.readback_interval, seed=args.seed)
    elif args.mode == "poisson" and args.tiered:
        serve_tiered_poisson(
            args.arch, rate=args.rate, n_requests=args.requests,
            base_slots=args.slots, prompt_len=args.prompt_len,
            max_new=args.max_new, threshold=args.threshold,
            prefill_chunk=args.prefill_chunk, scenario=args.scenario,
            plan_arch=args.plan_arch, deadline=args.deadline,
            long_mode=args.long, async_decode=args.async_decode,
            readback_interval=args.readback_interval, seed=args.seed)
    elif args.mode == "poisson":
        serve_poisson(args.arch, rate=args.rate, n_requests=args.requests,
                      slots=args.slots, prompt_len=args.prompt_len,
                      max_new=args.max_new, threshold=args.threshold,
                      prefill_chunk=args.prefill_chunk, long_mode=args.long,
                      paged=args.paged, async_decode=args.async_decode,
                      readback_interval=args.readback_interval,
                      seed=args.seed)
    else:
        serve(args.arch, args.batch, args.prompt_len, args.max_new,
              threshold=args.threshold, long_mode=args.long, seed=args.seed)


if __name__ == "__main__":
    main()
