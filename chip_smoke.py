"""Smoke run of the serving path on one TPU chip, at published widths.

Serves granite-3-2b (40 layers, d_model 2048, 32/8 heads, vocab 49155,
exit heads after layers 13 and 26) with random weights drawn from
``--seed`` through ``repro.launch.serve.serve_poisson``, the function
behind ``python -m repro.launch.serve --mode poisson``:

* phase A, the default path: depth-segmented decode, so the compiled
  exit-head entropy kernel runs in every decode step.  8 slots, 16
  requests arriving at 32 req/s, prompts of 128-512 tokens, 32 new
  tokens each, prefill in chunks of 128;
* phase B, the same trace through the overlapped decode pipeline
  (``async_decode``, one readback every 8 steps);
* phase C, the compiled exit-head kernel on a batch of real decode
  hiddens against its float32 oracle.

A phase fails unless every request returns exactly ``max_new`` tokens in
``[0, vocab)``, every jitted stage compiled at most once, and the kernel
agrees with the oracle.  The script refuses to run on anything but a TPU.
Its last line of stdout is one JSON object naming the device.

    python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "granite-3-2b"
TRACE = dict(rate=32.0, n_requests=16, slots=8, prompt_len=512, max_new=32,
             threshold=0.5, prefill_chunk=128)
KERNEL_TOL = 5e-2          # bf16 tolerance of tests/test_kernels.py
# XLA compile of every jitted program (a persistent-cache read on a hit)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _check_phase(name: str, stats, vocab: int) -> list:
    outs = stats["out_tokens"]
    n, max_new = TRACE["n_requests"], TRACE["max_new"]
    done = [o for o in outs if len(o) == max_new
            and all(0 <= t < vocab for t in o)]
    exit_frac = 1.0 - stats["exit_stats"]["full_depth_frac"]
    print(f"phase {name}: completed {len(done)}/{n} requests, "
          f"tokens={stats['tokens']}, exit fraction={exit_frac}")
    _require(len(outs) == n and len(done) == n,
             f"phase {name}: a request did not return {max_new} tokens "
             f"in [0, {vocab})")
    sizes = stats["jit_cache_sizes"]
    _require(all(v in (0, 1) for v in sizes.values()),
             f"phase {name}: a stage compiled more than once: {sizes}")
    return outs


def _decode_hiddens(model, params, tokens):
    """Exit-head inputs [B*S, D] of the first exit: each token of
    ``tokens`` [B, S] decoded in turn through the first depth segment,
    attending to the ones before it."""
    from repro.models import blocks

    seg = model.decode_segments[0]
    b, s = tokens.shape
    alive = jnp.ones((b,), bool)

    def body(cache, t):
        x = model.embed_decode_tokens(
            params, jax.lax.dynamic_slice_in_dim(tokens, t, 1, axis=1))
        x, cache = model.decode_segment(params, cache, x, seg, t, alive)
        return cache, x[:, 0]

    _, xs = jax.lax.scan(body, model.init_decode_cache(b, s), jnp.arange(s))
    head = params["exit_heads"][seg.exit_index]
    return blocks.exit_head_hidden(model.cfg, head,
                                   xs.reshape(b * s, model.cfg.d_model))


def run(arch: str, seed: int) -> None:
    """Phases A, B and C of ``arch``; raises SystemExit on any failure.
    Needs ``src`` on ``sys.path``."""
    from repro.configs import get_config
    from repro.kernels import ops, ref
    from repro.launch.serve import serve_poisson
    from repro.models import Model, ShardCtx

    compile_s = 0.0

    def on_event(event, secs, **_):
        nonlocal compile_s
        if event == _COMPILE_EVENT:
            compile_s += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    dev = jax.devices()[0]
    cfg = get_config(arch)
    model = Model(cfg, ShardCtx(None))
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    setup_s = time.perf_counter() - t0
    max_len = TRACE["prompt_len"] + TRACE["max_new"]
    cache = jax.eval_shape(lambda: model.init_decode_cache(TRACE["slots"],
                                                           max_len))
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    print(f"arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} exits={cfg.exits.exit_layers}")
    print(f"parameter bytes={param_bytes} "
          f"kv arena bytes ({TRACE['slots']} slots x {max_len})="
          f"{cache_bytes}")
    print(f"set-up (parameter init) seconds={setup_s}")

    outs_a = _check_phase("A", serve_poisson(
        arch, **TRACE, seed=seed, params=params), cfg.vocab_size)
    outs_b = _check_phase("B", serve_poisson(
        arch, **TRACE, async_decode=True, readback_interval=8, seed=seed,
        params=params), cfg.vocab_size)
    agree = sum(a == b for a, b in zip(outs_a, outs_b))
    print(f"phase A and B greedy tokens agree on {agree}/{len(outs_a)} "
          f"requests (information only)")

    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (8, 16), 0,
                                cfg.vocab_size)
    h = jax.jit(_decode_hiddens, static_argnums=0)(model, params, tokens)
    w = params["exit_heads"][model.decode_segments[0].exit_index]["w"]
    got = np.asarray(ops.exit_head_entropy(h, w))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.exit_head_entropy_ref(h, w))
    diff = np.abs(got - want)
    print(f"phase C: exit-head kernel on {h.shape[0]} decode hiddens "
          f"({h.shape[1]} x {w.shape[1]}): max |kernel - oracle|="
          f"{float(diff.max())} (tolerance {KERNEL_TOL} abs + rel)")
    _require(bool(np.all(np.isfinite(got))) and bool(
        np.all(diff <= KERNEL_TOL + KERNEL_TOL * np.abs(want))),
        "phase C: exit-head kernel differs from its oracle")

    print(f"compile seconds (XLA, all phases)={compile_s}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the trace")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{dev.platform!r} ({dev.device_kind}); not run")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SystemExit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import use_compile_cache

    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}")
    print(f"compile cache: {use_compile_cache()}")
    run(ARCH, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
