"""Where a cell's polls spend their host time, from the scheduler's spans.

    python bench/phases.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up and serves the cell's window as ``bench/run.py`` does, without
the reference check, and prints one JSON object as its last line: set-up,
output tokens/s and the host-clock length of the decode-only polls in the
first ``trace_seconds`` of the window.  With ``--trace 1`` the profiler
covers that part, and the line adds the cell's per-layer metrics, the
metrics read from the scheduler's spans (``SPAN_METRICS``), the longest
device idle gaps named by the innermost span open over them, and the
window's device idle by span, per decode step.  Refuses any platform but
a TPU, as ``bench/run.py`` does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SPAN_METRICS = ["admission_ms", "decode_host_ms", "syncs_per_step"]


def run(spec, seed: int, seconds: float, trace: bool, t_start: float, *,
        smoke: bool = False):
    import jax
    import check
    import harness
    import spans

    b = harness.build(spec, seed, seconds, smoke=smoke)
    setup_s = time.perf_counter() - t_start
    head = spec["traffic"]["trace_seconds"]
    hooks = {}
    if trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        hooks = dict(
            trace_start=lambda: jax.profiler.start_trace(harness.TRACE_DIR),
            trace_stop=jax.profiler.stop_trace, trace_seconds=head)
    log = harness.serve(b["sched"], b["reqs"], seconds, **hooks)
    dev = jax.devices()[0]
    ctx = harness.Context(log=log, setup_s=setup_s, model=b["model"],
                          cell=spec["cell"], n_slots=spec["cell"]["n_slots"],
                          peaks=harness._peaks(dev),
                          reference=check.load_reference(spec["config"]))
    polls = [(p.end - p.start) * 1e3 for p in log.polls
             if p.decode_stepped and not p.prefill_chunks
             and p.start < log.t0 + head]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "setup_s": setup_s,
           "output_tok_per_s":
               harness.load_reader("output_tok_per_s").read(ctx),
           "decode_polls": len(polls),
           "decode_poll_ms_mean": statistics.fmean(polls) if polls else None,
           "decode_poll_ms_median":
               statistics.median(polls) if polls else None}
    if trace:
        ctx.trace = spans.load(harness.TRACE_DIR, dev.id)
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        names = [m["name"] for m in spec["per_layer"]] + SPAN_METRICS
        out["metrics"] = {n: harness.load_reader(n).read(ctx) for n in names}
        steps = len(spans.named(ctx.trace, "sched.step"))
        out["steps"] = steps
        out["idle_gaps"] = spans.idle_gaps(ctx.trace)
        out["idle_ms_per_step"] = {
            k: v * 1e3 / steps for k, v in sorted(
                spans.idle_by_span(ctx.trace).items(), key=lambda kv: -kv[1])
        } if steps else {}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    import harness
    spec = harness.load_spec(a.workload)
    harness.require_chips(spec["workload"]["chips"])
    harness.use_compile_cache()
    print(json.dumps(run(spec, a.seed, a.seconds, bool(a.trace), T_START)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
