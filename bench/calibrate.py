"""Readings behind a cell's limits, on the chip.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--controls int8,fp8]

In one process, for each seed: the cell's weights and traffic, a window
of ``--seconds`` through the same scheduler (built and warmed once, its
weights swapped per seed), the same sample of served requests a run takes,
and then

* the program's numbers, as a run compares them (``check.py``): the
  widest and the mean gap of a served token's logit below the float32
  reference's best, and the mean gap of the compiled exit probes'
  entropies from the reference's;
* each control's numbers: the reference computed in a lower precision and
  put in the program's place, at the same prompts, tokens and probe rows;
  judged by the cell's limits as a run is judged, it has to come out not
  correct.

Prints one JSON line per seed and a summary: per number, the lower reading
(the largest program reading) and each control's upper reading (its
smallest), and whether any control was judged correct on any seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

NUMBERS = ("logit_gap", "logit_gap_mean", "exit_entropy_gap")


def readings(b, log, spec, seed, controls):
    import check

    m, params, cell = b["model"], b["sched"].params, spec["cell"]
    reference = check.load_reference(spec["config"])
    sel = check.select(m, log, spec["traffic"], seed, b["max_len"])
    if not sel["ids"]:
        return {"seed": seed, "requests": 0}
    stages = b["sched"].audit_stages()
    probes = [stages[f"probe{e}"] for e in range(len(m["exit_layers"]))]
    t = time.perf_counter()
    prog, rp = check.program_numbers(reference, m, params, sel, seed, probes)
    out = {"seed": seed, "requests": len(sel["ids"]),
           "tokens": int(sel["mask"].sum()),
           "check_s": time.perf_counter() - t, "program": prog,
           "program_correct": check.correct(check.judge(
               dict(prog, wrong_lengths=sel["wrong_lengths"]),
               cell["limits"]))}
    for q in controls:
        nums = check.control_numbers(reference, m, params, sel, rp, q)
        out[q] = nums
        out[q + "_correct"] = check.correct(check.judge(
            dict(nums, wrong_lengths=sel["wrong_lengths"]), cell["limits"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int8,fp8")
    a = ap.parse_args(argv)
    import importlib
    import harness
    import weights

    spec = harness.load_spec(a.workload)
    harness.require_chips(spec["workload"]["chips"])
    harness.use_compile_cache()
    seeds = [int(x) for x in a.seeds.split(",")]
    controls = [q for q in a.controls.split(",") if q]
    b = harness.build(spec, seeds[0], a.seconds)
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    gen = importlib.import_module("gen." + spec["traffic"]["generator"])
    vocab = b["model"]["vocab_size"]
    rows = []
    params = b.pop("params")
    for n, seed in enumerate(seeds):
        if n:       # one seed's weights on the chip at a time
            b["sched"].params = params = None
            params = weights.make(b["shapes"], seed,
                                  spec["config"].get("weight_rules"))
        harness.reset(b["sched"], params)
        reqs = gen.generate(spec["traffic"], spec["cell"], seed, vocab,
                            a.seconds)
        log = harness.serve(b["sched"], reqs, a.seconds)
        harness.reset(b["sched"], params)
        row = readings(b, log, spec, seed, controls)
        row["window"] = log.summary()
        rows.append(row)
        print(json.dumps(row), flush=True)
    rows = [r for r in rows if r["requests"]]
    summary = {"workload": a.workload, "seeds": len(rows),
               "lower": {k: max(r["program"][k] for r in rows)
                         for k in NUMBERS},
               "upper": {q: {k: min(r[q][k] for r in rows)
                             for k in NUMBERS} for q in controls},
               "program_ever_incorrect": not all(
                   r["program_correct"] for r in rows),
               "control_ever_correct": {
                   q: any(r[q + "_correct"] for r in rows)
                   for q in controls}}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
