"""Compile every cell's serving stages at full width for a described TPU
v5e (no chip needed) and print each program's memory.

    JAX_PLATFORMS=cpu python bench/compile_check.py [workload ...]

For each cell it builds the scheduler as ``harness.run`` does, on abstract
parameters, and compiles each stage of ``audit_stages()`` that the window
drives (init, merge, prefill chunk, segments, probes, finalize) for one
chip of a described v5e:2x2, with the Pallas kernels on their Mosaic path.
What the chip's compiler refuses (a shape, a kernel's fast memory, a
program over the chip's memory) shows here, at no chip time.  The
estimate of a cell's peak is parameters + the pool's and the pending
prefill's arenas + the largest stage's temporaries.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

STAGES = ("init_cache", "fresh_last", "merge", "prefill", "segment",
          "probe", "finalize")


def check(workload: str, one_chip) -> None:
    import jax
    from repro.models import Model, ShardCtx
    from repro.serving.scheduler import (ContinuousBatchScheduler,
                                         SchedulerConfig)
    import harness
    from gen import open_loop

    spec = harness.load_spec(workload)
    c, tr, cell = spec["config"], spec["traffic"], spec["cell"]
    model = Model(harness.model_config(c), ShardCtx(None))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    max_len = open_loop.max_len(tr["prompt_len"]) \
        + open_loop.max_len(tr["output_len"])
    sched = ContinuousBatchScheduler(model, params, SchedulerConfig(
        n_slots=cell["n_slots"], max_len=max_len, **c["scheduler"]))
    p_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    arena = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(jax.eval_shape(sched._init_cache)))
    worst = 0
    for name, st in sched.audit_stages().items():
        if not name.startswith(STAGES):
            continue
        args = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), st.args)
        exe = st.fn.lower(*args).compile()
        ma = exe.memory_analysis()
        kernel = "tpu_custom_call" in exe.as_text()
        worst = max(worst, ma.temp_size_in_bytes)
        print(f"  {workload} {name}: temp {ma.temp_size_in_bytes} "
              f"args {ma.argument_size_in_bytes} out "
              f"{ma.output_size_in_bytes}" + (" kernel" if kernel else ""))
    est = p_bytes + 2 * arena + worst
    print(f"{workload}: {cell['n_slots']} slots x {max_len} rows; "
          f"parameters {p_bytes}, arena {arena} (x2 with the pending "
          f"prefill), largest temporaries {worst}: about {est} bytes")


def main(argv) -> None:
    import json

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import backend

    jax.config.update("jax_enable_compilation_cache", False)
    backend.off_tpu = lambda: False     # compile the kernels for Mosaic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        names = argv or [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        check(name, one_chip)


if __name__ == "__main__":
    main(sys.argv[1:])
