"""Profile one (arch, shape, mesh): roofline terms + top byte/collective ops.

    PYTHONPATH=src python -m repro.launch.profile_pair granite-3-2b train_4k single

Compiles on 512 placeholder CPU devices, like ``launch/dryrun.py``: the CPU
platform is pinned before jax is imported, so the chip stays free.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import sys

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def main():
    arch, shape, mesh_name = sys.argv[1:4]
    strategy = sys.argv[4] if len(sys.argv) > 4 else "tp"
    variant = sys.argv[5] if len(sys.argv) > 5 else ""
    from repro.launch import dryrun
    from repro.launch.hlo_cost import analyze
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    spec = dryrun.input_specs(arch, shape, mesh, strategy=strategy,
                              variant=variant)
    ns = lambda s: jax.tree.map(lambda sp: NamedSharding(mesh, sp), s,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        compiled = jax.jit(spec["fn"], in_shardings=ns(spec["in_specs"]),
                           out_shardings=ns(spec["out_specs"])
                           ).lower(*spec["args"]).compile()
    c = analyze(compiled.as_text())
    print(f"flops={c.flops:.3e} bytes={c.bytes:.3e} "
          f"coll={ {k: f'{v:.2e}' for k, v in c.collective.items()} }")
    print("\n== top byte ops ==")
    for label, (b, cb) in c.top_bytes(20):
        print(f"  {b:12.3e} B  {label[:150]}")
    print("\n== top collective ops ==")
    for label, (b, cb) in c.top_collective(20):
        print(f"  {cb:12.3e} B  {label[:150]}")


if __name__ == "__main__":
    main()
