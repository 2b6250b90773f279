"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against placeholder devices; record memory/cost analysis and
roofline terms.

MUST be run as a script / fresh process (the environment lines below
execute before any jax import, giving 512 host CPU devices).  The CPU
platform is pinned so that on a machine with an accelerator the dry run
neither takes the chip from another process nor builds its production
meshes over the real devices.  Results land in
experiments/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all            # everything
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time
import traceback
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import (Roofline, collective_bytes_from_hlo,
                                   model_flops_for)
from repro.models import Model, ShardCtx
from repro.sharding.specs import ShardingRules
from repro.training import OptimizerConfig, TrainConfig, make_train_step
from repro.serving.engine import make_serve_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def batch_shapes(cfg, shape) -> Dict[str, jax.ShapeDtypeStruct]:
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    batch = {
        "tokens": sds((b, s), jnp.int32),
        "labels": sds((b, s), jnp.int32),
        "loss_mask": sds((b, s), jnp.float32),
    }
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = sds((b, cfg.frontend_tokens, cfg.d_model),
                                    jnp.bfloat16)
    if cfg.frontend == "audio_frames":
        batch["frames"] = sds((b, cfg.encdec.encoder_seq_len, cfg.d_model),
                              jnp.bfloat16)
    return batch


def input_specs(arch: str, shape_name: str, mesh,
                strategy: str = "tp", variant: str = "") -> Dict[str, Any]:
    """ShapeDtypeStructs + shardings for the step the shape lowers.

    variant "w8a8": serving params carry int8 expert weights
    (ffn.quantize_model_moe) — beyond-paper serving profile.
    """
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    long_mode = shape_name == "long_500k"
    model = Model(cfg, ShardCtx(mesh), remat=(shape.kind == "train"))
    rules = ShardingRules(mesh, strategy=strategy)

    params_sh = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if "w8a8" in variant and shape.kind == "decode":
        from repro.models.ffn import quantize_model_moe
        params_sh = jax.eval_shape(quantize_model_moe, params_sh)
    pspecs = rules.params_specs(params_sh)

    if shape.kind == "train":
        from repro.training.optimizer import init_optimizer
        opt_sh = jax.eval_shape(init_optimizer, params_sh)
        ospecs = rules.opt_specs(opt_sh, params_sh)
        batch_sh = batch_shapes(cfg, shape)
        bspecs = rules.batch_specs(batch_sh)
        rng_sh = jax.ShapeDtypeStruct((2,), jnp.uint32)
        step_fn = make_train_step(model, OptimizerConfig(),
                                  TrainConfig(microbatches=1))
        args = (params_sh, opt_sh, batch_sh, rng_sh)
        in_specs = (pspecs, ospecs, bspecs, P())
        out_specs = (pspecs, ospecs,
                     jax.tree.map(lambda _: P(),
                                  jax.eval_shape(step_fn, params_sh, opt_sh,
                                                 batch_sh, rng_sh)[2]))
        return dict(model=model, cfg=cfg, shape=shape, fn=step_fn, args=args,
                    in_specs=in_specs, out_specs=out_specs, kind="train")

    if shape.kind == "prefill":
        batch_sh = batch_shapes(cfg, shape)
        bspecs = rules.batch_specs(batch_sh)

        def prefill_step(params, batch):
            return model.forward(params, batch, long_mode=long_mode).logits

        args = (params_sh, batch_sh)
        in_specs = (pspecs, bspecs)
        data_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        vspec = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
        out_specs = P(data_ax if len(data_ax) > 1 else (data_ax[0] if data_ax else None),
                      None, vspec)
        return dict(model=model, cfg=cfg, shape=shape, fn=prefill_step,
                    args=args, in_specs=in_specs, out_specs=out_specs,
                    kind="prefill")

    # decode
    cache_len = model.cache_len_for(shape.seq_len, long_mode)
    cache_sh = jax.eval_shape(
        lambda: model.init_decode_cache(shape.global_batch, cache_len,
                                        long_mode=long_mode))
    cspecs = rules.cache_specs(cache_sh)
    toks_sh = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_sh = jax.ShapeDtypeStruct((), jnp.int32)
    serve = make_serve_step(model, long_mode=long_mode)
    args = (params_sh, cache_sh, toks_sh, pos_sh)
    data_ax = "data" if "data" in mesh.axis_names else None
    tspec = (P(data_ax, None)
             if data_ax and shape.global_batch % mesh.shape["data"] == 0
             else P(None, None))
    vspec = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    bspec = (data_ax if data_ax and
             shape.global_batch % mesh.shape["data"] == 0 else None)
    in_specs = (pspecs, cspecs, tspec, P())
    out_specs = (P(bspec, vspec), P(), cspecs)
    return dict(model=model, cfg=cfg, shape=shape, fn=serve, args=args,
                in_specs=in_specs, out_specs=out_specs, kind="decode")


# ---------------------------------------------------------------------------
# Dry-run one combination
# ---------------------------------------------------------------------------

def dryrun_one(arch: str, shape_name: str, mesh_name: str,
               save: bool = True, strategy: str = "tp",
               variant: str = "") -> Dict[str, Any]:
    cfg = get_config(arch)
    if not shape_applicable(cfg, shape_name):
        res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped",
               "reason": "long_500k skipped: pure full-attention arch "
                         "(DESIGN.md §3)"}
        if save:
            _save(res)
        return res

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    t0 = time.time()
    spec = input_specs(arch, shape_name, mesh, strategy=strategy,
                       variant=variant)
    ns = lambda s: jax.tree.map(lambda sp: NamedSharding(mesh, sp), s,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        jitted = jax.jit(spec["fn"], in_shardings=ns(spec["in_specs"]),
                         out_shardings=ns(spec["out_specs"]))
        lowered = jitted.lower(*spec["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    from repro.launch.hlo_cost import analyze
    hc = analyze(hlo)               # trip-count-scaled per-device costs
    chips = mesh.size
    rl = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=hc.flops,
        hlo_bytes=hc.bytes,
        collective=hc.collective,
        model_flops=model_flops_for(cfg, spec["shape"], spec["kind"]),
        peak_bytes_per_device=(mem_d.get("temp_size") or 0)
        if isinstance(mem_d.get("temp_size"), (int, float)) else None,
    )
    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "kind": spec["kind"], "chips": chips,
        "strategy": strategy, "variant": variant,
        "attn_impl": os.environ.get("REPRO_ATTN", "dense"),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "memory_analysis": mem_d,
        "roofline": rl.to_dict(),
        "hlo_bytes_len": len(hlo),
    }
    if save:
        _save(res)
    return res


def _save(res):
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = ""
    if res.get("strategy", "tp") != "tp" or res.get("variant") \
            or res.get("attn_impl", "dense") != "dense":
        tag = ("__" + "-".join(filter(None, [
            res.get("strategy") if res.get("strategy") != "tp" else "",
            res.get("variant", ""),
            res.get("attn_impl") if res.get("attn_impl") != "dense" else "",
        ])))
    fn = os.path.join(OUT_DIR,
                      f"{res['arch']}__{res['shape']}__{res['mesh']}{tag}.json")
    with open(fn, "w") as f:
        json.dump(res, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--strategy", default="tp", choices=["tp", "dp_zero"])
    ap.add_argument("--variant", default="")
    args = ap.parse_args()

    combos = []
    if args.all:
        for a in ARCHS:
            for s in INPUT_SHAPES:
                for m in ("single", "multi"):
                    combos.append((a, s, m))
    else:
        combos.append((args.arch, args.shape, args.mesh))

    for a, s, m in combos:
        fn = os.path.join(OUT_DIR, f"{a}__{s}__{m}.json")
        if args.skip_existing and os.path.exists(fn):
            print(f"skip {a} {s} {m} (exists)")
            continue
        t0 = time.time()
        try:
            res = dryrun_one(a, s, m, strategy=args.strategy,
                             variant=args.variant)
            status = res["status"]
            extra = ""
            if status == "ok":
                r = res["roofline"]
                extra = (f"flops={r['hlo_flops']:.3e} bytes={r['hlo_bytes']:.3e} "
                         f"coll={r['collective_bytes']:.3e} bottleneck={r['bottleneck']}")
            print(f"[{time.time()-t0:7.1f}s] {a:26s} {s:12s} {m:6s} {status} {extra}",
                  flush=True)
        except Exception as e:
            print(f"[{time.time()-t0:7.1f}s] {a:26s} {s:12s} {m:6s} FAIL "
                  f"{type(e).__name__}: {str(e)[:200]}", flush=True)
            _save({"arch": a, "shape": s, "mesh": m, "status": "fail",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]})


if __name__ == "__main__":
    main()
