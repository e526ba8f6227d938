import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, record memory/cost/collective analysis for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

MUST be the first jax-touching import in the process: the two lines above
create 512 host platform devices so ``jax.make_mesh((2,16,16), ...)`` works
on this CPU-only container. Do NOT set that flag globally — smoke tests and
benchmarks need the real single device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape decode_32k [--multipod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (adapt_for_shape, build_prefill_step,
                                build_serve_step, build_train_step,
                                resolve_drafter)
from repro.sharding.utils import mesh_scope


def flatten_shardings(args: dict, extras: dict, shardings: dict,
                      ex_sh: dict, order):
    arg_vals = [args[k] for k in order]
    shd_vals = [shardings[k] for k in order]
    if extras is not None:
        arg_vals.append(extras)
        shd_vals.append(ex_sh)
    return tuple(arg_vals), tuple(shd_vals)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            *, k_infer: int = 5, n_micro: int = 8,
            variant: str = "baseline") -> dict:
    t0 = time.time()
    shape = INPUT_SHAPES[shape_name]
    tcfg = adapt_for_shape(get_config(arch), shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "variant": variant}
    if tcfg is None:
        rec["status"] = "skip"
        rec["reason"] = get_config(arch).long_context
        return rec

    # "optimized" (§Perf): drafter block remat + flash custom-VJP attention
    # + last-position prefill head + p-cast attention (the latter three are
    # code-level fixes measured against the archived baseline results).
    dcfg = resolve_drafter(tcfg, remat=(variant == "optimized"))
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    if shape.kind == "train":
        fn, make_inputs = build_train_step(tcfg, dcfg, shape_name,
                                           n_micro=n_micro)
        order = ["tparams", "dparams", "opt_state", "tokens", "pos",
                 "depth", "labels", "rng"]
        donate = (1, 2)
    elif shape.kind == "prefill":
        fn, make_inputs = build_prefill_step(tcfg, shape_name)
        order = ["tparams", "tokens", "cache"]
        donate = (2,)
    else:
        fn, make_inputs = build_serve_step(tcfg, dcfg, shape_name, K=k_infer)
        order = ["tparams", "dparams", "state"]
        donate = (2,)

    args, extras, shardings, ex_sh = make_inputs(mesh)
    has_extras = shape.kind in ("train", "prefill")
    arg_vals, shd_vals = flatten_shardings(
        args, extras if has_extras else None, shardings,
        ex_sh if has_extras else None, order)

    with mesh_scope(mesh):
        jitted = jax.jit(fn, in_shardings=shd_vals, donate_argnums=donate)
        lowered = jitted.lower(*arg_vals)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()

    coll = RL.collective_stats(hlo)
    model_flops = RL.model_flops_estimate(tcfg, shape, dcfg, k_infer)
    terms = RL.roofline_terms(cost or {}, coll, n_chips,
                              model_flops=model_flops)
    rec.update(
        status="ok",
        compile_s=round(time.time() - t0, 1),
        n_chips=n_chips,
        memory=dict(
            argument_bytes=getattr(mem, "argument_size_in_bytes", None),
            output_bytes=getattr(mem, "output_size_in_bytes", None),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            code_bytes=getattr(mem, "generated_code_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
        ),
        collectives=coll,
        roofline=terms,
    )
    # fits-in-HBM check: args + temp − aliased, against 16 GB v5e
    try:
        live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        rec["memory"]["live_bytes"] = int(live)
        rec["memory"]["fits_16GB"] = bool(live < 16e9)
    except Exception:
        pass
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--n-micro", type=int, default=8)
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "optimized"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multipod]

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                out_fn = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_fn):
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    rec = run_one(arch, shape, mp, k_infer=args.k,
                                  n_micro=args.n_micro, variant=args.variant)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                with open(out_fn, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"  -> {rec['status']}"
                      + (f" ({rec.get('compile_s')}s, "
                         f"bottleneck={rec['roofline']['bottleneck']})"
                         if rec.get("status") == "ok" else
                         f" {rec.get('error', rec.get('reason', ''))}"),
                      flush=True)


if __name__ == "__main__":
    main()
