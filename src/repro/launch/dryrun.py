import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# -- the two lines above MUST run before any jax import (device count is
#    locked at first init). Tests may override the count via env:
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])
# the dry-run never EXECUTES the compiled module -> skip expensive LLVM
# codegen passes (measured 1.7x faster compiles, identical cost analysis)
os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on
the production meshes, record memory/cost/collective analyses.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Artifacts land in artifacts/dryrun/<mesh>/<arch>__<shape>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import all_cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import Roofline, parse_collectives
from repro.launch.steps import build_cell, model_flops

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _mesh_for(name: str):
    if name == "single":
        return make_production_mesh(multi_pod=False)
    if name == "multi":
        return make_production_mesh(multi_pod=True)
    if name == "tiny":  # CI-scale stand-in
        return jax.make_mesh((2, 4), ("data", "model"))
    if name == "tinymulti":
        return jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    raise KeyError(name)


def run_cell(arch: str, shape: str, mesh_name: str, opts=None,
             save: bool = True, hlo_out: str = None) -> dict:
    # unrolled layers by default: exact per-layer cost accounting
    opts = {"unroll": True, **(opts or {})}
    mesh = _mesh_for(mesh_name)
    n_chips = mesh.devices.size
    t0 = time.time()
    plan = build_cell(arch, shape, mesh, **opts)
    # set_mesh so in-model with_sharding_constraint(PartitionSpec) calls
    # resolve
    with jax.sharding.set_mesh(mesh):
        jfn = jax.jit(plan.fn, in_shardings=plan.in_shardings,
                      out_shardings=plan.out_shardings,
                      donate_argnums=plan.donate_argnums)
        lowered = jfn.lower(*plan.args_sds)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        }
        mem_d["total_bytes"] = (mem_d["argument_bytes"] + mem_d["output_bytes"]
                                + mem_d["temp_bytes"])
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    bytes_hbm = float(cost.get("bytes accessed", 0.0))

    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(hlo)

    mf = model_flops(arch, shape)
    min_bytes = float(mem_d.get("argument_bytes", 0) + mem_d.get("output_bytes", 0))
    rl = Roofline(flops=flops, bytes_hbm=bytes_hbm, bytes_coll=coll["total"],
                  n_chips=n_chips, model_flops_total=mf,
                  convert_elems=coll.get("convert_elems", 0.0),
                  convert_bytes=coll.get("convert_bytes", 0.0),
                  min_bytes=min_bytes)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "mesh_shape": list(mesh.devices.shape),
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem_d,
        "cost_analysis": {"flops": flops, "bytes_accessed": bytes_hbm},
        "collectives": {k: v for k, v in coll.items() if k != "counts"},
        "convert_artifact": {"elems": coll.get("convert_elems", 0.0),
                             "bytes": coll.get("convert_bytes", 0.0)},
        "cost_raw": {"flops": flops, "bytes_accessed": bytes_hbm},
        "collective_counts": coll["counts"],
        "roofline": rl.as_dict(),
        "opts": {k: str(v) for k, v in opts.items()},
        "hlo_lines": hlo.count("\n"),
    }
    if save:
        d = os.path.join(ART_DIR, mesh_name)
        os.makedirs(d, exist_ok=True)
        tag = "" if not opts else "__" + "_".join(f"{k}-{v}" for k, v in opts.items())
        with open(os.path.join(d, f"{arch}__{shape}{tag}.json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both", "tiny", "tinymulti"])
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--no-mla-absorb", action="store_true")
    ap.add_argument("--hlo-out", default=None)
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--scan", action="store_true",
                    help="keep scan-over-layers (fast compile; use for the "
                         "multi-pod compile-proof pass — cost accounting "
                         "then undercounts loop bodies)")
    args = ap.parse_args()

    opts = {}
    if args.scan:
        opts["unroll"] = False
    if args.grad_accum:
        opts["grad_accum"] = args.grad_accum
    if args.zero1:
        opts["zero1_axis"] = "data"
    if args.no_mla_absorb:
        opts["mla_absorb"] = False

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    failures = []
    for mesh_name in meshes:
        for arch, shape in cells:
            tag = f"{arch} x {shape} @ {mesh_name}"
            try:
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_name, opts,
                               hlo_out=args.hlo_out)
                r = rec["roofline"]
                print(f"[ok] {tag}: compile={rec['compile_s']:.1f}s "
                      f"flops/dev={r['flops_per_dev']:.3e} "
                      f"dominant={r['dominant']} "
                      f"bound={max(r['compute_s'], r['memory_s'], r['collective_s']):.4f}s "
                      f"useful={r['useful_ratio']:.2f}", flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"[FAIL] {tag}: {e!r}", flush=True)
                if not args.continue_on_error:
                    traceback.print_exc()
                    raise SystemExit(1)
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall cells passed")


if __name__ == "__main__":
    main()
