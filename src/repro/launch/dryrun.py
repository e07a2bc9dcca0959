import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers + compiles on the production mesh, and extract the
roofline inputs (memory / FLOPs / collective bytes) from the compiled
artifact. See DESIGN.md §3-4 and EXPERIMENTS.md §Dry-run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                     # all 40 x 2
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k --multi-pod both --out results/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as STEPS
from repro.sharding import analysis as AN


def _tree_device_bytes(tree, specs, mesh) -> int:
    """Per-device bytes of abstract arrays under their PartitionSpecs."""
    sizes = dict(mesh.shape)
    total = 0
    for leaf, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
                              x, jax.sharding.PartitionSpec))):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        div = 1
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                div *= sizes[ax]
        total += (n // max(div, 1)) * leaf.dtype.itemsize
    return total


def run_combo(arch: str, shape_name: str, multi_pod: bool, verbose=True,
              layout: str = "tp", weight_stationary: bool = False,
              kv8: bool = False):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    if shape.mode == "train" and layout != "tp":
        fn, args, in_specs = STEPS.build_train_step(
            cfg, mesh, shape, multi_pod=multi_pod, layout=layout)
    elif shape.mode == "decode" and (weight_stationary or kv8):
        import jax.numpy as jnp
        fn, args, in_specs = STEPS.build_decode_step(
            cfg, mesh, shape, multi_pod=multi_pod,
            weight_stationary=weight_stationary,
            kv_dtype=jnp.int8 if kv8 else jnp.bfloat16)
    else:
        fn, args, in_specs = STEPS.build_step(cfg, mesh, shape_name,
                                              multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- memory ----
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:  # CPU backend may not support it
        mem["memory_analysis_error"] = str(e)

    # analytic per-device argument bytes from the sharded input structure
    mem["args_bytes_per_device"] = _tree_device_bytes(args, in_specs, mesh)

    # ---- cost ----
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost["cost_analysis_error"] = str(e)

    # ---- collectives ----
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = AN.parse_collectives(hlo)

    flops_total = cost.get("flops", 0.0)
    # XLA reports whole-program (per-partition) flops for SPMD: treat as
    # per-device; see EXPERIMENTS.md §Dry-run notes.
    hbm_bytes = cost.get("bytes accessed", 0.0)
    roof = AN.Roofline(
        flops_per_device=flops_total,
        hbm_bytes_per_device=hbm_bytes,
        collective_bytes_per_device=float(coll.total_bytes),
        n_devices=n_dev,
        model_flops=AN.analytic_model_flops(cfg, shape),
    )

    rec = {
        "arch": arch,
        "shape": shape_name,
        "layout": layout,
        "weight_stationary": weight_stationary,
        "kv8": kv8,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost": cost,
        "collectives": {"bytes": coll.bytes_by_kind,
                        "count": coll.count_by_kind,
                        "total_bytes": coll.total_bytes},
        "roofline": roof.as_dict(),
    }
    if verbose:
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "lower_s", "compile_s")}),
              flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
        print(f"  cost_analysis: flops={flops_total:.3e} "
              f"bytes={hbm_bytes:.3e} coll={coll.total_bytes:.3e}", flush=True)
        print(f"  roofline: t_comp={roof.t_compute:.4f}s "
              f"t_mem={roof.t_memory:.4f}s t_coll={roof.t_collective:.4f}s "
              f"bottleneck={roof.bottleneck} "
              f"useful={roof.useful_flops_ratio:.3f}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--multi-pod", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--layout", default="tp", choices=["tp", "dp"],
                    help="dp: model axis carries batch (small-model hillclimb)")
    ap.add_argument("--ws", action="store_true",
                    help="weight-stationary decode (FSDP hillclimb)")
    ap.add_argument("--kv8", action="store_true",
                    help="int8 KV cache (decode hillclimb iteration 3)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"skip {tag} (exists)", flush=True)
                    continue
                try:
                    rec = run_combo(arch, shape, mp, layout=args.layout,
                                    weight_stationary=args.ws, kv8=args.kv8)
                except Exception as e:
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "ok": False, "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
    print(f"dryrun complete; failures={failures}", flush=True)
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
