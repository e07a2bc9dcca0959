"""Debug-mesh (8 host devices) sharding check: every family x mode builds,
compiles, and (train) executes with real values, on (2,2) and (2,2,2)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import ShapeConfig
from repro.launch import steps as STEPS
from repro.launch.mesh import make_debug_mesh

SHAPES = [
    ShapeConfig("t_train", 32, 4, "train"),
    ShapeConfig("t_prefill", 64, 4, "prefill"),
    ShapeConfig("t_decode", 64, 4, "decode"),
]

fails = 0
for multi_pod in (False, True):
    mesh = make_debug_mesh(tp=2, dp=2, multi_pod=multi_pod)
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        for shape in SHAPES:
            t0 = time.time()
            tag = f"{arch} {shape.name} {'mp' if multi_pod else 'sp'}"
            try:
                if shape.mode == "train":
                    fn, args, _ = STEPS.build_train_step(
                        cfg, mesh, shape, multi_pod=multi_pod)
                elif shape.mode == "prefill":
                    fn, args, _ = STEPS.build_prefill_step(
                        cfg, mesh, shape, multi_pod=multi_pod)
                else:
                    fn, args, _ = STEPS.build_decode_step(
                        cfg, mesh, shape, multi_pod=multi_pod)
                with jax.set_mesh(mesh):
                    compiled = fn.lower(*args).compile()
                print(f"OK  {tag}  ({time.time()-t0:.1f}s)", flush=True)
            except Exception as e:
                fails += 1
                import traceback; traceback.print_exc()
                print(f"FAIL {tag}: {type(e).__name__} {str(e)[:200]}", flush=True)
print("fails:", fails)
raise SystemExit(1 if fails else 0)
