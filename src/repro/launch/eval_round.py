"""Batched retrieval evaluation sharded over client rows (C ≫ 1000 path).

The device-resident eval program (``federated.base.stacked_eval_program``:
vmapped feature heads → all distance matrices → mAP/CMC on device) is
embarrassingly parallel over clients: every input carries a leading C dim
and no stage contracts it. The one sharded implementation is
``federated.base.sharded_eval_fn`` — the path ``run_simulation``'s
device eval uses on every engine — which runs the program per
shard inside ``shard_map``: inputs are placed with
``sharding.specs.stacked_eval_specs`` client-row shardings, each device
scores one block of clients (the distance kernel sees local blocks) and
there are no cross-client collectives. This CLI is just a demo/lowering
harness around that function.

Run a CPU demo:   PYTHONPATH=src python -m repro.launch.eval_round --demo
"""
import os as _os
if __name__ == "__main__":
    _os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.federated.base import sharded_eval_fn, stacked_eval_program
from repro.sharding.specs import (engine_mesh, named_shardings,
                                  stacked_eval_specs,
                                  stacked_eval_theta_specs)


def _demo():
    """8 host devices, C=8 clients sharded over data×4: the mesh-sharded
    eval round matches the single-device kernel-path program."""
    from repro.core import edge_model as EM
    from repro.core.edge_model import EdgeModelConfig

    mesh = engine_mesh(jax.devices()[:8], model=2)
    C, T, Q, G = 8, 3, 16, 96
    cfg = EdgeModelConfig()
    rng = np.random.default_rng(0)
    theta = jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), C))
    qp = jnp.asarray(rng.standard_normal((C, T, Q, cfg.proto_dim)), jnp.float32)
    qids = jnp.asarray(rng.integers(0, 30, (C, T, Q)), jnp.int32)
    task_mask = jnp.asarray(np.broadcast_to(
        (np.arange(T) < 2).astype(np.float32), (C, T)))
    gp = jnp.asarray(rng.standard_normal((C, G, cfg.proto_dim)), jnp.float32)
    gids = jnp.asarray(rng.integers(0, 30, (C, G)), jnp.int32)
    gmask = jnp.asarray((rng.random((C, G)) < 0.9).astype(np.float32))

    # place client rows along the data axis; the engine's shard_map'd eval
    # program scores each device's block
    sp = stacked_eval_specs()
    sh = named_shardings(mesh, sp)
    theta_sh = jax.device_put(
        theta, named_shardings(mesh, stacked_eval_theta_specs(theta)))
    qp, qids, task_mask, gp, gids, gmask = (
        jax.device_put(a, sh[k]) for a, k in
        ((qp, "qf"), (qids, "qids"), (task_mask, "task_mask"),
         (gp, "gf"), (gids, "gids"), (gmask, "gmask")))
    out = sharded_eval_fn(mesh, kernel_backend="ref")(
        theta_sh, qp, qids, task_mask, gp, gids, gmask)
    ref = stacked_eval_program(theta, qp, qids, task_mask, gp, gids, gmask,
                               kernel_backend="interpret")
    for k in out:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-5)
    print(f"sharded eval round (C={C} over data×{mesh.shape['data']}) == "
          f"kernel path; mean mAP={float(jnp.mean(out['mAP'])):.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--demo", action="store_true")
    ap.parse_args()
    _demo()


if __name__ == "__main__":
    main()
