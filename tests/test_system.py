"""End-to-end behaviour tests for the full system."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.tokens import synthetic_lm_batch
from repro.train import init_train_state, make_train_step
from repro.train.optimizer import adam


def test_lm_training_reduces_loss():
    """FedSTIL-split training (frozen trunk, adaptive B⊙alpha+A) learns on
    structured synthetic tokens."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    st = init_train_state(cfg, jax.random.PRNGKey(0),
                          optimizer=adam(lr=3e-3))
    step = jax.jit(make_train_step(cfg, optimizer=adam(lr=3e-3)))
    rng = np.random.default_rng(0)
    losses = []
    tr, opt = st.trainable, st.opt_state
    for i in range(30):
        toks, labels = synthetic_lm_batch(rng, 8, 32, cfg.vocab_size)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        tr, opt, m = step(st.frozen, st.B, tr, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[::6]


def test_paper_pipeline_end_to_end():
    """Full FedSTIL round-trip on the synthetic ReID benchmark."""
    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig
    from repro.data import FederatedReIDBenchmark
    from repro.federated import run_simulation

    bench = FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                                   ids_per_task=8, samples_per_id=6, seed=0)
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    res = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                         rounds=4, eval_every=2)
    assert len(res.rounds) >= 2
    assert res.rounds[-1]["mAP"] > 0.2
    assert res.comm.total_c2s > 0 and res.comm.total_s2c > 0
    assert res.storage_bytes > 0


@pytest.mark.slow
def test_debug_mesh_sharding_subprocess():
    """Sharded-vs-unsharded equivalence on an 8-device debug mesh (separate
    process because device count locks at first jax init)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, functools
from repro.configs import get_config
from repro.launch import steps as STEPS
from repro.launch.mesh import make_debug_mesh
from repro.configs.base import ShapeConfig
from repro.train import trainer as TR

cfg = get_config("qwen3-1.7b").reduced()
mesh = make_debug_mesh(tp=2, dp=2)
shape = ShapeConfig("t", 32, 4, "train")
fn, _, _ = STEPS.build_train_step(cfg, mesh, shape, multi_pod=False)
st = TR.init_train_state(cfg, jax.random.PRNGKey(0), tp=2)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)}
with jax.set_mesh(mesh):
    tr, opt, metrics = fn(st.frozen, st.B, st.trainable, st.opt_state, batch)
step0 = TR.make_train_step(cfg, tie_lambda=1e-4)
tr0, opt0, m0 = step0(st.frozen, st.B, st.trainable, st.opt_state, batch)
assert abs(float(metrics["loss"]) - float(m0["loss"])) < 2e-3, (
    float(metrics["loss"]), float(m0["loss"]))
print("OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert "OK" in r.stdout, r.stderr[-2000:]

