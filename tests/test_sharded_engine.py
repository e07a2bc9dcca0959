"""engine="sharded" regression tests:

  (a) on a 1-device engine mesh with ``wire_dtype="float32"`` (bf16 wire
      cast off) the sharded engine is bit-tight against the stacked
      oracle: identical metrics and identical measured comm bytes;
  (b) the default bf16 wire keeps metrics within the measured deviation
      (~1.8e-3) of the stacked engine;
  (c) wire-codec runs measure identical bytes on both engines (the codec
      formulas and buffer shapes are leading-dim independent);
  (d) a zero-validity client row (mesh padding) is provably inert in the
      sharded server round: it never acquires ring history, its relevance
      row AND column stay zero, and nz leaves its base untouched;
  (e) a forced 8-device host mesh (subprocess: XLA_FLAGS must precede the
      jax import) with C=5 — clients NOT divisible by the device count —
      still matches the stacked oracle exactly at wire_dtype="float32".
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedSTIL
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation
from repro.sharding.specs import engine_mesh


@pytest.fixture(scope="module")
def bench():
    return FederatedReIDBenchmark(n_clients=3, n_tasks=3, n_identities=60,
                                  ids_per_task=10, samples_per_id=8, seed=1)


@pytest.fixture(scope="module")
def cfg(bench):
    return EdgeModelConfig(n_classes=bench.n_classes)


def _run(cfg, bench, engine, *, wire_dtype="bfloat16", codec=None):
    kw = {"codec": codec} if codec else {}
    return run_simulation(
        FedSTIL(cfg, n_clients=3, epochs=2, wire_dtype=wire_dtype, **kw),
        bench, rounds=4, eval_every=2, engine=engine)


# ---------------------------------------------------------------------------
# (a) 1-device mesh, f32 wire: bit-tight vs the stacked oracle
# ---------------------------------------------------------------------------


def test_sharded_matches_stacked_one_device(bench, cfg):
    stacked = _run(cfg, bench, "stacked", wire_dtype="float32")
    sharded = _run(cfg, bench, "sharded", wire_dtype="float32")
    for key in ("mAP", "R1", "R5", "forgetting_mAP"):
        assert abs(stacked.final(key) - sharded.final(key)) < 1e-6, key
    assert stacked.comm.total_c2s == sharded.comm.total_c2s
    assert stacked.comm.total_s2c == sharded.comm.total_s2c
    assert stacked.storage_bytes == sharded.storage_bytes


# ---------------------------------------------------------------------------
# (b) default bf16 wire: bounded deviation
# ---------------------------------------------------------------------------


def test_sharded_bf16_wire_close_to_stacked(bench, cfg):
    stacked = _run(cfg, bench, "stacked")
    sharded = _run(cfg, bench, "sharded")
    for key in ("mAP", "R1", "R5"):
        # measured max deviation 1.8e-3 on this benchmark (bf16 has ~3
        # decimal digits); byte accounting is exact either way
        assert abs(stacked.final(key) - sharded.final(key)) < 5e-3, key
    assert stacked.comm.total_c2s == sharded.comm.total_c2s
    assert stacked.comm.total_s2c == sharded.comm.total_s2c


# ---------------------------------------------------------------------------
# (c) codec runs: measured wire bytes identical on both engines
# ---------------------------------------------------------------------------


def test_sharded_codec_bytes_match_stacked(bench, cfg):
    stacked = _run(cfg, bench, "stacked", wire_dtype="float32",
                   codec="topk+int8")
    sharded = _run(cfg, bench, "sharded", wire_dtype="float32",
                   codec="topk+int8")
    assert stacked.comm.total_c2s == sharded.comm.total_c2s
    assert stacked.comm.total_s2c == sharded.comm.total_s2c
    for key in ("mAP", "R1"):
        assert abs(stacked.final(key) - sharded.final(key)) < 1e-6, key


def test_fedavg_sharded_matches_host(bench, cfg):
    from repro.federated import FedAvg
    host = run_simulation(FedAvg(cfg, epochs=2), bench, rounds=3,
                          eval_every=3)
    sharded = run_simulation(FedAvg(cfg, epochs=2), bench, rounds=3,
                             eval_every=3, engine="sharded")
    for key in ("mAP", "R1"):
        assert abs(host.final(key) - sharded.final(key)) < 1e-4, key
    assert host.comm.total_c2s == sharded.comm.total_c2s
    assert host.comm.total_s2c == sharded.comm.total_s2c


# ---------------------------------------------------------------------------
# (d) zero-validity rows are inert in the sharded server round
# ---------------------------------------------------------------------------


def test_sharded_server_round_zero_mask_row_inert(cfg):
    strat = FedSTIL(cfg, n_clients=4, epochs=1, wire_dtype="float32")
    strat.mesh = engine_mesh(jax.devices()[:1])
    C = 4
    theta = jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), C))
    rng = np.random.default_rng(11)
    valid = jnp.asarray([1.0, 1.0, 1.0, 0.0], jnp.float32)
    for rnd in range(3):
        feats = jnp.asarray(rng.standard_normal((C, cfg.proto_dim)),
                            jnp.float32)
        out = strat.server_round_stacked(rnd, {"theta": theta,
                                               "task_feature": feats},
                                         valid=valid)
        nz = np.asarray(out["nz"])
        W = strat.last_W
        # the masked row never enters the ring: relevance row AND column
        # stay zero, so it neither receives nor donates a base
        assert not nz[3]
        assert (W[3] == 0).all() and (W[:, 3] == 0).all()
        if rnd > 0:
            assert nz[:3].all()


# ---------------------------------------------------------------------------
# (e) forced 8-device mesh, C=5 (not divisible): exact parity
# ---------------------------------------------------------------------------


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
assert jax.device_count() == 8, jax.device_count()

from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation

bench = FederatedReIDBenchmark(n_clients=5, n_tasks=2, n_identities=40,
                               ids_per_task=10, samples_per_id=6, seed=3)
cfg = EdgeModelConfig(n_classes=bench.n_classes)


def run(engine):
    res = run_simulation(FedSTIL(cfg, n_clients=5, epochs=1,
                                 wire_dtype="float32"), bench,
                         rounds=2, eval_every=2, engine=engine)
    return {"mAP": res.final("mAP"), "R1": res.final("R1"),
            "c2s": res.comm.total_c2s, "s2c": res.comm.total_s2c}


print(json.dumps({"stacked": run("stacked"), "sharded": run("sharded")}))
"""


def test_sharded_matches_stacked_on_forced_8_device_mesh():
    env = dict(os.environ)
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    st, sh = out["stacked"], out["sharded"]
    # C=5 pads to Cp=8 on the 8-device data axis; padding rows are masked
    # out of the ring and sliced out of eval/accounting, so the result is
    # the stacked oracle's, exactly
    assert abs(st["mAP"] - sh["mAP"]) < 1e-6
    assert abs(st["R1"] - sh["R1"]) < 1e-6
    assert st["c2s"] == sh["c2s"]
    assert st["s2c"] == sh["s2c"]
