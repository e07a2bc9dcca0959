"""Device-engine regression tests across device counts. The engine name
picks the device list only: ``engine="stacked"`` runs the engine on a
one-device mesh, ``engine="sharded"`` on every device.

  (a) on one device both names run the same programs: identical metrics
      and measured comm bytes at the default bf16 ``wire_dtype`` (Θ
      crosses no shard boundary, so no wire cast happens), with and
      without the wire codec;
  (b) on a forced 8-device mesh the default bf16 wire keeps metrics
      within the measured deviation (~1.8e-3) of the one-device run;
  (c) wire-codec runs measure identical bytes on 1 and 8 devices (the
      codec formulas and buffer shapes are leading-dim independent). Their
      metrics are not compared there: on CPU the local-train program
      rounds differently in the last bit when a device holds fewer client
      rows, and the codec's top-k turns that into ~1e-4 of mAP;
  (d) a zero-validity client row (mesh padding) is provably inert in the
      server round: it never acquires ring history, its relevance row
      AND column stay zero, and nz leaves its base untouched;
  (e) on the 8-device mesh with C=5 — clients NOT divisible by the
      device count — the engine still matches the one-device run's
      metrics to 1e-6 and its bytes exactly at wire_dtype="float32";
  (f) with 8 devices visible, "stacked" runs on a mesh of 1 device and
      "sharded" on 8.

(b), (c), (e) and (f) read one subprocess (XLA_FLAGS must precede the
jax import) that runs every engine once and prints the results as JSON.
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


def _run(cfg, bench, engine, **kw):
    return run_simulation(FedSTIL(cfg, n_clients=3, epochs=2, **kw), bench,
                          rounds=4, eval_every=2, engine=engine)


# ---------------------------------------------------------------------------
# (a) one device: both engine names run the same programs
# ---------------------------------------------------------------------------


def test_sharded_matches_stacked_one_device(bench, cfg):
    assert jax.device_count() == 1
    for kw in ({}, {"codec": "topk+int8"}):
        stacked = _run(cfg, bench, "stacked", **kw)
        sharded = _run(cfg, bench, "sharded", **kw)
        for key in ("mAP", "R1", "R5", "forgetting_mAP"):
            assert stacked.final(key) == sharded.final(key), (kw, key)
        assert stacked.comm.total_c2s == sharded.comm.total_c2s
        assert stacked.comm.total_s2c == sharded.comm.total_s2c
        assert stacked.storage_bytes == sharded.storage_bytes


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
# (b), (c), (e), (f): a forced 8-device host mesh, one subprocess
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

# the in-process tests' benchmark (C=3 pads to Cp=8) and a C=5 one
small = FederatedReIDBenchmark(n_clients=3, n_tasks=3, n_identities=60,
                               ids_per_task=10, samples_per_id=8, seed=1)
odd = FederatedReIDBenchmark(n_clients=5, n_tasks=2, n_identities=40,
                             ids_per_task=10, samples_per_id=6, seed=3)


def run(bench, engine, *, epochs, rounds, **kw):
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    strat = FedSTIL(cfg, n_clients=bench.n_clients, epochs=epochs, **kw)
    res = run_simulation(strat, bench, rounds=rounds, eval_every=2,
                         engine=engine)
    return {"mAP": res.final("mAP"), "R1": res.final("R1"),
            "R5": res.final("R5"), "c2s": res.comm.total_c2s,
            "s2c": res.comm.total_s2c, "devices": strat.mesh.devices.size}


out = {}
for engine in ("stacked", "sharded"):
    out[engine] = {
        "bf16": run(small, engine, epochs=2, rounds=4),
        "codec": run(small, engine, epochs=2, rounds=4,
                     wire_dtype="float32", codec="topk+int8"),
        "f32": run(odd, engine, epochs=1, rounds=2, wire_dtype="float32"),
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_runs():
    env = dict(os.environ)
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return out["stacked"], out["sharded"]


def test_sharded_bf16_wire_close_to_stacked(mesh_runs):
    st, sh = (r["bf16"] for r in mesh_runs)
    for key in ("mAP", "R1", "R5"):
        # measured max deviation 1.8e-3 on this benchmark (bf16 has ~3
        # decimal digits); byte accounting is exact either way
        assert abs(st[key] - sh[key]) < 5e-3, key
    assert st["c2s"] == sh["c2s"]
    assert st["s2c"] == sh["s2c"]


def test_sharded_codec_bytes_match_stacked(mesh_runs):
    st, sh = (r["codec"] for r in mesh_runs)
    assert st["c2s"] == sh["c2s"]
    assert st["s2c"] == sh["s2c"]


def test_sharded_matches_stacked_on_forced_8_device_mesh(mesh_runs):
    st, sh = (r["f32"] for r in mesh_runs)
    # C=5 pads to Cp=8 on the 8-device data axis; padding rows are masked
    # out of the ring and sliced out of eval/accounting, so the result is
    # the one-device run's
    assert abs(st["mAP"] - sh["mAP"]) < 1e-6
    assert abs(st["R1"] - sh["R1"]) < 1e-6
    assert st["c2s"] == sh["c2s"]
    assert st["s2c"] == sh["s2c"]


def test_engine_name_picks_device_count(mesh_runs):
    st, sh = mesh_runs
    for case in ("bf16", "codec", "f32"):
        assert st[case]["devices"] == 1, case
        assert sh[case]["devices"] == 8, case
