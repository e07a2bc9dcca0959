"""The device engine's round gather against the host loop it replaces.

``Strategy.gather_round_batches`` draws every client's minibatch indices
on the host and gathers the rows in one device program. Its batches, in
their mesh padding, equal bit for bit what the host loop below builds
from the same draws, and it leaves ``self.rng`` where that loop leaves
it. The task's rows are uploaded on the first round of a task only.

The mesh cases run in one subprocess on a forced 8-device CPU mesh
(XLA_FLAGS must precede the jax import), where C = 5 clients pad to
Cp = 8 rows.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.federated.base import pad_client_rows
from repro.lifelong import STL
from repro.obs import trace as obs
from repro.sharding import specs as shard_specs

CFG = EdgeModelConfig(proto_dim=16, hidden=16, feat_dim=8, n_classes=32)
EPOCHS, BATCH = 3, 8


def host_loop(strat, stacked, protos_list, labels_list):
    """The round gather as the host built it, row copy by row copy: the
    oracle."""
    bxs, bys = [], []
    for c in range(len(protos_list)):
        p, l = protos_list[c], labels_list[c]
        n = len(p)
        reh = strat._gather_rehearsal(stacked, c)
        ex, ey = [], []
        for _ in range(strat.epochs):
            idx = strat.rng.choice(n, size=min(strat.batch, n),
                                   replace=n < strat.batch)
            px, py = p[idx], l[idx]
            if reh is not None:
                rx, ry = reh
                ridx = strat.rng.choice(len(rx), size=strat.batch // 2,
                                        replace=True)
                px = np.concatenate([px, rx[ridx]])
                py = np.concatenate([py, ry[ridx]])
            ex.append(px)
            ey.append(py)
        bxs.append(np.stack(ex))
        bys.append(np.stack(ey))
    return np.stack(bxs), np.stack(bys)


def _task(rng, C, N):
    protos = [rng.standard_normal((N, CFG.proto_dim)).astype(np.float32)
              for _ in range(C)]
    labels = [rng.integers(0, CFG.n_classes, N) for _ in range(C)]
    return protos, labels


def _strategies(kind, C, seed):
    make = {"fedstil": lambda: FedSTIL(CFG, n_clients=C, epochs=EPOCHS,
                                       batch=BATCH, seed=seed),
            "stl": lambda: STL(CFG, epochs=EPOCHS, batch=BATCH, seed=seed)}
    return make[kind](), make[kind]()


def _stacked(strat, C, mesh, rng, pool_rows):
    """A placed stacked state; with ``pool_rows``, every FedSTIL client's
    rehearsal memory holds that many rows."""
    states = {c: strat.init_client(jax.random.PRNGKey(c)) for c in range(C)}
    stacked, _ = strat.shard_stacked_state(strat.stack_states(states), mesh)
    for mem in stacked.host.get("memory", []):
        if pool_rows:
            p, l = _task(rng, 1, pool_rows)
            mem.add_selected(p[0], l[0], np.arange(pool_rows), task_id=0)
    return stacked


# kind, N task rows per client, rows in each rehearsal memory
CASES = {
    "fedstil_pool": ("fedstil", 20, 30),
    "fedstil_small_pool": ("fedstil", 20, 5),   # pool < batch
    "fedstil_no_pool": ("fedstil", 20, 0),      # round 0
    "fedstil_n_below_batch": ("fedstil", 6, 30),  # replace=True task draw
    "stl": ("stl", 20, 0),
}


def check_case(name, mesh, C):
    """Run one case on ``mesh``; raises AssertionError on a mismatch."""
    kind, N, pool_rows = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    protos, labels = _task(rng, C, N)
    oracle, strat = _strategies(kind, C, seed=11 + C)
    stacked = _stacked(strat, C, mesh, rng, pool_rows)
    want = host_loop(oracle, stacked, protos, labels)
    bx, by = strat.gather_round_batches(stacked, protos, labels)
    assert strat.rng.bit_generator.state == oracle.rng.bit_generator.state
    want_x, want_y = pad_client_rows(want, strat.padded_clients)
    assert bx.dtype == np.float32 and bx.shape == want_x.shape, bx.shape
    np.testing.assert_array_equal(np.asarray(bx), want_x)
    np.testing.assert_array_equal(np.asarray(by), want_y)
    rows = jax.sharding.NamedSharding(mesh, shard_specs.client_row_spec(4))
    assert bx.sharding.is_equivalent_to(rows, bx.ndim)
    # placing the gather's output again copies nothing
    for a, b in zip(strat.place_batches(bx, by), (bx, by)):
        assert _buffers(a) == _buffers(b)


def _buffers(x):
    return [s.data.unsafe_buffer_pointer() for s in x.addressable_shards]


@pytest.mark.parametrize("name", sorted(CASES))
def test_gather_matches_host_loop(name):
    check_case(name, shard_specs.engine_mesh(jax.devices()[:1]), C=4)


def _uploads(strat, stacked, protos, labels):
    tracer = obs.Tracer()
    with obs.active(tracer):
        strat.gather_round_batches(stacked, protos, labels)
    up, = [e for e in tracer.events
           if e["kind"] == "span" and e["name"] == "gather.upload"]
    return up


def test_task_rows_upload_once_per_task():
    C, N, P = 3, 20, 30
    rng = np.random.default_rng(0)
    mesh = shard_specs.engine_mesh(jax.devices()[:1])
    strat, _ = _strategies("fedstil", C, seed=0)
    stacked = _stacked(strat, C, mesh, rng, P)
    protos, labels = _task(rng, C, N)
    D = CFG.proto_dim
    # per round: pool rows + labels, task-row and pool-row indices
    per_round = (C * BATCH * (D + 1) + C * EPOCHS * BATCH
                 + C * EPOCHS * (BATCH // 2)) * 4
    task = C * N * (D + 1) * 4
    first = _uploads(strat, stacked, protos, labels)
    assert (first["task_resident"], first["h2d_bytes"]) == (0, per_round
                                                            + task)
    again = _uploads(strat, stacked, list(protos), list(labels))
    assert (again["task_resident"], again["h2d_bytes"]) == (1, per_round)
    # the next task: other arrays, uploaded again, then resident
    protos, labels = _task(rng, C, N)
    assert _uploads(strat, stacked, protos, labels)["task_resident"] == 0
    assert _uploads(strat, stacked, protos, labels)["task_resident"] == 1


def test_ragged_batches_raise():
    C = 3
    rng = np.random.default_rng(0)
    mesh = shard_specs.engine_mesh(jax.devices()[:1])
    strat, _ = _strategies("fedstil", C, seed=0)
    stacked = _stacked(strat, C, mesh, rng, 0)
    protos, labels = _task(rng, C, 20)
    short_p, short_l = _task(rng, 1, 5)
    with pytest.raises(ValueError, match="uniform per-client batch"):
        strat.gather_round_batches(stacked, protos[:2] + short_p,
                                   labels[:2] + short_l)
    # one client with a rehearsal pool, the others without
    p, l = _task(rng, 1, 30)
    stacked.host["memory"][1].add_selected(p[0], l[0], np.arange(30), 0)
    with pytest.raises(ValueError, match="uniform per-client batch"):
        strat.gather_round_batches(stacked, protos, labels)


_MESH_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
assert jax.device_count() == 8, jax.device_count()
import repro.core  # noqa: F401  (before repro.federated: import order)
sys.path.insert(0, sys.argv[1])
import test_round_gather as T
from repro.sharding import specs

mesh = specs.engine_mesh()
for name in sorted(T.CASES):
    T.check_case(name, mesh, C=5)       # Cp = 8 > C
print("ok")
"""


def test_gather_matches_host_loop_on_forced_8_device_mesh():
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT, here],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().splitlines()[-1] == "ok"
