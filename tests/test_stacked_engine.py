"""Stacked (device-resident) engine regression tests:

  (a) the (C, k, D) ring-buffer history matches the host-list
      ``stacked_history()`` oracle across pushes, partial participation,
      and overflow past ``history_len``;
  (b) a FedSTIL simulation with ``engine="stacked"`` matches
      ``engine="host"`` metrics to tolerance (they draw identical
      minibatches by construction);
  (c) the fused normalize+mask aggregate kernel allcloses the
      ``backend="loop"`` reference, including all-zero rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedSTIL
from repro.core.aggregation import personalized_aggregate
from repro.core.edge_model import EdgeModelConfig
from repro.core.relevance import (DeviceRingHistory, RelevanceTracker,
                                  normalize_rows, ring_relevance)
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation
from repro.kernels import ops
from repro.lifelong import STL
from repro.sharding.specs import engine_mesh


# ---------------------------------------------------------------------------
# (a) ring buffer == host-list oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_rounds", [1, 3, 9])   # 9 > history_len: overflow
def test_ring_matches_host_oracle(n_rounds):
    rng = np.random.default_rng(0)
    C, k, D = 4, 4, 8
    tr = RelevanceTracker(C, history_len=k)
    ring = DeviceRingHistory(C, k, D)
    for r in range(n_rounds):
        feats = rng.standard_normal((C, D)).astype(np.float32)
        # partial participation after the first round
        mask = np.ones((C,), np.float32) if r == 0 else \
            (rng.random(C) < 0.6).astype(np.float32)
        for c in range(C):
            if mask[c] > 0:
                tr.push(c, feats[c])
        ring.push_all(feats, mask)
    dense, valid = tr.stacked_history()
    np.testing.assert_allclose(np.asarray(ring.buf), dense)
    np.testing.assert_allclose(np.asarray(ring.valid), valid)


def test_ring_empty_and_never_pushed_rows():
    ring = DeviceRingHistory(3, 2, 4)
    assert (np.asarray(ring.valid) == 0).all()
    feats = np.ones((3, 4), np.float32)
    ring.push_all(feats, np.array([1.0, 0.0, 0.0], np.float32))
    valid = np.asarray(ring.valid)
    assert valid[0, 0] == 1.0 and (valid[1:] == 0).all()
    W = np.asarray(ring_relevance(ring.buf, ring.valid,
                                  mesh=engine_mesh(jax.devices()[:1]),
                                  forgetting_ratio=0.5))
    assert (W[1:] == 0).all()          # rows without a current feature


def test_tracker_push_all_keeps_ring_and_oracle_in_sync():
    """push_all updates the device ring AND the host lists; the batched
    relevance (ring-sourced) still matches the loop oracle."""
    rng = np.random.default_rng(2)
    C, k, D = 5, 3, 16
    tr = RelevanceTracker(C, history_len=k)
    for r in range(k + 2):             # overflow past history_len
        mask = np.ones((C,), np.float32) if r == 0 else \
            (rng.random(C) < 0.7).astype(np.float32)
        tr.push_all(rng.standard_normal((C, D)).astype(np.float32), mask)
    assert tr._ring is not None and not tr._ring_dirty
    np.testing.assert_allclose(tr.relevance(), tr.relevance(backend="loop"),
                               atol=1e-5)


def test_tracker_per_client_push_resyncs_ring():
    """Interleaving per-client push (dirty ring) with push_all must rebuild
    the ring from the oracle lists before going resident again."""
    rng = np.random.default_rng(3)
    C, k, D = 3, 3, 8
    tr = RelevanceTracker(C, history_len=k)
    tr.push_all(rng.standard_normal((C, D)).astype(np.float32))
    tr.push(1, rng.standard_normal(D).astype(np.float32))   # dirties ring
    assert tr._ring_dirty
    tr.push_all(rng.standard_normal((C, D)).astype(np.float32))
    dense, valid = tr.stacked_history()
    np.testing.assert_allclose(np.asarray(tr._ring.buf), dense)
    np.testing.assert_allclose(np.asarray(tr._ring.valid), valid)
    np.testing.assert_allclose(tr.relevance(), tr.relevance(backend="loop"),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# (b) stacked engine == host engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench():
    return FederatedReIDBenchmark(n_clients=3, n_tasks=3, n_identities=60,
                                  ids_per_task=10, samples_per_id=8, seed=1)


@pytest.fixture(scope="module")
def cfg(bench):
    return EdgeModelConfig(n_classes=bench.n_classes)


def test_fedstil_stacked_matches_host(bench, cfg):
    host = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                          rounds=4, eval_every=2)
    stacked = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                             rounds=4, eval_every=2, engine="stacked")
    for key in ("mAP", "R1", "R5", "forgetting_mAP"):
        assert abs(host.final(key) - stacked.final(key)) < 1e-4, key
    # identical payloads -> identical byte accounting
    assert host.comm.total_c2s == stacked.comm.total_c2s
    assert host.comm.total_s2c == stacked.comm.total_s2c
    assert host.storage_bytes == stacked.storage_bytes


def test_stl_stacked_matches_host(bench, cfg):
    host = run_simulation(STL(cfg, epochs=2), bench, rounds=3, eval_every=3)
    stacked = run_simulation(STL(cfg, epochs=2), bench, rounds=3,
                             eval_every=3, engine="stacked")
    for key in ("mAP", "R1"):
        assert abs(host.final(key) - stacked.final(key)) < 1e-4, key
    assert stacked.comm.total == 0


def test_stacked_engine_rejects_host_only_strategy(bench, cfg):
    # FedCurv's per-upload Fisher estimation keeps it host-only
    from repro.federated import FedCurv
    with pytest.raises(ValueError, match="stacked"):
        run_simulation(FedCurv(cfg, epochs=2), bench, rounds=2,
                       engine="stacked")


@pytest.mark.parametrize("make", [
    lambda cfg: __import__("repro.federated", fromlist=["FedAvg"]
                           ).FedAvg(cfg, epochs=2),
    lambda cfg: __import__("repro.federated", fromlist=["FedProx"]
                           ).FedProx(cfg, epochs=2),
], ids=["fedavg", "fedprox"])
def test_mean_strategies_stacked_match_host(bench, cfg, make):
    host = run_simulation(make(cfg), bench, rounds=3, eval_every=3)
    stacked = run_simulation(make(cfg), bench, rounds=3, eval_every=3,
                             engine="stacked")
    for key in ("mAP", "R1"):
        assert abs(host.final(key) - stacked.final(key)) < 1e-4, key
    assert host.comm.total_c2s == stacked.comm.total_c2s
    assert host.comm.total_s2c == stacked.comm.total_s2c


def test_stacked_relevance_matrix_matches_host(bench, cfg):
    sh = FedSTIL(cfg, n_clients=3, epochs=2)
    ss = FedSTIL(cfg, n_clients=3, epochs=2)
    run_simulation(sh, bench, rounds=3, eval_every=3)
    run_simulation(ss, bench, rounds=3, eval_every=3, engine="stacked")
    assert ss.last_W is not None and ss.last_W.shape == (3, 3)
    np.testing.assert_allclose(ss.last_W, sh.last_W, atol=1e-4)
    assert np.allclose(np.diag(ss.last_W), 0.0)


# ---------------------------------------------------------------------------
# (c) fused normalize+mask aggregate kernel == loop reference
# ---------------------------------------------------------------------------


def _loop_reference(w, thetas_mat):
    """normalize_rows + the per-leaf loop aggregate, the PR-1 oracle path."""
    C = w.shape[0]
    wm = np.asarray(w, np.float32) * (1.0 - np.eye(C, dtype=np.float32))
    wn = normalize_rows(wm)
    thetas = [{"t": jnp.asarray(thetas_mat[c])} for c in range(C)]
    bases = personalized_aggregate(thetas, wn, backend="loop")
    return np.stack([np.asarray(b["t"]) for b in bases]), wn


@pytest.mark.parametrize("backend", [None, "ref", "interpret"])
@pytest.mark.parametrize("C", [2, 5])
def test_fused_aggregate_matches_loop(backend, C):
    rng = np.random.default_rng(7)
    w = rng.random((C, C)).astype(np.float32)   # junk on the diagonal
    thetas = rng.standard_normal((C, 300)).astype(np.float32)
    B_ref, Wn_ref = _loop_reference(w, thetas)
    B, Wn = ops.fused_relevance_aggregate(jnp.asarray(w),
                                          jnp.asarray(thetas),
                                          backend=backend)
    np.testing.assert_allclose(np.asarray(Wn), Wn_ref, atol=1e-5)
    np.testing.assert_allclose(np.asarray(B), B_ref, atol=1e-4)


@pytest.mark.parametrize("backend", [None, "interpret"])
def test_fused_aggregate_all_zero_rows(backend):
    """Zero-relevance rows must stay zero — no NaNs from 0/0."""
    rng = np.random.default_rng(8)
    w = rng.random((4, 4)).astype(np.float32)
    w[1] = 0.0                                   # isolated client
    w[3] = 0.0
    thetas = rng.standard_normal((4, 257)).astype(np.float32)
    B, Wn = ops.fused_relevance_aggregate(jnp.asarray(w),
                                          jnp.asarray(thetas),
                                          backend=backend)
    B, Wn = np.asarray(B), np.asarray(Wn)
    assert not np.isnan(B).any() and not np.isnan(Wn).any()
    assert (Wn[1] == 0).all() and (B[1] == 0).all()
    assert (Wn[3] == 0).all() and (B[3] == 0).all()
    B_ref, Wn_ref = _loop_reference(w, thetas)
    np.testing.assert_allclose(Wn, Wn_ref, atol=1e-5)
    np.testing.assert_allclose(B, B_ref, atol=1e-4)


def test_fused_aggregate_fully_zero_w():
    w = jnp.zeros((3, 3))
    thetas = jnp.ones((3, 130))
    B, Wn = ops.fused_relevance_aggregate(w, thetas, backend="interpret")
    assert (np.asarray(B) == 0).all() and (np.asarray(Wn) == 0).all()


def test_fused_aggregate_row_block_matches_full():
    """A row block (rows row0.., diagonal at column row0 + i) gives exactly
    the whole-matrix kernel's rows — what each shard of the sharded
    engine computes."""
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.random((12, 12)).astype(np.float32))
    w = w.at[5].set(0.0)                              # a zero row stays zero
    thetas = jnp.asarray(rng.standard_normal((12, 300)).astype(np.float32))
    for backend in ("interpret", "ref"):
        B, Wn = ops.fused_relevance_aggregate(w, thetas, backend=backend)
        for row0, rows in ((0, 4), (4, 4), (8, 4)):
            Bb, Wnb = ops.fused_relevance_aggregate(
                w[row0:row0 + rows], thetas, row0, backend=backend)
            np.testing.assert_array_equal(np.asarray(Wnb),
                                          np.asarray(Wn[row0:row0 + rows]))
            np.testing.assert_array_equal(np.asarray(Bb),
                                          np.asarray(B[row0:row0 + rows]))


# ---------------------------------------------------------------------------
# sharded path (single-device mesh exercises the program + specs)
# ---------------------------------------------------------------------------


def test_sharded_fused_aggregate_matches_kernel():
    from repro.core.fedstil import sharded_aggregate_fn

    mesh = engine_mesh(jax.devices()[:1])
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.random((8, 8)).astype(np.float32))
    thetas = jnp.asarray(rng.standard_normal((8, 512)).astype(np.float32))
    B, Wn = sharded_aggregate_fn(mesh)(w, thetas)
    B_ref, Wn_ref = ops.fused_relevance_aggregate(w, thetas, backend="ref")
    np.testing.assert_allclose(np.asarray(Wn), np.asarray(Wn_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(B), np.asarray(B_ref), atol=1e-5)
