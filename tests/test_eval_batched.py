"""Batched (C clients x T tasks) retrieval eval regression tests:

  (a) ``evaluate_retrieval_batched(backend="device")`` allcloses the numpy
      per-(c, t) oracle (``backend="host"``) across random problems,
      padding masks, exact distance ties, queries with no cross-camera
      match, and all-invalid query sets — for both kernel backends;
  (b) gallery prototypes assembled from the pre-extracted query prototypes
      (the per-(c, t) cache) match re-extracting the raw gallery;
  (c) ``run_simulation(eval_backend="device")`` matches
      ``eval_backend="host"`` tracker metrics on both engines;
  (d) the mesh-sharded eval round matches the single-device program;
  (e) CommLog batched logging equals the per-client loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.accounting import CommLog
from repro.core import FedSTIL
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.evalreid import evaluate_retrieval_batched
from repro.evalreid.batched import max_match_bound
from repro.federated import run_simulation
from repro.sharding.specs import engine_mesh


def _random_problem(rng, C=3, T=2, Q=6, G=40, F=8, n_ids=12):
    qf = rng.standard_normal((C, T, Q, F)).astype(np.float32)
    gf = rng.standard_normal((C, G, F)).astype(np.float32)
    qids = rng.integers(0, n_ids, (C, T, Q)).astype(np.int32)
    gids = rng.integers(0, n_ids, (C, G)).astype(np.int32)
    return qf, qids, gf, gids


def _assert_close(a, b):
    for k in a:
        np.testing.assert_allclose(a[k], b[k], atol=1e-5, err_msg=k)


@pytest.mark.parametrize("kernel_backend", [None, "interpret"])
def test_device_matches_oracle_random(kernel_backend):
    rng = np.random.default_rng(0)
    qf, qids, gf, gids = _random_problem(rng)
    host = evaluate_retrieval_batched(qf, qids, gf, gids, backend="host")
    dev = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device",
                                     kernel_backend=kernel_backend)
    _assert_close(host, dev)


@pytest.mark.parametrize("max_matches", [None, 64])
def test_padding_masks(max_matches):
    """Padded queries/gallery rows must be invisible: one fully-masked
    task, one fully-masked gallery, and random partial masks."""
    rng = np.random.default_rng(1)
    qf, qids, gf, gids = _random_problem(rng, C=4, T=3, Q=5, G=30)
    qmask = (rng.random((4, 3, 5)) < 0.7).astype(np.float32)
    gmask = (rng.random((4, 30)) < 0.8).astype(np.float32)
    qmask[1, 2] = 0.0                       # fully padded task
    gmask[2] = 0.0                          # fully padded gallery
    host = evaluate_retrieval_batched(qf, qids, gf, gids, qmask=qmask,
                                      gmask=gmask, backend="host")
    dev = evaluate_retrieval_batched(qf, qids, gf, gids, qmask=qmask,
                                     gmask=gmask, backend="device",
                                     max_matches=max_matches)
    _assert_close(host, dev)
    assert (host["mAP"][1, 2] == 0.0) and (dev["mAP"][1, 2] == 0.0)
    assert (host["mAP"][2] == 0.0).all() and (dev["mAP"][2] == 0.0).all()


def test_distance_ties():
    """Exactly duplicated gallery rows: both paths break the tie by
    gallery order (stable sort == counting rule)."""
    qf = np.zeros((1, 1, 1, 2), np.float32)
    qf[0, 0, 0] = [1.0, 0.0]
    gf = np.zeros((1, 4, 2), np.float32)
    gf[0] = [[1.0, 0.0], [1.0, 0.0], [1.0, 0.0], [0.0, 1.0]]
    qids = np.array([[[7]]], np.int32)
    gids = np.array([[3, 7, 7, 5]], np.int32)   # ties: non-match first
    host = evaluate_retrieval_batched(qf, qids, gf, gids, backend="host")
    dev = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device")
    _assert_close(host, dev)
    # matches at stable ranks 2, 3 -> AP = (1/2 + 2/3) / 2
    np.testing.assert_allclose(dev["mAP"][0, 0], (0.5 + 2 / 3) / 2,
                               atol=1e-6)
    assert dev["R1"][0, 0] == 0.0 and dev["R3"][0, 0] == 1.0


def test_no_cross_camera_match_excluded():
    """A query whose id never appears in its gallery is dropped from the
    averages by both paths (not scored 0)."""
    rng = np.random.default_rng(2)
    qf, qids, gf, gids = _random_problem(rng, C=2, T=1, Q=4, G=20, n_ids=6)
    qids[0, 0, 1] = 99                      # no such gallery id
    host = evaluate_retrieval_batched(qf, qids, gf, gids, backend="host")
    dev = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device")
    _assert_close(host, dev)


def test_all_invalid_query_set_scores_zero():
    rng = np.random.default_rng(3)
    qf, qids, gf, gids = _random_problem(rng, C=2, T=1, Q=3, G=10, n_ids=4)
    qids[1, 0] = [50, 51, 52]               # none present in the gallery
    host = evaluate_retrieval_batched(qf, qids, gf, gids, backend="host")
    dev = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device")
    _assert_close(host, dev)
    for k in ("mAP", "R1", "R5"):
        assert host[k][1, 0] == 0.0 and dev[k][1, 0] == 0.0


def test_max_match_bound_is_safe():
    """The tight bound gives the same result as the exhaustive M = G."""
    rng = np.random.default_rng(4)
    qf, qids, gf, gids = _random_problem(rng, C=2, T=2, Q=5, G=25, n_ids=5)
    bound = max_match_bound(qids, gids)
    exact = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device",
                                       max_matches=gf.shape[1])
    tight = evaluate_retrieval_batched(qf, qids, gf, gids, backend="device",
                                       max_matches=bound)
    _assert_close(exact, tight)


# ---------------------------------------------------------------------------
# (b) gallery prototype cache == re-extraction
# ---------------------------------------------------------------------------


def test_gallery_prototype_cache_matches_extraction():
    from repro.federated.simulation import (_EvalCache,
                                            _pre_extract_prototypes)
    bench = FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                                   ids_per_task=8, samples_per_id=6, seed=0)
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    g_params = EM.init_extraction(jax.random.PRNGKey(0), cfg)
    protos = _pre_extract_prototypes(bench, g_params)
    cache = _EvalCache(bench, protos, engine_mesh(jax.devices()[:1]), 3)
    for c in range(3):
        gal_x, gal_y = bench.gallery(c, 1)
        gal_p = np.asarray(EM.extract_prototypes(g_params, gal_x))
        p, y = cache.host_gallery(c, 1)
        np.testing.assert_array_equal(y, gal_y)
        np.testing.assert_allclose(p, gal_p, atol=1e-6)
    gp, gids, gmask = cache.device_gallery(1)
    assert (np.asarray(gmask) == 1.0).all()     # t = T-1: no padding
    p0, y0 = cache.host_gallery(0, 1)
    np.testing.assert_allclose(np.asarray(gp)[0], p0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(gids)[0], y0)


# ---------------------------------------------------------------------------
# (c) simulation: device eval == host eval, both engines
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bench():
    return FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                                  ids_per_task=8, samples_per_id=6, seed=0)


@pytest.fixture(scope="module")
def cfg(bench):
    return EdgeModelConfig(n_classes=bench.n_classes)


@pytest.mark.parametrize("engine", ["host", "stacked"])
def test_simulation_device_eval_matches_host_eval(bench, cfg, engine):
    dev = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                         rounds=4, eval_every=2, engine=engine,
                         eval_backend="device")
    host = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                          rounds=4, eval_every=2, engine=engine,
                          eval_backend="host")
    for key in ("mAP", "R1", "R3", "R5", "forgetting_mAP"):
        assert abs(dev.final(key) - host.final(key)) < 2e-3, key
    assert dev.comm.total_c2s == host.comm.total_c2s
    assert dev.comm.total_s2c == host.comm.total_s2c


def test_simulation_rejects_unknown_eval_backend(bench, cfg):
    with pytest.raises(ValueError, match="eval_backend"):
        run_simulation(FedSTIL(cfg, n_clients=3, epochs=1), bench,
                       rounds=1, eval_backend="gpu")


# ---------------------------------------------------------------------------
# (d) mesh-sharded eval round
# ---------------------------------------------------------------------------


def test_sharded_eval_round_matches_device_program():
    from repro.federated.base import sharded_eval_fn, stacked_eval_program

    mesh = engine_mesh(jax.devices()[:1])
    cfg = EdgeModelConfig()
    rng = np.random.default_rng(5)
    C, T, Q, G = 4, 2, 6, 30
    theta = jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(
        jax.random.split(jax.random.PRNGKey(1), C))
    qp = jnp.asarray(rng.standard_normal((C, T, Q, cfg.proto_dim)),
                     jnp.float32)
    qids = jnp.asarray(rng.integers(0, 10, (C, T, Q)), jnp.int32)
    tmask = jnp.ones((C, T), jnp.float32)
    gp = jnp.asarray(rng.standard_normal((C, G, cfg.proto_dim)), jnp.float32)
    gids = jnp.asarray(rng.integers(0, 10, (C, G)), jnp.int32)
    gmask = jnp.asarray((rng.random((C, G)) < 0.9).astype(np.float32))

    out = sharded_eval_fn(mesh)(theta, qp, qids, tmask, gp, gids, gmask)
    ref = stacked_eval_program(theta, qp, qids, tmask, gp, gids, gmask)
    for k in out:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# (e) batched comm accounting
# ---------------------------------------------------------------------------


def test_commlog_many_equals_loop():
    a, b = CommLog(), CommLog()
    payload = {"x": np.zeros((7, 3), np.float32)}
    for _ in range(5):
        a.log_c2s(0, payload)
        a.log_s2c(1, 123)
    b.log_c2s_many(0, payload, 5)
    b.log_s2c_many(1, 123, 5)
    assert a.c2s == b.c2s and a.s2c == b.s2c
    assert a.total == b.total
