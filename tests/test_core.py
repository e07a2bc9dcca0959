"""Unit tests for the FedSTIL core (paper equations 2-6, rehearsal, tying)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PrototypeMemory,
    RelevanceTracker,
    combine,
    init_adaptive,
    kl_similarity,
    pairwise_similarity,
    personalized_aggregate,
    fedavg_aggregate,
    tying_loss,
)
from repro.core.similarity import cosine_similarity, euclidean_similarity


def test_adaptive_combine_eq2():
    B = {"w": jnp.array([1.0, 2.0]), "b": jnp.array([[1.0, -1.0]])}
    al = {"w": jnp.array([0.5, 0.0]), "b": jnp.array([[2.0, 2.0]])}
    A = {"w": jnp.array([0.1, 0.1]), "b": jnp.array([[0.0, 1.0]])}
    th = combine(B, al, A)
    np.testing.assert_allclose(th["w"], [0.6, 0.1])
    np.testing.assert_allclose(th["b"], [[2.0, -1.0]])


def test_init_adaptive_identity():
    theta0 = {"w": jnp.arange(6.0).reshape(2, 3)}
    ad = init_adaptive(theta0)
    np.testing.assert_allclose(ad.theta()["w"], theta0["w"])


def test_similarities_basic():
    a = jnp.array([1.0, 2.0, 3.0])
    for fn in (kl_similarity, cosine_similarity, euclidean_similarity):
        s_self = float(fn(a, a))
        assert s_self == pytest.approx(1.0, abs=1e-5)
        b = jnp.array([-3.0, 5.0, 0.1])
        s = float(fn(a, b))
        assert 0.0 <= s <= 1.0 + 1e-6
        assert s < s_self


def test_pairwise_similarity_shape():
    fa = jnp.ones((3, 8))
    fb = jnp.zeros((4, 8))
    S = pairwise_similarity(fa, fb, "kl")
    assert S.shape == (3, 4)


def test_relevance_decay_and_normalization():
    tr = RelevanceTracker(n_clients=3, history_len=4, forgetting_ratio=0.5)
    rng = np.random.default_rng(0)
    # client 1's history matches client 0's current task; client 2 differs
    base = rng.standard_normal(16).astype(np.float32)
    for t in range(3):
        tr.push(0, base + 0.01 * rng.standard_normal(16))
        tr.push(1, base + 0.01 * rng.standard_normal(16))
        tr.push(2, 10 * rng.standard_normal(16))
    W = tr.relevance()
    assert W.shape == (3, 3)
    assert np.allclose(np.diag(W), 0)
    np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-5)
    assert W[0, 1] > W[0, 2]   # similar neighbour gets more weight


def test_personalized_aggregate_onehot():
    thetas = [{"w": jnp.full((2, 2), float(i))} for i in range(3)]
    W = np.array([[0, 1, 0], [0, 0, 1], [1, 0, 0]], np.float32)
    out = personalized_aggregate(thetas, W)
    np.testing.assert_allclose(out[0]["w"], 1.0)
    np.testing.assert_allclose(out[1]["w"], 2.0)
    np.testing.assert_allclose(out[2]["w"], 0.0)


def test_fedavg_aggregate_mean():
    thetas = [{"w": jnp.full((2,), float(i))} for i in range(4)]
    out = fedavg_aggregate(thetas)
    np.testing.assert_allclose(out["w"], 1.5)


def test_rehearsal_memory_nearest_mean_and_capacity():
    mem = PrototypeMemory(capacity=20, per_identity=2)
    rng = np.random.default_rng(0)
    for task in range(5):
        protos = rng.standard_normal((30, 8)).astype(np.float32)
        labels = np.repeat(np.arange(3) + 10 * task, 10)
        outputs = protos.copy()    # identity adaptive layer
        mem.add_task(protos, labels, outputs, task_id=task)
        assert len(mem) <= 20
    # per-identity cap respected at insert time
    mem2 = PrototypeMemory(capacity=100, per_identity=2)
    protos = rng.standard_normal((10, 4)).astype(np.float32)
    labels = np.zeros(10, np.int64)
    mem2.add_task(protos, labels, protos, task_id=0)
    assert len(mem2) == 2
    # stored exemplars are the nearest to the mean
    center = protos.mean(0)
    d = np.linalg.norm(protos - center, axis=1)
    expected = set(np.argsort(d)[:2].tolist())
    got = {int(np.nonzero((protos == p).all(1))[0][0]) for p in mem2.protos}
    assert got == expected


def test_rehearsal_sample():
    mem = PrototypeMemory(capacity=50, per_identity=5)
    rng = np.random.default_rng(1)
    protos = rng.standard_normal((40, 6)).astype(np.float32)
    labels = np.repeat(np.arange(4), 10)
    mem.add_task(protos, labels, protos, task_id=0)
    out = mem.sample(rng, 8)
    assert out is not None
    x, y = out
    assert len(x) == 8 and len(y) == 8


def test_tying_loss():
    th = {"w": jnp.array([1.0, 2.0])}
    prev = {"w": jnp.array([1.0, 1.0])}
    assert float(tying_loss(th, prev, lam_l1=1.0)) == pytest.approx(1.0)
    assert float(tying_loss(th, th, lam_l1=1.0)) == 0.0


@pytest.mark.parametrize("n", [1, 5, 64, 100])
def test_pairwise_sum_same_bits_in_numpy_and_jax(n):
    x = np.random.default_rng(n).standard_normal((n, 7)).astype(np.float32)
    from repro.common.precision import pairwise_sum
    got = np.asarray(pairwise_sum(jnp.asarray(x), 0))
    np.testing.assert_array_equal(got, pairwise_sum(x, 0))
    np.testing.assert_allclose(got, x.sum(0), rtol=1e-5, atol=1e-5)


def test_broadcast_rows_cotangent_is_pairwise_sum():
    """The gradient of a broadcast over rows is summed in pairwise_sum's
    order, bit for bit — not by a backend reduction."""
    import jax
    from repro.common.precision import broadcast_rows, pairwise_sum
    rng = np.random.default_rng(0)
    v = jnp.asarray(rng.standard_normal(6).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((37, 6)).astype(np.float32))
    out, vjp = jax.vjp(lambda u: broadcast_rows(u, 37), v)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.broadcast_to(np.asarray(v), (37, 6)))
    np.testing.assert_array_equal(np.asarray(vjp(g)[0]),
                                  np.asarray(pairwise_sum(g, 0)))


def test_client_gradient_independent_of_client_count():
    """A client's gradient in a vmapped step is the same whether the
    program holds 3 clients or that one alone (the sharded engine's
    per-chip client blocks against the stacked engine's whole C)."""
    import jax
    from repro.core import edge_model as EM
    cfg = EM.EdgeModelConfig(n_classes=32)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    theta = jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(keys)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 16, cfg.proto_dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 32, (3, 16)), jnp.int32)
    grad = jax.jit(jax.vmap(jax.grad(EM.ce_loss)))
    whole = grad(theta, x, y)
    one = grad(jax.tree.map(lambda l: l[1:2], theta), x[1:2], y[1:2])
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(a[1:2]), np.asarray(b))


def test_log_softmax_rows_matches_jax():
    import jax
    from repro.core.edge_model import log_softmax_rows
    z = jnp.asarray(np.random.default_rng(2).standard_normal((9, 40)) * 5,
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(log_softmax_rows(z)),
                               np.asarray(jax.nn.log_softmax(z)),
                               rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda u: log_softmax_rows(u)[:, 3].sum())(z)
    gr = jax.grad(lambda u: jax.nn.log_softmax(u)[:, 3].sum())(z)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)
