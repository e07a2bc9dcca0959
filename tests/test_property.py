"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="dev-only dependency (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st
import hypothesis.extra.numpy as hnp

from repro.core import (
    PrototypeMemory,
    combine,
    init_adaptive,
    kl_similarity,
    personalized_aggregate,
)
from repro.core.similarity import cosine_similarity, euclidean_similarity
from repro.evalreid import evaluate_retrieval
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.train.optimizer import adam, apply_updates

_feat = hnp.arrays(np.float32, st.integers(2, 24),
                   elements=st.floats(-5, 5, width=32))


@settings(max_examples=40, deadline=None)
@given(_feat)
def test_kl_similarity_bounds_and_identity(x):
    a = jnp.asarray(x)
    s = float(kl_similarity(a, a))
    assert abs(s - 1.0) < 1e-4                      # Π(x, x) = 1
    b = a + 1.0                                     # softmax-invariant shift
    assert abs(float(kl_similarity(a, b)) - 1.0) < 1e-4


@settings(max_examples=40, deadline=None)
@given(_feat, st.floats(-5, 5, width=32))
def test_similarities_in_unit_interval(x, shift):
    a = jnp.asarray(x)
    b = a[::-1] + shift
    for fn in (kl_similarity, cosine_similarity, euclidean_similarity):
        s = float(fn(a, b))
        assert -1e-5 <= s <= 1.0 + 1e-5


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 6), st.integers(1, 32))
def test_aggregation_convexity(c, p):
    """Row-stochastic W keeps aggregated params inside the convex hull."""
    rng = np.random.default_rng(0)
    thetas = [{"w": jnp.asarray(rng.standard_normal(p).astype(np.float32))}
              for _ in range(c)]
    W = rng.random((c, c)).astype(np.float32)
    np.fill_diagonal(W, 0)
    W = W / W.sum(1, keepdims=True)
    out = personalized_aggregate(thetas, W)
    stacked = np.stack([np.asarray(t["w"]) for t in thetas])
    lo, hi = stacked.min(0) - 1e-5, stacked.max(0) + 1e-5
    for o in out:
        v = np.asarray(o["w"])
        assert (v >= lo).all() and (v <= hi).all()


@settings(max_examples=25, deadline=None)
@given(hnp.arrays(np.float32, st.tuples(st.integers(1, 8), st.integers(1, 8)),
                  elements=st.floats(-10, 10, width=32)))
def test_combine_linearity(b):
    """theta(B, alpha, A) is affine: zero alpha -> A; zero A, unit alpha -> B."""
    B = jnp.asarray(b)
    ones, zeros = jnp.ones_like(B), jnp.zeros_like(B)
    # atol floor: XLA flushes subnormals to zero
    np.testing.assert_allclose(combine(B, ones, zeros), B, atol=1e-30)
    np.testing.assert_allclose(combine(B, zeros, B), B, atol=1e-30)
    ad = init_adaptive(B)
    np.testing.assert_allclose(ad.theta(), B, atol=1e-30)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(4, 40), st.integers(1, 10))
def test_memory_capacity_invariant(tasks, capacity, per_id):
    mem = PrototypeMemory(capacity=capacity, per_identity=per_id)
    rng = np.random.default_rng(0)
    for t in range(tasks):
        n = 12
        protos = rng.standard_normal((n, 4)).astype(np.float32)
        labels = rng.integers(0, 3, n)
        mem.add_task(protos, labels, protos, task_id=t)
        assert len(mem) <= capacity


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 30))
def test_retrieval_perfect_and_random(q):
    """Queries identical to gallery entries retrieve themselves: mAP=R1=1."""
    rng = np.random.default_rng(q)
    feats = rng.standard_normal((q, 16)).astype(np.float32)
    ids = np.arange(q)
    m = evaluate_retrieval(feats, ids, feats, ids)
    assert m["R1"] == 1.0 and m["mAP"] >= 0.99


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_checkpoint_roundtrip(seed):
    rng = np.random.default_rng(seed)
    tree = {"a": {"w": jnp.asarray(rng.standard_normal((3, 4)).astype(np.float32))},
            "b": [jnp.arange(5), jnp.asarray(rng.standard_normal(2))]}
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_checkpoint(path, tree, metadata={"seed": seed})
        loaded, meta = load_checkpoint(path)
        assert meta["seed"] == seed
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(loaded)):
            np.testing.assert_allclose(a, b)


_payload = hnp.arrays(np.float32, st.integers(1, 200),
                      elements=st.floats(-100, 100, width=32))


@settings(max_examples=30, deadline=None)
@given(_payload)
def test_codec_raw_roundtrip_exact(x):
    """Lossless wire codec: decode(encode(tree)) is bit-exact and nbytes
    equals the dense payload size."""
    from repro.comm.codec import make_codec
    codec = make_codec("raw")
    tree = {"w": x}
    payload = codec.encode(tree)
    assert payload.nbytes == x.nbytes
    np.testing.assert_array_equal(codec.decode(payload)["w"], x)


@settings(max_examples=30, deadline=None)
@given(_payload)
def test_codec_int8_error_bound(x):
    """int8 stage: reconstruction error <= half a quantization step of
    each chunk's absmax."""
    from repro.comm.codec import make_codec
    codec = make_codec("int8", chunk=32)
    dec = codec.decode(codec.encode({"w": x}))["w"]
    n = x.size
    for o in range(0, n, 32):
        chunk = x[o:o + 32]
        bound = np.abs(chunk).max() / 127.0 * 0.5 + 1e-6
        assert np.abs(chunk - dec[o:o + 32]).max() <= bound


@settings(max_examples=30, deadline=None)
@given(hnp.arrays(np.float32, st.integers(8, 160),
                  elements=st.floats(-50, 50, width=32)),
       st.integers(1, 7))
def test_codec_grouped_topk_keeps_group_maxima(x, kg):
    """Stateless grouped top-k: within every group the surviving entries
    are the kg largest magnitudes, and the payload is deterministic."""
    from repro.comm.codec import grouped_topk_select_host
    v1, i1 = grouped_topk_select_host(x, 8, kg)
    v2, i2 = grouped_topk_select_host(x, 8, kg)
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(i1, i2)
    nb = (x.size + 7) // 8
    assert len(v1) == nb * kg
    xp = np.zeros((nb * 8,), np.float32)
    xp[:x.size] = x
    for b in range(nb):
        grp = np.abs(xp[b * 8:(b + 1) * 8])
        kept = i1[(i1 >= b * 8) & (i1 < (b + 1) * 8)] - b * 8
        assert len(kept) == kg
        dropped = np.delete(grp, kept)
        if dropped.size:
            assert grp[kept].min() >= dropped.max() - 1e-6


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 4))
def test_codec_delta_stream_converges(seed, rounds):
    """delta+topk+int8 on a static stream: reconstruction error is
    non-increasing round over round (error feedback drains the residual)."""
    from repro.comm.codec import make_codec
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(256).astype(np.float32)
    codec = make_codec("topk+int8")
    errs = []
    for _ in range(rounds + 1):
        dec = codec.decode(codec.encode({"w": x}, peer=0), peer=0)
        errs.append(float(np.abs(dec["w"] - x).max()))
    assert errs[-1] <= errs[0] + 1e-6


def test_adam_decreases_quadratic():
    opt = adam(lr=0.1)
    params = {"x": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["x"] ** 2)
    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = apply_updates(params, upd)
    assert float(loss(params)) < 1e-2 * l0
