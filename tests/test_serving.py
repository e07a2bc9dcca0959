"""Serving-path guarantees (repro.serving):

  * kernel parity: the ``batched_int8_pairwise_dist`` dispatcher's Pallas
    interpret path vs the jnp ref, and ref vs manual dequant + the fp32
    batched distance oracle;
  * index-refresh parity: the jitted refresh program vs its numpy host
    oracle (int8 codes bit-exact on CPU, dequantized rows allclose);
  * exact rank parity: the fp32 serving program returns the numpy
    retrieval oracle's ids verbatim (stable-tie order included);
  * int8 fidelity: mAP delta vs fp32 bounded on the synthetic bench;
  * exact blocked top-k: ``_rank_topk`` bit-identical to one
    ``lax.top_k`` over all gallery rows, and no full-row sort in the
    serving program's lowering;
  * batch-composition invariance (the frozen-BN contract the continuous
    batcher relies on), batcher coalescing, and incremental head updates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import edge_model as EM
from repro.kernels import ops
from repro.kernels import ref as REF
from repro.obs import trace as obs
from repro.serving import engine as E
from repro.serving import (ContinuousBatcher, GalleryIndex, RetrievalEngine,
                           map_from_ranked_ids)
from repro.serving.index import refresh_host

CFG = EM.EdgeModelConfig()


def _stack_thetas(C, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), C)
    thetas = [EM.init_adaptive_layers(k, CFG) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *thetas)


def _mk_index(C=3, G=40, seed=0, ragged=True, keep_fp32=True):
    rng = np.random.default_rng(seed)
    sizes = [G - 5 * c if ragged else G for c in range(C)]
    protos = [rng.standard_normal((n, CFG.proto_dim)).astype(np.float32)
              for n in sizes]
    ids = [rng.integers(0, 12, n).astype(np.int32) for n in sizes]
    return GalleryIndex(protos, ids, capacity=G, keep_fp32=keep_fp32), rng


@pytest.fixture(scope="module")
def engines():
    index, rng = _mk_index()
    theta = _stack_thetas(index.n_clients)
    eng8 = RetrievalEngine(index, theta, k=5, mode="int8")
    engf = RetrievalEngine(index, theta, k=5, mode="fp32")
    return index, theta, eng8, engf, rng


@pytest.mark.parametrize("C,B,G,F", [(3, 4, 40, 64), (2, 16, 300, 64),
                                     (1, 1, 7, 32)])
def test_batched_int8_pairwise_dist_parity(C, B, G, F):
    """Dispatcher ref vs interpret vs dequant+fp32-dist oracle."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(k1, (C, B, F), jnp.float32)
    g = jax.random.normal(k2, (C, G, F), jnp.float32)
    gq, scales = ops.batched_quantize(g.reshape(C, G * F), chunk=F,
                                      backend="ref")
    gq = gq.reshape(C, G, F)
    gdeq = gq.astype(jnp.float32) * scales[..., None]
    gn2 = jnp.sum(jnp.square(gdeq), -1)
    d_ref = ops.batched_int8_pairwise_dist(q, gq, scales, gn2, backend="ref")
    d_int = ops.batched_int8_pairwise_dist(q, gq, scales, gn2,
                                           backend="interpret")
    d_ora = REF.batched_pairwise_dist_ref(q, gdeq)
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_int),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_ora),
                               atol=1e-4, rtol=1e-4)


def test_index_refresh_matches_host_oracle(engines):
    index, theta, _, _, _ = engines
    gmask = (index.gids_host >= 0).astype(np.float32)
    hq, hs, hn2, hmu, hsd, hf = refresh_host(theta, index.gp, gmask)
    np.testing.assert_array_equal(hq, np.asarray(index.gq))
    np.testing.assert_allclose(hs, np.asarray(index.gscale), rtol=1e-6)
    np.testing.assert_allclose(hn2, np.asarray(index.gn2), atol=1e-5)
    np.testing.assert_allclose(hmu, np.asarray(index.bn_mu), atol=1e-5)
    np.testing.assert_allclose(hsd, np.asarray(index.bn_sd), atol=1e-5)
    np.testing.assert_allclose(hf, np.asarray(index.gf), atol=1e-5)
    # empty slots: zero codes, unit scale, zero norm
    empty = np.asarray(index.gids) < 0
    assert np.all(np.asarray(index.gq)[empty] == 0)
    assert np.all(np.asarray(index.gscale)[empty] == 1.0)
    assert np.all(np.asarray(index.gn2)[empty] == 0.0)


def test_fp32_rank_parity_exact(engines):
    """The fp32 serving program == numpy retrieval oracle, id for id."""
    _, _, _, engf, rng = engines
    C = engf.index.n_clients
    qp = rng.standard_normal((C, 7, CFG.proto_dim)).astype(np.float32)
    qmask = np.ones((C, 7), np.float32)
    qmask[0, 5:] = 0.0                       # padded slots must come back -1
    ids_d, dist_d = engf.query_batch(qp, qmask)
    ids_h, dist_h = engf.query_host(qp, qmask)
    np.testing.assert_array_equal(ids_d, ids_h)
    np.testing.assert_allclose(dist_d[qmask > 0], dist_h[qmask > 0],
                               atol=1e-5)
    assert np.all(ids_d[0, 5:] == -1)


def test_int8_close_to_fp32(engines):
    """Quantization moves distances by O(1/127) — top-1 must agree on
    well-separated synthetic data, distances allclose at lsb tolerance."""
    _, _, eng8, engf, rng = engines
    C = engf.index.n_clients
    qp = rng.standard_normal((C, 6, CFG.proto_dim)).astype(np.float32)
    qmask = np.ones((C, 6), np.float32)
    ids8, d8 = eng8.query_batch(qp, qmask)
    idsf, df = engf.query_batch(qp, qmask)
    assert (ids8[..., 0] == idsf[..., 0]).mean() >= 0.9
    np.testing.assert_allclose(d8, df, atol=0.05)


def test_int8_map_delta_bounded():
    """Tier-1 fidelity bound: full-ranking mAP, int8 vs fp32, on galleries
    with real id structure (repeated ids -> multiple matches/query)."""
    index, rng = _mk_index(C=4, G=60, seed=3)
    theta = _stack_thetas(4, seed=3)
    eng8 = RetrievalEngine(index, theta, mode="int8")
    engf = RetrievalEngine(index, theta, mode="fp32")
    G = index.capacity
    qp = rng.standard_normal((4, 10, CFG.proto_dim)).astype(np.float32)
    qmask = np.ones((4, 10), np.float32)
    qids = rng.integers(0, 12, (4, 10))
    ids8, _ = eng8.query_batch(qp, qmask, k=G)
    idsf, _ = engf.query_batch(qp, qmask, k=G)
    m8 = np.mean([map_from_ranked_ids(ids8[c], qids[c]) for c in range(4)])
    mf = np.mean([map_from_ranked_ids(idsf[c], qids[c]) for c in range(4)])
    assert mf > 0.0
    assert abs(m8 - mf) <= 0.01, f"int8 mAP delta {abs(m8 - mf):.4f}"


def test_batch_composition_invariance(engines):
    """Frozen BN stats: a query's answer is identical no matter which
    batch it is coalesced into (ids exact; distances to ulp — XLA's GEMM
    reduction order varies with the batch shape)."""
    _, _, eng8, _, rng = engines
    C = eng8.index.n_clients
    probe = rng.standard_normal(CFG.proto_dim).astype(np.float32)
    qp1 = np.zeros((C, 1, CFG.proto_dim), np.float32)
    qp1[1, 0] = probe
    m1 = np.zeros((C, 1), np.float32)
    m1[1, 0] = 1.0
    ids1, d1 = eng8.query_batch(qp1, m1)
    qp8 = rng.standard_normal((C, 8, CFG.proto_dim)).astype(np.float32)
    qp8[1, 3] = probe
    m8 = np.ones((C, 8), np.float32)
    ids8, d8 = eng8.query_batch(qp8, m8)
    np.testing.assert_array_equal(ids1[1, 0], ids8[1, 3])
    np.testing.assert_allclose(d1[1, 0], d8[1, 3], atol=1e-5)


def test_update_swaps_head(engines):
    """engine.update(new theta) == building a fresh engine from scratch
    (incremental refresh is exact), and actually changes the index."""
    index, theta, _, _, rng = engines
    C = index.n_clients
    eng = RetrievalEngine(_mk_index()[0], theta, k=5, mode="int8")
    old_gq = np.asarray(eng.index.gq).copy()
    theta2 = _stack_thetas(C, seed=9)
    eng.update(theta2)
    assert not np.array_equal(old_gq, np.asarray(eng.index.gq))
    fresh = RetrievalEngine(_mk_index()[0], theta2, k=5, mode="int8")
    np.testing.assert_array_equal(np.asarray(eng.index.gq),
                                  np.asarray(fresh.index.gq))
    qp = rng.standard_normal((C, 3, CFG.proto_dim)).astype(np.float32)
    qmask = np.ones((C, 3), np.float32)
    np.testing.assert_array_equal(eng.query_batch(qp, qmask)[0],
                                  fresh.query_batch(qp, qmask)[0])


def test_extend_appends_rows():
    # leave headroom, then extend client 0 with fresh rows under new ids
    small, rng = _mk_index(C=2, G=20, ragged=False)
    theta = _stack_thetas(2)
    small.gids_host[:, 15:] = -1             # simulate 15/20 fill
    small._fill[:] = 15
    eng = RetrievalEngine(small, theta, k=3, mode="fp32")
    new_p = rng.standard_normal((4, CFG.proto_dim)).astype(np.float32)
    eng.extend(0, new_p, np.full(4, 99, np.int32))
    assert small.fill[0] == 19
    # the new rows are retrievable: query WITH one of them
    qp = np.zeros((2, 1, CFG.proto_dim), np.float32)
    qp[0, 0] = new_p[2]
    ids, _ = eng.query_batch(qp, np.ones((2, 1), np.float32))
    assert 99 in ids[0, 0]
    with pytest.raises(ValueError):
        eng.extend(0, rng.standard_normal((5, CFG.proto_dim)), np.arange(5))


def test_batcher_coalesces_and_matches_direct(engines):
    """Tickets drain oldest-first in <= ceil(n/B) steps per client and
    return exactly what a direct query_batch returns."""
    _, _, eng8, _, rng = engines
    C = eng8.index.n_clients
    b = ContinuousBatcher(eng8, batch=4)
    protos = rng.standard_normal((9, CFG.proto_dim)).astype(np.float32)
    tickets = [b.submit(1, protos[i], qid=i) for i in range(9)]
    assert b.pending == 9
    first = b.step()
    assert len(first) == 4 and [t.qid for t in first] == [0, 1, 2, 3]
    rest = b.drain()
    assert len(rest) == 5 and b.pending == 0
    # per-ticket results == the fixed-shape direct call
    qp = np.zeros((C, 1, CFG.proto_dim), np.float32)
    for t, p in zip(tickets, protos):
        qp[1, 0] = p
        m = np.zeros((C, 1), np.float32)
        m[1, 0] = 1.0
        ids, _ = eng8.query_batch(qp, m)
        np.testing.assert_array_equal(t.ids, ids[1, 0])
        assert t.t_done >= t.t_submit


def test_map_from_ranked_ids_semantics():
    # matches at ranks 1 and 3: AP = (1/1 + 2/3)/2
    ids = np.array([[7, 2, 7, 3], [1, 2, 3, 4]])
    assert map_from_ranked_ids(ids, np.array([7, 9])) == pytest.approx(5 / 6)
    # masked-out query dropped even if it would match
    assert map_from_ranked_ids(ids, np.array([7, 1]),
                               qmask=np.array([1.0, 0.0])) == pytest.approx(5 / 6)


def _plain_rank(dist, gids, qmask, k):
    """The single-``lax.top_k`` ranking the blocked path must equal."""
    C, B, _ = dist.shape
    dist = jnp.where((gids >= 0)[:, None, :], dist, E._PAD_DIST)
    negd, idx = jax.lax.top_k(-dist, k)
    ids = jnp.take_along_axis(gids, idx.reshape(C, B * k),
                              axis=1).reshape(C, B, k)
    return jnp.where(qmask[..., None] > 0, ids, -1), -negd


def _rank_case(case, G, k):
    """(dist, gids, qmask) of one named case, C=2 cameras x B=8 queries."""
    rng = np.random.default_rng([G, k, len(case)])
    C, B = 2, 8
    dist = rng.random((C, B, G), dtype=np.float32) * 4
    gids = np.arange(G, dtype=np.int32)[None].repeat(C, 0)   # row = id
    qmask = np.ones((C, B), np.float32)
    if case == "one_block":                  # the whole top k in block 5
        dist[:, :, 5 * 128 + 7:5 * 128 + 7 + 3 * k:3] = 1e-3 * np.arange(k)
    elif case == "edge_ties":                # equal rows across block edges
        for e in (128, 256, 1024):
            dist[:, :, e - 3:e + 3] = 0.25
        dist[:, :, G - 2:] = 0.25
    elif case == "rank_ties":                # blocks rank against index
        dist += 2.0                          # order; ties among their rows
        nb = min(6, G // 128)
        for j in range(nb):
            dist[:, :, j * 128] = 0.1 * (nb - j)        # later block better
            dist[:, :, j * 128 + 60] = 0.75             # tied across blocks
    elif case == "zero_block":               # one -0.0 in a block of +0.0
        dist[:, :, :128] = 0.0
        dist[:, :, 5] = -0.0
        dist[:, :, 128:] = -0.0
    elif case == "nan_rows":                 # broken rows rank last, as
        dist[:, :, 128:1280:5] = np.nan      # lax.top_k ranks them
        dist[:, :, 130] = 1e-4
    elif case == "int_ties":                 # heavy integer ties everywhere
        dist = rng.integers(0, 3, (C, B, G)).astype(np.float32)
    elif case == "constant":
        dist[:] = 2.0
    elif case == "signed_zero":              # -0.0 and +0.0 in one block
        dist = rng.choice(np.array([0.0, -0.0, 1.0], np.float32), (C, B, G))
    elif case == "few_valid":                # fewer valid rows than k
        gids[:] = -1
        gids[0, [3, 700, G - 1]] = [3, 700, G - 1]
        gids[1, 200] = 200
    elif case == "masked":                   # padded query slots
        qmask[0, 5:] = 0.0
        qmask[1, :] = 0.0
        gids[:, rng.random(G) < 0.2] = -1
    return jnp.asarray(dist), jnp.asarray(gids), jnp.asarray(qmask)


@pytest.mark.parametrize("G,k,blocked", [(4096, 10, True), (8192, 1, True),
                                         (8192, 10, True), (4000, 10, False),
                                         (1280, 10, False), (1408, 10, True)])
@pytest.mark.parametrize("case", ["random", "one_block", "edge_ties",
                                  "rank_ties", "zero_block", "nan_rows",
                                  "int_ties", "constant", "signed_zero",
                                  "few_valid", "masked"])
def test_blocked_rank_topk_bit_identical(case, G, k, blocked):
    """``_rank_topk`` == one ``lax.top_k`` over the same masked distances:
    ids and distance bits identical, ties to the lowest gallery index. The
    blocked path runs where 128 | G and G/128 > k, the plain one elsewhere."""
    assert (E.topk_rows(G, k) < G) == blocked
    dist, gids, qmask = _rank_case(case, G, k)
    ids, d = jax.jit(E._rank_topk, static_argnums=3)(dist, gids, qmask, k)
    ids0, d0 = _plain_rank(dist, gids, qmask, k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids0))
    np.testing.assert_array_equal(np.asarray(d).view(np.int32),
                                  np.asarray(d0).view(np.int32))


def test_query_int8_lowering_has_no_full_row_sort():
    """At the serving size (C=4, B=64, G=131,072) no top-k or sort of the
    int8 query program runs over the gallery axis."""
    S = jax.ShapeDtypeStruct
    C, B, G = 4, 64, 131072
    theta = jax.eval_shape(lambda key: EM.init_adaptive_layers(key, CFG),
                           jax.random.PRNGKey(0))
    theta = jax.tree_util.tree_map(lambda s: S((C,) + s.shape, s.dtype),
                                   theta)
    F = CFG.feat_dim
    args = (theta, S((C, F), jnp.float32), S((C, F), jnp.float32),
            S((C, B, CFG.proto_dim), jnp.float32), S((C, B), jnp.float32),
            S((C, G, F), jnp.int8), S((C, G), jnp.float32),
            S((C, G), jnp.float32), S((C, G), jnp.int32))
    text = E.query_int8_program.lower(*args, k=10, backend="ref").as_text()
    ranks = [line for line in text.splitlines()
             if "chlo.top_k" in line or "stablehlo.sort" in line]
    assert any("4x64x1280xf32" in line for line in ranks)
    for line in ranks:
        assert f"x{G}x" not in line and f"x{G}>" not in line, line


def test_blocked_engine_matches_host_oracle():
    """An fp32 engine at G = 4,096 (ragged fills, so padded rows) takes the
    blocked path, records it on ``serve.launch`` and returns the numpy
    oracle's ids."""
    index, rng = _mk_index(C=2, G=4096, seed=4)
    eng = RetrievalEngine(index, _stack_thetas(2, seed=4), k=5, mode="fp32")
    qp = rng.standard_normal((2, 6, CFG.proto_dim)).astype(np.float32)
    qmask = np.ones((2, 6), np.float32)
    qmask[1, 4:] = 0.0
    tracer = obs.Tracer()
    with obs.active(tracer):
        ids_d, dist_d = eng.query_batch(qp, qmask)
    launch = [e for e in tracer.events if e.get("name") == "serve.launch"]
    assert [e["topk_rows"] for e in launch] == [5 * 128]
    ids_h, dist_h = eng.query_host(qp, qmask)
    np.testing.assert_array_equal(ids_d, ids_h)
    np.testing.assert_allclose(dist_d[qmask > 0], dist_h[qmask > 0],
                               atol=1e-5)
