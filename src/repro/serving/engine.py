"""Online retrieval engine: fixed-shape batched top-k over the resident index.

Query contract (shared by the int8 fast path, the fp32 exact path, and the
numpy host oracle):

  1. featurize: frozen-BN forward (``edge_model.adaptive_forward_frozen``
     with the index's ``bn_mu``/``bn_sd``) + L2 normalization — identical
     to how the gallery rows were featurized at refresh, and independent
     of batch composition;
  2. score: squared euclidean distance to every resident row (int8 path
     dequantizes via per-row scale + precomputed norms inside the
     ``batched_int8_pairwise_dist`` kernel);
  3. rank: empty slots pushed to +inf, then top-k on negated distances,
     exact and in two stages that never sort all G rows: each block of
     128 contiguous rows (the lane width) is reduced to its best element,
     ``lax.top_k`` picks the k best blocks, and a second ``lax.top_k``
     ranks those blocks' k·128 rows, gathered in ascending row order.
     Blocks compare in ``lax.top_k``'s own total float order (-0.0 below
     +0.0), ties to the lower block, so ids and distances are
     bit-identical to one ``lax.top_k`` over all G rows: ties resolve to
     the lowest gallery index — the same deterministic order as the numpy
     oracle's stable argsort. Shapes with ``G % 128 != 0`` or
     ``G // 128 <= k`` take that one ``lax.top_k`` (``topk_rows``);
  4. mask: invalid query slots (padding from the continuous batcher)
     return id -1.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import register_program, register_runtime
from repro.core import edge_model as EM
from repro.kernels import ops
from repro.obs import trace as obs
from repro.serving.index import GalleryIndex, _l2n

_PAD_DIST = 1e30
_K = 10                                    # abstract / default top-k
_TOPK_BLOCK = 128                          # rows per block of the top-k


def _featurize(theta, bn_mu, bn_sd, qp):
    return _l2n(jax.vmap(EM.adaptive_forward_frozen)(theta, qp, bn_mu, bn_sd))


def topk_rows(G: int, k: int) -> int:
    """Rows per query that enter the final ``lax.top_k`` of ``_rank_topk``:
    k·``_TOPK_BLOCK`` on the blocked path, G on the plain one."""
    if G % _TOPK_BLOCK == 0 and G // _TOPK_BLOCK > k:
        return k * _TOPK_BLOCK
    return G


def _order_key(x):
    """f32 -> s32 whose signed order is ``lax.top_k``'s total float order
    (-0.0 below +0.0, NaNs at the ends)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _blocked_top_k(x, k):
    """``lax.top_k(x, k)`` over the last axis of (C, B, G), bit for bit,
    sorting G/128 block keys and k·128 candidates instead of G rows."""
    C, B, G = x.shape
    L = _TOPK_BLOCK
    xb = x.reshape(C, B, G // L, L)
    _, blk = jax.lax.top_k(jnp.max(_order_key(xb), axis=-1), k)
    blk = jnp.sort(blk, axis=-1)             # candidates in global order
    cand = jax.vmap(jax.vmap(lambda r, b: r[b]))(xb, blk)   # (C, B, k, L)
    gidx = blk[..., None] * L + jnp.arange(L, dtype=blk.dtype)
    val, pos = jax.lax.top_k(cand.reshape(C, B, k * L), k)
    return val, jnp.take_along_axis(gidx.reshape(C, B, k * L), pos, axis=-1)


def _rank_topk(dist, gids, qmask, k):
    """(C, B, G) distances -> ((C, B, k) ids, (C, B, k) distances)."""
    C, B, G = dist.shape
    dist = jnp.where((gids >= 0)[:, None, :], dist, _PAD_DIST)
    if topk_rows(G, k) < G:
        negd, idx = _blocked_top_k(-dist, k)
    else:
        negd, idx = jax.lax.top_k(-dist, k)
    ids = jnp.take_along_axis(gids, idx.reshape(C, B * k),
                              axis=1).reshape(C, B, k)
    ids = jnp.where(qmask[..., None] > 0, ids, -1)
    return ids, -negd


def _query_abstract(int8: bool):
    cfg = EM.EdgeModelConfig()
    theta = jax.eval_shape(
        lambda key: EM.init_adaptive_layers(key, cfg), jax.random.PRNGKey(0))
    C, B, G = 8, 32, 4096
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype), theta)
    S = jax.ShapeDtypeStruct
    common = (stacked, S((C, cfg.feat_dim), jnp.float32),
              S((C, cfg.feat_dim), jnp.float32),
              S((C, B, cfg.proto_dim), jnp.float32),
              S((C, B), jnp.float32))
    if int8:
        gal = (S((C, G, cfg.feat_dim), jnp.int8), S((C, G), jnp.float32),
               S((C, G), jnp.float32), S((C, G), jnp.int32))
    else:
        gal = (S((C, G, cfg.feat_dim), jnp.float32), S((C, G), jnp.int32))
    return (common + gal, {"k": _K, "backend": "ref"})


@register_program(
    "serving.query_int8",
    abstract_args=lambda: _query_abstract(True),
    oracle="repro.serving.engine.query_host", budget_bytes=64 << 20)
@functools.partial(jax.jit, static_argnames=("k", "backend"))
def query_int8_program(theta, bn_mu, bn_sd, qp, qmask, gq, gscale, gn2,
                       gids, *, k: int, backend: str = None):
    """The serving fast path: (C, B, proto_dim) padded query batch against
    the int8 resident gallery -> top-k ids + squared distances."""
    qf = _featurize(theta, bn_mu, bn_sd, qp)
    dist = ops.batched_int8_pairwise_dist(qf, gq, gscale, gn2,
                                          backend=backend)
    return _rank_topk(dist, gids, qmask, k)


@register_program(
    "serving.query_fp32",
    abstract_args=lambda: _query_abstract(False),
    oracle="repro.serving.engine.query_host", budget_bytes=64 << 20)
@functools.partial(jax.jit, static_argnames=("k", "backend"))
def query_fp32_program(theta, bn_mu, bn_sd, qp, qmask, gf, gids, *,
                       k: int, backend: str = None):
    """Exact-path twin of ``query_int8_program`` over the fp32 rows — the
    on-device parity oracle for the int8 index (and the mAP-delta
    reference in the serve bench)."""
    qf = _featurize(theta, bn_mu, bn_sd, qp)
    dist = ops.batched_pairwise_dist(qf, gf, backend=backend)
    return _rank_topk(dist, gids, qmask, k)


def _query_ivf_abstract():
    cfg = EM.EdgeModelConfig()
    theta = jax.eval_shape(
        lambda key: EM.init_adaptive_layers(key, cfg), jax.random.PRNGKey(0))
    C, B, L, K = 8, 32, 64, 96
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype), theta)
    S = jax.ShapeDtypeStruct
    F = cfg.feat_dim
    return ((stacked, S((C, F), jnp.float32), S((C, F), jnp.float32),
             S((C, B, cfg.proto_dim), jnp.float32), S((C, B), jnp.float32),
             S((C, L, F), jnp.float32), S((C, L), jnp.float32),
             S((C, L, K, F), jnp.int8), S((C, L, 3, K), jnp.float32)),
            {"k": _K, "nprobe": 8, "backend": "ref"})


@register_program(
    "serving.query_ivf",
    abstract_args=_query_ivf_abstract,
    oracle="repro.serving.engine.query_ivf_host", budget_bytes=64 << 20)
@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "backend", "with_metrics"))
def query_ivf_program(theta, bn_mu, bn_sd, qp, qmask, cent, cn2, bq, pack,
                      *, k: int, nprobe: int, backend: str = None,
                      with_metrics: bool = False):
    """The approximate serving path: featurize -> nearest ``nprobe``
    coarse buckets (``batched_cluster_assign``) -> score only those
    buckets' int8 rows (``batched_ivf_shortlist``) -> top-k. Scores
    nprobe*bcap rows per query instead of G (~sqrt(G)-fold less GEMM at
    nlist ~ sqrt(G)); distances are the same |q|^2 + |g|^2 - 2 q.g as the
    exact int8 path, so recall@k vs that path is the fidelity metric.

    ``with_metrics=True`` (the tracing specialization, registered as
    ``serving.query_ivf_metrics``) additionally returns per-client
    rows-scored counts and the probe-rank histogram of the final top-k —
    computed inside this same launch (hit mass at the last probe ranks
    means nprobe is too small for the workload)."""
    qf = _featurize(theta, bn_mu, bn_sd, qp)
    probe = ops.batched_cluster_assign(qf, cent, cn2, nprobe=nprobe,
                                       backend=backend)
    d, ids = ops.batched_ivf_shortlist(qf, probe, bq, pack, backend=backend)
    d = d + jnp.sum(jnp.square(qf), -1)[..., None]
    d = jnp.where(ids >= 0, d, _PAD_DIST)       # empty slots out of the race
    negd, idx = jax.lax.top_k(-d, k)
    top = jnp.take_along_axis(ids, idx, axis=2)
    top = jnp.where(qmask[..., None] > 0, top, -1)
    if not with_metrics:
        return top, -negd
    from repro.obs.metrics import ivf_metrics
    mets = ivf_metrics(ids, qmask, idx, bq.shape[2], nprobe)
    return top, -negd, mets


def _query_ivf_metrics_abstract():
    args, kw = _query_ivf_abstract()
    return args, {**kw, "with_metrics": True}


register_runtime(
    "serving.query_ivf_metrics",
    functools.partial(query_ivf_program, with_metrics=True),
    abstract_args=_query_ivf_metrics_abstract,
    module="repro.serving.engine",
    oracle="repro.serving.engine.query_ivf_host",
    budget_bytes=64 << 20)


def query_ivf_host(theta, bn_mu, bn_sd, qp, qmask, cent, cn2, bq, pack, *,
                   k: int, nprobe: int, backend: str = None):
    """Numpy oracle for ``query_ivf_program``: same features, nearest
    nprobe centroids by stable argsort, dequantized bucket rows scored
    exactly, empty slots masked, stable top-k."""
    del backend
    t = jax.tree_util.tree_map(np.asarray, theta)
    bn_mu, bn_sd = np.asarray(bn_mu), np.asarray(bn_sd)
    qp, qmask = np.asarray(qp, np.float32), np.asarray(qmask)
    cent, cn2 = np.asarray(cent, np.float32), np.asarray(cn2, np.float32)
    bq, pack = np.asarray(bq), np.asarray(pack, np.float32)
    C, B, _ = qp.shape
    K = bq.shape[2]
    ids = np.full((C, B, k), -1, np.int32)
    dd = np.full((C, B, k), _PAD_DIST, np.float32)
    for c in range(C):
        tc = jax.tree_util.tree_map(lambda a: a[c], t)
        h = np.maximum(qp[c] @ tc["l1"]["w"] + tc["l1"]["b"], 0.0)
        f = h @ tc["l2"]["w"] + tc["l2"]["b"]
        f = (f - bn_mu[c]) / bn_sd[c] * tc["bn"]["scale"] + tc["bn"]["bias"]
        f = f / np.sqrt(np.maximum(np.sum(np.square(f), -1, keepdims=True),
                                   1e-12))
        f = f.astype(np.float32)
        qq = np.sum(np.square(f), -1)
        dc = (qq[:, None] + cn2[c][None, :] - 2.0 * f @ cent[c].T)
        probe = np.argsort(dc, axis=1, kind="stable")[:, :nprobe]
        bids_c = pack[c, :, 2, :].view(np.int32)
        for b in range(B):
            if qmask[c, b] <= 0:
                continue
            sl_ids = bids_c[probe[b]].reshape(-1)
            blk = bq[c][probe[b]].reshape(-1, bq.shape[-1]).astype(np.float32)
            scale = pack[c, probe[b], 0, :].reshape(-1)
            n2 = pack[c, probe[b], 1, :].reshape(-1)
            d = qq[b] + n2 - 2.0 * (blk @ f[b]) * scale
            d = np.where(sl_ids >= 0, d, _PAD_DIST).astype(np.float32)
            order = np.argsort(d, kind="stable")[:k]
            ids[c, b] = sl_ids[order]
            dd[c, b] = d[order]
    return ids, dd


def recall_at_k(ids_approx: np.ndarray, ids_exact: np.ndarray,
                qmask: Optional[np.ndarray] = None) -> float:
    """Fraction of the exact path's top-k ids that the approximate path
    also returned, averaged over valid query slots — the standard ANN
    recall@k (both inputs (..., B, k) ranked id matrices, -1 = empty)."""
    a, e = np.asarray(ids_approx), np.asarray(ids_exact)
    hit = (e[..., :, None] == a[..., None, :]).any(-1) | (e < 0)
    per_q = hit.mean(-1)
    if qmask is not None:
        per_q = per_q[np.asarray(qmask) > 0]
    return float(per_q.mean())


@functools.partial(jax.jit, static_argnames=("k",))
def _naive_query_one(theta_c, mu, sd, proto, gf_c, gids_c, *, k: int):
    """One query, one client, fp32 — the per-query dispatch baseline the
    serve bench measures the batched paths against (NOT a registered fast
    path; it exists to be beaten)."""
    qf = _l2n(EM.adaptive_forward_frozen(theta_c, proto[None], mu, sd))
    dist = ops.pairwise_dist(qf, gf_c, backend="ref")[0]
    dist = jnp.where(gids_c >= 0, dist, _PAD_DIST)
    negd, idx = jax.lax.top_k(-dist, k)
    return jnp.take(gids_c, idx), -negd


def query_host(theta, bn_mu, bn_sd, qp, qmask, gf, gids, *, k: int,
               backend: str = None):
    """Numpy retrieval oracle for both registered query programs: per
    valid query slot, frozen-BN features -> exact squared distances to the
    valid fp32 gallery rows -> stable argsort -> top-k ids. Exact-match
    ground truth for the fp32 path (same fp32 feature math, same
    lowest-index tie order); allclose reference for int8."""
    del backend
    t = jax.tree_util.tree_map(np.asarray, theta)
    bn_mu, bn_sd = np.asarray(bn_mu), np.asarray(bn_sd)
    qp, qmask = np.asarray(qp, np.float32), np.asarray(qmask)
    gf, gids = np.asarray(gf, np.float32), np.asarray(gids)
    C, B, _ = qp.shape
    ids = np.full((C, B, k), -1, np.int32)
    dd = np.full((C, B, k), _PAD_DIST, np.float32)
    for c in range(C):
        tc = jax.tree_util.tree_map(lambda a: a[c], t)
        h = np.maximum(qp[c] @ tc["l1"]["w"] + tc["l1"]["b"], 0.0)
        f = h @ tc["l2"]["w"] + tc["l2"]["b"]
        f = (f - bn_mu[c]) / bn_sd[c] * tc["bn"]["scale"] + tc["bn"]["bias"]
        f = f / np.sqrt(np.maximum(np.sum(np.square(f), -1, keepdims=True),
                                   1e-12))
        f = f.astype(np.float32)
        dist = (np.sum(np.square(f), -1)[:, None]
                + np.sum(np.square(gf[c]), -1)[None, :]
                - 2.0 * (f @ gf[c].T)).astype(np.float32)
        dist[:, gids[c] < 0] = _PAD_DIST
        order = np.argsort(dist, axis=1, kind="stable")[:, :k]
        for b in range(B):
            if qmask[c, b] > 0:
                ids[c, b] = gids[c][order[b]]
                dd[c, b] = dist[b, order[b]]
    return ids, dd


def ap_from_ranked_ids(ranked_ids: np.ndarray, qid: int) -> Optional[float]:
    """Average precision of one query given its full ranked id list
    (numpy; -1 = empty slot). Same AP semantics as evalreid: precision at
    each match, averaged; None when the gallery holds no match."""
    match = np.asarray(ranked_ids) == qid
    n = int(match.sum())
    if n == 0:
        return None
    ranks = np.nonzero(match)[0] + 1
    return float(np.mean(np.arange(1, n + 1) / ranks))


def map_from_ranked_ids(ranked_ids: np.ndarray, qids: np.ndarray,
                        qmask: Optional[np.ndarray] = None) -> float:
    """mAP over a (B, k) ranked-id matrix (k spanning the whole gallery);
    queries with no gallery match (or masked out) are dropped, matching
    ``evalreid.retrieval.evaluate_retrieval``."""
    aps = []
    for b, qid in enumerate(np.asarray(qids)):
        if qmask is not None and qmask[b] <= 0:
            continue
        ap = ap_from_ranked_ids(ranked_ids[b], int(qid))
        if ap is not None:
            aps.append(ap)
    return float(np.mean(aps)) if aps else 0.0


class RetrievalEngine:
    """Online top-k retrieval over a ``GalleryIndex``.

    ``mode="int8"`` queries the quantized resident image (the exact fast
    path); ``mode="fp32"`` queries the exact rows (requires
    ``keep_fp32=True`` on the index); ``mode="ivf"`` queries only the
    ``nprobe`` nearest coarse buckets (requires ``nlist > 0`` on the
    index — the int8 path over the same index is its recall oracle).
    ``update(theta_stacked)`` is the federated integration point: when a
    round lands a new stacked adaptive head, one jitted refresh rebuilds
    the index in place — cached prototypes, no re-extraction — and
    subsequent queries see the new head.
    """

    def __init__(self, index: GalleryIndex, theta_stacked, *, k: int = _K,
                 mode: str = "int8", nprobe: int = 8,
                 backend: Optional[str] = None, refresh: bool = True):
        if mode not in ("int8", "fp32", "ivf"):
            raise ValueError(f"unknown serving mode {mode!r}")
        if mode == "fp32" and not index.keep_fp32:
            raise ValueError("fp32 mode needs keep_fp32=True on the index")
        if mode == "ivf" and not index.nlist:
            raise ValueError("ivf mode needs nlist > 0 on the index")
        self.index = index
        self.k = k
        self.mode = mode
        self.nprobe = min(int(nprobe), index.nlist) if index.nlist else 0
        self.backend = backend
        self._naive = None
        if refresh:
            self.update(theta_stacked)
        else:
            # share an already-refreshed index (e.g. several engines/modes
            # over one resident image in the serve bench)
            if index.gq is None:
                raise ValueError("refresh=False needs a refreshed index")
            self.theta = jax.tree_util.tree_map(jnp.asarray, theta_stacked)

    @classmethod
    def from_eval_cache(cls, theta_stacked, cache, t: int, *,
                        capacity: Optional[int] = None, **kw):
        """Bootstrap serving from a simulation's ``_EvalCache``: per-client
        galleries are the cache's pre-extracted prototype assembly for
        task horizon ``t`` (exactly the eval path's galleries, never
        re-extracted)."""
        protos, ids = [], []
        for c in range(cache.bench.n_clients):
            p, y = cache.host_gallery(c, t)
            protos.append(np.asarray(p))
            ids.append(np.asarray(y))
        index = GalleryIndex(protos, ids, capacity=capacity,
                             keep_fp32=kw.pop("keep_fp32", True),
                             backend=kw.get("backend"))
        return cls(index, theta_stacked, **kw)

    def update(self, theta_stacked):
        """A federated round landed: swap the head, rebuild the index."""
        self.theta = jax.tree_util.tree_map(jnp.asarray, theta_stacked)
        with obs.span("serve.index_refresh", cat="serve",
                      mode=self.mode) as sp:
            self.index.refresh(self.theta)
            sp.sync(self.index.gq)
        self._naive = None

    def extend(self, client: int, protos, ids):
        """Append gallery rows for one client and re-land the index."""
        self.index.extend(client, protos, ids)
        self.index.refresh(self.theta)
        self._naive = None

    def query_batch(self, qp, qmask, *, k: Optional[int] = None):
        """(C, B, proto_dim) padded queries + (C, B) validity -> ((C, B, k)
        ids, distances) as numpy. ONE device launch for all clients."""
        k = self.k if k is None else k
        with obs.span("serve.upload", cat="stage",
                      h2d_bytes=4 * (np.size(qp) + np.size(qmask))) as sp:
            qp, qmask = sp.sync((jnp.asarray(qp, jnp.float32),
                                 jnp.asarray(qmask, jnp.float32)))
        with obs.span("serve.launch", cat="stage",
                      topk_rows=self._topk_rows(k)):
            ids, d = self._launch(qp, qmask, k)
        with obs.span("serve.readback", cat="stage",
                      d2h_bytes=obs.device_nbytes(ids, d)):
            return np.asarray(ids), np.asarray(d)

    def _topk_rows(self, k):
        """Rows per query entering the launch's final top-k (static)."""
        if self.mode == "ivf":
            return self.nprobe * self.index.bcap
        return topk_rows(self.index.capacity, k)

    def _launch(self, qp, qmask, k):
        """Dispatch the mode's query program (no sync): device (ids, d)."""
        ix = self.index
        if self.mode == "int8":
            return query_int8_program(
                self.theta, ix.bn_mu, ix.bn_sd, qp, qmask,
                ix.gq, ix.gscale, ix.gn2, ix.gids, k=k, backend=self.backend)
        if self.mode == "ivf":
            if obs.is_active():
                # tracing specialization: same launch also returns probe
                # hit-rates + rows-scored ("serving.query_ivf_metrics")
                ids, d, mets = query_ivf_program(
                    self.theta, ix.bn_mu, ix.bn_sd, qp, qmask,
                    ix.cent, ix.cn2, ix.bq, ix.pack, k=k,
                    nprobe=self.nprobe, backend=self.backend,
                    with_metrics=True)
                obs.metric("serve.ivf", mets, nprobe=self.nprobe)
                return ids, d
            return query_ivf_program(
                self.theta, ix.bn_mu, ix.bn_sd, qp, qmask,
                ix.cent, ix.cn2, ix.bq, ix.pack, k=k,
                nprobe=self.nprobe, backend=self.backend)
        return query_fp32_program(
            self.theta, ix.bn_mu, ix.bn_sd, qp, qmask,
            ix.gf, ix.gids, k=k, backend=self.backend)

    def query_host(self, qp, qmask, *, k: Optional[int] = None):
        """The numpy oracle at this engine's current state (always fp32)."""
        if self.index.gf is None:
            raise ValueError("host oracle needs keep_fp32=True on the index")
        return query_host(self.theta, self.index.bn_mu, self.index.bn_sd,
                          qp, qmask, self.index.gf, self.index.gids,
                          k=self.k if k is None else k)

    def query_naive(self, client: int, proto, *, k: Optional[int] = None):
        """The baseline: one fp32 query, one client, one device dispatch.
        Per-client operands are pre-sliced once so the measured loop pays
        dispatch + compute, not host tree slicing."""
        if self.index.gf is None:
            raise ValueError("naive path needs keep_fp32=True on the index")
        if self._naive is None:
            C = self.index.n_clients
            self._naive = [
                (jax.tree_util.tree_map(lambda a, c=c: a[c], self.theta),
                 self.index.bn_mu[c], self.index.bn_sd[c],
                 self.index.gf[c], self.index.gids[c]) for c in range(C)]
        tc, mu, sd, gf_c, gids_c = self._naive[client]
        ids, d = _naive_query_one(tc, mu, sd, jnp.asarray(proto, jnp.float32),
                                  gf_c, gids_c, k=self.k if k is None else k)
        return np.asarray(ids), np.asarray(d)
