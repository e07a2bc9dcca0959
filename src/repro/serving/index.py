"""Device-resident per-client gallery index for online ReID retrieval.

Layout (all leading-axis C = clients, fixed capacity G rows per client so
every refresh/query compiles once):

  host side (the cloud's copy, never re-extracted):
    gp         (C, G, proto_dim) fp32   gallery prototypes (Eq. 1 outputs)
    gids_host  (C, G) int32             person ids, -1 = empty slot
  device side (rebuilt by ONE jitted refresh when a federated round lands
  a new adaptive head — prototypes are reused, only the head math reruns):
    gq         (C, G, feat_dim) int8    quantized L2-normalized features
    gscale     (C, G) fp32              per-ROW symmetric scale (absmax/127)
    gn2        (C, G) fp32              |dequant(row)|^2 (kernel never
                                        re-reduces the gallery)
    gids       (C, G) int32             device copy of gids_host
    bn_mu/sd   (C, feat_dim) fp32       BN statistics frozen over each
                                        client's valid gallery rows — the
                                        query featurization uses THESE, so
                                        results are batch-composition
                                        independent (see engine/batcher)
    gf         (C, G, feat_dim) fp32    optional exact fp32 rows, kept only
                                        when the index doubles as the
                                        parity/fidelity oracle

Resident bytes per row: feat_dim + 8 (int8 codes + scale + norm) vs
4*feat_dim + 8 fp32 — ~3.7x more rows in the same device budget at
feat_dim=64 (the "4x capacity" the quantize kernel buys, less the two
fp32 sidecars).

IVF image (optional, ``nlist > 0``; built by the same refresh launch so
the coarse quantizer always matches the head that produced the rows):

    cent  (C, nlist, F) fp32        coarse centroids (k-means over the
                                    valid dequantized rows)
    cn2   (C, nlist) fp32           |centroid|^2
    bq    (C, nlist, bcap, F) int8  bucket-major copy of the row codes
                                    (empty slots zeroed)
    pack  (C, nlist, 3, bcap) fp32  [row scale; dequant |g|^2; person id
                                    bitcast int32->f32] — one contiguous
                                    sidecar load per probed bucket
    binv  (C, nlist, bcap) int32    gallery ROW index per slot (-1 empty;
                                    the build invariant: every valid row
                                    sits in exactly one slot)

Bucket shapes are static: nlist ~ sqrt(2G) centroids, bcap ~ 1.4 * G /
nlist slots (headroom over the mean occupancy; a mild count-balance
penalty in Lloyd keeps the tail under it, and overflow rows spill to
empty slots elsewhere so none are dropped — recall@k == 1.0 at
nprobe == nlist is structural, not statistical).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import register_program
from repro.common.precision import INV127, pairwise_sum, sum_of_squares
from repro.core import edge_model as EM
from repro.kernels import ops

_EPS = 1e-12


def _l2n(x):
    return x / jnp.sqrt(jnp.maximum(
        sum_of_squares(x, -1, keepdims=True), _EPS))


def _refresh_abstract():
    cfg = EM.EdgeModelConfig()
    theta = jax.eval_shape(
        lambda k: EM.init_adaptive_layers(k, cfg), jax.random.PRNGKey(0))
    C, G = 8, 4096
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype), theta)
    return ((stacked,
             jax.ShapeDtypeStruct((C, G, cfg.proto_dim), jnp.float32),
             jax.ShapeDtypeStruct((C, G), jnp.float32)),
            {"backend": "ref"})


@register_program(
    "serving.index_refresh",
    abstract_args=_refresh_abstract,
    oracle="repro.serving.index.refresh_host", budget_bytes=192 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def index_refresh_program(theta, gp, gmask, *, backend: str = None):
    """Rebuild the resident index under a (stacked) adaptive head:
    (C, G, proto_dim) prototypes + (C, G) validity -> int8 codes, per-row
    scales, dequantized squared norms, frozen BN stats, and the exact fp32
    rows (the caller drops those unless it keeps the parity oracle).

    Features are L2-normalized before quantization so every row shares the
    same dynamic range; empty slots are zeroed (scale 1, norm 0)."""
    f = jax.vmap(EM.adaptive_pre_bn)(theta, gp)
    mu, sd = jax.vmap(EM.adaptive_bn_stats)(f, gmask)
    fn = jax.vmap(EM.adaptive_bn_apply)(theta, f, mu, sd)
    fn = _l2n(fn) * gmask[..., None]
    C, G, F = fn.shape
    q8, scales = ops.batched_quantize(fn.reshape(C, G * F), chunk=F,
                                      backend=backend)
    gq = q8.reshape(C, G, F)
    gn2 = (jnp.sum(jnp.square(gq.astype(jnp.float32)), -1)
           * jnp.square(scales))
    return gq, scales, gn2, mu, sd, fn


def _ivf_build_one(deq, gmask, *, nlist: int, bcap: int, iters: int,
                   train_cap: int, balance: float):
    """Fixed-shape balanced k-means + capacity placement for ONE client.

    deq (G, F) dequantized rows, gmask (G,) validity -> (cent (nlist, F),
    cn2 (nlist,), inv (nlist, bcap) int32 row indices, -1 = empty slot).

    Everything is static-shape so the build vmaps over clients inside one
    jitted refresh: valid rows are argsort-compacted to a prefix, Lloyd
    runs over a strided subsample with a count-balance penalty
    ``balance * (est_count/target - 1)`` added to the assignment metric
    (query-time probing stays unpenalized), and placement is a stable
    sort by (bucket, row): the first bcap rows of a bucket take its
    slots, overflow rows spill — in row order — into the globally
    leftover empty slots, so every valid row lands in exactly one slot
    (nlist * bcap >= G is validated by the index)."""
    G, F = deq.shape
    valid = gmask > 0
    g_idx = jnp.arange(G, dtype=jnp.int32)
    vorder = jnp.argsort(jnp.where(valid, g_idx, G + g_idx))
    nv = jnp.maximum(jnp.sum(valid.astype(jnp.int32)), 1)
    S = min(G, train_cap)
    tpick = (jnp.arange(S, dtype=jnp.int32) * nv) // S
    train = deq[vorder[tpick]]
    tm = gmask[vorder[tpick]]               # all-invalid client -> zeros
    cpick = (jnp.arange(nlist, dtype=jnp.int32) * nv) // nlist
    cent = deq[vorder[cpick]]
    target = jnp.maximum(nv.astype(jnp.float32) / nlist, 1e-6)

    def assign_chunked(rows, cent, pen, chunk):
        n = rows.shape[0]
        pad = (-n) % chunk
        rp = jnp.pad(rows, ((0, pad), (0, 0)))
        cn2 = jnp.sum(cent * cent, -1)

        def one(cr):
            d = (jnp.sum(cr * cr, -1, keepdims=True) + cn2[None, :]
                 - 2.0 * cr @ cent.T)
            return jnp.argmin(d + pen[None, :], -1).astype(jnp.int32)

        return jax.lax.map(one, rp.reshape(-1, chunk, F)).reshape(-1)[:n]

    cnt_est = jnp.full((nlist,), 1.0) * target    # zero penalty at start
    for _ in range(iters):
        pen = balance * (cnt_est / target - 1.0)
        a = assign_chunked(train, cent, pen, 512)
        seg = jax.ops.segment_sum(train * tm[:, None], a, num_segments=nlist)
        cnt = jax.ops.segment_sum(tm, a, num_segments=nlist)
        cent = jnp.where(cnt[:, None] > 0,
                         seg / jnp.maximum(cnt[:, None], 1.0), cent)
        cnt_est = cnt * (nv.astype(jnp.float32)
                         / jnp.maximum(jnp.sum(tm), 1.0))
    pen = balance * (cnt_est / target - 1.0)
    a = assign_chunked(deq, cent, pen, 2048)
    a = jnp.where(valid, a, nlist)          # invalid rows sort past the end
    # stable sort by (bucket, row index); within-bucket rank via the
    # run-start positions (cummax of the change marks)
    skey = a * (G + 1) + g_idx
    order = jnp.argsort(skey)
    a_s = a[order]
    change = jnp.concatenate([jnp.ones((1,), bool), a_s[1:] != a_s[:-1]])
    first = jax.lax.cummax(jnp.where(change, g_idx, 0), axis=0)
    rank = g_idx - first
    valid_s = a_s < nlist
    primary = valid_s & (rank < bcap)
    NS = nlist * bcap
    slot = a_s * bcap + rank
    inv = jnp.full((NS,), -1, jnp.int32)
    inv = inv.at[jnp.where(primary, slot, NS)].set(
        jnp.where(primary, order.astype(jnp.int32), -1), mode="drop")
    # overflow rows -> leftover empty slots (count(spill) <= count(empty)
    # because NS >= G >= nv); both sides sorted ascending -> deterministic
    spill = jnp.sort(jnp.where(valid_s & ~primary,
                               order.astype(jnp.int32), G))
    empty = jnp.sort(jnp.where(inv < 0, jnp.arange(NS, dtype=jnp.int32), NS))
    npair = min(G, NS)
    ok = spill[:npair] < G
    inv = inv.at[jnp.where(ok, empty[:npair], NS)].set(
        jnp.where(ok, spill[:npair], -1), mode="drop")
    cn2 = jnp.sum(cent * cent, -1)
    return cent, cn2, inv.reshape(nlist, bcap)


def _ivf_abstract():
    cfg = EM.EdgeModelConfig()
    theta = jax.eval_shape(
        lambda k: EM.init_adaptive_layers(k, cfg), jax.random.PRNGKey(0))
    C, G = 8, 4096
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((C,) + s.shape, s.dtype), theta)
    return ((stacked,
             jax.ShapeDtypeStruct((C, G, cfg.proto_dim), jnp.float32),
             jax.ShapeDtypeStruct((C, G), jnp.float32),
             jax.ShapeDtypeStruct((C, G), jnp.int32)),
            {"nlist": 64, "bcap": 96, "iters": 4, "train_cap": 2048,
             "balance": 0.1, "backend": "ref"})


@register_program(
    "serving.index_refresh_ivf",
    abstract_args=_ivf_abstract,
    oracle="repro.serving.index.ivf_refresh_host", budget_bytes=256 << 20)
@functools.partial(jax.jit, static_argnames=(
    "nlist", "bcap", "iters", "train_cap", "balance", "backend"))
def index_refresh_ivf_program(theta, gp, gmask, gids, *, nlist: int,
                              bcap: int, iters: int, train_cap: int,
                              balance: float, backend: str = None):
    """``index_refresh_program`` + the IVF coarse quantizer, one launch:
    the flat int8 image is rebuilt exactly as in the non-IVF path (the
    exact-oracle queries keep working), then per-client k-means over the
    valid dequantized rows trains the centroids and the inverted lists
    are materialized bucket-major (codes + packed sidecar) so a probed
    bucket is one contiguous block load at query time."""
    gq, scales, gn2, mu, sd, fn = index_refresh_program(
        theta, gp, gmask, backend=backend)
    C, G, F = gq.shape
    deq = gq.astype(jnp.float32) * scales[..., None]
    cent, cn2, binv = jax.vmap(
        lambda d, m: _ivf_build_one(d, m, nlist=nlist, bcap=bcap,
                                    iters=iters, train_cap=train_cap,
                                    balance=balance))(deq, gmask)
    present = binv >= 0
    flat = jnp.maximum(binv, 0).reshape(C, nlist * bcap)
    bq = jnp.take_along_axis(gq, flat[:, :, None],
                             axis=1).reshape(C, nlist, bcap, F)
    bq = jnp.where(present[..., None], bq, 0)
    bscale = jnp.where(
        present,
        jnp.take_along_axis(scales, flat, axis=1).reshape(C, nlist, bcap),
        1.0)
    bn2 = jnp.where(
        present,
        jnp.take_along_axis(gn2, flat, axis=1).reshape(C, nlist, bcap),
        0.0)
    bids = jnp.where(
        present,
        jnp.take_along_axis(gids, flat, axis=1).reshape(C, nlist, bcap),
        -1)
    pack = jnp.stack(
        [bscale, bn2, jax.lax.bitcast_convert_type(bids, jnp.float32)],
        axis=2)
    return gq, scales, gn2, mu, sd, fn, cent, cn2, bq, pack, binv


def ivf_refresh_host(theta, gp, gmask, gids, *, nlist: int, bcap: int,
                     iters: int, train_cap: int, balance: float,
                     backend: str = None):
    """Numpy oracle for ``index_refresh_ivf_program``: the flat image via
    ``refresh_host``, then the same balanced Lloyd (same strided init,
    same penalty, same iteration count) and the same sorted placement in
    numpy. Centroids are allclose (fp reduction order differs from XLA,
    so boundary rows may flip buckets — the structural invariants, not
    bit-equal lists, are the contract); flat arrays are bit-exact."""
    del backend
    q, s, n2, mu, sd, fn = refresh_host(theta, gp, gmask)
    gids = np.asarray(gids)
    C, G, F = q.shape
    deq = q.astype(np.float32) * s[..., None]
    cents, cn2s, invs = [], [], []
    for c in range(C):
        valid = np.asarray(gmask)[c] > 0
        g_idx = np.arange(G, dtype=np.int32)
        vorder = np.argsort(np.where(valid, g_idx, G + g_idx), kind="stable")
        nv = max(int(valid.sum()), 1)
        S = min(G, train_cap)
        tpick = (np.arange(S, dtype=np.int64) * nv) // S
        train = deq[c][vorder[tpick]]
        tm = np.asarray(gmask)[c][vorder[tpick]]
        cpick = (np.arange(nlist, dtype=np.int64) * nv) // nlist
        cent = deq[c][vorder[cpick]].copy()
        target = max(nv / nlist, 1e-6)
        cnt_est = np.full(nlist, target, np.float32)
        for _ in range(iters):
            pen = balance * (cnt_est / target - 1.0)
            d = ((train * train).sum(-1)[:, None]
                 + (cent * cent).sum(-1)[None] - 2.0 * train @ cent.T)
            a = np.argmin(d + pen[None], -1)
            seg = np.zeros_like(cent)
            np.add.at(seg, a, train * tm[:, None])
            cnt = np.zeros(nlist, np.float32)
            np.add.at(cnt, a, tm)
            nz = cnt > 0
            cent[nz] = seg[nz] / cnt[nz, None]
            cnt_est = cnt * (nv / max(tm.sum(), 1.0))
        pen = balance * (cnt_est / target - 1.0)
        d = ((deq[c] * deq[c]).sum(-1)[:, None]
             + (cent * cent).sum(-1)[None] - 2.0 * deq[c] @ cent.T)
        a = np.argmin(d + pen[None], -1)
        a = np.where(valid, a, nlist)
        inv = np.full((nlist, bcap), -1, np.int32)
        spill = []
        for l in range(nlist):
            rows = np.nonzero(a == l)[0]
            inv[l, :min(len(rows), bcap)] = rows[:bcap]
            spill.extend(rows[bcap:])
        empties = np.argwhere(inv < 0)
        for r, (l, sl) in zip(sorted(spill), empties):
            inv[l, sl] = r
        cents.append(cent.astype(np.float32))
        cn2s.append((cent * cent).sum(-1).astype(np.float32))
        invs.append(inv)
    cent = np.stack(cents)
    cn2 = np.stack(cn2s)
    binv = np.stack(invs)
    present = binv >= 0
    flat = np.maximum(binv, 0).reshape(C, nlist * bcap)
    take = np.take_along_axis
    bq = np.where(present[..., None],
                  take(q, flat[:, :, None], axis=1).reshape(C, nlist, bcap, F),
                  0).astype(np.int8)
    bscale = np.where(present, take(s, flat, 1).reshape(C, nlist, bcap),
                      1.0).astype(np.float32)
    bn2 = np.where(present, take(n2, flat, 1).reshape(C, nlist, bcap),
                   0.0).astype(np.float32)
    bids = np.where(present, take(gids, flat, 1).reshape(C, nlist, bcap),
                    -1).astype(np.int32)
    pack = np.stack([bscale, bn2, bids.view(np.float32)], axis=2)
    return q, s, n2, mu, sd, fn, cent, cn2, bq, pack, binv


def refresh_host(theta, gp, gmask, *, backend: str = None):
    """Numpy oracle for ``index_refresh_program``: identical head math,
    masked BN statistics and L2 norms (summed in ``pairwise_sum``'s fixed
    order, as the program does), and per-row symmetric int8
    quantization (round-half-to-even, clip to ±127, scale 1.0 for empty
    rows) — allclose on dequantized rows, exact on shapes/masks."""
    del backend
    t = jax.tree_util.tree_map(np.asarray, theta)
    gp = np.asarray(gp, np.float32)
    gmask = np.asarray(gmask, np.float32)
    C, G, _ = gp.shape
    out_q, out_s, out_n2, out_mu, out_sd, out_f = [], [], [], [], [], []
    for c in range(C):
        tc = jax.tree_util.tree_map(lambda a: a[c], t)
        h = np.maximum(gp[c] @ tc["l1"]["w"] + tc["l1"]["b"], 0.0)
        f = h @ tc["l2"]["w"] + tc["l2"]["b"]
        m = gmask[c][:, None]
        n = max(float(gmask[c].sum()), 1.0)
        mu = pairwise_sum(f * m, 0) / n
        sd = np.sqrt(pairwise_sum(np.square(f - mu[None, :]) * m, 0) / n) \
            + 1e-5
        fn = (f - mu) / sd * tc["bn"]["scale"] + tc["bn"]["bias"]
        fn = fn / np.sqrt(np.maximum(
            sum_of_squares(fn, -1, keepdims=True), _EPS))
        fn = (fn * m).astype(np.float32)
        scale = np.abs(fn).max(-1) * np.float32(INV127)
        scale = np.where(scale > 0, scale, 1.0).astype(np.float32)
        q = np.clip(np.round(fn / scale[:, None]), -127, 127).astype(np.int8)
        n2 = (np.square(q.astype(np.float32)).sum(-1)
              * np.square(scale)).astype(np.float32)
        out_q.append(q); out_s.append(scale); out_n2.append(n2)
        out_mu.append(mu.astype(np.float32)); out_sd.append(sd.astype(np.float32))
        out_f.append(fn)
    return (np.stack(out_q), np.stack(out_s), np.stack(out_n2),
            np.stack(out_mu), np.stack(out_sd), np.stack(out_f))


class GalleryIndex:
    """Fixed-capacity per-client gallery with a device-resident int8 image.

    Host arrays are the source of truth (``extend`` appends rows there);
    the device image is (re)built by ``refresh(theta_stacked)`` — one
    jitted launch per head swap, no prototype re-extraction.
    """

    def __init__(self, protos: Sequence[np.ndarray], ids: Sequence[np.ndarray],
                 *, capacity: Optional[int] = None, keep_fp32: bool = True,
                 backend: Optional[str] = None, nlist=0,
                 bcap: Optional[int] = None, ivf_iters: int = 8,
                 ivf_train_cap: Optional[int] = None,
                 ivf_balance: float = 0.1):
        C = len(protos)
        if C == 0:
            raise ValueError("need at least one client")
        counts = [len(p) for p in protos]
        G = capacity if capacity is not None else max(max(counts), 1)
        if max(counts) > G:
            raise ValueError(f"capacity {G} < largest client gallery "
                             f"{max(counts)}")
        Dp = int(np.asarray(protos[0]).shape[-1])
        self.keep_fp32 = keep_fp32
        self.backend = backend
        # IVF shape parameters (compile-shape contract, like capacity):
        # nlist="auto" = sqrt(2G) centroids — per-query rows touched is
        # nlist (assign) + nprobe*bcap ~ nprobe*1.4*G/nlist (shortlist),
        # so the minimum sits above sqrt(G); sqrt(2G) keeps buckets big
        # enough for recall while shaving ~25% off the shortlist GEMM
        # vs sqrt(G) (measured at G=131072). bcap defaults to ~1.4x the
        # mean occupancy rounded up to 32 so the balance penalty keeps
        # nearly all buckets under capacity (spill stays ~0).
        if nlist == "auto":
            nlist = max(8, int(round((2 * G) ** 0.5)))
        self.nlist = int(nlist or 0)
        if self.nlist:
            if bcap is None:
                bcap = -(-int(1.4 * G / self.nlist) // 32) * 32
            self.bcap = int(bcap)
            if self.nlist * self.bcap < G:
                raise ValueError(
                    f"nlist*bcap = {self.nlist}*{self.bcap} < capacity {G}"
                    " — every row needs a slot")
            if self.nlist * (G + 1) >= 2 ** 31:
                raise ValueError("nlist*(G+1) overflows the int32 sort key")
            self.ivf_iters = int(ivf_iters)
            self.ivf_train_cap = int(ivf_train_cap
                                     if ivf_train_cap is not None
                                     else min(G, 32 * self.nlist))
            self.ivf_balance = float(ivf_balance)
        else:
            self.bcap = 0
        self.gp = np.zeros((C, G, Dp), np.float32)
        self.gids_host = np.full((C, G), -1, np.int32)
        self._fill = np.zeros((C,), np.int64)
        for c, (p, y) in enumerate(zip(protos, ids)):
            n = len(p)
            self.gp[c, :n] = np.asarray(p, np.float32)
            self.gids_host[c, :n] = np.asarray(y, np.int32)
            self._fill[c] = n
        # device image — populated by refresh()
        self.gq = self.gscale = self.gn2 = None
        self.bn_mu = self.bn_sd = self.gids = self.gf = None
        self.cent = self.cn2 = self.bq = self.pack = self.binv = None

    @property
    def n_clients(self) -> int:
        return self.gp.shape[0]

    @property
    def capacity(self) -> int:
        return self.gp.shape[1]

    @property
    def fill(self) -> List[int]:
        return [int(n) for n in self._fill]

    @property
    def has_ivf(self) -> bool:
        return self.nlist > 0 and self.cent is not None

    def resident_bytes(self, mode: str = "int8") -> int:
        """Device bytes of the queryable image (per all C clients):
        int8 = codes + scale + norm + ids; fp32 = rows + ids; ivf = the
        bucket-major codes + packed sidecar + centroids (queried INSTEAD
        of the flat image — nlist*bcap ~ 1.4*G slots at the same
        bytes/slot, plus the small coarse quantizer)."""
        C, G = self.gids_host.shape
        F = EM.EdgeModelConfig().feat_dim
        if mode == "int8":
            return C * G * (F + 4 + 4 + 4)
        if mode == "ivf":
            slots = self.nlist * self.bcap
            return C * (slots * (F + 12) + self.nlist * (4 * F + 4))
        return C * G * (4 * F + 4)

    def extend(self, client: int, protos: np.ndarray, ids: np.ndarray):
        """Append new gallery rows for one client (host side; the next
        ``refresh`` lands them on device). Raises when capacity is hit —
        capacity is a compile-shape contract, not a ring buffer."""
        n0 = int(self._fill[client])
        n = len(protos)
        if n0 + n > self.capacity:
            raise ValueError(f"client {client}: {n0}+{n} rows exceed "
                             f"capacity {self.capacity}")
        self.gp[client, n0:n0 + n] = np.asarray(protos, np.float32)
        self.gids_host[client, n0:n0 + n] = np.asarray(ids, np.int32)
        self._fill[client] = n0 + n

    def refresh(self, theta_stacked):
        """Swap in a new stacked adaptive head: rerun the head math over
        the cached prototypes and replace the resident image (including
        the IVF coarse quantizer when ``nlist > 0`` — one launch)."""
        gmask = (self.gids_host >= 0).astype(np.float32)
        self.gids = jnp.asarray(self.gids_host)
        if self.nlist:
            (gq, gscale, gn2, mu, sd, gf, cent, cn2, bq, pack,
             binv) = index_refresh_ivf_program(
                theta_stacked, jnp.asarray(self.gp), jnp.asarray(gmask),
                self.gids, nlist=self.nlist, bcap=self.bcap,
                iters=self.ivf_iters, train_cap=self.ivf_train_cap,
                balance=self.ivf_balance, backend=self.backend)
            self.cent, self.cn2 = cent, cn2
            self.bq, self.pack, self.binv = bq, pack, binv
        else:
            gq, gscale, gn2, mu, sd, gf = index_refresh_program(
                theta_stacked, jnp.asarray(self.gp), jnp.asarray(gmask),
                backend=self.backend)
        self.gq, self.gscale, self.gn2 = gq, gscale, gn2
        self.bn_mu, self.bn_sd = mu, sd
        self.gf = gf if self.keep_fp32 else None
        return self
