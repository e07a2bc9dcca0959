"""Batched (C clients x T tasks) retrieval evaluation.

Layout: query features are stacked into padded/masked ``(C, T, Q, F)``
arrays (one query set per trained task per client), galleries into
``(C, G, F)`` (one cross-camera gallery per client, padded to a common G).
All C x T mAP/CMC evaluations then run as ONE device program: the distance
matrices go through the ``kernels/pairwise_dist`` Pallas kernel
(``ops.batched_pairwise_dist``), and the ranking/metric math is pure jnp —
an exact replica of ``evalreid.retrieval.evaluate_retrieval``, computed
WITHOUT a full sort. A (Q, G) argsort is the numpy oracle's formulation,
but mAP/CMC only depend on each *matching* gallery item's rank, so we

  1. select each query's matches ordered by (distance, gallery index) with
     one ``lax.top_k`` (its tie rule — lower index first — is exactly the
     oracle's ``kind="stable"`` argsort order);
  2. recover every match's full-gallery rank by *counting* the gallery
     items strictly closer (or equal-distance with a lower index) — an
     exact integer count, so ties resolve identically to the stable sort;
  3. AP = mean over matches of (match position / full rank); R@k = best
     match rank <= k.

This replaces the O(G log G) comparator sort (the CPU bottleneck — XLA's
sort is serial per row) with one top-k plus an O(M·G) vectorized count,
where M = ``max_matches`` is the tiny per-query match bound.

Semantics shared with the oracle: features are L2-normalised, distances
squared euclidean; queries with no gallery match are dropped from every
average; a set with no valid query scores 0.0 across the board. Padded
gallery rows get distance ``_PAD_DIST`` (never closer than a real row) and
sentinel id -1; padded/masked queries get sentinel id -2, so padding can
never match and never shifts a real match's rank.

``evaluate_retrieval_batched(backend="host")`` is the retained numpy
oracle: a Python loop over (c, t) slices calling ``evaluate_retrieval`` on
the unpadded arrays.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.registry import register_program
from repro.common.precision import pairwise_sum, sum_of_squares
from repro.evalreid.retrieval import evaluate_retrieval
from repro.kernels import ops

_PAD_DIST = 1e30      # >> max squared distance of unit vectors (4.0)
_PAD_GID = -1
_PAD_QID = -2


def _l2n(x, eps=1e-9):
    # fixed-order norm: a backend reduction would round a client's norms
    # by the layout the whole (C, ...) program gets, i.e. by C
    n = jnp.sqrt(sum_of_squares(x, -1, keepdims=True))
    return x / jnp.maximum(n, eps)


def max_match_bound(qids, gids, *, qmask=None, gmask=None) -> int:
    """Tight host-side bound on per-query gallery matches (the static
    ``max_matches`` for ``batched_retrieval_metrics``): the most often any
    queried identity appears in its client's (valid) gallery."""
    qids, gids = np.asarray(qids), np.asarray(gids)
    best = 1
    for c in range(qids.shape[0]):
        g = gids[c] if gmask is None else gids[c][np.asarray(gmask[c]) > 0]
        q = qids[c].ravel() if qmask is None else \
            qids[c].ravel()[np.asarray(qmask[c]).ravel() > 0]
        q = q[q >= 0]
        if len(g) == 0 or len(q) == 0:
            continue
        vals, cnts = np.unique(g, return_counts=True)
        hit = np.isin(vals, q)
        if hit.any():
            best = max(best, int(cnts[hit].max()))
    return best


def batched_retrieval_metrics(qf, qids, gf, gids, *, qmask=None, gmask=None,
                              ranks: Tuple[int, ...] = (1, 3, 5),
                              backend: Optional[str] = None,
                              max_matches: Optional[int] = None):
    """Traceable batched mAP/CMC — usable inside jit / on a mesh.

    qf: (C, T, Q, F) query features; qids: (C, T, Q) identity ids;
    gf: (C, G, F) gallery features; gids: (C, G) identity ids;
    qmask: (C, T, Q) query validity (None = all valid; combine the task
    mask in here — or pre-sentinel invalid qids to a negative value);
    gmask: (C, G) gallery validity (None = all valid);
    backend: kernel backend for ``ops.batched_pairwise_dist``;
    max_matches: static upper bound on gallery matches per query (see
    ``max_match_bound``; None = G, always safe but does more counting).

    Returns {"mAP": (C, T), "R1": ..., ...} fp32 arrays, averaged over the
    valid queries of each (c, t) set (0.0 where none are valid).
    """
    C, T, Q, F = qf.shape
    G = gf.shape[1]
    M = G if max_matches is None else max(1, min(int(max_matches), G))
    qn = _l2n(qf.astype(jnp.float32))
    gn = _l2n(gf.astype(jnp.float32))
    dist = ops.batched_pairwise_dist(qn.reshape(C, T * Q, F), gn,
                                     backend=backend)
    dist = dist.reshape(C, T, Q, G)

    gids_eff = gids.astype(jnp.int32)
    if gmask is not None:
        gvalid = gmask > 0
        dist = jnp.where(gvalid[:, None, None, :], dist, _PAD_DIST)
        gids_eff = jnp.where(gvalid, gids_eff, _PAD_GID)
    qids_eff = qids.astype(jnp.int32)
    if qmask is not None:
        qids_eff = jnp.where(qmask > 0, qids_eff, _PAD_QID)

    m = gids_eff[:, None, None, :] == qids_eff[..., None]    # (C, T, Q, G)
    n_match = jnp.sum(m.astype(jnp.float32), -1)             # (C, T, Q)

    # matches in stable-sort order: top_k breaks value ties by lower index,
    # exactly the oracle's argsort(kind="stable") order among matches
    neg = jnp.where(m, -dist, -jnp.inf)
    mvals, midx = jax.lax.top_k(neg, M)                      # (C, T, Q, M)
    match_d = -mvals                                         # ascending
    mvalid = mvals > -jnp.inf                                # slot < n_match

    # full-gallery stable rank of match i: 1 + #{closer} + #{tied, earlier}
    # (padding rows sit at _PAD_DIST, never closer / never tied with a real
    # match, so they can't shift a rank — counts are exact integers)
    gdx = jnp.arange(G, dtype=jnp.int32)
    before = ((dist[..., None, :] < match_d[..., None])
              | ((dist[..., None, :] == match_d[..., None])
                 & (gdx < midx[..., None])))
    r = 1.0 + jnp.sum(before.astype(jnp.float32), -1)        # (C, T, Q, M)

    # AP = mean over matches of (position among matches) / (full rank);
    # the float sums run in pairwise_sum order (counts above are exact)
    pos = jnp.arange(1, M + 1, dtype=jnp.float32)
    ap = (pairwise_sum(jnp.where(mvalid, pos / r, 0.0), -1)
          / jnp.maximum(n_match, 1.0))                       # (C, T, Q)

    valid = n_match > 0
    vf = valid.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(vf, -1), 1.0)                  # (C, T)
    best = r[..., 0]                                         # best match rank
    out = {"mAP": pairwise_sum(ap * vf, -1) / cnt}
    for k in ranks:
        hit = (best <= k).astype(jnp.float32)
        out[f"R{k}"] = jnp.sum(hit * vf, -1) / cnt
    return out


def _metrics_abstract():
    """Bench-scale abstract eval inputs: C=100 clients x T=3 tasks."""
    S, f32, i32 = jax.ShapeDtypeStruct, jnp.float32, jnp.int32
    C, T, Q, G, F = 100, 3, 16, 96, 64
    return ((S((C, T, Q, F), f32), S((C, T, Q), i32), S((C, G, F), f32),
             S((C, G), i32), S((C, T, Q), f32), S((C, G), f32)),
            {"ranks": (1, 3, 5), "backend": "ref", "max_matches": 4})


@register_program(
    "evalreid.batched_retrieval_metrics",
    abstract_args=_metrics_abstract,
    oracle="repro.evalreid.batched._metrics_host", budget_bytes=64 << 20)
@functools.partial(jax.jit,
                   static_argnames=("ranks", "backend", "max_matches"))
def _metrics_device(qf, qids, gf, gids, qmask, gmask, *, ranks, backend,
                    max_matches):
    return batched_retrieval_metrics(qf, qids, gf, gids, qmask=qmask,
                                     gmask=gmask, ranks=ranks,
                                     backend=backend,
                                     max_matches=max_matches)


def _metrics_host(qf, qids, gf, gids, qmask, gmask, ranks):
    """The allclose oracle: per-(c, t) numpy ``evaluate_retrieval`` over
    the unpadded slices."""
    qf, qids = np.asarray(qf), np.asarray(qids)
    gf, gids = np.asarray(gf), np.asarray(gids)
    C, T = qf.shape[:2]
    keys = ["mAP"] + [f"R{k}" for k in ranks]
    out = {k: np.zeros((C, T), np.float32) for k in keys}
    for c in range(C):
        gsel = slice(None) if gmask is None else np.asarray(gmask[c]) > 0
        gfc, gic = gf[c][gsel], gids[c][gsel]
        for t in range(T):
            qsel = (slice(None) if qmask is None
                    else np.asarray(qmask[c, t]) > 0)
            qfc, qic = qf[c, t][qsel], qids[c, t][qsel]
            if len(qfc) == 0 or len(gfc) == 0:
                continue                      # all-invalid set scores 0.0
            m = evaluate_retrieval(qfc, qic, gfc, gic, ranks=ranks)
            for k in keys:
                out[k][c, t] = m[k]
    return out


def evaluate_retrieval_batched(qf, qids, gf, gids, *, qmask=None, gmask=None,
                               ranks: Tuple[int, ...] = (1, 3, 5),
                               backend: str = "device",
                               kernel_backend: Optional[str] = None,
                               max_matches: Optional[int] = None
                               ) -> Dict[str, np.ndarray]:
    """All (c, t) retrieval evaluations at once -> {"mAP": (C, T), ...}.

    ``backend="device"`` runs the single jitted program (distances through
    the Pallas kernel path selected by ``kernel_backend``);
    ``backend="host"`` is the numpy loop-over-(c, t) oracle.
    """
    if backend == "host":
        return _metrics_host(qf, qids, gf, gids, qmask, gmask, tuple(ranks))
    if backend != "device":
        raise ValueError(f"unknown eval backend {backend!r}")
    if max_matches is None:
        max_matches = max_match_bound(qids, gids, qmask=qmask, gmask=gmask)
    out = _metrics_device(
        jnp.asarray(qf), jnp.asarray(qids), jnp.asarray(gf),
        jnp.asarray(gids),
        None if qmask is None else jnp.asarray(qmask),
        None if gmask is None else jnp.asarray(gmask),
        ranks=tuple(ranks), backend=kernel_backend,
        max_matches=int(max_matches))
    return {k: np.asarray(v) for k, v in out.items()}
