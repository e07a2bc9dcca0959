"""Plain reference of retrieval serving over an int8 camera gallery.

Semantics (the configuration's): a gallery row's feature is the adaptive
head's output with BN statistics taken over the client's whole gallery,
L2-normalised, then quantised per row to int8 with scale absmax / 127
(round half to even); a query's feature is the same head with those
frozen statistics, L2-normalised; its answer is the k gallery rows at the
least squared euclidean distance to the dequantised rows.

The comparison, per sampled query (answers served by the program):
  * ``rank_gap`` — the widest gap, over ranks j, between the reference
    distance of the program's j-th answer (its answers sorted by the
    reference's distances) and the reference's own j-th best distance.
    Zero when the program returned the true top k; ties cost nothing.
  * ``dist_gap`` — the widest gap between the distance the program served
    with an answer and the reference distance of that row.
  * ``recall`` — the share of the reference's top k the program returned
    (approximate serving; the configuration states its floor).
A duplicate, empty or out-of-range answer reads as an infinite gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK = 512                       # queries per reference block


def _pre_bn(t, x):
    h = jax.nn.relu(jnp.matmul(x, t["l1"]["w"], precision=HI) + t["l1"]["b"])
    return jnp.matmul(h, t["l2"]["w"], precision=HI) + t["l2"]["b"]


def _bn_l2(t, f, mu, sd):
    fn = (f - mu) / sd * t["bn"]["scale"] + t["bn"]["bias"]
    return fn / jnp.sqrt(jnp.maximum(jnp.sum(fn * fn, -1, keepdims=True),
                                     1e-12))


@functools.partial(jax.jit, static_argnames=("bits",))
def _gallery(t, rows, *, bits):
    """Dequantised gallery features + the frozen BN statistics."""
    f = _pre_bn(t, rows)
    mu = jnp.mean(f, 0)
    sd = jnp.sqrt(jnp.mean(jnp.square(f - mu), 0)) + 1e-5
    g = _bn_l2(t, f, mu, sd)
    top = float(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(g), -1, keepdims=True) * (1.0 / top)
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(g / scale), -top, top) * scale, mu, sd


@functools.partial(jax.jit, static_argnames=("k",))
def _block(t, mu, sd, deq, q, ids, served, *, k):
    """One block of queries: reference top-k, and the program's answers
    read against the reference distances."""
    qf = _bn_l2(t, _pre_bn(t, q), mu, sd)
    d = (jnp.sum(qf * qf, -1)[:, None] + jnp.sum(deq * deq, -1)[None, :]
         - 2.0 * jnp.matmul(qf, deq.T, precision=HI))
    negd, top = jax.lax.top_k(-d, k)
    got = jnp.take_along_axis(d, jnp.clip(ids, 0, d.shape[1] - 1), axis=1)
    rank_gap = jnp.max(jnp.sort(got, -1) + negd, axis=-1)   # best = -negd
    dist_gap = jnp.max(jnp.abs(served - got), -1)
    hits = jnp.sum(ids[:, :, None] == top[:, None, :], (1, 2))
    return rank_gap, dist_gap, hits, top, -negd


def _blocks(heads, rows, clients, queries, bits):
    heads = jax.tree.map(np.asarray, heads)
    for c in np.unique(clients):
        t = jax.tree.map(lambda a: jnp.asarray(a[c]), heads)
        deq, mu, sd = _gallery(t, jnp.asarray(rows[c]), bits=bits)
        idx = np.flatnonzero(clients == c)
        for s in range(0, len(idx), BLOCK):
            yield t, mu, sd, deq, idx[s:s + BLOCK]


def compare(heads, rows, clients, queries, served_ids, served_dists, *,
            k: int) -> dict:
    """The numbers ``correct`` is decided on, over the sampled queries:
    ``clients`` (n,), ``queries`` (n, D), the program's ``served_ids`` and
    ``served_dists`` (n, k). See the module docstring."""
    served_ids = np.asarray(served_ids, np.int64)
    G = rows.shape[1]
    bad = ((served_ids < 0) | (served_ids >= G)).any(1) | np.array(
        [len(np.unique(r)) != k for r in served_ids])
    if served_ids.shape[1:] != (k,) or bad.any():
        return {"rank_gap": float("inf"), "dist_gap": float("inf"),
                "recall": 0.0}
    rank_gap = dist_gap = 0.0
    hits = 0
    for t, mu, sd, deq, blk in _blocks(heads, rows, clients, queries, 8):
        rg, dg, h, _, _ = _block(
            t, mu, sd, deq, jnp.asarray(queries[blk]),
            jnp.asarray(served_ids[blk], jnp.int32),
            jnp.asarray(served_dists[blk], jnp.float32), k=k)
        rank_gap = max(rank_gap, float(jnp.max(rg)))
        dist_gap = max(dist_gap, float(jnp.max(dg)))
        hits += int(jnp.sum(h))
    return {"rank_gap": rank_gap, "dist_gap": dist_gap,
            "recall": hits / (k * len(served_ids))}


def serve(heads, rows, clients, queries, *, k: int, bits: int):
    """The reference's own answers at ``bits`` (8: the reference; 4: the
    control, one precision below the configuration's int8 gallery):
    ((n, k) row ids, (n, k) distances)."""
    n = len(clients)
    ids = np.zeros((n, k), np.int64)
    dists = np.zeros((n, k), np.float32)
    zero_i = jnp.zeros((1, k), jnp.int32)
    zero_d = jnp.zeros((1, k), jnp.float32)
    for t, mu, sd, deq, blk in _blocks(heads, rows, clients, queries, bits):
        q = jnp.asarray(queries[blk])
        *_, top, d = _block(t, mu, sd, deq, q,
                            jnp.broadcast_to(zero_i, (len(blk), k)),
                            jnp.broadcast_to(zero_d, (len(blk), k)), k=k)
        ids[blk] = np.asarray(top)
        dists[blk] = np.asarray(d)
    return ids, dists
