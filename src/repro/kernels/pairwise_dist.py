"""Pallas TPU kernel: query x gallery squared-euclidean distance matrix.

This is the ReID retrieval hot spot (paper §V: every evaluation round ranks
a cross-camera gallery for every query). dist = |q|² + |g|² − 2·q·gᵀ with
the inner product on the MXU; tiles (q_block x D) x (g_block x D).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

Q_BLOCK = 128
G_BLOCK = 128


def _dist_kernel(q_ref, g_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # (qb, D)
    g = g_ref[...].astype(jnp.float32)          # (gb, D)
    qq = jnp.sum(q * q, -1, keepdims=True)      # (qb, 1)
    gg = jnp.sum(g * g, -1)                     # (gb,)
    dot = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[...] = qq + gg[None, :] - 2.0 * dot


def pairwise_dist(q, g, *, q_block: int = Q_BLOCK, g_block: int = G_BLOCK,
                  interpret: Optional[bool] = None):
    """(Q, D) x (G, D) -> (Q, G) fp32 squared distances. Q, G padded to
    block multiples internally."""
    if interpret is None:
        interpret = default_interpret()
    Q, D = q.shape
    G = g.shape[0]
    q_block = min(q_block, max(8, Q))
    g_block = min(g_block, max(8, G))
    Qp = (Q + q_block - 1) // q_block * q_block
    Gp = (G + g_block - 1) // g_block * g_block
    qp = jnp.pad(q, ((0, Qp - Q), (0, 0)))
    gp = jnp.pad(g, ((0, Gp - G), (0, 0)))

    out = pl.pallas_call(
        _dist_kernel,
        grid=(Qp // q_block, Gp // g_block),
        in_specs=[
            pl.BlockSpec((q_block, D), lambda i, j: (i, 0)),
            pl.BlockSpec((g_block, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q_block, g_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Gp), jnp.float32),
        interpret=interpret,
    )(qp, gp)
    return out[:Q, :G]


def _bdist_kernel(q_ref, g_ref, o_ref):
    q = q_ref[0].astype(jnp.float32)            # (qb, D)
    g = g_ref[0].astype(jnp.float32)            # (gb, D)
    qq = jnp.sum(q * q, -1, keepdims=True)      # (qb, 1)
    gg = jnp.sum(g * g, -1)                     # (gb,)
    dot = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = qq + gg[None, :] - 2.0 * dot


def batched_pairwise_dist(q, g, *, q_block: int = Q_BLOCK,
                          g_block: int = G_BLOCK,
                          interpret: Optional[bool] = None):
    """(C, Q, D) x (C, G, D) -> (C, Q, G) fp32 squared distances.

    The batched-eval layout: one client per leading grid step, so evaluating
    all C clients' query-vs-gallery distance matrices is a single kernel
    launch instead of C ``pairwise_dist`` dispatches. Q, G padded to block
    multiples internally.
    """
    if interpret is None:
        interpret = default_interpret()
    C, Q, D = q.shape
    G = g.shape[1]
    q_block = min(q_block, max(8, Q))
    g_block = min(g_block, max(8, G))
    Qp = (Q + q_block - 1) // q_block * q_block
    Gp = (G + g_block - 1) // g_block * g_block
    qp = jnp.pad(q, ((0, 0), (0, Qp - Q), (0, 0)))
    gp = jnp.pad(g, ((0, 0), (0, Gp - G), (0, 0)))

    out = pl.pallas_call(
        _bdist_kernel,
        grid=(C, Qp // q_block, Gp // g_block),
        in_specs=[
            pl.BlockSpec((1, q_block, D), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, g_block, D), lambda c, i, j: (c, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, g_block),
                               lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, Qp, Gp), jnp.float32),
        interpret=interpret,
    )(qp, gp)
    return out[:, :Q, :G]
