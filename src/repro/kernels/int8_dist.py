"""Pallas TPU kernel: fp32 queries x int8-quantized gallery distances.

The serving hot spot (repro/serving): the resident retrieval index holds
every gallery row as int8 with one fp32 scale per row (~4x the rows of an
fp32 index under the same device-memory budget), and query batches arrive
fp32. Each grid step dequantizes one (g_block, F) int8 tile in VMEM and
runs the same |q|^2 + |g|^2 - 2 q.g tile math as kernels/pairwise_dist on
the MXU — int8 buys HBM capacity and bandwidth; the accumulate stays fp32.
Squared norms of the DEQUANTIZED rows are precomputed once at index-refresh
time and passed in, so the kernel never re-reduces the gallery:

    dist[c, b, g] = |q[c, b]|^2 + gn2[c, g] - 2 * scale[c, g] * (q . gq[c, g])
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

B_BLOCK = 128
G_BLOCK = 128


def _i8dist_kernel(q_ref, g_ref, s_ref, n2_ref, o_ref):
    q = q_ref[0].astype(jnp.float32)            # (bb, F)
    g = g_ref[0].astype(jnp.float32)            # (gb, F) int8 -> f32 in VMEM
    s = s_ref[0]                                # (1, gb) per-row scales
    n2 = n2_ref[0]                              # (1, gb) dequantized |g|^2
    qq = jnp.sum(q * q, -1, keepdims=True)      # (bb, 1)
    dot = jax.lax.dot_general(q, g, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = qq + n2 - 2.0 * (dot * s)


def batched_int8_pairwise_dist(q, gq, gscale, gn2, *,
                               b_block: int = B_BLOCK,
                               g_block: int = G_BLOCK,
                               interpret: Optional[bool] = None):
    """(C, B, F) fp32 x ((C, G, F) int8, (C, G) scales, (C, G) sq-norms)
    -> (C, B, G) fp32 squared distances to the dequantized gallery rows.

    One client per leading grid step (the serving layout: every client's
    query batch scores its own resident gallery in a single launch). B, G
    padded to block multiples internally. The per-row scales and norms
    ride as (C, 1, G) so each step's sidecar block is a lane-dense
    (1, g_block) row.
    """
    if interpret is None:
        interpret = default_interpret()
    C, B, F = q.shape
    G = gq.shape[1]
    b_block = min(b_block, max(8, B))
    g_block = min(g_block, max(8, G))
    Bp = (B + b_block - 1) // b_block * b_block
    Gp = (G + g_block - 1) // g_block * g_block
    qp = jnp.pad(q, ((0, 0), (0, Bp - B), (0, 0)))
    gp = jnp.pad(gq, ((0, 0), (0, Gp - G), (0, 0)))
    sp = jnp.pad(gscale, ((0, 0), (0, Gp - G)))[:, None, :]
    np_ = jnp.pad(gn2, ((0, 0), (0, Gp - G)))[:, None, :]

    out = pl.pallas_call(
        _i8dist_kernel,
        grid=(C, Bp // b_block, Gp // g_block),
        in_specs=[
            pl.BlockSpec((1, b_block, F), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, g_block, F), lambda c, i, j: (c, j, 0)),
            pl.BlockSpec((1, 1, g_block), lambda c, i, j: (c, 0, j)),
            pl.BlockSpec((1, 1, g_block), lambda c, i, j: (c, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, b_block, g_block),
                               lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, Bp, Gp), jnp.float32),
        interpret=interpret,
    )(qp, gp, sp, np_)
    return out[:, :B, :G]
