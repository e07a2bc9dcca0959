"""Pallas TPU kernels for the IVF shortlist serving path.

Two kernels back the approximate-query pipeline (repro/serving, phase 2):

  * cluster distances — fp32 query batches vs each client's nlist coarse
    centroids, the same |q|^2 + |c|^2 - 2 q.c tile math as pairwise_dist
    (the dispatcher runs ``lax.top_k`` on the result to pick nprobe
    buckets; top-k is not a kernel).
  * shortlist scores — for every (client, query, probe) the kernel loads
    ONE bucket of the bucket-major int8 image plus its packed fp32
    sidecar, dequantizes in VMEM and fp32-accumulates exactly like
    int8_dist.py. Bucket selection is data dependent, so the probe ids
    ride in as a scalar-prefetch operand and the BlockSpec index maps
    read them: grid step (c, b, j) maps the bucket operand to block
    (c, probe[c, b, j]) — the gather IS the block indexing, no in-kernel
    dynamic slicing.

Bucket-major layout (built at index refresh, see serving/index.py):

    bq    (C, nlist, bcap, F) int8   bucket rows (empty slots zeroed)
    pack  (C, nlist, 3, bcap) f32    [row scale; dequant |g|^2; row id
                                      bitcast int32->f32]

The sidecar is packed into one array so a probe costs a single
contiguous block load instead of three (measured ~20% off the CPU
shortlist launch; ids are bitcast back to int32 by the dispatcher).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.compat import default_interpret

B_BLOCK = 64
L_BLOCK = 128


def _cdist_kernel(q_ref, c_ref, n2_ref, o_ref):
    q = q_ref[0]                                # (bb, F) fp32
    cent = c_ref[0]                             # (lb, F) fp32 centroids
    n2 = n2_ref[0]                              # (1, lb) |centroid|^2
    qq = jnp.sum(q * q, -1, keepdims=True)      # (bb, 1)
    dot = jax.lax.dot_general(q, cent, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0] = qq + n2 - 2.0 * dot


def batched_cluster_dist(qf, cent, cn2, *, b_block: int = B_BLOCK,
                         l_block: int = L_BLOCK,
                         interpret: Optional[bool] = None):
    """(C, B, F) fp32 queries x ((C, L, F) centroids, (C, L) sq-norms)
    -> (C, B, L) squared distances. B, L padded to block multiples; the
    norms ride as (C, 1, L) so each step's block is a lane-dense row."""
    if interpret is None:
        interpret = default_interpret()
    C, B, F = qf.shape
    L = cent.shape[1]
    b_block = min(b_block, max(8, B))
    l_block = min(l_block, max(8, L))
    Bp = (B + b_block - 1) // b_block * b_block
    Lp = (L + l_block - 1) // l_block * l_block
    qp = jnp.pad(qf, ((0, 0), (0, Bp - B), (0, 0)))
    cp = jnp.pad(cent, ((0, 0), (0, Lp - L), (0, 0)))
    np_ = jnp.pad(cn2, ((0, 0), (0, Lp - L)))[:, None, :]

    out = pl.pallas_call(
        _cdist_kernel,
        grid=(C, Bp // b_block, Lp // l_block),
        in_specs=[
            pl.BlockSpec((1, b_block, F), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((1, l_block, F), lambda c, i, j: (c, j, 0)),
            pl.BlockSpec((1, 1, l_block), lambda c, i, j: (c, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, b_block, l_block),
                               lambda c, i, j: (c, i, j)),
        out_shape=jax.ShapeDtypeStruct((C, Bp, Lp), jnp.float32),
        interpret=interpret,
    )(qp, cp, np_)
    return out[:, :B, :L]


def _shortlist_kernel(probe_ref, q_ref, bq_ref, pk_ref, o_ref):
    del probe_ref                               # consumed by the index maps
    b = pl.program_id(1)
    j = pl.program_id(2)
    q = q_ref[0, pl.ds(b, 1), :]                # (1, F) this query
    blk = bq_ref[0, 0].astype(jnp.float32)      # (bcap, F) int8 -> f32 VMEM
    s = pk_ref[0, 0, 0:1, :]                    # (1, bcap) per-row scales
    n2 = pk_ref[0, 0, 1:2, :]                   # (1, bcap) dequant |g|^2
    dot = jax.lax.dot_general(q, blk, (((1,), (1,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    o_ref[0, 0, pl.ds(j, 1), :] = n2 - 2.0 * (dot * s)


def batched_ivf_shortlist_scores(qf, probe, bq, pack, *,
                                 interpret: Optional[bool] = None):
    """(C, B, F) queries + (C, B, P) probe bucket ids against the
    bucket-major image -> (C, B, P, bcap) partial squared distances
    (|g|^2 - 2 q.g; the caller adds |q|^2 and masks empty slots).

    One grid step per (client, query, probe); the probe ids are a
    scalar-prefetch operand so the bucket/sidecar BlockSpecs can index
    blocks by ``probe[c, b, j]`` directly. The query block is the
    client's whole (B, F) batch (the step reads its one row) and the
    output block is the query's whole (P, bcap) score tile, resident
    across the probe axis (row j written at step j) — both keep every
    block's last two dims whole.
    """
    if interpret is None:
        interpret = default_interpret()
    C, B, F = qf.shape
    P = probe.shape[-1]
    bcap = bq.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C, B, P),
        in_specs=[
            pl.BlockSpec((1, B, F), lambda c, b, j, probe: (c, 0, 0)),
            pl.BlockSpec((1, 1, bcap, F),
                         lambda c, b, j, probe: (c, probe[c, b, j], 0, 0)),
            pl.BlockSpec((1, 1, 3, bcap),
                         lambda c, b, j, probe: (c, probe[c, b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, P, bcap),
                               lambda c, b, j, probe: (c, b, 0, 0)),
    )
    return pl.pallas_call(
        _shortlist_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, B, P, bcap), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(probe, qf, bq, pack)
