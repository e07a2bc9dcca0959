"""Pallas TPU kernel: personalized server aggregation (paper Eq. 6)

    B = W @ Θ,   W: (C, C) relevance,  Θ: (C, P) stacked client params.

P is the flattened adaptive parameter count (millions); C is small (edge
clients). W stays resident in VMEM; Θ streams in (C x p_block) tiles and
every tile is one (C,C)x(C,pb) MXU matmul — the kernel is purely
bandwidth-bound, reading each client's parameters exactly once.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.compat import default_interpret

P_BLOCK = 2048


def _agg_kernel(w_ref, t_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)          # (R, C)
    t = t_ref[...].astype(jnp.float32)          # (C, pb)
    o_ref[...] = jax.lax.dot_general(
        w, t, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def relevance_aggregate(w, thetas, *, p_block: int = P_BLOCK,
                        interpret: Optional[bool] = None):
    """w: (R, C) relevance rows; thetas: (C, P) -> (R, P). R = C in the
    classic round; R < C when the server skips zero-relevance rows."""
    if interpret is None:
        interpret = default_interpret()
    R = w.shape[0]
    C, Pn = thetas.shape
    p_block = min(p_block, max(128, Pn))
    Pp = (Pn + p_block - 1) // p_block * p_block
    tp = jnp.pad(thetas, ((0, 0), (0, Pp - Pn)))

    out = pl.pallas_call(
        _agg_kernel,
        grid=(Pp // p_block,),
        in_specs=[
            pl.BlockSpec((R, C), lambda i: (0, 0)),
            pl.BlockSpec((C, p_block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((R, p_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((R, Pp), thetas.dtype),
        interpret=interpret,
    )(w, tp)
    return out[:, :Pn]


def _normalized_w(w, row0):
    """Diagonal-masked, row-normalized relevance rows; all-zero rows stay
    zero. ``w`` holds rows row0..row0+R-1 of the (C, C) matrix, so row i's
    diagonal entry sits in column row0 + i.

    Runs inside the kernel on the full (R, C) block — C is the client
    count, tiny next to P, so recomputing it per grid step is free and
    keeps the whole Eq. 5→6 post-processing in VMEM.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + row0
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    wm = jnp.where(row == col, 0.0, w.astype(jnp.float32))
    rows = jnp.sum(wm, axis=1, keepdims=True)
    return jnp.where(rows > 0, wm / jnp.where(rows > 0, rows, 1.0), 0.0)


def _fused_kernel(row0_ref, w_ref, t_ref, o_ref, wn_ref):
    wn = _normalized_w(w_ref[...], row0_ref[0])     # (R, C) fp32
    t = t_ref[...].astype(jnp.float32)              # (C, pb)
    o_ref[...] = jax.lax.dot_general(
        wn, t, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)
    wn_ref[...] = wn                                # idempotent per grid step


def fused_relevance_aggregate(w, thetas, row0=0, *, p_block: int = P_BLOCK,
                              interpret: Optional[bool] = None):
    """One fused device program for the server round's Eq. 5→6 tail:
    diagonal masking, row normalization (zero-row safe), and B = Wn @ Θ.

    w: (R, C) raw decayed relevance, rows row0..row0+R-1 of the (C, C)
    matrix (R = C, row0 = 0 for the whole matrix; the sharded engine
    passes each device's row block; diagonal ignored); thetas: (C, P).
    Returns (B: (R, P), Wn: (R, C) fp32 normalized relevance). Each row's
    result does not depend on which block it rides in.
    """
    if interpret is None:
        interpret = default_interpret()
    R = w.shape[0]
    C, Pn = thetas.shape
    p_block = min(p_block, max(128, Pn))
    Pp = (Pn + p_block - 1) // p_block * p_block
    tp = jnp.pad(thetas, ((0, 0), (0, Pp - Pn)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Pp // p_block,),
        in_specs=[
            pl.BlockSpec((R, C), lambda i, r0: (0, 0)),
            pl.BlockSpec((C, p_block), lambda i, r0: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((R, p_block), lambda i, r0: (0, i)),
            pl.BlockSpec((R, C), lambda i, r0: (0, 0)),
        ],
    )
    out, wn = pl.pallas_call(
        _fused_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, Pp), thetas.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(row0, jnp.int32), (1,)), w, tp)
    return out[:, :Pn], wn
