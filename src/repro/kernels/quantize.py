"""Pallas TPU kernel: batched per-chunk int8 quantization (wire codec).

The comm subsystem's quantize stage maps every client's flattened upload
row to int8 with one fp32 scale per ``chunk`` contiguous elements:

    scale[c, j] = max(|x[c, j*chunk:(j+1)*chunk]|) * (1/127) (0 -> 1.0)
    q[c, i]     = clip(round(x[c, i] / scale), -127, 127)

x: (C, P) stacked client payloads -> (q: (C, P) int8, scales: (C, ceil(P /
chunk)) fp32). One grid step quantizes a (rows, p_block) tile of several
client rows: the row block is the whole C when C <= ROWS, else ROWS rows
(the last block may run past C; its out-of-range rows are never written
back). ``p_block`` holds 128 chunks, so the step's scales form one
lane-dense (rows, 128) tile; when the whole padded row has at most 128
chunks it is a single block and the scales tile is the whole row. Inside
the step the chunks are walked in lane-aligned static slices — chunks of
128 lanes or more one at a time, narrower chunks (``chunk`` dividing 128)
as masked segments of one 128-lane slice — so no in-kernel reshape
crosses the lane dimension. Rounding is round-half-to-even
(deterministic, bit-identical to ``ref.batched_quantize_ref``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret
from repro.common.precision import INV127

CHUNK = 256
LANES = 128          # TPU lane width: scale tiles are (rows, 128) lane-dense
ROWS = 8             # client rows per grid step when C > ROWS


def _slice_width(chunk: int) -> int:
    """Lanes per in-kernel slice: a whole chunk, or one 128-lane vreg
    holding 128 // chunk chunks."""
    if chunk % LANES == 0:
        return chunk
    if LANES % chunk == 0:
        return LANES
    raise ValueError(f"chunk={chunk} must divide {LANES} or be a multiple "
                     f"of it")


def _plan(p: int, chunk: int):
    """(p_block, padded P): 128 chunks per block, or one block for rows
    of at most 128 chunks."""
    w = _slice_width(chunk)
    nc = -(-p // chunk)
    if nc <= LANES:
        pb = -(-nc * chunk // w) * w
    else:
        pb = LANES * chunk
    return pb, -(-p // pb) * pb


def _row_block(c: int) -> int:
    return c if c <= ROWS else ROWS


def _scale(absmax):
    scale = absmax * INV127
    return jnp.where(scale > 0, scale, 1.0)   # all-zero / subnormal chunks


def _quant_kernel(x_ref, q_ref, s_ref, *, chunk: int):
    rb, pb = x_ref.shape
    w = _slice_width(chunk)
    segs = w // chunk
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    seg = jax.lax.broadcasted_iota(jnp.int32, (rb, w), 1) // chunk
    s = jnp.ones(s_ref.shape, jnp.float32)
    for t in range(pb // w):
        x = x_ref[:, t * w:(t + 1) * w].astype(jnp.float32)
        a = jnp.abs(x)
        full = jnp.zeros_like(x)
        for j in range(segs):
            m = seg == j
            sc = _scale(jnp.max(jnp.where(m, a, 0.0), axis=1, keepdims=True))
            full = jnp.where(m, sc, full)
            s = jnp.where(col == t * segs + j, sc, s)
        q = jnp.clip(jnp.round(x / full), -127.0, 127.0)
        q_ref[:, t * w:(t + 1) * w] = q.astype(jnp.int8)
    s_ref[...] = s


def batched_quantize(x, *, chunk: int = CHUNK,
                     interpret: Optional[bool] = None):
    """(C, P) fp32 -> ((C, P) int8, (C, ceil(P/chunk)) fp32 scales)."""
    if interpret is None:
        interpret = default_interpret()
    C, P = x.shape
    nc = -(-P // chunk)
    pb, Pp = _plan(P, chunk)
    rb = _row_block(C)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, Pp - P)))

    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, chunk=chunk),
        grid=(pl.cdiv(C, rb), Pp // pb),
        in_specs=[pl.BlockSpec((rb, pb), lambda c, j: (c, j))],
        out_specs=[
            pl.BlockSpec((rb, pb), lambda c, j: (c, j)),
            pl.BlockSpec((rb, pb // chunk), lambda c, j: (c, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, Pp), jnp.int8),
            jax.ShapeDtypeStruct((C, Pp // chunk), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return q[:, :P], s[:, :nc]


def _dequant_kernel(q_ref, s_ref, o_ref, *, chunk: int):
    rb, pb = q_ref.shape
    w = _slice_width(chunk)
    segs = w // chunk
    s = s_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    seg = jax.lax.broadcasted_iota(jnp.int32, (rb, w), 1) // chunk
    for t in range(pb // w):
        full = jnp.zeros((rb, w), jnp.float32)
        for j in range(segs):
            # the one scale of chunk t*segs+j: a masked row sum is exact
            sc = jnp.sum(jnp.where(col == t * segs + j, s, 0.0), axis=1,
                         keepdims=True)
            full = jnp.where(seg == j, sc, full)
        q = q_ref[:, t * w:(t + 1) * w].astype(jnp.float32)
        o_ref[:, t * w:(t + 1) * w] = q * full


def batched_dequantize(q, scales, *, chunk: int = CHUNK,
                       interpret: Optional[bool] = None):
    """Inverse of ``batched_quantize``: (C, P) int8 + scales -> (C, P) fp32."""
    if interpret is None:
        interpret = default_interpret()
    C, P = q.shape
    pb, Pp = _plan(P, chunk)
    rb = _row_block(C)
    qp = jnp.pad(q, ((0, 0), (0, Pp - P)))
    sp = jnp.pad(scales, ((0, 0), (0, Pp // chunk - scales.shape[1])),
                 constant_values=1.0)

    out = pl.pallas_call(
        functools.partial(_dequant_kernel, chunk=chunk),
        grid=(pl.cdiv(C, rb), Pp // pb),
        in_specs=[
            pl.BlockSpec((rb, pb), lambda c, j: (c, j)),
            pl.BlockSpec((rb, pb // chunk), lambda c, j: (c, j)),
        ],
        out_specs=pl.BlockSpec((rb, pb), lambda c, j: (c, j)),
        out_shape=jax.ShapeDtypeStruct((C, Pp), jnp.float32),
        interpret=interpret,
    )(qp, sp)
    return out[:, :P]
