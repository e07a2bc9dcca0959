"""Pallas TPU kernel: batched grouped top-k sparsify + pack (wire codec).

The comm subsystem's sparsify stage keeps, within every group of ``group``
contiguous elements, the ``kg`` largest-magnitude entries (exact, ties by
lowest index) and ships them as (values, packed int32 indices) in
magnitude-rank order. The group-local budget is what makes top-k
hardware-friendly: selection is an O(group^2) counting compare per group
and packing is a one-hot reduction into a REGULAR output layout (group
b's survivors occupy slots [b*kg, (b+1)*kg)) — no global sort, no
scatter, no cross-tile communication, so the grid is embarrassingly
parallel over (client, tile). Global exact top-k lives in the host codec
(``comm.codec.topk_select_host``) where numpy's introselect is the right
tool; on the wire the two formats carry identical byte counts at the same
keep fraction.

TPU layout: the wrappers view each client row group-transposed, (group,
n_groups) — a group's members share a lane and sit on consecutive
sublanes — so ranking is a handful of whole-tile compares reduced over
sublanes, and every block is (group | kg | 8 | bits rows) x (a multiple
of 128 lanes). The transposes are plain XLA ops around the kernel.

Semantics are bit-identical to ``ref.batched_topk_pack_ref`` and to the
numpy host codec (same counting formulas), which the comm-round bench
asserts. The unpack kernel mirrors the pack (one-hot expansion per group).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

GROUP = 8
LANES = 128
NB_BLOCK = 2048      # groups (lanes) per grid step


def _lane_plan(n: int, cap: int = NB_BLOCK):
    """(lane block, padded n): the block is a multiple of 128 lanes."""
    nb = min(cap, -(-n // LANES) * LANES)
    return nb, -(-n // nb) * nb


def _grouped_t(x, n: int, group: int):
    """(C, n*group) row-major -> (C, group, n) group-transposed."""
    C = x.shape[0]
    return x.reshape(C, n, group).transpose(0, 2, 1)


def _ungrouped(x):
    """(C, rows, n) -> (C, n*rows): inverse of ``_grouped_t``."""
    C, r, n = x.shape
    return x.transpose(0, 2, 1).reshape(C, n * r)


def _pack_kernel(x_ref, v_ref, i_ref, *, group: int, kg: int):
    t = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)                       # (group, nbb)
    nbb = x.shape[1]
    a = jnp.abs(x)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)  # in-group index i
    rank = jnp.zeros(x.shape, jnp.int32)
    for j in range(group):                                 # j beats i?
        aj = a[j:j + 1]
        beats = jnp.logical_or(aj > a, jnp.logical_and(aj == a, j < row))
        rank = rank + beats.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    gidx = (t * nbb + lane) * group + row                  # absolute index
    for r in range(kg):
        oh = rank == r
        v_ref[0, r:r + 1, :] = jnp.sum(x * oh.astype(jnp.float32), axis=0,
                                       keepdims=True)
        i_ref[0, r:r + 1, :] = jnp.sum(gidx * oh.astype(jnp.int32), axis=0,
                                       keepdims=True)


def batched_topk_pack(x, *, group: int = GROUP, kg: int,
                      nb_block: int = NB_BLOCK,
                      interpret: Optional[bool] = None):
    """(C, P) -> (values (C, nb*kg) fp32, indices (C, nb*kg) int32),
    nb = ceil(P/group): every group keeps its kg largest magnitudes."""
    if interpret is None:
        interpret = default_interpret()
    C, P = x.shape
    nb = -(-P // group)
    nbb, nbp = _lane_plan(nb, nb_block)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, nbp * group - P)))

    vals, idx = pl.pallas_call(
        functools.partial(_pack_kernel, group=group, kg=kg),
        grid=(C, nbp // nbb),
        in_specs=[pl.BlockSpec((1, group, nbb), lambda c, t: (c, 0, t))],
        out_specs=[
            pl.BlockSpec((1, kg, nbb), lambda c, t: (c, 0, t)),
            pl.BlockSpec((1, kg, nbb), lambda c, t: (c, 0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C, kg, nbp), jnp.float32),
            jax.ShapeDtypeStruct((C, kg, nbp), jnp.int32),
        ],
        interpret=interpret,
    )(_grouped_t(xp, nbp, group))
    K = nb * kg
    return _ungrouped(vals)[:, :K], _ungrouped(idx)[:, :K]


def _bitpack_kernel(i_ref, o_ref, *, group: int, kg: int, k: int,
                    bits: int):
    t = pl.program_id(1)
    ix = i_ref[0]                                          # (8, kbb) int32
    kbb = ix.shape[1]
    m = jax.lax.broadcasted_iota(jnp.int32, ix.shape, 0)   # bit within byte
    s = (t * kbb + jax.lax.broadcasted_iota(jnp.int32, ix.shape, 1)) * 8 + m
    # local in-group index per pack slot; padding slots (s >= k) pack as 0
    li = jnp.where(s < k, ix - jax.lax.div(s, kg) * group, 0)
    for j in range(bits):
        o_ref[0, j:j + 1, :] = jnp.sum(((li >> j) & 1) << m, axis=0,
                                       keepdims=True)


def batched_idx_bitpack(x, *, group: int = GROUP, kg: int,
                        interpret: Optional[bool] = None):
    """(C, K) int32 grouped-pack indices -> (C, bits*ceil(K/8)) uint8
    bitplanes, bits = ceil(log2(group)): only the 3-bit (at group=8) local
    index per slot crosses the wire; the absolute index is slot-position
    arithmetic. Bitplane-major layout (plane j = bit j of every slot, 8
    slots per byte) keeps the kernel pure shift/mask/reduce — no gather.
    Bit-identical to ``ref.batched_idx_bitpack_ref``."""
    if interpret is None:
        interpret = default_interpret()
    C, K = x.shape
    bits = (group - 1).bit_length()
    kb = -(-K // 8)
    kbb, kbp = _lane_plan(kb)
    xp = jnp.pad(x, ((0, 0), (0, kbp * 8 - K)))
    planes = pl.pallas_call(
        functools.partial(_bitpack_kernel, group=group, kg=kg, k=K,
                          bits=bits),
        grid=(C, kbp // kbb),
        in_specs=[pl.BlockSpec((1, 8, kbb), lambda c, t: (c, 0, t))],
        out_specs=pl.BlockSpec((1, bits, kbb), lambda c, t: (c, 0, t)),
        out_shape=jax.ShapeDtypeStruct((C, bits, kbp), jnp.int32),
        interpret=interpret,
    )(_grouped_t(xp, kbp, 8))
    return planes[:, :, :kb].reshape(C, bits * kb).astype(jnp.uint8)


def _bitunpack_kernel(p_ref, o_ref, *, group: int, kg: int, bits: int):
    t = pl.program_id(1)
    b = p_ref[0]                                           # (bits, kbb)
    kbb = b.shape[1]
    shape = (8, kbb)
    m = jax.lax.broadcasted_iota(jnp.int32, shape, 0)      # bit within byte
    li = jnp.zeros(shape, jnp.int32)
    for j in range(bits):
        li = li + (((b[j:j + 1] >> m) & 1) << j)
    s = (t * kbb + jax.lax.broadcasted_iota(jnp.int32, shape, 1)) * 8 + m
    o_ref[0] = jax.lax.div(s, kg) * group + li


def batched_idx_bitunpack(packed, *, k: int, group: int = GROUP, kg: int,
                          interpret: Optional[bool] = None):
    """Inverse of ``batched_idx_bitpack``: uint8 bitplanes -> (C, k) int32
    absolute indices ((slot // kg) * group + local index)."""
    if interpret is None:
        interpret = default_interpret()
    C = packed.shape[0]
    bits = (group - 1).bit_length()
    kb = packed.shape[1] // bits
    kbb, kbp = _lane_plan(kb)
    pk = jnp.pad(packed.astype(jnp.int32).reshape(C, bits, kb),
                 ((0, 0), (0, 0), (0, kbp - kb)))
    out = pl.pallas_call(
        functools.partial(_bitunpack_kernel, group=group, kg=kg, bits=bits),
        grid=(C, kbp // kbb),
        in_specs=[pl.BlockSpec((1, bits, kbb), lambda c, t: (c, 0, t))],
        out_specs=pl.BlockSpec((1, 8, kbb), lambda c, t: (c, 0, t)),
        out_shape=jax.ShapeDtypeStruct((C, 8, kbp), jnp.int32),
        interpret=interpret,
    )(pk)
    return _ungrouped(out)[:, :k]


def _unpack_kernel(v_ref, i_ref, o_ref, *, group: int, kg: int):
    t = pl.program_id(1)
    v = v_ref[0].astype(jnp.float32)                       # (kg, nbb)
    ix = i_ref[0]                                          # (kg, nbb)
    nbb = v.shape[1]
    shape = (group, nbb)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)    # in-group index
    base = (t * nbb + jax.lax.broadcasted_iota(jnp.int32, shape, 1)) * group
    dense = jnp.zeros(shape, jnp.float32)
    for r in range(kg):
        oh = (ix[r:r + 1] - base) == row
        dense = dense + v[r:r + 1] * oh.astype(jnp.float32)
    o_ref[0] = dense


def batched_topk_unpack(vals, idx, *, p: int, group: int = GROUP, kg: int,
                        nb_block: int = NB_BLOCK,
                        interpret: Optional[bool] = None):
    """Inverse of ``batched_topk_pack``: one-hot expand (C, nb*kg) values
    back into dense (C, p) fp32 rows (dropped entries zero)."""
    if interpret is None:
        interpret = default_interpret()
    C, K = vals.shape
    nb = -(-p // group)
    nbb, nbp = _lane_plan(nb, nb_block)
    Kp = nbp * kg
    vp = jnp.pad(vals.astype(jnp.float32), ((0, 0), (0, Kp - K)))
    # padded slots carry value 0 and index -1: -1 can never equal a local
    # in-group index (0..group-1), so they contribute nothing even in the
    # first tile (index 0 would alias group 0's first element there)
    ip = jnp.pad(idx, ((0, 0), (0, Kp - K)), constant_values=-1)

    out = pl.pallas_call(
        functools.partial(_unpack_kernel, group=group, kg=kg),
        grid=(C, nbp // nbb),
        in_specs=[
            pl.BlockSpec((1, kg, nbb), lambda c, t: (c, 0, t)),
            pl.BlockSpec((1, kg, nbb), lambda c, t: (c, 0, t)),
        ],
        out_specs=pl.BlockSpec((1, group, nbb), lambda c, t: (c, 0, t)),
        out_shape=jax.ShapeDtypeStruct((C, group, nbp), jnp.float32),
        interpret=interpret,
    )(_grouped_t(vp, nbp, kg), _grouped_t(ip, nbp, kg))
    return _ungrouped(out)[:, :p]
