"""Pallas TPU kernel: fused FedSTIL adaptive combine (paper Eq. 2)

    theta = B ⊙ alpha + A

Applied to every adaptive tensor at every training step on every client —
a fused multiply-add streaming kernel (one pass over HBM instead of two for
the unfused mul+add). Arrays are flattened and tiled (8 x 1024) in VMEM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

ROWS = 8
COLS = 1024
TILE = ROWS * COLS


def _combine_kernel(b_ref, al_ref, a_ref, o_ref):
    o_ref[...] = (b_ref[...].astype(jnp.float32)
                  * al_ref[...].astype(jnp.float32)
                  + a_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def adaptive_combine(base, alpha, a, *, interpret: Optional[bool] = None):
    """Elementwise B*alpha + A for a single array of any shape."""
    if interpret is None:
        interpret = default_interpret()
    shape = base.shape
    n = base.size
    npad = (n + TILE - 1) // TILE * TILE
    def prep(x):
        return jnp.pad(jnp.ravel(x), (0, npad - n)).reshape(-1, COLS)
    bf, alf, af = prep(base), prep(alpha), prep(a)
    rows = bf.shape[0]

    out = pl.pallas_call(
        _combine_kernel,
        grid=(rows // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, COLS), lambda i: (i, 0))] * 3,
        out_specs=pl.BlockSpec((ROWS, COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, COLS), base.dtype),
        interpret=interpret,
    )(bf, alf, af)
    return jnp.ravel(out)[:n].reshape(shape)


def adaptive_combine_tree(base_tree, alpha_tree, a_tree, *,
                          interpret: Optional[bool] = None):
    """Leaf-wise Eq. 2 over a full adaptive pytree."""
    return jax.tree.map(
        lambda b, al, a: adaptive_combine(b, al, a, interpret=interpret),
        base_tree, alpha_tree, a_tree)
