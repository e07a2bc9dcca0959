"""Pallas TPU kernel: pairwise KL task-similarity (paper Eq. 4)

    S[i, j] = exp(-KL(softmax(a_i) || softmax(b_j)))
            = exp(-(Σ p_i log p_i − p_i · log q_j))

The cross term is a matmul (MXU); row entropies are computed once per
a-block. Tiles (n_block x D) x (m_block x D) -> (n_block x m_block).
At production scale this runs over the full spatial-temporal task-feature
history on the parameter server every round.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

N_BLOCK = 128
M_BLOCK = 128


def _kl_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    a = a - jnp.max(a, -1, keepdims=True)
    b = b - jnp.max(b, -1, keepdims=True)
    logp = a - jnp.log(jnp.sum(jnp.exp(a), -1, keepdims=True))
    logq = b - jnp.log(jnp.sum(jnp.exp(b), -1, keepdims=True))
    p = jnp.exp(logp)
    h = jnp.sum(p * logp, -1)                    # (nb,)
    cross = jax.lax.dot_general(p, logq, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    o_ref[...] = jnp.exp(-(h[:, None] - cross))


def kl_similarity(a, b, *, n_block: int = N_BLOCK, m_block: int = M_BLOCK,
                  interpret: Optional[bool] = None):
    """a: (N, D), b: (M, D) -> (N, M) fp32 similarities in (0, 1]."""
    if interpret is None:
        interpret = default_interpret()
    N, D = a.shape
    M = b.shape[0]
    n_block = min(n_block, max(8, N))
    m_block = min(m_block, max(8, M))
    Np = (N + n_block - 1) // n_block * n_block
    Mp = (M + m_block - 1) // m_block * m_block
    ap = jnp.pad(a, ((0, Np - N), (0, 0)))
    bp = jnp.pad(b, ((0, Mp - M), (0, 0)))

    out = pl.pallas_call(
        _kl_kernel,
        grid=(Np // n_block, Mp // m_block),
        in_specs=[
            pl.BlockSpec((n_block, D), lambda i, j: (i, 0)),
            pl.BlockSpec((m_block, D), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((n_block, m_block), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Mp), jnp.float32),
        interpret=interpret,
    )(ap, bp)
    return out[:N, :M]
