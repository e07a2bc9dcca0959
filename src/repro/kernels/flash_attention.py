"""Pallas TPU flash-attention (forward) — the extraction-layer hot spot.

Tiling: grid over (batch*heads, q-blocks); each program streams KV blocks
through VMEM with an online-softmax accumulator held in fp32 scratch.
Block shapes are MXU-aligned (q_block x head_dim, kv_block x head_dim with
head_dim a multiple of 128 where the config allows; the lane dim is the
head_dim so 64-wide heads still map cleanly onto the 8x128 VREG tiles).

Validated against ref.flash_attention_ref in interpret mode on CPU
(tests/test_kernels.py sweeps shapes and dtypes); on TPU the compiled
kernel runs (``interpret`` defaults to ``default_interpret()``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret

DEFAULT_Q_BLOCK = 128
DEFAULT_KV_BLOCK = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block, causal, scale,
                 q_block, seq_k):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale            # (q_block, hd)
    hd = q.shape[-1]
    n_kv = seq_k // kv_block

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * q_block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = i * kv_block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, -1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    a0 = jnp.zeros((q.shape[0], hd), jnp.float32)
    # causal: kv blocks past the diagonal contribute nothing — skip them
    if causal:
        hi = (qi + 1) * q_block
        n_live = (hi + kv_block - 1) // kv_block
        n_iter = jnp.minimum(n_live, n_kv)
    else:
        n_iter = n_kv
    m, l, acc = lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_block: int = DEFAULT_Q_BLOCK,
                    kv_block: int = DEFAULT_KV_BLOCK,
                    interpret: Optional[bool] = None):
    """q: (B,H,Sq,hd); k,v: (B,H,Sk,hd). Sq % q_block == Sk % kv_block == 0."""
    if interpret is None:
        interpret = default_interpret()
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    assert Sq % q_block == 0 and Sk % kv_block == 0
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B * H, Sq, hd)
    kf = k.reshape(B * H, Sk, hd)
    vf = v.reshape(B * H, Sk, hd)

    kernel = functools.partial(_attn_kernel, kv_block=kv_block, causal=causal,
                               scale=scale, q_block=q_block, seq_k=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // q_block),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd)
