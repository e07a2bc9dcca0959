"""Pallas TPU flash-attention BACKWARD + custom_vjp wiring.

Standard two-pass scheme (Dao 2022 adapted to TPU tiling):
  pass A (per q-block):  recompute p = softmax(q kᵀ), accumulate
                         dq = (p ∘ (dp − D)) k        (D = rowsum(do ∘ o))
  pass B (per kv-block): accumulate dk = (p ∘ (dp − D))ᵀ q,  dv = pᵀ do

Both passes stream the opposite operand through VMEM with fp32 accumulators;
the forward kernel additionally stores the per-row logsumexp so the backward
never re-does the online-softmax rescaling. Validated in interpret mode
against jax.grad of the jnp oracle (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.common.compat import default_interpret
from repro.kernels.flash_attention import NEG_INF

Q_BLOCK = 128
KV_BLOCK = 128


# ---------------------------------------------------------------------------
# forward that also emits the softmax stats (logsumexp per row)
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, kv_block, causal,
                scale, q_block, seq_k):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    hd = q.shape[-1]
    n_kv = seq_k // kv_block

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * q_block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = i * kv_block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, -1)
        acc_new = acc * corr[:, None] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((q.shape[0],), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    a0 = jnp.zeros((q.shape[0], hd), jnp.float32)
    if causal:
        n_iter = jnp.minimum(((qi + 1) * q_block + kv_block - 1) // kv_block,
                             n_kv)
    else:
        n_iter = n_kv
    m, l, acc = lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(jnp.maximum(l, 1e-30)))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               kv_block, causal, scale, q_block, seq_k):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]
    delta = delta_ref[...]
    n_kv = seq_k // kv_block

    def body(i, dq):
        k = k_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * kv_block, kv_block), :].astype(jnp.float32)
        s = lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * q_block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = i * kv_block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    if causal:
        n_iter = jnp.minimum(((qi + 1) * q_block + kv_block - 1) // kv_block,
                             n_kv)
    else:
        n_iter = n_kv
    dq0 = jnp.zeros_like(q)
    dq_ref[...] = lax.fori_loop(0, n_iter, body, dq0).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, q_block, causal, scale, kv_block, seq_q):
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    n_q = seq_q // q_block

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * q_block, q_block), :].astype(jnp.float32)
        do = do_ref[pl.ds(i * q_block, q_block), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * q_block, q_block)]
        delta = delta_ref[pl.ds(i * q_block, q_block)]
        s = lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            qpos = i * q_block + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * kv_block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                       # (qb, kb)
        dv_new = dv + lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_new = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk_new, dv_new

    # causal: q blocks before this kv block see nothing
    lo = (ki * kv_block) // q_block if causal else 0
    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    dk, dv = lax.fori_loop(lo, n_q, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_vjp(q, k, v, causal=True, q_block=Q_BLOCK,
                        kv_block=KV_BLOCK, interpret=None):
    out, _ = _fwd(q, k, v, causal, q_block, kv_block, interpret)
    return out


def _fwd(q, k, v, causal, q_block, kv_block, interpret):
    if interpret is None:
        interpret = default_interpret()
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    scale = 1.0 / math.sqrt(hd)
    qf, kf, vf = (t.reshape(B * H, t.shape[2], hd) for t in (q, k, v))
    kern = functools.partial(_fwd_kernel, kv_block=kv_block, causal=causal,
                             scale=scale, q_block=q_block, seq_k=Sk)
    out, lse = pl.pallas_call(
        kern,
        grid=(B * H, Sq // q_block),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, q_block), lambda b, i: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd), lse


def _fwd_rule(q, k, v, causal, q_block, kv_block, interpret):
    out, lse = _fwd(q, k, v, causal, q_block, kv_block, interpret)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, q_block, kv_block, interpret, res, do):
    q, k, v, out, lse = res
    if interpret is None:
        interpret = default_interpret()
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    scale = 1.0 / math.sqrt(hd)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), -1)
    qf, kf, vf, dof = (t.reshape(B * H, t.shape[2], hd)
                       for t in (q, k, v, do))
    deltaf = delta.reshape(B * H, Sq)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, kv_block=kv_block, causal=causal,
                          scale=scale, q_block=q_block, seq_k=Sk),
        grid=(B * H, Sq // q_block),
        in_specs=[
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, q_block), lambda b, i: (b, i)),
            pl.BlockSpec((None, q_block), lambda b, i: (b, i)),
        ],
        out_specs=pl.BlockSpec((None, q_block, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, deltaf)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, q_block=q_block, causal=causal,
                          scale=scale, kv_block=kv_block, seq_q=Sq),
        grid=(B * H, Sk // kv_block),
        in_specs=[
            pl.BlockSpec((None, Sq, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, kv_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, kv_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Sq, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Sq), lambda b, i: (b, 0)),
            pl.BlockSpec((None, Sq), lambda b, i: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, kv_block, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, kv_block, hd), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, hd), v.dtype),
        ],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, deltaf)

    rs = lambda t: t.reshape(B, H, t.shape[1], hd)
    return rs(dq), rs(dk), rs(dv)


flash_attention_vjp.defvjp(_fwd_rule, _bwd_rule)
