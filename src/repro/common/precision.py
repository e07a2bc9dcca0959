"""Precision policy for the mesh engine: bf16 on the wire, fp32 in state.

The mesh-transformer-jax exemplar (SNIPPETS.md) keeps optimizer state in
fp32 and casts activations/wire traffic to bf16 at shard boundaries. The
sharded federated engine follows the same rule: the flattened ``(C, P)``
upload rows that cross the ``data``/``model`` shard boundary travel as
bf16, and the server upcasts back to fp32 before the relevance-weighted
aggregate (whose normalizer psum must stay fp32 — bf16 accumulation of
10k relevance weights loses the low-order mass).

``to_bf16``/``to_f32`` are pytree-wide casts that only touch float
leaves: int8/int32 wire buffers, bool masks, and index arrays pass
through untouched, so they are safe to apply to mixed codec buffer
dicts. Programs that contain an intentional f32 -> bf16 -> f32
round-trip declare it via ``ProgramSpec.sanctioned_casts`` so the
convert-churn lint knows it is a wire cast, not churn.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# int8 scales are absmax * (1/127) in every implementation (kernel, jnp
# oracle, numpy host codec): XLA rewrites a division by a constant into a
# multiply by its reciprocal, so "absmax / 127" would round differently on
# device and in numpy
INV127 = 1.0 / 127.0

# the (src, dst) convert pairs the analysis convert-churn lint accepts in
# programs that declare them: the wire cast down and its matching upcast
WIRE_CASTS = frozenset({("float32", "bfloat16"), ("bfloat16", "float32")})


def _cast_floating(x, dtype):
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return jnp.asarray(x).astype(dtype)
    return x


def to_bf16(tree):
    """Cast every floating leaf to bfloat16 (wire / cross-shard form)."""
    return jax.tree.map(lambda x: _cast_floating(x, jnp.bfloat16), tree)


def to_f32(tree):
    """Cast every floating leaf to float32 (state / accumulate form)."""
    return jax.tree.map(lambda x: _cast_floating(x, jnp.float32), tree)


def pairwise_sum(x, axis: int, *, keepdims: bool = False):
    """Sum over ``axis`` in one fixed order, on numpy and jax arrays alike.

    The axis is zero-padded to a power of two and its two halves are added
    until one slice is left. A backend reduction (``jnp.sum``) picks its
    own association order — XLA's CPU order changed between JAX releases
    and never matched numpy's — while elementwise float adds are exact
    IEEE ops that XLA does not reassociate. So a program and its numpy
    oracle that both reduce through here agree bit for bit on any backend.
    """
    xp = np if isinstance(x, np.ndarray) else jnp
    x = xp.moveaxis(x, axis, 0)
    n = x.shape[0]
    m = 1 << max(n - 1, 0).bit_length()
    if m > n:
        x = xp.concatenate([x, xp.zeros((m - n,) + x.shape[1:], x.dtype)])
    while m > 1:
        m //= 2
        x = x[:m] + x[m:]
    out = x[0]
    return xp.expand_dims(out, axis) if keepdims else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def broadcast_rows(v, n: int):
    """``v`` repeated as ``n`` leading rows, (d...) -> (n, d...), whose
    cotangent is summed back over the rows by ``pairwise_sum``.

    Autodiff would transpose the broadcast into a backend reduction, and
    the TPU compiler lays that reduction out by the shape of the whole
    program: a vmapped client step holding 100 clients and one holding 25
    sum the same client's bias gradient in different orders. Through here
    a client's gradient is the same at any client count — the sharded
    engine's 25 clients per chip match the stacked engine's 100 on one."""
    return jnp.broadcast_to(v, (n,) + v.shape)


def _broadcast_rows_fwd(v, n):
    return broadcast_rows(v, n), None


def _broadcast_rows_bwd(n, _, g):
    return (pairwise_sum(g, 0),)


broadcast_rows.defvjp(_broadcast_rows_fwd, _broadcast_rows_bwd)


def sum_of_squares(x, axis: int, *, keepdims: bool = False):
    """``pairwise_sum`` of ``x ** 2``. On jax arrays the squares pass
    through an identity ``maximum(., 0)``: a multiply fed straight into
    the first add is contracted into an FMA by XLA's CPU backend, which
    rounds once where numpy rounds twice."""
    if isinstance(x, np.ndarray):
        return pairwise_sum(np.square(x), axis, keepdims=keepdims)
    return pairwise_sum(jnp.maximum(jnp.square(x), 0.0), axis,
                        keepdims=keepdims)
