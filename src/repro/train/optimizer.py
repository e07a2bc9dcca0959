"""Minimal optax-style optimizer library (pure JAX, no external deps).

Implements Adam/AdamW/SGD, global-norm clipping, and schedules — the paper
trains with Adam(lr=1e-3, weight_decay=1e-5).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.common.precision import sum_of_squares


class Optimizer(NamedTuple):
    init: Callable
    update: Callable   # (grads, state, params) -> (updates, state)


def adam(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
         schedule: Optional[Callable] = None):
    def init(params):
        return {
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params=None):
        count = state["count"] + 1
        step_lr = lr * (schedule(count) if schedule else 1.0)
        m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, state["m"], grads)
        v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * jnp.square(g),
                         state["v"], grads)
        c = count.astype(jnp.float32)
        bc1 = 1 - b1 ** c
        bc2 = 1 - b2 ** c

        def upd(mm, vv, p):
            u = -step_lr * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps)
            if weight_decay and p is not None:
                u = u - step_lr * weight_decay * p
            return u

        if params is None:
            updates = jax.tree.map(lambda mm, vv: upd(mm, vv, None), m, v)
        else:
            updates = jax.tree.map(upd, m, v, params)
        return updates, {"m": m, "v": v, "count": count}

    return Optimizer(init, update)


def sgd(lr=1e-2, momentum=0.0):
    def init(params):
        return {"mom": jax.tree.map(jnp.zeros_like, params)} if momentum else {}

    def update(grads, state, params=None):
        if momentum:
            mom = jax.tree.map(lambda m, g: momentum * m + g, state["mom"], grads)
            return jax.tree.map(lambda m: -lr * m, mom), {"mom": mom}
        return jax.tree.map(lambda g: -lr * g, grads), state

    return Optimizer(init, update)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def clip_by_global_norm(grads, max_norm: float, *, fixed_order: bool = False):
    """``fixed_order`` sums each leaf's squares in ``pairwise_sum`` order,
    so a federated client's clip scale does not depend on how many clients
    its vmapped train program holds (see ``precision.broadcast_rows``)."""
    leaves = jax.tree.leaves(grads)
    if fixed_order:
        sq = [sum_of_squares(g.astype(jnp.float32).reshape(-1), 0)
              for g in leaves]
    else:
        sq = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves]
    gn = jnp.sqrt(sum(sq))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


def cosine_schedule(warmup: int, total: int, floor: float = 0.1):
    def fn(count):
        c = count.astype(jnp.float32)
        warm = c / jnp.maximum(warmup, 1)
        prog = jnp.clip((c - warmup) / jnp.maximum(total - warmup, 1), 0, 1)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return jnp.where(c < warmup, warm, cos)
    return fn
