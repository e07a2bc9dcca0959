"""Clustered camera galleries, their queries and the serving heads, all
made on the device from the seed.

Copied from ``benchmarks/serve_bench._clustered_gallery`` and moved onto
the device: rows sit around unit identity centres drawn in a rank-``rank``
subspace of prototype space, ``n_per_id`` rows per identity, each the
centre plus a perturbation of norm ``rho``, L2-normalised. Real ReID
embeddings decay fast spectrally; on isotropic rows every IVF bucket is
equidistant and no shortlist can recall, so the clustering is what makes
the IVF cell's recall meaningful. A query is a fresh perturbation of a
centre drawn uniformly over the client's identities.

Row ids are the row index, so an answer names a gallery row exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _l2n(x):
    return x / jnp.sqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True), 1e-12))


def init_head(key, m):
    """One client's adaptive head, the shapes of ``core/edge_model``:
    ``{"l1": {w, b}, "l2": {w, b}, "bn": {scale, bias}, "head": {w}}``."""
    k1, k2, k3 = jax.random.split(key, 3)
    p, h, f, n = m["proto_dim"], m["hidden"], m["feat_dim"], m["n_classes"]
    return {
        "l1": {"w": jax.random.normal(k1, (p, h)) * (1.0 / jnp.sqrt(p)),
               "b": jnp.zeros((h,))},
        "l2": {"w": jax.random.normal(k2, (h, f)) * (1.0 / jnp.sqrt(h)),
               "b": jnp.zeros((f,))},
        "bn": {"scale": jnp.ones((f,)), "bias": jnp.zeros((f,))},
        "head": {"w": jax.random.normal(k3, (f, n)) * (1.0 / jnp.sqrt(f))},
    }


@functools.partial(jax.jit, static_argnames=("C", "G", "m", "g"))
def _make(key, *, C, G, m, g):
    m, g = dict(m), dict(g)
    D, r, n_id = m["proto_dim"], g["id_rank"], G // g["n_per_id"]
    kh, kg = jax.random.split(key)
    heads = jax.vmap(lambda k: init_head(k, m))(jax.random.split(kh, C))

    def one(k):
        ku, kz, kn = jax.random.split(k, 3)
        U, _ = jnp.linalg.qr(jax.random.normal(ku, (D, r)))
        centres = _l2n(_l2n(jax.random.normal(kz, (n_id, r))) @ U.T)
        noise = _l2n(jax.random.normal(kn, (G, D)))
        rows = _l2n(jnp.repeat(centres, g["n_per_id"], axis=0)
                    + g["id_rho"] * noise)
        return rows, centres

    rows, centres = jax.vmap(one)(jax.random.split(kg, C))
    return heads, rows, centres


def make(seed: int, C: int, G: int, model: dict, gallery: dict):
    """(stacked heads, (C, G, D) rows, (C, G // n_per_id, D) centres), all
    on the device, from one jitted call."""
    return _make(jax.random.PRNGKey(seed), C=C, G=G,
                 m=tuple(sorted(model.items())),
                 g=tuple(sorted(gallery.items())))


@functools.partial(jax.jit, static_argnames=("rho",))
def _queries(key, centres, clients, *, rho):
    n = clients.shape[0]
    kc, kn = jax.random.split(key)
    pick = jax.random.randint(kc, (n,), 0, centres.shape[1])
    noise = _l2n(jax.random.normal(kn, (n, centres.shape[2])))
    return _l2n(centres[clients, pick] + rho * noise), pick


def queries(seed: int, centres, clients: np.ndarray, rho: float):
    """(n, D) query prototypes for the given per-query clients, and the
    identity each was drawn around, as host arrays."""
    q, pick = _queries(jax.random.PRNGKey(seed ^ 0x5EED), centres,
                       jnp.asarray(clients, jnp.int32), rho=float(rho))
    return np.asarray(q, np.float32), np.asarray(pick)
