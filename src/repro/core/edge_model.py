"""Edge-scale ReID model: frozen extraction layers + adaptive layers.

This is the paper's deployment model at benchmark scale: the backbone trunk
("extraction layers" G_c, initialized from pre-trained weights and frozen)
encodes raw images into compact prototypes (Eq. 1); the "adaptive layers"
(last block + classifier in the paper; an MLP block + bias-free classifier
here, matching the paper's modified-ResNet head: BN after the representation,
no classifier bias) are what FedSTIL decomposes as theta = B ⊙ alpha + A.

For the assigned large architectures the same split is realised as
(transformer trunk | last block + head) — see repro/core/adaptive.split_params.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.common.precision import broadcast_rows, pairwise_sum


@dataclasses.dataclass(frozen=True)
class EdgeModelConfig:
    img_dim: int = 256         # stub "image" dimensionality (synthetic data)
    proto_dim: int = 128       # prototype size (extraction-layer output)
    hidden: int = 128          # adaptive-layer hidden
    feat_dim: int = 64         # retrieval feature size
    n_classes: int = 512       # global identity space


def init_extraction(key, cfg: EdgeModelConfig):
    """Frozen G_c: simulates the pre-trained ResNet trunk."""
    k1, k2 = jax.random.split(key)
    s1 = 1.0 / jnp.sqrt(cfg.img_dim)
    s2 = 1.0 / jnp.sqrt(cfg.proto_dim)
    return {
        "w1": jax.random.normal(k1, (cfg.img_dim, cfg.proto_dim)) * s1,
        "w2": jax.random.normal(k2, (cfg.proto_dim, cfg.proto_dim)) * s2,
    }


def extract_prototypes(g_params, images):
    """Eq. (1): P = G(X). images: (N, img_dim) -> (N, proto_dim)."""
    h = jnp.tanh(images @ g_params["w1"])
    return jnp.tanh(h @ g_params["w2"])


def init_adaptive_layers(key, cfg: EdgeModelConfig):
    """Trainable F_c (decomposed by FedSTIL into B ⊙ alpha + A)."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(cfg.proto_dim)
    s2 = 1.0 / jnp.sqrt(cfg.hidden)
    return {
        "l1": {"w": jax.random.normal(k1, (cfg.proto_dim, cfg.hidden)) * s1,
               "b": jnp.zeros((cfg.hidden,))},
        "l2": {"w": jax.random.normal(k2, (cfg.hidden, cfg.feat_dim)) * s2,
               "b": jnp.zeros((cfg.feat_dim,))},
        "bn": {"scale": jnp.ones((cfg.feat_dim,)),
               "bias": jnp.zeros((cfg.feat_dim,))},
        # bias-free classifier (paper: "bias of the classifier is removed")
        "head": {"w": jax.random.normal(k3, (cfg.feat_dim, cfg.n_classes))
                 * (1.0 / jnp.sqrt(cfg.feat_dim))},
    }


# Every per-feature vector that meets a batch of rows (biases, BN
# statistics and affine) does so through ``broadcast_rows``: its gradient
# is then a ``pairwise_sum`` over the rows, whose order does not depend on
# how many clients one vmapped train program holds.


def adaptive_pre_bn(theta, protos):
    """The head up to (not including) BN: protos (N, D) -> (N, feat_dim)."""
    n = protos.shape[0]
    h = jax.nn.relu(protos @ theta["l1"]["w"]
                    + broadcast_rows(theta["l1"]["b"], n))
    return h @ theta["l2"]["w"] + broadcast_rows(theta["l2"]["b"], n)


def adaptive_bn_stats(f, mask):
    """BN statistics (mu, sd) of a pre-BN batch over ``mask``-valid rows
    only (zero-padded rows contribute nothing). f: (N, feat_dim);
    mask: (N,) 1.0 = valid. Returns (feat_dim,) each. The row sums run in
    ``pairwise_sum``'s fixed order, which the serving index's numpy oracle
    shares bit for bit."""
    m = mask.astype(f.dtype)[:, None]
    n = jnp.maximum(jnp.sum(m), 1.0)
    mu = pairwise_sum(f * m, 0) / n
    dev = f - broadcast_rows(mu, f.shape[0])
    sd = jnp.sqrt(pairwise_sum(jnp.square(dev) * m, 0) / n) + 1e-5
    return mu, sd


def adaptive_bn_apply(theta, f, mu, sd):
    """BN affine with the given statistics: (N, feat_dim) -> features."""
    n = f.shape[0]
    return ((f - broadcast_rows(mu, n)) / broadcast_rows(sd, n)
            * broadcast_rows(theta["bn"]["scale"], n)
            + broadcast_rows(theta["bn"]["bias"], n))


def adaptive_forward_masked(theta, protos, mask):
    """prototypes -> (retrieval features, class logits) over a padded
    batch: the BN-style statistics (paper adds BN after the representation)
    are computed over ``mask``-valid rows only, so zero-padded rows
    contribute nothing. protos: (N, D); mask: (N,) 1.0 = valid."""
    f = adaptive_pre_bn(theta, protos)
    mu, sd = adaptive_bn_stats(f, mask)
    fn = adaptive_bn_apply(theta, f, mu, sd)
    logits = fn @ theta["head"]["w"]
    return fn, logits


def adaptive_forward_frozen(theta, protos, mu, sd):
    """Inference-mode featurization with FROZEN BN statistics: the serving
    forward. ``mu``/``sd`` come from ``adaptive_bn_stats`` over the client's
    resident gallery at index-refresh time, so a query's feature does not
    depend on whichever batch it was coalesced into (batch-composition
    invariance — the contract the continuous batcher relies on). Returns
    features only: the classifier head is dead weight at retrieval time."""
    return adaptive_bn_apply(theta, adaptive_pre_bn(theta, protos), mu, sd)


def adaptive_features(theta, protos):
    """prototypes -> retrieval features: ``adaptive_forward``'s first
    output, bit for bit, without the classifier head."""
    f = adaptive_pre_bn(theta, protos)
    mu, sd = adaptive_bn_stats(f, jnp.ones((protos.shape[0],), jnp.float32))
    return adaptive_bn_apply(theta, f, mu, sd)


def adaptive_forward(theta, protos):
    """prototypes -> (retrieval features, class logits)."""
    return adaptive_forward_masked(
        theta, protos, jnp.ones((protos.shape[0],), jnp.float32))


def log_softmax_rows(z):
    """``jax.nn.log_softmax`` over the classes of (N, K) logits, with the
    class sums (forward and gradient) in ``pairwise_sum`` order."""
    k = z.shape[1]
    s = z - broadcast_rows(jax.lax.stop_gradient(jnp.max(z, axis=1)), k).T
    lse = jnp.log(pairwise_sum(jnp.exp(s), 1))
    return s - broadcast_rows(lse, k).T


def ce_loss(theta, protos, labels):
    feats, logits = adaptive_forward(theta, protos)
    logp = log_softmax_rows(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], 1)[:, 0]
    return pairwise_sum(nll, 0) / nll.shape[0]
