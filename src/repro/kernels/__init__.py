"""Pallas TPU kernels for the paper's compute hot spots + jnp oracles.

kernels: flash_attention (backbone prefill), pairwise_dist (ReID retrieval),
adaptive_combine (Eq. 2), relevance_aggregate (Eq. 6), kl_similarity (Eq. 4).
Each has a pl.pallas_call + BlockSpec implementation validated in
interpret mode against the pure-jnp oracle in ref.py, and compiled for a
TPU v5e in tests/test_tpu_compile.py.
"""
from repro.kernels.ops import (
    adaptive_combine,
    flash_attention,
    kl_similarity,
    pairwise_dist,
    relevance_aggregate,
)
