"""Public jit'd wrappers for the Pallas kernels.

``backend`` selects the implementation:
  * "ref"       — pure-jnp oracle (default on CPU / in the dry-run HLO)
  * "pallas"    — compiled Pallas TPU kernel (production)
  * "interpret" — Pallas kernel body interpreted on CPU (correctness tests)
  * "auto"/None — "pallas" on TPU, "ref" everywhere else
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.registry import register_program
from repro.kernels import ref as REF
from repro.kernels.adaptive_combine import adaptive_combine as _combine
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_dist import batched_int8_pairwise_dist as _bi8dist
from repro.kernels.ivf import batched_cluster_dist as _bcdist
from repro.kernels.ivf import batched_ivf_shortlist_scores as _bivfshort
from repro.kernels.kl_similarity import kl_similarity as _kl
from repro.kernels.pairwise_dist import batched_pairwise_dist as _bpdist
from repro.kernels.pairwise_dist import pairwise_dist as _pdist
from repro.kernels.quantize import batched_dequantize as _bdequant
from repro.kernels.quantize import batched_quantize as _bquant
from repro.kernels.relevance_aggregate import relevance_aggregate as _agg
from repro.kernels.relevance_aggregate import \
    fused_relevance_aggregate as _fused_agg
from repro.kernels.topk_pack import batched_idx_bitpack as _bidxpack
from repro.kernels.topk_pack import batched_idx_bitunpack as _bidxunpack
from repro.kernels.topk_pack import batched_topk_pack as _btopk
from repro.kernels.topk_pack import batched_topk_unpack as _buntopk

DEFAULT_BACKEND = "auto"

# ---- static-analysis registration (repro.analysis) -------------------------
# Every dispatcher registers with bench-scale abstract shapes (C=100 clients,
# P=4096 payload entries — where the BENCH_*.json sweeps top out) and
# backend="ref" so the traced program is pallas_call-free. Tracing is lazy;
# the decorator only records metadata.
_S = jax.ShapeDtypeStruct
_AC, _AP = 100, 4096                      # analysis-time client / payload dims


def _f32(*shape):
    return _S(shape, jnp.float32)


def _dispatch(backend):
    b = backend or DEFAULT_BACKEND
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "ref"
    if b not in ("ref", "pallas", "interpret"):
        raise ValueError(f"unknown kernel backend {b!r}")
    return b


@register_program(
    "kernels.flash_attention",
    abstract_args=lambda: ((_f32(2, 4, 128, 64),) * 3,
                           {"causal": True, "backend": "ref"}),
    oracle="repro.kernels.ref.flash_attention_ref", budget_bytes=64 << 20)
@functools.partial(jax.jit, static_argnames=("causal", "backend"))
def flash_attention(q, k, v, *, causal: bool = True, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.flash_attention_ref(q, k, v, causal=causal)
    return _flash(q, k, v, causal=causal, interpret=(b == "interpret"))


@register_program(
    "kernels.pairwise_dist",
    abstract_args=lambda: ((_f32(128, 64), _f32(256, 64)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.pairwise_dist_ref", budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def pairwise_dist(q, g, *, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.pairwise_dist_ref(q, g)
    return _pdist(q, g, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_pairwise_dist",
    abstract_args=lambda: ((_f32(_AC, 48, 64), _f32(_AC, 96, 64)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.batched_pairwise_dist_ref",
    budget_bytes=64 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def batched_pairwise_dist(q, g, *, backend: str = None):
    """(C, Q, D) x (C, G, D) -> (C, Q, G): all clients' distance matrices
    in one launch (the batched retrieval-eval hot spot)."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_pairwise_dist_ref(q, g)
    return _bpdist(q, g, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_int8_pairwise_dist",
    abstract_args=lambda: ((_f32(8, 32, 64), _S((8, 4096, 64), jnp.int8),
                            _f32(8, 4096), _f32(8, 4096)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.batched_int8_pairwise_dist_ref",
    budget_bytes=32 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def batched_int8_pairwise_dist(q, gq, gscale, gn2, *, backend: str = None):
    """(C, B, F) fp32 queries x int8 resident gallery ((C, G, F) codes +
    (C, G) scales + (C, G) dequantized squared norms) -> (C, B, G): the
    serving-path distance hot spot (see repro.serving)."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_int8_pairwise_dist_ref(q, gq, gscale, gn2)
    return _bi8dist(q, gq, gscale, gn2, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_cluster_assign",
    abstract_args=lambda: ((_f32(8, 32, 64), _f32(8, 64, 64), _f32(8, 64)),
                           {"nprobe": 8, "backend": "ref"}),
    oracle="repro.kernels.ref.batched_cluster_assign_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("nprobe", "backend"))
def batched_cluster_assign(qf, cent, cn2, *, nprobe: int,
                           backend: str = None):
    """IVF coarse-quantizer stage: (C, B, F) fp32 queries x ((C, L, F)
    centroids + (C, L) sq-norms) -> (C, B, nprobe) int32 nearest-bucket
    ids (query x centroid distances + ``lax.top_k`` nprobe selection)."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_cluster_assign_ref(qf, cent, cn2, nprobe=nprobe)
    dc = _bcdist(qf, cent, cn2, interpret=(b == "interpret"))
    return jax.lax.top_k(-dc, nprobe)[1]


@register_program(
    "kernels.batched_ivf_shortlist",
    abstract_args=lambda: ((_f32(8, 32, 64), _S((8, 32, 8), jnp.int32),
                            _S((8, 64, 96, 64), jnp.int8),
                            _f32(8, 64, 3, 96)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.batched_ivf_shortlist_ref",
    budget_bytes=32 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def batched_ivf_shortlist(qf, probe, bq, pack, *, backend: str = None):
    """IVF shortlist stage: score only the probed buckets of the
    bucket-major int8 image. (C, B, F) queries + (C, B, P) probe ids x
    ((C, L, K, F) int8 bucket rows, (C, L, 3, K) packed sidecar) ->
    ((C, B, P*K) partial squared distances, (C, B, P*K) row ids, -1 on
    empty slots). Rows scored per query: P*K ~ nprobe * bcap << G."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_ivf_shortlist_ref(qf, probe, bq, pack)
    C, B, P = probe.shape
    K = bq.shape[2]
    d = _bivfshort(qf, probe, bq, pack, interpret=(b == "interpret"))
    pids = jax.lax.bitcast_convert_type(pack[:, :, 2, :], jnp.int32)
    ids = jnp.take_along_axis(pids, probe.reshape(C, B * P)[:, :, None],
                              axis=1).reshape(C, B, P, K)
    return d.reshape(C, B, P * K), ids.reshape(C, B, P * K)


@register_program(
    "kernels.adaptive_combine",
    abstract_args=lambda: ((_f32(_AC, _AP),) * 3, {"backend": "ref"}),
    oracle="repro.kernels.ref.adaptive_combine_ref", budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def adaptive_combine(base, alpha, a, *, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.adaptive_combine_ref(base, alpha, a)
    return _combine(base, alpha, a, interpret=(b == "interpret"))


@register_program(
    "kernels.relevance_aggregate",
    abstract_args=lambda: ((_f32(_AC, _AC), _f32(_AC, _AP)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.relevance_aggregate_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def relevance_aggregate(w, thetas, *, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.relevance_aggregate_ref(w, thetas)
    return _agg(w, thetas, interpret=(b == "interpret"))


@register_program(
    "kernels.fused_relevance_aggregate",
    abstract_args=lambda: ((_f32(_AC, _AC), _f32(_AC, _AP)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.fused_relevance_aggregate_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def fused_relevance_aggregate(w, thetas, row0=0, *, backend: str = None):
    """Diag-mask + row-normalize + W @ Θ in one program -> (B, Wn). ``w``
    may be a row block starting at row ``row0`` of the (C, C) matrix."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.fused_relevance_aggregate_ref(w, thetas, row0)
    return _fused_agg(w, thetas, row0, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_quantize",
    abstract_args=lambda: ((_f32(_AC, _AP),),
                           {"chunk": 256, "backend": "ref"}),
    oracle="repro.kernels.ref.batched_quantize_ref", budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def batched_quantize(x, *, chunk: int = 256, backend: str = None):
    """Wire-codec quantize stage: (C, P) fp32 -> ((C, P) int8, per-chunk
    scales) for all C clients' payloads in one launch."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_quantize_ref(x, chunk=chunk)
    return _bquant(x, chunk=chunk, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_dequantize",
    abstract_args=lambda: ((_S((_AC, _AP), jnp.int8),
                            _f32(_AC, _AP // 256)),
                           {"chunk": 256, "backend": "ref"}),
    oracle="repro.kernels.ref.batched_dequantize_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def batched_dequantize(q, scales, *, chunk: int = 256, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_dequantize_ref(q, scales, chunk=chunk)
    return _bdequant(q, scales, chunk=chunk, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_topk_pack",
    abstract_args=lambda: ((_f32(_AC, _AP),),
                           {"group": 8, "kg": 2, "backend": "ref"}),
    oracle="repro.kernels.ref.batched_topk_pack_ref", budget_bytes=32 << 20)
@functools.partial(jax.jit, static_argnames=("group", "kg", "backend"))
def batched_topk_pack(x, *, group: int = 8, kg: int, backend: str = None):
    """Wire-codec sparsify stage: (C, P) -> (values (C, ceil(P/group)*kg),
    packed int32 indices); exact top-kg magnitudes per group of ``group``
    contiguous elements, deterministic ties (lowest index)."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_topk_pack_ref(x, group=group, kg=kg)
    return _btopk(x, group=group, kg=kg, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_topk_unpack",
    abstract_args=lambda: ((_f32(_AC, _AP // 8 * 2),
                            _S((_AC, _AP // 8 * 2), jnp.int32)),
                           {"p": _AP, "group": 8, "kg": 2,
                            "backend": "ref"}),
    oracle="repro.kernels.ref.batched_topk_unpack_ref",
    budget_bytes=32 << 20)
@functools.partial(jax.jit, static_argnames=("p", "group", "kg", "backend"))
def batched_topk_unpack(vals, idx, *, p: int, group: int = 8, kg: int,
                        backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_topk_unpack_ref(vals, idx, p=p, group=group, kg=kg)
    return _buntopk(vals, idx, p=p, group=group, kg=kg,
                    interpret=(b == "interpret"))


@register_program(
    "kernels.batched_idx_bitpack",
    abstract_args=lambda: ((_S((_AC, _AP // 8 * 2), jnp.int32),),
                           {"group": 8, "kg": 2, "backend": "ref"}),
    oracle="repro.kernels.ref.batched_idx_bitpack_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("group", "kg", "backend"))
def batched_idx_bitpack(idx, *, group: int = 8, kg: int, backend: str = None):
    """Wire-codec index compression: (C, K) int32 grouped-pack indices ->
    (C, bits*ceil(K/8)) uint8 bitplanes (bits = ceil(log2(group)), 3 at
    group=8 — only the local in-group index ships; absolute indices are
    slot arithmetic on the receiver)."""
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_idx_bitpack_ref(idx, group=group, kg=kg)
    return _bidxpack(idx, group=group, kg=kg, interpret=(b == "interpret"))


@register_program(
    "kernels.batched_idx_bitunpack",
    abstract_args=lambda: ((_S((_AC, 3 * (_AP // 8 * 2 // 8)), jnp.uint8),),
                           {"k": _AP // 8 * 2, "group": 8, "kg": 2,
                            "backend": "ref"}),
    oracle="repro.kernels.ref.batched_idx_bitunpack_ref",
    budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("k", "group", "kg", "backend"))
def batched_idx_bitunpack(packed, *, k: int, group: int = 8, kg: int,
                          backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.batched_idx_bitunpack_ref(packed, k=k, group=group, kg=kg)
    return _bidxunpack(packed, k=k, group=group, kg=kg,
                       interpret=(b == "interpret"))


@register_program(
    "kernels.kl_similarity",
    abstract_args=lambda: ((_f32(64, 128), _f32(48, 128)),
                           {"backend": "ref"}),
    oracle="repro.kernels.ref.kl_similarity_ref", budget_bytes=16 << 20)
@functools.partial(jax.jit, static_argnames=("backend",))
def kl_similarity(a, b_, *, backend: str = None):
    b = _dispatch(backend)
    if b == "ref":
        return REF.kl_similarity_ref(a, b_)
    return _kl(a, b_, interpret=(b == "interpret"))
