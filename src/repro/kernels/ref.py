"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These are also the implementations the CPU benchmarks and the dry-run HLO
use (identical math, no pallas_call in the lowered program). Matmuls that
a kernel mirrors run at ``Precision.HIGHEST``, as the kernels do: on the
TPU both then accumulate full fp32 products (the default there is one
bf16 pass), and on the CPU the flag changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.precision import INV127

_HI = jax.lax.Precision.HIGHEST


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """q: (B,H,Sq,hd), k/v: (B,H,Sk,hd) -> (B,H,Sq,hd). fp32 softmax."""
    hd = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    if causal:
        Sq, Sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.arange(Sk)[None, :] <= (jnp.arange(Sq)[:, None] + (Sk - Sq))
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def pairwise_dist_ref(q, g):
    """Squared euclidean distances: (Q,D) x (G,D) -> (Q,G), fp32."""
    q = q.astype(jnp.float32)
    g = g.astype(jnp.float32)
    qq = jnp.sum(q * q, -1, keepdims=True)
    gg = jnp.sum(g * g, -1)
    return qq + gg[None, :] - 2.0 * jnp.matmul(q, g.T, precision=_HI)


def batched_pairwise_dist_ref(q, g):
    """Per-client squared euclidean: (C,Q,D) x (C,G,D) -> (C,Q,G), fp32."""
    q = q.astype(jnp.float32)
    g = g.astype(jnp.float32)
    qq = jnp.sum(q * q, -1)[:, :, None]
    gg = jnp.sum(g * g, -1)[:, None, :]
    return qq + gg - 2.0 * jnp.einsum("cqd,cgd->cqg", q, g, precision=_HI)


def batched_int8_pairwise_dist_ref(q, gq, gscale, gn2):
    """fp32 queries vs an int8-quantized resident gallery (the serving
    index layout): (C, B, F) x ((C, G, F) int8, (C, G) per-row scales,
    (C, G) dequantized squared norms) -> (C, B, G) squared distances to
    the dequantized rows. One-way int8 -> f32 dequant (no round-trip)."""
    q = q.astype(jnp.float32)
    qq = jnp.sum(q * q, -1)[:, :, None]
    dot = jnp.einsum("cbf,cgf->cbg", q, gq.astype(jnp.float32),
                     precision=_HI)
    return qq + gn2[:, None, :] - 2.0 * (dot * gscale[:, None, :])


def adaptive_combine_ref(base, alpha, a):
    """FedSTIL Eq. 2: theta = B ⊙ alpha + A (elementwise, any shape)."""
    return base * alpha + a


def relevance_aggregate_ref(w, thetas):
    """FedSTIL Eq. 6: (C,C) x (C,P) -> (C,P), fp32 accumulate."""
    return jnp.matmul(w.astype(jnp.float32), thetas.astype(jnp.float32),
                      precision=_HI).astype(thetas.dtype)


def fused_relevance_aggregate_ref(w, thetas, row0=0):
    """Fused FedSTIL server math (Eq. 5 post-processing + Eq. 6):

        Wm = w ⊙ (1 - I)                 (no self-relevance)
        Wn = Wm / rowsum(Wm)             (zero rows stay zero)
        B  = Wn @ thetas                 (fp32 accumulate)

    w: (R, C) *raw* decayed relevance, rows row0..row0+R-1 of the (C, C)
    matrix (diagonal may hold junk); thetas: (C, P). Returns (B: (R, P) in
    thetas.dtype, Wn: (R, C) fp32).
    """
    R, C = w.shape
    row = jnp.arange(R)[:, None] + row0
    wm = jnp.where(row == jnp.arange(C)[None, :], 0.0, w.astype(jnp.float32))
    rows = jnp.sum(wm, axis=1, keepdims=True)
    wn = jnp.where(rows > 0, wm / jnp.where(rows > 0, rows, 1.0), 0.0)
    b = jnp.matmul(wn, thetas.astype(jnp.float32),
                   precision=_HI).astype(thetas.dtype)
    return b, wn


def batched_quantize_ref(x, *, chunk: int = 256):
    """Per-chunk symmetric int8 quantization of stacked payload rows:
    (C, P) fp32 -> ((C, P) int8, (C, ceil(P/chunk)) fp32 scales). Chunks of
    ``chunk`` contiguous elements share one scale = absmax*(1/127) (1.0 for
    all-zero chunks); round-half-to-even, clip to [-127, 127]."""
    C, P = x.shape
    nc = (P + chunk - 1) // chunk
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, nc * chunk - P)))
    xc = xp.reshape(C, nc, chunk)
    absmax = jnp.max(jnp.abs(xc), axis=2, keepdims=True)
    scale = absmax * INV127
    scale = jnp.where(scale > 0, scale, 1.0)   # all-zero / subnormal chunks
    q = jnp.clip(jnp.round(xc / scale), -127.0, 127.0).astype(jnp.int8)
    return q.reshape(C, nc * chunk)[:, :P], scale[..., 0]


def batched_dequantize_ref(q, scales, *, chunk: int = 256):
    """Inverse of ``batched_quantize_ref``: (C, P) int8 + per-chunk scales
    -> (C, P) fp32."""
    C, P = q.shape
    nc = scales.shape[1]
    qp = jnp.pad(q, ((0, 0), (0, nc * chunk - P))).astype(jnp.float32)
    out = qp.reshape(C, nc, chunk) * scales[..., None]
    return out.reshape(C, nc * chunk)[:, :P]


def grouped_topk_rank_ref(x, *, group: int):
    """Exact within-group magnitude ranks for stacked rows.

    x: (C, P) (P padded to a group multiple by the callers) viewed as
    groups of ``group`` contiguous elements; returns (C, P//group, group)
    int32 ranks, 0 = largest magnitude. Ties broken by lowest index, so
    ranks are a permutation of 0..group-1 — the counting form (an 8x8
    broadcast compare, no sort / no scatter / no cumsum) is what makes
    top-k selection fast on every backend, and the deterministic
    semantics every implementation (numpy host codec, this oracle, the
    Pallas kernel) shares bit-for-bit."""
    C, P = x.shape
    nb = P // group
    a = jnp.abs(x.astype(jnp.float32)).reshape(C, nb, group)
    ai = a[..., :, None]                                   # rank of i ...
    aj = a[..., None, :]                                   # ... vs every j
    ii = jnp.arange(group)
    beats = jnp.logical_or(aj > ai,
                           jnp.logical_and(aj == ai,
                                           ii[None, :] < ii[:, None]))
    return jnp.sum(beats.astype(jnp.int32), axis=-1)       # (C, nb, group)


def batched_topk_pack_ref(x, *, group: int, kg: int):
    """Grouped top-k sparsify+pack: (C, P) -> (values (C, nb*kg) fp32,
    indices (C, nb*kg) int32) where nb = ceil(P/group) and every group of
    ``group`` contiguous elements keeps its ``kg`` largest magnitudes
    (ties by lowest index), packed in magnitude-rank order.

    The group-local budget is the device-friendly form of top-k: selection
    is an O(group^2) counting compare and packing is a one-hot reduction —
    no global sort, no scatter — while delta/error-feedback encoding (see
    comm.codec) makes the uniform per-group budget self-correcting."""
    C, P = x.shape
    nb = (P + group - 1) // group
    Pp = nb * group
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, Pp - P)))
    rank = grouped_topk_rank_ref(xp, group=group)          # (C, nb, G)
    xg = xp.reshape(C, nb, group)
    onehot = (rank[..., None] ==
              jnp.arange(kg)[None, None, None, :])         # (C, nb, G, kg)
    oh = onehot.astype(jnp.float32)
    vals = jnp.sum(xg[..., None] * oh, axis=2)             # (C, nb, kg)
    gidx = (jnp.arange(nb, dtype=jnp.int32)[:, None] * group
            + jnp.arange(group, dtype=jnp.int32)[None, :])  # (nb, G)
    idx = jnp.sum(gidx[None, :, :, None] * onehot.astype(jnp.int32), axis=2)
    return vals.reshape(C, nb * kg), idx.reshape(C, nb * kg)


def batched_topk_unpack_ref(vals, idx, *, p: int, group: int, kg: int):
    """Inverse of ``batched_topk_pack_ref``: (C, nb*kg) values + indices
    -> dense (C, p) fp32 (dropped entries zero). One-hot reduction per
    group — scatter-free like the pack."""
    C, K = vals.shape
    nb = K // kg
    vb = vals.astype(jnp.float32).reshape(C, nb, kg)
    li = (idx.reshape(C, nb, kg)
          - (jnp.arange(nb, dtype=jnp.int32) * group)[None, :, None])
    onehot = (li[..., None] ==
              jnp.arange(group, dtype=jnp.int32)[None, None, None, :])
    dense = jnp.sum(vb[..., None] * onehot.astype(jnp.float32), axis=2)
    return dense.reshape(C, nb * group)[:, :p]


def batched_idx_bitpack_ref(idx, *, group: int, kg: int):
    """Bit-pack grouped top-k indices: (C, K) int32 absolute indices from
    ``batched_topk_pack`` -> (C, bits * ceil(K/8)) uint8, where
    bits = ceil(log2(group)) (3 at group=8 — a 10.7x shrink vs int32).

    Each slot s of the K = nb*kg pack slots belongs to group s // kg, so
    only the LOCAL index li = idx - (s // kg) * group (0..group-1) carries
    information; the absolute index is reconstructed from the slot
    position. Layout is bitplane-major: plane j holds bit j of every
    slot's li, 8 slots per byte (slot s -> byte s // 8, bit s % 8), planes
    concatenated along the last axis — byte lanes, plain shift/mask ALU
    ops, no gather/scatter. Padding slots (K up to a byte multiple)
    carry li = 0 and are sliced off by the unpack."""
    C, K = idx.shape
    bits = (group - 1).bit_length()
    kb = (K + 7) // 8
    slot = jnp.arange(K, dtype=jnp.int32)
    li = idx.astype(jnp.int32) - (slot // kg)[None, :] * group
    lip = jnp.pad(li, ((0, 0), (0, kb * 8 - K)))
    lib = lip.reshape(C, kb, 8)
    lane = jnp.left_shift(jnp.int32(1), jnp.arange(8, dtype=jnp.int32))
    planes = [jnp.sum(((lib >> j) & 1) * lane, axis=2) for j in range(bits)]
    return jnp.concatenate(planes, axis=1).astype(jnp.uint8)


def batched_idx_bitunpack_ref(packed, *, k: int, group: int, kg: int):
    """Inverse of ``batched_idx_bitpack_ref``: (C, bits * ceil(k/8)) uint8
    bitplanes -> (C, k) int32 absolute indices (slot s's group base
    (s // kg) * group plus the unpacked local index)."""
    C = packed.shape[0]
    bits = (group - 1).bit_length()
    kb = packed.shape[1] // bits
    b = packed.reshape(C, bits, kb).astype(jnp.int32)
    lanes = ((b[..., None] >> jnp.arange(8, dtype=jnp.int32)) & 1)
    planes = lanes.reshape(C, bits, kb * 8)[:, :, :k]
    shift = jnp.arange(bits, dtype=jnp.int32)[None, :, None]
    li = jnp.sum(jnp.left_shift(planes, shift), axis=1)
    slot = jnp.arange(k, dtype=jnp.int32)
    return (slot // kg)[None, :] * group + li


def batched_cluster_assign_ref(qf, cent, cn2, *, nprobe: int):
    """IVF coarse-quantizer probe selection: (C, B, F) queries x
    ((C, L, F) centroids, (C, L) sq-norms) -> (C, B, nprobe) int32 bucket
    ids, nearest first (``lax.top_k`` ties resolve to the lowest id —
    shared with the Pallas dispatcher and the numpy host oracle)."""
    q = qf.astype(jnp.float32)
    qq = jnp.sum(q * q, -1)
    dc = (qq[..., None] + cn2[:, None, :]
          - 2.0 * jnp.einsum("cbf,clf->cbl", q, cent.astype(jnp.float32),
                             precision=_HI))
    return jax.lax.top_k(-dc, nprobe)[1]


def batched_ivf_shortlist_ref(qf, probe, bq, pack):
    """Score the probed buckets of the bucket-major int8 image:
    (C, B, F) queries + (C, B, P) probe ids x ((C, L, K, F) int8 rows,
    (C, L, 3, K) packed [scale; |g|^2; id-bitcast] sidecar) ->
    ((C, B, P*K) partial squared distances |g|^2 - 2 q.g, (C, B, P*K)
    int32 row ids, -1 for empty slots). The caller adds |q|^2 and masks
    ids < 0 before ranking.

    Formulation: ``lax.scan`` over the flattened C*B query stream with
    one contiguous ``dynamic_slice`` per probe for the bucket block and
    one for the packed sidecar. On XLA CPU this is the measured-fast
    shape — slice + (K, F) dequant matvec beats every gather variant
    ~2x at G=131k because gathers lower to per-element loads while
    slices stay memcpy-like (see benchmarks/BENCH_serve_round.json)."""
    C, B, F = qf.shape
    P = probe.shape[-1]
    K = bq.shape[2]
    q2 = qf.astype(jnp.float32).reshape(C * B, F)
    pf = probe.reshape(C * B, P)
    cidx = jnp.repeat(jnp.arange(C, dtype=jnp.int32), B)

    def step(_, inp):
        qi, pi, ci = inp
        ds, ids = [], []
        for j in range(P):
            blk = jax.lax.dynamic_slice(bq, (ci, pi[j], 0, 0),
                                        (1, 1, K, F))[0, 0]
            pk = jax.lax.dynamic_slice(pack, (ci, pi[j], 0, 0),
                                       (1, 1, 3, K))[0, 0]
            dot = jnp.matmul(blk.astype(jnp.float32), qi, precision=_HI)
            ds.append(pk[1] - 2.0 * (dot * pk[0]))
            ids.append(jax.lax.bitcast_convert_type(pk[2], jnp.int32))
        return None, (jnp.concatenate(ds), jnp.concatenate(ids))

    _, (d, ids) = jax.lax.scan(step, None, (q2, pf, cidx))
    return d.reshape(C, B, P * K), ids.reshape(C, B, P * K)


def kl_similarity_ref(a, b):
    """exp(-KL(softmax(a_i) || softmax(b_j))): (N,D) x (M,D) -> (N,M)."""
    p = jax.nn.softmax(a.astype(jnp.float32), -1)
    logp = jax.nn.log_softmax(a.astype(jnp.float32), -1)
    logq = jax.nn.log_softmax(b.astype(jnp.float32), -1)
    h = jnp.sum(p * logp, -1)                    # (N,)
    cross = jnp.matmul(p, logq.T, precision=_HI)  # (N,M)
    return jnp.exp(-(h[:, None] - cross))
