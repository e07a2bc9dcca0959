"""Device-resident batched wire codec for the stacked engine.

``BatchedCodec`` runs the same stage stack as the host ``PipelineCodec``
(delta -> topk -> {int8|bf16}) over ALL C clients' flattened (C, P)
payload rows as one jitted device program — the sparsify/quantize hot
paths are the Pallas kernels in ``kernels/topk_pack.py`` /
``kernels/quantize.py`` (via ``kernels.ops``, so the jnp oracle serves CPU
and the compiled kernel serves TPU). Encoded buffers stay on device; the
measured per-client wire bytes fall out of the buffer shapes, so a
simulated round needs NO host readback at all, and a real dispatch needs
exactly one (the encoded buffers themselves).

Stage semantics are bit-identical to the host codec on CPU (same top-k tie
handling, same round-half-to-even per-chunk scales), which the comm-round
bench asserts (``benchmarks/comm_round.py --smoke``).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm.codec import PipelineCodec
from repro.common import compat
from repro.common.precision import sum_of_squares
from repro.kernels import ops


class BatchedCodec:
    """One direction's (C, P) encode/decode program, built from the host
    codec's stage parameters. Stateful only when delta is on (device ref).
    ``mesh``: the sharded engine's mesh — the programs then run per
    shard of payload rows."""

    def __init__(self, like: PipelineCodec, p: int, *,
                 backend: Optional[str] = None, mesh=None):
        if like.topk and like.group is None:
            raise ValueError(
                "BatchedCodec needs the grouped top-k stage (group=N); "
                "explicit-k global top-k is a host-codec-only mode")
        self.spec = like.spec
        self.delta = like.delta
        self.topk = like.topk
        self.quant = like.quant
        self.chunk = like.chunk
        self.group = like.group
        self.kg = like.kg
        self.p = int(p)
        self.k = like.k_for(self.p) if like.topk else None
        self.backend = backend
        self._enc_ref = None
        self._dec_ref = None
        self.last_metrics = None   # most recent encode's device telemetry

        chunk, quant, topk = self.chunk, self.quant, self.topk
        group, kg = self.group, self.kg

        def program(fn):
            """jit ``fn``; on a mesh (the sharded engine, payload rows on
            "data") run it per shard inside ``shard_map`` — every stage
            is row-local, and a Pallas call cannot be partitioned by the
            compiler."""
            if mesh is None:
                return jax.jit(fn)
            rows = P("data")
            return jax.jit(compat.shard_map(fn, mesh=mesh, in_specs=rows,
                                            out_specs=rows, check_vma=False))

        def _quant(vals, buffers):
            if quant == "int8":
                q, scales = ops.batched_quantize(vals, chunk=chunk,
                                                 backend=backend)
                buffers["values"] = q
                buffers["scales"] = scales
            elif quant == "bf16":
                buffers["values"] = vals.astype(jnp.bfloat16)
            else:
                buffers["values"] = vals
            return buffers

        kk = self.k
        pp = self.p

        # encode telemetry rides the same launch: per-row residual norm
        # (decoder-reference staleness — grows as the ref drifts), the
        # fraction of residual energy the wire kept, and the effective
        # keep-rate. Tiny (C,) outputs of a program that already runs; the
        # host only reads them back when a tracer is active. The sums run
        # in a fixed order, so a row's metrics do not depend on how many
        # rows the program holds (sharded and stacked runs compare equal).
        def _enc_metrics(x, vals):
            r2 = sum_of_squares(x, 1)
            k2 = sum_of_squares(vals, 1)
            return {"residual_norm": jnp.sqrt(r2),
                    "kept_energy": k2 / jnp.maximum(r2, 1e-12),
                    "keep_rate": jnp.sum(vals != 0, axis=1) / pp}

        @program
        def _enc_sparse(x):
            vals, idx = ops.batched_topk_pack(x, group=group, kg=kg,
                                              backend=backend)
            packed = ops.batched_idx_bitpack(idx, group=group, kg=kg,
                                             backend=backend)
            return _quant(vals, {"idx_bits": packed}), _enc_metrics(x, vals)

        @program
        def _enc_dense(x):
            x = x.astype(jnp.float32)
            return _quant(x, {}), _enc_metrics(x, x)

        def _dequant(buffers):
            v = buffers["values"]
            if quant == "int8":
                return ops.batched_dequantize(v, buffers["scales"],
                                              chunk=chunk, backend=backend)
            return v.astype(jnp.float32)

        @program
        def _dec_sparse(buffers):
            idx = ops.batched_idx_bitunpack(buffers["idx_bits"], k=kk,
                                            group=group, kg=kg,
                                            backend=backend)
            return ops.batched_topk_unpack(_dequant(buffers), idx, p=pp,
                                           group=group, kg=kg,
                                           backend=backend)

        @program
        def _dec_dense(buffers):
            return _dequant(buffers)

        self._enc_sparse = _enc_sparse
        self._enc_dense = _enc_dense
        self._dec_sparse = _dec_sparse
        self._dec_dense = _dec_dense

    # ---- wire ----------------------------------------------------------------
    def _dec(self, buffers):
        return (self._dec_sparse(buffers) if "idx_bits" in buffers
                else self._dec_dense(buffers))

    def _encode_residual(self, x):
        """Apply the keyframe rule and encode; advances NO state.
        Returns (buffers, delta reference or None). Stores the encode
        launch's rider telemetry in ``self.last_metrics`` (per-row
        residual norm / kept energy / keep-rate, still on device)."""
        if not self.delta:
            buffers, mets = (self._enc_sparse(x) if self.topk
                             else self._enc_dense(x))
            self.last_metrics = mets
            return buffers, None
        keyframe = self._enc_ref is None
        ref = jnp.zeros_like(x) if keyframe else self._enc_ref
        r = x - ref
        buffers, mets = (self._enc_dense(r) if keyframe or not self.topk
                         else self._enc_sparse(r))
        self.last_metrics = mets
        return buffers, ref

    def encode(self, mat) -> Dict[str, jax.Array]:
        """(C, P) stacked payload rows -> dict of device wire buffers.

        Mirrors the host codec's keyframe rule: a delta stream's first
        payload ships dense (quantized only) to establish the reference;
        every later payload is a sparse residual."""
        buffers, ref = self._encode_residual(mat.astype(jnp.float32))
        if self.delta:
            self._enc_ref = ref + self._dec(buffers)
        return buffers

    def decode(self, buffers) -> jax.Array:
        """Wire buffers -> reconstructed (C, P) fp32 rows."""
        x = self._dec(buffers)
        if self.delta:
            x = x if self._dec_ref is None else self._dec_ref + x
            self._dec_ref = x
        return x

    def roundtrip(self, mat):
        """encode + decode in one device pass: (reconstruction, buffers).

        The stacked simulation plays both wire ends, and the encoder's
        error-feedback ref IS the decoder's reconstruction — running the
        unpack+dequant program once per round instead of twice. Both refs
        advance exactly as separate encode()/decode() calls would."""
        buffers, ref = self._encode_residual(mat.astype(jnp.float32))
        recon = self._dec(buffers)
        if self.delta:
            recon = ref + recon
            self._enc_ref = recon
            self._dec_ref = recon
        return recon, buffers

    # ---- accounting ----------------------------------------------------------
    def per_client_bytes(self, buffers) -> int:
        """Measured wire bytes per client (row) from the buffer shapes —
        no readback needed."""
        total = 0
        for b in buffers.values():
            total += int(np.prod(b.shape[1:])) * b.dtype.itemsize
        return total
