"""Per-round wire-codec wall time: host per-client encode loop vs the
batched device program.

The host path is what a real parameter server would do naively: encode and
decode each of C clients' payloads one at a time with the numpy
``PipelineCodec``. The batched path is the stacked engine's
``comm.BatchedCodec``: ALL C clients' flattened (C, P) payload rows go
through one jitted sparsify+quantize program (``kernels/topk_pack.py`` +
``kernels/quantize.py`` via ``kernels.ops``), encoded buffers staying on
device.

``python -m benchmarks.run --bench comm`` sweeps C ∈ {5, 20, 100} at the
edge model's real payload size and writes ``BENCH_comm_round.json`` (repo
root). ``--smoke`` runs C=5 only and additionally asserts host-vs-batched
parity: identical wire bytes and matching reconstructions (the tier-1
smoke in ``scripts/run_tier1.sh --smoke``).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.comm.batched import BatchedCodec
from repro.comm.codec import make_codec
from repro.common.pytree import tree_size
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.sharding.specs import engine_mesh

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_comm_round.json"
SPEC = "topk+int8"


def _payload_dim() -> int:
    cfg = EdgeModelConfig()
    theta = EM.init_adaptive_layers(jax.random.PRNGKey(0), cfg)
    return tree_size(theta)


def _bench_host(mat: np.ndarray, iters: int) -> float:
    C = mat.shape[0]
    codec = make_codec(SPEC, delta=False)
    def one_round():
        for c in range(C):
            payload = codec.encode({"theta": mat[c]})
            codec.decode(payload)
    one_round()                              # warm caches
    t0 = time.perf_counter()
    for _ in range(iters):
        one_round()
    return (time.perf_counter() - t0) / iters


def _bench_batched(mat: np.ndarray, iters: int):
    import jax.numpy as jnp
    codec = BatchedCodec(make_codec(SPEC, delta=False), mat.shape[1],
                         engine_mesh(jax.devices()[:1]))
    dev = jnp.asarray(mat)
    wire = codec.per_client_bytes(codec.encode(dev))
    def one_round():
        buffers = codec.encode(dev)
        jax.block_until_ready(codec.decode(buffers))
    one_round()                              # warmup (jit compile)
    t0 = time.perf_counter()
    for _ in range(iters):
        one_round()
    return (time.perf_counter() - t0) / iters, wire


def _parity_check(mat: np.ndarray) -> None:
    """Host codec and batched device program must produce the same wire
    bytes and the same reconstruction (delta off: single-shot parity)."""
    host = make_codec(SPEC, delta=False)
    batched = BatchedCodec(make_codec(SPEC, delta=False), mat.shape[1],
                           engine_mesh(jax.devices()[:1]))
    buffers = batched.encode(np.asarray(mat))
    dec_b = np.asarray(batched.decode(buffers))
    per_client_b = batched.per_client_bytes(buffers)
    for c in range(mat.shape[0]):
        payload = host.encode({"theta": mat[c]})
        assert payload.nbytes == per_client_b, \
            (payload.nbytes, per_client_b)
        dec_h = host.decode(payload)["theta"]
        np.testing.assert_allclose(dec_h, dec_b[c], atol=1e-6, rtol=0)
    print(f"parity OK: per-client wire bytes={per_client_b}, "
          f"decoded host==batched for C={mat.shape[0]}")


def bench_comm_round(Cs=(5, 20, 100), *, iters=5, out=DEFAULT_OUT,
                     smoke=False):
    P = _payload_dim()
    rng = np.random.default_rng(0)
    if smoke:
        Cs, iters = (5,), 2
    cases = []
    print(f"payload P={P} ({P * 4} dense bytes/client), codec={SPEC}")
    print("C,host_ms,batched_ms,speedup,wire_bytes_per_client,reduction")
    for C in Cs:
        mat = rng.standard_normal((C, P)).astype(np.float32)
        if smoke:
            _parity_check(mat)
        host_s = _bench_host(mat, iters)
        batched_s, wire = _bench_batched(mat, iters)
        case = {"C": C, "host_ms": host_s * 1e3,
                "batched_ms": batched_s * 1e3,
                "speedup": host_s / batched_s,
                "wire_bytes_per_client": wire,
                "dense_bytes_per_client": P * 4,
                "reduction": 1.0 - wire / (P * 4)}
        cases.append(case)
        print(f"{C},{case['host_ms']:.2f},{case['batched_ms']:.2f},"
              f"{case['speedup']:.1f}x,{wire},{case['reduction']:.3f}",
              flush=True)
    from benchmarks.common import mesh_metadata
    from repro.analysis.registry import coverage
    cov = coverage()
    payload = {
        "bench": "comm_round",
        "env": mesh_metadata(),
        "config": {"P": P, "codec": SPEC, "iters": iters,
                   "backend": jax.default_backend()},
        "analysis_coverage": {k: cov[k] for k in ("programs_registered",
                                                  "programs_traced")},
        "cases": cases,
    }
    if not smoke:
        Path(out).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {out}")
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="C=5 only + host-vs-batched parity assert")
    args = ap.parse_args()
    bench_comm_round(smoke=args.smoke)


if __name__ == "__main__":
    main()
