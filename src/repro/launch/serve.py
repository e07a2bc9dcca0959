"""ReID retrieval serving launcher: device-resident int8 gallery index +
continuous query batching (repro.serving). Builds a synthetic fleet,
streams queries through the batcher at peak throughput, demonstrates a
mid-stream federated-round index update, and prints QPS / p50 / p99.
(The LM-decode launcher this module used to hold is now
``repro.launch.serve_lm``.)

Usage:
  PYTHONPATH=src python -m repro.launch.serve --clients 4 --gallery 8192 \
      --queries 512 --batch 64 --mode int8

With ``--trace out.jsonl`` the run executes under a live ``repro.obs``
tracer: serve.batch / serve.index_refresh spans, bucket-exact latency
histograms and rolling QPS from a ``ServeStats`` wired into the batcher,
and IVF probe metrics when ``--mode ivf``. Inspect the sink with
``python -m repro.obs.report out.jsonl``. ``--profile DIR`` runs the
traced serving inside ``jax.profiler.trace(DIR)``: the device trace holds
the spans (``serve.admit`` / ``serve.upload`` / ``serve.launch`` /
``serve.readback`` / ``serve.complete``) on its own clock, and
``perf/trace_reduce.reduce(load(<DIR>/plugins/profile/*/*.xplane.pb),
window="serve.run", kernels={})`` reads device busy time and idle time by
span from it.
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core import edge_model as EM
from repro.obs import trace as obs
from repro.obs.metrics import ServeStats
from repro.serving import ContinuousBatcher, GalleryIndex, RetrievalEngine
from repro.serving.batcher import run_closed_loop


def _stack_thetas(keys, cfg):
    thetas = [EM.init_adaptive_layers(k, cfg) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *thetas)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--gallery", type=int, default=8192)
    ap.add_argument("--queries", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--mode", choices=("int8", "fp32", "ivf"), default="int8")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="coarse buckets scored per query (ivf mode)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="OUT.jsonl",
                    help="write a repro.obs telemetry JSONL (spans + serve "
                         "stats); read it with python -m repro.obs.report")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="record a jax.profiler device trace of the traced "
                         "serving into DIR")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = (obs.Tracer(path=args.trace) if args.trace or args.profile
              else obs.NullTracer())
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    with obs.active(tracer), profile:
        with obs.span("serve.run", cat="phase", mode=args.mode):
            _serve(args)
    if args.trace:
        tracer.close()
        print(f"telemetry: {args.trace}  "
              f"(python -m repro.obs.report {args.trace})")


def _serve(args):
    cfg = EM.EdgeModelConfig()
    rng = np.random.default_rng(args.seed)
    C, G = args.clients, args.gallery
    protos = [rng.standard_normal((G, cfg.proto_dim), np.float32)
              for _ in range(C)]
    ids = [np.arange(G, dtype=np.int32) for _ in range(C)]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), C)
    theta = _stack_thetas(keys, cfg)

    t0 = time.perf_counter()
    index = GalleryIndex(protos, ids, keep_fp32=(args.mode == "fp32"),
                         nlist="auto" if args.mode == "ivf" else 0)
    engine = RetrievalEngine(index, theta, k=args.k, mode=args.mode,
                             nprobe=args.nprobe)
    print(f"index: C={C} G={G} mode={args.mode} "
          f"resident={index.resident_bytes(args.mode) / 1e6:.1f} MB "
          f"built in {time.perf_counter() - t0:.2f}s")

    stream = [(int(rng.integers(C)),
               rng.standard_normal(cfg.proto_dim).astype(np.float32), -1)
              for _ in range(args.queries)]

    stats = ServeStats() if obs.is_active() else None
    batcher = ContinuousBatcher(engine, batch=args.batch, stats=stats)
    # warmup launch (compile) before measuring
    batcher.submit(0, stream[0][1])
    batcher.drain()

    half = len(stream) // 2
    r1 = run_closed_loop(batcher, stream[:half])
    # a federated round lands mid-stream: new heads, same prototypes —
    # one jitted refresh and the very next batch serves the new index
    keys2 = jax.random.split(jax.random.PRNGKey(args.seed + 1), C)
    tr = time.perf_counter()
    engine.update(_stack_thetas(keys2, cfg))
    refresh_ms = (time.perf_counter() - tr) * 1e3
    r2 = run_closed_loop(batcher, stream[half:])

    for tag, r in (("pre-update ", r1), ("post-update", r2)):
        print(f"{tag}: {r['n']} queries  QPS={r['qps']:.0f}  "
              f"p50={r['p50_ms']:.2f}ms  p99={r['p99_ms']:.2f}ms")
    print(f"index update (new adaptive heads, no re-extraction): "
          f"{refresh_ms:.1f} ms")
    if stats is not None:
        obs.metric("serve.stats", stats.snapshot(), mode=args.mode)


if __name__ == "__main__":
    main()
