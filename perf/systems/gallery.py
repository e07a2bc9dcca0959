"""Retrieval serving: open-loop queries through ``ContinuousBatcher`` into
``RetrievalEngine`` over a device-resident ``GalleryIndex``.

Set-up makes the heads, the clustered gallery and the query stream on the
device from the seed, builds the index (refresh: frozen-BN featurisation,
int8 codes and, in ivf mode, the coarse quantizer), and warms the one
launch shape the batcher uses. The window offers the cell's Poisson
arrivals at the cell's fixed rate; every query is answered, late or not,
and its latency runs from its scheduled arrival to its answer.

With ``--trace 1`` the first half of the schedule runs under the profiler
(device busy time, kernel time, top operations, idle gaps by host span)
and the second half without it (queue, service, fill and pacer readings).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from perf import harness
from perf.reference import gallery as ref
from perf.traffic import gallery_data, pacer

SAMPLE = 2048                     # answers compared with the reference


def _p(values, q):
    return float(np.percentile(np.asarray(values), q)) if len(values) else 0.0


def build(spec, seed):
    """Heads, gallery and index from the seed, behind a batcher whose one
    launch shape is compiled: (heads, host rows, centres, index,
    batcher)."""
    import jax

    from repro.serving import ContinuousBatcher, GalleryIndex, RetrievalEngine

    cfg, mode = spec.config, spec.traffic["mode"]
    C, G = cfg["n_clients"], cfg["gallery_rows"]
    heads, rows, centres = gallery_data.make(seed, C, G, cfg["model"],
                                             cfg["gallery"])
    rows_host = np.asarray(rows)
    index = GalleryIndex(list(rows_host),
                         [np.arange(G, dtype=np.int32)] * C,
                         keep_fp32=False,
                         nlist=cfg["nlist"] if mode == "ivf" else 0)
    engine = RetrievalEngine(index, heads, mode=mode, k=cfg["k"],
                             nprobe=cfg["nprobe"])
    batcher = ContinuousBatcher(engine, batch=cfg["batch"],
                                policy=cfg["policy"])
    warm, _ = gallery_data.queries(seed + 1, centres, np.arange(C),
                                   cfg["gallery"]["id_rho"])
    for _ in range(3):                       # the one launch shape
        for c in range(C):
            batcher.submit(c, warm[c])
        batcher.drain()
    jax.block_until_ready(index.gq)
    return heads, rows_host, centres, index, batcher


def run(spec, *, seed, seconds, trace, clock, t_start, devices):

    cfg, mix = spec.config, spec.traffic
    C, k = cfg["n_clients"], cfg["k"]
    mode = mix["mode"]
    sched = pacer.poisson_schedule(seed, mix["rate_qps"], seconds, C)
    heads, rows_host, centres, index, batcher = build(spec, seed)
    protos, _ = gallery_data.queries(seed, centres, sched.clients,
                                     cfg["gallery"]["id_rho"])
    engine = batcher.engine
    gc.collect()
    gc.freeze()     # set-up's objects: out of the window's collections

    n_compiles = clock.n
    setup_s = time.perf_counter() - t_start
    prof = None
    if trace:
        from repro.obs import trace as obs
        first, second = sched.split(seconds / 2)
        prof = harness.Profile()
        prof.start()
        with obs.active(harness.annotator()):
            part = pacer.run_open_loop(batcher, first,
                                       protos[:len(first.times)],
                                       seconds / 2, annotate=True)
        prof.stop()
        profiled_queries = sum(l.slots for l in part.launches)
        run_ = pacer.run_open_loop(batcher, second,
                                   protos[len(first.times):], seconds / 2)
        offset = len(first.times)
    else:
        run_ = pacer.run_open_loop(batcher, sched, protos, seconds)
        offset = 0
    window_compiles = clock.n - n_compiles
    peak = harness.memory_peak(devices)

    lat = run_.latency
    e2e = {"setup_s": (setup_s, "s"),
           "query_p50_ms": (_p(lat, 50) * 1e3, "ms"),
           "served_qps": (run_.answered_by(run_.t_end) / seconds, "queries/s")}
    layer = {"window_compiles": window_compiles, "latency_s": lat,
             "lag_s": run_.lag, "queue_s": run_.queue_s,
             "launches": run_.launches, "slots_per_launch": C * cfg["batch"],
             "serve": True}
    if prof is not None:
        layer["profile"] = prof.reduce(harness.kernel_patterns())
        layer["kernel_shapes"] = _kernel_shapes(cfg, mode, index)
        layer["profiled_queries"] = profiled_queries
        prof.close()

    # every query's answer, then a sample drawn from the seed
    n_all = len(lat)
    ids, dists = run_.ids, run_.dists
    clients = sched.clients[offset:]
    qs = protos[offset:]
    del batcher, engine, index, run_
    gc.collect()
    rng = np.random.default_rng([seed, 0xC0DE])
    pick = np.sort(rng.choice(n_all, size=min(SAMPLE, n_all), replace=False))
    readings = ref.compare(heads, rows_host, clients[pick], qs[pick],
                           ids[pick], dists[pick], k=k)
    return harness.Record(e2e=e2e, readings=readings, attempted=n_all,
                          failed=0, memory_peak_bytes=peak, layer=layer)


def _kernel_shapes(cfg, mode, index):
    C, B, F = cfg["n_clients"], cfg["batch"], cfg["model"]["feat_dim"]
    shapes = {"C": C, "B": B, "F": F, "D": cfg["model"]["proto_dim"],
              "H": cfg["model"]["hidden"], "G": cfg["gallery_rows"],
              "k": cfg["k"], "mode": mode}
    if mode == "ivf":
        shapes.update(nlist=index.nlist, bcap=index.bcap,
                      nprobe=cfg["nprobe"])
    return shapes
