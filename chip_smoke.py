"""Bring-up smoke run of the FedSTIL main path on TPU.

One chip (the default) drives, through the normal entry points and at the
full width of ``EdgeModelConfig()`` (P = 57,664 adaptive parameters per
client):

  * train — ``run_simulation(engine="stacked")`` of FedSTIL at C=100 with
    the ``topk+int8`` wire codec: gather, vmapped local training, batched
    codec, staged server (ring push, KL relevance, fused Eq. 5→6
    aggregate), apply and one batched device eval; then the compiled
    aggregate / quantize / top-k kernels against their jnp oracles on the
    same inputs;
  * serve — a ``GalleryIndex`` of 131,072 rows per client (C=4) behind
    ``RetrievalEngine`` + ``ContinuousBatcher`` in int8 and ivf modes with
    an index update mid-stream; top-k ids against the ``backend="ref"``
    engine, and full-probe ivf recall against exact int8.

``--chips 4`` runs only the sharded engine at C=100 over every device,
against the stacked engine on the first device.

The last line of standard output is ``{"ok": true, "device": {...}}`` and
the exit code 0 only when every phase passed. Without a TPU, or outside
the repository, the script exits non-zero and prints no such line.

Usage: python chip_smoke.py [--chips 1|4]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
TOL_DIST = 1e-5           # distances that close are a tie for top-k order


def log(msg):
    print(msg, flush=True)


class CompileClock:
    """Sums the backend compile seconds JAX reports while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.total += secs


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def phase_train(C=100, n_tasks=2, rounds=3):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig
    from repro.data import FederatedReIDBenchmark
    from repro.federated import run_simulation

    cfg = EdgeModelConfig()
    bench = FederatedReIDBenchmark(n_clients=C, n_tasks=n_tasks, seed=SEED)
    assert bench.n_classes <= cfg.n_classes
    t0 = time.perf_counter()
    res = run_simulation(FedSTIL(cfg, n_clients=C, codec="topk+int8"), bench,
                         engine="stacked", rounds=rounds, eval_every=rounds,
                         seed=SEED)
    wall = time.perf_counter() - t0
    m = res.final_metrics()
    log(f"train: C={C} rounds={rounds} wall={wall:.1f}s "
        f"mAP={m['mAP']:.4f} R1={m['R1']:.4f} "
        f"forgetting_mAP={m['forgetting_mAP']:.4f}")
    log(f"train: codec bytes c2s={res.comm.total_c2s} "
        f"s2c={res.comm.total_s2c} (formula "
        f"{res.comm.total_formula}) device_eval={res.eval_on_device}")
    assert res.eval_on_device, "eval fell back to the host oracle"
    assert all(np.isfinite(v) for v in m.values()), m
    _kernels_vs_ref(C, cfg, jax, jnp, np)


def _kernels_vs_ref(C, cfg, jax, jnp, np):
    """The compiled aggregate / quantize / top-k kernels against their jnp
    oracles on the same inputs, within their interpret-mode test
    tolerances (tests/test_kernels.py, tests/test_stacked_engine.py)."""
    from repro.common.pytree import tree_flatten_stacked
    from repro.core import edge_model as EM
    from repro.kernels import ops

    keys = jax.random.split(jax.random.PRNGKey(SEED), C + 2)
    theta = jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(keys[:C])
    flat, _ = tree_flatten_stacked(theta)
    flat = flat + 0.01 * jax.random.normal(keys[C], flat.shape)
    w = jax.random.uniform(keys[C + 1], (C, C))

    b, wn = ops.fused_relevance_aggregate(w, flat)
    br, wnr = ops.fused_relevance_aggregate(w, flat, backend="ref")
    np.testing.assert_allclose(np.asarray(wn), np.asarray(wnr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(b), np.asarray(br),
                               rtol=1e-5, atol=1e-5)
    log(f"kernels: fused_relevance_aggregate (C={C}, P={flat.shape[1]}) "
        f"== ref, max|dB|={float(jnp.max(jnp.abs(b - br))):.3g}")

    q, s = ops.batched_quantize(flat)
    qr, sr = ops.batched_quantize(flat, backend="ref")
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    dq = np.asarray(q, np.int32) - np.asarray(qr, np.int32)
    log(f"kernels: batched_quantize codes differing {int((dq != 0).sum())}"
        f" of {dq.size}")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    d = ops.batched_dequantize(q, s)
    dr = ops.batched_dequantize(qr, sr, backend="ref")
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                               rtol=1e-6, atol=1e-7)

    v, i = ops.batched_topk_pack(flat, kg=2)
    vr, ir = ops.batched_topk_pack(flat, kg=2, backend="ref")
    np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    log(f"kernels: batched_quantize and batched_topk_pack (C={C}, "
        f"P={flat.shape[1]}) == ref bit for bit")


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def _ids_match(ids, d, ids_ref, d_ref, np):
    """(fraction of queries with identical top-k ids, True when every
    difference is a tie: the two distance lists agree within TOL_DIST)."""
    same = (ids == ids_ref).all(-1)
    close = np.isclose(d, d_ref, rtol=TOL_DIST, atol=TOL_DIST).all(-1)
    return float(same.mean()), bool((same | close).all())


def _tie_aware_recall(ids, d, ids_exact, d_exact, np):
    """(plain recall@k, True when every exact top-k id the approximate
    list misses sits at the k-th distance within TOL_DIST — a tie)."""
    hit = (ids_exact[..., :, None] == ids[..., None, :]).any(-1)
    kth = d[..., -1:]
    tie = np.abs(d_exact - kth) <= TOL_DIST * np.maximum(1.0, np.abs(kth))
    return float(hit.mean()), bool((hit | tie).all())


def phase_serve(C=4, G=131072, batch=64):
    import jax
    import numpy as np

    from repro.core import edge_model as EM
    from repro.serving import (ContinuousBatcher, GalleryIndex,
                               RetrievalEngine)

    cfg = EM.EdgeModelConfig()
    rng = np.random.default_rng(SEED)

    def heads(seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), C)
        return jax.vmap(lambda k: EM.init_adaptive_layers(k, cfg))(keys)

    protos = [rng.standard_normal((G, cfg.proto_dim), np.float32)
              for _ in range(C)]
    ids = [np.arange(G, dtype=np.int32) for _ in range(C)]
    t0 = time.perf_counter()
    index = GalleryIndex(protos, ids, keep_fp32=False, nlist="auto")
    theta = heads(SEED)
    engines = {"int8": RetrievalEngine(index, theta, mode="int8")}
    engines["ivf"] = RetrievalEngine(index, theta, mode="ivf",
                                     refresh=False)
    log(f"serve: C={C} G={G} nlist={index.nlist} bcap={index.bcap} "
        f"int8 image {index.resident_bytes('int8') / 1e6:.1f} MB, "
        f"built in {time.perf_counter() - t0:.1f}s")

    def check(tag):
        qp = rng.standard_normal((C, batch, cfg.proto_dim)).astype(np.float32)
        qm = np.ones((C, batch), np.float32)
        out = {}
        for mode, eng in engines.items():
            # the same batch through the continuous batcher...
            batcher = ContinuousBatcher(eng, batch=batch)
            tickets = [batcher.submit(c, qp[c, b])
                       for b in range(batch) for c in range(C)]
            t1 = time.perf_counter()
            batcher.drain()
            dt = time.perf_counter() - t1
            got = np.stack([np.stack([tickets[b * C + c].ids
                                      for b in range(batch)])
                            for c in range(C)])
            gd = np.stack([np.stack([tickets[b * C + c].dists
                                     for b in range(batch)])
                           for c in range(C)])
            # ...and through the ref-backend engine over the same image
            ref = RetrievalEngine(index, eng.theta, mode=mode,
                                  nprobe=eng.nprobe, backend="ref",
                                  refresh=False)
            ri, rd = ref.query_batch(qp, qm)
            frac, ok = _ids_match(got, gd, ri, rd, np)
            log(f"serve[{tag}] {mode}: {C * batch} queries in {dt:.3f}s "
                f"(cold: includes compile), top-k ids == ref on "
                f"{frac:.4f} of queries (rest ties: {ok})"
                if tag == "pre-update" else
                f"serve[{tag}] {mode}: {C * batch} queries in {dt:.3f}s, "
                f"top-k ids == ref on {frac:.4f} of queries "
                f"(rest ties: {ok})")
            assert ok, f"{mode} top-k differs from the ref engine"
            out[mode] = (got, gd)
        full = RetrievalEngine(index, engines["ivf"].theta, mode="ivf",
                               nprobe=index.nlist, refresh=False)
        fi, fd = full.query_batch(qp, qm)
        rec, ok = _tie_aware_recall(fi, fd, *out["int8"], np)
        log(f"serve[{tag}] ivf nprobe=nlist recall@10 vs int8 = {rec:.4f} "
            f"(misses are ties: {ok})")
        assert ok, "full-probe ivf missed exact int8 neighbours"

    check("pre-update")
    t1 = time.perf_counter()
    theta2 = heads(SEED + 1)
    for eng in engines.values():
        eng.update(theta2)
    jax.block_until_ready(index.bq)
    log(f"serve: index update (new heads, both engines) "
        f"{time.perf_counter() - t1:.1f}s")
    check("post-update")


# --------------------------------------------------------------------------
# four chips: sharded engine == stacked engine
# --------------------------------------------------------------------------

def phase_sharded(C=100, n_tasks=2, rounds=2):
    from repro.core import FedSTIL
    from repro.core.edge_model import EdgeModelConfig
    from repro.data import FederatedReIDBenchmark
    from repro.federated import run_simulation
    from repro.obs import trace as obs

    cfg = EdgeModelConfig()
    bench = FederatedReIDBenchmark(n_clients=C, n_tasks=n_tasks, seed=SEED)

    def run(engine):
        strat = FedSTIL(cfg, n_clients=C, codec="topk+int8",
                        wire_dtype="float32")
        tracer = obs.Tracer()
        t0 = time.perf_counter()
        res = run_simulation(strat, bench, engine=engine, rounds=rounds,
                             eval_every=rounds, seed=SEED, trace=tracer)
        log(f"{engine}: {strat.mesh.devices.size} device(s) "
            f"wall={time.perf_counter() - t0:.1f}s (traced) "
            f"mAP={res.final('mAP'):.6f} c2s={res.comm.total_c2s} "
            f"s2c={res.comm.total_s2c} device_eval={res.eval_on_device}")
        return res, [e for e in tracer.events if e["kind"] == "metric"]

    sharded, sm = run("sharded")
    stacked, tm = run("stacked")
    _report_divergence(sharded, sm, stacked, tm, C)
    gap = abs(sharded.final("mAP") - stacked.final("mAP"))
    log(f"sharded vs stacked: |dmAP|={gap:.3g}")
    assert sharded.eval_on_device and stacked.eval_on_device
    assert gap <= 1e-5, gap
    assert sharded.comm.total_c2s == stacked.comm.total_c2s
    assert sharded.comm.total_s2c == stacked.comm.total_s2c


def _report_divergence(a, a_metrics, b, b_metrics, C):
    """Where two runs of the same rounds part: the per-client device
    metrics each round records (upload/dispatch residual norms, relevance
    row mass, ...) in recording order, then the per-(client, task) eval."""
    import numpy as np

    for i, (ea, eb) in enumerate(zip(a_metrics, b_metrics)):
        worst, n_diff = (0.0, ""), 0
        for key, va in ea["values"].items():
            xa = np.asarray(va, np.float64).ravel()[:C]
            xb = np.asarray(eb["values"][key], np.float64).ravel()[:C]
            rel = np.abs(xa - xb) / np.maximum(np.abs(xb), 1e-30)
            worst = max(worst, (float(rel.max()), key))
            n_diff = max(n_diff, int((xa != xb).sum()))
        log(f"  metric #{i} {ea['name']} "
            f"({ea.get('direction', ea.get('round', ''))}): max rel diff "
            f"{worst[0]:.3g} ({worst[1]}), clients differing {n_diff}/{C}")
    diffs = [abs(ma["mAP"] - mb["mAP"])
             for ra, rb in zip(a.tracker.records, b.tracker.records)
             for t in ra for (_, ma), (_, mb) in zip(ra[t], rb[t])]
    log(f"  eval: (client, task) mAPs differing "
        f"{sum(d > 0 for d in diffs)}/{len(diffs)}, max {max(diffs):.3g}")


# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    try:
        from repro.common.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: the repository's src/repro is not beside this "
              "script", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} x{len(devices)} "
        f"cache={enable_compile_cache()} jax={jax.__version__}")
    clock = CompileClock()
    phases = ([("sharded", phase_sharded)] if args.chips == 4
              else [("train", phase_train), ("serve", phase_serve)])
    failed = []
    for name, fn in phases:
        t0, c0 = time.perf_counter(), clock.total
        try:
            fn()
            status = "ok"
        except Exception:                     # report, run the next phase
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        log(f"phase {name}: {status} in {time.perf_counter() - t0:.1f}s "
            f"(compile {clock.total - c0:.1f}s)")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
