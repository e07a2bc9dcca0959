"""Per-eval-round wall time: host Python loop vs the batched device program.

The host baseline is a faithful replica of the pre-PR ``_eval_round``: per
client it re-extracts the gallery prototypes (``EM.extract_prototypes`` on
the raw gallery every eval round), runs the eager per-client feature head
(which materialises the unused classifier logits — eager jax cannot DCE
them), and per trained task runs one more feature dispatch plus a numpy
``evaluate_retrieval`` — O(C·T) host iterations per eval round. The device
path is this PR's ``_eval_round_device``: padded (C, T, Q, D) query stacks,
gallery prototypes cached across rounds, vmapped feature heads, all
distance matrices through the kernels/pairwise_dist path, and sort-free
mAP/CMC + forgetting inputs in ONE jitted program, with only the
(C, T, metrics) result read back. ``host_cached_ms`` additionally reports
the PR's improved host path (gallery prototype cache, satellite task) so
the JSON separates the caching win from the batching win.

``python -m benchmarks.run --bench eval`` sweeps C ∈ {5, 20, 100} and
writes ``BENCH_eval_round.json`` (repo root). ``--smoke`` (used by
``scripts/run_tier1.sh --smoke``) runs a single C=5 eval as a wiring check.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.data.synthetic import FederatedReIDBenchmark
from repro.evalreid import evaluate_retrieval
from repro.federated.simulation import (_EvalCache, _eval_round,
                                        _eval_round_device,
                                        _pre_extract_prototypes)
from repro.lifelong import STL
from repro.sharding.specs import engine_mesh
from repro.train.metrics import LifelongTracker

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_eval_round.json"


def _setup(C: int, n_tasks: int):
    bench = FederatedReIDBenchmark(n_clients=C, n_tasks=n_tasks,
                                   n_identities=max(400, 10 * C),
                                   ids_per_task=6, samples_per_id=4, seed=0)
    cfg = EdgeModelConfig(n_classes=bench.n_classes)
    strat = STL(cfg)
    key = jax.random.PRNGKey(0)
    g_key, *client_keys = jax.random.split(key, C + 1)
    g_params = EM.init_extraction(g_key, cfg)
    states = {c: strat.init_client(client_keys[c]) for c in range(C)}
    protos = _pre_extract_prototypes(bench, g_params)
    cache = _EvalCache(bench, protos, engine_mesh(jax.devices()[:1]), C)
    return bench, strat, states, g_params, protos, cache


def _eval_round_pre_pr(strategy, states, bench, g_params, protos, tracker,
                       rnd, t):
    """The pre-PR host eval loop, verbatim: gallery prototypes re-extracted
    every round, eager per-client features, numpy metrics per (c, t)."""
    for c in range(bench.n_clients):
        state = states[c]
        gal_x, gal_y = bench.gallery(c, t)
        gal_p = np.asarray(EM.extract_prototypes(g_params, gal_x))
        gal_f = strategy.features(state, gal_p)
        for tt in range(t + 1):
            _, _, qx, qy = protos[(c, tt)]
            qf = strategy.features(state, qx)
            m = evaluate_retrieval(qf, qy, gal_f, gal_y)
            tracker.record(c, tt, rnd, m)


def _time(fn, iters):
    fn(0)                                    # warmup (jit compile / caches)
    t0 = time.perf_counter()
    for r in range(1, iters + 1):
        fn(r)
    return (time.perf_counter() - t0) / iters


def bench_eval_round(Cs=(5, 20, 100), *, n_tasks=2, iters=4,
                     out=DEFAULT_OUT):
    cases = []
    print("C,host_ms,host_cached_ms,device_ms,speedup")
    for C in Cs:
        bench, strat, states, g_params, protos, cache = _setup(C, n_tasks)
        t = n_tasks - 1                      # all tasks trained: worst case

        tr_h = LifelongTracker(C)
        host_s = _time(lambda r: _eval_round_pre_pr(
            strat, states, bench, g_params, protos, tr_h, r, t), iters)
        tr_c = LifelongTracker(C)
        cached_s = _time(lambda r: _eval_round(
            strat, lambda c: states[c], bench, cache, tr_c, r, t), iters)
        # host-engine device path: restacks the eval thetas each round (the
        # stacked engine keeps them resident and is strictly cheaper)
        tr_d = LifelongTracker(C)
        dev_s = _time(lambda r: _eval_round_device(
            strat.stack_eval_thetas(states), cache, tr_d, r, t),
            iters)

        # same tracker metrics from all paths (allclose guard, not a perf op)
        for key in ("mAP", "R1", "R3", "R5"):
            np.testing.assert_allclose(tr_h.mean_accuracy(iters, key),
                                       tr_d.mean_accuracy(iters, key),
                                       atol=2e-3)
            np.testing.assert_allclose(tr_c.mean_accuracy(iters, key),
                                       tr_d.mean_accuracy(iters, key),
                                       atol=2e-3)
        case = {"C": C, "gallery_rows": int(cache.g_max),
                "max_matches": int(cache.max_matches),
                "host_ms": host_s * 1e3, "host_cached_ms": cached_s * 1e3,
                "device_ms": dev_s * 1e3, "speedup": host_s / dev_s}
        cases.append(case)
        print(f"{C},{case['host_ms']:.2f},{case['host_cached_ms']:.2f},"
              f"{case['device_ms']:.2f},{case['speedup']:.1f}x", flush=True)
    from benchmarks.common import mesh_metadata
    from repro.analysis.registry import coverage
    cov = coverage()
    payload = {
        "bench": "eval_round",
        "env": mesh_metadata(),
        "config": {"n_tasks": n_tasks, "iters": iters,
                   "backend": jax.default_backend()},
        "analysis_coverage": {k: cov[k] for k in ("programs_registered",
                                                  "programs_traced")},
        "cases": cases,
    }
    Path(out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    return payload


def smoke():
    """One C=5 eval round on both paths (the run_tier1.sh --smoke hook)."""
    bench, strat, states, g_params, protos, cache = _setup(5, 2)
    theta = strat.stack_eval_thetas(states)
    tr_h, tr_d = LifelongTracker(5), LifelongTracker(5)
    _eval_round(strat, lambda c: states[c], bench, cache, tr_h, 0, 1)
    _eval_round_device(theta, cache, tr_d, 0, 1)
    for key in ("mAP", "R1"):
        np.testing.assert_allclose(tr_h.mean_accuracy(0, key),
                                   tr_d.mean_accuracy(0, key), atol=2e-3)
    print(f"eval smoke OK: device mAP={tr_d.mean_accuracy(0, 'mAP'):.4f} "
          f"== host mAP={tr_h.mean_accuracy(0, 'mAP'):.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single C=5 eval round (wiring check, no JSON)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        bench_eval_round()


if __name__ == "__main__":
    main()
