"""Per-round server wall time: host engine vs the stacked device program.

The host path is PR 1's parameter server — per-client upload dicts, tracker
push into host lists, (re)stacking C pytrees to a (C, P) matrix every round,
normalize on host, unstacking C base pytrees. The stacked path is this PR's
device-resident program — one batched ring push, decayed relevance over the
resident (C, k, D) history, and the fused normalize+mask+aggregate kernel
over the already-stacked (C, ...) parameter pytree.

``python -m benchmarks.run --bench server`` sweeps C ∈ {5, 20, 100} and
writes ``BENCH_server_round.json`` (repo root) so future PRs have a
machine-readable perf trajectory to regress against. The payload also
carries a ``telemetry`` block: the per-stage span breakdown of a traced
stacked round at the largest C, and the measured overhead of running
with tracing ON vs OFF — gated at <2% of stacked round wall-time, the
subsystem's off-by-default-cheap contract.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.pytree import tree_size, tree_stack
from repro.core import edge_model as EM
from repro.core.edge_model import EdgeModelConfig
from repro.core.fedstil import FedSTIL
from repro.obs import trace as obs
from repro.sharding.specs import engine_mesh

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_server_round.json"

OVERHEAD_GATE = 0.02          # traced round may cost at most +2% wall-time


def _client_thetas(C: int, cfg: EdgeModelConfig):
    keys = jax.random.split(jax.random.PRNGKey(0), C)
    return [EM.init_adaptive_layers(k, cfg) for k in keys]


def _bench_host(C, cfg, thetas, feats, iters):
    strat = FedSTIL(cfg, n_clients=C)
    def one_round(r):
        uploads = {c: {"theta": thetas[c], "task_feature": feats[r % len(feats), c]}
                   for c in range(C)}
        dispatches = strat.server_round(r, uploads)
        jax.block_until_ready([jax.tree.leaves(d["B"])
                               for d in dispatches.values() if d])
    one_round(0)                             # warmup (jit compile)
    t0 = time.perf_counter()
    for r in range(1, iters + 1):
        one_round(r)
    return (time.perf_counter() - t0) / iters


def _one_device(strat, C):
    """Put ``strat`` on a one-device engine mesh; the all-valid mask."""
    strat.mesh = engine_mesh(jax.devices()[:1])
    return jnp.ones((C,), jnp.float32)


def _bench_stacked(C, cfg, thetas, feats, iters):
    strat = FedSTIL(cfg, n_clients=C)
    valid = _one_device(strat, C)
    stacked_theta = tree_stack(thetas)       # resident between rounds
    feats_dev = jnp.asarray(feats)
    def one_round(r):
        upload = {"theta": stacked_theta,
                  "task_feature": feats_dev[r % len(feats)]}
        d = strat.server_round_stacked(r, upload, valid)
        jax.block_until_ready(jax.tree.leaves(d["B"]))
    one_round(0)                             # warmup (jit compile)
    t0 = time.perf_counter()
    for r in range(1, iters + 1):
        one_round(r)
    return (time.perf_counter() - t0) / iters


def measure_overhead(C=100, *, D=128, iters=8, repeats=3):
    """Measure the tracing tax on the stacked server round at client
    count C: the same resident-state round loop timed with the null
    tracer (``obs.suspended`` — the off-by-default path) and with a live
    ``obs.Tracer`` (stage spans, device syncs, metric readbacks).

    Min-of-``repeats`` on both sides so scheduler noise on a small CPU
    runner cannot fake an overhead. Returns (overhead dict incl. the
    <2% gate verdict, the tracer — its events carry the per-stage span
    breakdown the bench stamps into the payload)."""
    rng = np.random.default_rng(0)
    cfg = EdgeModelConfig()
    thetas = _client_thetas(C, cfg)
    feats = rng.standard_normal((iters + 1, C, D)).astype(np.float32)
    strat = FedSTIL(cfg, n_clients=C)
    valid = _one_device(strat, C)
    stacked_theta = tree_stack(thetas)
    feats_dev = jnp.asarray(feats)

    def one_round(r):
        upload = {"theta": stacked_theta,
                  "task_feature": feats_dev[r % (iters + 1)]}
        d = strat.server_round_stacked(r, upload, valid)
        jax.block_until_ready(jax.tree.leaves(d["B"]))

    one_round(0)                             # warmup (jit compile)

    def timed():
        t0 = time.perf_counter()
        for r in range(1, iters + 1):
            one_round(r)
        return (time.perf_counter() - t0) / iters

    tracer = obs.Tracer()
    off, on = [], []
    for _ in range(repeats):
        with obs.suspended():
            off.append(timed())
        with obs.active(tracer):
            on.append(timed())
    base, traced = min(off), min(on)
    frac = max(0.0, traced - base) / base
    return ({"C": C, "iters": iters, "repeats": repeats,
             "untraced_ms": base * 1e3, "traced_ms": traced * 1e3,
             "overhead_frac": frac, "gate": OVERHEAD_GATE,
             "pass": bool(frac < OVERHEAD_GATE)}, tracer)


def bench_server_round(Cs=(5, 20, 100), *, D=128, iters=8, out=DEFAULT_OUT):
    rng = np.random.default_rng(0)
    cfg = EdgeModelConfig()
    cases = []
    print("C,host_ms,stacked_ms,speedup")
    for C in Cs:
        thetas = _client_thetas(C, cfg)
        feats = rng.standard_normal((iters + 1, C, D)).astype(np.float32)
        host_s = _bench_host(C, cfg, thetas, feats, iters)
        stacked_s = _bench_stacked(C, cfg, thetas, feats, iters)
        case = {"C": C, "host_ms": host_s * 1e3,
                "stacked_ms": stacked_s * 1e3,
                "speedup": host_s / stacked_s}
        cases.append(case)
        print(f"{C},{case['host_ms']:.2f},{case['stacked_ms']:.2f},"
              f"{case['speedup']:.1f}x", flush=True)
    # telemetry: trace the stacked round at the largest C, stamp the
    # per-stage span breakdown + the measured on-vs-off overhead gate
    overhead, tracer = measure_overhead(C=max(Cs), D=D, iters=iters)
    from repro.obs.report import telemetry_block
    telemetry = telemetry_block(tracer.events)
    telemetry["overhead"] = overhead
    print(f"tracing overhead @C={overhead['C']}: "
          f"{overhead['untraced_ms']:.2f}ms -> {overhead['traced_ms']:.2f}ms "
          f"({overhead['overhead_frac'] * 100:.2f}%, gate "
          f"{overhead['gate'] * 100:.0f}%: "
          f"{'PASS' if overhead['pass'] else 'FAIL'})", flush=True)
    from benchmarks.common import mesh_metadata
    from repro.analysis.registry import coverage
    cov = coverage()
    payload = {
        "bench": "server_round",
        "env": mesh_metadata(),
        "config": {"D": D, "history_len": 6, "iters": iters,
                   "params_per_client": tree_size(thetas[0]),
                   "backend": jax.default_backend()},
        "analysis_coverage": {k: cov[k] for k in ("programs_registered",
                                                  "programs_traced")},
        "cases": cases,
        "telemetry": telemetry,
    }
    Path(out).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {out}")
    return payload


def main():
    bench_server_round()


if __name__ == "__main__":
    main()
