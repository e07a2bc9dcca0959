"""Mesh-resident server round scaling: stacked (1-device) vs sharded
(forced 8-device host mesh) FedSTIL server rounds at C → 10k clients.

Both paths run the SAME staged device programs (`FedSTIL.server_round_stacked`:
ring push + Eq. 4/5 relevance, (C, P) flatten, fused Eq. 5→6 aggregate);
the sharded path additionally pads C to the data-axis multiple, places the
(Cp, P) payload client-row-sharded (`sharding.specs`), ships the flatten
in bf16 wire form, and pins the aggregate output row-sharded (a
reduce-scatter: each device ends the round holding Cp/d × P bases, never
the full C × P). On this host the 8 "devices" are threads multiplexed
onto one physical core, so the sharded path pays a constant collective +
scheduling overhead (measured ratio 2-4x vs stacked) and no speedup is
expected; what this bench pins down is (1) the sharded path completes a
C=10k round at all, (2) its per-device peak bytes scale as Cp/d x P, and
(3) the ratio stays a flat constant (a regression in the SPMD lowering
shows up as a ratio blow-up with C).

Scaling dims are synthetic (P=1024, D=16, k=2 — recorded in config): C is
the swept axis, and the paper model's real payload is covered by
``--bench server``.

``python -m benchmarks.run --bench mesh`` sweeps C ∈ {100, 1000, 10000}
and writes ``BENCH_mesh_round.json``; ``--smoke`` runs C=100 only and
asserts sharded-vs-stacked parity on the aggregated bases.
"""
from __future__ import annotations

import os

# must precede the jax import: the forced 8-device host platform is the
# whole point of this bench
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_mesh_round.json"
P_DIM = 1024
HIST, D = 2, 16


def _per_device_peak(strat, lead: int):
    """Per-device peak bytes of the sharded aggregate program: XLA's
    ``memory_analysis`` when the backend exposes it, else the analytic
    layout footprint (resident shards + the f32 upcast + outputs)."""
    agg = strat._jit_cache["server"][2]
    d = strat.mesh.shape["data"]
    # the flatten emits the bf16 wire form only across shard boundaries
    wire = (jnp.bfloat16 if strat.wire_dtype == "bfloat16" and d > 1
            else jnp.float32)
    try:
        args = (jax.ShapeDtypeStruct((lead, lead), jnp.float32),
                jax.ShapeDtypeStruct((lead, P_DIM), wire))
        mem = agg.lower(*args).compile().memory_analysis()
        total = (int(mem.temp_size_in_bytes)
                 + int(mem.argument_size_in_bytes)
                 + int(mem.output_size_in_bytes))
        if total > 0:
            return {"source": "xla", "bytes": total // d}
    except Exception:
        pass
    itemsize = jnp.dtype(wire).itemsize
    per_dev = (lead // d) * P_DIM * (itemsize + 4 + 4)  # wire + upcast + B
    per_dev += lead * lead * 4 * 2                      # W in + Wn out (repl.)
    return {"source": "analytic", "bytes": int(per_dev)}


def _one_engine(C: int, iters: int, *, sharded: bool):
    from repro.core.edge_model import EdgeModelConfig
    from repro.core.fedstil import FedSTIL
    from repro.federated.base import pad_client_rows
    from repro.sharding import specs as shard_specs

    strat = FedSTIL(EdgeModelConfig(), n_clients=C, history_len=HIST)
    rng = np.random.default_rng(0)
    theta = {"w": jnp.asarray(rng.standard_normal((C, P_DIM)), jnp.float32)}
    feats = rng.standard_normal((iters + 1, C, D)).astype(np.float32)
    mesh = shard_specs.engine_mesh(
        jax.devices() if sharded else jax.devices()[:1])
    strat.mesh = mesh
    lead = shard_specs.padded_clients(C, mesh)
    theta = pad_client_rows(theta, lead)
    theta = jax.device_put(theta, shard_specs.named_shardings(
        mesh, shard_specs.stacked_tree_specs(theta)))
    valid = jnp.concatenate([jnp.ones((C,), jnp.float32),
                             jnp.zeros((lead - C,), jnp.float32)])
    valid = jax.device_put(valid, jax.sharding.NamedSharding(
        mesh, shard_specs.client_row_spec(1)))

    last = {}

    def one_round(r):
        f = feats[r % feats.shape[0]]
        if lead > C:
            f = np.concatenate([f, np.zeros((lead - C, D), np.float32)])
        upload = {"theta": theta, "task_feature": jnp.asarray(f)}
        d = strat.server_round_stacked(r, upload, valid)
        jax.block_until_ready(jax.tree.leaves(d["B"]))
        last["B"] = d["B"]["w"]

    one_round(0)                             # warmup (jit compile)
    t0 = time.perf_counter()
    for r in range(1, iters + 1):
        one_round(r)
    per_round = (time.perf_counter() - t0) / iters
    peak = _per_device_peak(strat, lead) if sharded else None
    return per_round, lead, peak, np.asarray(last["B"][:C])


def bench_mesh_round(Cs=(100, 1000, 10000), *, out=DEFAULT_OUT, smoke=False):
    if smoke:
        Cs = (100,)
    mesh_d = None
    cases = []
    print(f"payload P={P_DIM}, D={D}, history k={HIST}, "
          f"devices={jax.device_count()}")
    print("C,Cp,stacked_ms,sharded_ms,ratio,per_device_peak")
    for C in Cs:
        iters = 1 if C >= 10000 else 3
        stacked_s, _, _, b_st = _one_engine(C, iters, sharded=False)
        sharded_s, Cp, peak, b_sh = _one_engine(C, iters, sharded=True)
        if smoke:
            # bf16 wire is the only delta between the two paths
            np.testing.assert_allclose(b_sh, b_st, atol=5e-2, rtol=5e-2)
            print(f"parity OK: sharded B[:{C}] == stacked B (bf16 wire tol)")
        case = {"C": C, "Cp": Cp, "iters": iters,
                "stacked_ms": stacked_s * 1e3,
                "sharded_ms": sharded_s * 1e3,
                "ratio": sharded_s / stacked_s,
                "per_device_peak": peak}
        cases.append(case)
        mesh_d = peak and peak.get("source")
        print(f"{C},{Cp},{case['stacked_ms']:.2f},{case['sharded_ms']:.2f},"
              f"{case['ratio']:.2f}x,{peak['bytes']}", flush=True)
    from benchmarks.common import mesh_metadata
    from repro.analysis.registry import coverage
    cov = coverage()
    payload = {
        "bench": "mesh_round",
        "env": mesh_metadata(),
        "config": {"P": P_DIM, "D": D, "history_len": HIST,
                   "wire_dtype": "bfloat16",
                   "peak_source": mesh_d,
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
                    help="C=100 only + sharded-vs-stacked parity assert")
    args = ap.parse_args()
    bench_mesh_round(smoke=args.smoke)


if __name__ == "__main__":
    main()
