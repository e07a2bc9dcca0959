#!/usr/bin/env bash
# Reproducible tier-1 run: install dev extras (best-effort: the suite
# degrades gracefully — hypothesis-only modules importorskip) and run the
# ROADMAP verify command.
#
# Usage: scripts/run_tier1.sh [--smoke] [pytest args...]
#   --smoke  additionally exercise the device-resident path end-to-end:
#            a 2-round FedSTIL simulation on engine="stacked", the
#            `--only relevance` kernel-bench sweep, a 1-eval smoke of
#            the batched eval-round bench (device vs host-loop parity),
#            the wire-codec comm bench at C=5 (1-round encode/decode
#            host-vs-batched parity assert), a 2-round engine="sharded"
#            simulation on a forced 8-device host mesh (stacked-parity
#            assert), the mesh scaling bench at C=100
#            (sharded-vs-stacked aggregate parity), and a tiny-gallery
#            retrieval-serving smoke (int8 + ivf shortlist + naive
#            paths, exact fp32-vs-numpy-oracle rank parity, full-probe
#            ivf recall == 1.0), and an observability smoke (2-round
#            stacked sim traced to JSONL, report CLI parses it, tracing
#            overhead gate <2%).
set -euo pipefail
cd "$(dirname "$0")/.."

SMOKE=0
if [[ "${1:-}" == "--smoke" ]]; then
    SMOKE=1
    shift
fi

python -m pip install -q -r requirements-dev.txt \
    || echo "warning: dev extras not installed (offline?); continuing" >&2

# fast style/import gate (best-effort: the container image ships no ruff
# wheel; repro.analysis.lint below enforces the unused-import class anyway)
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests
else
    echo "warning: ruff not installed; skipping style gate" >&2
fi

# static-analysis gate: trace every registered program and run the jaxpr +
# convention lints (zero non-baselined findings required)
echo "=== static analysis: repro.analysis.lint ==="
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.lint

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"

if [[ "$SMOKE" == "1" ]]; then
    echo "=== smoke: 2-round engine=\"stacked\" FedSTIL simulation ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark

from repro.federated import run_simulation

bench = FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                               ids_per_task=8, samples_per_id=6, seed=0)
cfg = EdgeModelConfig(n_classes=bench.n_classes)
res = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                     rounds=2, eval_every=2, engine="stacked", verbose=True)
assert res.rounds, "stacked smoke produced no eval rounds"
print(f"stacked smoke OK: mAP={res.final('mAP'):.4f} "
      f"server={res.server_time_s*1e3:.1f}ms")
EOF
    echo "=== smoke: relevance bench sweep ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.kernels_bench --only relevance
    echo "=== smoke: batched eval round (device vs host loop) ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.eval_round --smoke
    echo "=== smoke: wire-codec comm round (host loop vs batched, parity) ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.comm_round --smoke
    echo "=== smoke: 2-round engine=\"sharded\" simulation, 8-device mesh ==="
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import jax
assert jax.device_count() == 8, jax.device_count()
from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation

bench = FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                               ids_per_task=8, samples_per_id=6, seed=0)
cfg = EdgeModelConfig(n_classes=bench.n_classes)
mk = lambda: FedSTIL(cfg, n_clients=3, epochs=2, wire_dtype="float32")
sharded = run_simulation(mk(), bench, rounds=2, eval_every=2,
                         engine="sharded")
stacked = run_simulation(mk(), bench, rounds=2, eval_every=2,
                         engine="stacked")
assert abs(sharded.final("mAP") - stacked.final("mAP")) < 1e-6
assert sharded.comm.total_c2s == stacked.comm.total_c2s
print(f"sharded smoke OK: 8 devices, C=3 padded, "
      f"mAP={sharded.final('mAP'):.4f} == stacked, comm bytes equal")
EOF
    echo "=== smoke: mesh scaling bench (stacked vs sharded aggregate) ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.mesh_round --smoke
    echo "=== smoke: retrieval serving (int8 + ivf + naive, oracle parity) ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.serve_bench --smoke
    echo "=== smoke: observability (traced sim -> report CLI, overhead gate) ==="
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'EOF'
import contextlib, io, json, tempfile
from pathlib import Path

from repro.core import FedSTIL
from repro.core.edge_model import EdgeModelConfig
from repro.data import FederatedReIDBenchmark
from repro.federated import run_simulation
from repro.obs.report import main as report_main, summarize
from repro.obs.trace import RunLog

out = Path(tempfile.mkdtemp()) / "obs_run.jsonl"
bench = FederatedReIDBenchmark(n_clients=3, n_tasks=2, n_identities=40,
                               ids_per_task=8, samples_per_id=6, seed=0)
cfg = EdgeModelConfig(n_classes=bench.n_classes)
res = run_simulation(FedSTIL(cfg, n_clients=3, epochs=2), bench,
                     rounds=2, eval_every=2, engine="stacked",
                     trace=str(out))
events = RunLog.read(out)
s = summarize(events)
assert s["events"]["spans"] > 0, "traced sim recorded no spans"
assert "round.server" in s["phases"], sorted(s["phases"])
assert "server.relevance" in s["stages"], sorted(s["stages"])
assert isinstance(s["clients"].get("staleness"), list), s["clients"]
# the report CLI must parse the same JSONL end-to-end; it runs in this
# process (a child would be a second process that may reach for the chip)
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    assert report_main([str(out), "--json"]) == 0
parsed = json.loads(buf.getvalue())
assert parsed["events"] == s["events"]
print(f"obs smoke OK: {s['events']['spans']} spans, "
      f"{s['events']['metrics']} metrics, report CLI parses")

# off-by-default-cheap: re-measure the tracing tax (small C: quick)
from benchmarks.server_round import measure_overhead
overhead, _ = measure_overhead(C=20, iters=4, repeats=2)
assert overhead["pass"], f"tracing overhead gate FAILED: {overhead}"
print(f"overhead gate OK: {overhead['overhead_frac']*100:.2f}% "
      f"< {overhead['gate']*100:.0f}% @C={overhead['C']}")
EOF
fi
