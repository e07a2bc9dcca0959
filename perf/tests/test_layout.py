"""The benchmark's files fit together: cells, configurations, traffic,
readers and kernels found by name, and ``BENCHMARK.json`` consistent with
them."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

PERF = ROOT / "perf"
CELLS = sorted(p.stem for p in (PERF / "cells").glob("*.json"))


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load(cell):
    from perf import harness
    spec = harness.load_spec(cell)
    assert (PERF / "systems" / f"{spec.config['system']}.py").exists()
    assert (PERF / "reference" / f"{spec.config['system']}.py").exists()
    assert spec.limits, "a cell without limits can never be correct"
    assert spec.chips in (1, 4)


def test_benchmark_names_existing_pieces():
    bench = _bench()
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        assert (ROOT / c["file"]).exists()
    for w in bench["workloads"]:
        cell = json.loads((PERF / "cells" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and w["config"] in configs
        assert cell["traffic"] == w["traffic"]
        assert cell.get("chips", 1) == w["chips"]
    for m in bench["per_layer"]:
        assert (PERF / "metrics" / f"{m['name']}.py").exists(), m["name"]


def test_moves_targets_are_reported_where_read():
    """Each per-layer metric's cells report the end-to-end metric it
    moves."""
    bench = _bench()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        where = set(m.get("workloads", cells))
        assert where <= e2e[m["moves"]], (m["name"], where - e2e[m["moves"]])
    for cell in cells:
        reported = [k for k, v in e2e.items() if cell in v]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"]), cell


def test_readers_and_kernels_are_well_formed():
    from perf import harness
    for path in sorted((PERF / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        mod = harness.load_module(path)
        assert isinstance(mod.UNIT, str) and callable(mod.read)
        assert mod.read({}, None) is None      # nothing to read: nothing
    pats = harness.kernel_patterns()
    assert set(pats) >= {"batched_quantize", "fused_relevance_aggregate",
                         "batched_pairwise_dist", "batched_int8_pairwise_dist",
                         "batched_ivf_shortlist"}


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = CELLS[0]
    r = subprocess.run([sys.executable, "perf/run.py", "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    last = (r.stdout.strip().splitlines() or [""])[-1]
    assert not last.startswith("{"), last
