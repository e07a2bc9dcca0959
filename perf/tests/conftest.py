"""Tests of the benchmark itself, on the CPU at sizes a test run holds.

They import the benchmark as the package ``perf`` and the program from
``src``; run them from the repository root with
``JAX_PLATFORMS=cpu python -m pytest perf/tests``.
"""
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def tiny():
    """Cell specs cut to CPU test size (widths kept; clients, gallery rows,
    batch and rate smaller)."""
    from perf import harness

    def make(cell):
        spec = harness.load_spec(cell)
        if spec.config["system"] == "gallery":
            spec.config.update(n_clients=2, gallery_rows=4096, batch=8)
            spec.traffic["rate_qps"] = 100
        else:
            # the cell's own warm-up: ring full, FIFO eviction running
            spec.config.update(n_clients=4)
        return spec
    return make


@pytest.fixture
def drive():
    """Run a spec the way ``run.py`` does, past the look for a chip."""
    import jax

    from perf import harness

    def run(spec, seed=2 ** 31 + 3, seconds=1.5):
        clock = harness.CompileCounter()
        rec = harness.system(spec.config["system"]).run(
            spec, seed=seed, seconds=seconds, trace=False, clock=clock,
            t_start=time.perf_counter(), devices=jax.devices())
        out, _ = harness.result_line(rec, spec, jax.devices(), False)
        return out, rec
    return run
