"""Arithmetic shared by the per-layer readers."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PERF = Path(__file__).resolve().parent.parent


def peaks(kind: str) -> dict:
    table = json.loads((PERF / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def roofline(layer, kernel: str):
    """Share (%) of the least time the chip could take for the kernel's
    calls (the larger of operations over peak and HBM bytes over HBM
    bandwidth) in the kernel's summed device time. The HBM bytes are the
    kernel's bytes (``kernels/<kernel>.py``, from shapes) times the share
    the trace places in HBM; a kernel with no operations and nothing in
    HBM has no least time that the published peaks bound: nothing."""
    prof, shapes = layer.get("profile"), layer.get("kernel_shapes")
    if prof is None or shapes is None:
        return None
    calls, secs = prof.kernel_calls.get(kernel, 0), prof.kernel_s.get(kernel)
    if not calls or not secs:
        return None
    from perf.harness import load_module
    cost = load_module(PERF / "kernels" / f"{kernel}.py").cost(shapes)
    if cost is None:
        return None
    ops, nbytes, peak = cost
    pk = peaks(layer["device_kind"])
    nbytes *= prof.kernel_hbm_share.get(kernel, 1.0)
    least = max(ops / pk[peak], nbytes / pk["hbm_bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least * calls / secs


def idle_pct(layer, key: str):
    prof = layer.get("profile")
    if prof is None or not layer.get(key):
        return None
    return 100.0 * (1.0 - prof.busy_s / prof.window_s)


def phase_ms(layer, span: str):
    """Mean milliseconds per round the program's synced span took."""
    spans = layer.get("spans") or []
    rounds = sum(1 for e in spans if e["name"] == "round.gather")
    if not rounds:
        return None
    return 1e3 * sum(e["dur"] for e in spans if e["name"] == span) / rounds


def p99(values):
    values = np.asarray(values, np.float64)
    return float(np.percentile(values, 99)) if values.size else None
