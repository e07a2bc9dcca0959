"""Whole round: the operations the profiled rounds required
(``kernels/fleet_round.py``) per second of the profiled window, as a share
of the chip's bf16 peak."""
from perf.harness import load_module
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    prof = layer.get("profile")
    if prof is None or not layer.get("fleet"):
        return None
    ops = load_module(_common.PERF / "kernels" / "fleet_round.py").round_ops(
        layer["kernel_shapes"]) * layer["profiled_rounds"]
    pk = _common.peaks(layer["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / prof.window_s / pk
