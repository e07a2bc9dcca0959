"""Whole launch: the operations the queries answered in the profiled
window required (``kernels/gallery_query.py``) per second of that window,
as a share of the chip's bf16 peak."""
from perf.harness import load_module
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    prof = layer.get("profile")
    if prof is None or not layer.get("serve"):
        return None
    ops = load_module(_common.PERF / "kernels" / "gallery_query.py").query_ops(
        layer["kernel_shapes"]) * layer["profiled_queries"]
    pk = _common.peaks(layer["device_kind"])["bf16_flops_per_s"]
    return 100.0 * ops / prof.window_s / pk
