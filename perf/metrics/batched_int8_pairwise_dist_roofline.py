"""Kernel: ``batched_int8_pairwise_dist``'s share of its roofline in the profiled window
(operations and bytes from ``kernels/batched_int8_pairwise_dist.py``)."""
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    return _common.roofline(layer, "batched_int8_pairwise_dist")
