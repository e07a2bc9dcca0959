"""Kernel: ``fused_relevance_aggregate``'s share of its roofline in the profiled window
(operations and bytes from ``kernels/fused_relevance_aggregate.py``)."""
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    return _common.roofline(layer, "fused_relevance_aggregate")
