"""Kernel: ``batched_ivf_shortlist``'s share of its roofline in the profiled window
(operations and bytes from ``kernels/batched_ivf_shortlist.py``)."""
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    return _common.roofline(layer, "batched_ivf_shortlist")
