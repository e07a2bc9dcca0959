"""Front end: 99th percentile of query latency, from scheduled arrival to
answer, over every query of the traced run's unprofiled part. At the
cell's load it swings between about two and four launch times with
whether a few launches overflowed, so it is read here and not bounded."""
from perf.metrics import _common

UNIT = "ms"


def read(layer, spec):
    if "latency_s" not in layer:
        return None
    v = _common.p99(layer["latency_s"])
    return None if v is None else 1e3 * v
