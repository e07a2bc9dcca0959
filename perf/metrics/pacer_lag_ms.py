"""Load generator: 99th percentile of how late the pacer submitted a
query against its scheduled arrival (a launch in progress delays the
submits that come due during it)."""
from perf.metrics import _common

UNIT = "ms"


def read(layer, spec):
    if "lag_s" not in layer:
        return None
    v = _common.p99(layer["lag_s"])
    return None if v is None else 1e3 * v
