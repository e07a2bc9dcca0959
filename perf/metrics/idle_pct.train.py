"""Device: share of the profiled window of federated rounds in which no
operation ran on the chip."""
from perf.metrics import _common

UNIT = "%"


def read(layer, spec):
    return _common.idle_pct(layer, "fleet")
