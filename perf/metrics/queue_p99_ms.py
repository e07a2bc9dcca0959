"""Front end: 99th percentile of the time a query waited for a batch slot
(``Ticket.queue_s``: scheduled arrival to its launch)."""
from perf.metrics import _common

UNIT = "ms"


def read(layer, spec):
    if "queue_s" not in layer:
        return None
    v = _common.p99(layer["queue_s"])
    return None if v is None else 1e3 * v
