"""Round driver: milliseconds per round in ``round.server`` (the program's
span, synced), over the traced run's second part."""
from perf.metrics import _common

UNIT = "ms"


def read(layer, spec):
    return _common.phase_ms(layer, "round.server")
