"""Local train: milliseconds per round in ``local.forward`` (the
prototype upload, the vmapped forward and its readback; the program's
span, synced), over the traced run's second part."""
from perf.metrics import _spans

UNIT = "ms"


def read(layer, spec):
    return _spans.phase_ms(layer, "local.forward")
