"""Local train: milliseconds per round in ``local.train`` (the stacked
train program and theta's combine; the program's span, synced), over the
traced run's second part."""
from perf.metrics import _spans

UNIT = "ms"


def read(layer, spec):
    return _spans.phase_ms(layer, "local.train")
