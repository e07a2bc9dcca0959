"""Local train: milliseconds per round in ``local.rehearsal``
(``PrototypeMemory.add_task`` over the clients; the program's span),
over the traced run's second part."""
from perf.metrics import _spans

UNIT = "ms"


def read(layer, spec):
    return _spans.phase_ms(layer, "local.rehearsal")
