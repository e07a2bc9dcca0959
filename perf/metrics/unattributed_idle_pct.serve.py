"""Device: share of the profiled serving window's device idle time that
no leaf span names (idle under ``serve.batch`` itself or outside any
span, or under a label beyond the trace's ten largest; the pacer's
``pacer.sleep`` names its idle)."""
from perf.metrics import _spans

UNIT = "%"


def read(layer, spec):
    return _spans.unattributed_idle_pct(layer, "serve", ("serve.batch",))
