"""Device: share of the profiled round window's device idle time that no
leaf span names (idle under a ``round.*`` phase itself or outside any
span, or under a label beyond the trace's ten largest)."""
from perf.metrics import _spans

UNIT = "%"
PHASES = ("round.gather", "round.local_train", "round.encode",
          "round.server", "round.apply")


def read(layer, spec):
    return _spans.unattributed_idle_pct(layer, "fleet", PHASES)
