"""Readings of the program's child spans and of the idle time they name."""
from perf.metrics import _common

OUTSIDE = "host (outside any span)"


def phase_ms(layer, span: str):
    """``_common.phase_ms`` of ``span``, or nothing where the run recorded
    no such span (a program without it)."""
    if not any(e["name"] == span for e in layer.get("spans") or []):
        return None
    return _common.phase_ms(layer, span)


def unattributed_idle_pct(layer, key: str, coarse):
    """Share (%) of the profiled window's device idle time that no leaf
    span names: idle less the idle of each listed label other than the
    ``coarse`` parent spans and ``OUTSIDE``. The trace lists its ten
    largest labels only; idle under the others counts as unnamed."""
    prof = layer.get("profile")
    if prof is None or not layer.get(key):
        return None
    idle = prof.window_s - prof.busy_s
    if idle <= 0:
        return None
    named = sum(secs for label, secs in prof.idle_by_host
                if label not in coarse and label != OUTSIDE)
    return 100.0 * (idle - named) / idle
