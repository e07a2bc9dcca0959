"""Runtime telemetry: span tracing, device-side round metrics, serving
histograms, and the run reporter (``python -m repro.obs.report``).

Three layers, all off-by-default-cheap:

  * ``obs.trace`` — a span tracer with explicit device-sync boundaries.
    Engines call the module-level ``span()``/``metric()`` helpers, which
    dispatch to the globally active tracer; when none is active they hit
    the null tracer (one attribute load + a no-op context manager, no
    timestamps, no allocation), so instrumented hot loops stay untraced
    for free. ``Tracer`` buffers events in memory and writes JSONL on
    ``close()``; each span carries its ``id`` and ``parent`` and enters a
    ``jax.profiler.TraceAnnotation``, so a profiler session holds the
    spans on the device trace's clock; host<->device byte counters ride
    on spans as attributes; ``chrome_trace`` converts a run to the
    Chrome-trace / Perfetto ``traceEvents`` format.
  * ``obs.metrics`` — device-side metric math that runs INSIDE existing
    jitted programs (relevance row mass/sparsity, ring staleness, codec
    keep-rate/residual-norm, IVF probe hit-rates) plus the host-side
    fixed-bucket ``LatencyHistogram`` / ``RollingMeter`` / ``ServeStats``
    the serving tier records into.
  * ``obs.report`` — ``summarize()`` over a run's events (per-phase time
    breakdown, per-client drift/staleness table, serve percentiles), the
    ``telemetry_block`` the benches stamp into ``BENCH_*.json``, and the
    CLI.
"""
from repro.obs.metrics import (LatencyHistogram, RollingMeter,  # noqa: F401
                               ServeStats)
from repro.obs.trace import (RunLog, Tracer, activate,  # noqa: F401
                             chrome_trace, deactivate, get_tracer,
                             is_active, metric, span, suspended)

__all__ = [
    "Tracer", "RunLog", "chrome_trace", "activate", "deactivate",
    "get_tracer", "is_active", "span", "metric", "suspended",
    "LatencyHistogram", "RollingMeter", "ServeStats",
]
