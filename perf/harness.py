"""Finds a cell's pieces by name, runs it, and prints its result.

A system driver (``systems/<system>.py``) exposes
``run(spec, *, seed, seconds, trace, clock, t_start, devices) -> Record``:
it builds the system from the seed, warms it up, measures for
``seconds``, reads the device's peak memory, frees the program's state and
then compares what the timed path produced with the plain reference. This module turns the record into
the result line: the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics (one reader per ``metrics/<metric>.py``) with
``--trace 1``, and the comparison's numbers with their limits.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List

PERF = Path(__file__).resolve().parent


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Dict[str, float]]


@dataclasses.dataclass
class Record:
    """What one run measured. ``e2e``: end-to-end metric -> (value, unit);
    ``readings``: the numbers compared with the reference; ``layer``: what
    the per-layer readers read (spans, tickets, the reduced trace, kernel
    call shapes, the step's operations)."""
    e2e: Dict[str, tuple]
    readings: Dict[str, float]
    attempted: int
    failed: int
    memory_peak_bytes: int
    layer: Dict[str, Any] = dataclasses.field(default_factory=dict)


class WindowClosed(Exception):
    """Raised from a span hook to end the program's loop at the window's
    close (whole rounds only)."""


def load_json(path: Path) -> dict:
    with path.open() as f:
        return json.load(f)


def load_spec(cell: str, root: Path = PERF) -> Spec:
    c = load_json(root / "cells" / f"{cell}.json")
    return Spec(name=cell, chips=int(c.get("chips", 1)),
                config=load_json(root / "configs" / f"{c['config']}.json"),
                traffic=load_json(root / "traffic" / f"{c['traffic']}.json"),
                limits=c.get("limits", {}))


def system(name: str):
    return importlib.import_module(f"perf.systems.{name}")


# ---------------------------------------------------------------------------
# device, compiles, profiler
# ---------------------------------------------------------------------------

def tpu_devices(chips: int):
    """The devices this run may use, or None (with a message) when the
    accelerator is missing or too small."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run: no TPU (JAX found {devs[0].platform})", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"run: the cell asks for {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        return None
    return devs[:chips]


def enable_cache():
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program however fast it
    compiled, so a second run in a checkout compiles nothing."""
    import jax
    from repro.common.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileCounter:
    """Counts backend compiles (and their seconds) JAX reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.secs += secs


def memory_peak(devices) -> int:
    out = 0
    for d in devices:
        stats = d.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out


class Profile:
    """A profiler session writing to a private temporary directory; the
    window it covers is marked by a host annotation (``WINDOW``) so the
    reduction can cut the trace to it."""

    WINDOW = "perf.window"

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="perf-trace-")
        self._annot = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._annot = jax.profiler.TraceAnnotation(self.WINDOW)
        self._annot.__enter__()

    def stop(self):
        import jax
        self._annot.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, kernels):
        from perf import trace_reduce
        path = next(Path(self.dir).rglob("*.xplane.pb"))
        return trace_reduce.reduce(trace_reduce.load(path), window=self.WINDOW,
                                   kernels=kernels)

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


class AnnotatedSpan:
    """A program span as a profiler annotation: on the profiler's clock,
    with no sync (``sync`` returns its argument, as tracing-off does)."""

    __slots__ = ("_annot",)

    def __init__(self, name: str):
        import jax
        self._annot = jax.profiler.TraceAnnotation(name)

    def sync(self, value):
        return value

    def __enter__(self):
        self._annot.__enter__()
        return self

    def __exit__(self, *exc):
        return self._annot.__exit__(*exc)


def annotator():
    """A tracer for ``obs.active`` that records the program's spans as
    profiler annotations and nothing else: inactive (``obs.is_active()``
    stays false, so no program switches to a metrics variant), no syncs,
    no readbacks."""
    from repro.obs import trace as obs

    class Annotator(obs.NullTracer):
        def span(self, name, **attrs):
            return AnnotatedSpan(name)

    return Annotator()


def kernel_patterns() -> Dict[str, List[str]]:
    """Trace-name patterns of every kernel that has an ops/bytes file."""
    out = {}
    for path in sorted((PERF / "kernels").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        mod = load_module(path)
        if hasattr(mod, "TRACE_NAMES"):
            out[path.stem] = list(mod.TRACE_NAMES)
    return out


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "perf_dyn_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# result
# ---------------------------------------------------------------------------

def judge(readings: Dict[str, float], limits: Dict[str, Dict[str, float]]):
    """[(name, value, limit, ok, sense)] for every limited number; a number
    whose reading is missing or not finite fails."""
    out = []
    for name, lim in sorted(limits.items()):
        v = readings.get(name)
        if "max" in lim:
            ok = v is not None and math.isfinite(v) and v <= lim["max"]
            out.append((name, v, lim["max"], ok, "<="))
        if "min" in lim:
            ok = v is not None and math.isfinite(v) and v >= lim["min"]
            out.append((name, v, lim["min"], ok, ">="))
    return out


def per_layer(record: Record, spec: Spec) -> Dict[str, dict]:
    out = {}
    for path in sorted((PERF / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        mod = load_module(path)
        v = mod.read(record.layer, spec)
        if v is not None:
            out[path.stem] = {"value": float(v), "unit": mod.UNIT}
    return out


def result_line(record: Record, spec: Spec, devices, trace: bool) -> dict:
    verdicts = judge(record.readings, spec.limits)
    correct = (bool(verdicts) and all(v[3] for v in verdicts)
               and record.attempted > 0 and record.failed == 0)
    if trace:
        record.layer["device_kind"] = devices[0].device_kind
        metrics = per_layer(record, spec)
    else:
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in record.e2e.items()}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record.memory_peak_bytes}
    out = {"correct": correct, "attempted": record.attempted,
           "failed": record.failed, "metrics": metrics, "device": device}
    summary = record.layer.get("profile")
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops[:10],
                            "idle_gaps": summary.idle_by_host[:10]}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim, _, _ in verdicts}
    return out, verdicts


def main(cell: str, *, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    try:
        spec = load_spec(cell)
    except FileNotFoundError as e:
        print(f"run: unknown cell {cell!r} ({e})", file=sys.stderr)
        return 2
    devices = tpu_devices(spec.chips)
    if devices is None:
        return 1
    print(f"compile cache: {enable_cache()}", flush=True)
    clock = CompileCounter()
    sysmod = system(spec.config["system"])
    record = sysmod.run(spec, seed=seed, seconds=seconds, trace=trace,
                        clock=clock, t_start=t_start, devices=devices)
    out, verdicts = result_line(record, spec, devices, trace)
    print(f"backend compiles inside the window: "
          f"{record.layer.get('window_compiles', 'n/a')}", flush=True)
    print(f"readings: {json.dumps(record.readings)}", flush=True)
    for name, v, lim, ok, sense in verdicts:
        print(f"check {name}: {v!r} (limit {sense} {lim!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
