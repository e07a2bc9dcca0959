"""repro.obs: histogram/percentile math, tracer semantics, reporter.

The histogram tests pin the subsystem's accuracy contract: a reported
percentile is the upper edge of the bucket holding the true sample
percentile, so it must bound ``np.percentile`` from above within one
bucket's relative width. The tracer tests pin the off-by-default-cheap
contract (null path records nothing and never syncs) and the JSONL
round trip the report CLI consumes.
"""
import json
import math

import numpy as np
import pytest

from repro.obs import metrics as M
from repro.obs import trace as T
from repro.obs.report import summarize, telemetry_block


# ---------------------------------------------------------------------------
# LatencyHistogram vs numpy percentile
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("q", [50, 90, 99])
@pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
def test_histogram_percentile_bounds_numpy(dist, q):
    rng = np.random.default_rng(hash((dist, q)) % (2**32))
    if dist == "uniform":
        xs = rng.uniform(1e-4, 0.5, 5000)
    elif dist == "lognormal":
        xs = np.exp(rng.normal(math.log(5e-3), 1.0, 5000))
    else:
        xs = np.concatenate([rng.uniform(1e-4, 3e-4, 2500),
                             rng.uniform(0.1, 0.2, 2500)])
    xs = np.clip(xs, 1.1e-5, 9.0)          # stay inside the bucket span
    h = M.LatencyHistogram()
    h.record_many(xs)
    got = h.percentile(q)
    true = float(np.percentile(xs, q, method="inverted_cdf"))
    # upper bound, tight to one log bucket's width
    bucket_ratio = (10.0 / 1e-5) ** (1.0 / 64)
    assert got >= true * (1 - 1e-12)
    assert got <= true * bucket_ratio * (1 + 1e-9)


def test_histogram_empty_and_single_sample():
    h = M.LatencyHistogram()
    assert math.isnan(h.percentile(50))
    assert math.isnan(h.mean)
    h.record(0.003)
    # one sample: every percentile is that sample's bucket edge
    assert h.percentile(1) == h.percentile(50) == h.percentile(99)
    assert h.percentile(50) >= 0.003
    assert h.mean == pytest.approx(0.003)
    assert h.snapshot()["n"] == 1


def test_histogram_out_of_range_clamps():
    h = M.LatencyHistogram()
    h.record(1e-9)                 # below lo -> first bucket
    h.record(100.0)                # above hi -> overflow bucket
    assert h.n == 2
    assert int(h.counts[0]) == 1 and int(h.counts[-1]) == 1
    assert h.percentile(99) == h.edges[-1]


def test_histogram_merge_matches_combined_stream():
    rng = np.random.default_rng(0)
    a, b = rng.uniform(1e-4, 1.0, 400), rng.uniform(1e-3, 0.1, 600)
    ha, hb, hc = (M.LatencyHistogram() for _ in range(3))
    ha.record_many(a)
    hb.record_many(b)
    hc.record_many(np.concatenate([a, b]))
    ha.merge(hb)
    np.testing.assert_array_equal(ha.counts, hc.counts)
    assert ha.n == hc.n == 1000
    for q in (50, 90, 99):
        assert ha.percentile(q) == hc.percentile(q)


def test_rolling_meter_window():
    m = M.RollingMeter(window_s=1.0)
    m.tick(10, now=100.0)
    m.tick(5, now=100.5)
    assert m.rate(now=100.6) == pytest.approx(15.0)
    assert m.rate(now=101.2) == pytest.approx(5.0)   # first burst evicted
    assert m.rate(now=105.0) == 0.0
    assert m.total == 15


def test_serve_stats_snapshot_shapes():
    class _T:
        latency, queue_s, service_s = 0.004, 0.001, 0.003
    s = M.ServeStats()
    s.record_launch(7, deficit=[3, 0, 1])
    for _ in range(4):
        s.record_ticket(_T())
    snap = s.snapshot()
    assert snap["completed"] == 4 and snap["launches"] == 1
    assert snap["queue_depth"] == {"mean": 7.0, "max": 7}
    assert snap["latency"]["n"] == 4
    assert snap["drr_deficit_spread"] == 3.0
    json.dumps(snap)               # JSON-ready by contract


# ---------------------------------------------------------------------------
# tracer: null path, activation, JSONL round trip
# ---------------------------------------------------------------------------


def test_null_tracer_records_nothing_and_sync_is_identity():
    assert not T.is_active()
    sentinel = object()
    with T.span("x", cat="phase") as sp:
        assert sp.sync(sentinel) is sentinel     # no block_until_ready
    T.metric("x", {"a": 1.0})                    # no-op, must not raise


def test_active_tracer_restores_previous_on_exit():
    tr = T.Tracer()
    with T.active(tr):
        assert T.is_active() and T.get_tracer() is tr
        with T.suspended():
            assert not T.is_active()
        assert T.get_tracer() is tr
    assert not T.is_active()


def test_span_and_metric_events_jsonl_round_trip(tmp_path):
    path = tmp_path / "run.jsonl"
    tr = T.Tracer(path=path)
    with T.active(tr):
        with T.span("round.server", cat="phase", round=3) as sp:
            sp.sync(np.zeros(2))
        T.metric("server.relevance",
                 {"staleness": np.array([0.0, 2.0]), "scalar": np.float32(1)},
                 round=3)
    tr.close()
    events = T.RunLog.read(path)
    kinds = [e["kind"] for e in events]
    assert kinds.count("span") == 1 and kinds.count("metric") == 1
    span = next(e for e in events if e["kind"] == "span")
    assert span["name"] == "round.server" and span["round"] == 3
    assert span["dur"] >= 0.0
    met = next(e for e in events if e["kind"] == "metric")
    assert met["values"]["staleness"] == [0.0, 2.0]    # device -> list
    assert met["values"]["scalar"] == 1.0


def test_chrome_trace_export():
    tr = T.Tracer()
    with T.active(tr):
        with T.span("a", cat="stage"):
            pass
        T.metric("m", {"v": 1.0})
    ct = T.chrome_trace(tr.events)
    phs = [e["ph"] for e in ct["traceEvents"]]
    assert "X" in phs and "i" in phs
    x = next(e for e in ct["traceEvents"] if e["ph"] == "X")
    assert x["tid"] == "stage" and x["dur"] >= 0.0


# ---------------------------------------------------------------------------
# tracer: span ids and parents, profiler annotations, counter attributes
# ---------------------------------------------------------------------------


def _spans(tr):
    return {e["name"]: e for e in tr.events if e["kind"] == "span"}


def test_nested_and_sibling_spans_carry_id_and_parent():
    tr = T.Tracer()
    with T.active(tr):
        with T.span("round.local_train"):
            with T.span("local.train"):
                pass
            with T.span("local.rehearsal"):
                with T.span("rehearsal.inner"):
                    pass
        with T.span("round.server"):
            pass
    s = _spans(tr)
    outer = s["round.local_train"]
    assert outer["parent"] is None and s["round.server"]["parent"] is None
    assert s["local.train"]["parent"] == outer["id"]
    assert s["local.rehearsal"]["parent"] == outer["id"]
    assert s["rehearsal.inner"]["parent"] == s["local.rehearsal"]["id"]
    ids = [e["id"] for e in s.values()]
    assert len(set(ids)) == len(ids)
    # ids in opening order
    assert (outer["id"] < s["local.train"]["id"] < s["local.rehearsal"]["id"]
            < s["rehearsal.inner"]["id"] < s["round.server"]["id"])


def test_exception_in_child_pops_it():
    tr = T.Tracer()
    with T.active(tr):
        with T.span("round.gather"):
            with pytest.raises(RuntimeError):
                with T.span("gather.sample"):
                    raise RuntimeError("boom")
            with T.span("gather.upload"):
                pass
        with T.span("round.server"):
            pass
    s = _spans(tr)
    assert s["gather.sample"]["parent"] == s["round.gather"]["id"]
    assert s["gather.upload"]["parent"] == s["round.gather"]["id"]
    assert s["round.server"]["parent"] is None
    assert tr._open_ids == []


class _Closed(Exception):
    pass


def test_raise_from_span_call_before_enter_leaves_no_open_span():
    class Clock(T.Tracer):
        def span(self, name, **attrs):
            if name == "round.gather":
                raise _Closed
            return super().span(name, **attrs)

    tr = Clock()
    with T.active(tr):
        with pytest.raises(_Closed):
            with T.span("round.local_train"):
                T.span("round.gather")
        with T.span("round.server"):
            pass
    assert _spans(tr)["round.server"]["parent"] is None
    assert tr._open_ids == []


class _Annotation:
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_active_span_enters_one_trace_annotation(monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.log = []
    tr = T.Tracer()
    with T.active(tr):
        with T.span("round.server", cat="phase"):
            with T.span("server.readback", cat="stage", d2h_bytes=8):
                pass
    assert _Annotation.log == [("enter", "round.server"),
                               ("enter", "server.readback"),
                               ("exit", "server.readback"),
                               ("exit", "round.server")]


def test_null_tracer_enters_no_annotation_and_records_nothing(monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    _Annotation.log = []
    null = T.get_tracer()
    assert not null.active
    with T.span("round.server", cat="phase") as sp:
        with T.span("server.readback", h2d_bytes=4) as inner:
            x = object()
            assert inner.sync(x) is x
    assert sp is inner                       # the one shared null span
    assert _Annotation.log == []
    assert not hasattr(null, "events")


def test_counter_attributes_survive_jsonl_and_chrome_trace(tmp_path):
    path = tmp_path / "run.jsonl"
    tr = T.Tracer(path=path)
    attrs = {"h2d_bytes": 24_576_000, "d2h_bytes": 3_686_400, "rows": 14_400,
             "slots": 256, "depth": 311}
    with T.active(tr):
        with T.span("serve.admit", cat="stage", **attrs):
            pass
    tr.close()
    (span,) = [e for e in T.RunLog.read(path) if e["kind"] == "span"]
    assert {k: span[k] for k in attrs} == attrs
    assert span["parent"] is None and span["id"] == 0
    (x,) = [e for e in T.chrome_trace(tr.events)["traceEvents"]
            if e["ph"] == "X"]
    assert {k: x["args"][k] for k in attrs} == attrs


def test_device_nbytes_counts_device_dtypes():
    jnp = pytest.importorskip("jax.numpy")
    host = np.zeros((3, 5), np.int64)          # narrowed to int32 on device
    assert T.device_nbytes(host) == 3 * 5 * 4
    assert T.device_nbytes(np.zeros((2, 2), np.float32),
                           jnp.zeros((7,), jnp.bool_)) == 16 + 7


# ---------------------------------------------------------------------------
# report aggregation + device metric helpers
# ---------------------------------------------------------------------------


def test_summarize_and_telemetry_block():
    tr = T.Tracer()
    with T.active(tr):
        for name, dur in (("round.local_train", None), ("round.server", None)):
            with T.span(name, cat="phase"):
                pass
        with T.span("server.relevance", cat="stage"):
            pass
        T.metric("server.relevance", {"staleness": [0.0, 1.0]}, round=0)
        T.metric("server.relevance", {"staleness": [1.0, 0.0]}, round=1)
    s = summarize(tr.events)
    assert set(s["phases"]) == {"round.local_train", "round.server"}
    assert abs(sum(g["share"] for g in s["phases"].values()) - 1.0) < 1e-9
    assert s["clients"]["staleness"] == [1.0, 0.0]     # LAST round wins
    assert s["clients"]["round"] == 1
    block = telemetry_block(tr.events)
    assert block["events"]["spans"] == 3
    assert "serve" not in block                        # no serve metrics
    json.dumps(block)


def test_update_staleness_partial_mask():
    jnp = pytest.importorskip("jax.numpy")
    stale = jnp.asarray([0.0, 3.0, 1.0])
    mask = jnp.asarray([1.0, 0.0, 1.0])
    out = np.asarray(M.update_staleness(stale, mask))
    np.testing.assert_array_equal(out, [0.0, 4.0, 0.0])


def test_relevance_metrics_values():
    jnp = pytest.importorskip("jax.numpy")
    W = jnp.asarray([[0.0, 1.0], [0.5, 0.5]])
    valid = jnp.asarray([[1.0, 0.0], [1.0, 1.0]])
    stale = jnp.asarray([2.0, 0.0])
    m = {k: np.asarray(v) for k, v in
         M.relevance_metrics(W, valid, stale).items()}
    np.testing.assert_allclose(m["row_mass"], [1.0, 1.0])
    np.testing.assert_allclose(m["row_density"], [0.5, 1.0])
    np.testing.assert_allclose(m["self_weight"], [0.0, 0.5])
    np.testing.assert_allclose(m["hist_fill"], [1.0, 2.0])
    np.testing.assert_allclose(m["staleness"], [2.0, 0.0])
