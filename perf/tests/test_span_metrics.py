"""The readers of the program's child spans, byte counters and the idle
they name, on hand-built ``layer`` dicts; each reads nothing from a run of
a program that records none of them."""
import pytest

from conftest import ROOT

METRICS = ROOT / "perf" / "metrics"
NEW = ("phase_ms.train_program", "phase_ms.forward", "phase_ms.rehearsal",
       "host_device_bytes_per_round", "unattributed_idle_pct.train",
       "unattributed_idle_pct.serve")


def _read(name, layer):
    from perf import harness
    return harness.load_module(METRICS / f"{name}.py").read(layer, None)


def _span(name, dur=0.0, **attrs):
    return {"kind": "span", "name": name, "dur": dur, **attrs}


def _summary(window_s, busy_s, idle_by_host):
    from perf.trace_reduce import Summary
    return Summary(window_s=window_s, busy_s=busy_s, kernel_s={},
                   kernel_calls={}, kernel_hbm_share={}, top_ops=[],
                   idle_by_host=[list(x) for x in idle_by_host],
                   host_count={}, n_devices=1)


@pytest.mark.parametrize("metric,span", [
    ("phase_ms.train_program", "local.train"),
    ("phase_ms.forward", "local.forward"),
    ("phase_ms.rehearsal", "local.rehearsal")])
def test_phase_readers_divide_by_rounds(metric, span):
    spans = [_span("round.gather", 0.03) for _ in range(4)]
    spans += [_span(span, d) for d in (0.010, 0.012, 0.008, 0.010)]
    spans += [_span("round.local_train", 0.2)]
    assert _read(metric, {"spans": spans}) == pytest.approx(10.0)


def test_bytes_per_round_sums_both_ways():
    spans = []
    for _ in range(3):
        spans += [_span("round.gather"),
                  _span("gather.upload", h2d_bytes=1000),
                  _span("local.forward", h2d_bytes=300, d2h_bytes=200),
                  _span("server.readback", d2h_bytes=5),
                  _span("round.local_train", rows=7)]
    assert _read("host_device_bytes_per_round",
                 {"spans": spans}) == pytest.approx(1505.0)


def test_unattributed_train_counts_phases_outside_and_beyond_top_ten():
    # idle 8 s: 5.5 s named by leaf spans among the ten listed; the
    # phases' own idle (1.2 s), outside any span (0.3 s) and 1 s under
    # labels the trace did not list stay unnamed
    gaps = [("local.rehearsal", 3.0), ("round.local_train", 1.0),
            ("gather.sample", 1.0), ("local.forward", 0.5),
            ("host (outside any span)", 0.3), ("comm.flatten", 0.3),
            ("round.apply", 0.2), ("local.task_feature", 0.3),
            ("comm.unflatten", 0.2), ("gather.upload", 0.2)]
    layer = {"fleet": True, "profile": _summary(10.0, 2.0, gaps)}
    named = 3.0 + 1.0 + 0.5 + 0.3 + 0.3 + 0.2 + 0.2
    assert _read("unattributed_idle_pct.train", layer) == pytest.approx(
        100.0 * (8.0 - named) / 8.0)
    assert _read("unattributed_idle_pct.serve", layer) is None


def test_unattributed_serve_counts_the_batch_span_and_outside():
    gaps = [("serve.batch", 0.1), ("host (outside any span)", 0.4),
            ("serve.readback", 0.6), ("serve.admit", 0.2),
            ("pacer.sleep", 0.1)]
    layer = {"serve": True, "profile": _summary(20.0, 18.6, gaps)}
    assert _read("unattributed_idle_pct.serve", layer) == pytest.approx(
        100.0 * 0.5 / 1.4)
    assert _read("unattributed_idle_pct.train", layer) is None


def test_parent_commit_layers_read_nothing_new():
    """A program whose spans have no children, counters or ids: the phase
    and byte readers find nothing; the idle readers read it all as
    unnamed, or nothing without a profile."""
    spans = []
    for _ in range(2):
        spans += [_span(n, 0.01) for n in (
            "round.gather", "round.local_train", "round.encode",
            "comm.upload", "round.server", "server.relevance",
            "round.apply")]
    fleet = {"fleet": True, "spans": spans}
    for name in NEW:
        assert _read(name, fleet) is None, name
    fleet["profile"] = _summary(15.0, 0.5, [("round.local_train", 11.0),
                                            ("round.gather", 2.0),
                                            ("comm.upload", 0.05),
                                            ("round.encode", 1.45)])
    assert _read("unattributed_idle_pct.train", fleet) == pytest.approx(
        100.0 * 14.45 / 14.5)
    assert _read("unattributed_idle_pct.serve", {"serve": True}) is None
