"""The trace reduction: on a hand-built trace, and on a small trace
recorded on a TPU v5 lite (``perf/testdata/tiny.xplane.pb``: three rounds
of a quantize kernel and a matmul inside ``round.*`` annotations, within a
``perf.window`` annotation)."""
import dataclasses
from typing import List

import pytest

from conftest import ROOT

TINY = ROOT / "perf" / "testdata" / "tiny.xplane.pb"


@dataclasses.dataclass
class _E:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class _L:
    name: str
    events: List[_E]


@dataclasses.dataclass
class _P:
    name: str
    lines: List[_L]


@dataclasses.dataclass
class _PD:
    planes: List[_P]


def _fake():
    host = _P("/host:CPU", [_L("python", [
        _E("perf.window", 0, 1000),
        _E("round.gather", 0, 400),
        _E("round.server", 400, 600),
        _E("PjitFunction(f)", 500, 10),           # a runtime event, unlabelled
    ])])
    dev = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit_f", 0, 1000)]),
        _L("XLA Ops", [
            _E("_quant_kernel.1", 100, 100),      # 100-200
            _E("fusion.2", 150, 100),             # overlaps: union 100-250
            _E("_fused_kernel", 500, 200),        # 500-700
            _E("fusion.2", 1200, 50),             # outside the window
        ])])
    return _PD([host, dev])


def test_reduce_hand_built_trace():
    from perf import trace_reduce
    s = trace_reduce.reduce(_fake(), window="perf.window",
                            kernels={"batched_quantize": ["_quant_kernel"],
                                     "fused": ["_fused_kernel"]})
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(350e-9)
    assert s.kernel_s["batched_quantize"] == pytest.approx(100e-9)
    assert s.kernel_calls == {"batched_quantize": 1, "fused": 1}
    assert s.top_ops[0] == ["_fused_kernel", pytest.approx(200e-9)]
    idle = dict(s.idle_by_host)
    # each part of a gap goes to the span open in it: 0-100 and 250-400
    # to round.gather, 400-500 and 700-1000 to round.server
    assert idle["round.gather"] == pytest.approx(250e-9)
    assert idle["round.server"] == pytest.approx(400e-9)
    assert s.host_count == {"round.gather": 1, "round.server": 1}
    assert s.n_devices == 1


def test_reduce_matches_kernels_named_by_hlo_text():
    from perf import harness, trace_reduce
    pd = _fake()
    pd.planes[1].lines[1].events[0].name = (
        "%batched_quantize.1 = (s8[100,21760]{1,0:T(8,128)(4,1)}, "
        "f32[100,85]{1,0:T(8,128)}) custom-call(%x.1), "
        'custom_call_target="tpu_custom_call"')
    pd.planes[1].lines[1].events[2].name = (
        "%fused_relevance_aggregate.1 = (f32[100,59392]{1,0:T(8,128)S(1)}, "
        "f32[100,100]{1,0:T(8,128)}) custom-call(f32[100,100]{1,0:S(1)} %w, "
        "f32[100,100]{1,0:S(1)} %v), "
        'custom_call_target="tpu_custom_call", '
        "operand_layout_constraints={f32[100,100]{1,0}}")
    s = trace_reduce.reduce(pd, window="perf.window",
                            kernels=harness.kernel_patterns())
    assert s.kernel_calls == {"batched_quantize": 1,
                              "fused_relevance_aggregate": 1}
    assert s.kernel_s["batched_quantize"] == pytest.approx(100e-9)
    # the quantize call's arrays are all in HBM; the aggregate's (C, P)
    # result sits in on-chip memory (S(1)), its (C, C) one in HBM
    assert s.kernel_hbm_share["batched_quantize"] == 1.0
    assert s.kernel_hbm_share["fused_relevance_aggregate"] == pytest.approx(
        100 * 100 / (100 * 59392 + 100 * 100 + 2 * 100 * 100))
    assert trace_reduce.op_name("%sub.1 = f32[8]{0} subtract(%x, %y)") == \
        "sub.1"
    assert trace_reduce.op_name("fusion.2") == "fusion.2"


def test_reduce_needs_the_window():
    from perf import trace_reduce
    pd = _fake()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(pd, window="perf.window", kernels={})


@pytest.mark.skipif(not TINY.exists(), reason="no recorded trace")
def test_reduce_recorded_chip_trace():
    from perf import harness, trace_reduce
    s = trace_reduce.reduce(trace_reduce.load(TINY), window="perf.window",
                            kernels=harness.kernel_patterns())
    assert 0 < s.busy_s < s.window_s
    assert s.kernel_calls.get("batched_quantize") == 3
    assert s.host_count["round.gather"] == 3
    idle = dict(s.idle_by_host)
    assert idle["round.gather"] > 0.005          # three 2 ms host sleeps
