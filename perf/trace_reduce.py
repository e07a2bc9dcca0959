"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's readings.

From one trace, cut to the host annotation that marks the measured
window:
  * ``busy_s`` — the union of the intervals in which an operation ran on
    a device, averaged over the devices in the trace; ``window_s`` — the
    window's length;
  * ``kernel_s`` / ``kernel_calls`` — summed device time and count of
    the operations whose name matches each kernel's patterns;
  * ``kernel_hbm_share`` — the share of each kernel's operand and result
    bytes that its instruction text places in HBM (memory space 0): the
    TPU compiler may keep a kernel's arrays in on-chip memory (``S(1)``
    in the layout), whose traffic the HBM bandwidth does not bound;
  * ``top_ops`` — device operations by summed time;
  * ``idle_by_host`` — device idle time inside the window, each part of
    a gap attributed to the innermost host span open then (the program's
    spans recorded as profiler annotations), summed per span;
  * ``host_count`` — how many times each host span opened in the window.

Device operations are read from each device plane's "XLA Ops" line
(every line of the plane, when it has none). A TPU trace names each
operation by its whole HLO instruction text (``%batched_quantize.1 =
(s8[100,21760]...) custom-call(...), ...``); kernels are matched on the
instruction's name alone (``batched_quantize.1``), which the compiler
takes from the jitted function that holds the Pallas call.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import math
import re
from collections import defaultdict
from typing import Dict, List, Tuple

HOST_PREFIX = "/host:"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
# the program's and the benchmark's own span names ("round.gather",
# "serve.batch", "pacer.sleep"), not the runtime's internal TraceMe events
SPAN_NAME = re.compile(r"^[a-z_]+(\.[a-z_]+)+$")
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = ")
ARRAY = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f(?:16|32|64))"
                   r"\[([\d,]*)\]\{([^}]*)\}")
WIDTH = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
         "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
MEMORY_SPACE = re.compile(r"S\((\d+)\)")


def op_name(name: str) -> str:
    """The instruction's name, where an event is named by HLO text."""
    m = HLO_TEXT.match(name)
    return m.group(1) if m else name


def hbm_share(name: str) -> float:
    """Share of an instruction's result and operand bytes that its HLO
    text places in HBM; 1 where the text shows no arrays. Only the text
    before ``custom_call_target`` counts: later attributes repeat shapes
    without their placement."""
    head = name.split(", custom_call_target=")[0]
    total = hbm = 0
    for dtype, dims, layout in ARRAY.findall(head):
        n = WIDTH[dtype] * math.prod(int(d) for d in dims.split(",") if d)
        total += n
        space = MEMORY_SPACE.search(layout)
        if space is None or space.group(1) == "0":
            hbm += n
    return hbm / total if total else 1.0


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: Dict[str, float]
    kernel_calls: Dict[str, int]
    kernel_hbm_share: Dict[str, float]
    top_ops: List[list]
    idle_by_host: List[list]
    host_count: Dict[str, int]
    n_devices: int


def load(path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, ws, we):
    return max(s, ws), min(e, we)


def reduce(pd, *, window: str, kernels: Dict[str, List[str]]) -> Summary:
    host = []
    for plane in pd.planes:
        if plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                host.extend(_events(line))
    wins = [(s, e) for n, s, e in host if n == window]
    if not wins:
        raise ValueError(f"no host annotation {window!r} in the trace")
    ws, we = wins[0]
    spans = [(n, s, e) for n, s, e in host
             if n != window and SPAN_NAME.match(n) and s < we and e > ws]
    host_count: Dict[str, int] = defaultdict(int)
    for n, s, _ in spans:
        if ws <= s < we:
            host_count[n] += 1

    pats = {k: [re.compile(p) for p in ps] for k, ps in kernels.items()}
    kernel_s: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    kernel_hbm: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    idle: Dict[str, float] = defaultdict(float)
    segs = _segments(spans, ws, we)
    seg_end = [e for _, e, _ in segs]
    devices = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    n_dev = 0
    for plane in devices:
        lines = [l for l in plane.lines if l.name == OPS_LINE] or list(
            plane.lines)
        ops = []
        for line in lines:
            for n, s, e in _events(line):
                s, e = _clip(s, e, ws, we)
                if e > s:
                    ops.append((n, s, e))
        if not ops:
            continue
        n_dev += 1
        for n, s, e in ops:
            by_name[n] += (e - s) * 1e-9
            short = op_name(n)
            for k, ps in pats.items():
                if any(p.search(short) for p in ps):
                    kernel_s[k] += (e - s) * 1e-9
                    kernel_calls[k] += 1
                    kernel_hbm[k] += hbm_share(n)
        merged = _union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [ws] + [x for iv in merged for x in iv] + [we]
        for a, b in zip(edges[::2], edges[1::2]):
            i = bisect.bisect_right(seg_end, a)
            while i < len(segs) and segs[i][0] < b:
                lo, hi = max(a, segs[i][0]), min(b, segs[i][1])
                if hi > lo:
                    idle[segs[i][2]] += (hi - lo) * 1e-9
                i += 1
    n_dev = max(n_dev, 1)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return Summary(window_s=(we - ws) * 1e-9, busy_s=busy_total / n_dev,
                   kernel_s={k: v / n_dev for k, v in kernel_s.items()},
                   kernel_calls={k: v // n_dev for k, v in kernel_calls.items()},
                   kernel_hbm_share={k: v / kernel_calls[k]
                                     for k, v in kernel_hbm.items()},
                   top_ops=[[n, v / n_dev] for n, v in top],
                   idle_by_host=[[n, v / n_dev] for n, v in gaps],
                   host_count=dict(host_count), n_devices=n_dev)


def _segments(spans, ws, we) -> List[Tuple[float, float, str]]:
    """[ws, we) cut at every span's edges, each piece labelled with the
    innermost span open in it (the one that opened last)."""
    edges = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    out: List[Tuple[float, float, str]] = []
    heap: List[Tuple[float, int]] = []         # (-start, span) of open spans
    closed = set()
    t = ws
    for x, opening, i in edges + [(we, 0, -1)]:
        x = min(max(x, ws), we)
        if x > t:
            while heap and heap[0][1] in closed:
                heapq.heappop(heap)
            label = spans[heap[0][1]][0] if heap else "host (outside any span)"
            if out and out[-1][2] == label and out[-1][1] == t:
                out[-1] = (out[-1][0], x, label)
            else:
                out.append((t, x, label))
            t = x
        if i < 0:
            break
        if opening:
            heapq.heappush(heap, (-spans[i][1], i))
        else:
            closed.add(i)
    return out
