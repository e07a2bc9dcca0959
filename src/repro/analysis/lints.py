"""Jaxpr lint passes: the repo's measured invariants, checked at trace time.

Each pass walks a ``ClosedJaxpr`` (from ``registry.trace`` — abstract
tracing, no data, no execution) and returns ``Finding`` records:

  * ``dtype-widen``     — an aval dtype outside the program's declared set
    (default: the wire/compute dtypes bf16/int8/f32 plus index/mask types).
    f64 / i64 / complex creep fails here before it ever doubles a buffer.
  * ``convert-churn``   — an A→B→A ``convert_element_type`` round-trip
    (a value converted and converted straight back: wasted casts that
    usually mark an accidental promotion being papered over).
  * ``host-callback``   — ``pure_callback``/``io_callback``/debug prints
    in the program; fatal inside ``scan``/``while`` bodies, where one
    callback per iteration serializes the whole loop on host round-trips.
  * ``host-transfer``   — ``device_put`` inside a loop body.
  * ``undonated-carry`` — a declared round-carried input the program does
    not donate: at C ≫ 1000 the stacked (C, ...) state doubles in memory
    every round. Checked against the declaration AND the traced jit's
    ``donated_invars``.
  * ``dead-code``       — equations whose outputs never reach a program
    output (XLA DCEs them, but they are trace/compile churn and usually
    mark an API returning data nobody consumes).
  * ``peak-bytes``      — a static peak-live-intermediate-bytes estimate
    (linear-scan liveness over the jaxpr, dtype widths from
    ``sharding.analysis``) exceeding the program's declared budget.

``run_jaxpr_lints`` runs every pass and also returns per-program stats
(peak-bytes estimate, eqn count) for the CLI report.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

from jax.core import DropVar
from jax.extend import core

from repro.analysis.registry import ProgramSpec
from repro.sharding.analysis import aval_bytes

_LOOP_PRIMS = ("scan", "while")
_TRANSFER_PRIMS = ("device_put",)


@dataclasses.dataclass(frozen=True)
class Finding:
    code: str            # lint pass id, e.g. "dtype-widen"
    program: str         # registered program name, or "<repo>" for AST lints
    message: str

    def as_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> Iterator[core.Jaxpr]:
    for v in eqn.params.values():
        if isinstance(v, core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for item in v:
                if isinstance(item, core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, core.Jaxpr):
                    yield item


def iter_eqns(jaxpr: core.Jaxpr, path: Tuple[str, ...] = (),
              in_loop: bool = False):
    """Yield (eqn, path, in_loop) over the jaxpr and every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, path, in_loop
        name = eqn.primitive.name
        inner_loop = in_loop or name in _LOOP_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (name,), inner_loop)


def iter_jaxprs(jaxpr: core.Jaxpr) -> Iterator[core.Jaxpr]:
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from iter_jaxprs(sub)


def _nbytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return aval_bytes(shape, dtype)


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------


def lint_dtypes(closed: core.ClosedJaxpr, spec: ProgramSpec) -> List[Finding]:
    """Flag any aval dtype outside the program's allowed set."""
    seen: Dict[str, str] = {}
    top = closed.jaxpr
    for v in list(top.invars) + list(top.constvars):
        dt = getattr(v.aval, "dtype", None)
        if dt is not None and dt.name not in spec.allowed_dtypes:
            seen.setdefault(dt.name, f"program input {v.aval.str_short()}")
    for eqn, path, _ in iter_eqns(top):
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and dt.name not in spec.allowed_dtypes:
                where = "/".join(path) or "top"
                seen.setdefault(
                    dt.name,
                    f"`{eqn.primitive.name}` -> {v.aval.str_short()} "
                    f"at {where}")
    return [Finding("dtype-widen", spec.name,
                    f"dtype {name} outside allowed "
                    f"{sorted(spec.allowed_dtypes)}: first at {ctx}")
            for name, ctx in sorted(seen.items())]


def lint_convert_churn(closed: core.ClosedJaxpr,
                       spec: ProgramSpec) -> List[Finding]:
    """Flag A→B→A convert_element_type round-trips (per jaxpr level).

    A round-trip whose BOTH legs are in ``spec.sanctioned_casts`` —
    e.g. the engine's f32→bf16 wire cast and the server's bf16→f32
    upcast from ``common/precision.py`` — is a declared precision
    boundary, not churn, and is skipped."""
    out: List[Finding] = []
    for jaxpr in iter_jaxprs(closed.jaxpr):
        produced = {}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0]
            dst = eqn.outvars[0]
            if isinstance(src, core.Var) and src in produced:
                orig = produced[src]
                mid = getattr(src.aval, "dtype", None)
                if getattr(dst.aval, "dtype", None) == orig:
                    mid_name = getattr(mid, "name", "?")
                    legs = {(orig.name, mid_name), (mid_name, orig.name)}
                    if not legs <= spec.sanctioned_casts:
                        out.append(Finding(
                            "convert-churn", spec.name,
                            f"{orig.name} -> {mid_name} -> "
                            f"{orig.name} convert round-trip"))
            if isinstance(src, (core.Var, core.Literal)):
                dt = getattr(src.aval, "dtype", None)
                if dt is not None:
                    produced[dst] = dt
    return out


def lint_host_transfers(closed: core.ClosedJaxpr,
                        spec: ProgramSpec) -> List[Finding]:
    """Flag callbacks (always) and device_put (inside loop bodies)."""
    out: List[Finding] = []
    for eqn, path, in_loop in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        where = "/".join(path) or "top"
        if "callback" in name or name in ("infeed", "outfeed"):
            if spec.allow_callbacks:
                continue
            loop_note = (" INSIDE a loop body (one host round-trip per "
                         "iteration)" if in_loop else "")
            out.append(Finding(
                "host-callback", spec.name,
                f"host callback `{name}` at {where}{loop_note}"))
        elif name in _TRANSFER_PRIMS and in_loop:
            out.append(Finding(
                "host-transfer", spec.name,
                f"`{name}` inside a loop body at {where}"))
    return out


def lint_donation(spec: ProgramSpec,
                  closed: Optional[core.ClosedJaxpr] = None) -> List[Finding]:
    """Round-carried state must be donated, by declaration and in fact."""
    out = [Finding("undonated-carry", spec.name,
                   f"round-carried arg {i} is not in donate={spec.donate}: "
                   f"the old buffer stays live an extra round "
                   f"(memory doubles at C >> 1000)")
           for i in spec.carry if i not in spec.donate]
    if spec.donate and closed is not None:
        # the traced jit eqn records donation per flattened invar — if the
        # registered callable is the production jit, this is ground truth
        jits = [e for e in closed.jaxpr.eqns if e.primitive.name == "jit"]
        if len(jits) == 1 and not any(jits[0].params.get("donated_invars",
                                                         ())):
            out.append(Finding(
                "undonated-carry", spec.name,
                f"declares donate={spec.donate} but the traced jit has no "
                f"donated invars (donate_argnums missing on the jit?)"))
    return out


def _dead_eqns(jaxpr: core.Jaxpr):
    """Equations whose outputs never (transitively) reach this jaxpr's
    outputs. Effectful eqns are always live."""
    live = {v for v in jaxpr.outvars if isinstance(v, core.Var)}
    dead = []
    for eqn in reversed(jaxpr.eqns):
        outs = [v for v in eqn.outvars if not isinstance(v, DropVar)]
        if eqn.effects or any(v in live for v in outs):
            for v in eqn.invars:
                if isinstance(v, core.Var):
                    live.add(v)
        else:
            dead.append(eqn)
    return dead


def lint_dead_code(closed: core.ClosedJaxpr,
                   spec: ProgramSpec) -> List[Finding]:
    out: List[Finding] = []
    for jaxpr in iter_jaxprs(closed.jaxpr):
        dead = _dead_eqns(jaxpr)
        if dead:
            prims = sorted({e.primitive.name for e in dead})
            out.append(Finding(
                "dead-code", spec.name,
                f"{len(dead)} equation(s) never reach an output "
                f"(prims: {', '.join(prims[:6])})"))
    return out


def peak_bytes_estimate(jaxpr: core.Jaxpr) -> int:
    """Static peak live-intermediate bytes: linear-scan liveness over the
    eqns (inputs + consts live throughout their use span, outputs pinned),
    plus the recursive peak of whichever sub-jaxpr is on the stack."""
    n = len(jaxpr.eqns)
    last_use: Dict[core.Var, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, core.Var):
                last_use[v] = i
    for v in jaxpr.outvars:
        if isinstance(v, core.Var):
            last_use[v] = n
    alive: Dict[core.Var, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        alive[v] = _nbytes(v.aval)
    peak = sum(alive.values())
    for i, eqn in enumerate(jaxpr.eqns):
        # a sub-jaxpr's inputs are bound to values already counted in the
        # outer frame, so only its NET peak (intermediates beyond its own
        # inputs) stacks on top
        sub_peak = max((max(0, peak_bytes_estimate(s)
                            - sum(_nbytes(v.aval)
                                  for v in list(s.invars) + list(s.constvars)))
                        for s in _sub_jaxprs(eqn)),
                       default=0)
        for v in eqn.outvars:
            if not isinstance(v, DropVar):
                alive[v] = _nbytes(v.aval)
        peak = max(peak, sum(alive.values()) + sub_peak)
        for v in [v for v, last in last_use.items() if last == i]:
            alive.pop(v, None)
    return peak


def lint_peak_bytes(closed: core.ClosedJaxpr, spec: ProgramSpec,
                    peak: Optional[int] = None) -> List[Finding]:
    if peak is None:
        peak = peak_bytes_estimate(closed.jaxpr)
    if peak > spec.budget_bytes:
        return [Finding(
            "peak-bytes", spec.name,
            f"estimated peak intermediates {peak / 1e6:.1f} MB exceed the "
            f"declared budget {spec.budget_bytes / 1e6:.1f} MB")]
    return []


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_jaxpr_lints(closed: core.ClosedJaxpr, spec: ProgramSpec
                    ) -> Tuple[List[Finding], Dict[str, int]]:
    """All passes over one traced program -> (findings, stats)."""
    peak = peak_bytes_estimate(closed.jaxpr)
    findings: List[Finding] = []
    findings += lint_dtypes(closed, spec)
    findings += lint_convert_churn(closed, spec)
    findings += lint_host_transfers(closed, spec)
    findings += lint_donation(spec, closed)
    findings += lint_dead_code(closed, spec)
    findings += lint_peak_bytes(closed, spec, peak)
    n_eqns = sum(len(j.eqns) for j in iter_jaxprs(closed.jaxpr))
    return findings, {"peak_bytes": peak, "eqns": n_eqns}
