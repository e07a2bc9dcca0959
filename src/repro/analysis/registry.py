"""Program registry: every hot jitted entry point, traceable abstractly.

A *program* is a traceable callable plus the metadata the lint passes
need: abstract input shapes (a thunk returning ``(args, kwargs)`` of
``jax.ShapeDtypeStruct`` pytrees — kwargs are static config), which
positional args are round-carried state (``carry``) and which the program
donates (``donate``), a peak-intermediate-bytes budget, the dtype set the
program is allowed to touch, and the dotted path of its retained host
oracle.

Module-level functions register with the decorator::

    @register_program("kernels.fused_relevance_aggregate",
                      abstract_args=lambda: ((w_sds, th_sds),
                                             {"backend": "ref"}),
                      oracle="repro.kernels.ref.fused_relevance_aggregate_ref",
                      budget_bytes=8 << 20)
    @functools.partial(jax.jit, static_argnames=("backend",))
    def fused_relevance_aggregate(w, thetas, *, backend=None): ...

Closures built at runtime (``FedSTIL._server_fns``, the
``BatchedCodec`` encode/decode jits) cannot be decorated at import time;
``analysis/manifest.py`` constructs them with tiny concrete configs and
registers them via ``register_runtime`` when ``load_all()`` runs.

Registering is free at import time: the decorator only records metadata.
Tracing happens lazily, via ``trace(spec)`` (``jax.make_jaxpr`` over the
abstract args — no data ever touches a device).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Dict, List, Optional, Tuple

# dtypes a program may touch unless it declares otherwise: the measured
# wire/compute dtypes (bf16 / int8 / f32) plus the index/mask types every
# jaxpr needs. float64 / int64 / complex are NEVER in a default set — f64
# creep is exactly what the dtype lint exists to catch.
DEFAULT_ALLOWED_DTYPES = frozenset({
    "float32", "bfloat16", "float16", "int8", "uint8", "int32", "uint32",
    "bool",
})

# default peak-intermediate budget, sized for the 2-core CPU runner (the
# bench configs keep live intermediates well under this; mesh configs
# declare their own)
DEFAULT_BUDGET_BYTES = 256 << 20

# modules whose import registers the decorated programs. repro.core leads
# (registers nothing itself): the core <-> federated import cycle only
# resolves when rooted at repro.core, so federated.base must not be the
# first of the pair imported.
PROGRAM_MODULES = (
    "repro.core",
    "repro.kernels.ops",
    "repro.evalreid.batched",
    "repro.federated.base",
    "repro.serving.engine",
    "repro.analysis.manifest",
)


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One registered program and the invariants the lints check."""

    name: str
    fn: Callable
    abstract_args: Callable[[], Tuple[tuple, dict]]
    module: str
    oracle: Optional[str] = None          # dotted path of the host oracle
    carry: Tuple[int, ...] = ()           # round-carried positional args
    donate: Tuple[int, ...] = ()          # args the program donates
    budget_bytes: int = DEFAULT_BUDGET_BYTES
    allowed_dtypes: frozenset = DEFAULT_ALLOWED_DTYPES
    allow_callbacks: bool = False
    # (src, dst) convert_element_type pairs this program performs on
    # purpose (e.g. the bf16 wire cast from common/precision.py). The
    # convert-churn lint skips A->B->A round-trips whose both legs are
    # sanctioned; everything else still fails.
    sanctioned_casts: frozenset = frozenset()

    def build_args(self) -> Tuple[tuple, dict]:
        return self.abstract_args()


_REGISTRY: Dict[str, ProgramSpec] = {}
_LOADED = False


def _register(spec: ProgramSpec) -> None:
    prev = _REGISTRY.get(spec.name)
    if prev is not None and prev.module != spec.module:
        raise ValueError(
            f"program {spec.name!r} registered twice "
            f"({prev.module} and {spec.module})")
    _REGISTRY[spec.name] = spec


def register_program(name: str, *, abstract_args, oracle=None, carry=(),
                     donate=(), budget_bytes=DEFAULT_BUDGET_BYTES,
                     allowed_dtypes=DEFAULT_ALLOWED_DTYPES,
                     allow_callbacks=False, sanctioned_casts=frozenset()):
    """Decorator: record ``fn`` as the traceable program ``name``."""

    def wrap(fn):
        _register(ProgramSpec(
            name=name, fn=fn, abstract_args=abstract_args,
            module=getattr(fn, "__module__", "<runtime>"), oracle=oracle,
            carry=tuple(carry), donate=tuple(donate),
            budget_bytes=budget_bytes,
            allowed_dtypes=frozenset(allowed_dtypes),
            allow_callbacks=allow_callbacks,
            sanctioned_casts=frozenset(sanctioned_casts)))
        return fn

    return wrap


def register_runtime(name: str, fn: Callable, *, abstract_args, module: str,
                     **kw) -> None:
    """Manifest entry point for closures built at runtime."""
    spec = ProgramSpec(
        name=name, fn=fn, abstract_args=abstract_args, module=module,
        oracle=kw.get("oracle"), carry=tuple(kw.get("carry", ())),
        donate=tuple(kw.get("donate", ())),
        budget_bytes=kw.get("budget_bytes", DEFAULT_BUDGET_BYTES),
        allowed_dtypes=frozenset(
            kw.get("allowed_dtypes", DEFAULT_ALLOWED_DTYPES)),
        allow_callbacks=kw.get("allow_callbacks", False),
        sanctioned_casts=frozenset(kw.get("sanctioned_casts", ())))
    _register(spec)


def load_all() -> Dict[str, ProgramSpec]:
    """Import every program module (running the decorators + manifest)
    and return the full registry. Idempotent."""
    global _LOADED
    if not _LOADED:
        for mod in PROGRAM_MODULES:
            importlib.import_module(mod)
        _LOADED = True
    return dict(_REGISTRY)


def iter_programs() -> List[ProgramSpec]:
    return [load_all()[k] for k in sorted(load_all())]


def get_program(name: str) -> ProgramSpec:
    reg = load_all()
    if name not in reg:
        raise KeyError(f"unknown program {name!r}; registered: {sorted(reg)}")
    return reg[name]


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def trace(spec: ProgramSpec):
    """ClosedJaxpr of the program over its abstract args (no execution)."""
    import jax
    args, kwargs = spec.build_args()
    return jax.make_jaxpr(functools.partial(spec.fn, **kwargs))(*args)


def lowered_text(spec: ProgramSpec) -> Optional[str]:
    """StableHLO text of the program's own jit (None when the registered
    callable is not a jit wrapper). Used by the donation lint: donated
    inputs carry a ``tf.aliasing_output`` attribute in the lowering."""
    lower = getattr(spec.fn, "lower", None)
    if lower is None:
        return None
    args, kwargs = spec.build_args()
    try:
        return lower(*args, **kwargs).as_text()
    except Exception:
        return None


def resolve_oracle(path: str) -> Any:
    """Import the dotted ``module.attr[.attr...]`` oracle path."""
    parts = path.split(".")
    for split in range(len(parts) - 1, 0, -1):
        try:
            obj = importlib.import_module(".".join(parts[:split]))
        except ImportError:
            continue
        try:
            for attr in parts[split:]:
                obj = getattr(obj, attr)
        except AttributeError:
            break
        return obj
    raise ImportError(f"oracle path {path!r} does not resolve")


def coverage() -> Dict[str, Any]:
    """Registry coverage for the BENCH_*.json metadata: how many of the
    registered programs trace cleanly right now. A program silently
    dropping out of analysis shows up as traced < registered."""
    traced, failed = [], []
    for spec in iter_programs():
        try:
            trace(spec)
            traced.append(spec.name)
        except Exception as e:                      # noqa: BLE001
            failed.append({"name": spec.name, "error": repr(e)[:200]})
    out = {"programs_registered": len(traced) + len(failed),
           "programs_traced": len(traced), "traced": traced}
    if failed:
        out["failed"] = failed
    return out
