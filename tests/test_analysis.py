"""The static analyzer's own tests: each lint pass fires on a known-bad toy
program, the convention passes fire on a synthetic bad tree, and the real
repo is clean (zero non-baselined findings over every registered program).
"""
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import conventions, lints, registry
from repro.analysis.lints import Finding
from repro.analysis.registry import ProgramSpec

_S = jax.ShapeDtypeStruct


def _spec(fn, args, name="toy", **kw):
    return ProgramSpec(name=name, fn=fn,
                       abstract_args=lambda: (args, {}),
                       module="tests.test_analysis", **kw)


def _codes(findings):
    return {f.code for f in findings}


# ---------------------------------------------------------------------------
# jaxpr passes on known-bad toy programs
# ---------------------------------------------------------------------------


def test_dtype_widen_fires_on_f64():
    def f(x):
        return jnp.sum(x.astype(jnp.float64))

    with jax.enable_x64(True):
        spec = _spec(f, (_S((8,), jnp.float32),))
        fs, _ = lints.run_jaxpr_lints(registry.trace(spec), spec)
    widen = [f_ for f_ in fs if f_.code == "dtype-widen"]
    assert widen and "float64" in widen[0].message


def test_dtype_widen_quiet_when_declared():
    def f(x):
        return jnp.sum(x.astype(jnp.float64))

    with jax.enable_x64(True):
        spec = _spec(f, (_S((8,), jnp.float32),),
                     allowed_dtypes=frozenset({"float32", "float64"}))
        fs, _ = lints.run_jaxpr_lints(registry.trace(spec), spec)
    assert "dtype-widen" not in _codes(fs)


def test_convert_churn_fires_on_roundtrip():
    def f(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    spec = _spec(f, (_S((16,), jnp.float32),))
    fs, _ = lints.run_jaxpr_lints(registry.trace(spec), spec)
    assert "convert-churn" in _codes(fs)


def test_host_callback_in_scan_body_fires():
    def body(c, x):
        y = jax.pure_callback(lambda v: np.asarray(v),
                              _S((), jnp.float32), x)
        return c + y, y

    def f(xs):
        out, _ = jax.lax.scan(body, jnp.float32(0.0), xs)
        return out

    spec = _spec(f, (_S((4,), jnp.float32),))
    fs, _ = lints.run_jaxpr_lints(registry.trace(spec), spec)
    cb = [f_ for f_ in fs if f_.code == "host-callback"]
    assert cb and "INSIDE a loop body" in cb[0].message
    # and the escape hatch silences it
    spec_ok = _spec(f, (_S((4,), jnp.float32),), allow_callbacks=True)
    fs_ok, _ = lints.run_jaxpr_lints(registry.trace(spec_ok), spec_ok)
    assert "host-callback" not in _codes(fs_ok)


def test_undonated_carry_by_declaration():
    spec = _spec(lambda s, x: s + x,
                 (_S((8,), jnp.float32), _S((8,), jnp.float32)),
                 carry=(0,), donate=())
    fs = lints.lint_donation(spec)
    assert [f_.code for f_ in fs] == ["undonated-carry"]


def test_undonated_carry_by_trace():
    """Declared donate but the registered jit forgot donate_argnums."""
    args = (_S((8,), jnp.float32), _S((8,), jnp.float32))
    bad = _spec(jax.jit(lambda s, x: s + x), args, carry=(0,), donate=(0,))
    fs = lints.lint_donation(bad, registry.trace(bad))
    assert any("no donated invars" in f_.message for f_ in fs)
    good = _spec(jax.jit(lambda s, x: s + x, donate_argnums=(0,)), args,
                 carry=(0,), donate=(0,))
    assert not lints.lint_donation(good, registry.trace(good))


def test_dead_code_fires_on_unused_intermediate():
    def f(x):
        _ = jnp.dot(x, x.T)          # never reaches an output
        return jnp.sum(x)

    spec = _spec(f, (_S((32, 32), jnp.float32),))
    fs, _ = lints.run_jaxpr_lints(registry.trace(spec), spec)
    dead = [f_ for f_ in fs if f_.code == "dead-code"]
    assert dead and "dot_general" in dead[0].message


def test_peak_bytes_budget():
    def f(x):
        y = jnp.outer(x, x)          # (4096, 4096) f32 = 64 MB live
        return jnp.sum(y)

    spec = _spec(f, (_S((4096,), jnp.float32),), budget_bytes=1 << 20)
    closed = registry.trace(spec)
    fs, stats = lints.run_jaxpr_lints(closed, spec)
    assert "peak-bytes" in _codes(fs)
    assert stats["peak_bytes"] >= 64 * 1024 * 1024


# ---------------------------------------------------------------------------
# convention passes on a synthetic bad tree
# ---------------------------------------------------------------------------


@pytest.fixture
def bad_repo(tmp_path):
    k = tmp_path / "src" / "repro" / "kernels"
    k.mkdir(parents=True)
    (tmp_path / "src" / "repro" / "__init__.py").write_text("")
    (k / "__init__.py").write_text("")
    (k / "ref.py").write_text("def wired_ref(x):\n    return x\n")
    (k / "ops.py").write_text(textwrap.dedent("""\
        from repro.kernels import ref as REF
        from repro.kernels.wired import wired as _w

        def wired(x, *, backend=None):
            if backend == "ref":
                return REF.wired_ref(x)
            return _w(x)

        def orphan(x, *, backend=None):
            return x
    """))
    (k / "wired.py").write_text("def wired(x):\n    return x\n")
    (k / "lonely.py").write_text("def lonely(x):\n    return x\n")
    (tmp_path / "tests").mkdir()
    (tmp_path / "tests" / "test_wired.py").write_text(
        "import os\n\ndef test_wired():\n    assert True  # wired\n")
    return tmp_path


def test_kernel_conventions_fire(bad_repo):
    fs = conventions.lint_kernel_conventions(bad_repo)
    codes = _codes(fs)
    # orphan: no ref oracle, no parity test; lonely.py: not wired into ops
    assert "kernel-no-ref" in codes
    assert "kernel-no-parity-test" in codes
    assert any(f.code == "kernel-module-unwired" and "lonely" in f.message
               for f in fs)
    # the properly wired dispatcher is clean
    assert not any("`wired`" in f.message for f in fs)


def test_unused_imports_fire(bad_repo):
    fs = conventions.lint_unused_imports(bad_repo)
    assert any(f.code == "unused-import" and "os" in f.message for f in fs)


def test_fast_path_oracle_checks():
    no_oracle = _spec(lambda x: x, (_S((2,), jnp.float32),))
    broken = _spec(lambda x: x, (_S((2,), jnp.float32),),
                   oracle="repro.kernels.ref.does_not_exist")
    good = _spec(lambda x: x, (_S((2,), jnp.float32),),
                 oracle="repro.kernels.ref.pairwise_dist_ref")
    fs = conventions.lint_fast_path_oracles([no_oracle, broken, good])
    assert sorted(f.code for f in fs) == ["fast-path-no-oracle",
                                          "fast-path-oracle-unresolved"]


def test_dead_module_detection(bad_repo):
    (bad_repo / "src" / "repro" / "configs").mkdir()
    (bad_repo / "src" / "repro" / "configs" / "__init__.py").write_text("")
    (bad_repo / "src" / "repro" / "configs" / "orphaned.py").write_text(
        "X = 1\n")
    (bad_repo / "src" / "repro" / "configs" / "testonly.py").write_text(
        "Y = 2\n")
    (bad_repo / "tests" / "test_cfg.py").write_text(
        "from repro.configs import testonly\n")
    spec = ProgramSpec(name="kernels.wired", fn=lambda x: x,
                       abstract_args=lambda: ((), {}),
                       module="repro.kernels.ops")
    fs = conventions.lint_dead_modules(bad_repo, [spec])
    by_code = {f.code: f.message for f in fs}
    assert "orphaned" in by_code["dead-module"]
    assert "testonly" in by_code["seed-module"]


def test_dead_module_init_fanout_does_not_keep_alive(bad_repo):
    """A scope package init re-exporting a submodule (the registry
    pattern) must NOT count as registry reachability — only an import by
    name does. Tests importing the init still reach it (full graph), so
    the finding is seed-module, not dead-module."""
    cfg = bad_repo / "src" / "repro" / "configs"
    cfg.mkdir()
    (cfg / "__init__.py").write_text(
        "from repro.configs.fanout import X\n")
    (cfg / "fanout.py").write_text("X = 1\n")
    (bad_repo / "src" / "repro" / "uses_cfg.py").write_text(
        "import repro.configs\n")
    (bad_repo / "tests" / "test_cfg.py").write_text(
        "import repro.configs\n")
    spec = ProgramSpec(name="kernels.wired", fn=lambda x: x,
                       abstract_args=lambda: ((), {}),
                       module="repro.uses_cfg")
    fs = conventions.lint_dead_modules(bad_repo, [spec])
    assert any(f.code == "seed-module" and "fanout" in f.message
               for f in fs)


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------


def test_baseline_partition_and_stale():
    from repro.analysis.lint import partition_findings
    fs = [Finding("dead-code", "p1", "x is dead"),
          Finding("dtype-widen", "p2", "float64 crept in")]
    sups = [{"code": "dead-code", "program": "p1", "match": "dead",
             "reason": "known"},
            {"code": "host-callback", "program": "p9", "reason": "gone"}]
    new, base, stale = partition_findings(fs, sups)
    assert [f.code for f in new] == ["dtype-widen"]
    assert [f.code for f in base] == ["dead-code"]
    assert stale == [sups[1]]


# ---------------------------------------------------------------------------
# the real repo is clean
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_repo_programs_trace_and_lint_clean():
    """Every registered program traces; lints minus baseline == zero.

    This is the CI gate (scripts/run_tier1.sh) in test form: the acceptance
    floor is >= 8 traced programs covering the server round, stacked local
    train, the fused aggregate, batched eval, and the wire codec programs.
    """
    from repro.analysis.lint import (BASELINE_PATH, load_baseline,
                                     partition_findings, run)
    report = run()
    traced = [n for n, p in report["programs"].items() if p["traced"]]
    assert len(traced) >= 8, traced
    for needed in ("federated.fedstil_server_round",
                   "federated.stacked_local_train",
                   "kernels.fused_relevance_aggregate",
                   "federated.stacked_eval",
                   "kernels.batched_pairwise_dist",
                   "kernels.batched_quantize",
                   "comm.batched_encode",
                   "comm.batched_decode"):
        assert needed in traced
    new, base, stale = partition_findings(
        report["findings"], load_baseline(BASELINE_PATH))
    assert not new, [f.as_dict() for f in new]
    assert not stale, stale
    # every baseline entry carries its why
    for s in json.loads(BASELINE_PATH.read_text())["suppressions"]:
        assert s.get("reason"), s


def test_registered_programs_declare_resolvable_oracles():
    specs = registry.iter_programs()
    fs = conventions.lint_fast_path_oracles(specs)
    assert not fs, [f.as_dict() for f in fs]
