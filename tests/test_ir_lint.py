"""tpu-lint IR tier (apex_tpu.analysis.ir) coverage.

Mirrors the PR 3 load-bearing pattern one layer down, per ISSUE 5:

1. per-rule fixture pairs — a bad PROGRAM whose jaxpr triggers EXACTLY
   its rule (and passes with the rule deselected), and a good twin
   that is clean;
2. machinery — source-info anchoring, inline suppression of IR
   findings, the trace-error path, the case registry's domain span;
3. interprocedural AST-tier fixtures that need a cross-module package
   (host-sync through an imported helper, imported donated wrappers,
   the host-boundary pragma);
4. end-to-end — ``--ir`` over the repo itself exits 0 at HEAD: the
   tier-1 twin of the ``--ir`` CI gate.
"""

import os
import sys
import textwrap
from pathlib import Path

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax import lax                                            # noqa: E402
from jax.experimental import pallas as pl                      # noqa: E402

from apex_tpu.analysis import cli                              # noqa: E402
from apex_tpu.analysis.ir import IR_RULES, analyze_ir          # noqa: E402
from apex_tpu.analysis.ir.harness import (AnalysisCase,        # noqa: E402
                                          CaseProgram,
                                          analysis_cases,
                                          build_case_ir)
from apex_tpu.analysis.ir.ir_report import findings_for_case   # noqa: E402

f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32


def _sds(shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _trace_case(name, fn, args, **kw):
    return build_case_ir(AnalysisCase(
        name, "test", lambda: CaseProgram(fn=fn, args=tuple(args), **kw)))


def _fired(ir, select=None):
    return [f.rule for f in findings_for_case(ir, Path(REPO),
                                              select=select)]


# --------------------------------------------------------------------------
# per-rule program fixture pairs
# --------------------------------------------------------------------------
# Each entry: rule -> (bad CaseProgram builder, good CaseProgram builder).
# Builders are lazy so a broken fixture fails its own test, not import.

def _promotion_bad():
    def f(x):
        y = x.astype(f32) * 2.0            # 16 MiB fp32 round trip
        return y.astype(bf16)
    return CaseProgram(fn=f, args=(_sds((2048, 2048), bf16),))


def _promotion_good():
    def f(x):
        return x * 2
    return CaseProgram(fn=f, args=(_sds((2048, 2048), bf16),))


def _x64_bad():
    def f(x):
        return x.astype(jnp.float64).sum()
    return CaseProgram(fn=f, args=(_sds((64, 64), f32),), x64=True)


def _x64_good():
    def f(x):
        return x.sum()
    return CaseProgram(fn=f, args=(_sds((64, 64), f32),))


def _dead_output_bad():
    def f(a, b):
        _unused = a @ b                    # dead dot_general
        return a + b
    return CaseProgram(fn=f, args=(_sds((256, 256)), _sds((256, 256))))


def _dead_output_good():
    def f(a, b):
        return a @ b
    return CaseProgram(fn=f, args=(_sds((256, 256)), _sds((256, 256))))


def _donation_bad():
    def f(x):
        return x.astype(bf16)              # no f32 output to alias
    return CaseProgram(fn=f, args=(_sds((1024, 1024)),), donate=(0,))


def _donation_good():
    def f(x):
        return x + 1.0
    return CaseProgram(fn=f, args=(_sds((1024, 1024)),), donate=(0,))


_BIG_CONST = np.ones((512, 512), np.float32)       # 1 MiB
_SMALL_CONST = np.ones((16, 16), np.float32)


def _const_bad():
    def f(x):
        return x + jnp.asarray(_BIG_CONST)
    return CaseProgram(fn=f, args=(_sds((512, 512)),))


def _const_good():
    def f(x):
        return x[:16, :16] + jnp.asarray(_SMALL_CONST)
    return CaseProgram(fn=f, args=(_sds((512, 512)),))


def _blowup_bad():
    def f(x):
        return jnp.broadcast_to(x[None, :], (4096, 1024)) + 0.5
    return CaseProgram(fn=f, args=(_sds((1024,)),))


def _blowup_good():
    def f(x):
        return jnp.broadcast_to(x[None, :], (4, 1024)) + 0.5
    return CaseProgram(fn=f, args=(_sds((1024,)),))


def _effectful_bad():
    def f(x):
        def body(c, _):
            jax.debug.print("step {c}", c=c.sum())
            return c + 1.0, c.sum()
        c, ys = lax.scan(body, x, None, length=2)
        return c, ys
    return CaseProgram(fn=f, args=(_sds((8,)),))


def _effectful_good():
    def f(x):
        def body(c, _):
            return c + 1.0, c.sum()
        c, ys = lax.scan(body, x, None, length=2)
        jax.debug.print("done {c}", c=c.sum())   # chunk boundary: fine
        return c, ys
    return CaseProgram(fn=f, args=(_sds((8,)),))


def _cardinality_bad():
    # the "bucketing" fails to collapse: each raw length is its own trace
    def f(x):
        return x * 2.0
    return CaseProgram(fn=f, args=(_sds((90,)),),
                       variants=[(_sds((93,)),)], max_traces=1)


def _cardinality_good():
    def f(x):
        return x * 2.0
    bucket = (_sds((96,)),)                   # both lengths pad to 96
    return CaseProgram(fn=f, args=bucket, variants=[bucket],
                       max_traces=1)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _transpose_bad():
    def f(x):
        y = jnp.swapaxes(x, -1, -2)           # 4 MiB minor-dim relayout
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            interpret=True)(y)
    return CaseProgram(fn=f, args=(_sds((8, 512, 256)),))


def _transpose_good():
    def f(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
    return CaseProgram(fn=f, args=(_sds((8, 512, 256)),))


IR_FIXTURES = {
    "ir-dtype-promotion-drift": (_promotion_bad, _promotion_good),
    "ir-x64-leak": (_x64_bad, _x64_good),
    "ir-dead-output": (_dead_output_bad, _dead_output_good),
    "ir-donation-ineffective": (_donation_bad, _donation_good),
    "ir-large-const-capture": (_const_bad, _const_good),
    "ir-broadcast-blowup": (_blowup_bad, _blowup_good),
    "ir-effectful-in-scan": (_effectful_bad, _effectful_good),
    "ir-compile-key-cardinality": (_cardinality_bad, _cardinality_good),
    "ir-transpose-heavy-layout": (_transpose_bad, _transpose_good),
}


def _ir_for(builder, name):
    return build_case_ir(AnalysisCase(name, "test", builder))


@pytest.mark.parametrize("rule", sorted(IR_FIXTURES))
def test_bad_program_triggers_exactly_its_rule(rule):
    ir = _ir_for(IR_FIXTURES[rule][0], f"bad_{rule}")
    fired = _fired(ir)
    assert fired, f"bad program for {rule} produced no findings"
    assert set(fired) == {rule}, fired


@pytest.mark.parametrize("rule", sorted(IR_FIXTURES))
def test_good_program_is_clean(rule):
    ir = _ir_for(IR_FIXTURES[rule][1], f"good_{rule}")
    assert not _fired(ir)


@pytest.mark.parametrize("rule", sorted(IR_FIXTURES))
def test_ir_rules_individually_load_bearing(rule):
    """With the rule deselected (≈ deleted), its bad program passes: no
    other IR rule shadows it."""
    ir = _ir_for(IR_FIXTURES[rule][0], f"bad_{rule}")
    others = [r for r in IR_RULES if r != rule]
    assert not _fired(ir, select=others)


def test_every_ir_rule_has_a_fixture():
    assert set(IR_RULES) == set(IR_FIXTURES)


# --------------------------------------------------------------------------
# machinery: anchoring, suppression, trace errors, registry
# --------------------------------------------------------------------------

def test_findings_anchor_to_this_file():
    """eqn.source_info maps the dead dot_general back to the fixture's
    own line in this test file."""
    ir = _ir_for(_dead_output_bad, "anchor_case")
    (finding,) = findings_for_case(ir, Path(REPO))
    assert finding.path == "tests/test_ir_lint.py"
    assert finding.scope == "anchor_case"
    src = Path(REPO, finding.path).read_text().splitlines()
    assert "a @ b" in src[finding.line - 1]


def test_ir_finding_is_inline_suppressible(tmp_path):
    """The ordinary disable pragma, placed at the ANCHORED source line,
    silences an IR finding — proven through analyze_ir's suppression
    path by anchoring a finding into a scratch root."""
    mod = tmp_path / "prog.py"
    mod.write_text(textwrap.dedent("""\
        import jax.numpy as jnp

        def wasteful(a, b):
            _unused = a @ b  # tpu-lint: disable=ir-dead-output -- test
            return a + b
    """))
    sys.path.insert(0, str(tmp_path))
    try:
        import prog

        def build():
            return CaseProgram(fn=prog.wasteful,
                               args=(_sds((256, 256)), _sds((256, 256))))
        from apex_tpu.analysis.ir import ir_report
        case = AnalysisCase("supp_case", "test", build)
        ir = build_case_ir(case)
        findings = findings_for_case(ir, tmp_path)
        assert [f.rule for f in findings] == ["ir-dead-output"]
        supp = ir_report._SuppressionCache(tmp_path)
        assert supp.get(findings[0].path).covers(findings[0])
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("prog", None)


def test_trace_error_is_a_finding_not_a_crash(monkeypatch):
    import apex_tpu.analysis.ir.ir_report as ir_report

    def boom():
        raise RuntimeError("fixture exploded")

    monkeypatch.setattr(
        ir_report, "analysis_cases",
        lambda root: [AnalysisCase("boom_case", "test", boom)])
    findings, suppressed, n = analyze_ir(REPO)
    assert n == 1
    assert [f.rule for f in findings] == ["ir-trace-error"]
    assert "boom_case" in findings[0].message
    assert "fixture exploded" in findings[0].message


def test_registry_spans_the_stack():
    """ISSUE 5 acceptance: >= 6 registered cases spanning serving,
    models, ops and optimizers."""
    cases = analysis_cases(REPO)
    assert len(cases) >= 6
    domains = {c.domain for c in cases}
    assert {"serving", "models", "ops", "optimizers"} <= domains
    names = [c.name for c in cases]
    assert len(names) == len(set(names)), "duplicate case names"
    for expected in ("gpt2s_engine_decode_chunk",
                     "gpt2s_engine_admit_bucketed",
                     "gpt2s_prefix_cached_admit",
                     "paged_attention_gpt2s_decode"):
        assert expected in names


def test_unknown_ir_case_and_rule_are_usage_errors(capsys):
    assert cli.main(["--root", REPO, "--ir-case", "no-such-case"]) == 2
    assert cli.main(["--root", REPO, "--ir",
                     "--select", "no-such-ir-rule"]) == 2
    # AST rule names are not valid in IR mode (and vice versa)
    assert cli.main(["--root", REPO, "--ir",
                     "--select", "host-sync-in-jit"]) == 2


def test_ir_rejects_paths(capsys):
    assert cli.main(["apex_tpu", "--root", REPO, "--ir"]) == 2


def test_diff_refuses_ir(capsys):
    assert cli.main(["--root", REPO, "--ir", "--diff", "HEAD"]) == 2


# --------------------------------------------------------------------------
# cardinality contract of the real admission case
# --------------------------------------------------------------------------

def test_admit_bucketing_case_collapses_variants():
    """The registered serving admission case traces its two same-bucket
    prompt lengths to ONE program (the engine's compile-key contract)."""
    (case,) = [c for c in analysis_cases(REPO)
               if c.name == "gpt2s_engine_admit_bucketed"]
    ir = build_case_ir(case)
    assert ir.variant_closed, "case lost its cardinality variants"
    assert not [r for r in _fired(ir)
                if r == "ir-compile-key-cardinality"]


# --------------------------------------------------------------------------
# end-to-end: the repo's staged programs are clean (tier-1 IR gate twin)
# --------------------------------------------------------------------------

def test_repo_ir_is_clean_at_head(capsys):
    rc = cli.main(["--root", REPO, "--ir"])
    out = capsys.readouterr().out
    assert rc == 0, f"tpu-lint --ir found new issues in the repo:\n{out}"


def test_ir_case_scoped_write_baseline_keeps_other_cases(tmp_path,
                                                         monkeypatch):
    """--ir-case A --write-baseline replaces only case A's entries;
    other cases' (and the AST tier's) baselined debt survives."""
    import json

    from apex_tpu.analysis.walker import Finding

    baseline = tmp_path / "tpu_lint_baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": {
        "x.py::ir-dead-output::case_a": 1,
        "y.py::ir-dead-output::case_b": 2,
        "z.py::host-sync-in-jit::fn": 3,
    }}))
    fresh_a = Finding(rule="ir-x64-leak", severity="error", path="x.py",
                      line=1, col=1, message="m", scope="case_a")
    import apex_tpu.analysis.ir as ir_pkg
    monkeypatch.setattr(ir_pkg, "analyze_ir",
                        lambda root, select=None, case=None:
                        ([fresh_a], 0, 1))
    assert cli.main(["--root", str(tmp_path), "--ir-case", "case_a",
                     "--write-baseline"]) == 0
    counts = json.loads(baseline.read_text())["findings"]
    assert counts == {
        "x.py::ir-x64-leak::case_a": 1,       # case A replaced
        "y.py::ir-dead-output::case_b": 2,    # other case kept
        "z.py::host-sync-in-jit::fn": 3,      # AST tier kept
    }


def test_registry_build_failure_is_a_finding(monkeypatch):
    """An import-time error in tpu_aot.py keeps the findings-not-crashes
    contract instead of dumping a traceback with a misleading exit 1."""
    import apex_tpu.analysis.ir.ir_report as ir_report

    def boom_registry(root):
        raise RuntimeError("tpu_aot import exploded")

    monkeypatch.setattr(ir_report, "analysis_cases", boom_registry)
    findings, suppressed, n = analyze_ir(REPO)
    assert n == 0 and suppressed == 0
    assert [f.rule for f in findings] == ["ir-trace-error"]
    assert "registry" in findings[0].message
    assert "tpu_aot import exploded" in findings[0].message
