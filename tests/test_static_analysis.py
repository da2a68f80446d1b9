"""tpu-lint (apex_tpu.analysis) coverage.

Three layers, matching ISSUE 3's acceptance criteria:

1. fixture pairs — per rule, a bad snippet that triggers EXACTLY that
   rule and a good twin that is clean. Running the bad fixture with the
   rule deselected must also be clean, so every rule is individually
   load-bearing (deleting one makes precisely its fixture pass).
2. machinery — inline suppressions, the baseline workflow, the JSON
   format, exit codes, the AOT case-drift project rule.
3. end-to-end — the repo itself is clean at the current baseline: the
   tier-1 twin of the CI fail-fast gate.
"""

import json
import os
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.analysis import cli                              # noqa: E402
from apex_tpu.analysis.rules import RULES, module_rules        # noqa: E402

# --------------------------------------------------------------------------
# per-rule fixture pairs
# --------------------------------------------------------------------------

_PALLAS_HEADER = """\
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
"""


def _pallas(body):
    return _PALLAS_HEADER + textwrap.dedent(body)

FIXTURES = {
    "host-sync-in-jit": (
        """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return float(x) + np.asarray(x).sum()
        """,
        """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.asarray(x).sum() + x
        """,
    ),
    "pallas-index-map-arity": (
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
    ),
    "pallas-block-tiling": (
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((7, 100), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((16, 256), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 1), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
    ),
    "pallas-dtype-drift": (
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, jnp.bfloat16),
            )(x)
        """),
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
    ),
    "pallas-traced-branch": (
        _pallas("""
        def kernel(x_ref, o_ref):
            if x_ref[0, 0] > 0:
                o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
        _pallas("""
        def kernel(x_ref, o_ref):
            o_ref[...] = jnp.where(x_ref[...] > 0, x_ref[...], 0.0)

        def call(x):
            return pl.pallas_call(
                kernel,
                grid=(4,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            )(x)
        """),
    ),
    "jit-unhashable-static": (
        """\
        import jax

        def f(cfg, x):
            return x

        g = jax.jit(f, static_argnums=(0,))

        def run(x):
            return g({"mode": "fast"}, x)
        """,
        """\
        import jax

        def f(cfg, x):
            return x

        g = jax.jit(f, static_argnums=(0,))
        CFG = ("mode", "fast")

        def run(x):
            return g(CFG, x)
        """,
    ),
    "compile-key-unbounded": (
        """\
        import jax

        _step_jit = {}

        def get_step(fn, seq_len):
            if f"s{seq_len}" not in _step_jit:
                _step_jit[f"s{seq_len}"] = jax.jit(fn)
            return _step_jit[f"s{seq_len}"]
        """,
        """\
        import jax

        _step_jit = {}

        def get_step(fn, seq_len):
            bucket = 1 << (seq_len - 1).bit_length()
            if bucket not in _step_jit:
                _step_jit[bucket] = jax.jit(fn)
            return _step_jit[bucket]
        """,
    ),
    "jit-donated-reuse": (
        """\
        import jax

        def f(buf):
            return buf + 1

        g = jax.jit(f, donate_argnums=(0,))

        def run(buf):
            out = g(buf)
            return out + buf.sum()
        """,
        """\
        import jax

        def f(buf):
            return buf + 1

        g = jax.jit(f, donate_argnums=(0,))

        def run(buf):
            buf = g(buf)
            return buf + buf.sum()
        """,
    ),
}


def _run_on(tmp_path, source, select=None):
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(source))
    findings, suppressed = cli.analyze_paths(
        [str(f)], root=tmp_path, select=select, with_project_rules=False)
    return findings, suppressed


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_bad_fixture_triggers_exactly_its_rule(rule, tmp_path):
    bad, _ = FIXTURES[rule]
    findings, _ = _run_on(tmp_path, bad)
    assert findings, f"bad fixture for {rule} produced no findings"
    assert {f.rule for f in findings} == {rule}, [
        (f.rule, f.line, f.message) for f in findings]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_good_fixture_is_clean(rule, tmp_path):
    _, good = FIXTURES[rule]
    findings, _ = _run_on(tmp_path, good)
    assert not findings, [(f.rule, f.line, f.message) for f in findings]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rules_individually_load_bearing(rule, tmp_path):
    """With the rule deselected (≈ deleted), its bad fixture passes:
    no other rule shadows it."""
    bad, _ = FIXTURES[rule]
    others = [r for r in RULES if r != rule]
    findings, _ = _run_on(tmp_path, bad, select=others)
    assert not findings, [(f.rule, f.line, f.message) for f in findings]


def test_every_module_rule_has_a_fixture():
    assert {r.name for r in module_rules()} == set(FIXTURES)


# --------------------------------------------------------------------------
# suppression + baseline machinery
# --------------------------------------------------------------------------

def test_inline_suppression_same_line(tmp_path):
    bad, _ = FIXTURES["host-sync-in-jit"]
    src = bad.replace(
        "return float(x) + np.asarray(x).sum()",
        "return float(x) + np.asarray(x).sum()  "
        "# tpu-lint: disable=host-sync-in-jit -- test justification")
    findings, suppressed = _run_on(tmp_path, src)
    assert not findings
    assert suppressed == 2      # float() and np.asarray on the same line


def test_inline_suppression_comment_line_above(tmp_path):
    bad, _ = FIXTURES["host-sync-in-jit"]
    src = bad.replace(
        "            return float(x) + np.asarray(x).sum()",
        "            # tpu-lint: disable=host-sync-in-jit\n"
        "            return float(x) + np.asarray(x).sum()")
    findings, _ = _run_on(tmp_path, src)
    assert not findings


def test_suppression_of_other_rule_does_not_apply(tmp_path):
    bad, _ = FIXTURES["host-sync-in-jit"]
    src = bad.replace(
        "return float(x) + np.asarray(x).sum()",
        "return float(x) + np.asarray(x).sum()  "
        "# tpu-lint: disable=pallas-block-tiling")
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_baseline_workflow(tmp_path, capsys):
    bad, _ = FIXTURES["jit-donated-reuse"]
    f = tmp_path / "legacy.py"
    f.write_text(textwrap.dedent(bad))
    args = [str(f), "--root", str(tmp_path)]

    assert cli.main(args) == 1
    assert cli.main(args + ["--write-baseline"]) == 0
    assert (tmp_path / "tpu_lint_baseline.json").exists()
    # baselined finding no longer fails the run ...
    assert cli.main(args) == 0
    # ... but a NEW finding of the same rule in another scope does
    f.write_text(textwrap.dedent(bad) + textwrap.dedent("""
        def run2(buf):
            out = g(buf)
            return out + buf.sum()
    """))
    capsys.readouterr()
    assert cli.main(args) == 1
    out = capsys.readouterr().out
    assert "run2" in out or "jit-donated-reuse" in out


def test_json_format(tmp_path, capsys):
    bad, _ = FIXTURES["pallas-dtype-drift"]
    f = tmp_path / "snippet.py"
    f.write_text(textwrap.dedent(bad))
    rc = cli.main([str(f), "--root", str(tmp_path), "--format", "json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["counts"]["new"] == 1
    (finding,) = data["findings"]
    assert finding["rule"] == "pallas-dtype-drift"
    assert finding["path"].endswith("snippet.py")
    assert finding["line"] > 0


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    findings, _ = cli.analyze_paths([str(f)], root=tmp_path,
                                    with_project_rules=False)
    assert [f.rule for f in findings] == ["parse-error"]


def test_unknown_rule_is_usage_error(tmp_path, capsys):
    assert cli.main(["--root", str(tmp_path),
                     "--select", "no-such-rule"]) == 2


def test_list_rules(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in RULES:
        assert name in out


# --------------------------------------------------------------------------
# aot-case-drift project rule
# --------------------------------------------------------------------------

_AOT_STUB = """\
def kernel_cases():
    yield ("layer_norm_bwd", None, [])
    yield ("flash_bwd_seq512", None, [])
"""


def _drift_tree(tmp_path, case_names):
    (tmp_path / "tpu_aot.py").write_text(_AOT_STUB)
    tests = tmp_path / "tests"
    tests.mkdir()
    names = ", ".join(repr(n) for n in case_names)
    (tests / "test_aot_mosaic.py").write_text(f"CASE_NAMES = [{names}]\n")


def test_aot_case_drift_detects_stale_name(tmp_path):
    _drift_tree(tmp_path, ["layer_norm_bwd", "renamed_case"])
    findings, _ = cli.analyze_paths([], root=tmp_path,
                                    select=["aot-case-drift"])
    assert len(findings) == 1
    assert "renamed_case" in findings[0].message


def test_aot_case_drift_clean_when_in_sync(tmp_path):
    _drift_tree(tmp_path, ["layer_norm_bwd", "flash_bwd_seq512"])
    findings, _ = cli.analyze_paths([], root=tmp_path,
                                    select=["aot-case-drift"])
    assert not findings


# --------------------------------------------------------------------------
# end-to-end: the repo itself is clean (the CI gate, tier-1)
# --------------------------------------------------------------------------

def test_repo_is_clean_at_current_baseline(capsys):
    rc = cli.main(["--root", REPO])
    out = capsys.readouterr().out
    assert rc == 0, f"tpu-lint found new issues in the repo:\n{out}"


def test_repo_case_names_in_sync():
    """Direct tier-1 pin of the drift pair, independent of the CLI."""
    findings, _ = cli.analyze_paths([], root=REPO,
                                    select=["aot-case-drift"])
    assert not findings, [f.message for f in findings]


# --------------------------------------------------------------------------
# jit-entry marking regressions (code-review repros)
# --------------------------------------------------------------------------

def test_switch_branch_list_is_traced(tmp_path):
    """lax.switch branches arrive as ONE list argument; each element is a
    traced body and must be reachable for the host-sync rule."""
    src = """\
        import jax
        import numpy as np
        from jax import lax

        def branch_a(x):
            return np.asarray(x).sum()

        def branch_b(x):
            return x

        @jax.jit
        def step(i, x):
            return lax.switch(i, [branch_a, branch_b], x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}
    assert any("branch_a" in f.message for f in findings)


def test_cond_operand_is_not_marked_traced(tmp_path):
    """cond(pred, true_fun, false_fun, *operands): an operand that happens
    to be a host-side function must NOT be marked as a traced body."""
    src = """\
        import jax
        import numpy as np
        from jax import lax

        def helper(x):
            return np.asarray(x)

        @jax.jit
        def step(pred, v):
            return lax.cond(pred, lambda a: a + 1, lambda a: a, v)

        def host_drive(v):
            return helper(v)
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


# --------------------------------------------------------------------------
# host-sync exemption: jax.debug.callback / metrics.record (ISSUE 4)
# --------------------------------------------------------------------------
# The metrics channel (``metrics.record`` -> ``jax.debug.callback``) is
# non-blocking: the payload callable runs on the HOST with delivered
# values after the step executes. The good/bad pairs below prove the
# exemption covers exactly the callback's callable argument — the same
# host ops flagged everywhere else in jit-reachable code stay flagged.

_CB_GOOD = """\
    import jax
    import numpy as np

    def _emit(v):
        return float(np.asarray(v).sum())

    @jax.jit
    def step(x):
        jax.debug.callback(_emit, x)
        return x + 1
"""


def test_debug_callback_payload_is_exempt(tmp_path):
    """A module-level callback full of host ops, reachable ONLY through
    jax.debug.callback, is clean — instrumented jit code stays
    lint-clean."""
    findings, _ = _run_on(tmp_path, _CB_GOOD)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_debug_callback_inline_lambda_is_exempt(tmp_path):
    src = """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            jax.debug.callback(lambda v: np.asarray(v).sum(), x)
            return x + 1
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_debug_callback_exemption_is_narrow_direct_call(tmp_path):
    """The SAME callback also called directly from the jitted body is
    genuinely jit-reachable — still flagged."""
    src = _CB_GOOD.replace("return x + 1", "_emit(x)\n        return x + 1")
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}
    assert any("_emit" in f.message for f in findings)


def test_debug_callback_exemption_is_narrow_operand(tmp_path):
    """Only the CALLABLE argument is exempt: a host materialization in
    the callback's traced-operand position is a real trace-time hazard
    and stays flagged."""
    src = """\
        import jax
        import numpy as np

        def _emit(v):
            return v

        @jax.jit
        def step(x):
            jax.debug.callback(_emit, np.asarray(x))
            return x + 1
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_debug_callback_partial_callable_is_exempt(tmp_path):
    """functools.partial(fn, static) as the callback is the prescribed
    record() pattern — the partial's CALLABLE is exempt."""
    src = """\
        import functools
        import jax
        import numpy as np

        def _emit(tag, v):
            return float(np.asarray(v).sum())

        @jax.jit
        def step(x):
            jax.debug.callback(functools.partial(_emit, "loss"), x)
            return x + 1
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_debug_callback_partial_operand_stays_flagged(tmp_path):
    """partial OPERANDS evaluate at trace time — `.item()` there is a
    genuine sync and must not ride the exemption."""
    src = """\
        import functools
        import jax

        def _emit(tag, v):
            return v

        @jax.jit
        def step(x):
            jax.debug.callback(functools.partial(_emit, x.item()), x)
            return x + 1
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}
    assert any("item" in f.message for f in findings)


def test_debug_callback_factory_call_is_not_exempt(tmp_path):
    """A FACTORY call in the callable position runs at trace time —
    nothing about it is exempt, including the call itself: its callee
    stays jit-reachable and its internals stay scrutinized."""
    src = """\
        import jax

        def make_cb(x):
            x.item()
            return print

        @jax.jit
        def step(x):
            jax.debug.callback(make_cb(x), x)
            return x + 1
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}
    assert any("make_cb" in f.message for f in findings)


def test_metrics_record_in_scan_body_is_clean(tmp_path):
    """The prescribed instrumentation pattern — metrics.record on a
    traced scalar inside a scan body — lints clean end to end."""
    src = """\
        import jax
        import jax.numpy as jnp
        from jax import lax
        from apex_tpu.utils import metrics

        @jax.jit
        def run(x):
            def body(c, t):
                metrics.record("loss", c)
                return c + t, c
            return lax.scan(body, x, jnp.arange(4.0))[0]
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


# --------------------------------------------------------------------------
# suppression-parsing / baseline-write hardening (code-review repros)
# --------------------------------------------------------------------------

def test_justification_comma_does_not_leak_rules(tmp_path):
    """'disable=<other-rule> -- wrong rule, all good here' must not parse
    the prose token 'all' as a disable-everything suppression."""
    bad, _ = FIXTURES["host-sync-in-jit"]
    src = bad.replace(
        "return float(x) + np.asarray(x).sum()",
        "return float(x) + np.asarray(x).sum()  "
        "# tpu-lint: disable=pallas-block-tiling -- wrong rule, all good here")
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_pragma_inside_string_literal_is_inert(tmp_path):
    bad, _ = FIXTURES["host-sync-in-jit"]
    src = bad.replace(
        "return float(x) + np.asarray(x).sum()",
        'doc = "example: # tpu-lint: disable=all"\n'
        "            return float(x) + np.asarray(x).sum()")
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_write_baseline_refuses_select(tmp_path, capsys):
    assert cli.main(["--root", str(tmp_path), "--select",
                     "host-sync-in-jit", "--write-baseline"]) == 2


def test_scoped_write_baseline_keeps_other_files(tmp_path):
    """--write-baseline over one file must not erase another file's
    baselined legacy findings."""
    bad, _ = FIXTURES["jit-donated-reuse"]
    a = tmp_path / "legacy_a.py"
    b = tmp_path / "legacy_b.py"
    a.write_text(textwrap.dedent(bad))
    b.write_text(textwrap.dedent(bad))
    # baseline both, then re-write scoped to b only
    assert cli.main([str(a), str(b), "--root", str(tmp_path),
                     "--write-baseline"]) == 0
    assert cli.main([str(b), "--root", str(tmp_path),
                     "--write-baseline"]) == 0
    # a's legacy entry survived the scoped write
    assert cli.main([str(a), str(b), "--root", str(tmp_path)]) == 0


# --------------------------------------------------------------------------
# numpy scalar-constructor coercions (ISSUE 5 satellite)
# --------------------------------------------------------------------------

def test_np_scalar_cast_on_traced_param_is_flagged(tmp_path):
    src = """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return np.float32(x) + np.int32(x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert [f.rule for f in findings] == ["host-sync-in-jit"] * 2
    assert any("np.float32(x)" in f.message for f in findings)


def test_np_array_of_traced_param_is_flagged(tmp_path):
    src = """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return np.array(x).sum()
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_np_scalar_cast_of_literal_is_clean(tmp_path):
    """Precision: np.float32(0.5) on a CONSTANT in jitted code is a
    plain host scalar, not a sync."""
    src = """\
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x * np.float32(0.5)
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


# --------------------------------------------------------------------------
# report ordering (ISSUE 5 satellite)
# --------------------------------------------------------------------------

def test_text_report_sorted_by_path_line_rule_with_severity():
    from apex_tpu.analysis import report
    from apex_tpu.analysis.walker import Finding

    def f(path, line, rule, severity="error", col=1):
        return Finding(rule=rule, severity=severity, path=path,
                       line=line, col=col, message="m")

    out = report.render_text(
        [f("b.py", 3, "zz-rule"), f("a.py", 9, "b-rule"),
         f("a.py", 9, "a-rule", col=30), f("a.py", 2, "z-rule",
                                           severity="warning")],
        [f("a.py", 5, "old-rule", severity="warning")], 0,
        show_baselined=True)
    lines = out.splitlines()
    assert lines[0].startswith("a.py:2:")       # line beats rule name
    assert lines[1].startswith("a.py:9:30: [a-rule]")  # rule beats col
    assert lines[2].startswith("a.py:9:1: [b-rule]")
    assert lines[3].startswith("b.py:3:")
    assert "warning (baselined):" in lines[4]   # severity on baselined
    assert "error:" in lines[1]


# --------------------------------------------------------------------------
# interprocedural call graph (ISSUE 5 tentpole, part B)
# --------------------------------------------------------------------------

def _pkg(tmp_path, files):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    for name, src in files.items():
        (pkg / name).write_text(textwrap.dedent(src))
    findings, suppressed = cli.analyze_paths(
        [str(pkg)], root=tmp_path, with_project_rules=False)
    return findings, suppressed


_XMOD_UTILS = """\
    import numpy as np

    def norm(x):
        return np.asarray(x).sum()

    def host_only(x):
        return np.asarray(x)
"""


def test_host_sync_seen_through_imported_helper(tmp_path):
    """A utils/ helper full of host ops, called from a jitted scan body
    in ANOTHER module, is flagged — with the cross-module chain in the
    message. Its host-only sibling stays clean."""
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "from pkg.helpers import norm\n",
        "helpers.py": _XMOD_UTILS,
        "main.py": """\
            import jax
            from jax import lax
            from pkg import norm
            from pkg.helpers import host_only

            @jax.jit
            def step(x):
                def body(c, _):
                    return c + norm(c), None
                return lax.scan(body, x, None, length=4)

            def host_drive(x):
                return host_only(x)
        """,
    })
    assert [f.rule for f in findings] == ["host-sync-in-jit"]
    assert findings[0].path.endswith("helpers.py")
    assert findings[0].scope == "norm"
    assert "main.py" in findings[0].message


def test_jit_of_imported_function_marks_it(tmp_path):
    """jax.jit(mod.fn) marks fn in its HOME module (the scheduler's
    ``jax.jit(kv_pool.free_slot)`` pattern)."""
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "",
        "pool.py": """\
            import numpy as np

            def free_slot(cache, slot):
                return np.asarray(cache)
        """,
        "engine.py": """\
            import jax
            from pkg import pool

            _free = jax.jit(pool.free_slot)
        """,
    })
    assert [f.rule for f in findings] == ["host-sync-in-jit"]
    assert findings[0].path.endswith("pool.py")


def test_unreached_import_is_clean(tmp_path):
    """Importing a host-op-heavy module does NOT taint it: only real
    call edges from jit entries do."""
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "",
        "helpers.py": _XMOD_UTILS,
        "main.py": """\
            import jax
            from pkg.helpers import norm

            @jax.jit
            def step(x):
                return x + 1

            def host_drive(x):
                return norm(x)
        """,
    })
    assert not findings, [(f.rule, f.path, f.message) for f in findings]


def test_reexport_chain_is_followed(tmp_path):
    """__init__ re-exports resolve one more hop (the serving package's
    ``from pkg import helper`` style)."""
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "from pkg.impl import helper\n",
        "impl.py": """\
            import numpy as np

            def helper(x):
                return float(np.asarray(x).sum())
        """,
        "main.py": """\
            import jax
            from pkg import helper

            @jax.jit
            def step(x):
                return helper(x)
        """,
    })
    assert {f.rule for f in findings} == {"host-sync-in-jit"}
    assert {f.path.split("/")[-1] for f in findings} == {"impl.py"}


def test_imported_donated_wrapper_tracked(tmp_path):
    """jit-donated-reuse sees a wrapper IMPORTED from another module:
    the home module's donate_argnums travel with the name."""
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "",
        "kernels.py": """\
            import jax

            def _upd(buf):
                return buf + 1

            fused_update = jax.jit(_upd, donate_argnums=(0,))
        """,
        "train.py": """\
            from pkg.kernels import fused_update

            def run(buf):
                out = fused_update(buf)
                return out + buf.sum()
        """,
    })
    assert [f.rule for f in findings] == ["jit-donated-reuse"]
    assert findings[0].path.endswith("train.py")


def test_imported_wrapper_rebind_is_clean(tmp_path):
    findings, _ = _pkg(tmp_path, {
        "__init__.py": "",
        "kernels.py": """\
            import jax

            def _upd(buf):
                return buf + 1

            fused_update = jax.jit(_upd, donate_argnums=(0,))
        """,
        "train.py": """\
            from pkg.kernels import fused_update

            def run(buf):
                buf = fused_update(buf)
                return buf + buf.sum()
        """,
    })
    assert not findings, [(f.rule, f.message) for f in findings]


# --------------------------------------------------------------------------
# host-boundary pragma
# --------------------------------------------------------------------------

def test_host_boundary_cuts_reachability(tmp_path):
    """A declared host boundary (the engine's generate_paged pattern):
    host ops below it are host code, not jit-reachable."""
    src = """\
        import jax
        import numpy as np

        # tpu-lint: host-boundary -- drives jitted programs from the host
        def drive(x):
            return np.asarray(x).sum()

        @jax.jit
        def step(x):
            return drive(x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_without_host_boundary_same_code_is_flagged(tmp_path):
    src = """\
        import jax
        import numpy as np

        def drive(x):
            return np.asarray(x).sum()

        @jax.jit
        def step(x):
            return drive(x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert {f.rule for f in findings} == {"host-sync-in-jit"}


def test_host_boundary_pragma_in_comment_block(tmp_path):
    """The pragma may sit anywhere in the comment block directly above
    the def (real-world blocks wrap justifications over lines)."""
    src = """\
        import jax
        import numpy as np

        # this is the serving engine's host loop, and the pragma below
        # tpu-lint: host-boundary -- declared never-traced
        # (more prose after it is fine too)
        def drive(x):
            return np.asarray(x).sum()

        @jax.jit
        def step(x):
            return drive(x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


# --------------------------------------------------------------------------
# --diff mode (ISSUE 5 satellite)
# --------------------------------------------------------------------------

import subprocess  # noqa: E402


def _git(cwd, *args):
    subprocess.run(["git", "-C", str(cwd), *args], check=True,
                   capture_output=True)


_DIFF_LEGACY = """\
import jax
import numpy as np

@jax.jit
def old_step(x):
    return np.asarray(x).sum()
"""


def _diff_repo(tmp_path):
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "t@t")
    _git(tmp_path, "config", "user.name", "t")
    (tmp_path / "apex_tpu").mkdir()
    (tmp_path / "apex_tpu" / "legacy.py").write_text(_DIFF_LEGACY)
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "base")


def test_diff_mode_ignores_preexisting_findings(tmp_path, capsys):
    _diff_repo(tmp_path)
    assert cli.main(["--root", str(tmp_path)]) == 1       # absolute: dirty
    assert cli.main(["--root", str(tmp_path),
                     "--diff", "HEAD"]) == 0              # diff: clean


def test_diff_mode_fails_on_introduced_finding(tmp_path, capsys):
    _diff_repo(tmp_path)
    (tmp_path / "apex_tpu" / "fresh.py").write_text(_DIFF_LEGACY)
    capsys.readouterr()
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD"]) == 1
    out = capsys.readouterr().out
    assert "fresh.py" in out
    assert "legacy.py" not in out.split("NEW relative")[0]


def test_diff_mode_new_finding_in_old_scope_fails(tmp_path, capsys):
    """A SECOND finding of the same rule in the same function exceeds
    the base count and fails, mirroring baseline semantics."""
    _diff_repo(tmp_path)
    (tmp_path / "apex_tpu" / "legacy.py").write_text(
        _DIFF_LEGACY.replace(
            "return np.asarray(x).sum()",
            "return np.asarray(x).sum() + float(x)"))
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD"]) == 1


def test_diff_mode_bad_rev_is_usage_error(tmp_path, capsys):
    _diff_repo(tmp_path)
    assert cli.main(["--root", str(tmp_path),
                     "--diff", "no-such-rev"]) == 2


def test_host_boundary_on_decorated_def(tmp_path):
    """The pragma must attach through a decorator stack (the header
    span starts at the first decorator, not the def line)."""
    src = """\
        import functools
        import jax
        import numpy as np

        def deco(f):
            return f

        # tpu-lint: host-boundary -- host driver, wrapped for logging
        @deco
        @functools.wraps(print)
        def drive(x):
            return np.asarray(x).sum()

        @jax.jit
        def step(x):
            return drive(x)
    """
    findings, _ = _run_on(tmp_path, src)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_diff_refuses_baseline_flags(tmp_path, capsys):
    _diff_repo(tmp_path)
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD",
                     "--write-baseline"]) == 2
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD",
                     "--baseline", "x.json"]) == 2


def test_diff_refuses_explicit_paths(tmp_path, capsys):
    """The base side always lints the default surface; explicit paths
    would misreport off-surface pre-existing findings as new."""
    _diff_repo(tmp_path)
    assert cli.main([str(tmp_path / "apex_tpu" / "legacy.py"),
                     "--root", str(tmp_path), "--diff", "HEAD"]) == 2
