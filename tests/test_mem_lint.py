"""tpu-lint mem tier (apex_tpu.analysis.mem) coverage.

Mirrors the IR tier's load-bearing pattern (tests/test_ir_lint.py) for
the fourth tier, per ISSUE 18:

1. per-rule fixture pairs — a bad PROGRAM whose static memory estimate
   triggers EXACTLY its rule (and passes with the rule deselected), and
   a good twin that is clean;
2. machinery — case anchoring, inline suppression, the trace-error
   path, tier-partitioned ``--write-baseline``, ``--diff --mem``;
3. a seeded-mutation pin: shrinking a REAL registered case's declared
   HBM budget makes the fit proof fail (and between the two peaks, the
   scan-carry rule — the two HBM rules are disjoint by construction);
4. end-to-end — ``--mem`` over the repo itself exits 0 at HEAD: the
   tier-1 twin of the ``--mem`` CI gate.
"""

import dataclasses
import json
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
from jax import lax                                            # noqa: E402
from jax.experimental import pallas as pl                      # noqa: E402
from jax.sharding import PartitionSpec as P                    # noqa: E402

from apex_tpu.analysis import cli                              # noqa: E402
from apex_tpu.analysis.ir.harness import (AnalysisCase,        # noqa: E402
                                          CaseProgram,
                                          analysis_cases,
                                          build_case_ir)
from apex_tpu.analysis.mem import (MEM_RULES, analyze_mem,     # noqa: E402
                                   estimate_case)
from apex_tpu.analysis.mem.mem_report import (                 # noqa: E402
    findings_for_mem_case)
from apex_tpu.analysis.tiers import tier_of                    # noqa: E402

f32, i32 = jnp.float32, jnp.int32

MIB = 1024 ** 2


def _sds(shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _mesh2():
    from apex_tpu.serving.tp import abstract_tp_mesh

    return abstract_tp_mesh(2)


def _fired(ir, select=None):
    return [f.rule for f in findings_for_mem_case(ir, Path(REPO),
                                                  select=select)]


# --------------------------------------------------------------------------
# per-rule program fixture pairs
# --------------------------------------------------------------------------
# Each entry: rule -> (bad CaseProgram builder, good CaseProgram builder).
# Builders are lazy so a broken fixture fails its own test, not import.

def _hbm_bad():
    # 1 MiB input + 1 MiB matmul result = 2 MiB peak vs a 1.5 MiB budget
    def f(x):
        return x @ x
    return CaseProgram(fn=f, args=(_sds((512, 512)),),
                       meta={"hbm_budget_bytes": int(1.5 * MIB)})


def _hbm_good():
    def f(x):
        return x @ x
    return CaseProgram(fn=f, args=(_sds((512, 512)),),
                       meta={"hbm_budget_bytes": 4 * MIB})


def _scan_carry_bad():
    # the donated 1 MiB carry updates in place (peak 1 MiB) — but XLA
    # double-buffers the scan carry, so the true peak is 2 MiB; a
    # 1.5 MiB budget passes the naive sweep and fails the real one.
    # This is docs/tp_serving.md's pool-sizing lesson at lint scale.
    def f(x):
        def body(c, _):
            return c + 1.0, ()
        c, _ = lax.scan(body, x, None, length=3)
        return c
    return CaseProgram(fn=f, args=(_sds((512, 512)),), donate=(0,),
                       meta={"hbm_budget_bytes": int(1.5 * MIB)})


def _scan_carry_good():
    def f(x):
        def body(c, _):
            return c + 1.0, ()
        c, _ = lax.scan(body, x, None, length=3)
        return c
    # sized for BOTH copies of the carry — the rule's prescribed fix
    return CaseProgram(fn=f, args=(_sds((512, 512)),), donate=(0,),
                       meta={"hbm_budget_bytes": 3 * MIB})


def _vmem_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _vmem_bad():
    # one (2048, 2080) f32 block pads to ~17.8 MiB > the 16 MiB stack
    def f(x):
        return pl.pallas_call(
            _vmem_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
    return CaseProgram(fn=f, args=(_sds((2048, 2080)),))


def _vmem_good():
    def f(x):
        return pl.pallas_call(
            _vmem_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
    return CaseProgram(fn=f, args=(_sds((1024, 128)),))


def _padding_bad():
    # minor dim 64 pads to 128: 128 MiB logical occupies 256 MiB (2.0x,
    # 128 MiB wasted) — the PR 10 d=64 pool lesson at fixture scale
    def f(x):
        return x + 1.0
    return CaseProgram(fn=f, args=(_sds((256, 2048, 64)),))


def _padding_good():
    def f(x):
        return x + 1.0
    return CaseProgram(fn=f, args=(_sds((256, 1024, 128)),))


def _indivisible_bad():
    def f(x):
        return x * 2.0
    return CaseProgram(fn=f, args=(_sds((6, 128)),),
                       meta={"mesh_axes": {"model": 4},
                             "arg_specs": (P("model", None),)})


def _indivisible_good():
    def f(x):
        return x * 2.0
    return CaseProgram(fn=f, args=(_sds((8, 128)),),
                       meta={"mesh_axes": {"model": 4},
                             "arg_specs": (P("model", None),)})


def _replicated_bad():
    # out_specs P() promises every chip the same value, but the body
    # reduces a SHARDED operand with no psum — check_vma=False (the
    # production seam, serving/tp.py) asserts nothing
    fn = jax.shard_map(lambda v: v.sum(), mesh=_mesh2(),
                       in_specs=P("model"), out_specs=P(),
                       check_vma=False)
    return CaseProgram(fn=fn, args=(_sds((2, 128)),))


def _replicated_good():
    fn = jax.shard_map(lambda v: lax.psum(v.sum(), "model"),
                       mesh=_mesh2(), in_specs=P("model"), out_specs=P(),
                       check_vma=False)
    return CaseProgram(fn=fn, args=(_sds((2, 128)),))


def _donation_spec_bad():
    # donated buffer sharded on dim 0, only output sharded on dim 1:
    # no same-shape+dtype+spec output, the aliasing cannot happen
    fn = jax.shard_map(lambda p: p * 2.0, mesh=_mesh2(),
                       in_specs=P("model", None),
                       out_specs=P(None, "model"), check_vma=False)
    return CaseProgram(fn=fn, args=(_sds((8, 128)),), donate=(0,))


def _donation_spec_good():
    fn = jax.shard_map(lambda p: p + 1.0, mesh=_mesh2(),
                       in_specs=P("model", None),
                       out_specs=P("model", None), check_vma=False)
    return CaseProgram(fn=fn, args=(_sds((8, 128)),), donate=(0,))


def _scale_drift_prog(scale_spec):
    fn = jax.shard_map(
        lambda d: lax.psum(d["weight"].sum() * d["scale"].sum(),
                           "model"),
        mesh=_mesh2(),
        in_specs=({"scale": scale_spec, "weight": P("model", None)},),
        out_specs=P(), check_vma=False)
    args = ({"scale": _sds((256,)), "weight": _sds((256, 128))},)
    return CaseProgram(fn=fn, args=args)


def _scale_drift_bad():
    # the weight shards its 256 output channels over 'model'; its
    # per-out-channel scale replicates — each chip would scale its
    # shard with the wrong rows (the PR 16 invariant)
    return _scale_drift_prog(P())


def _scale_drift_good():
    return _scale_drift_prog(P("model"))


MEM_FIXTURES = {
    "mem-hbm-over-budget": (_hbm_bad, _hbm_good),
    "mem-scan-carry-double-buffer": (_scan_carry_bad, _scan_carry_good),
    "mem-vmem-over-budget": (_vmem_bad, _vmem_good),
    "mem-padding-blowup": (_padding_bad, _padding_good),
    "mem-spec-indivisible": (_indivisible_bad, _indivisible_good),
    "mem-replicated-no-collective": (_replicated_bad, _replicated_good),
    "mem-donation-spec-mismatch": (_donation_spec_bad,
                                   _donation_spec_good),
    "mem-scale-shard-drift": (_scale_drift_bad, _scale_drift_good),
}


def _ir_for(builder, name):
    return build_case_ir(AnalysisCase(name, "test", builder))


@pytest.mark.parametrize("rule", sorted(MEM_FIXTURES))
def test_bad_program_triggers_exactly_its_rule(rule):
    ir = _ir_for(MEM_FIXTURES[rule][0], f"bad_{rule}")
    fired = _fired(ir)
    assert fired, f"bad program for {rule} produced no findings"
    assert set(fired) == {rule}, fired


@pytest.mark.parametrize("rule", sorted(MEM_FIXTURES))
def test_good_program_is_clean(rule):
    ir = _ir_for(MEM_FIXTURES[rule][1], f"good_{rule}")
    assert not _fired(ir)


@pytest.mark.parametrize("rule", sorted(MEM_FIXTURES))
def test_mem_rules_individually_load_bearing(rule):
    """With the rule deselected (≈ its check deleted), its bad program
    passes: no other mem rule shadows it."""
    ir = _ir_for(MEM_FIXTURES[rule][0], f"bad_{rule}")
    others = [r for r in MEM_RULES if r != rule]
    assert not _fired(ir, select=others)


def test_every_mem_rule_has_a_fixture():
    assert set(MEM_RULES) == set(MEM_FIXTURES)


def test_mem_rules_are_in_the_mem_tier():
    for name in MEM_RULES:
        assert tier_of(name) == "mem", name


# --------------------------------------------------------------------------
# the estimator's model, pinned at fixture scale
# --------------------------------------------------------------------------

def test_scan_carry_peaks_are_disjoint_evidence():
    """The two HBM rules partition on (peak_no_db, peak): the donated
    in-place carry costs 1 MiB until double buffering doubles it."""
    ir = _ir_for(_scan_carry_bad, "peaks_case")
    est = estimate_case(ir)
    assert est.peak_no_db_bytes == 1 * MIB
    assert est.peak_bytes == 2 * MIB
    assert est.scan_carry_extra_bytes == 1 * MIB
    assert est.alias_bytes == 1 * MIB          # the in-place credit


def test_undonated_scan_carry_gets_no_inplace_credit():
    """Without donation the program input is not writable: both copies
    count even before double buffering (donation-ineffective at the
    memory level)."""
    prog = _scan_carry_bad()
    undonated = dataclasses.replace(prog, donate=())
    ir = build_case_ir(AnalysisCase("no_donate", "test",
                                    lambda: undonated))
    est = estimate_case(ir)
    assert est.peak_no_db_bytes == 2 * MIB
    assert est.alias_bytes == 0


def test_traced_grid_bound_still_counts_as_pipelined():
    """The paged kernels' walk axis has a traced bound (``n_work``): the
    estimator cannot size it, and must still charge every block its two
    pipeline buffers — at the Qwen3-Next cell's shape (64 slots of 2048
    entries, 2 kv heads of 256) the K and V page blocks alone are the
    512 KiB that ``_page_walk._tile`` budgets double-buffered."""
    from apex_tpu.ops._page_walk import _tile
    from apex_tpu.ops.paged_attention import paged_attention

    bf16 = jnp.bfloat16
    args = (_sds((64, 16, 1, 256), bf16), _sds((4096, 2, 16, 256), bf16),
            _sds((4096, 2, 16, 256), bf16), _sds((64, 2048), i32),
            _sds((64,), i32))
    ir = build_case_ir(AnalysisCase(
        "qwen3_next_paged", "test",
        lambda: CaseProgram(fn=paged_attention, args=args)))
    (call,) = estimate_case(ir).vmem
    assert call.grid == (1, None)          # (kv // heads, n_work)
    assert call.buffering == 2
    pages, heads = _tile(2, 16, 256, bf16, 2048)
    kv_blocks = 2 * pages * heads * 16 * 256 * 2       # K and V, one buffer
    assert call.est_bytes >= 2 * kv_blocks == 512 * 1024
    assert not _fired(ir)


def test_per_chip_scope_on_shard_map_programs():
    ir = _ir_for(_donation_spec_good, "scope_case")
    est = estimate_case(ir)
    assert est.scope == "per-chip"
    # boundary arrays carry LOCAL shard shapes: (8,128) over 2 chips
    shapes = {b.shape for b in est.boundary}
    assert (4, 128) in shapes, est.boundary


# --------------------------------------------------------------------------
# seeded mutation: shrink a REAL case's declared budget
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tp2_decode_ir():
    (case,) = [c for c in analysis_cases(REPO)
               if c.name == "tp2_engine_decode_chunk"]
    return build_case_ir(case)


def _with_budget(ir, budget):
    meta = dict(ir.prog.meta or {})
    meta["hbm_budget_bytes"] = budget
    return dataclasses.replace(ir, prog=dataclasses.replace(
        ir.prog, meta=meta))


def test_shrunk_budget_fails_the_fit_proof(tp2_decode_ir):
    """The registered tp2 decode case fits a v5e; declare a budget
    below its static peak and the fit proof must fail — proof the gate
    would catch a pool/model growth that outruns the chip."""
    est = estimate_case(tp2_decode_ir)
    assert not _fired(tp2_decode_ir), "case should be clean as shipped"
    mutated = _with_budget(tp2_decode_ir, est.peak_no_db_bytes - 1)
    assert "mem-hbm-over-budget" in _fired(mutated)


def test_budget_between_peaks_names_the_double_buffer(tp2_decode_ir):
    """A budget that fits the naive sweep but not the double-buffered
    carry blames the SCAN rule, not the generic over-budget one — each
    failure names the lesson to apply."""
    est = estimate_case(tp2_decode_ir)
    assert est.peak_no_db_bytes < est.peak_bytes, (
        "decode chunk lost its scan double-buffer charge")
    between = (est.peak_no_db_bytes + est.peak_bytes) // 2
    fired = _fired(_with_budget(tp2_decode_ir, between))
    assert "mem-scan-carry-double-buffer" in fired
    assert "mem-hbm-over-budget" not in fired


# --------------------------------------------------------------------------
# machinery: anchoring, suppression, trace errors
# --------------------------------------------------------------------------

def test_findings_anchor_into_this_file():
    """Estimate-level findings anchor at the case's def site in this
    test file; equation-level ones (vmem) at the pallas_call eqn."""
    ir = _ir_for(_hbm_bad, "anchor_case")
    findings = findings_for_mem_case(ir, Path(REPO))
    assert findings
    for f in findings:
        assert f.path == "tests/test_mem_lint.py"
        assert f.scope == "anchor_case"
        assert "[case anchor_case]" in f.message


def test_mem_finding_is_inline_suppressible(tmp_path):
    """The ordinary disable pragma at the ANCHORED line silences a mem
    finding through the same suppression cache the other tiers use."""
    from apex_tpu.analysis.ir import ir_report

    mod = tmp_path / "memprog.py"
    mod.write_text(textwrap.dedent("""\
        def hungry(x):  # tpu-lint: disable=mem-hbm-over-budget -- test
            return x @ x
    """))
    sys.path.insert(0, str(tmp_path))
    try:
        import memprog

        def build():
            return CaseProgram(fn=memprog.hungry,
                               args=(_sds((512, 512)),),
                               meta={"hbm_budget_bytes": MIB})
        ir = build_case_ir(AnalysisCase("supp_case", "test", build))
        findings = findings_for_mem_case(ir, tmp_path)
        assert [f.rule for f in findings] == ["mem-hbm-over-budget"]
        supp = ir_report._SuppressionCache(tmp_path)
        assert supp.get(findings[0].path).covers(findings[0])
    finally:
        sys.path.remove(str(tmp_path))
        sys.modules.pop("memprog", None)


def test_trace_error_is_a_finding_not_a_crash(monkeypatch):
    import apex_tpu.analysis.mem.mem_report as mem_report

    def boom():
        raise RuntimeError("fixture exploded")

    monkeypatch.setattr(
        mem_report, "mem_cases",
        lambda root: [AnalysisCase("boom_case", "test", boom)])
    findings, suppressed, n = analyze_mem(REPO)
    assert n == 1
    assert [f.rule for f in findings] == ["mem-trace-error"]
    assert "boom_case" in findings[0].message
    assert "fixture exploded" in findings[0].message


def test_registry_build_failure_is_a_finding(monkeypatch):
    import apex_tpu.analysis.mem.mem_report as mem_report

    def boom_registry(root):
        raise RuntimeError("tpu_aot import exploded")

    monkeypatch.setattr(mem_report, "mem_cases", boom_registry)
    findings, suppressed, n = analyze_mem(REPO)
    assert n == 0 and suppressed == 0
    assert [f.rule for f in findings] == ["mem-trace-error"]
    assert "registry" in findings[0].message
    assert "tpu_aot import exploded" in findings[0].message


def test_registry_covers_ir_cases_plus_acceptance():
    from apex_tpu.analysis.mem import ACCEPTANCE_TO_AOT, mem_cases

    names = [c.name for c in mem_cases(REPO)]
    assert len(names) == len(set(names)), "duplicate case names"
    ir_names = {c.name for c in analysis_cases(REPO)}
    assert ir_names <= set(names), "mem tier dropped IR cases"
    for acc in ACCEPTANCE_TO_AOT:
        assert acc in names, f"acceptance case {acc} missing"


# --------------------------------------------------------------------------
# CLI: usage errors, baseline partitioning, --diff
# --------------------------------------------------------------------------

def test_unknown_mem_case_and_rule_are_usage_errors(capsys):
    assert cli.main(["--root", REPO, "--mem-case", "no-such-case"]) == 2
    assert cli.main(["--root", REPO, "--mem",
                     "--select", "no-such-mem-rule"]) == 2
    # rule names from other tiers are not valid in mem mode
    assert cli.main(["--root", REPO, "--mem",
                     "--select", "ir-dead-output"]) == 2


def test_mem_rejects_paths_and_other_tiers(capsys):
    assert cli.main(["apex_tpu", "--root", REPO, "--mem"]) == 2
    assert cli.main(["--root", REPO, "--mem", "--ir"]) == 2
    assert cli.main(["--root", REPO, "--mem", "--conc"]) == 2


def test_mem_diff_refuses_baseline_flags(capsys):
    assert cli.main(["--root", REPO, "--mem", "--diff", "HEAD",
                     "--write-baseline"]) == 2
    assert cli.main(["--root", REPO, "--mem", "--diff", "HEAD",
                     "--baseline", "x.json"]) == 2


def test_mem_case_scoped_write_baseline_keeps_other_entries(tmp_path,
                                                            monkeypatch):
    """--mem-case A --write-baseline replaces only case A's mem
    entries; other mem cases' and other tiers' debt survives."""
    from apex_tpu.analysis.walker import Finding

    baseline = tmp_path / "tpu_lint_baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": {
        "x.py::mem-hbm-over-budget::case_a": 1,
        "y.py::mem-padding-blowup::case_b": 2,
        "z.py::ir-dead-output::case_c": 3,
        "w.py::host-sync-in-jit::fn": 4,
    }}))
    fresh_a = Finding(rule="mem-vmem-over-budget", severity="error",
                      path="x.py", line=1, col=1, message="m",
                      scope="case_a")
    import apex_tpu.analysis.mem as mem_pkg
    monkeypatch.setattr(mem_pkg, "analyze_mem",
                        lambda root, select=None, case=None:
                        ([fresh_a], 0, 1))
    assert cli.main(["--root", str(tmp_path), "--mem-case", "case_a",
                     "--write-baseline"]) == 0
    counts = json.loads(baseline.read_text())["findings"]
    assert counts == {
        "x.py::mem-vmem-over-budget::case_a": 1,   # case A replaced
        "y.py::mem-padding-blowup::case_b": 2,     # other mem case kept
        "z.py::ir-dead-output::case_c": 3,         # IR tier kept
        "w.py::host-sync-in-jit::fn": 4,           # AST tier kept
    }


def test_mem_diff_splits_on_base_findings(tmp_path, monkeypatch,
                                          capsys):
    """--diff BASE --mem: base-side keys absorb matching current
    findings; the remainder fails the run."""
    from collections import Counter

    from apex_tpu.analysis.walker import Finding

    old = Finding(rule="mem-hbm-over-budget", severity="error",
                  path="a.py", line=3, col=1, message="old",
                  scope="case_x")
    new = Finding(rule="mem-padding-blowup", severity="warning",
                  path="b.py", line=7, col=1, message="new",
                  scope="case_y")
    import apex_tpu.analysis.mem as mem_pkg
    monkeypatch.setattr(mem_pkg, "analyze_mem",
                        lambda root, select=None, case=None:
                        ([old, new], 0, 2))
    monkeypatch.setattr(
        cli, "_mem_base_findings",
        lambda root, rev: Counter({old.baseline_key(): 1}))
    assert cli.main(["--root", REPO, "--mem", "--diff", "BASE",
                     "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert [f["rule"] for f in data["findings"]] == \
        ["mem-padding-blowup"]
    assert [f["rule"] for f in data["baselined"]] == \
        ["mem-hbm-over-budget"]
    # base side covering everything -> clean exit
    monkeypatch.setattr(
        cli, "_mem_base_findings",
        lambda root, rev: Counter({old.baseline_key(): 1,
                                   new.baseline_key(): 1}))
    assert cli.main(["--root", REPO, "--mem", "--diff", "BASE"]) == 0


@pytest.mark.slow       # a second full --mem run, in the worktree
def test_mem_diff_base_side_runs_in_a_worktree():
    """The real base-side runner materializes HEAD in a worktree and
    runs its --mem there. HEAD ships this very tier, and the repo is
    clean at HEAD, so the base side must come back empty — this also
    proves the worktree run actually executes (a crash would raise)."""
    counts = cli._mem_base_findings(Path(REPO), "HEAD")
    assert sum(counts.values()) == 0, counts


def test_mem_diff_base_rev_without_tier_is_empty(capsys):
    """A base rev that predates --mem contributes no findings (its CLI
    exits 2 on the unknown flag); the diff then degrades to the
    absolute gate instead of crashing."""
    # the growth seed commit has no apex_tpu.analysis at all
    import subprocess

    seed = subprocess.run(
        ["git", "-C", REPO, "rev-list", "--max-parents=0", "HEAD"],
        capture_output=True, text=True).stdout.split()[0]
    counts = cli._mem_base_findings(Path(REPO), seed)
    assert sum(counts.values()) == 0


# --------------------------------------------------------------------------
# end-to-end: the repo's programs fit their chips (the mem gate)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_repo_mem_is_clean_at_head(capsys):
    """The full-registry mem gate (~85 s: every case re-traced). Slow
    tier to hold the tier-1 verify wall;
    test_mem_gate_case_is_clean_at_head below is the fast tier-1 twin."""
    rc = cli.main(["--root", REPO, "--mem"])
    out = capsys.readouterr().out
    assert rc == 0, f"tpu-lint --mem found new issues in the repo:\n{out}"


def test_mem_gate_case_is_clean_at_head(capsys):
    """Tier-1 twin of the full gate: one real registry case through the
    whole pipeline — trace, estimate, all 8 rules, baseline, exit code.
    tp2_engine_decode_chunk is the load-bearing choice: a shard_map
    program with mesh_axes/arg_specs meta, so the sharding-contract
    rules run against real engine specs, not just fixtures."""
    rc = cli.main(["--root", REPO, "--mem-case", "tp2_engine_decode_chunk"])
    out = capsys.readouterr().out
    assert rc == 0, f"tpu-lint --mem-case found new issues:\n{out}"
    assert "0 finding(s)" in out
