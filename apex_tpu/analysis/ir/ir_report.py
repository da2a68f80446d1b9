"""Source mapping + orchestration for the tpu-lint IR tier.

Jaxpr equations carry ``source_info`` tracebacks; :func:`eqn_anchor`
maps each finding back to the innermost frame inside the repo, so IR
findings are file:line-addressable exactly like AST ones — and
suppressible with the same ``# tpu-lint: disable=RULE`` pragmas, read
from the anchored file. Findings with no single equation (donation,
closed-over constants, trace cardinality) anchor at the case function's
definition site.

:func:`analyze_ir` is the tier's engine: build the case registry, trace
each case, run the selected IR rules, apply inline suppressions.
Baseline handling stays in the CLI (same split as the AST tier).
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from apex_tpu.analysis.ir.harness import (AnalysisCase, CaseIR,
                                          analysis_cases, build_case_ir)
from apex_tpu.analysis.ir.ir_rules import IR_RULES
from apex_tpu.analysis.suppressions import Suppressions
from apex_tpu.analysis.walker import Finding


def _rel_to(root: Path, filename: str) -> Optional[str]:
    try:
        return Path(filename).resolve().relative_to(root).as_posix()
    except (ValueError, OSError):
        return None


def eqn_anchor(eqn, root: Path) -> Optional[Tuple[str, int]]:
    """(repo-relative path, line) of the innermost user frame under
    ``root`` for one equation, or None (e.g. jax-internal synthesized
    eqns)."""
    from jax._src import source_info_util as siu

    for frame in siu.user_frames(eqn.source_info.traceback):
        rel = _rel_to(root, frame.file_name)
        if rel is not None and frame.start_line:
            return (rel, int(frame.start_line))
    return None


def _case_anchor(ir: CaseIR, root: Path) -> Tuple[str, int]:
    rel = _rel_to(root, ir.origin[0])
    if rel is not None:
        return (rel, ir.origin[1])
    # a case defined outside the repo (shouldn't happen) still needs a
    # stable, baseline-able path
    return (Path(ir.origin[0]).name, ir.origin[1])


class _SuppressionCache:
    """Suppressions per anchored file, loaded lazily from disk."""

    def __init__(self, root: Path):
        self.root = root
        self._cache: Dict[str, Suppressions] = {}

    def get(self, rel: str) -> Suppressions:
        if rel not in self._cache:
            try:
                src = (self.root / rel).read_text()
            except OSError:
                src = ""
            self._cache[rel] = Suppressions(src)
        return self._cache[rel]


def findings_for_case(ir: CaseIR, root: Path,
                      select: Optional[Iterable[str]] = None
                      ) -> List[Finding]:
    """Run the (selected) IR rules over one traced case; findings carry
    ``scope=<case name>`` so baseline keys are per-entry-point."""
    chosen = set(select) if select is not None else set(IR_RULES)
    out: List[Finding] = []
    for name in sorted(chosen):
        rule = IR_RULES[name]
        for raw in rule.check(ir):
            anchor = eqn_anchor(raw.eqn, root) if raw.eqn is not None \
                else None
            if anchor is None:
                anchor = _case_anchor(ir, root)
            out.append(Finding(
                rule=rule.name, severity=rule.severity, path=anchor[0],
                line=anchor[1], col=1,
                message=f"[case {ir.name}] {raw.message}",
                scope=ir.name))
    return out


def analyze_ir(root, *, select: Optional[Iterable[str]] = None,
               case: Optional[str] = None,
               ) -> Tuple[List[Finding], int, int]:
    """Trace the registry and lint every jaxpr; returns
    ``(findings, #suppressed, #cases)``.

    ``select`` restricts to a subset of IR rule names; ``case`` runs a
    single registered case (``--ir-case``). A case that fails to trace
    yields an ``ir-trace-error`` finding (severity error) instead of
    crashing the run — one broken entry point must not hide the rest.
    """
    root = Path(root).resolve()
    if select is not None:
        unknown = set(select) - set(IR_RULES)
        if unknown:
            raise ValueError(
                f"unknown IR rule(s): {', '.join(sorted(unknown))}")
    try:
        cases = analysis_cases(root)
    except Exception as e:          # noqa: BLE001 — findings, not crashes
        # an import-time failure in tpu_aot.py (env-dependent check,
        # missing dep) must keep the 0/1/2 contract, like parse-error
        return ([Finding(
            rule="ir-trace-error", severity="error", path="tpu_aot.py",
            line=1, col=1, scope="<registry>",
            message=f"failed to build the IR case registry: "
                    f"{type(e).__name__}: {e}")], 0, 0)
    if case is not None:
        cases = [c for c in cases if c.name == case]
        if not cases:
            raise ValueError(f"unknown IR case: {case}")
    supp = _SuppressionCache(root)
    findings: List[Finding] = []
    suppressed = 0
    for c in cases:
        try:
            ir = build_case_ir(c)
        except Exception as e:      # noqa: BLE001 — findings, not crashes
            findings.append(Finding(
                rule="ir-trace-error", severity="error",
                path="apex_tpu/analysis/ir/harness.py", line=1, col=1,
                scope=c.name,
                message=f"[case {c.name}] failed to trace: "
                        f"{type(e).__name__}: {e}"))
            continue
        for f in findings_for_case(ir, root, select):
            if supp.get(f.path).covers(f):
                suppressed += 1
            else:
                findings.append(f)
    return findings, suppressed, len(cases)
