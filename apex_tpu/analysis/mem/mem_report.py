"""Orchestration for the tpu-lint mem tier (``--mem``).

Same engine shape as the IR tier one directory over: build the case
registry, trace each case (the IR harness's ``build_case_ir`` — one
trace serves both tiers' rule sets), run the selected ``mem-*`` rules
over the static estimate, anchor findings to source via equation
``source_info`` (case-origin fallback), apply inline suppressions.
Baseline handling stays in the CLI.

The registry is ``analysis_cases()`` **plus the AOT acceptance meshes**:
the ``tp4_paged_engine_*`` programs ``tpu_aot.py`` compiles for the
deviceless v5e topology are re-registered here over an ``AbstractMesh``
at the same acceptance shape (384 slots, hidden 1024, tp=4) — so the
per-chip fit proof the slow AOT tier measures with
``memory_analysis()`` is also computed statically on every lint run,
and ``tests/test_aot_mosaic.py`` pins the two within a ±20% band
instead of hand-typed byte pins.

A case that fails to trace (or estimate) yields a ``mem-trace-error``
finding instead of crashing — one broken entry point must not hide the
rest.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from apex_tpu.analysis.ir.harness import (AnalysisCase, CaseIR,
                                          CaseProgram, analysis_cases,
                                          build_case_ir)
from apex_tpu.analysis.ir.ir_report import (_case_anchor,
                                            _SuppressionCache,
                                            eqn_anchor)
from apex_tpu.analysis.mem.estimator import MemEstimate, estimate_case
from apex_tpu.analysis.mem.layout import HBM_BYTES_V5E
from apex_tpu.analysis.mem.mem_rules import MEM_RULES, MemContext
from apex_tpu.analysis.walker import Finding

#: mem-tier case name -> the AOT multichip case it mirrors (the ±20%
#: static-vs-measured band in tests/test_aot_mosaic.py joins on this)
ACCEPTANCE_TO_AOT = {
    "tp4_serving_admit": "tp4_paged_engine_admit",
    "tp4_serving_decode_chunk": "tp4_paged_engine_decode_chunk",
    "tp4_serving_decode_w8": "tp4_paged_engine_decode_w8",
}


def hbm_budget(prog: CaseProgram) -> Tuple[int, str]:
    """The case's per-chip HBM budget and its label: an explicit
    ``meta['hbm_budget_bytes']`` override, else one v5e chip's 16 GiB
    (the serving acceptance chip)."""
    meta = prog.meta or {}
    if "hbm_budget_bytes" in meta:
        return int(meta["hbm_budget_bytes"]), "declared"
    return HBM_BYTES_V5E, "v5e"


# --------------------------------------------------------------------------
# the acceptance-mesh cases
# --------------------------------------------------------------------------

def _build_tp4_acceptance(kind: str, weight_policy=None) -> CaseProgram:
    """The ``tpu_aot.py`` tp=4 serving acceptance programs, traced over
    a deviceless ``AbstractMesh`` at the REAL acceptance shape (the IR
    registry's tp2 twins run lint-scale pools; the fit proof needs the
    18 GiB-unsharded one). Shape constants and config come from
    ``tpu_aot`` so the static and AOT sides cannot drift apart."""
    import jax
    import jax.numpy as jnp
    import tpu_aot

    from apex_tpu.serving.scheduler import prompt_bucket
    from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                     abstract_tp_mesh,
                                     infer_variable_specs)
    from jax.sharding import PartitionSpec as P

    tp = tpu_aot.TP_SERVING_TP
    cfg = tpu_aot.tp_serving_config(weight_policy=weight_policy)
    engine = TensorParallelPagedEngine(
        model=__import__("apex_tpu.models.gpt",
                         fromlist=["GPTModel"]).GPTModel(cfg),
        variables=None, mesh=abstract_tp_mesh(tp),
        num_slots=tpu_aot.TP_SERVING_SLOTS,
        page_size=tpu_aot.TP_SERVING_PAGE_SIZE,
        max_pages_per_seq=tpu_aot.TP_SERVING_MAX_PAGES_PER_SEQ,
        sync_every=4)
    dvars, var_specs = infer_variable_specs(engine.model)
    i32 = jnp.int32
    meta = {"mesh_axes": {"model": tp}}
    n = tpu_aot.TP_SERVING_SLOTS
    if kind == "decode":
        args = (engine.cache, dvars,
                jax.ShapeDtypeStruct((n,), i32),
                jax.ShapeDtypeStruct((n,), jnp.bool_),
                jax.ShapeDtypeStruct((n,), i32),
                jax.ShapeDtypeStruct((n, 2), jnp.uint32),
                jax.ShapeDtypeStruct((n,), i32))
        meta["arg_specs"] = (engine._cache_specs, var_specs,
                             P(), P(), P(), P(), P())
        # the pool updates in place in production (tpu_aot donates arg
        # 0 the same way) — without the alias credit no 16 GiB chip
        # holds a >4 GiB-sharded double-buffered program
        return CaseProgram(fn=engine._step_fn(), args=args, donate=(0,),
                           meta=meta)
    assert kind == "admit"
    bucket = prompt_bucket(128, engine.page_size,
                           cfg.max_position_embeddings)
    args = (engine.cache, dvars,
            jax.ShapeDtypeStruct((1, bucket), i32),
            jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((), i32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), i32))
    meta["arg_specs"] = (engine._cache_specs, var_specs,
                         P(), P(), P(), P(), P(), P())
    return CaseProgram(fn=engine._admit_fn(bucket), args=args,
                       donate=(0,), meta=meta)


def acceptance_cases(root: Path) -> List[AnalysisCase]:
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    return [
        AnalysisCase("tp4_serving_admit", "serving",
                     lambda: _build_tp4_acceptance("admit")),
        AnalysisCase("tp4_serving_decode_chunk", "serving",
                     lambda: _build_tp4_acceptance("decode")),
        AnalysisCase("tp4_serving_decode_w8", "serving",
                     lambda: _build_tp4_acceptance(
                         "decode", weight_policy="int8")),
    ]


def mem_cases(root) -> List[AnalysisCase]:
    """The mem tier's registry: every IR-harness case plus the AOT
    acceptance meshes."""
    root = Path(root).resolve()
    return list(analysis_cases(root)) + acceptance_cases(root)


def acceptance_estimates(root) -> Dict[str, MemEstimate]:
    """``{aot_case_name: MemEstimate}`` for the tp4 acceptance
    programs — what ``tests/test_aot_mosaic.py`` bands against
    ``compiled.memory_analysis()``."""
    root = Path(root).resolve()
    out: Dict[str, MemEstimate] = {}
    for case in acceptance_cases(root):
        out[ACCEPTANCE_TO_AOT[case.name]] = estimate_case(
            build_case_ir(case))
    return out


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

def findings_for_mem_case(ir: CaseIR, root: Path,
                          select: Optional[Iterable[str]] = None
                          ) -> List[Finding]:
    """Estimate + run the (selected) mem rules over one traced case."""
    chosen = set(select) if select is not None else set(MEM_RULES)
    try:
        est = estimate_case(ir)
        budget, label = hbm_budget(ir.prog)
        ctx = MemContext(ir=ir, est=est, budget_bytes=budget,
                         budget_label=label)
    except Exception as e:          # noqa: BLE001 — findings, not crashes
        anchor = _case_anchor(ir, root)
        return [Finding(
            rule="mem-trace-error", severity="error", path=anchor[0],
            line=anchor[1], col=1, scope=ir.name,
            message=f"[case {ir.name}] failed to estimate: "
                    f"{type(e).__name__}: {e}")]
    out: List[Finding] = []
    for name in sorted(chosen):
        rule = MEM_RULES[name]
        for raw in rule.check(ctx):
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


def analyze_mem(root, *, select: Optional[Iterable[str]] = None,
                case: Optional[str] = None,
                ) -> Tuple[List[Finding], int, int]:
    """Trace the mem registry and run the fit proofs; returns
    ``(findings, #suppressed, #cases)`` — the same contract as
    ``analyze_ir``."""
    root = Path(root).resolve()
    if select is not None:
        unknown = set(select) - set(MEM_RULES)
        if unknown:
            raise ValueError(
                f"unknown mem rule(s): {', '.join(sorted(unknown))}")
    try:
        cases = mem_cases(root)
    except Exception as e:          # noqa: BLE001 — findings, not crashes
        return ([Finding(
            rule="mem-trace-error", severity="error", path="tpu_aot.py",
            line=1, col=1, scope="<registry>",
            message=f"failed to build the mem case registry: "
                    f"{type(e).__name__}: {e}")], 0, 0)
    if case is not None:
        cases = [c for c in cases if c.name == case]
        if not cases:
            raise ValueError(f"unknown mem case: {case}")
    supp = _SuppressionCache(root)
    findings: List[Finding] = []
    suppressed = 0
    for c in cases:
        try:
            ir = build_case_ir(c)
        except Exception as e:      # noqa: BLE001 — findings, not crashes
            findings.append(Finding(
                rule="mem-trace-error", severity="error",
                path="apex_tpu/analysis/mem/mem_report.py", line=1,
                col=1, scope=c.name,
                message=f"[case {c.name}] failed to trace: "
                        f"{type(e).__name__}: {e}"))
            continue
        for f in findings_for_mem_case(ir, root, select):
            if supp.get(f.path).covers(f):
                suppressed += 1
            else:
                findings.append(f)
    return findings, suppressed, len(cases)
