"""tpu-lint IR tier: jaxpr-level semantic analysis of real entry points.

The AST tier (``apex_tpu.analysis.rules``) lints what the source says;
this tier lints what JAX stages. ``harness.analysis_cases()`` discovers
traceable entry points (every ``tpu_aot.kernel_cases()`` program plus
the serving engine's decode chunk and bucketed admission), builds their
jaxprs on CPU (``jax.make_jaxpr`` over ``ShapeDtypeStruct`` args — no
TPU, no compile), ``ir_rules`` checks them (dtype promotion drift, dead
outputs, ineffective donation, large closed-over
constants, broadcast blowup, effectful primitives in scan bodies,
compile-key cardinality, minor-dim transposes feeding Pallas), and
``ir_report`` maps every finding back to source via ``eqn.source_info``
— file:line-addressable and suppressible with the ordinary
``# tpu-lint: disable=RULE`` pragma.

Usage::

    python -m apex_tpu.analysis --ir              # the whole registry
    python -m apex_tpu.analysis --ir-case NAME    # one entry point
    python -m apex_tpu.analysis --ir --select ir-dead-output
"""

from apex_tpu.analysis.ir.harness import (AnalysisCase, CaseIR,
                                          CaseProgram, analysis_cases,
                                          build_case_ir)
from apex_tpu.analysis.ir.ir_report import analyze_ir, findings_for_case
from apex_tpu.analysis.ir.ir_rules import IR_RULES

__all__ = [
    "AnalysisCase",
    "CaseIR",
    "CaseProgram",
    "IR_RULES",
    "analysis_cases",
    "analyze_ir",
    "build_case_ir",
    "findings_for_case",
]
