"""tpu-lint tier 5: wire & observability contract analysis.

The stack's remaining un-proved surface is string-keyed: metric
families, event kinds, HTTP routes, SSE frame kinds, ``apex-tpu/*``
schema pins, report field pins. Producers and
consumers of those names live in different files (and two of the
consumers are not even python — the docs catalogs and the golden
Prometheus exposition), so no per-module check can see them drift.
This tier builds one repo-wide :class:`~apex_tpu.analysis.contract.
extract.ContractIndex` and proves both directions of each contract
with the ``contract-*`` rules — stdlib ``ast`` plus text parsing, no
TPU, no network, same CLI/suppression/baseline/diff conventions as
tiers 1–4 (``python -m apex_tpu.analysis --contract``).
"""

from apex_tpu.analysis.contract.contract_rules import (CONTRACT_RULES,
                                                       ContractRule)
from apex_tpu.analysis.contract.contract_report import (
    TEXT_SURFACE, TextSuppressions, analyze_contract,
    analyze_contract_sources, build_contract_index, read_text_surface,
    split_surface)
from apex_tpu.analysis.contract.extract import ContractIndex, build_index

__all__ = [
    "CONTRACT_RULES", "ContractRule", "ContractIndex", "TEXT_SURFACE",
    "TextSuppressions", "analyze_contract", "analyze_contract_sources",
    "build_contract_index", "build_index", "read_text_surface",
    "split_surface",
]
