"""tpu-lint mem tier: static memory-budget & sharding-contract analysis.

The fourth lint tier (``--mem``). The AST tier reads source, the IR
tier reads staged jaxprs, the conc tier reads the host side; this tier
proves MEMORY FIT — per-chip, before any compile, on any machine:

- **tiled-layout-aware peak HBM** (``layout.py`` + ``estimator.py``):
  a liveness sweep priced at TPU tile-padded sizes
  (minor dim -> 128 lanes, second-minor -> the dtype's sublane
  multiple), at LOCAL shard shapes inside shard_map, with each scan's
  carry double-buffered and donated buffers alias-credited — checked
  against one v5e chip's 16 GiB, or the case's declared budget;
- **per-``pallas_call`` VMEM** vs the 16 MiB scoped stack;
- **sharding contracts** over shard_map programs: divisibility,
  replicated-output honesty under ``check_vma=False``, donation spec
  aliasing, quantization-scale/weight co-sharding.

Eight rules (``mem_rules.py``), each mechanizing a lesson the repo paid
for on hardware or in a compile log — the PR 10 d=64 padding OOM and
pool double-buffering, the PR 14 VMEM block rejections, the PR 16
scale-sharding invariant.

Usage::

    python -m apex_tpu.analysis --mem
    python -m apex_tpu.analysis --mem --select mem-hbm-over-budget

Findings share the AST tier's suppression pragmas, baseline file
(tier-partitioned by the ``mem-`` prefix — ``analysis/tiers.py``), and
the ``--diff`` CI mode (the base side re-runs the tier in a temporary
worktree of the base rev).
"""

from apex_tpu.analysis.mem.estimator import (MemEstimate,  # noqa: F401
                                             VMEM_BUDGET_BYTES,
                                             estimate_case)
from apex_tpu.analysis.mem.layout import (sublane_multiple,  # noqa: F401
                                          tiled_padded_bytes)
from apex_tpu.analysis.mem.mem_report import (ACCEPTANCE_TO_AOT,  # noqa: F401
                                              acceptance_estimates,
                                              analyze_mem, hbm_budget,
                                              mem_cases)
from apex_tpu.analysis.mem.mem_rules import (MEM_RULES,  # noqa: F401
                                             MemContext)

__all__ = ["MEM_RULES", "MemContext", "MemEstimate",
           "VMEM_BUDGET_BYTES", "ACCEPTANCE_TO_AOT",
           "acceptance_estimates", "analyze_mem", "estimate_case",
           "hbm_budget", "mem_cases", "sublane_multiple",
           "tiled_padded_bytes"]
