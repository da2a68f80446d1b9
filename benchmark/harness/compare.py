"""The comparisons that decide ``correct``: each number beside its limit.

Training: per step the relative gap of the loss; by the worst leaf the gap
between the program's norm and the reference's (not the norm of their
difference) for the first gradient as the optimizer got it and for the
parameters' change after the followed steps, each measured against the
reference's norm of that leaf or of the median leaf, whichever is larger;
for the gradient also the 95th-percentile leaf's gap, the number that
separates bfloat16 from the float8 control at 8 rows a step, and for the
change the median moving leaf's gap, which separates them at 32 rows.
Leaves whose reference gradient is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of the change.

Serving: the widest gap, over the served tokens of the sampled requests, by
which the served token's reference logit lies below the reference's best.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Sequence

ZERO_GRADIENT_SHARE = 1e-3


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              leaves: Sequence[str] = None) -> Dict[str, float]:
    """Per leaf: |program's norm - reference's norm| over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    if set(program) != set(reference):
        raise KeyError(
            f"leaves differ: {sorted(set(program) ^ set(reference))[:6]}")
    floor = statistics.median(reference.values())
    return {name: abs(program[name] - reference[name])
            / max(reference[name], floor)
            for name in (leaves if leaves is not None else reference)}


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   leaves: Sequence[str] = None) -> tuple:
    """(gap, leaf) of the worst leaf; a NaN counts as the worst."""
    worst, where = 0.0, ""
    for name, gap in leaf_gaps(program, reference, leaves).items():
        if not gap <= worst:
            worst, where = gap, name
    return worst, where


def quantile_leaf_gap(program: Dict[str, float],
                      reference: Dict[str, float], q: float,
                      leaves: Sequence[str] = None) -> float:
    """The gap of the leaf at quantile ``q`` of the leaves' gaps (nearest
    rank): the worst leaf but for the noisiest ``1 - q`` of them.  Steady
    from seed to seed where the worst leaf's gap is the noise of one small
    leaf (see PERF.md, Findings)."""
    ordered = sorted(leaf_gaps(program, reference, leaves).values())
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def moving_leaves(reference_grad_norms: Dict[str, float]) -> list:
    floor = ZERO_GRADIENT_SHARE * statistics.median(
        reference_grad_norms.values())
    return [k for k, g in reference_grad_norms.items() if g >= floor]


def train_numbers(program: dict, reference: dict) -> Dict[str, float]:
    """``program`` and ``reference`` hold ``losses``, ``grad_norms`` and
    ``change_norms`` of the same steps."""
    out = {}
    for n, (a, b) in enumerate(zip(program["losses"], reference["losses"]),
                               start=1):
        out[f"loss{n}_gap"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    moving = moving_leaves(reference["grad_norms"])
    out["change_norm_gap"], out["change_norm_leaf"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], moving)
    out["grad_norm_p95_gap"] = quantile_leaf_gap(
        program["grad_norms"], reference["grad_norms"], 0.95)
    out["change_norm_p50_gap"] = quantile_leaf_gap(
        program["change_norms"], reference["change_norms"], 0.5, moving)
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> tuple:
    """(correct, checks) where checks is ``{name: {"value", "limit"}}`` for
    every number that has a limit.  A number that is not finite fails."""
    checks = {}
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"no number {name!r} to hold to its limit")
        checks[name] = {"value": numbers[name], "limit": limit}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
