"""Cost ledger + regression gate (``COST_LEDGER.jsonl``).

The repo's own record of what its programs are modelled to cost. (The
file ``PERF_LEDGER.jsonl`` at the repo root is the driver's record of
measured runs; nothing here reads or writes it.)

- **Trajectory**: ``--append`` adds one JSON line per source — the
  deviceless cost-model rollups (``obs/costs.py``, deterministic on
  CPU), and the fields of a bench/scenario artifact when ``--bench``
  names one — each stamped with git rev + timestamp.
- **Gate**: ``python -m apex_tpu.obs.ledger --check`` recomputes HEAD's
  metrics and compares them against the most recent ledger values.
  Deterministic ``cost.*`` metrics must match EXACTLY (they only change
  when the staged programs change — which is precisely what a reviewer
  must see); wall-time metrics get a tolerance band (default ±20 %),
  direction-aware (throughput may rise freely, latency may fall
  freely). Exit 1 on regression/drift, 2 on a broken ledger.

The ratchet workflow mirrors tpu-lint's baseline: an intentional
cost-model change fails ``--check`` until the author runs
``python -m apex_tpu.obs.ledger --append`` and commits the new entry —
the perf delta is then an explicit, reviewable line in the PR.

Entry format (one JSON object per line)::

    {"schema": 1, "kind": "cost"|"bench"|"seed", "tag": "r06",
     "git_rev": "<sha>[-dirty]", "time_unix": 1699...,
     "metrics": {"cost.total_flops": ..., ...}, "meta": {...}}
"""

from __future__ import annotations

import dataclasses
import glob as _glob
import json
import os
import subprocess
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["LEDGER_NAME", "load", "append_entry", "head_cost_metrics",
           "bench_metrics_from_file", "check", "main"]

LEDGER_NAME = "COST_LEDGER.jsonl"

#: substrings classifying a wall-time metric's good direction; anything
#: matching neither is recorded but not gated (informational counters)
_HIGHER_BETTER = ("tokens_per_sec", "_per_sec", "hit_rate", "step_savings",
                  "speedup", "recovered_rate")
_LOWER_BETTER = ("_ms", "misses", "miss_rate", "bubble")

#: [0, 1] ratios with small integer denominators (one request flipping a
#: ~8-deadline scenario moves miss_rate by 0.125 — a relative ±20 % band
#: would flag scheduling noise as a regression): gate on ABSOLUTE
#: worsening beyond this instead
_RATE_SUFFIXES = ("miss_rate", "hit_rate", "recovered_rate")
_RATE_ABS_TOL = 0.25


def _git_rev(root: Path) -> str:
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10).stdout.strip()
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root,
            capture_output=True, text=True, timeout=10).stdout.strip()
        return (rev + "-dirty") if dirty else rev or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"        # the ledger works without git


# --------------------------------------------------------------------------
# storage
# --------------------------------------------------------------------------

def load(path) -> List[dict]:
    """Parse the ledger; raises ValueError on a corrupt line (a broken
    trajectory should fail loudly, not truncate silently)."""
    entries = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                entry = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(
                    f"{path}:{i}: corrupt ledger line: {e}") from e
            if not isinstance(entry, dict) or "metrics" not in entry:
                raise ValueError(
                    f"{path}:{i}: ledger entry without metrics")
            entries.append(entry)
    return entries


def append_entry(path, *, kind: str, tag: str,
                 metrics: Dict[str, float], root=None,
                 meta: Optional[dict] = None,
                 when: Optional[float] = None) -> dict:
    entry = {
        "schema": 1, "kind": kind, "tag": tag,
        "git_rev": _git_rev(Path(root) if root else Path(path).parent),
        "time_unix": round(when if when is not None else time.time(), 3),
        "metrics": {k: metrics[k] for k in sorted(metrics)},
    }
    if meta:
        entry["meta"] = meta
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")
    return entry


# --------------------------------------------------------------------------
# metric sources
# --------------------------------------------------------------------------

def head_cost_metrics(root, *, costs_json: Optional[str] = None,
                      profile: str = "v5e") -> Dict[str, float]:
    """HEAD's deterministic cost metrics — from a pre-computed
    ``--json`` report when given, else by tracing the registry now
    (~15 s on CPU)."""
    from apex_tpu.obs import costs

    if costs_json:
        with open(costs_json) as f:
            report = json.load(f)
    else:
        report = costs.cost_report(root, profile=profile)
    if report.get("errors"):
        raise RuntimeError(
            "cost report has trace errors; fix those before gating: "
            + "; ".join(e["case"] for e in report["errors"]))
    return costs.ledger_metrics(report)


#: per-scenario SLO fields extracted from a SCENARIOS_<tag>.json doc
#: (``python -m apex_tpu.serving.scenarios --json``) as
#: ``scenario.<name>.<field>`` — each matches a direction class below
#: (``_ms`` relative band / ``miss_rate`` absolute ±``_RATE_ABS_TOL``)
_SCENARIO_FIELDS = ("ttft_ms_p95", "tpot_ms_p95", "deadline_miss_rate")

#: per-scenario ROUTER fields (the replicated-serving chaos/A-B tier,
#: docs/router.md): extracted from a report's ``router`` block as
#: ``scenario.<name>.<field>``. ``failover_recovered_rate`` and the
#: hit-rate pair gate on the absolute rate band; the delta is the
#: affinity-beats-round-robin proof (higher-better, rate band)
_SCENARIO_ROUTER_FIELDS = (
    "failover_recovered_rate",
    "affinity_hit_rate",
    # the A/B pair lives under the report's ``compare_round_robin``
    # sub-block, not the pinned ``ROUTER_FIELDS`` top level — the
    # extractor reads the merged block the scenario runner flattens
    # tpu-lint: disable=contract-ledger-class-drift -- A/B keys, see above
    "round_robin_hit_rate",
    # tpu-lint: disable=contract-ledger-class-drift -- A/B keys, see above
    "affinity_delta_hit_rate",
)

#: per-scenario HOST-TIER fields (the tiered KV pool's churn A/B,
#: docs/serving.md "Tiered KV pool"): extracted from a report's
#: ``host_tier`` block as ``scenario.<name>.<field>``. The hit-rate
#: trio and ``promote_hit_rate`` gate on the absolute rate band as
#: higher-better; ``tier_delta_hit_rate`` is the tier-beats-reprefill
#: proof (strictly positive at a thrash-sized pool)
_SCENARIO_HOST_TIER_FIELDS = ("tier_on_hit_rate", "tier_off_hit_rate",
                              "tier_delta_hit_rate", "promote_hit_rate")

#: per-scenario FLEET fields (the federated observability plane,
#: docs/observability.md "Fleet plane"): extracted from a report's
#: ``fleet`` block as ``scenario.<name>.fleet_<field>``. The latency
#: aggregates band-gate as ``_ms`` lower-better; the rest are
#: informational counters banked so the alerting/federation trajectory
#: stays reviewable per round
_SCENARIO_FLEET_FIELDS = (
    "ttft_ms_p95", "tpot_ms_p95",
    # the rest are deliberately informational (no gating class): raw
    # counters/levels whose healthy values depend on the scenario's
    # chaos schedule — banked for trajectory review, never gated
    # tpu-lint: disable=contract-ledger-class-drift -- informational, see above
    "queue_depth",
    # tpu-lint: disable=contract-ledger-class-drift -- informational counter
    "slo_burn", "compile_storms",
    # tpu-lint: disable=contract-ledger-class-drift -- informational counter
    "alerts_fired",
)

#: per-scenario HTTP fields (the over-the-wire chaos tier,
#: docs/http.md): extracted from a report's ``http`` block as
#: ``scenario.<name>.http_<field>``. Counters, so informational —
#: recorded in the banked trajectory (the spill/disconnect proof stays
#: reviewable per round) while the scenario's SLO percentiles above do
#: the band-gating
#: all five are chaos-schedule-shaped counters: informational by
#: design (the scenario's SLO percentiles do the band-gating) — banked
#: so the spill/disconnect proof stays reviewable per round
_SCENARIO_HTTP_FIELDS = (
    # tpu-lint: disable=contract-ledger-class-drift -- informational, see above
    "backpressure_spills", "disconnects",
    # tpu-lint: disable=contract-ledger-class-drift -- informational, see above
    "conn_reset_retries", "slow_reader_stalls",
    # tpu-lint: disable=contract-ledger-class-drift -- informational, see above
    "errors",
)

#: numeric bench-record fields worth tracking besides the headline value
_BENCH_FIELDS = (
    "step_ms", "int8_speedup", "step_savings",
    "gpt2_paged_decode_ttft_ms_p50", "gpt2_paged_decode_ttft_ms_p95",
    "decode_step_ms_p50", "decode_step_ms_p95",
    "gpt2_tp2_paged_decode_ttft_ms_p50",
    "gpt2_tp2_paged_decode_ttft_ms_p95",
    "gpt2_tp2_paged_decode_tpot_ms_p50",
    "gpt2_tp2_paged_decode_tpot_ms_p95",
    "gpt2_frontend_ttft_ms_p50", "gpt2_frontend_ttft_ms_p95",
    "gpt2_frontend_tpot_ms_p50", "gpt2_frontend_tpot_ms_p95",
    "gpt2_frontend_deadline_miss_rate", "prefix_hit_rate",
    "pump.bubble_ms",
    # tpu-lint: disable=contract-ledger-class-drift -- recompile count: trajectory only
    "jit.compiles",
    # ISSUE 13: in-engine speculative decode + chunked-prefill TTFT
    # tpu-lint: disable=contract-ledger-class-drift -- acceptance length: trajectory only
    "mean_acceptance_len",
    "gpt2_frontend_chunked_ttft_ms_p50", "gpt2_frontend_chunked_ttft_ms_p95",
    "gpt2_frontend_monolithic_ttft_ms_p50",
    "gpt2_frontend_monolithic_ttft_ms_p95",
    # ISSUE 16: quantized weight streaming (int8 policy, fused dequant)
    "gpt2_w8_paged_decode_ttft_ms_p50", "gpt2_w8_paged_decode_ttft_ms_p95",
    # tpu-lint: disable=contract-ledger-class-drift -- compression ratio: trajectory only
    "weight_bytes_ratio_vs_fp",
    # ISSUE 17: tiered KV pool (host-RAM spill under the device pool)
    # tpu-lint: disable=contract-ledger-class-drift -- churn counters: trajectory only
    "host_tier_demotes", "host_tier_promotes",
    "host_tier_promote_hit_rate",
)


def _scenario_metrics(doc: dict) -> Dict[str, float]:
    """Flatten a scenarios document's aggregate SLO fields into ledger
    metrics (``scenario.<name>.ttft_ms_p95`` etc.)."""
    out: Dict[str, float] = {}
    for name, rep in sorted(doc.get("scenarios", {}).items()):
        agg = rep.get("aggregate", {}) if isinstance(rep, dict) else {}
        for field in _SCENARIO_FIELDS:
            v = agg.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"scenario.{name}.{field}"] = float(v)
        router = rep.get("router", {}) if isinstance(rep, dict) else {}
        for field in _SCENARIO_ROUTER_FIELDS:
            v = router.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"scenario.{name}.{field}"] = float(v)
        tier = rep.get("host_tier", {}) if isinstance(rep, dict) else {}
        for field in _SCENARIO_HOST_TIER_FIELDS:
            v = tier.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"scenario.{name}.{field}"] = float(v)
        fleet = rep.get("fleet", {}) if isinstance(rep, dict) else {}
        for field in _SCENARIO_FLEET_FIELDS:
            v = fleet.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"scenario.{name}.fleet_{field}"] = float(v)
        http = rep.get("http", {}) if isinstance(rep, dict) else {}
        for field in _SCENARIO_HTTP_FIELDS:
            v = http.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"scenario.{name}.http_{field}"] = float(v)
    return out


def bench_metrics_from_file(path) -> Tuple[Dict[str, float], dict]:
    """Extract (metrics, meta) from a bench artifact. Accepts the
    driver's wrapper shape (``BENCH_r0*.json``: one object with a
    ``parsed`` record), a bare record, JSONL of records
    (``DECODE_*.json``), or a scenarios document
    (``SCENARIOS_*.json`` — per-scenario SLO fields, see
    ``_SCENARIO_FIELDS``)."""
    text = Path(path).read_text().strip()
    records: List[dict] = []
    meta: dict = {"source": os.path.basename(str(path))}
    try:
        doc = json.loads(text)
        if (isinstance(doc, dict)
                and str(doc.get("schema", "")).startswith(
                    "apex-tpu/scenarios")):
            meta["schema"] = doc["schema"]
            return _scenario_metrics(doc), meta
        if isinstance(doc, dict) and "parsed" in doc:
            meta["rc"] = doc.get("rc")
            if isinstance(doc.get("parsed"), dict):
                records = [doc["parsed"]]
        elif isinstance(doc, dict):
            records = [doc]
    except json.JSONDecodeError:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(rec, dict) and "metric" in rec:
                records.append(rec)
    out: Dict[str, float] = {}
    errors = []
    for rec in records:
        name = rec.get("metric")
        if name and isinstance(rec.get("value"), (int, float)):
            out[name] = float(rec["value"])
        if rec.get("error"):
            errors.append(str(rec["error"])[:200])
        for field in _BENCH_FIELDS:
            v = rec.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[field] = float(v)
    if errors:
        meta["errors"] = errors
    return out, meta


# --------------------------------------------------------------------------
# the gate
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Regression:
    metric: str
    baseline: float
    head: float
    kind: str                         # "exact-drift" | "band"
    baseline_tag: str

    def __str__(self):
        return (f"{self.metric}: {self.baseline} -> {self.head} "
                f"[{self.kind}, baseline {self.baseline_tag}]")


def _direction(name: str) -> Optional[str]:
    if any(s in name for s in _HIGHER_BETTER):
        return "higher"
    if any(s in name for s in _LOWER_BETTER):
        return "lower"
    return None


def check(head: Dict[str, float], entries: List[dict], *,
          band_pct: float = 20.0) -> List[Regression]:
    """Compare HEAD metrics against the most recent ledger value of
    EACH metric, scanning the whole history — cost entries append far
    more often than bench ones, so a fixed entry window would age the
    bench metrics out of the baseline and silently stop gating them. Only metrics present on BOTH sides gate — a newly
    added metric passes, a retired one is the next append's business."""
    baseline: Dict[str, Tuple[float, str]] = {}
    for entry in entries:            # oldest -> newest: newest wins
        tag = f"{entry.get('tag', '?')}@{entry.get('git_rev', '?')[:12]}"
        for name, value in entry.get("metrics", {}).items():
            if isinstance(value, (int, float)):
                baseline[name] = (float(value), tag)
    out: List[Regression] = []
    for name, head_v in sorted(head.items()):
        if name not in baseline:
            continue
        base_v, tag = baseline[name]
        if name.startswith("cost."):
            # deterministic: any drift is a (possibly intentional)
            # change that must be appended, i.e. reviewed
            if head_v != base_v:
                out.append(Regression(name, base_v, head_v,
                                      "exact-drift", tag))
            continue
        direction = _direction(name)
        if direction is None:
            continue                 # informational counter
        worse = (base_v - head_v) if direction == "higher" \
            else (head_v - base_v)
        if name.endswith(_RATE_SUFFIXES):
            # quantized [0,1] ratio: absolute tolerance, not relative —
            # and checked BEFORE the zero-baseline skip, because a 0.0
            # miss-rate baseline is a healthy perfect score that must
            # keep gating (the zero skip exists for dead-round seeds,
            # which record throughputs, not rates; a 0.0 higher-better
            # rate can never flag anyway since worse = -head <= 0)
            if worse > _RATE_ABS_TOL:
                out.append(Regression(name, base_v, head_v, "band", tag))
            continue
        if base_v == 0.0:
            continue                 # dead baseline (failed-round seed)
        if worse > abs(base_v) * band_pct / 100.0:
            out.append(Regression(name, base_v, head_v, "band", tag))
    return out


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _seed_history(root: Path, path: Path) -> int:
    """Backfill the ledger from banked bench artifacts under ``root``
    (``BENCH_r<N>.json`` wrappers; a failed run lands with value 0.0 and
    its error in meta). Idempotent: an artifact whose seed entry already
    exists is skipped, so re-running cannot duplicate the trajectory."""
    seeded = set()
    if path.exists():
        seeded = {(e.get("kind"), e.get("tag")) for e in load(path)}
    n = 0
    for bench in sorted(_glob.glob(str(root / "BENCH_r[0-9]*.json"))):
        base = os.path.basename(bench)
        tag = base[len("BENCH_"):].split(".")[0].split("_")[0]
        if ("seed", tag) in seeded:
            continue
        metrics, meta = bench_metrics_from_file(bench)
        if not metrics:
            continue
        append_entry(path, kind="seed", tag=tag, metrics=metrics,
                     root=root, meta=meta,
                     when=os.path.getmtime(bench))
        n += 1
    return n


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser(
        prog="python -m apex_tpu.obs.ledger",
        description="Perf ledger: append round entries, gate HEAD "
                    "against the trajectory (docs/observability.md)")
    parser.add_argument("--root", default=None)
    parser.add_argument("--ledger", default=None,
                        help=f"path (default <root>/{LEDGER_NAME})")
    parser.add_argument("--tag", default="head")
    parser.add_argument("--costs", default=None, metavar="JSON",
                        help="pre-computed obs.costs --json report")
    parser.add_argument("--bench", default=None, metavar="JSON",
                        help="bench/decode artifact to extract metrics "
                             "from")
    parser.add_argument("--profile", default="v5e")
    parser.add_argument("--band-pct", type=float, default=20.0)
    action = parser.add_mutually_exclusive_group(required=True)
    action.add_argument("--check", action="store_true",
                        help="exit 1 if HEAD regressed vs the ledger")
    action.add_argument("--append", action="store_true",
                        help="append HEAD's entry (cost metrics, plus "
                             "--bench fields when given)")
    action.add_argument("--seed-history", action="store_true",
                        help="backfill from banked BENCH_r0*.json")
    action.add_argument("--show", action="store_true")
    args = parser.parse_args(argv)

    root = Path(args.root).resolve() if args.root \
        else Path(__file__).resolve().parents[2]
    path = Path(args.ledger) if args.ledger else root / LEDGER_NAME

    if args.seed_history:
        n = _seed_history(root, path)
        print(f"[ledger] seeded {n} historical entries into {path}")
        return 0

    if args.show:
        try:
            entries = load(path)
        except (OSError, ValueError) as e:
            print(f"[ledger] {e}")
            return 2
        for entry in entries:
            named = entry.get("metrics", {})
            print(f"{entry.get('tag'):>6s} {entry.get('kind'):>5s} "
                  f"{entry.get('git_rev', '')[:12]:12s} "
                  f"{len(named)} metrics")
        return 0

    if args.append:
        try:
            if args.bench:
                metrics, meta = bench_metrics_from_file(args.bench)
                entry = append_entry(path, kind="bench", tag=args.tag,
                                     metrics=metrics, root=root,
                                     meta=meta)
            else:
                metrics = head_cost_metrics(root, costs_json=args.costs,
                                            profile=args.profile)
                entry = append_entry(path, kind="cost", tag=args.tag,
                                     metrics=metrics, root=root)
        except (OSError, ValueError, RuntimeError,
                json.JSONDecodeError) as e:
            print(f"[ledger] append failed: {e}")
            return 2
        print(f"[ledger] appended {entry['kind']} entry "
              f"({len(entry['metrics'])} metrics) as {entry['git_rev']}")
        return 0

    # --check
    if not path.exists():
        print(f"[ledger] {path} missing — the perf trajectory is empty. "
              f"Seed it: python -m apex_tpu.obs.ledger --seed-history "
              f"&& ... --append")
        return 2
    try:
        entries = load(path)
    except ValueError as e:
        print(f"[ledger] {e}")
        return 2
    if not entries:
        print(f"[ledger] {path} is empty — append an entry first")
        return 2
    try:
        head = head_cost_metrics(root, costs_json=args.costs,
                                 profile=args.profile)
        if args.bench:
            bench, _ = bench_metrics_from_file(args.bench)
            head.update(bench)
    except (OSError, ValueError, RuntimeError,
            json.JSONDecodeError) as e:
        print(f"[ledger] cannot compute HEAD metrics: {e}")
        return 2
    regressions = check(head, entries, band_pct=args.band_pct)
    if regressions:
        print(f"[ledger] {len(regressions)} regression(s) vs "
              f"{path.name}:")
        for r in regressions:
            print(f"  {r}")
        print("[ledger] if intentional, append + commit the new entry: "
              "python -m apex_tpu.obs.ledger --append --tag <tag>")
        return 1
    print(f"[ledger] OK — {len(head)} HEAD metrics checked against "
          f"{len(entries)} entries, no regressions")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
