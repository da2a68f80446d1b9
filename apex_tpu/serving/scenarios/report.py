"""The pinned-schema ScenarioReport: per-tenant + aggregate SLO stats.

One scenario run produces one report dict with a FIXED shape (CI and
the tests key into it — ``validate_report`` is the contract check).
Latency percentiles are computed from the span tracer's
per-request lifecycles (docs/observability.md) — exact percentiles over
this run's requests, the same source the frontend's run stats use — so
the per-tenant splits and the aggregate are consistent by construction.
Engine counters (hit rate, preemptions, evictions, window drops) come
from the frontend's ``stats()`` delta dict and are embedded verbatim
under ``engine`` for postmortems.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["REPORT_SCHEMA", "SCENARIOS_SCHEMA", "AGGREGATE_FIELDS",
           "TENANT_FIELDS", "ROUTER_FIELDS", "HTTP_FIELDS",
           "HOST_TIER_FIELDS", "FLEET_FIELDS", "build_report",
           "validate_report"]

REPORT_SCHEMA = "apex-tpu/scenario-report/v1"
#: the multi-scenario CLI document wrapping one report per scenario.
#: Write-only CI evidence like the ``--fleet`` sidecar below: each report
#: inside it carries ``REPORT_SCHEMA``, which ``validate_report`` reads.
# tpu-lint: disable=contract-schema-unpinned -- write-only CI evidence
SCENARIOS_SCHEMA = "apex-tpu/scenarios/v1"
#: the ``--fleet`` sidecar document (per-scenario federated fleet
#: blocks). Write-only CI evidence — banked per round for human review,
#: nothing in-repo reads it back, hence no paired validator.
# tpu-lint: disable=contract-schema-unpinned -- write-only CI evidence
FLEET_DOC_SCHEMA = "apex-tpu/fleet/v1"

#: pinned aggregate keys — every report carries exactly these
AGGREGATE_FIELDS = (
    "ttft_ms_p50", "ttft_ms_p95", "tpot_ms_p50", "tpot_ms_p95",
    "queue_wait_ms_p50", "queue_wait_ms_p95",
    "deadline_requests", "deadline_misses", "deadline_miss_rate",
    "tpot_slo_misses", "preemptions", "resumes",
    "prefix_hit_rate", "prefill_tokens_skipped", "evicted_pages",
    "window_dropped_pages", "generated_tokens", "tokens_per_sec",
    "duration_s",
)

#: pinned per-tenant keys
TENANT_FIELDS = (
    "requests", "generated_tokens",
    "ttft_ms_p50", "ttft_ms_p95", "tpot_ms_p50", "tpot_ms_p95",
    "queue_wait_ms_p50", "queue_wait_ms_p95",
    "deadline_requests", "deadline_misses", "deadline_miss_rate",
)

#: pinned ``router`` block keys (present on replicated scenarios only;
#: the A/B keys ``round_robin_hit_rate``/``affinity_delta_hit_rate``
#: appear additionally under ``compare_round_robin``)
ROUTER_FIELDS = (
    "replicas", "replicas_alive", "routing",
    "failovers", "failover_requests", "failover_recovered",
    "failover_recovered_rate", "shed_requests", "migrations",
    "replica_deaths", "affinity_hit_rate",
)

#: pinned ``host_tier`` block keys (present on tiered scenarios only —
#: ``EngineSpec(host_tier_bytes > 0)``; the A/B keys come from the same
#: trace re-replayed with the tier off, docs/serving.md "Tiered KV
#: pool")
HOST_TIER_FIELDS = (
    "budget_bytes", "demotes", "promotes", "host_evicted_pages",
    "promote_hit_rate", "tier_on_hit_rate", "tier_off_hit_rate",
    "tier_delta_hit_rate",
)

#: pinned ``fleet`` block keys (present on replicated scenarios — the
#: router's federated observability block, ``router.fleet.block()``;
#: docs/observability.md "Fleet plane")
FLEET_FIELDS = (
    "replicas", "ttft_ms_p95", "tpot_ms_p95", "queue_depth",
    "slo_burn", "compile_storms", "scrape_age_s_max",
    "alerts_fired", "alert_firing", "per_replica",
)

#: pinned ``http`` block keys (present when the scenario replayed over
#: the wire — ``EngineSpec(http=True)``, scenarios/http_driver.py)
HTTP_FIELDS = (
    "streams", "tokens", "disconnects", "rejected", "errors",
    "conn_reset_retries", "slow_reader_stalls",
    "backpressure_spills", "free_pages_recovered",
)


def _pct(vals: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if len(vals) else 0.0


def _latency_block(lifes: List[dict], missed: Dict[int, bool],
                   deadlined: Dict[int, bool]) -> dict:
    ttft = [lf["ttft_ms"] for lf in lifes if "ttft_ms" in lf]
    tpot = [lf["tpot_ms"] for lf in lifes if "tpot_ms" in lf]
    qw = [lf["queue_wait_ms"] for lf in lifes if "queue_wait_ms" in lf]
    n_dl = sum(1 for lf in lifes if deadlined.get(lf["request_id"]))
    n_miss = sum(1 for lf in lifes if missed.get(lf["request_id"]))
    return {
        "ttft_ms_p50": round(_pct(ttft, 50), 3),
        "ttft_ms_p95": round(_pct(ttft, 95), 3),
        "tpot_ms_p50": round(_pct(tpot, 50), 3),
        "tpot_ms_p95": round(_pct(tpot, 95), 3),
        "queue_wait_ms_p50": round(_pct(qw, 50), 3),
        "queue_wait_ms_p95": round(_pct(qw, 95), 3),
        "deadline_requests": n_dl,
        "deadline_misses": n_miss,
        "deadline_miss_rate": round(n_miss / max(n_dl, 1), 4),
    }


def build_report(spec, trace, outputs, stats: dict, tracer,
                 wall_s: float, checks: Optional[dict] = None,
                 router: Optional[dict] = None,
                 http: Optional[dict] = None,
                 host_tier: Optional[dict] = None,
                 fleet: Optional[dict] = None) -> dict:
    """Assemble the pinned-schema report for one replayed scenario.
    ``router`` is the replicated-scenario block (``ROUTER_FIELDS``) —
    failover/recovery facts and the affinity A/B; ``http`` the
    over-the-wire replay's block (``HTTP_FIELDS``); ``host_tier`` the
    tiered-pool block (``HOST_TIER_FIELDS``) — demote/promote facts and
    the tier-on/off A/B; ``fleet`` the router's federated
    observability block (``FLEET_FIELDS``, ``router.fleet.block()``);
    ``tracer`` may be the router's cross-replica
    lifecycle adapter (same ``lifecycle``/``spans`` surface as a
    :class:`~apex_tpu.obs.spans.SpanTracer`)."""
    events = trace.events
    lifes = [tracer.lifecycle(e.request_id) for e in events]
    # per-request deadline facts: carried by the trace (who had one) and
    # the tracer's deadline_miss instants (who missed it)
    deadlined = {e.request_id: e.deadline_ms is not None for e in events}
    missed = {e.request_id: any(s.name == "deadline_miss"
                                for s in tracer.spans(e.request_id))
              for e in events}
    gen_total = int(sum(np.asarray(o).shape[0] for o in outputs))

    aggregate = _latency_block(lifes, missed, deadlined)
    aggregate.update({
        "tpot_slo_misses": int(stats.get("tpot_slo_misses", 0)),
        "preemptions": int(stats.get("preemptions", 0)),
        "resumes": int(stats.get("resumes", 0)),
        "prefix_hit_rate": round(float(stats.get("prefix_hit_rate",
                                                 0.0)), 4),
        "prefill_tokens_skipped": int(stats.get("prefill_tokens_skipped",
                                                0)),
        "evicted_pages": int(stats.get("evicted_pages", 0)),
        "window_dropped_pages": int(stats.get("window_dropped_pages",
                                              0)),
        "generated_tokens": gen_total,
        "tokens_per_sec": round(gen_total / max(wall_s, 1e-9), 1),
        "duration_s": round(wall_s, 4),
    })

    per_tenant: Dict[str, dict] = {}
    for name in sorted({e.tenant for e in events}):
        ids = {e.request_id for e in events if e.tenant == name}
        t_lifes = [lf for lf in lifes if lf["request_id"] in ids]
        block = _latency_block(t_lifes, missed, deadlined)
        block["requests"] = len(ids)
        block["generated_tokens"] = int(sum(
            np.asarray(outputs[i]).shape[0] for i in range(len(events))
            if events[i].request_id in ids))
        per_tenant[name] = block

    report = {
        "schema": REPORT_SCHEMA,
        "scenario": spec.name,
        "seed": spec.seed,
        "model": spec.engine.model,
        "n_requests": len(events),
        "n_tenants": len(per_tenant),
        "trace_sha256": trace.sha256(),
        "aggregate": aggregate,
        "per_tenant": per_tenant,
        "engine": {k: v for k, v in sorted(stats.items())},
    }
    if router is not None:
        report["router"] = dict(router)
    if fleet is not None:
        report["fleet"] = dict(fleet)
    if http is not None:
        report["http"] = dict(http)
    if host_tier is not None:
        report["host_tier"] = dict(host_tier)
    if checks is not None:
        report["checks"] = dict(checks)
    return report


def validate_report(report: dict) -> None:
    """The schema pin: raise ``ValueError`` on any missing key (CI's
    smoke and the tests call this so readers can rely on the shape)."""
    for key in ("schema", "scenario", "seed", "model", "n_requests",
                "n_tenants", "trace_sha256", "aggregate", "per_tenant",
                "engine"):
        if key not in report:
            raise ValueError(f"scenario report missing {key!r}")
    if report["schema"] != REPORT_SCHEMA:
        raise ValueError(f"unexpected report schema "
                         f"{report['schema']!r} != {REPORT_SCHEMA!r}")
    missing = [f for f in AGGREGATE_FIELDS
               if f not in report["aggregate"]]
    if missing:
        raise ValueError(f"aggregate block missing {missing}")
    for name, block in report["per_tenant"].items():
        t_missing = [f for f in TENANT_FIELDS if f not in block]
        if t_missing:
            raise ValueError(f"tenant {name!r} block missing "
                             f"{t_missing}")
    if "router" in report:
        r_missing = [f for f in ROUTER_FIELDS
                     if f not in report["router"]]
        if r_missing:
            raise ValueError(f"router block missing {r_missing}")
    if "fleet" in report:
        f_missing = [f for f in FLEET_FIELDS
                     if f not in report["fleet"]]
        if f_missing:
            raise ValueError(f"fleet block missing {f_missing}")
    if "http" in report:
        h_missing = [f for f in HTTP_FIELDS
                     if f not in report["http"]]
        if h_missing:
            raise ValueError(f"http block missing {h_missing}")
    if "host_tier" in report:
        ht_missing = [f for f in HOST_TIER_FIELDS
                      if f not in report["host_tier"]]
        if ht_missing:
            raise ValueError(f"host_tier block missing {ht_missing}")
