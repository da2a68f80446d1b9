"""Scenario engine: trace-driven load generation + multi-tenant
prefix workloads over the serving stack (ROADMAP open item 5).

The bench layer used to hard-code two synthetic workloads; this package
makes workloads DECLARATIVE, SEEDED, and REPLAYABLE:

- :mod:`traces` — composable arrival processes (Poisson, bursty
  Markov-modulated on/off, closed-loop) and length distributions
  (lognormal / Zipf / uniform / fixed), all driven by one explicit seed;
  materialized traces round-trip through JSONL.
- :mod:`tenants` — N tenants with distinct system prompts and
  priority/deadline/TPOT-SLO profiles contending for one radix prefix
  cache, plus the adversarial eviction-churn tenant set.
- :mod:`runner` / :mod:`report` — open-loop replay through
  :class:`~apex_tpu.serving.frontend.ServingFrontend` into a
  pinned-schema per-tenant + aggregate SLO report; ``check=`` turns any
  scenario into a correctness amplifier (greedy token identity vs
  lock-step, scheduling invariance across chunk sizes).
- :mod:`library` — the named catalog (``steady-poisson``,
  ``burst-storm``, ``long-tail-lengths``,
  ``multi-tenant-shared-prefix``, ``eviction-churn``,
  ``priority-flood``, ``windowed-llama``, the two bench workloads, the
  ``preemption-storm`` adversary, and the replicated-serving tier:
  ``chaos-replica-kill`` / ``chaos-pump-stall`` (seeded fault injection
  through ``serving/faults.py``), ``router-affinity-ab`` (the
  affinity-vs-round-robin hit-rate A/B over ``serving/router.py``), and
  the over-the-wire network-chaos tier ``chaos-slow-reader`` /
  ``chaos-disconnect-storm`` (``EngineSpec(http=True)`` replays the
  trace through a real localhost HTTP/SSE server via
  :mod:`http_driver`, delivering the NETWORK fault kinds on the client
  side of the socket; the report grows an ``http`` block)).

CLI: ``python -m apex_tpu.serving.scenarios --list`` /
``--scenario NAME [--scenario NAME ...] --json OUT --seed N [--check]``
(also installed as ``apex-tpu-scenarios``).

Docs: docs/scenarios.md (spec format, seeding contract, catalog, report
schema, extension guide).
"""

from apex_tpu.serving.scenarios.library import (  # noqa: F401
    SCENARIOS,
    scenario_names,
    scenario_spec,
)
from apex_tpu.serving.scenarios.report import (  # noqa: F401
    AGGREGATE_FIELDS,
    HOST_TIER_FIELDS,
    HTTP_FIELDS,
    REPORT_SCHEMA,
    ROUTER_FIELDS,
    SCENARIOS_SCHEMA,
    TENANT_FIELDS,
    validate_report,
)
from apex_tpu.serving.scenarios.runner import (  # noqa: F401
    EngineSpec,
    ScenarioResult,
    ScenarioSpec,
    build_model,
    materialize,
    replay,
    run_scenario,
    trace_requests,
)
from apex_tpu.serving.scenarios.tenants import Tenant  # noqa: F401
from apex_tpu.serving.scenarios.traces import (  # noqa: F401
    Arrival,
    Lengths,
    Trace,
    TraceEvent,
)
