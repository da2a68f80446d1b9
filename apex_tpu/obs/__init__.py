"""apex_tpu.obs — serving/training observability (docs/observability.md).

Four host-side layers over the ``apex_tpu.utils.metrics`` instrument
registry, built for operating the continuous-batching serving engine the
way production paged-KV systems are operated (Orca, Yu et al. 2022;
vLLM, Kwon et al. 2023) — per-request lifecycle traces in the spirit of
Dapper (Sigelman et al. 2010):

- ``spans``  — :class:`SpanTracer`: per-request lifecycle spans
  (enqueue → admit → prefill → first_token → decode → retire) with
  derived queue-wait / TTFT / TPOT, nested under ``jax.profiler`` trace
  annotations so they also land in xprof captures.
- ``events`` — :class:`EventLog`: bounded ring-buffer event log with a
  JSONL postmortem ``dump()``.
- ``export`` — Prometheus text exposition + JSON snapshots of the
  metric registry, file-based or via a stdlib HTTP endpoint
  (``/metrics``, ``/healthz``).
- ``compile_watch`` — :class:`CompileWatcher`: jit recompile /
  trace-cache-miss counters keyed by function name, with the serving
  frontend's recompile-storm warning built on top.

The fleet plane (``fleet``, docs/observability.md "Fleet plane") spans
processes: process-independent trace ids stitched across replica
failovers, router-side metrics federation (:class:`FleetCollector`),
multi-window SLO burn-rate alerting (:class:`BurnRateAlerter`), and
the schema-pinned postmortem flight recorder
(:func:`build_flight` / :func:`validate_flight`).
"""

from apex_tpu.obs.compile_watch import CompileWatcher, watcher
from apex_tpu.obs.events import EventLog
from apex_tpu.obs.export import (describe, health_doc, json_snapshot,
                                 prometheus_text, serve, write_snapshot)
from apex_tpu.obs.fleet import (FLIGHT_SCHEMA, BurnRateAlerter,
                                FleetCollector, build_flight,
                                mint_trace_id, parse_traceparent,
                                row_from_snapshot, stitch_traces,
                                traceparent, validate_flight)
from apex_tpu.obs.spans import PHASES, Span, SpanTracer

__all__ = ["BurnRateAlerter", "CompileWatcher", "EventLog",
           "FLIGHT_SCHEMA", "FleetCollector", "PHASES", "Span",
           "SpanTracer", "build_flight", "describe", "health_doc",
           "json_snapshot", "mint_trace_id",
           "parse_traceparent", "prometheus_text",
           "row_from_snapshot", "serve", "stitch_traces",
           "traceparent", "validate_flight", "watcher",
           "write_snapshot"]
