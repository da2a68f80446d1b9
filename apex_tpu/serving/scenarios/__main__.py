"""CLI for the scenario engine (``python -m apex_tpu.serving.scenarios``,
installed as ``apex-tpu-scenarios``).

Runs catalog scenarios on the local backend (CI pins CPU via
``JAX_PLATFORMS=cpu``) and writes one JSON document —
``{"schema": "apex-tpu/scenarios/v1", "scenarios": {name: report}}``.
Exit codes: 0 ok, 1 a ``--check`` amplifier found divergence, 2
usage/unknown scenario.
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Optional

__all__ = ["main"]


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser(
        prog="python -m apex_tpu.serving.scenarios",
        description="Replay named serving scenarios and report "
                    "per-tenant SLO percentiles (docs/scenarios.md)")
    parser.add_argument("--list", action="store_true",
                        help="list the scenario catalog and exit")
    parser.add_argument("--scenario", action="append", default=[],
                        metavar="NAME",
                        help="scenario to run (repeatable)")
    parser.add_argument("--seed", type=int, default=0,
                        help="trace seed (same seed = identical trace "
                             "+ greedy tokens)")
    parser.add_argument("--json", default=None, metavar="OUT",
                        help="write the scenarios document here")
    parser.add_argument("--check", action="store_true",
                        help="run the correctness amplifiers (greedy "
                             "token identity vs lock-step + scheduling "
                             "invariance)")
    parser.add_argument("--http", action="store_true",
                        help="force replay over localhost HTTP/SSE "
                             "(EngineSpec(http=True)): every request is "
                             "a real POST /v1/generate stream and the "
                             "report grows the pinned http block")
    parser.add_argument("--fleet", default=None, metavar="OUT",
                        help="write the replicated scenarios' federated "
                             "fleet blocks here (one JSON document, "
                             "docs/observability.md \"Fleet plane\")")
    parser.add_argument("--flight", default=None, metavar="OUT",
                        help="write the kill-triggered postmortem "
                             "flight bundle here (schema-validated; "
                             "skipped when no replica died)")
    parser.add_argument("--save-trace", default=None, metavar="DIR",
                        help="save each materialized trace as "
                             "<DIR>/<name>.trace.jsonl")
    parser.add_argument("--trace", default=None, metavar="JSONL",
                        help="replay a saved trace instead of "
                             "materializing (single --scenario only)")
    args = parser.parse_args(argv)

    from apex_tpu.serving.scenarios import library, report, runner
    from apex_tpu.serving.scenarios.traces import Trace

    if args.list:
        for name in library.scenario_names():
            spec = library.scenario_spec(name)
            print(f"{name:28s} n={spec.n_requests:<3d} "
                  f"model={spec.engine.model:<20s} "
                  f"{spec.description}")
        return 0
    if not args.scenario:
        parser.error("--scenario NAME required (or --list)")
    if args.trace and len(args.scenario) != 1:
        parser.error("--trace replays exactly one --scenario")

    # resolve every name BEFORE replaying anything: a typo in the third
    # --scenario must not discard the first two scenarios' minutes of
    # replay (the same evidence-preservation rule as check_failed below)
    specs = {}
    for name in args.scenario:
        try:
            specs[name] = library.scenario_spec(name, seed=args.seed)
        except KeyError as e:
            print(f"[scenarios] {e.args[0]}")
            return 2
    if args.http:
        # drive the same catalog entries over the wire: replay_http boots
        # an HttpServingServer and each trace event becomes a real SSE
        # stream (docs/http.md) — the amplifiers then prove the transport
        # corrupts nothing
        import dataclasses
        specs = {name: dataclasses.replace(
                     spec,
                     engine=dataclasses.replace(spec.engine, http=True))
                 for name, spec in specs.items()}

    reports = {}
    fleets = {}
    flight_doc = None
    check_failed = False
    doc_seed = args.seed
    for name in args.scenario:
        spec = specs[name]
        trace = None
        if args.trace:
            try:
                trace = Trace.load(args.trace)
            except (OSError, ValueError) as e:
                print(f"[scenarios] cannot load trace: {e}")
                return 2
            if trace.scenario != name:
                # a trace is only replayable under the spec that
                # materialized it — the events carry the spec's model
                # bounds (vocab/position table), and the report would
                # otherwise carry A's trace under B's name
                print(f"[scenarios] trace {args.trace} was materialized "
                      f"for scenario {trace.scenario!r}, not {name!r}")
                return 2
            if trace.seed != args.seed:
                # the report's seed field must name the seed that
                # regenerates the trace (the documented seed ->
                # trace_sha256 contract), not whatever --seed defaulted
                # to on the replay invocation
                spec = library.scenario_spec(name, seed=trace.seed)
                doc_seed = trace.seed
        t0 = time.perf_counter()
        try:
            result = runner.run_scenario(spec, check=args.check,
                                         trace=trace)
        except AssertionError as e:
            print(f"[scenarios] CHECK FAILED: {e}")
            check_failed = True
            continue
        agg = result.report["aggregate"]
        print(f"[scenarios] {name}: {result.report['n_requests']} req "
              f"/ {result.report['n_tenants']} tenant(s) in "
              f"{time.perf_counter() - t0:.1f}s — "
              f"ttft_p95={agg['ttft_ms_p95']:.1f}ms "
              f"tpot_p95={agg['tpot_ms_p95']:.2f}ms "
              f"miss_rate={agg['deadline_miss_rate']:.2f} "
              f"hit_rate={agg['prefix_hit_rate']:.2f}", flush=True)
        reports[name] = result.report
        if "fleet" in result.report:
            fleets[name] = result.report["fleet"]
        if result.flight is not None and flight_doc is None:
            from apex_tpu.obs.fleet import validate_flight

            flight_doc = validate_flight(dict(result.flight,
                                              tag=name))
        if args.save_trace:
            os.makedirs(args.save_trace, exist_ok=True)
            path = os.path.join(args.save_trace,
                                f"{name}.trace.jsonl")
            result.trace.save(path)
            print(f"[scenarios] trace saved to {path}")

    # a --check divergence exits 1, but only after every requested
    # scenario has run and the completed reports are on disk — the
    # failing amplifier's evidence (and the passing scenarios' ~minutes
    # of replay) must not be discarded
    doc = {"schema": report.SCENARIOS_SCHEMA, "seed": doc_seed,
           "time_unix": round(time.time(), 3), "scenarios": reports}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"[scenarios] report written to {args.json}")
    if args.fleet:
        fleet_out = {"schema": report.FLEET_DOC_SCHEMA, "seed": doc_seed,
                     "time_unix": round(time.time(), 3),
                     "scenarios": fleets}
        with open(args.fleet, "w") as f:
            json.dump(fleet_out, f, indent=2, sort_keys=True)
        print(f"[scenarios] fleet blocks written to {args.fleet}")
    if args.flight:
        if flight_doc is None:
            print("[scenarios] no flight recorded (no replica died); "
                  f"skipping {args.flight}")
        else:
            with open(args.flight, "w") as f:
                json.dump(flight_doc, f, indent=2, sort_keys=True)
            print(f"[scenarios] flight bundle written to {args.flight}")
    return 1 if check_failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
