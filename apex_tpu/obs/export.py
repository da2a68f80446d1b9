"""Metric export: Prometheus text exposition + JSON snapshots.

Readers of the ``apex_tpu.utils.metrics`` registry — nothing here ever
touches a device. Three transports:

- :func:`prometheus_text` — the text exposition format (v0.0.4) any
  Prometheus-compatible scraper ingests: counters and gauges as single
  samples, histograms as the canonical ``_bucket``/``_sum``/``_count``
  triplet with cumulative ``le`` buckets, and the raw ``record()``
  series as ``_count``/``_mean``/``_last`` gauges. Output is sorted and
  deterministic for a given registry state (the golden-file test pins
  it).
- :func:`json_snapshot` / :func:`write_snapshot` — the full registry as
  one JSON document (a CI artifact next to the bench JSON).
- :func:`serve` — optional stdlib ``http.server`` endpoint exposing
  ``/metrics`` (Prometheus), ``/metrics.json`` and ``/healthz``
  (liveness: pump-alive + queue depth of the frontend passed via
  ``serve(..., frontend=)``) on a daemon thread; returns the
  server (``.server_address`` for the bound port, ``.shutdown()`` to
  stop). No third-party client library, per the no-new-deps rule.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional

from apex_tpu.utils import metrics

__all__ = ["prometheus_text", "json_snapshot", "write_snapshot", "serve",
           "health_doc", "describe"]

_NAME_OK = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")

# per-instrument description registry: `# HELP` text per metric family
# (registry names — sanitized on emit). Seeded with the core serving
# families; describe() registers more. Families without an entry get a
# generated default so every TYPE line still carries a HELP line (the
# exposition-parse test pins the pairing).
_HELP_LOCK = threading.Lock()
_HELP: Dict[str, str] = {
    "serving.ttft_ms": "Time to first token per request (ms).",
    "serving.tpot_ms": "Steady-state time per output token (ms).",
    "serving.queue_wait_ms": "Enqueue-to-admit wait per request (ms).",
    "serving.decode_step_ms": "Wall time per batched decode step (ms).",
    "serving.queue_depth": "Requests waiting for admission.",
    "serving.slots_in_use": "Decode slots currently occupied.",
    "serving.slo_burn": "SLO miss rate over the rolling retirement "
                        "window.",
    "serving.admitted": "Requests admitted to decode slots.",
    "serving.retired": "Requests retired (complete/cancelled/failed).",
    "router.replicas_alive": "Live replicas behind the router.",
    "router.replica_queue_depth": "Queue depth per routed replica.",
    "fleet.ttft_ms_p95": "Federated per-replica TTFT p95 (ms).",
    "fleet.tpot_ms_p95": "Federated per-replica TPOT p95 (ms).",
    "fleet.queue_depth": "Federated per-replica queue depth.",
    "fleet.slo_burn": "Federated per-replica SLO burn rate.",
    "fleet.scrape_age_s": "Seconds since the replica's last "
                          "successful federation scrape.",
    "kv_pool.free_pages": "Free pages in the device KV pool.",
    "http.connections": "Open HTTP connections.",
    "http.streams_active": "Live SSE token streams.",
}


def describe(name: str, help_text: str) -> None:
    """Register the ``# HELP`` description for a metric family (by
    registry name, e.g. ``serving.ttft_ms``)."""
    with _HELP_LOCK:
        _HELP[name] = " ".join(str(help_text).split())


def _help_for(prom_name: str) -> str:
    """The HELP text for a sanitized family name (falls back to a
    generated default — HELP/TYPE pairing is unconditional)."""
    with _HELP_LOCK:
        for name, text in _HELP.items():
            if _prom_name(name) == prom_name:
                return text
    return f"apex-tpu metric {prom_name}."


def _prom_name(name: str) -> str:
    """Sanitize a registry name (``serving.ttft_ms``) into a Prometheus
    metric name (``serving_ttft_ms``)."""
    out = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    if not _NAME_OK.match(out):
        out = "_" + out
    return out


def _prom_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    esc = {k: str(v).replace("\\", r"\\").replace('"', r'\"')
           .replace("\n", r"\n") for k, v in labels.items()}
    inner = ",".join(f'{_prom_name(k)}="{esc[k]}"'
                     for k in sorted(esc))
    return "{" + inner + "}"


def _merge_labels(labels: Dict[str, str], **extra) -> str:
    merged = dict(labels)
    merged.update(extra)
    return _prom_labels(merged)


def _fmt(v: float) -> str:
    if isinstance(v, float):
        if math.isnan(v):
            return "NaN"            # valid exposition literal — a NaN
        #                             metric must not kill the exporter
        if math.isinf(v):
            return "+Inf" if v > 0 else "-Inf"
    if float(v) == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


def prometheus_text(snap: Optional[dict] = None) -> str:
    """Render a ``metrics.snapshot()`` (current registry if omitted) in
    the Prometheus text exposition format, trailing newline included."""
    if snap is None:
        snap = metrics.snapshot()
    lines = []

    def sample(name, labels_str, value):
        lines.append(f"{name}{labels_str} {_fmt(value)}")

    def family(name, prom_type):
        # ONE `# HELP` + `# TYPE` pair per metric family: all label
        # sets of a name are samples of the same family (a second TYPE
        # line for a name is invalid text exposition — two
        # engine-labeled counters hit this), and every TYPE line is
        # preceded by its HELP line from the description registry
        lines.append(f"# HELP {name} {_help_for(name)}")
        lines.append(f"# TYPE {name} {prom_type}")

    for kind, prom_type in (("counters", "counter"), ("gauges", "gauge")):
        seen = set()
        for entry in sorted(snap.get(kind, ()),
                            key=lambda e: (e["name"], sorted(
                                e["labels"].items()))):
            name = _prom_name(entry["name"])
            if name not in seen:
                seen.add(name)
                family(name, prom_type)
            sample(name, _prom_labels(entry["labels"]), entry["value"])

    seen = set()
    for entry in sorted(snap.get("histograms", ()),
                        key=lambda e: (e["name"], sorted(
                            e["labels"].items()))):
        name = _prom_name(entry["name"])
        if name not in seen:
            seen.add(name)
            family(name, "histogram")
        for le, cum in entry["buckets"]:
            le_str = "+Inf" if le is None else format(le, ".6g")
            sample(name + "_bucket",
                   _merge_labels(entry["labels"], le=le_str), cum)
        sample(name + "_sum", _prom_labels(entry["labels"]), entry["sum"])
        sample(name + "_count", _prom_labels(entry["labels"]),
               entry["count"])

    # a name that is BOTH an instrument and a raw series (StepTimer
    # writes its histogram and its record() series under one name) must
    # export once: the typed instrument wins, else `x_count` would appear
    # twice with conflicting TYPE metadata and the scrape is rejected
    instrumented = {_prom_name(e["name"])
                    for kind in ("counters", "gauges", "histograms")
                    for e in snap.get(kind, ())}
    for raw_name in sorted(snap.get("series", ())):
        s = snap["series"][raw_name]
        name = _prom_name(raw_name)
        if name in instrumented:
            continue
        for suffix, value in (("_count", s["count"]), ("_mean", s["mean"]),
                              ("_last", s["last"])):
            family(name + suffix, "gauge")
            sample(name + suffix, "", value)

    return "\n".join(lines) + "\n" if lines else ""


def json_snapshot(extra: Optional[dict] = None) -> dict:
    """The registry snapshot as a JSON-ready document with a timestamp
    (and optional caller context, e.g. the bench tag)."""
    doc = {"time_unix": time.time(), **metrics.snapshot()}
    if extra:
        doc.update(extra)
    return doc


def write_snapshot(path: str, fmt: Optional[str] = None,
                   extra: Optional[dict] = None) -> str:
    """Write the current registry to ``path`` — Prometheus text when
    ``fmt='prom'`` (or the path ends in ``.prom``/``.txt``), JSON
    otherwise. Returns the path."""
    if fmt is None:
        fmt = "prom" if path.endswith((".prom", ".txt")) else "json"
    if fmt not in ("prom", "json"):
        raise ValueError(f"unknown snapshot format {fmt!r}")
    with open(path, "w") as f:
        if fmt == "prom":
            f.write(prometheus_text())
        else:
            json.dump(json_snapshot(extra), f, indent=1, sort_keys=True)
            f.write("\n")
    return path


def health_doc(frontend=None, router=None) -> dict:
    """The ``/healthz`` payload: process liveness plus — when a serving
    frontend is wired in — pump-thread liveness, queue depth, active
    slots, and the pump's terminal failure if it died. Shape pinned by
    tests/test_observability.py (the frontend-only shape is unchanged;
    ``router=`` ADDS a ``router`` block with per-replica liveness and
    queue depth — the router-level health the HTTP surface serves)."""
    doc = {"ok": True, "time_unix": time.time(), "frontend": False,
           "pump_alive": False, "queue_depth": None, "active_slots": None,
           "failure": None}
    if frontend is not None:
        failure = frontend.failure
        doc.update(
            frontend=True, pump_alive=frontend.pump_alive,
            queue_depth=frontend.queue_depth,
            active_slots=frontend.active_slots,
            failure=repr(failure) if failure is not None else None)
        doc["ok"] = failure is None
    if router is not None:
        # fleet-plane staleness (PR 19): liveness is readable from
        # /healthz alone — supervision-tick age, per-replica failover
        # counts, and federation scrape age ride along. All three read
        # through getattr so a router-shaped stub (tests) stays valid.
        fleet = getattr(router, "fleet", None)
        ages = fleet.scrape_ages() if fleet is not None else {}
        tick_age = getattr(router, "last_tick_age_s", None)
        per_replica = []
        for rep in router.replicas:
            per_replica.append({
                "replica": rep.index,
                "alive": rep.alive,
                "draining": rep.draining,
                "pump_alive": rep.frontend.pump_alive if rep.alive
                else False,
                "queue_depth": rep.frontend.queue_depth if rep.alive
                else None,
                "failure": repr(rep.dead_reason)
                if rep.dead_reason is not None else None,
                "last_tick_age_s": tick_age,
                "failovers": getattr(rep, "failovers", 0),
                "scrape_age_s": ages.get(f"replica{rep.index}"),
            })
        n_alive = sum(1 for r in per_replica if r["alive"])
        doc["router"] = {"replicas": len(per_replica), "alive": n_alive,
                         "queue_depth": sum(r["queue_depth"] or 0
                                            for r in per_replica),
                         "per_replica": per_replica}
        doc["ok"] = doc["ok"] and n_alive > 0
    return doc


class _Handler(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 (stdlib handler contract)
        path = self.path.split("?", 1)[0]
        if path in ("/metrics", "/"):
            body = prometheus_text().encode()
            ctype = "text/plain; version=0.0.4; charset=utf-8"
        elif path == "/metrics.json":
            body = (json.dumps(json_snapshot(), sort_keys=True)
                    + "\n").encode()
            ctype = "application/json"
        elif path == "/healthz":
            doc = health_doc(getattr(self.server, "frontend", None),
                             router=getattr(self.server, "router", None))
            body = (json.dumps(doc, sort_keys=True) + "\n").encode()
            ctype = "application/json"
        else:
            self.send_error(404)
            return
        self.send_response(200)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):     # silence per-request stderr lines
        pass


def serve(port: int = 0, host: str = "127.0.0.1",
          frontend=None, router=None) -> ThreadingHTTPServer:
    """Start the metrics endpoint on a daemon thread. ``port=0`` binds an
    ephemeral port (read it from ``server.server_address[1]``).
    ``frontend=`` wires a :class:`~apex_tpu.serving.frontend.
    ServingFrontend` into ``/healthz``; ``router=`` a
    :class:`~apex_tpu.serving.router.ReplicaRouter` (per-replica
    liveness and queue depth in the ``router`` block)."""
    server = ThreadingHTTPServer((host, port), _Handler)
    server.frontend = frontend
    server.router = router
    thread = threading.Thread(target=server.serve_forever,
                              name="apex-tpu-metrics", daemon=True)
    thread.start()
    return server


# --------------------------------------------------------------------------
# golden regeneration (``python -m apex_tpu.obs.export --golden``)
# --------------------------------------------------------------------------

def seed_golden_registry() -> None:
    """Seed the registry with the FIXED state the golden exposition
    pins (``tests/golden/observability.prom``). One representative of
    every exposition shape, each a real production family (the contract
    tier proves golden families against registered instruments): an
    unlabeled counter, a labeled counter, a gauge, a histogram with its
    ``_bucket``/``_sum``/``_count`` triplet, and a raw ``record()``
    series with its ``_count``/``_mean``/``_last`` gauges. Clears the
    registry first — the golden describes exactly this state."""
    metrics.clear()
    metrics.counter("serving.admitted").inc(3)
    metrics.counter("jit.compiles", labels={"fn": "decode_step"}).inc(2)
    metrics.gauge("kv_pool.free_pages").set(12)
    h = metrics.histogram("serving.ttft_ms", base=1.0, growth=2.0,
                          n_buckets=6)
    for v in (0.5, 1.0, 3.0, 100.0):
        h.observe(v)
    metrics.record("serving.decode_steps", 9)


def _default_golden_path() -> str:
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "tests", "golden", "observability.prom")


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m apex_tpu.obs.export",
        description="Regenerate the golden Prometheus exposition from "
                    "the canonical seeded registry state (instead of "
                    "hand-editing it).")
    parser.add_argument("--golden", action="store_true", required=True,
                        help="write the golden exposition file")
    parser.add_argument("--out", default=None,
                        help="output path (default: the in-repo "
                             "tests/golden/observability.prom)")
    args = parser.parse_args(argv)
    path = args.out or _default_golden_path()
    seed_golden_registry()
    text = prometheus_text()
    with open(path, "w") as f:
        f.write(text)
    print(f"[export] golden exposition written to {path} "
          f"({len(text.splitlines())} lines)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
