"""Observability layer coverage (docs/observability.md).

Five tiers, matching ISSUE 4's acceptance criteria:

1. instrument math — Counter/Gauge semantics, log-bucket placement (le
   boundaries), quantile interpolation + single-value exactness, labels.
2. the jit-safe channel — recording order inside ``jit`` + ``lax.scan``
   (read after ``jax.effects_barrier()``), the hoisted per-name callback
   (no fresh closure per call), thread-safe delivery.
3. spans — lifecycle assembly from a fake clock, and the engine
   integration: a mixed-length serving run reconstructs queue-wait /
   TTFT / TPOT for EVERY request, with run stats derived from the
   instrument registry.
4. export — Prometheus text exposition pinned by a golden file, a
   parse check of a real serving run's exposition, the JSON snapshot,
   and the stdlib HTTP endpoint.
5. event log — ring-buffer wraparound + the JSONL postmortem dump.
"""

import json
import math
import os
import re
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.obs import (EventLog, SpanTracer, json_snapshot,
                          prometheus_text, serve, write_snapshot)
from apex_tpu.obs import export
from apex_tpu.serving import PagedDecodeEngine, Request, kv_pool
from apex_tpu.utils import metrics

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "observability.prom")


@pytest.fixture(autouse=True)
def _clean_registry():
    metrics.clear()
    yield
    metrics.clear()


# --------------------------------------------------------------------------
# 1. instrument math
# --------------------------------------------------------------------------

def test_counter_monotonic():
    c = metrics.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert metrics.counter("c") is c          # interned by (name, labels)


def test_gauge_set_inc_dec():
    g = metrics.gauge("g")
    g.set(5)
    g.inc(2)
    g.dec(3)
    assert g.value == 4.0


def test_labels_make_distinct_instruments():
    a = metrics.counter("req", labels={"route": "a"})
    b = metrics.counter("req", labels={"route": "b"})
    assert a is not b
    a.inc(3)
    b.inc(1)
    assert (a.value, b.value) == (3.0, 1.0)
    assert metrics.counter("req", labels={"route": "a"}) is a


def test_kind_conflict_raises():
    metrics.counter("kind_clash")
    with pytest.raises(TypeError):
        metrics.gauge("kind_clash")


def test_kind_conflict_across_label_sets_raises():
    """Kind is a property of the NAME: a Counter under one label set and
    a Gauge under another would be one Prometheus family with
    conflicting TYPE metadata."""
    metrics.counter("xlabel_clash", labels={"engine": "0"})
    with pytest.raises(TypeError):
        metrics.gauge("xlabel_clash", labels={"engine": "1"})


def test_exposition_one_type_line_per_family():
    """Multiple label sets of one name are samples of ONE family — a
    second '# TYPE' line is invalid exposition (two engine-labeled
    counters is exactly the serving scenario)."""
    metrics.counter("fam.total", labels={"engine": "0"}).inc(1)
    metrics.counter("fam.total", labels={"engine": "1"}).inc(2)
    text = prometheus_text()
    assert text.count("# TYPE fam_total counter") == 1
    assert 'fam_total{engine="0"} 1' in text
    assert 'fam_total{engine="1"} 2' in text


def test_histogram_config_conflict_raises():
    """Re-registering a histogram with different buckets must fail loudly
    — silently returning the old layout would mis-bucket everything."""
    h = metrics.histogram("cfg_clash", base=1.0, growth=2.0)
    with pytest.raises(ValueError, match="different config"):
        metrics.histogram("cfg_clash", base=1e-6, n_buckets=64)
    assert metrics.histogram("cfg_clash", base=1.0, growth=2.0) is h
    assert metrics.histogram("cfg_clash") is h   # no kwargs: no check


def test_histogram_config_consistent_across_label_sets():
    """Bucket layout is a property of the FAMILY: a sibling label set
    with different buckets would make cross-label aggregation
    (histogram_quantile over engines) silently wrong."""
    metrics.histogram("fam_cfg", labels={"engine": "0"}, base=1.0)
    with pytest.raises(ValueError, match="registered with"):
        metrics.histogram("fam_cfg", labels={"engine": "1"}, base=1e-3)
    metrics.histogram("fam_cfg", labels={"engine": "1"}, base=1.0)


def test_histogram_bucket_boundaries_le():
    """Bucket i covers (base*g**(i-1), base*g**i] — a value exactly on a
    boundary lands in the LOWER bucket (le semantics)."""
    h = metrics.histogram("h_le", base=1.0, growth=2.0, n_buckets=6)
    for v in (0.5, 1.0, 2.0, 2.0001, 4.0, 1000.0):
        h.observe(v)
    les = [le for le, _ in h.buckets()]
    assert les == [1.0, 2.0, 4.0, 8.0, 16.0, math.inf]
    cums = [c for _, c in h.buckets()]
    # 0.5,1.0 -> le=1; 2.0 -> le=2; 2.0001,4.0 -> le=4; 1000 -> +Inf
    assert cums == [2, 3, 5, 5, 5, 6]
    assert h.count == 6 and h.sum == pytest.approx(1009.5001)


def test_histogram_quantiles_interpolate():
    h = metrics.histogram("h_q", base=1.0, growth=2.0)
    for v in (1.0, 2.0, 4.0, 8.0):           # one count per bucket 0..3
        h.observe(v)
    # target rank 2 falls at the end of bucket 1 -> its upper bound
    assert h.quantile(0.5) == pytest.approx(2.0)
    # p100 == max; clamping keeps every quantile inside [min, max]
    assert h.quantile(1.0) == pytest.approx(8.0)
    assert h.quantile(0.0) >= 1.0
    p = h.percentiles()
    assert set(p) == {"p50", "p90", "p99"} and p["p50"] <= p["p99"]


def test_histogram_single_value_exact_everywhere():
    h = metrics.histogram("h_one")
    h.observe(7.31)
    for q in (0.0, 0.5, 0.9, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(7.31)


def test_histogram_empty_and_bad_quantile():
    h = metrics.histogram("h_empty")
    assert h.quantile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_clear_name_drops_series_and_instruments():
    metrics.counter("doomed").inc()
    metrics.record("doomed", 1.0)
    metrics.counter("kept").inc()
    metrics.clear("doomed")
    assert metrics.get("doomed") == []
    assert metrics.counter("doomed").value == 0.0   # fresh registration
    assert metrics.counter("kept").value == 1.0


# --------------------------------------------------------------------------
# 2. the jit-safe channel
# --------------------------------------------------------------------------

def test_record_inside_jit_scan_ordered():
    """Values recorded by a scan body arrive in execution order once
    ``jax.effects_barrier()`` drains the callbacks."""

    @jax.jit
    def run(x):
        def body(c, t):
            metrics.record("obs.scan", c)
            return c + t, c
        c, _ = lax.scan(body, x, jnp.arange(4.0))
        return c

    run(jnp.float32(0.0)).block_until_ready()
    jax.effects_barrier()
    assert metrics.get("obs.scan") == [0.0, 0.0, 1.0, 3.0]


def test_record_callback_is_hoisted_per_name():
    """The jit path must bake ONE module-level callable per metric name
    into every trace — not a fresh lambda per record() call (the
    satellite fix: per-call closures defeat jaxpr caching)."""
    cb = metrics._callback_for("obs.hoist")
    assert metrics._callback_for("obs.hoist") is cb

    @jax.jit
    def step(x):
        metrics.record("obs.hoist", x.sum())
        return x * 2

    step(jnp.ones((4,))).block_until_ready()
    step(jnp.ones((8,))).block_until_ready()     # second trace, same cb
    jax.effects_barrier()
    assert metrics._callback_for("obs.hoist") is cb
    assert metrics.get("obs.hoist") == [4.0, 8.0]


def test_registry_is_thread_safe():
    """Callbacks can arrive on runtime threads; concurrent appends and
    instrument updates must not lose writes."""
    n_threads, n_each = 8, 500
    h = metrics.histogram("obs.mt_ms")

    def work():
        for i in range(n_each):
            metrics.record("obs.mt", float(i))
            metrics.counter("obs.mt_count").inc()
            h.observe(float(i % 17) + 0.1)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(metrics.get("obs.mt")) == n_threads * n_each
    assert metrics.counter("obs.mt_count").value == n_threads * n_each
    assert h.count == n_threads * n_each


def test_step_timer_feeds_histogram_once():
    """The satellite de-dup: one observe() = exactly one raw-series entry
    + one histogram observation (the old AverageMeter double write is
    gone)."""
    t = metrics.StepTimer("obs.t_ms")
    t.start()
    out = jax.jit(lambda x: x * 2)(jnp.ones((16,)))
    dt = t.observe(out)
    assert dt > 0
    assert metrics.get("obs.t_ms") == [dt]
    assert t.hist.count == 1
    assert t.hist.quantile(0.5) == pytest.approx(dt)
    with pytest.raises(RuntimeError):
        t.observe()


# --------------------------------------------------------------------------
# 3. spans
# --------------------------------------------------------------------------

def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    clock.advance = lambda dt: t.__setitem__(0, t[0] + dt)
    return clock


def test_span_lifecycle_assembly():
    clk = _fake_clock()
    tr = SpanTracer(clock=clk)
    tr.event(17, "enqueue", prompt_tokens=48)
    clk.advance(0.010)                               # 10 ms queued
    tr.event(17, "admit", slot=1)
    with tr.span(17, "prefill", cached_tokens=32, computed_tokens=16):
        clk.advance(0.020)                           # 20 ms prefill
    tr.event(17, "first_token")
    tr.begin(17, "decode")
    clk.advance(0.100)                               # 100 ms decoding
    tr.end(17, "decode", new_tokens=11)
    tr.event(17, "retire")

    life = tr.lifecycle(17)
    assert life["queue_wait_ms"] == pytest.approx(10.0)
    assert life["ttft_ms"] == pytest.approx(30.0)
    assert life["prefill_ms"] == pytest.approx(20.0)
    assert life["cached_tokens"] == 32 and life["computed_tokens"] == 16
    assert life["decode_ms"] == pytest.approx(100.0)
    assert life["tpot_ms"] == pytest.approx(10.0)    # 100 ms / (11 - 1)
    assert life["total_ms"] == pytest.approx(130.0)
    assert [s.name for s in tr.spans(17)] == [
        "enqueue", "admit", "prefill", "first_token", "decode", "retire"]
    assert tr.lifecycles().keys() == {17}


def test_span_misuse_raises():
    tr = SpanTracer(clock=_fake_clock())
    with pytest.raises(RuntimeError):
        tr.end(0, "never_opened")
    tr.begin(0, "twice")
    with pytest.raises(RuntimeError):
        tr.begin(0, "twice")
    # a double-begin with annotation must raise BEFORE entering the
    # TraceMe (no leaked annotation); later nested spans still work
    with pytest.raises(RuntimeError):
        tr.begin(0, "twice", annotate=True)
    with tr.span(0, "after"):
        pass
    assert tr.spans(0)[-1].duration_ms is not None


def _tiny_engine(**kw):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, PagedDecodeEngine(model, v, num_slots=2, page_size=8, **kw)


def test_engine_spans_reconstruct_every_request():
    """Acceptance: a mixed-length workload's span trace yields queue-wait
    + TTFT + TPOT for every request, and run() stats come from the
    instrument registry (second run's deltas are clean)."""
    rng = np.random.default_rng(0)
    cfg, engine = _tiny_engine()
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (int(s),)
                                        ).astype(np.int32),
                    max_new_tokens=int(m))
            for s, m in zip([10, 20, 7, 33, 12], [5, 3, 8, 4, 1])]
    outs, stats = engine.run(reqs)

    for i, out in enumerate(outs):
        life = engine.tracer.lifecycle(i)
        assert life["queue_wait_ms"] >= 0.0, life
        assert life["ttft_ms"] >= life["queue_wait_ms"], life
        assert life["tpot_ms"] >= 0.0, life
        assert life["new_tokens"] == len(out), life
        assert life["computed_tokens"] >= 1
    for key in ("ttft_ms_p50", "ttft_ms_p95", "tpot_ms_p50",
                "queue_wait_ms_p50", "decode_step_ms_p50",
                "decode_step_ms_p95"):
        assert stats[key] >= 0.0, key
    assert stats["ttft_ms_p95"] >= stats["ttft_ms_p50"]

    # stats are registry deltas over the engine's OWN labeled counters:
    # they carry across runs, stats don't — and another engine's traffic
    # cannot leak into them
    retired = metrics.counter("serving.retired", labels=engine.obs_labels)
    assert retired.value == len(reqs)
    _, stats2 = engine.run(reqs)
    assert stats2["admitted"] == len(reqs)
    assert stats2["retired"] == len(reqs)
    assert retired.value == 2 * len(reqs)
    assert metrics.histogram("serving.ttft_ms",
                             labels=engine.obs_labels).count == 2 * len(reqs)
    other = metrics.counter("serving.retired", labels={"engine": "ghost"})
    other.inc(100)                         # concurrent-engine traffic
    _, stats3 = engine.run(reqs)
    assert stats3["retired"] == len(reqs)  # isolation: 100 not counted

    # the engine's event ring saw every admission and retirement
    kinds = [e["kind"] for e in engine.events.tail()]
    assert kinds.count("admit") == 3 * len(reqs)
    assert kinds.count("retire") == 3 * len(reqs)


def test_engine_pool_and_prefix_gauges():
    """kv_pool/prefix_cache publish residency gauges during a cached
    serving run."""
    rng = np.random.default_rng(1)
    cfg, engine = _tiny_engine(prefix_cache=True)
    head = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
                [head, rng.integers(0, cfg.vocab_size, (int(s),)
                                    ).astype(np.int32)]),
                    max_new_tokens=3) for s in (4, 6, 5)]
    _, stats = engine.run(reqs)
    lbl = engine.obs_labels        # pool/prefix gauges are per-engine
    assert metrics.gauge("prefix_cache.pages",
                         labels=lbl).value == len(engine.prefix)
    assert metrics.gauge("kv_pool.free_pages", labels=lbl).value >= 0
    assert metrics.gauge("kv_pool.pages_total", labels=lbl).value == \
        kv_pool.num_pages_of(engine.cache) - 1
    assert metrics.counter("prefix_cache.inserted_pages",
                           labels=lbl).value == len(engine.prefix)
    _, stats2 = engine.run(reqs)
    assert stats2["prefix_hits"] > 0      # warm cache: the head is shared


def test_observe_pool_direct():
    vals = kv_pool.observe_pool({
        "layers": [{"k_pages": jnp.zeros((5, 1, 8, 4)),
                    "v_pages": jnp.zeros((5, 1, 8, 4))}],
        "page_ref": jnp.asarray([0, 2, 1, 0, 0], jnp.int32),
        "free_top": jnp.asarray(2, jnp.int32),
    })
    assert vals == {"kv_pool.free_pages": 2, "kv_pool.pages_total": 4,
                    "kv_pool.shared_pages_active": 2,
                    "kv_pool.page_refs_total": 3}
    assert metrics.gauge("kv_pool.page_refs_total").value == 3


# --------------------------------------------------------------------------
# 4. export
# --------------------------------------------------------------------------

def _seed_golden_registry():
    # the canonical seeded state lives in export.py so the golden can
    # be regenerated (`python -m apex_tpu.obs.export --golden`) instead
    # of hand-edited — the test and the regenerator CANNOT drift
    export.seed_golden_registry()


def test_prometheus_exposition_golden_file():
    _seed_golden_registry()
    with open(GOLDEN) as f:
        assert prometheus_text() == f.read()


_PROM_LINE = re.compile(
    r"^(?:# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (?:counter|gauge|histogram)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? -?(?:[0-9.e+-]+|\+Inf))$")


def test_serving_run_exposition_parses():
    """Acceptance: the Prometheus text exposition of a real serving run
    parses line by line, every family's # HELP line immediately
    precedes its # TYPE line, and histogram buckets are cumulative."""
    rng = np.random.default_rng(2)
    cfg, engine = _tiny_engine()
    engine.run([Request(prompt=rng.integers(0, cfg.vocab_size, (9,)
                                            ).astype(np.int32),
                        max_new_tokens=4)])
    text = prometheus_text()
    assert "serving_ttft_ms_bucket" in text
    assert "serving_slots_in_use" in text
    cums = []
    lines = text.rstrip("\n").split("\n")
    for line in lines:
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
        if line.startswith("serving_ttft_ms_bucket"):
            cums.append(float(line.rsplit(" ", 1)[1]))
    assert cums == sorted(cums) and cums[-1] == 1.0
    # HELP/TYPE pairing: exactly one HELP per family, named the same as
    # — and directly above — its TYPE line (the exposition contract
    # registered HELP text rides on; docs/observability.md)
    helps = [i for i, ln in enumerate(lines) if ln.startswith("# HELP")]
    types = [i for i, ln in enumerate(lines) if ln.startswith("# TYPE")]
    assert helps and len(helps) == len(types)
    for i in helps:
        assert lines[i + 1].startswith("# TYPE")
        assert lines[i].split(" ")[2] == lines[i + 1].split(" ")[2]
    # a registered description is used verbatim; the fallback is generic
    assert "# HELP serving_ttft_ms Time to first token per request" \
           in text


def test_exposition_survives_nan_and_inf():
    """A diverging loss (NaN) is exactly when metrics matter — the
    exporter must emit the valid literals, not crash the scrape."""
    metrics.record("train.loss", float("nan"))
    metrics.gauge("weird").set(float("inf"))
    text = prometheus_text()
    assert "train_loss_last NaN" in text
    assert "weird +Inf" in text


def test_step_timer_survives_registry_clear():
    """clear() between observations must not orphan the timer's
    histogram — observations after the clear land in the re-interned
    instrument that snapshots actually see."""
    t = metrics.StepTimer("obs.clear_ms")
    t.start()
    t.observe()
    metrics.clear()
    t.start()
    t.observe()
    assert t.hist.count == 1
    assert metrics.histogram("obs.clear_ms") is t.hist


def test_exposition_no_duplicate_family_for_step_timer():
    """A name that is both a Histogram and a raw record() series (what
    every StepTimer produces) must export ONE metric family — a second
    `x_count` with conflicting TYPE metadata makes the scrape invalid."""
    t = metrics.StepTimer("obs.step_ms")
    t.start()
    t.observe()
    text = prometheus_text()
    assert text.count("obs_step_ms_count") == 1
    assert "# TYPE obs_step_ms histogram" in text
    assert "# TYPE obs_step_ms_count gauge" not in text


def test_json_snapshot_and_write(tmp_path):
    _seed_golden_registry()
    doc = json_snapshot(extra={"tag": "t"})
    assert doc["tag"] == "t"
    hists = {h["name"]: h for h in doc["histograms"]}
    assert hists["serving.ttft_ms"]["count"] == 4
    assert hists["serving.ttft_ms"]["buckets"][-1] == [None, 4]

    path = write_snapshot(str(tmp_path / "snap.json"))
    with open(path) as f:
        parsed = json.load(f)          # strict JSON round trip
    assert parsed["counters"]
    prom = write_snapshot(str(tmp_path / "snap.prom"))
    with open(prom) as f:
        assert "# TYPE serving_admitted counter" in f.read()


def test_http_endpoint():
    _seed_golden_registry()
    server = serve(port=0)
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            assert b"serving_admitted 3" in r.read()
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics.json") as r:
            doc = json.loads(r.read())
            assert doc["gauges"][0]["name"] == "kv_pool.free_pages"
    finally:
        server.shutdown()
        server.server_close()


#: the /healthz payload shape (ISSUE 8 satellite) — golden-pinned key
#: set so operators' probes can rely on it
_HEALTHZ_KEYS = {"ok", "time_unix", "frontend", "pump_alive",
                 "queue_depth", "active_slots", "failure"}


def test_healthz_endpoint_without_frontend():
    from apex_tpu.obs import export

    server = serve(port=0)
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz") as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            doc = json.loads(r.read())
        assert set(doc) == _HEALTHZ_KEYS
        assert doc["ok"] is True and doc["frontend"] is False
        assert doc["pump_alive"] is False
        assert doc["queue_depth"] is None and doc["failure"] is None
    finally:
        server.shutdown()
        server.server_close()
    # the doc builder is directly usable too (no server needed)
    assert set(export.health_doc()) == _HEALTHZ_KEYS


def test_healthz_endpoint_with_live_frontend():
    from apex_tpu.serving.frontend import ServingFrontend

    rng = np.random.default_rng(5)
    cfg, engine = _tiny_engine()
    fe = ServingFrontend(engine)
    fe.start()
    server = serve(port=0, frontend=fe)
    try:
        host, port = server.server_address[:2]
        h = fe.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, (9,)
                                ).astype(np.int32), max_new_tokens=4))
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz") as r:
            doc = json.loads(r.read())
        assert doc["ok"] is True and doc["frontend"] is True
        assert doc["pump_alive"] is True
        assert doc["queue_depth"] >= 0 and doc["active_slots"] >= 0
        h.result(timeout=60.0)
    finally:
        fe.stop()
        server.shutdown()
        server.server_close()


class _StubReplica:
    """Just enough of router.py's replica record for health_doc."""

    def __init__(self, index, alive, queue_depth, dead_reason=None):
        self.index = index
        self.alive = alive
        self.draining = False
        self.dead_reason = dead_reason

        class _FE:
            pump_alive = alive

        _FE.queue_depth = queue_depth
        self.frontend = _FE()


def test_healthz_router_block(tmp_path):
    """ISSUE 15 satellite: ``serve(router=)`` / ``health_doc(router=)``
    add per-replica liveness + queue depth, and overall ``ok`` goes
    false only when NO replica is alive."""
    from apex_tpu.obs import export

    class _StubRouter:
        replicas = [_StubReplica(0, True, 3),
                    _StubReplica(1, False, 0,
                                 dead_reason=RuntimeError("killed"))]

    doc = export.health_doc(router=_StubRouter())
    r = doc["router"]
    assert (r["replicas"], r["alive"], r["queue_depth"]) == (2, 1, 3)
    assert doc["ok"] is True             # one survivor keeps us healthy
    rows = {row["replica"]: row for row in r["per_replica"]}
    assert rows[0]["alive"] and rows[0]["pump_alive"]
    assert rows[0]["queue_depth"] == 3 and rows[0]["failure"] is None
    assert not rows[1]["alive"] and rows[1]["queue_depth"] is None
    assert "killed" in rows[1]["failure"]
    # fleet-plane fields (ISSUE 19 satellite): every row carries the
    # supervision-tick age, its failover count, and its federation
    # scrape staleness — None/0 on a router without the fleet plane
    for row in rows.values():
        assert set(row) >= {"last_tick_age_s", "failovers",
                            "scrape_age_s"}
        assert row["last_tick_age_s"] is None    # stub has no tick
        assert row["failovers"] == 0
        assert row["scrape_age_s"] is None       # stub has no collector

    class _DeadRouter:
        replicas = [_StubReplica(0, False, 0,
                                 dead_reason=RuntimeError("gone"))]

    assert export.health_doc(router=_DeadRouter())["ok"] is False

    server = serve(port=0, router=_StubRouter())
    try:
        host, port = server.server_address[:2]
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz") as resp:
            served = json.loads(resp.read())
        assert served["router"]["alive"] == 1
        assert len(served["router"]["per_replica"]) == 2
    finally:
        server.shutdown()
        server.server_close()


# --------------------------------------------------------------------------
# 5. event log
# --------------------------------------------------------------------------

def test_event_ring_wraparound(tmp_path):
    clock = iter(range(100)).__next__
    log = EventLog(capacity=4, clock=lambda: float(clock()))
    for i in range(10):
        log.emit("tick", i=i)
    assert len(log) == 4
    assert log.total == 10 and log.dropped == 6
    assert [e["seq"] for e in log.tail()] == [6, 7, 8, 9]
    assert [e["i"] for e in log.tail(2)] == [8, 9]

    path = tmp_path / "events.jsonl"
    text = log.dump(str(path))
    assert path.read_text() == text
    lines = [json.loads(line) for line in text.splitlines()]
    assert lines[0] == {"kind": "event_log_header", "capacity": 4,
                        "total": 10, "dropped": 6}
    assert [r["seq"] for r in lines[1:]] == [6, 7, 8, 9]
    assert all(r["kind"] == "tick" for r in lines[1:])

    # emit returns a copy: mutating it must not corrupt the ring
    rec = log.emit("tick", i=99)
    rec["i"] = "mutated"
    assert log.tail(1)[0]["i"] == 99


def test_event_log_validates_capacity():
    with pytest.raises(ValueError):
        EventLog(capacity=0)
