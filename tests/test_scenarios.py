"""Scenario engine (apex_tpu/serving/scenarios, docs/scenarios.md).

Trace tier (no model forward): seeded arrival/length samplers, JSONL
round-trip, byte-identical materialization per seed, the catalog's
spec/JSON round-trip.

Replay tier (tiny models): the ISSUE 9 acceptance bars — same seed ⇒
identical trace sha AND identical greedy tokens across two full replays;
``check=`` token-identity + scheduling-invariance amplifiers pass; the
pinned report schema with per-tenant SLO splits; multi-tenant isolation
(a flood tenant cannot starve a higher-priority tenant's deadline under
``PriorityDeadlinePolicy``); eviction-churn lights the
``prefix_cache.churn`` / ``evicted_reinserted`` instruments; and
windowed-Llama runs PAGED — token-identical to the rolling-cache
lock-step at window < prompt length, with dead pages dropped and the
pool fully recovered."""

import dataclasses
import json

import numpy as np
import pytest

from apex_tpu.serving.scenarios import (AGGREGATE_FIELDS, SCENARIOS,
                                        TENANT_FIELDS, Arrival,
                                        EngineSpec, Lengths, ScenarioSpec,
                                        Tenant, Trace, materialize,
                                        replay, run_scenario,
                                        scenario_names, scenario_spec,
                                        validate_report)
from apex_tpu.serving.scenarios.traces import TraceEvent
from apex_tpu.utils import metrics

# a deliberately small spec for the replay-tier tests: one engine
# compile footprint, a few seconds on CPU
_SMALL = ScenarioSpec(
    name="small", seed=7, n_requests=6,
    arrival=Arrival(kind="poisson", rate_rps=500.0),
    prompt_lens=Lengths(kind="uniform", lo=4, hi=20),
    output_lens=Lengths(kind="uniform", lo=3, hi=7),
    tenants=(Tenant("default"),),
    engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                      prefix_cache=False))


# --- trace tier --------------------------------------------------------------


def test_arrival_kinds_sorted_and_seeded():
    rng = np.random.default_rng(3)
    for kind in ("poisson", "bursty", "closed"):
        arr = Arrival(kind=kind)
        t = arr.sample_ms(32, np.random.default_rng(3))
        assert t.shape == (32,) and (np.diff(t) >= 0).all()
        assert (t >= 0).all()
        t2 = arr.sample_ms(32, np.random.default_rng(3))
        np.testing.assert_array_equal(t, t2)       # seeded
    with pytest.raises(ValueError):
        Arrival(kind="warp").sample_ms(4, rng)
    # degenerate parameters fail loudly, not with ZeroDivisionError
    for bad in (Arrival(kind="closed", users=0),
                Arrival(kind="closed", think_ms=0.0),
                Arrival(kind="poisson", rate_rps=0.0),
                Arrival(kind="bursty", idle_rate_rps=-1.0)):
        with pytest.raises(ValueError):
            bad.sample_ms(4, rng)


def test_length_kinds_bounded():
    rng = np.random.default_rng(5)
    for kind in ("lognormal", "zipf", "uniform", "fixed"):
        v = Lengths(kind=kind, lo=3, hi=17).sample(200, rng)
        assert v.dtype == np.int32
        assert v.min() >= 3 and v.max() <= 17
    # the long tail actually reaches past the body
    z = Lengths(kind="zipf", zipf_a=1.3, lo=3, hi=64).sample(
        500, np.random.default_rng(1))
    assert z.max() > 32 and np.median(z) < 10
    with pytest.raises(ValueError):
        Lengths(kind="normal").sample(4, rng)
    with pytest.raises(ValueError):
        Lengths(lo=5, hi=4).sample(4, rng)


def test_trace_determinism_and_jsonl_roundtrip(tmp_path):
    """Same seed ⇒ byte-identical materialized trace; different seed
    differs; save/load round-trips exactly."""
    a = materialize(_SMALL)
    b = materialize(_SMALL)
    assert a.to_jsonl() == b.to_jsonl()
    assert a.sha256() == b.sha256()
    c = materialize(dataclasses.replace(_SMALL, seed=8))
    assert c.sha256() != a.sha256()

    path = tmp_path / "t.jsonl"
    a.save(path)
    loaded = Trace.load(path)
    assert loaded.to_jsonl() == a.to_jsonl()
    # corruption fails loudly
    path.write_text(a.to_jsonl().rsplit("\n", 2)[0] + "\n")
    with pytest.raises(ValueError):
        Trace.load(path)


def test_catalog_specs_build_and_roundtrip():
    """Every registered scenario builds, names itself consistently, and
    survives the JSON spec round-trip; the ISSUE 9 six-plus are all
    present."""
    required = {"steady-poisson", "burst-storm", "long-tail-lengths",
                "multi-tenant-shared-prefix", "eviction-churn",
                "priority-flood", "windowed-llama"}
    assert required <= set(scenario_names())
    for name in scenario_names():
        spec = scenario_spec(name, seed=11)
        assert spec.name == name and spec.seed == 11
        back = ScenarioSpec.from_json(spec.to_json())
        assert back == spec
        trace = materialize(spec)          # bounds-clipped, materializes
        assert len(trace.events) == spec.n_requests
    with pytest.raises(KeyError):
        scenario_spec("no-such-scenario")
    # overrides apply at the top level
    assert scenario_spec("steady-poisson", n_requests=3).n_requests == 3


def test_materialize_rejects_oversized_system_prompt():
    """A tenant header too long for the model's position table raises a
    ValueError naming the tenant, not an opaque numpy error."""
    spec = ScenarioSpec(
        name="big-header",
        tenants=(Tenant("big", system_prompt_tokens=4096),))
    with pytest.raises(ValueError, match="'big'"):
        materialize(spec)


def test_tenant_prompts_deterministic_and_weighted():
    from apex_tpu.serving.scenarios.tenants import (assign_tenants,
                                                    system_prompt)

    t = Tenant("acme", system_prompt_tokens=16)
    p1 = system_prompt(t, 128, seed=5)
    p2 = system_prompt(t, 128, seed=5)
    np.testing.assert_array_equal(p1, p2)
    assert p1.shape == (16,)
    assert not np.array_equal(p1, system_prompt(t, 128, seed=6))
    other = Tenant("other", system_prompt_tokens=16)
    assert not np.array_equal(p1, system_prompt(other, 128, seed=5))
    idx = assign_tenants([Tenant("a", weight=9.0),
                          Tenant("b", weight=1.0)], 200,
                         np.random.default_rng(0))
    assert (idx == 0).sum() > (idx == 1).sum()


# --- replay tier -------------------------------------------------------------


def test_run_determinism_and_report_schema():
    """ISSUE 9 acceptance: re-running with the same seed reproduces an
    identical trace AND identical greedy tokens; the report carries the
    pinned schema."""
    r1 = run_scenario(_SMALL)
    r2 = run_scenario(_SMALL)
    assert r1.trace.sha256() == r2.trace.sha256()
    assert r1.report["trace_sha256"] == r1.trace.sha256()
    for a, b in zip(r1.outputs, r2.outputs):
        np.testing.assert_array_equal(a, b)
    validate_report(r1.report)
    assert set(AGGREGATE_FIELDS) <= set(r1.report["aggregate"])
    for block in r1.report["per_tenant"].values():
        assert set(TENANT_FIELDS) <= set(block)
    assert r1.report["aggregate"]["generated_tokens"] > 0
    assert r1.report["aggregate"]["tpot_ms_p95"] > 0


@pytest.mark.slow
def test_check_mode_amplifiers_pass():
    """check= re-derives every output via lock-step generate and re-runs
    the trace at a different sync_every — both must agree. (Slow tier
    since ISSUE 15 to hold the 870 s verify wall: tier-1 keeps a full
    check=True path in test_chaos_slow_reader_scenario_spills_over_the_wire
    — both amplifiers, over the wire — and CI's scenario/chaos/HTTP
    smokes run --check on five catalog entries every round.)"""
    r = run_scenario(_SMALL, check=True)
    assert r.report["checks"]["greedy_identity_requests"] == 6
    assert r.report["checks"]["scheduling_invariance"] is True


@pytest.mark.slow
def test_saved_trace_replays_identically(tmp_path):
    """A trace saved to JSONL and replayed (the --trace path) yields the
    same tokens as the materialized original. (Slow tier since ISSUE 15
    to hold the 870 s verify wall: the CLI --trace round-trip — save,
    wrong-scenario refusal, seed provenance, sha pin — stays tier-1 in
    test_cli_json_document_and_trace_replay.)"""
    r1 = run_scenario(_SMALL)
    path = tmp_path / "small.trace.jsonl"
    r1.trace.save(path)
    r2 = run_scenario(_SMALL, trace=Trace.load(path))
    for a, b in zip(r1.outputs, r2.outputs):
        np.testing.assert_array_equal(a, b)


def test_multi_tenant_isolation_under_priority_policy():
    """ISSUE 9 isolation pin: tenant A's burst cannot starve tenant B's
    higher-priority deadline — B's requests preempt into service and
    miss no (generous) deadline while A floods every slot."""
    events = []
    # six flood requests land first and pin both slots with long decodes
    for i in range(6):
        events.append(TraceEvent(
            request_id=i, arrival_ms=float(i), tenant="flood",
            prompt=list(range(4, 20)), max_new_tokens=24))
    # two vip requests arrive mid-flood with a deadline the policy must
    # protect by preempting flood work
    for j in range(2):
        events.append(TraceEvent(
            request_id=6 + j, arrival_ms=40.0 + j, tenant="vip",
            prompt=list(range(8 + j, 20 + j)), max_new_tokens=4,
            priority=5, deadline_ms=8000.0))
    spec = ScenarioSpec(
        name="isolation", seed=0, n_requests=len(events),
        tenants=(Tenant("flood"),
                 Tenant("vip", priority=5, deadline_ms=8000.0)),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                          prefix_cache=True, preempt_on_priority=True))
    trace = Trace(scenario="isolation", seed=0, events=events)
    # the deadline is wall clock and a cold engine spends it compiling (2.7
    # to 4.2 s alone, past 8 s beside five other workers: PERF.md section
    # 7), so the programs are warmed first: the same trace once through
    # the engine the timed replay then uses
    from apex_tpu.serving.scenarios.runner import _build_engine, build_model

    _, model, variables = build_model(spec.engine.model)
    engine = _build_engine(spec, model, variables)
    replay(spec, trace, engine=engine)
    outputs, stats, tracer, wall = replay(spec, trace, engine=engine)
    assert stats["preemptions"] >= 1          # vip displaced flood work
    assert stats["deadline_misses"] == 0
    vip = [tracer.lifecycle(6 + j) for j in range(2)]
    flood = [tracer.lifecycle(i) for i in range(6)]
    # vip TTFT beats the flood's tail: the burst did not starve it
    assert (max(lf["ttft_ms"] for lf in vip)
            < max(lf["ttft_ms"] for lf in flood))


def test_eviction_churn_scenario_lights_the_churn_instruments():
    """The adversarial tenant set actually thrashes the radix tree, and
    the PR's churn observability (evicted_reinserted counter + churn
    gauge) reports it."""
    metrics.clear()
    try:
        r = run_scenario(scenario_spec("eviction-churn", seed=0))
        assert r.report["aggregate"]["evicted_pages"] > 0
        assert r.report["aggregate"]["prefix_hit_rate"] > 0
        reinserted = churn = 0.0
        for inst in metrics.instruments():
            if inst.name == "prefix_cache.evicted_reinserted":
                reinserted = max(reinserted, inst.value)
            if inst.name == "prefix_cache.churn":
                churn = max(churn, inst.value)
        assert reinserted > 0, "no evicted path was ever re-inserted"
        assert churn > 0, "churn gauge never left zero"
    finally:
        metrics.clear()


def test_windowed_llama_paged_identity_and_page_drops():
    """ISSUE 9 acceptance: windowed-Llama generate(paged=True) is
    token-identical to the ROLLING-cache lock-step at window < prompt
    length, while the engine drops dead pages (O(window) live pages) and
    returns every page to the pool."""
    import jax.numpy as jnp

    from apex_tpu.models.generation import generate
    from apex_tpu.models.llama import LlamaModel
    from apex_tpu.serving.scenarios.runner import build_model

    cfg, model, v = build_model("llama-tiny-windowed")
    W = cfg.sliding_window
    rng = np.random.default_rng(2)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, W + 9)),
                         jnp.int32)                 # window < prompt
    rmodel = LlamaModel(dataclasses.replace(cfg, rolling_cache=True))
    from apex_tpu.serving import generate_paged

    ref = np.asarray(generate(rmodel, v, prompt, max_new_tokens=30))
    out, stats = generate_paged(model, v, prompt, max_new_tokens=30,
                                page_size=8, sync_every=2,
                                return_stats=True)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert stats["window_dropped_pages"] > 0


def test_windowed_scenario_runs_and_recovers_the_pool():
    r = run_scenario(scenario_spec("windowed-llama", seed=1,
                                   n_requests=4))
    assert r.report["aggregate"]["window_dropped_pages"] > 0
    assert r.report["model"] == "llama-tiny-windowed"


# --- ISSUE 11: chaos / router scenarios + the preemption-storm adversary -----


@pytest.mark.slow
def test_chaos_replica_kill_scenario_recovers_token_exact():
    """ISSUE 11 acceptance: the catalogued mid-decode replica kill
    completes every request — the greedy-identity amplifier proves the
    failover corrupted nothing — with the failure facts in the pinned
    router block. (Slow tier since
    ISSUE 15 to hold the 870 s verify wall: the kill bar stays tier-1
    twice over — tests/test_router.py::
    test_replica_kill_mid_decode_recovers_token_identical in-process and
    tests/test_http.py::
    test_router_over_http_replicas_kill_recovers_token_identical over
    the wire — and CI's chaos smoke replays this full entry with
    --check per round.)"""
    from apex_tpu.serving.scenarios.runner import _check_greedy_identity

    spec = scenario_spec("chaos-replica-kill", seed=0, n_requests=8)
    r = run_scenario(spec)
    rb = r.report["router"]
    assert rb["replicas"] == 2 and rb["replicas_alive"] == 1
    assert rb["replica_deaths"] == 1
    assert rb["failover_requests"] >= 1
    assert rb["failover_recovered_rate"] == 1.0
    # the greedy-identity amplifier, directly: every replayed output
    # (failed-over ones included) must equal lock-step generate. (The
    # scheduling-invariance half of --check runs in CI's chaos smoke
    # and the slow-tier A/B test — it re-replays the whole trace on a
    # fresh engine, which tier-1's budget doesn't need twice.)
    assert _check_greedy_identity(spec, r.trace, r.outputs) == 8
    validate_report(r.report)


@pytest.mark.slow
def test_chaos_pump_stall_scenario_is_latency_only():
    """(slow tier: the latency-not-death contract is already pinned in
    tier-1 by tests/test_router.py::test_pump_stall_is_latency_not_death;
    this adds the catalogued-scenario + amplifier form.)"""
    r = run_scenario(scenario_spec("chaos-pump-stall", seed=0),
                     check=True)
    rb = r.report["router"]
    assert rb["replica_deaths"] == 0 and rb["failovers"] == 0
    assert rb["replicas_alive"] == 2
    assert r.report["checks"]["greedy_identity_requests"] == 10


@pytest.mark.slow
def test_router_affinity_ab_beats_round_robin():
    """ISSUE 11 acceptance: the multi-tenant workload's aggregate
    prefix hit-rate under affinity routing strictly beats round-robin
    on the same trace (both numbers + the delta land in the report).
    (Slow tier: the deterministic tier-1 twin is
    tests/test_router.py::
    test_affinity_hit_rate_beats_round_robin_deterministic; CI's chaos
    smoke replays this full entry per round.)"""
    r = run_scenario(scenario_spec("router-affinity-ab", seed=0))
    rb = r.report["router"]
    assert rb["routing"] == "affinity"
    assert rb["affinity_hit_rate"] > rb["round_robin_hit_rate"]
    assert rb["affinity_delta_hit_rate"] == pytest.approx(
        rb["affinity_hit_rate"] - rb["round_robin_hit_rate"], abs=1e-3)


def test_tenant_output_tokens_override():
    """A tenant with a pinned output budget overrides the sampled
    output length (the preemption-storm's urgent-vs-bulk shape)."""
    spec = ScenarioSpec(
        name="pin", seed=0, n_requests=12,
        output_lens=Lengths(kind="uniform", lo=20, hi=30),
        tenants=(Tenant("short", output_tokens=2),))
    trace = materialize(spec)
    assert all(e.max_new_tokens == 2 for e in trace.events)


@pytest.mark.slow
def test_preemption_storm_scenario_no_compile_storm():
    """The catalogued storm replays clean: whatever preempt/resume
    cycles the pacing produced, the resume compile-key set stayed
    bounded — no compile_storm event, a bounded jit.compiles delta
    (the deterministic cycle-count pin is the frontend-driven test
    below)."""
    r = run_scenario(scenario_spec("preemption-storm", seed=0))
    eng = r.report["engine"]
    assert eng["compile_storms"] == 0
    assert eng["jit.compiles"] <= 24
    assert eng["deadline_misses"] == 0
    validate_report(r.report)


def test_preemption_storm_deterministic_cycles_bounded_compiles(rng):
    """ISSUE 11 satellite (ROADMAP 5's named gap), deterministically: a
    bulk long-runner on ONE slot is preempted by six consecutive urgent
    arrivals — six full preempt/spill/resume cycles — and the recompile
    watcher pins the resume compile-key set: zero compile_storm events
    and a bounded jit.compiles delta (page-quantized resume t_starts
    reuse their shared-admit programs instead of growing one compile
    per cycle), with the bulk output still token-identical to an
    undisturbed run."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.generation import generate
    from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
    from apex_tpu.serving import (PagedDecodeEngine,
                                  PriorityDeadlinePolicy, Request)
    from apex_tpu.serving.frontend import ServingFrontend

    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=16,
                               prefix_cache=True)
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    bulk_prompt = rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32)
    h_bulk = fe.submit(Request(prompt=bulk_prompt, max_new_tokens=36),
                       request_id=0)
    while fe.queue_depth:
        fe.pump()
    n_cycles = 6
    for k in range(n_cycles):
        fe.pump()                        # let the victim make progress
        h = fe.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, (10,)
                                ).astype(np.int32),
            max_new_tokens=2, priority=5), request_id=1 + k)
        while not h.done:                # urgent runs to completion
            fe.pump()
    fe.drain()
    stats = fe.stats()
    assert stats["preemptions"] >= n_cycles - 1
    assert stats["resumes"] >= n_cycles - 1
    # the recompile-watcher pin: no program recompiled storm-many
    # times, and the whole storm cost a bounded number of compiles
    assert stats["compile_storms"] == 0
    ring = engine.events.tail()
    assert not any(e["kind"] == "compile_storm" for e in ring)
    assert stats["jit.compiles"] <= 20, stats["jit.compiles"]
    ref = np.asarray(generate(model, v, bulk_prompt[None],
                              max_new_tokens=36))[0, 12:]
    np.testing.assert_array_equal(h_bulk.result(timeout=0), ref)


def test_chaos_specs_roundtrip_with_faults():
    """A chaos spec's fault plan survives the JSON round-trip (the
    replayability contract: same spec file, same kills)."""
    spec = scenario_spec("chaos-replica-kill", seed=3)
    back = ScenarioSpec.from_json(spec.to_json())
    assert back == spec
    assert back.faults[0].kind == "kill_replica"
    assert back.engine.replicas == 2
    # the HTTP tier's knobs round-trip too (and stay JSON-back-compat:
    # specs that predate them load with the defaults)
    spec = scenario_spec("chaos-slow-reader", seed=3)
    back = ScenarioSpec.from_json(spec.to_json())
    assert back == spec
    assert back.engine.http and back.engine.backpressure_window == 6
    assert back.engine.sse_pad_bytes == 2048
    assert back.engine.sndbuf == 4096
    assert back.faults[0].kind == "slow_reader"
    doc = json.loads(_SMALL.to_json())
    assert "http" not in json.dumps(doc) or not doc["engine"]["http"]
    assert ScenarioSpec.from_json(_SMALL.to_json()).engine.http is False


# --- ISSUE 15: the over-the-wire (HTTP/SSE) chaos tier -----------------------


def test_chaos_slow_reader_scenario_spills_over_the_wire():
    """ISSUE 15 acceptance: the catalogued slow-reader chaos replays
    over a REAL localhost socket — stalled readers cross the
    backpressure window, slots spill (never pinning pages for a
    socket), and every stream still completes token-identically on
    resume; the facts land in the report's pinned ``http`` block. (The
    tier-1 single-request twin of the spill mechanics is
    tests/test_http.py::test_backpressure_spill_resume_token_identical;
    CI's HTTP smoke replays this entry per round and banks it.)"""
    r = run_scenario(scenario_spec("chaos-slow-reader", seed=0),
                     check=True)
    hb = r.report["http"]
    assert hb["streams"] == 4 and hb["errors"] == 0
    assert hb["slow_reader_stalls"] == 2
    assert hb["backpressure_spills"] >= 1        # the no-pin proof
    assert hb["disconnects"] == 0
    assert hb["free_pages_recovered"] > 0        # pool settled clean
    assert r.report["checks"]["greedy_identity_requests"] == 4
    assert r.report["checks"]["scheduling_invariance"] is True
    validate_report(r.report)


@pytest.mark.slow
def test_chaos_disconnect_storm_prefixes_and_no_leak():
    """ISSUE 15 acceptance: mid-stream socket drops + torn submits —
    the server cancels and frees every page (the driver's in-band leak
    check), survivors complete token-identically, and each dropped
    stream's banked output is the exact prefix it read (the
    prefix-tolerant identity amplifier). (Slow tier: the tier-1
    disconnect-frees-pages twin is tests/test_http.py::
    test_disconnect_cancels_and_frees_pages; CI's HTTP smoke replays
    this full entry per round.)"""
    r = run_scenario(scenario_spec("chaos-disconnect-storm", seed=0),
                     check=True)
    hb = r.report["http"]
    assert hb["streams"] == 10 and hb["errors"] == 0
    assert hb["disconnects"] == 4
    assert hb["conn_reset_retries"] == 2
    # 4 dropped streams read exactly at=3 tokens; 6 survivors run their
    # pinned 24 out
    assert sorted(len(np.asarray(o)) for o in r.outputs) \
        == [3] * 4 + [24] * 6
    assert r.report["checks"]["greedy_identity_requests"] == 10
    validate_report(r.report)


def test_host_tier_churn_scenario_beats_tier_off():
    """ISSUE 17 acceptance: at the eviction-churn pool size the host
    spill tier turns churned re-prefills into promotes — the report's
    host_tier block banks a STRICTLY positive tier-on-vs-off hit-rate
    delta on the same trace, with the identity amplifiers green (the
    tier changed nothing about WHAT was generated, only how its K/V
    came back)."""
    r = run_scenario(scenario_spec("host-tier-churn", seed=0),
                     check=True)
    ht = r.report["host_tier"]
    assert ht["demotes"] > 0 and ht["promotes"] > 0
    assert ht["tier_on_hit_rate"] > ht["tier_off_hit_rate"]
    assert ht["tier_delta_hit_rate"] == pytest.approx(
        ht["tier_on_hit_rate"] - ht["tier_off_hit_rate"], abs=1e-3)
    assert ht["promote_hit_rate"] > 0
    assert r.report["checks"]["scheduling_invariance"] is True


# --- CLI ---------------------------------------------------------------------


def test_cli_json_document_and_trace_replay(tmp_path):
    """python -m apex_tpu.serving.scenarios writes the scenarios/v1
    document (one validated report per scenario), refuses unknown
    scenarios and foreign traces, and replays a saved trace under the
    trace's own seed."""
    from apex_tpu.serving.scenarios.__main__ import main

    out = tmp_path / "scen.json"
    rc = main(["--scenario", "bench-mixed-length", "--seed", "4",
               "--json", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["schema"] == "apex-tpu/scenarios/v1"
    assert doc["seed"] == 4 and set(doc["scenarios"]) \
        == {"bench-mixed-length"}
    rep = doc["scenarios"]["bench-mixed-length"]
    validate_report(rep)
    assert rep["aggregate"]["ttft_ms_p95"] > 0
    assert rep["aggregate"]["tpot_ms_p95"] > 0
    # unknown scenario is a usage error caught BEFORE any replay runs
    # (a typo in the last --scenario must not cost the first ones'
    # replay time), --list succeeds
    assert main(["--scenario", "nope"]) == 2
    assert main(["--scenario", "bench-mixed-length",
                 "--scenario", "nope"]) == 2
    assert main(["--list"]) == 0
    # --trace refuses a trace materialized for a DIFFERENT scenario
    # (its events carry the other spec's model bounds, and its report
    # would carry the wrong scenario's name)
    tr = tmp_path / "mixed.trace.jsonl"
    materialize(scenario_spec("bench-mixed-length", seed=4)).save(tr)
    assert main(["--scenario", "steady-poisson",
                 "--trace", str(tr)]) == 2
    # a --trace replay records the TRACE's seed (the one that
    # regenerates its sha), not the CLI --seed default
    out2 = tmp_path / "replayed.json"
    assert main(["--scenario", "bench-mixed-length",
                 "--trace", str(tr), "--json", str(out2)]) == 0
    doc2 = json.loads(out2.read_text())
    assert doc2["seed"] == 4
    assert (doc2["scenarios"]["bench-mixed-length"]["trace_sha256"]
            == doc["scenarios"]["bench-mixed-length"]["trace_sha256"])


@pytest.mark.slow
def test_cli_http_flag_drives_the_wire(tmp_path):
    """--http forces EngineSpec(http=True) on any catalog entry: the
    replay goes over real localhost SSE and the banked document grows
    the pinned http block — the flag CI's HTTP smoke is built on."""
    from apex_tpu.serving.scenarios.__main__ import main

    out = tmp_path / "http.json"
    rc = main(["--scenario", "bench-shared-prefix", "--http", "--check",
               "--seed", "0", "--json", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    rep = doc["scenarios"]["bench-shared-prefix"]
    validate_report(rep)
    hb = rep["http"]
    assert hb["streams"] == 8 and hb["errors"] == 0
    assert hb["free_pages_recovered"] > 0
    assert rep["checks"]["greedy_identity_requests"] == 8
