"""Scenario specs + the open-loop replay runner.

A :class:`ScenarioSpec` is a declarative, JSON-round-trippable bundle of
(arrival process, length distributions, tenant set, engine knobs) plus a
seed; ``materialize(spec)`` turns it into a reproducible
:class:`~apex_tpu.serving.scenarios.traces.Trace` (pure function of the
spec — same seed, byte-identical trace) and ``run_scenario`` replays the
trace open-loop through a fresh :class:`ServingFrontend`, assembling the
pinned-schema report (``report.py``).

Replay semantics: requests are submitted when their trace arrival time
comes due on the host clock (scaled by ``time_scale``), with
``Request.arrival_time`` pinned to the INTENDED arrival — so queue-wait,
TTFT, and deadline accounting measure offered load, not how quickly the
replay loop happened to spin (the standard open-loop load-gen
convention: falling behind shows up as latency, not as a slower trace).
The pump is driven synchronously on the caller's thread, exactly the
``engine.run`` discipline, so replays are single-threaded and the greedy
outputs depend only on the trace (scheduling invariance — what lets the
determinism tests pin tokens across runs with different wall-clock
behavior).

``check=True`` turns a scenario into a correctness amplifier: every
replayed request's greedy output is re-derived by lock-step
``generate`` (token identity — the engine/cache/preemption machinery
re-derives nothing), and the whole trace is re-run as a fixed batch
through ``engine.run`` at a DIFFERENT ``sync_every`` (scheduling
invariance — outputs must not depend on arrival pacing or chunk size).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.serving.scenarios import report as report_mod
from apex_tpu.serving.scenarios import tenants as tenants_mod
from apex_tpu.serving.scenarios.tenants import Tenant
from apex_tpu.serving.scenarios.traces import (Arrival, Lengths, Trace,
                                               TraceEvent)

__all__ = ["EngineSpec", "ScenarioSpec", "ScenarioResult", "MODELS",
           "model_config", "build_model", "materialize",
           "trace_requests", "replay", "run_scenario"]

#: scenario model registry: tiny CPU-fast configs (the scenario layer is
#: a workload/SLO harness, not a throughput bench — on-chip numbers
#: come from ``benchmark/`` at real sizes).
#: ``gpt2-small`` exists to materialize a trace inside a full-size
#: model's vocab/position bounds; don't replay it on CPU.
MODELS = ("gpt2-tiny", "llama-tiny", "llama-tiny-windowed",
          "gpt2-small")

_MODEL_CACHE: Dict[str, tuple] = {}


def model_config(name: str):
    if name == "gpt2-tiny":
        from apex_tpu.models.gpt import gpt_tiny_config

        return gpt_tiny_config()
    if name == "gpt2-small":
        import jax.numpy as jnp

        from apex_tpu.models.gpt import gpt2_small_config

        return gpt2_small_config(dtype=jnp.bfloat16)
    if name == "llama-tiny":
        from apex_tpu.models.llama import llama_tiny_config

        return llama_tiny_config()
    if name == "llama-tiny-windowed":
        from apex_tpu.models.llama import llama_tiny_config

        # window < typical prompt+output so the band (and the engine's
        # page drops) actually engage
        return llama_tiny_config(sliding_window=16)
    raise ValueError(f"unknown scenario model {name!r} "
                     f"(one of {MODELS})")


def build_model(name: str):
    """``(config, model, variables)`` for a registry model —
    deterministic init (``PRNGKey(0)``), cached per process so repeated
    scenario runs share one weight set."""
    if name not in _MODEL_CACHE:
        import jax
        import jax.numpy as jnp

        cfg = model_config(name)
        if name.startswith("gpt2"):
            from apex_tpu.models.gpt import GPTModel

            model = GPTModel(cfg)
        else:
            from apex_tpu.models.llama import LlamaModel

            model = LlamaModel(cfg)
        v = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))
        _MODEL_CACHE[name] = (cfg, model, v)
    return _MODEL_CACHE[name]


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """The engine/frontend half of a scenario: which model serves the
    trace and how the slots/pool/policy are configured.

    ``tensor_parallel > 1`` serves the trace through a
    :class:`~apex_tpu.serving.tp.TensorParallelPagedEngine` over a
    ``tp``-device mesh (docs/tp_serving.md) — the registry model's
    tp=1 weights are sharded on first use, so replays stay
    token-comparable to the single-chip engine and to lock-step
    ``generate`` (the ``check=True`` amplifiers bind exactly that).

    ``host_tier_bytes > 0`` gives the engine a host-RAM spill tier of
    that byte budget under the device pool (docs/serving.md "Tiered KV
    pool"): evicted/spilled refcount-0 pages demote instead of
    dropping, and churned hits promote instead of re-prefilling. The
    report then carries a ``host_tier`` block with the tier-on vs
    tier-off hit-rate A/B (the same trace re-replayed tier-off).

    ``replicas > 1`` serves the trace through a
    :class:`~apex_tpu.serving.router.ReplicaRouter` over that many
    frontend+engine replicas (docs/router.md): ``routing`` picks the
    router policy (``"affinity"`` keys on the trace event's TENANT —
    the system-prompt unit — so one tenant's requests land where its
    header pages are cached; ``"round_robin"`` is the A/B baseline),
    and ``compare_round_robin=True`` re-replays the same trace through
    a fresh round-robin router so the report's ``router`` block can
    bank both hit rates and their delta. ``ScenarioSpec.faults``
    injects deterministic chaos into the replicas
    (``serving/faults.py``).

    ``http=True`` replays the trace OVER THE WIRE: real
    ``POST /v1/generate`` SSE streams against a localhost
    :class:`~apex_tpu.serving.http.HttpServingServer`
    (``scenarios/http_driver.py``), one client thread per request —
    the outputs checked are what the clients read off their sockets,
    and the NETWORK fault kinds (``client_disconnect``,
    ``slow_reader``, ``conn_reset``) are delivered on the client side.
    ``backpressure_window`` bounds unconsumed in-flight tokens per
    stream (``ServingFrontend``'s spill-through-preemption window) and
    ``sse_pad_bytes`` pads every SSE frame so socket backpressure
    reaches that window quickly on tiny scenarios."""

    model: str = "gpt2-tiny"
    num_slots: int = 3
    page_size: int = 8
    sync_every: int = 1
    prefix_cache: bool = True
    num_pages: Optional[int] = None      # None = worst-case pool
    host_tier_bytes: int = 0             # >0 = host-RAM spill tier budget
    preempt_on_priority: bool = False
    preempt_margin_ms: float = 50.0
    tensor_parallel: int = 1             # >1 = TP mesh engine
    replicas: int = 1                    # >1 = ReplicaRouter DP serving
    routing: str = "affinity"            # router policy (replicas > 1)
    compare_round_robin: bool = False    # bank the affinity-vs-RR A/B
    http: bool = False                   # replay over localhost HTTP/SSE
    backpressure_window: Optional[int] = None  # frontend spill window
    sse_pad_bytes: int = 0               # pad SSE frames (chaos knob)
    sndbuf: Optional[int] = None         # shrink kernel send buffer
    #                                      (socket backpressure reaches
    #                                      the window fast; chaos knob)


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One declarative scenario. ``materialize`` consumes everything but
    ``engine``/``time_scale``; ``replay`` consumes those."""

    name: str
    seed: int = 0
    n_requests: int = 24
    arrival: Arrival = Arrival()
    prompt_lens: Lengths = Lengths()
    output_lens: Lengths = Lengths(kind="uniform", lo=4, hi=12)
    tenants: Tuple[Tenant, ...] = (Tenant("default"),)
    engine: EngineSpec = EngineSpec()
    time_scale: float = 1.0              # arrival-time multiplier at replay
    description: str = ""
    #: deterministic chaos plan (``serving/faults.py``) delivered into
    #: the replica frontends at replay — only meaningful with
    #: ``engine.replicas > 1`` (a single frontend has no survivor)
    faults: Tuple = ()

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True,
                          indent=2)

    @classmethod
    def from_json(cls, text: str) -> "ScenarioSpec":
        from apex_tpu.serving.faults import FaultSpec

        d = json.loads(text)
        d["arrival"] = Arrival(**d.get("arrival", {}))
        d["prompt_lens"] = Lengths(**d.get("prompt_lens", {}))
        d["output_lens"] = Lengths(**d.get("output_lens", {}))
        d["tenants"] = tuple(Tenant(**t) for t in d.get("tenants", ()))
        d["engine"] = EngineSpec(**d.get("engine", {}))
        d["faults"] = tuple(FaultSpec(**f) for f in d.get("faults", ()))
        return cls(**d)


@dataclasses.dataclass
class ScenarioResult:
    """One run's artifacts: the pinned-schema ``report`` (serialized),
    plus the in-memory trace/outputs the tests pin determinism over."""

    spec: ScenarioSpec
    trace: Trace
    outputs: List[np.ndarray]
    stats: dict
    report: dict
    #: the router's kill-triggered postmortem bundle (replicated chaos
    #: scenarios where a replica died; None otherwise) — schema-pinned,
    #: ``apex_tpu.obs.fleet.validate_flight``-clean
    flight: Optional[dict] = None


def materialize(spec: ScenarioSpec) -> Trace:
    """Sample the spec into a trace — a pure function of the spec (the
    PRNG is ``default_rng(spec.seed)`` and nothing else): arrivals,
    tenant assignment, tenant-header + random-tail prompts, output
    budgets, all clipped to the model's position table."""
    cfg = model_config(spec.engine.model)
    max_pos = cfg.max_position_embeddings
    rng = np.random.default_rng(spec.seed)
    n = spec.n_requests
    arrivals = spec.arrival.sample_ms(n, rng)
    tails = spec.prompt_lens.sample(n, rng)
    outs = spec.output_lens.sample(n, rng)
    t_idx = tenants_mod.assign_tenants(spec.tenants, n, rng)
    headers = [tenants_mod.system_prompt(t, cfg.vocab_size, spec.seed)
               for t in spec.tenants]
    events: List[TraceEvent] = []
    for name, header in zip((t.name for t in spec.tenants), headers):
        if header.shape[0] > max_pos - 2:
            raise ValueError(
                f"scenario {spec.name!r}: tenant {name!r}'s system "
                f"prompt ({header.shape[0]} tokens) leaves no room in "
                f"{spec.engine.model!r}'s position table ({max_pos}) "
                f"for the >=1 tail + >=1 generated token every request "
                f"needs")
    for i in range(n):
        ten = spec.tenants[int(t_idx[i])]
        header = headers[int(t_idx[i])]
        # clip to the position table: header + >=1 tail token + >=1
        # generated token must all fit (header length validated above)
        tail_len = int(np.clip(tails[i], 1,
                               max_pos - 1 - header.shape[0]))
        tail = rng.integers(0, cfg.vocab_size, tail_len)
        prompt = np.concatenate([header, tail.astype(np.int32)])
        # a tenant with a pinned output budget overrides the sampled one
        want_out = ten.output_tokens if ten.output_tokens is not None \
            else outs[i]
        max_new = int(np.clip(want_out, 1, max_pos - prompt.shape[0]))
        events.append(TraceEvent(
            request_id=i, arrival_ms=float(arrivals[i]),
            tenant=ten.name, prompt=[int(t) for t in prompt],
            max_new_tokens=max_new, priority=ten.priority,
            deadline_ms=ten.deadline_ms, tpot_slo_ms=ten.tpot_slo_ms))
    return Trace(scenario=spec.name, seed=spec.seed, events=events)


def _event_request(e: TraceEvent, *, arrival_time=None):
    """The single TraceEvent -> Request mapping (every consumer builds
    through here, so a new trace-carried field cannot silently reach
    only one of the replay / fixed-batch paths)."""
    from apex_tpu.serving.scheduler import Request

    return Request(prompt=np.asarray(e.prompt, np.int32),
                   max_new_tokens=e.max_new_tokens,
                   priority=e.priority, deadline_ms=e.deadline_ms,
                   arrival_time=arrival_time, tpot_slo_ms=e.tpot_slo_ms)


def trace_requests(trace: Trace) -> List:
    """The trace's events as engine ``Request`` objects (arrival times
    are the REPLAY loop's business — a fixed-list ``engine.run`` over
    these ignores pacing, which is exactly what the bench's closed-loop
    throughput sections want)."""
    return [_event_request(e) for e in trace.events]


_TP_MODEL_CACHE: Dict[tuple, tuple] = {}


def _build_tp_model(name: str, tp: int):
    """``(config, model, sharded_variables, mesh)`` for a registry model
    at tensor-parallel degree ``tp`` — the tp=1 cached weights sliced
    over a fresh ``tp``-device mesh, cached per (name, tp) like
    ``build_model``."""
    if (name, tp) not in _TP_MODEL_CACHE:
        import dataclasses as _dc

        from apex_tpu.serving.tp import shard_model_variables, tp_mesh

        cfg, model, v = build_model(name)
        cfg_tp = _dc.replace(cfg, tensor_parallel_size=tp)
        model_tp = type(model)(cfg_tp)
        mesh = tp_mesh(tp)
        v_tp, _ = shard_model_variables(model_tp, v, mesh)
        _TP_MODEL_CACHE[(name, tp)] = (cfg_tp, model_tp, v_tp, mesh)
    return _TP_MODEL_CACHE[(name, tp)]


def _build_engine(spec: ScenarioSpec, model, variables, *,
                  sync_every: Optional[int] = None):
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    es = spec.engine
    kw = dict(num_slots=es.num_slots, page_size=es.page_size,
              num_pages=es.num_pages,
              sync_every=sync_every if sync_every is not None
              else es.sync_every,
              prefix_cache=es.prefix_cache,
              host_tier_bytes=es.host_tier_bytes or None)
    if es.tensor_parallel > 1:
        from apex_tpu.serving.tp import TensorParallelPagedEngine

        _, model_tp, v_tp, mesh = _build_tp_model(es.model,
                                                  es.tensor_parallel)
        return TensorParallelPagedEngine(model_tp, v_tp, mesh=mesh, **kw)
    return PagedDecodeEngine(model, variables, **kw)


def _build_router(spec: ScenarioSpec, model, variables, *,
                  routing: Optional[str] = None, faults=None):
    """N fresh frontend+engine replicas behind one
    :class:`~apex_tpu.serving.router.ReplicaRouter`, with the spec's
    fault plan (or an override) injected through the frontends' fault
    hooks."""
    from apex_tpu.serving.faults import FaultPlan
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.policy import PriorityDeadlinePolicy
    from apex_tpu.serving.router import ReplicaRouter, RouterPolicy

    es = spec.engine
    plan = FaultPlan(specs=tuple(spec.faults if faults is None
                                 else faults))
    frontends = []
    for i in range(es.replicas):
        engine = _build_engine(spec, model, variables)
        policy = PriorityDeadlinePolicy(
            preempt_on_priority=es.preempt_on_priority,
            preempt_margin_ms=es.preempt_margin_ms)
        frontends.append(ServingFrontend(engine, policy=policy,
                                         fault_hook=plan.injector(i)))
    return ReplicaRouter(
        frontends,
        policy=RouterPolicy(routing=routing if routing is not None
                            else es.routing,
                            backoff_base_ms=2.0))


def _replay_router(spec: ScenarioSpec, trace: Trace, router):
    """Open-loop replay through a :class:`ReplicaRouter` (the
    ``engine.replicas > 1`` path): affinity keys on the trace event's
    TENANT (the system-prompt unit), the router's synchronous ``pump``
    drives every replica. Raises if any request failed terminally —
    catalog chaos scenarios are sized to always recover; non-recovery
    coverage lives in tests/test_router.py."""
    events = trace.events
    scale = spec.time_scale
    handles = {}
    t0 = time.perf_counter()
    i = 0
    while i < len(events):
        now_s = time.perf_counter() - t0
        while (i < len(events)
               and events[i].arrival_ms * scale * 1e-3 <= now_s):
            e = events[i]
            req = _event_request(
                e, arrival_time=t0 + e.arrival_ms * scale * 1e-3)
            handles[e.request_id] = router.submit(
                req, request_id=e.request_id, affinity_key=e.tenant)
            i += 1
        if not router.pump() and i < len(events):
            gap = (events[i].arrival_ms * scale * 1e-3
                   - (time.perf_counter() - t0))
            time.sleep(min(max(gap, 0.0), 0.002))
    router.drain()
    wall_s = time.perf_counter() - t0
    outputs = [np.asarray(handles[e.request_id].result(timeout=0),
                          np.int32) for e in events]
    return outputs, wall_s


def replay(spec: ScenarioSpec, trace: Trace, *, engine=None):
    """Open-loop replay of ``trace`` through a fresh frontend; returns
    ``(outputs, stats, tracer, wall_s)``. ``engine=`` injects a
    pre-built (e.g. pre-warmed) engine. With ``engine.replicas > 1``
    the trace replays through a fresh :class:`ReplicaRouter` instead —
    ``stats`` is then the router's stats dict (aggregated engine
    counters included) and ``tracer`` the router's cross-replica
    lifecycle adapter."""
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.policy import PriorityDeadlinePolicy

    if spec.engine.http and engine is None:
        from apex_tpu.serving.scenarios.http_driver import replay_http

        outputs, stats, tracer, wall_s, http_block = replay_http(
            spec, trace)
        stats = dict(stats)
        stats["http"] = http_block       # run_scenario lifts this out
        return outputs, stats, tracer, wall_s
    if spec.engine.replicas > 1 and engine is None:
        _, model, v = build_model(spec.engine.model)
        router = _build_router(spec, model, v)
        outputs, wall_s = _replay_router(spec, trace, router)
        # one final federation pass so the banked fleet block reflects
        # end-of-run state; the kill-triggered flight (if any replica
        # died) rides along for run_scenario to lift out
        router.fleet.tick(force=True)
        stats = router.stats()
        stats["flight"] = router.last_flight
        return outputs, stats, router, wall_s
    if engine is None:
        _, model, v = build_model(spec.engine.model)
        engine = _build_engine(spec, model, v)
    policy = PriorityDeadlinePolicy(
        preempt_on_priority=spec.engine.preempt_on_priority,
        preempt_margin_ms=spec.engine.preempt_margin_ms)
    frontend = ServingFrontend(engine, policy=policy)
    events = trace.events
    scale = spec.time_scale
    handles = {}
    t0 = time.perf_counter()
    i = 0
    while i < len(events):
        now_s = time.perf_counter() - t0
        while (i < len(events)
               and events[i].arrival_ms * scale * 1e-3 <= now_s):
            e = events[i]
            req = _event_request(
                e, arrival_time=t0 + e.arrival_ms * scale * 1e-3)
            handles[e.request_id] = frontend.submit(
                req, request_id=e.request_id)
            i += 1
        if not frontend.pump() and i < len(events):
            # idle before the next arrival: nap up to it (bounded so the
            # loop stays responsive to device completions)
            gap = (events[i].arrival_ms * scale * 1e-3
                   - (time.perf_counter() - t0))
            time.sleep(min(max(gap, 0.0), 0.002))
    frontend.drain()
    wall_s = time.perf_counter() - t0
    outputs = [np.asarray(handles[e.request_id].result(timeout=0),
                          np.int32) for e in events]
    return outputs, frontend.stats(), frontend.tracer, wall_s


def _net_prefix_ids(spec: ScenarioSpec) -> set:
    """Request ids whose replayed output is a PREFIX by design: a
    ``client_disconnect`` drops the stream after ``at`` tokens, so the
    client banked only what it read before dropping (the server then
    cancels at the next sync boundary — the amplifiers must tolerate
    the truncation but still bind every delivered token)."""
    ids: set = set()
    for f in spec.faults:
        if getattr(f, "kind", None) == "client_disconnect":
            ids.update(range(f.count))
    return ids


def _check_greedy_identity(spec: ScenarioSpec, trace: Trace,
                           outputs: List[np.ndarray],
                           limit: int = 16) -> int:
    """Token identity vs lock-step ``generate`` for up to ``limit``
    replayed requests (tiny models — each re-derivation is one eager
    prefill + scan). Raises AssertionError on the first mismatch.
    Disconnect-faulted ids (``_net_prefix_ids``) compare as prefixes —
    every token the client read must still be the lock-step token."""
    from apex_tpu.models.generation import generate

    prefix_ok = _net_prefix_ids(spec)
    _, model, v = build_model(spec.engine.model)
    n = min(len(trace.events), limit)
    for e, out in list(zip(trace.events, outputs))[:n]:
        prompt = np.asarray(e.prompt, np.int32)
        ref = np.asarray(generate(model, v, prompt[None],
                                  max_new_tokens=e.max_new_tokens))
        ref_gen = ref[0, prompt.shape[0]:]
        got = np.asarray(out)
        if e.request_id in prefix_ok:
            ref_gen = ref_gen[:got.shape[0]]
        if not np.array_equal(got, ref_gen):
            raise AssertionError(
                f"scenario {spec.name!r} request {e.request_id}: "
                f"replayed greedy output diverges from lock-step "
                f"generate ({got[:8]}... vs "
                f"{ref_gen[:8]}...)")
    return n


def _check_scheduling_invariance(spec: ScenarioSpec, trace: Trace,
                                 outputs: List[np.ndarray]) -> None:
    """Re-run the SAME trace as a fixed batch through ``engine.run`` at
    a different ``sync_every`` — greedy outputs must not depend on
    arrival pacing, admission order, or chunk size.
    Disconnect-faulted ids compare as prefixes (the fixed batch runs
    them to completion; the replay banked what the client read)."""
    prefix_ok = _net_prefix_ids(spec)
    _, model, v = build_model(spec.engine.model)
    alt_sync = spec.engine.sync_every % 3 + 1     # always != sync_every
    engine = _build_engine(spec, model, v, sync_every=alt_sync)
    outs2, _ = engine.run(trace_requests(trace))
    for e, a, b in zip(trace.events, outputs, outs2):
        a, b = np.asarray(a), np.asarray(b)
        if e.request_id in prefix_ok:
            b = b[:a.shape[0]]
        if not np.array_equal(a, b):
            raise AssertionError(
                f"scenario {spec.name!r} request {e.request_id}: "
                f"greedy output changed under a different schedule "
                f"(sync_every {spec.engine.sync_every} -> {alt_sync})")


def _router_block(spec: ScenarioSpec, trace: Trace,
                  stats: dict) -> dict:
    """The report's ``router`` block for a replicated scenario:
    supervision/failover facts plus — with ``compare_round_robin`` —
    the affinity-vs-round-robin hit-rate A/B (the same trace re-played
    through a fresh round-robin router, faults stripped so the baseline
    measures routing, not luck-of-the-kill)."""
    block = {
        "replicas": int(stats.get("replicas", 0)),
        "replicas_alive": int(stats.get("replicas_alive", 0)),
        "routing": spec.engine.routing,
        "failovers": int(stats.get("failovers", 0)),
        "failover_requests": int(stats.get("failover_requests", 0)),
        "failover_recovered": int(stats.get("failover_recovered", 0)),
        "failover_recovered_rate":
            round(float(stats.get("failover_recovered_rate", 1.0)), 4),
        "shed_requests": int(stats.get("shed_requests", 0)),
        "migrations": int(stats.get("migrations", 0)),
        "replica_deaths": int(stats.get("replica_deaths", 0)),
        "affinity_hit_rate":
            round(float(stats.get("prefix_hit_rate", 0.0)), 4),
    }
    if spec.engine.compare_round_robin:
        _, model, v = build_model(spec.engine.model)
        rr_router = _build_router(spec, model, v,
                                  routing="round_robin", faults=())
        _replay_router(spec, trace, rr_router)
        rr_stats = rr_router.stats()
        rr_rate = round(float(rr_stats.get("prefix_hit_rate", 0.0)), 4)
        block["round_robin_hit_rate"] = rr_rate
        block["affinity_delta_hit_rate"] = round(
            block["affinity_hit_rate"] - rr_rate, 4)
    return block


def _host_tier_block(spec: ScenarioSpec, trace: Trace,
                     stats: dict) -> dict:
    """The report's ``host_tier`` block for a tiered scenario
    (``engine.host_tier_bytes > 0``): the tier's demote/promote facts
    plus the tier-on vs tier-off hit-rate A/B — the same trace
    re-replayed through a fresh engine with the tier OFF, so the banked
    delta measures what demote/promote earned, not workload luck. The
    acceptance bar (docs/scenarios.md): at a thrash-sized pool the
    delta must be strictly positive."""
    tier_on_rate = round(float(stats.get("prefix_hit_rate", 0.0)), 4)
    off_spec = dataclasses.replace(
        spec, engine=dataclasses.replace(spec.engine, host_tier_bytes=0))
    _, off_stats, _, _ = replay(off_spec, trace)
    tier_off_rate = round(float(off_stats.get("prefix_hit_rate", 0.0)), 4)
    return {
        "budget_bytes": int(spec.engine.host_tier_bytes),
        "demotes": int(stats.get("host_tier_demotes", 0)),
        "promotes": int(stats.get("host_tier_promotes", 0)),
        "host_evicted_pages": int(stats.get("host_tier_evicted_pages",
                                            0)),
        "promote_hit_rate":
            round(float(stats.get("host_tier_promote_hit_rate", 0.0)), 4),
        "tier_on_hit_rate": tier_on_rate,
        "tier_off_hit_rate": tier_off_rate,
        "tier_delta_hit_rate": round(tier_on_rate - tier_off_rate, 4),
    }


def run_scenario(spec: ScenarioSpec, *, check: bool = False,
                 trace: Optional[Trace] = None) -> ScenarioResult:
    """Materialize (unless a saved ``trace`` is injected), replay, and
    report one scenario. ``check=True`` additionally runs the
    token-identity and scheduling-invariance amplifiers and records
    their outcome under ``report["checks"]`` (raising on divergence).
    Replicated scenarios (``engine.replicas > 1``) add the ``router``
    block — failover/recovery facts and, with
    ``compare_round_robin``, the affinity-vs-round-robin hit-rate A/B.
    Tiered scenarios (``engine.host_tier_bytes > 0``) add the
    ``host_tier`` block — demote/promote facts and the tier-on vs
    tier-off hit-rate A/B on the same trace."""
    if trace is None:
        trace = materialize(spec)
    outputs, stats, tracer, wall_s = replay(spec, trace)
    http_block = stats.pop("http", None) if isinstance(stats, dict) \
        else None
    fleet_block = stats.pop("fleet", None) if isinstance(stats, dict) \
        else None
    flight = stats.pop("flight", None) if isinstance(stats, dict) \
        else None
    checks = None
    if check:
        n_checked = _check_greedy_identity(spec, trace, outputs)
        _check_scheduling_invariance(spec, trace, outputs)
        checks = {"greedy_identity_requests": n_checked,
                  "scheduling_invariance": True}
    router_block = _router_block(spec, trace, stats) \
        if spec.engine.replicas > 1 else None
    host_tier_block = _host_tier_block(spec, trace, stats) \
        if spec.engine.host_tier_bytes > 0 else None
    rep = report_mod.build_report(spec, trace, outputs, stats, tracer,
                                  wall_s, checks=checks,
                                  router=router_block, http=http_block,
                                  host_tier=host_tier_block,
                                  fleet=fleet_block)
    report_mod.validate_report(rep)
    return ScenarioResult(spec=spec, trace=trace, outputs=outputs,
                          stats=stats, report=rep, flight=flight)
