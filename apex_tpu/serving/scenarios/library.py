"""The named scenario catalog — one registry, ``--list``-able.

Every entry is a factory ``(seed) -> ScenarioSpec`` registered under a
stable name; ``scenario_spec(name, seed, **overrides)`` builds the spec
and applies top-level ``dataclasses.replace`` overrides (how the tests
scale a scenario up or down without forking its definition).
Sizes here are deliberately tiny-model/CPU-tier: a scenario is a
workload SHAPE + SLO harness, reproducible in tier-1 time — on-chip
throughput numbers are ``benchmark/``'s business.

The catalog (docs/scenarios.md has the prose):

- ``steady-poisson`` — the baseline: memoryless arrivals, lognormal
  lengths, one tenant, no SLOs. The sanity row every other scenario is
  read against.
- ``burst-storm`` — on/off Markov-modulated arrivals with TTFT
  deadlines into few slots: queueing spikes, deadline misses, and the
  policy's EDF ordering under pressure.
- ``long-tail-lengths`` — Zipf prompt AND output lengths: a few huge
  requests among many small ones (continuous batching's reason to
  exist; the step-savings and occupancy counters tell the story).
- ``multi-tenant-shared-prefix`` — three tenants with distinct system
  prompts and distinct priority/deadline/TPOT-SLO profiles contending
  for one radix cache: per-tenant SLO splits + cross-request hit rate.
- ``eviction-churn`` — the adversary: more cacheable header pages than
  the pool holds, so admissions evict each other's headers and the tree
  thrashes (``prefix_cache.churn`` / ``evicted_reinserted`` light up).
- ``host-tier-churn`` — eviction-churn with a host-RAM spill tier
  under the same thrash-sized pool (``EngineSpec.host_tier_bytes``):
  churned hits promote instead of re-prefilling, and the report's
  ``host_tier`` block banks the tier-on-vs-off hit-rate A/B (strictly
  positive delta is the acceptance bar).
- ``priority-flood`` — a low-priority flood pinning every slot while a
  high-priority deadline stream arrives: preempt-and-spill under
  ``preempt_on_priority``, priority-inversion bounded.
- ``tp-shared-prefix`` — the multi-tenant radix-cache workload replayed
  through the tp=2 TENSOR-PARALLEL engine (``serving/tp.py``): hits,
  SLO splits, and contention must compose with the head-sharded pool.
- ``windowed-llama`` — sliding-window Llama on the PAGED path (the band
  rides the paged kernel, dead pages drop at sync boundaries): long
  generations at O(window) live pages per slot.
- ``bench-mixed-length`` / ``bench-shared-prefix`` — mixed
  prompt/output lengths, and one system prompt before random tails: the
  two workloads the CLI tests and the ``--http`` smoke replay.
- ``preemption-storm`` — the ROADMAP-5 adversary: a rapid
  high-priority deadline stream over one slot forces repeated
  preempt/resume cycles on a long-running bulk request; the recompile
  watcher pins the resume compile-key set (no ``compile_storm`` event,
  bounded ``jit.compiles``).
- ``chaos-replica-kill`` — replicated serving (``serving/router.py``)
  with a seeded mid-decode replica kill (``serving/faults.py``): every
  in-flight request must re-home to the survivor token-identically
  (the greedy-identity amplifier proves recovery corrupts nothing);
  the kill triggers the flight recorder and the report banks the
  federated ``fleet`` block (docs/observability.md "Fleet plane").
- ``chaos-pump-stall`` — a wedged-but-alive replica (injected pump
  stalls): latency, not death — nothing may hang, fail over, or leak.
- ``chaos-slow-reader`` — the replay driven over real localhost HTTP
  (``EngineSpec(http=True)``, scenarios/http_driver.py): clients stop
  reading their SSE streams mid-generation, unconsumed tokens cross the
  frontend's ``backpressure_window``, the slot spills into the radix
  cache, and every stream still completes token-identically when the
  reader resumes — the no-pin contract, banked (``http.
  backpressure_spills``).
- ``chaos-disconnect-storm`` — the HTTP replay under network chaos:
  several clients drop their sockets for real mid-stream and two tear
  their connections mid-request (RST) then retry; the server must
  cancel, free every page, and keep serving — surviving outputs
  token-identical, dropped ones exact prefixes.
- ``router-affinity-ab`` — the multi-tenant workload over 2 replicas,
  replayed under affinity routing AND round-robin on the same trace:
  the aggregate prefix hit-rate delta is the banked proof affinity
  routing earns its keep.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

from apex_tpu.serving.faults import FaultSpec
from apex_tpu.serving.scenarios.runner import EngineSpec, ScenarioSpec
from apex_tpu.serving.scenarios.tenants import Tenant, churn_tenants
from apex_tpu.serving.scenarios.traces import Arrival, Lengths

__all__ = ["SCENARIOS", "register", "scenario_names", "scenario_spec"]

SCENARIOS: Dict[str, Callable[[int], ScenarioSpec]] = {}


def register(name: str):
    def deco(fn: Callable[[int], ScenarioSpec]):
        SCENARIOS[name] = fn
        return fn
    return deco


def scenario_names() -> List[str]:
    return sorted(SCENARIOS)


def scenario_spec(name: str, seed: int = 0,
                  **overrides) -> ScenarioSpec:
    """Build a catalog scenario at ``seed``, with optional top-level
    field overrides (``n_requests=``, ``engine=``, ``prompt_lens=``,
    ...)."""
    if name not in SCENARIOS:
        raise KeyError(f"unknown scenario {name!r}; have "
                       f"{scenario_names()}")
    spec = SCENARIOS[name](seed)
    return dataclasses.replace(spec, **overrides) if overrides else spec


@register("steady-poisson")
def _steady_poisson(seed: int) -> ScenarioSpec:
    return ScenarioSpec(
        name="steady-poisson", seed=seed, n_requests=20,
        arrival=Arrival(kind="poisson", rate_rps=400.0),
        prompt_lens=Lengths(kind="lognormal", mean=20.0, sigma=0.5,
                            lo=4, hi=48),
        output_lens=Lengths(kind="uniform", lo=4, hi=10),
        tenants=(Tenant("default"),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=8,
                          prefix_cache=False),
        description="memoryless open-loop baseline, one tenant")


@register("burst-storm")
def _burst_storm(seed: int) -> ScenarioSpec:
    return ScenarioSpec(
        name="burst-storm", seed=seed, n_requests=24,
        arrival=Arrival(kind="bursty", burst_rate_rps=2000.0,
                        idle_rate_rps=40.0, mean_burst_s=0.015,
                        mean_idle_s=0.06),
        prompt_lens=Lengths(kind="lognormal", mean=16.0, sigma=0.5,
                            lo=4, hi=40),
        output_lens=Lengths(kind="uniform", lo=4, hi=10),
        tenants=(Tenant("bursty", deadline_ms=250.0),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                          prefix_cache=False),
        description="on/off MMPP arrivals + TTFT deadlines into 2 slots")


@register("long-tail-lengths")
def _long_tail(seed: int) -> ScenarioSpec:
    return ScenarioSpec(
        name="long-tail-lengths", seed=seed, n_requests=20,
        arrival=Arrival(kind="poisson", rate_rps=300.0),
        prompt_lens=Lengths(kind="zipf", zipf_a=1.4, lo=4, hi=80),
        output_lens=Lengths(kind="zipf", zipf_a=1.6, lo=2, hi=32),
        tenants=(Tenant("default"),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=8,
                          prefix_cache=False),
        description="Zipf prompt+output mix: few huge, many small")


@register("multi-tenant-shared-prefix")
def _multi_tenant(seed: int) -> ScenarioSpec:
    ps = 8
    return ScenarioSpec(
        name="multi-tenant-shared-prefix", seed=seed, n_requests=24,
        arrival=Arrival(kind="poisson", rate_rps=400.0),
        prompt_lens=Lengths(kind="lognormal", mean=10.0, sigma=0.5,
                            lo=2, hi=24),
        output_lens=Lengths(kind="uniform", lo=4, hi=10),
        tenants=(
            Tenant("free", weight=2.0, system_prompt_tokens=2 * ps),
            Tenant("pro", weight=1.0, system_prompt_tokens=4 * ps,
                   priority=2, deadline_ms=400.0),
            Tenant("batch", weight=1.0, system_prompt_tokens=2 * ps,
                   tpot_slo_ms=500.0),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=ps,
                          prefix_cache=True),
        description="3 tenants, distinct headers + SLO profiles, one "
                    "radix cache")


@register("eviction-churn")
def _eviction_churn(seed: int) -> ScenarioSpec:
    ps = 8
    # 8 tenants x 4 header pages = 32 cacheable pages vs a 23-page pool:
    # the tree cannot hold every header and admissions evict each other
    return ScenarioSpec(
        name="eviction-churn", seed=seed, n_requests=32,
        arrival=Arrival(kind="closed", users=4, think_ms=4.0),
        prompt_lens=Lengths(kind="uniform", lo=1, hi=8),
        output_lens=Lengths(kind="uniform", lo=2, hi=6),
        tenants=churn_tenants(8, 4, ps),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=ps,
                          prefix_cache=True, num_pages=24),
        description="adversarial header set > pool capacity: radix "
                    "thrash")


@register("host-tier-churn")
def _host_tier_churn(seed: int) -> ScenarioSpec:
    ps = 8
    # the eviction-churn adversary with a host-RAM spill tier under the
    # same thrash-sized pool: every churned header eviction demotes and
    # every revisit promotes, so the banked host_tier block's
    # tier-on-vs-off hit-rate delta must be strictly positive
    return ScenarioSpec(
        name="host-tier-churn", seed=seed, n_requests=32,
        arrival=Arrival(kind="closed", users=4, think_ms=4.0),
        prompt_lens=Lengths(kind="uniform", lo=1, hi=8),
        output_lens=Lengths(kind="uniform", lo=2, hi=6),
        tenants=churn_tenants(8, 4, ps),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=ps,
                          prefix_cache=True, num_pages=24,
                          host_tier_bytes=1 << 24),
        description="eviction-churn with a host spill tier: churned "
                    "hits promote instead of re-prefilling")


@register("priority-flood")
def _priority_flood(seed: int) -> ScenarioSpec:
    return ScenarioSpec(
        name="priority-flood", seed=seed, n_requests=24,
        arrival=Arrival(kind="poisson", rate_rps=600.0),
        prompt_lens=Lengths(kind="uniform", lo=8, hi=24),
        output_lens=Lengths(kind="uniform", lo=8, hi=16),
        tenants=(
            Tenant("flood", weight=5.0),
            Tenant("urgent", weight=1.0, priority=5, deadline_ms=60.0),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                          prefix_cache=True, preempt_on_priority=True),
        description="low-priority flood vs high-priority deadline "
                    "stream: preempt-and-spill")


@register("tp-shared-prefix")
def _tp_shared_prefix(seed: int) -> ScenarioSpec:
    ps = 8
    # the multi-tenant radix-cache workload on the TENSOR-PARALLEL
    # engine (serving/tp.py, docs/tp_serving.md): three tenants with
    # distinct headers + SLO profiles replayed through a tp=2 mesh —
    # prefix hits, preemption-free contention, and per-tenant SLO
    # splits must all compose with the head-sharded pool. Needs >= 2
    # devices (tests/CI force 8 CPU devices; the CLI raises otherwise).
    return ScenarioSpec(
        name="tp-shared-prefix", seed=seed, n_requests=16,
        arrival=Arrival(kind="poisson", rate_rps=400.0),
        prompt_lens=Lengths(kind="lognormal", mean=10.0, sigma=0.5,
                            lo=2, hi=24),
        output_lens=Lengths(kind="uniform", lo=4, hi=10),
        tenants=(
            Tenant("free", weight=2.0, system_prompt_tokens=2 * ps),
            Tenant("pro", weight=1.0, system_prompt_tokens=4 * ps,
                   priority=2, deadline_ms=400.0),
            Tenant("batch", weight=1.0, system_prompt_tokens=2 * ps,
                   tpot_slo_ms=500.0),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=ps,
                          prefix_cache=True, tensor_parallel=2),
        description="multi-tenant shared-prefix replay through the "
                    "tp=2 tensor-parallel engine")


@register("windowed-llama")
def _windowed_llama(seed: int) -> ScenarioSpec:
    return ScenarioSpec(
        name="windowed-llama", seed=seed, n_requests=10,
        arrival=Arrival(kind="poisson", rate_rps=300.0),
        prompt_lens=Lengths(kind="uniform", lo=8, hi=32),
        output_lens=Lengths(kind="uniform", lo=24, hi=40),
        tenants=(Tenant("default"),),
        engine=EngineSpec(model="llama-tiny-windowed", num_slots=2,
                          page_size=8, sync_every=2,
                          prefix_cache=False),
        description="sliding-window Llama on the paged path: "
                    "generations past the window drop dead pages")


@register("preemption-storm")
def _preemption_storm(seed: int) -> ScenarioSpec:
    ps = 16
    # ONE slot, a long-running bulk stream, and a rapid deadline-armed
    # urgent stream: every urgent arrival preempts the bulk victim,
    # which resumes (spill -> cache-hit re-admission) when the urgent
    # request retires — many preempt/resume cycles per replay. The
    # page size is deliberately LARGE and the urgent bursts short, so
    # the victim's written length crosses few page boundaries and the
    # resume compile-key set (t_start values) stays small — the
    # recompile-watcher pin (no compile_storm, bounded jit.compiles)
    # binds exactly that design rule (docs/frontend.md Limits).
    # arrivals are PACED against the tiny model's CPU decode step
    # (~5-15 ms): a bulk long-runner must actually be decoding when the
    # next urgent request lands, or priority ordering alone would serve
    # the queue and nothing would ever preempt
    return ScenarioSpec(
        name="preemption-storm", seed=seed, n_requests=16,
        arrival=Arrival(kind="poisson", rate_rps=5.0),
        prompt_lens=Lengths(kind="uniform", lo=8, hi=14),
        output_lens=Lengths(kind="uniform", lo=24, hi=32),
        tenants=(
            Tenant("bulk", weight=1.0, output_tokens=40),
            Tenant("urgent", weight=2.0, priority=5,
                   deadline_ms=10000.0, output_tokens=2),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=1, page_size=ps,
                          prefix_cache=True, preempt_on_priority=True),
        description="repeated preempt/resume cycles on one slot; the "
                    "resume compile-key set must stay bounded")


@register("chaos-replica-kill")
def _chaos_replica_kill(seed: int) -> ScenarioSpec:
    ps = 8
    # 2 replicas, one killed mid-decode at its 3rd pump iteration:
    # every request it held (active, pending, mid-stream) must re-home
    # to the survivor with its generated-so-far tokens folded into the
    # resume prompt — greedy outputs identical to an unfailed run (the
    # check amplifier), zero hung handles, zero leaked pages. The kill
    # also exercises the fleet plane: the report banks the federated
    # ``fleet`` block and the death triggers the flight recorder, so
    # the CI round banks FLEET_/FLIGHT_ artifacts off this scenario
    # (``--fleet``/``--flight``; docs/observability.md "Fleet plane")
    return ScenarioSpec(
        name="chaos-replica-kill", seed=seed, n_requests=12,
        arrival=Arrival(kind="poisson", rate_rps=600.0),
        prompt_lens=Lengths(kind="uniform", lo=6, hi=20),
        output_lens=Lengths(kind="uniform", lo=6, hi=12),
        tenants=(
            Tenant("alpha", weight=1.0, system_prompt_tokens=2 * ps),
            Tenant("beta", weight=1.0, system_prompt_tokens=2 * ps),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=ps,
                          prefix_cache=True, replicas=2),
        faults=(FaultSpec(kind="kill_replica", replica=0, at=3),),
        description="seeded mid-decode replica kill: recovery must be "
                    "token-exact on the survivor")


@register("chaos-pump-stall")
def _chaos_pump_stall(seed: int) -> ScenarioSpec:
    # a wedged-but-alive replica: the pump sleeps 20 ms for 4
    # iterations — pure latency; nothing may die, fail over, or leak
    return ScenarioSpec(
        name="chaos-pump-stall", seed=seed, n_requests=10,
        arrival=Arrival(kind="poisson", rate_rps=600.0),
        prompt_lens=Lengths(kind="uniform", lo=6, hi=16),
        output_lens=Lengths(kind="uniform", lo=4, hi=8),
        tenants=(Tenant("default"),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                          prefix_cache=False, replicas=2),
        faults=(FaultSpec(kind="pump_stall", replica=1, at=2, count=4,
                          delay_ms=20.0),),
        description="injected pump stalls on one replica: latency, "
                    "not death")


@register("chaos-slow-reader")
def _chaos_slow_reader(seed: int) -> ScenarioSpec:
    # over-the-wire replay with stalled readers: requests 0 and 1 read
    # two tokens then stop reading for 700 ms with the socket open.
    # The padded SSE frames + tiny kernel buffers (sndbuf/SO_RCVBUF)
    # make the TCP window fill within a few events, writer.drain()
    # parks, acks stop, and the pump — still generating — crosses the
    # 6-token backpressure window: the slot spills into the radix cache
    # instead of pinning pages for a socket. When the reader resumes,
    # the stream completes token-identically (the identity amplifier
    # proves the spill/resume cycle corrupted nothing). Outputs are
    # pinned long (48 tokens) so the stall always lands mid-generation.
    return ScenarioSpec(
        name="chaos-slow-reader", seed=seed, n_requests=4,
        arrival=Arrival(kind="poisson", rate_rps=200.0),
        prompt_lens=Lengths(kind="uniform", lo=6, hi=12),
        output_lens=Lengths(kind="uniform", lo=48, hi=48),
        tenants=(Tenant("default", output_tokens=48),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=8,
                          prefix_cache=True, http=True,
                          backpressure_window=6, sse_pad_bytes=2048,
                          sndbuf=4096),
        faults=(FaultSpec(kind="slow_reader", at=2, count=2,
                          delay_ms=700.0),),
        description="stalled SSE readers cross the backpressure window:"
                    " spill, resume, token-identical completion")


@register("chaos-disconnect-storm")
def _chaos_disconnect_storm(seed: int) -> ScenarioSpec:
    # network chaos on the HTTP surface: requests 0-3 drop their
    # sockets for real (shutdown(SHUT_RDWR)) after reading 3 tokens,
    # and requests 0-1 additionally tear their submit mid-request with
    # an RST (SO_LINGER 0) before retrying on a fresh connection. The
    # server must notice every drop, cancel at the next sync boundary,
    # free the pages (the driver's leak check), and keep streaming the
    # survivors untouched. Outputs are pinned at 24 tokens so the drop
    # always lands mid-generation; the greedy/scheduling checks accept
    # exact PREFIXES for the dropped ids (runner._net_prefix_ids).
    return ScenarioSpec(
        name="chaos-disconnect-storm", seed=seed, n_requests=10,
        arrival=Arrival(kind="poisson", rate_rps=300.0),
        prompt_lens=Lengths(kind="uniform", lo=6, hi=16),
        output_lens=Lengths(kind="uniform", lo=24, hi=24),
        tenants=(Tenant("default", output_tokens=24),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=8,
                          prefix_cache=True, http=True),
        faults=(FaultSpec(kind="client_disconnect", at=3, count=4),
                FaultSpec(kind="conn_reset", count=2)),
        description="mid-stream socket drops + torn submits: cancel, "
                    "free pages, survivors token-identical")


@register("router-affinity-ab")
def _router_affinity_ab(seed: int) -> ScenarioSpec:
    ps = 8
    # the multi-tenant radix-cache workload over TWO replicas, banked
    # both ways: affinity routing (tenant header -> one replica, its
    # cache warm) vs round-robin (headers smeared over both caches).
    # The aggregate hit-rate delta is the proof
    return ScenarioSpec(
        name="router-affinity-ab", seed=seed, n_requests=24,
        arrival=Arrival(kind="poisson", rate_rps=500.0),
        prompt_lens=Lengths(kind="lognormal", mean=10.0, sigma=0.5,
                            lo=2, hi=24),
        output_lens=Lengths(kind="uniform", lo=4, hi=8),
        tenants=(
            Tenant("free", weight=1.0, system_prompt_tokens=2 * ps),
            Tenant("pro", weight=1.0, system_prompt_tokens=4 * ps,
                   priority=2),
            Tenant("batch", weight=1.0, system_prompt_tokens=3 * ps),
            Tenant("edge", weight=1.0, system_prompt_tokens=2 * ps),
        ),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=ps,
                          prefix_cache=True, replicas=2,
                          compare_round_robin=True),
        description="affinity vs round-robin hit-rate A/B over 2 "
                    "replicas, same trace")


@register("bench-mixed-length")
def _bench_mixed_length(seed: int) -> ScenarioSpec:
    # mixed prompt/output lengths, so continuous batching beats
    # lock-step padding
    return ScenarioSpec(
        name="bench-mixed-length", seed=seed, n_requests=8,
        arrival=Arrival(kind="poisson", rate_rps=500.0),
        prompt_lens=Lengths(kind="uniform", lo=8, hi=64),
        output_lens=Lengths(kind="uniform", lo=8, hi=24),
        tenants=(Tenant("default"),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=3, page_size=8,
                          prefix_cache=False),
        description="mixed prompt/output lengths, closed loop")


@register("bench-shared-prefix")
def _bench_shared_prefix(seed: int) -> ScenarioSpec:
    ps = 8
    return ScenarioSpec(
        name="bench-shared-prefix", seed=seed, n_requests=8,
        arrival=Arrival(kind="poisson", rate_rps=500.0),
        prompt_lens=Lengths(kind="uniform", lo=4, hi=16),
        output_lens=Lengths(kind="uniform", lo=6, hi=12),
        tenants=(Tenant("shared", system_prompt_tokens=4 * ps),),
        engine=EngineSpec(model="gpt2-tiny", num_slots=2, page_size=ps,
                          prefix_cache=True),
        description="one shared system prompt before random tails")
