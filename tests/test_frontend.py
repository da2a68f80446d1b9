"""Serving front-end (apex_tpu/serving/frontend.py + policy.py).

Policy tier (no model): queue ordering (priority desc, EDF inside a
class, FIFO tiebreak), victim selection (strictly-lower priority only,
most recent first), preemption arming (margin/deadline semantics).

Frontend tier (tiny GPT): the acceptance bars for preemption-by-spill —
greedy outputs token-identical with preemption forced on vs off, the
resumed request's re-admission skipping its FULL-page prefix via the
radix cache, priority inversion bounded (a low-priority flood cannot
starve a high-priority arrival past its deadline), streaming handles
delivering tokens in order and terminating on EOS/cancel, and sampled
decode staying scheduling-invariant ACROSS a preemption (the resume
continues the request's fold_in key stream)."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.serving import (FaultPlan, FaultSpec, PagedDecodeEngine,
                              PriorityDeadlinePolicy, Request,
                              ServingError, free_page_count)
from apex_tpu.serving.frontend import ServingFrontend
from apex_tpu.utils import metrics


def _model():
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, v


def _refs(model, v, reqs, **kw):
    return [np.asarray(generate(model, v, np.asarray(r.prompt)[None],
                                max_new_tokens=r.max_new_tokens, **kw)
                       )[0, np.asarray(r.prompt).shape[0]:]
            for r in reqs]


# --------------------------------------------------------------------------
# policy (pure host logic)
# --------------------------------------------------------------------------

class _E:
    """Minimal entry stand-in for policy unit tests."""

    def __init__(self, priority=0, deadline_at=None, arrival=0.0, seq=0):
        self.priority = priority
        self.deadline_at = deadline_at
        self.arrival = arrival
        self.seq = seq


def test_request_backcompat_defaults():
    """The pre-frontend constructor shape still works; the scheduling
    fields default to plain FIFO traffic."""
    r = Request(prompt=np.zeros((4,), np.int32), max_new_tokens=3)
    assert (r.priority, r.deadline_ms, r.arrival_time) == (0, None, None)
    r2 = Request(np.zeros((4,), np.int32), 3)         # positional form
    assert r2.max_new_tokens == 3 and r2.priority == 0


def test_policy_ordering():
    pol = PriorityDeadlinePolicy()
    hi = _E(priority=2, arrival=3.0, seq=3)
    edf = _E(priority=0, deadline_at=5.0, arrival=2.0, seq=2)
    old = _E(priority=0, arrival=0.0, seq=0)
    new = _E(priority=0, arrival=1.0, seq=1)
    ordered = sorted([new, old, edf, hi],
                     key=lambda e: pol.sort_key(e, now=0.0))
    # priority first, then earliest deadline, then arrival FIFO
    assert ordered == [hi, edf, old, new]


def test_policy_victim_selection_and_arming():
    pol = PriorityDeadlinePolicy(preempt_margin_ms=100.0)
    active = {0: _E(priority=1, seq=0), 1: _E(priority=0, seq=1),
              2: _E(priority=0, seq=2)}
    cand = _E(priority=2, deadline_at=1.0)
    # lowest priority wins; inside the class, the most recent admission
    assert pol.select_victim(cand, active, now=0.0) == 2
    # equal-or-higher priority never qualifies (no ping-pong)
    assert pol.select_victim(_E(priority=0), active, now=0.0) is None
    assert pol.select_victim(_E(priority=1), active,
                             now=0.0) in (1, 2)       # only the 0s
    # arming: inside the margin of the deadline, or past it
    assert not pol.at_risk(_E(deadline_at=10.0), now=0.0)
    assert pol.at_risk(_E(deadline_at=10.0), now=9.95)
    assert pol.at_risk(_E(deadline_at=10.0), now=11.0)
    assert not pol.wants_preempt(_E(), now=0.0)       # no deadline
    assert PriorityDeadlinePolicy(preempt_on_priority=True).wants_preempt(
        _E(), now=0.0)
    assert not PriorityDeadlinePolicy(preemption=False).wants_preempt(
        _E(deadline_at=0.0), now=1.0)


# --------------------------------------------------------------------------
# streaming handles
# --------------------------------------------------------------------------

def test_streaming_tokens_in_order_and_eos_termination(rng):
    """Tokens arrive on the handle in generation order as the pump runs
    and the stream terminates; a request ending at EOS includes it and
    stops."""
    import queue as queue_mod

    cfg, model, v = _model()
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    ref = np.asarray(generate(model, v, prompt[None], max_new_tokens=6))
    eos = int(ref[0, 10])                 # forces an EOS mid-budget
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8,
                               eos_token_id=eos)
    fe = ServingFrontend(engine)
    h = fe.submit(Request(prompt=prompt, max_new_tokens=6))
    streamed = []
    while fe.pump():                      # consume between boundaries
        try:
            while (tok := h.get(timeout=0)) is not None:
                streamed.append(tok)
        except queue_mod.Empty:
            pass
    streamed.extend(list(h))              # whatever the last chunk left
    out = h.result()
    assert h.done
    assert streamed == list(out)          # in order, nothing dropped
    assert h.tokens_so_far() == list(out)
    assert int(out[-1]) == eos or out.shape[0] == 6
    assert list(h) == []                  # the stream stays terminated


def test_streaming_cancel_stops_stream_and_frees_pages(rng):
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8)
    fe = ServingFrontend(engine)
    prompt = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
    h = fe.submit(Request(prompt=prompt, max_new_tokens=30))
    for _ in range(4):
        fe.pump()
    h.cancel()
    fe.drain()
    out = h.result()
    assert h.done
    assert 1 <= out.shape[0] < 30         # truncated at the cancel point
    # the prefix of an uncancelled run matches (cancel loses no tokens)
    ref = np.asarray(generate(model, v, prompt[None], max_new_tokens=30)
                     )[0, 9:]
    np.testing.assert_array_equal(out, ref[:out.shape[0]])
    # pages all returned (no prefix cache: everything frees)
    assert int(free_page_count(engine.cache)) == \
        engine.cache["free_stack"].shape[0] - 1
    # a cancelled PENDING request never admits and finishes empty
    fe2 = ServingFrontend(engine)
    h2 = fe2.submit(Request(prompt=prompt, max_new_tokens=4))
    h2.cancel()
    fe2.drain()
    assert h2.result().shape[0] == 0


def test_background_pump_thread(rng):
    """start()/stop(): submissions stream results without the caller
    driving the pump."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    fe = ServingFrontend(engine)
    fe.start()
    try:
        prompt = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
        h = fe.submit(Request(prompt=prompt, max_new_tokens=5))
        out = h.result(timeout=120.0)
    finally:
        fe.stop()
    ref = np.asarray(generate(model, v, prompt[None], max_new_tokens=5)
                     )[0, 10:]
    np.testing.assert_array_equal(out, ref)


# --------------------------------------------------------------------------
# preemption / resume
# --------------------------------------------------------------------------

def _forced_preemption_run(model, v, cfg, low, hi, *, engine_kw=None,
                           warm_pumps=3):
    """Admit the low-priority requests, let them decode a few chunks,
    then submit the high-priority one under an aggressive policy — with
    every slot busy it MUST preempt. Returns (frontend, handles)."""
    engine = PagedDecodeEngine(model, v, num_slots=len(low), page_size=8,
                               prefix_cache=True, **(engine_kw or {}))
    fe = ServingFrontend(
        engine, policy=PriorityDeadlinePolicy(preempt_on_priority=True))
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(low)]
    while fe.queue_depth:
        fe.pump()
    for _ in range(warm_pumps):           # give the victims some progress
        fe.pump()
    handles.append(fe.submit(hi, request_id=len(low)))
    fe.drain()
    return fe, handles


def test_forced_preemption_token_identity_and_full_prefix_resume(rng):
    """THE acceptance bar: a high-priority arrival that must evict a
    low-priority slot produces greedy output token-identical to the
    unconstrained run for every request, and the resumed request's
    re-admission skips its ENTIRE full-page written prefix via the
    radix cache."""
    cfg, model, v = _model()
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                       ).astype(np.int32),
                   max_new_tokens=16, priority=0) for _ in range(2)]
    hi = Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                     ).astype(np.int32),
                 max_new_tokens=8, priority=5)
    fe, handles = _forced_preemption_run(model, v, cfg, low, hi)
    stats = fe.stats()
    assert stats["preemptions"] >= 1
    assert stats["resumes"] >= 1

    # token identity: every request matches its unconstrained lock-step
    # run — the preempt/spill/resume cycle changed nothing
    for h, ref in zip(handles, _refs(model, v, low + [hi])):
        np.testing.assert_array_equal(h.result(), ref)

    # the resume hit the cache for its FULL written full-page prefix:
    # ample pages mean the spilled pages survived until the resume, and
    # resumes skip the power-of-two match flooring
    ring = fe.engine.events.tail()
    preempts = {e["request"]: e for e in ring if e["kind"] == "preempt"}
    resumes = [e for e in ring if e["kind"] == "resume"]
    assert resumes, ring
    for ev in resumes:
        generated = preempts[ev["request"]]["generated"]
        s0 = 24                           # every prompt here is 24 tokens
        full_pages = (s0 + generated - 1) // 8
        assert ev["cached_pages"] == full_pages, (ev, generated)
    assert stats["prefill_tokens_skipped"] >= 8

    # pool hygiene: every non-cached page returned after the drain
    usable = fe.engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(fe.engine.cache)) == \
        usable - len(fe.engine.prefix)


def test_preemption_on_off_identical_via_run(rng):
    """engine.run() outputs are identical whether the policy may preempt
    or not (same requests, same engine config)."""
    cfg, model, v = _model()
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (int(s),)
                                        ).astype(np.int32),
                    max_new_tokens=int(m), priority=int(p))
            for s, m, p in zip((16, 24, 9), (10, 6, 12), (0, 3, 1))]
    e1 = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                           prefix_cache=True)
    outs_off, _ = e1.run(reqs, policy=PriorityDeadlinePolicy(
        preemption=False))
    e2 = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                           prefix_cache=True)
    outs_on, _ = e2.run(reqs, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    for a, b in zip(outs_off, outs_on):
        np.testing.assert_array_equal(a, b)


def test_priority_inversion_bounded(rng):
    """A flood of low-priority work cannot starve a high-priority
    deadline request: the policy preempts the running victim and the
    high-priority request completes before any further low-priority
    request is even admitted."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8)
    # a huge margin arms preemption the moment the request is blocked —
    # long before the (comfortable) deadline could be missed
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_margin_ms=1e7))
    lows = [fe.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (12,)).astype(np.int32),
        max_new_tokens=12), request_id=i) for i in range(3)]
    while fe.queue_depth == 3:            # let the first low admit
        fe.pump()
    fe.pump()
    h_hi = fe.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32),
        max_new_tokens=4, priority=9, deadline_ms=600000.0),
        request_id=9)
    fe.drain()
    stats = fe.stats()
    assert stats["preemptions"] >= 1
    assert stats["deadline_misses"] == 0
    ring = fe.engine.events.tail()
    hi_retire = next(e["seq"] for e in ring
                     if e["kind"] == "retire" and e["request"] == 9)
    later_low_admits = [e["seq"] for e in ring
                        if e["kind"] == "admit" and e["request"] in (1, 2)]
    assert all(hi_retire < s for s in later_low_admits), ring
    for h in lows:                        # the flood still completes
        assert h.result().shape[0] == 12


@pytest.mark.slow
def test_sampled_preemption_scheduling_invariance(rng):
    """Sampled decode draws the SAME tokens with and without a
    preemption in the middle: the resume admission continues the
    request's fold_in key stream at its token index (samp0)."""
    cfg, model, v = _model()
    key = jax.random.PRNGKey(3)
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                       ).astype(np.int32),
                   max_new_tokens=12, priority=0) for _ in range(2)]
    hi = Request(prompt=rng.integers(0, cfg.vocab_size, (16,)
                                     ).astype(np.int32),
                 max_new_tokens=6, priority=5)
    kw = dict(temperature=1.0, top_k=8, rng=key)

    # undisturbed: plain run, no preemption possible (FIFO, no deadlines)
    e_plain = PagedDecodeEngine(model, v, num_slots=3, page_size=8,
                                **kw)
    outs_plain, stats_plain = e_plain.run(low + [hi])
    assert stats_plain.get("preemptions", 0) == 0

    # forced preemption mid-decode; prefix_cache on for the spill path
    e_pre = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                              prefix_cache=True, **kw)
    fe = ServingFrontend(e_pre, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(low)]
    while fe.queue_depth:
        fe.pump()
    for _ in range(3):
        fe.pump()
    handles.append(fe.submit(hi, request_id=2))
    fe.drain()
    assert fe.stats()["preemptions"] >= 1
    for h, ref in zip(handles, outs_plain):
        np.testing.assert_array_equal(h.result(), np.asarray(ref))


def test_deadline_miss_counted_and_queue_metrics(rng):
    """An already-expired deadline is counted exactly once at first
    token; the queue-depth gauge tracks ingest and the preemption
    counters carry the engine label."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8)
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preemption=False))
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                        ).astype(np.int32),
                    max_new_tokens=3,
                    deadline_ms=0.0 if i == 0 else None,
                    arrival_time=time.perf_counter() - 1.0)
            for i in range(3)]
    for i, r in enumerate(reqs):
        fe.submit(r, request_id=i)
    assert fe.queue_depth == 3
    assert metrics.gauge("serving.queue_depth",
                         labels=engine.obs_labels).value == 3
    fe.drain()
    stats = fe.stats()
    assert stats["deadline_misses"] == 1
    assert stats["peak_queue_depth"] >= 3
    assert stats["preemptions"] == 0 and stats["resumes"] == 0
    assert metrics.counter("serving.deadline_misses",
                           labels=engine.obs_labels).value >= 1
    assert metrics.gauge("serving.queue_depth",
                         labels=engine.obs_labels).value == 0


def test_lifecycle_reports_time_in_preempted(rng):
    """The span tracer's lifecycle sums decode segments across a
    preemption and reports preempted_ms/preemptions; TTFT anchors on
    the ORIGINAL first token, not the resume's."""
    cfg, model, v = _model()
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                       ).astype(np.int32),
                   max_new_tokens=10, priority=0) for _ in range(2)]
    hi = Request(prompt=rng.integers(0, cfg.vocab_size, (16,)
                                     ).astype(np.int32),
                 max_new_tokens=4, priority=5)
    fe, handles = _forced_preemption_run(model, v, cfg, low, hi)
    ring = fe.engine.events.tail()
    victim = next(e["request"] for e in ring if e["kind"] == "preempt")
    life = fe.tracer.lifecycle(victim)
    assert life["preemptions"] >= 1
    assert life["preempted_ms"] > 0.0
    assert life["new_tokens"] == handles[victim].result().shape[0]
    assert life["ttft_ms"] >= 0.0
    assert life["tpot_ms"] >= 0.0
    # an unpreempted request reports no preemption keys
    untouched = next(i for i in (0, 1) if i != victim)
    assert "preemptions" not in fe.tracer.lifecycle(untouched)


def test_pump_timing_fields_present_and_sane(rng):
    """ISSUE 8 acceptance: a frontend run's stats carry the pump
    pipeline attribution (`pump.bubble_ms`, dispatch-ready/host-work
    percentiles) and the recompile window (`jit.compiles`), and the
    engine-labeled pump instruments exist in the registry."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                               sync_every=2)
    fe = ServingFrontend(engine)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (9,)
                                        ).astype(np.int32),
                    max_new_tokens=6) for _ in range(4)]
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(reqs)]
    fe.drain()
    for h in handles:
        h.result(timeout=0)
    stats = fe.stats()
    assert stats["pump.bubble_ms"] >= 0.0
    assert stats["pump.dispatch_ready_ms_p50"] > 0.0
    assert (stats["pump.dispatch_ready_ms_p95"]
            >= stats["pump.dispatch_ready_ms_p50"])
    assert (0.0 <= stats["pump.host_work_ms_p50"]
            <= stats["pump.host_work_ms_p95"])
    assert stats["jit.compiles"] >= 0
    assert stats["jit.trace_cache_misses"] >= stats["jit.compiles"]
    labels = dict(engine.obs_labels, phase="steady")
    assert metrics.histogram("pump.dispatch_ready_ms",
                             labels=labels).count > 0
    assert metrics.histogram("pump.host_work_ms",
                             labels=engine.obs_labels).count > 0
    assert metrics.gauge("pump.bubble_ms",
                         labels=engine.obs_labels).value >= 0.0


def test_preempt_flush_chunks_labeled_separately(rng):
    """A preemption flush harvests the in-flight chunk synchronously;
    its device time lands under phase="preempt", not in the
    steady-state distribution."""
    cfg, model, v = _model()
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                       ).astype(np.int32),
                   max_new_tokens=10, priority=0) for _ in range(2)]
    hi = Request(prompt=rng.integers(0, cfg.vocab_size, (16,)
                                     ).astype(np.int32),
                 max_new_tokens=4, priority=5)
    fe, _ = _forced_preemption_run(model, v, cfg, low, hi)
    eng_labels = fe.engine.obs_labels
    preempt = metrics.histogram(
        "pump.dispatch_ready_ms", labels=dict(eng_labels,
                                              phase="preempt"))
    assert fe.stats()["preemptions"] >= 1
    assert preempt.count >= 1


def test_tpot_slo_miss_counted_and_burn_gauge(rng):
    """ISSUE 8 satellite: a request with an impossible TPOT SLO is
    counted once (`serving.tpot_slo_misses`, engine-labeled) and the
    rolling `serving.slo_burn` gauge reports the miss rate over
    SLO-carrying retirements; a generous SLO records no miss."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    fe = ServingFrontend(engine)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                    ).astype(np.int32),
                max_new_tokens=4, tpot_slo_ms=0.0),       # must miss
        Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                    ).astype(np.int32),
                max_new_tokens=4, tpot_slo_ms=1e9),       # cannot miss
        Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                    ).astype(np.int32),
                max_new_tokens=4),                        # no SLO
    ]
    for i, r in enumerate(reqs):
        fe.submit(r, request_id=i)
    fe.drain()
    stats = fe.stats()
    assert stats["tpot_slo_misses"] == 1
    # burn = misses / SLO-carrying retirements in the window (the
    # no-SLO request does not dilute it)
    assert stats["slo_burn"] == pytest.approx(0.5)
    assert metrics.counter("serving.tpot_slo_misses",
                           labels=engine.obs_labels).value == 1
    assert metrics.gauge("serving.slo_burn",
                         labels=engine.obs_labels).value \
        == pytest.approx(0.5)
    ring = engine.events.tail()
    misses = [e for e in ring if e["kind"] == "tpot_slo_miss"]
    assert len(misses) == 1 and misses[0]["request"] == 0
    assert fe.tracer.lifecycle(0)["tpot_ms"] > 0.0


def test_slo_window_prunes_by_policy_horizon(rng):
    """The burn gauge forgets misses older than the policy's
    slo_window_s (injected clock)."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8)
    t = [0.0]
    fe = ServingFrontend(
        engine, policy=PriorityDeadlinePolicy(slo_window_s=10.0),
        clock=lambda: t[0])
    miss = Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                       ).astype(np.int32),
                   max_new_tokens=4, tpot_slo_ms=0.0)
    fe.submit(miss, request_id=0)
    fe.drain()
    assert fe.stats()["slo_burn"] == 1.0
    # 60 fake seconds later, a healthy retirement: the old miss has
    # aged out of the 10 s window
    t[0] = 60.0
    ok = Request(prompt=rng.integers(0, cfg.vocab_size, (8,)
                                     ).astype(np.int32),
                 max_new_tokens=4, tpot_slo_ms=1e9)
    fe.submit(ok, request_id=1)
    fe.drain()
    assert metrics.gauge("serving.slo_burn",
                         labels=engine.obs_labels).value == 0.0


def test_deadlock_still_raises_and_fails_handles(rng):
    """A request the pool can never hold dies loudly through the
    frontend too (the engine's original deadlock contract)."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8,
                               num_pages=3)
    fe = ServingFrontend(engine)
    fe.submit(Request(prompt=np.zeros((30,), np.int32),
                      max_new_tokens=10))
    with pytest.raises(RuntimeError, match="deadlock"):
        fe.drain()


# --------------------------------------------------------------------------
# pump death (ISSUE 11 satellite: a dead engine must never hang a handle)
# --------------------------------------------------------------------------

def _killed_frontend(model, v, *, at=2, start=True):
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    plan = FaultPlan(specs=(FaultSpec(kind="kill_replica", at=at),))
    fe = ServingFrontend(engine, fault_hook=plan.injector(0))
    if start:
        fe.start()
    return fe


def test_pump_death_mid_decode_raises_serving_error_bounded(rng):
    """ISSUE 11 satellite (the pump-death hang): an engine that dies
    mid-decode must surface a terminal ServingError from result() AND
    from blocked iteration within a bounded time — before this PR the
    synchronous pump path left handles un-finished and iteration ended
    silently instead of raising."""
    import queue as queue_mod

    cfg, model, v = _model()
    # submit BEFORE the pump thread starts: the injected kill counts pump
    # iterations, and a pump that had already idled through two of them
    # before the request arrived died with nothing in flight (one full run
    # in eight under six workers: PERF.md section 7)
    fe = _killed_frontend(model, v, at=2, start=False)
    try:
        prompt = rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
        h = fe.submit(Request(prompt=prompt, max_new_tokens=40))
        fe.start()
        # consumer 1: blocked in result() on another thread
        res: dict = {}

        def consume_result():
            try:
                res["out"] = h.result(timeout=300)
            except BaseException as exc:     # noqa: BLE001
                res["exc"] = exc

        import threading
        t = threading.Thread(target=consume_result, daemon=True)
        t.start()
        t.join(timeout=300)
        assert not t.is_alive(), "result() hung on a dead engine"
        assert isinstance(res.get("exc"), ServingError)
        # consumer 2: blocked iteration raises too (never silent-ends)
        with pytest.raises(ServingError):
            for _ in h:
                pass
        with pytest.raises(ServingError):
            while h.get(timeout=10) is not None:
                pass
        assert h.error is not None
        # the frontend is terminally failed: late submits raise, the
        # failure is observable (the /healthz surface)
        assert fe.failure is not None
        with pytest.raises(ServingError, match="pump has failed"):
            fe.submit(Request(prompt=prompt, max_new_tokens=4))
        del queue_mod
    finally:
        fe.stop()


def test_pump_death_sync_path_fails_handles(rng):
    """The SYNCHRONOUS pump driver takes the same terminal path: the
    exception propagates to the driving caller AND every live handle
    (active + pending) fails — nothing dangles for a streaming
    consumer on another thread to block on."""
    cfg, model, v = _model()
    fe = _killed_frontend(model, v, at=3, start=False)
    prompts = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(4)]
    handles = [fe.submit(Request(prompt=p, max_new_tokens=30))
               for p in prompts]        # 2 active + 2 pending (2 slots)
    from apex_tpu.serving.faults import InjectedFault

    with pytest.raises(InjectedFault):
        fe.drain()
    for h in handles:
        assert h.done
        with pytest.raises(ServingError):
            h.result(timeout=0)


# --------------------------------------------------------------------------
# shutdown under load (ISSUE 11 satellite: stop() must not strand work)
# --------------------------------------------------------------------------

def _loaded_frontend(model, v, cfg, rng, *, n=6, max_new=16):
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                               prefix_cache=True)
    fe = ServingFrontend(engine)
    handles = [fe.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32),
        max_new_tokens=max_new), request_id=i) for i in range(n)]
    return fe, handles


@pytest.mark.parametrize("mode", ["drain", "cancel"])
def test_shutdown_under_load_resolves_deterministically(rng, mode):
    """shutdown() with queued + active + mid-stream requests: every
    handle reaches done (full output under drain, truncated under
    cancel), zero pool-page leaks, zero dangling threads, and late
    submits raise."""
    import threading

    cfg, model, v = _model()
    fe, handles = _loaded_frontend(model, v, cfg, rng)
    fe.start()
    try:
        handles[0].get(timeout=120)      # at least one token streamed
        fe.shutdown(deadline_s=300.0, mode=mode)
    finally:
        fe.stop()
    for h in handles:
        assert h.done
        out = h.result(timeout=0)        # never raises: resolved, not
        if mode == "drain":              # stranded
            assert out.shape[0] == 16
        else:
            assert out.shape[0] <= 16
    with pytest.raises(ServingError, match="shutting down"):
        fe.submit(Request(prompt=np.zeros((4,), np.int32),
                          max_new_tokens=2))
    # zero dangling threads, zero leaked pages
    assert not fe.pump_alive
    assert "serving-frontend-pump" not in {
        t.name for t in threading.enumerate()}
    engine = fe.engine
    usable = engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(engine.cache)) == \
        usable - len(engine.prefix)
    assert int(np.asarray(engine.cache["page_ref"]).sum()) == 0


def test_shutdown_sync_and_deadline_expiry(rng):
    """A synchronously driven frontend shuts down the same way, and an
    already-expired drain deadline degrades to cancellation — bounded,
    never an infinite pump loop."""
    cfg, model, v = _model()
    fe, handles = _loaded_frontend(model, v, cfg, rng, n=4, max_new=24)
    for _ in range(3):
        fe.pump()
    fe.shutdown(deadline_s=0.0, mode="drain")   # expires immediately
    for h in handles:
        assert h.done
        assert h.result(timeout=0).shape[0] <= 24   # truncated is fine
    engine = fe.engine
    usable = engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(engine.cache)) == \
        usable - len(engine.prefix)
    with pytest.raises(ValueError, match="mode"):
        fe.shutdown(mode="nope")


# --------------------------------------------------------------------------
# host-concurrency stress (ISSUE 7: the dynamic counterpart of --conc)
# --------------------------------------------------------------------------

def test_concurrent_submit_cancel_stress(rng):
    """N producer threads concurrently submit()/cancel()/iterate handles
    against the background pump under a watchdog: no lost or duplicated
    tokens (each handle's streamed sequence equals its final output), no
    deadlock (every thread finishes inside the timeout), and the pool's
    free-page count returns to baseline after the drain (leak check —
    preemption spill/resume and cancellation paths all release)."""
    import threading

    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                               prefix_cache=True)
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    fe.start()
    n_threads, n_req = 3, 3
    errors: list = []
    results: dict = {}

    def producer(tid: int) -> None:
        try:
            local = np.random.default_rng(tid)
            for i in range(n_req):
                s0 = 8 + 2 * ((tid + i) % 3)
                prompt = local.integers(0, cfg.vocab_size, (s0,)
                                        ).astype(np.int32)
                h = fe.submit(Request(prompt=prompt, max_new_tokens=5,
                                      priority=(tid + i) % 3),
                              request_id=tid * 10 + i)
                streamed: list = []
                if (tid + i) % 4 == 3:
                    # consume one token, then cancel mid-stream
                    tok = h.get(timeout=120)
                    if tok is not None:
                        streamed.append(tok)
                    h.cancel()
                for tok in h:            # live-stream the rest
                    streamed.append(tok)
                out = h.result(timeout=120)
                results[(tid, i)] = (streamed, list(out), h.cancelled)
        except BaseException as exc:     # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=producer, args=(t,), daemon=True)
               for t in range(n_threads)]
    try:
        for t in threads:
            t.start()
        for t in threads:                # watchdog: a hang fails, not wedges
            t.join(timeout=300)
        stuck = [t.name for t in threads if t.is_alive()]
        assert not stuck, f"deadlocked producer threads: {stuck}"
    finally:
        fe.stop()
    assert not errors, errors
    assert len(results) == n_threads * n_req
    for (tid, i), (streamed, out, cancelled) in results.items():
        # in-order, nothing dropped, nothing pushed twice
        assert streamed == out, (tid, i, streamed, out)
        if not cancelled:
            assert len(out) == 5 or (
                engine.eos_token_id is not None)
    # pool hygiene: every non-cached page returned after the drain
    usable = engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(engine.cache)) == \
        usable - len(engine.prefix)
    # the cached pages are all refcount-0 (no dangling prefix refs)
    assert int(np.asarray(engine.cache["page_ref"]).sum()) == 0
    assert fe.stats()["retired"] == n_threads * n_req


# --------------------------------------------------------------------------
# the pump's own account (docs/frontend.md "Measuring the pump")
# --------------------------------------------------------------------------

class _TickClock:
    """A millisecond passes at every read; ``jump`` adds more."""

    def __init__(self):
        self.t = 0.0
        self.reads = []

    def __call__(self):
        self.t += 1e-3
        self.reads.append(self.t)
        return self.t

    def jump(self, seconds):
        self.t += seconds


class _SlowValue:
    """A device value whose read takes ``seconds`` of the fake clock."""

    def __init__(self, value, clock, seconds):
        self.value, self.clock, self.seconds = value, clock, seconds

    def __array__(self, dtype=None, copy=None):
        self.clock.jump(self.seconds)
        return np.asarray(self.value, dtype)


def _clocked_frontend(num_slots=2, **kw):
    from apex_tpu.obs.spans import SpanTracer

    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=num_slots, page_size=8,
                               sync_every=2, **kw)
    clk = _TickClock()
    fe = ServingFrontend(engine, clock=clk, tracer=SpanTracer(clock=clk))
    return cfg, fe, clk


def _requests(rng, cfg, shapes):
    return [Request(prompt=rng.integers(0, cfg.vocab_size, (s0,)
                                        ).astype(np.int32),
                    max_new_tokens=n) for s0, n in shapes]


def test_pump_host_plus_blocked_is_the_iterations_wall_time(rng):
    """Every second of an iteration that did work is either host work or
    a wait for the device: the two counters sum to the iterations' wall
    time by the pump's own clock, and the admission's share is part of
    the host's."""
    cfg, fe, clk = _clocked_frontend()
    for i, r in enumerate(_requests(rng, cfg, [(9, 6)] * 4)):
        fe.submit(r, request_id=i)
    wall = accounted = 0.0
    iterations = 0
    alive = True
    while alive:
        before, first_read = fe.counter_deltas(), len(clk.reads)
        alive = fe.pump()
        after = fe.counter_deltas()
        if after["pump_iterations"] == before["pump_iterations"]:
            continue                     # neither harvested nor admitted
        iterations += 1
        # the pump reads its clock first (t_iter0) and, in an iteration
        # it counts, last where it observes host_work_ms
        wall += clk.reads[-1] - clk.reads[first_read]
        accounted += sum(after[k] - before[k] for k in
                         ("pump_host_seconds", "pump_blocked_seconds"))
    d = fe.counter_deltas()
    assert iterations == d["pump_iterations"] > 2
    assert accounted == pytest.approx(wall, rel=1e-9)
    assert d["pump_blocked_seconds"] > 0.0
    assert 0.0 < d["pump_admission_seconds"] < d["pump_host_seconds"]
    # one measurement feeds the histogram's series and the counter
    assert d["pump_host_seconds"] * 1e3 == pytest.approx(
        sum(fe._per_run["pump.host_work_ms"]))
    assert d["pump_bubble_seconds"] * 1e3 == pytest.approx(
        sum(fe._per_run["pump.bubble_ms"]))


def test_an_admission_that_blocks_is_device_wait_not_host_work(rng):
    """The first-token sync of an admission waits for the prefill on the
    device: it belongs to ``pump_blocked_seconds``, and neither
    ``pump.host_work_ms`` nor the admission's host seconds may hold it."""
    cfg, fe, clk = _clocked_frontend(num_slots=1)
    program = fe.admission_program

    def slow_admission(s0):
        admit, bucket = program(s0)

        def run(*args):
            cache, tok0 = admit(*args)
            return cache, _SlowValue(tok0, clk, 5.0)

        return run, bucket

    fe.admission_program = slow_admission
    [req] = _requests(rng, cfg, [(9, 4)])
    handle = fe.submit(req, request_id=0)
    fe.drain()
    assert handle.result(timeout=0).shape[0] == 4
    d = fe.counter_deltas()
    assert d["pump_blocked_seconds"] >= 5.0
    assert d["pump_host_seconds"] < 1.0
    assert d["pump_admission_seconds"] < 1.0
    assert fe.stats()["pump.host_work_ms_p95"] < 1000.0
    # the same five seconds lie between admit and the first token
    assert d["first_token_wait_seconds"] >= 5.0 > d["queue_wait_seconds"]
    assert fe.tracer.lifecycle(0)["ttft_ms"] == pytest.approx(
        (d["queue_wait_seconds"] + d["first_token_wait_seconds"]) * 1e3)


def test_the_two_waits_of_ttft_sum_to_the_lifecycles(rng):
    """enqueue -> admit and admit -> first token, summed over requests,
    are the lifecycles' ``queue_wait_ms`` and ``ttft_ms`` (four requests
    over two slots, so two of them queue)."""
    cfg, fe, clk = _clocked_frontend()
    for i, r in enumerate(_requests(rng, cfg, [(9, 6), (12, 4), (9, 5),
                                               (17, 3)])):
        fe.submit(r, request_id=i)
    fe.drain()
    lives = [fe.tracer.lifecycle(i) for i in range(4)]
    d = fe.counter_deltas()
    assert d["admitted"] == 4
    assert d["queue_wait_seconds"] * 1e3 == pytest.approx(
        sum(life["queue_wait_ms"] for life in lives))
    assert (d["queue_wait_seconds"] + d["first_token_wait_seconds"]) * 1e3 \
        == pytest.approx(sum(life["ttft_ms"] for life in lives))
    assert d["first_token_wait_seconds"] > 0.0
    assert max(life["queue_wait_ms"] for life in lives) > \
        2 * min(life["queue_wait_ms"] for life in lives)


def test_kv_bytes_attended_equals_the_hand_count(rng):
    """Two requests of known lengths: a request of ``s0`` prompt tokens
    and ``n`` new ones takes ``n - 1`` decode steps, and step ``i``
    attends ``s0 + i`` tokens; each costs its K and V in every layer."""
    from apex_tpu.serving import kv_pool

    cfg, fe, _ = _clocked_frontend()
    shapes = [(9, 6), (12, 4)]
    for i, r in enumerate(_requests(rng, cfg, shapes)):
        fe.submit(r, request_id=i)
    fe.drain()
    tokens = sum((n - 1) * s0 + (n - 1) * n // 2 for s0, n in shapes)
    assert tokens == 60 + 42
    layers = fe.engine.cache["layers"]
    pool_tokens = layers[0]["k_pages"].shape[0] * fe.engine.page_size
    token_bytes = sum(lc["k_pages"].nbytes + lc["v_pages"].nbytes
                      for lc in layers) / pool_tokens
    assert token_bytes == kv_pool.page_bytes(cfg, 8) / 8
    d = fe.counter_deltas()
    assert d["kv_bytes_attended"] == tokens * token_bytes
    # frozen steps of the last chunks are counted busy but attend nothing
    assert d["busy_slot_steps"] >= sum(n - 1 for _, n in shapes)


def test_kv_bytes_fetched_counts_whole_page_blocks(rng):
    """The kernel moves page blocks whole, in every step a slot is
    decoding in, frozen ones too: at this engine's sizes the table is one
    block of 16 pages, so each busy slot-step fetches 16 pages of K and V
    in every layer; what is attended is the part that is no rounding
    (the blocks' own arithmetic: tests/test_paged_attention.py)."""
    from apex_tpu.serving import kv_pool

    cfg, fe, _ = _clocked_frontend()
    for i, r in enumerate(_requests(rng, cfg, [(9, 6), (12, 4)])):
        fe.submit(r, request_id=i)
    fe.drain()
    (kv,) = fe._kv_groups                      # layers alike: one group
    assert kv.pages_fetched(1) == kv.pages_fetched(128) == 16
    d = fe.counter_deltas()
    assert d["kv_bytes_fetched"] == d["busy_slot_steps"] * 16 * \
        kv_pool.page_bytes(cfg, fe.engine.page_size)
    assert 0 < d["kv_bytes_attended"] < d["kv_bytes_fetched"]


# --------------------------------------------------------------------------
# the two-level account, counted at one moment (PR 39)
# --------------------------------------------------------------------------

PER_CHUNK = ("decode_steps", "busy_slot_steps", "kv_bytes_attended",
             "kv_full_bytes_attended", "kv_window_bytes_attended",
             "kv_bytes_fetched", "kv_bytes_held_steps",
             "context_token_steps", "state_bytes_moved",
             "expert_pairs_routed", "experts_hit", "expert_load_max",
             "expert_bytes_read")


def test_a_parent_phase_holds_its_children_and_no_wait(rng):
    """A phase's seconds include its children's and are net of every
    ``wait_device`` under it, at either level; the second level feeds no
    counter of its own."""
    cfg, fe, clk = _clocked_frontend()
    before = fe.counter_deltas()
    with fe._phase("admission"):
        clk.jump(1.0)
        with fe._phase("admission.launch"):
            clk.jump(2.0)
            assert int(fe._await(_SlowValue(7, clk, 5.0))) == 7
        with fe._phase("wait_device"):
            clk.jump(7.0)
        clk.jump(0.5)
    with fe._phase("retire"):            # a child with no parent open
        clk.jump(3.0)
    after = fe.counter_deltas()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert set(moved) == {"pump_admission_seconds", "pump_blocked_seconds"}
    # the clock ticks a millisecond a read: eight reads inside the parent
    assert moved["pump_admission_seconds"] == pytest.approx(3.5, abs=0.01)
    assert moved["pump_blocked_seconds"] == pytest.approx(12.0, abs=0.01)
    assert fe._iter_s["admission"] == pytest.approx(
        moved["pump_admission_seconds"])
    assert fe._iter_s["wait_device"] == pytest.approx(
        moved["pump_blocked_seconds"])


def test_the_four_host_phases_are_the_iterations_host_work(rng):
    """dispatch + harvest + housekeeping + admission, each net of its
    waits, are the iterations' host seconds but for what lies between
    phases (a clock tick here and there)."""
    cfg, fe, clk = _clocked_frontend(prefix_cache=True)
    for i, r in enumerate(_requests(rng, cfg, [(9, 6), (17, 5), (9, 4)])):
        fe.submit(r, request_id=i)
    fe.drain()
    d = fe.counter_deltas()
    phases = sum(d[f"pump_{p}_seconds"] for p in (
        "dispatch", "harvest", "housekeeping", "admission"))
    assert all(d[f"pump_{p}_seconds"] > 0.0 for p in (
        "dispatch", "harvest", "housekeeping", "admission"))
    between = d["pump_host_seconds"] - phases
    # between phases the pump reads its clock a few times an iteration
    assert 0.0 <= between <= 0.02 * d["pump_iterations"]


@pytest.fixture(scope="module")
def routed_tiny():
    """The tiny routed model of tests/test_glm4_moe_lite.py: a decode
    chunk hands its routing back with its tokens."""
    from apex_tpu.models.glm4_moe_lite import (Glm4MoeLiteModel,
                                               glm4_moe_lite_tiny_config)
    from benchmark.harness import weights

    cfg = glm4_moe_lite_tiny_config()
    model = Glm4MoeLiteModel(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    return cfg, model, {"params": weights.make_like(like["params"],
                                                    20261001)}


def _chunk_counts(cfg, chunk):
    """What harvesting ``chunk`` adds to the per-chunk counters."""
    from apex_tpu.serving.scheduler import SHARE_ROUTING_STATS

    counts = dict(chunk.account)
    if not isinstance(chunk.routed, tuple):
        routed = dict(zip(SHARE_ROUTING_STATS,
                          np.asarray(chunk.routed).sum(axis=0).tolist()))
        counts.update(routed, expert_bytes_read=(
            routed["experts_hit"] * cfg.routed_expert_bytes))
    return counts


@pytest.mark.parametrize("case", ["steady", "preempt_flush",
                                  "shutdown_cancel"])
def test_every_per_chunk_counter_counts_the_harvested_chunks(routed_tiny,
                                                             case):
    """One moment for the whole account: after a dispatch and before its
    harvest NO per-chunk counter has moved, after the harvest all have,
    and between ANY two snapshots every one of them holds exactly the
    chunks harvested in between, so every ratio of two of them
    (``experts_hit / decode_steps``, ``busy_slot_steps / decode_steps``,
    ``kv_bytes_attended / decode_steps``) is exact. A preemption flush
    harvests and counts; a chunk in flight at ``shutdown(mode="cancel")``
    counts if and only if it is harvested."""
    cfg, model, variables = routed_tiny
    engine = PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                               num_pages=24, sync_every=2,
                               prefix_cache=True)
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    harvested = []                       # (chunk, phase) in harvest order
    harvest = fe._harvest

    def spy(chunk, *, phase="steady"):
        harvested.append((chunk, phase))
        return harvest(chunk, phase=phase)

    fe._harvest = spy
    local = np.random.default_rng(5)
    for i, n in enumerate((9, 12)):
        fe.submit(Request(prompt=local.integers(4, cfg.vocab_size, n
                                                ).astype(np.int32),
                          max_new_tokens=9, priority=0), request_id=i)
    snapshots = []                       # (chunks harvested, counters)

    def snap():
        d = fe.counter_deltas()
        snapshots.append((len(harvested), {k: d[k] for k in PER_CHUNK}))

    snap()
    fe.pump()                            # admits both
    fe.pump()                            # dispatches chunk 1
    assert fe._inflight is not None and not harvested
    snap()
    assert not any(snapshots[-1][1].values()), \
        "a per-chunk counter moved between a dispatch and its harvest"
    fe.pump()                            # harvests 1, dispatches 2
    snap()
    first = snapshots[-1][1]
    assert first["decode_steps"] == engine.sync_every
    assert first["busy_slot_steps"] == 2 * engine.sync_every
    assert all(first[k] > 0 for k in PER_CHUNK if k not in (
        "kv_window_bytes_attended", "state_bytes_moved"))
    if case == "preempt_flush":
        fe.submit(Request(prompt=local.integers(4, cfg.vocab_size, 10
                                                ).astype(np.int32),
                          max_new_tokens=3, priority=9), request_id=2)
        fe.pump()
        snap()
        assert fe.counter_deltas()["preemptions"] == 1
        assert "preempt" in [phase for _, phase in harvested]
    if case == "shutdown_cancel":
        assert fe._inflight is not None
        fe.shutdown(mode="cancel")
        snap()
    else:
        alive = True
        while alive:
            alive = fe.pump()
            snap()
    # between any two snapshots: exactly the chunks harvested in between
    for i, (n0, c0) in enumerate(snapshots):
        for n1, c1 in snapshots[i + 1:]:
            want = dict.fromkeys(PER_CHUNK, 0.0)
            for chunk, _ in harvested[n0:n1]:
                for name, n in _chunk_counts(cfg, chunk).items():
                    want[name] += n
            assert {k: c1[k] - c0[k] for k in PER_CHUNK} == \
                pytest.approx(want)
    total = snapshots[-1][1]
    assert total["decode_steps"] == engine.sync_every * len(harvested)
    assert total["experts_hit"] / total["decode_steps"] == pytest.approx(
        sum(_chunk_counts(cfg, c)["experts_hit"] for c, _ in harvested)
        / (engine.sync_every * len(harvested)))


def test_a_retirement_stamps_the_chunk_in_flight_before_its_read(rng):
    """With the prefix cache on a retirement reads the slot's block table,
    which waits for the chunk in flight: that chunk is stamped before the
    read, so its ``decode_step_ms`` holds no later host work and the NEXT
    dispatch records the bubble the drained device sat through."""
    cfg, fe, clk = _clocked_frontend(prefix_cache=True)
    stamped = []
    release = fe._release_pages
    await_ = fe._await

    def releasing(slot, entry):
        inflight = fe._inflight
        reads = []

        def reading(value):
            if inflight is not None:
                reads.append(inflight.t_done)
            return await_(value)

        fe._await = reading
        try:
            release(slot, entry)
        finally:
            fe._await = await_
        if inflight is not None:
            stamped.append((reads, inflight.t_done, clk.t))

    fe._release_pages = releasing
    # the first retires while the second still decodes: a chunk in flight
    for i, r in enumerate(_requests(rng, cfg, [(9, 3), (9, 12)])):
        fe.submit(r, request_id=i)
    retired_then_bubble = []
    alive = True
    while alive:
        before = fe.counter_deltas()
        bubbles = len(fe._per_run["pump.bubble_ms"])
        alive = fe.pump()
        after = fe.counter_deltas()
        retired_then_bubble.append((
            after["retired"] - before["retired"],
            len(fe._per_run["pump.bubble_ms"]) - bubbles,
            fe._inflight is not None))
    assert stamped, "no retirement found a chunk in flight"
    for reads, t_done, t_end in stamped:
        assert reads and all(t is not None for t in reads)
        assert t_done <= t_end
    # an iteration that retired with a chunk in flight: the next dispatch
    # knows when the device went idle
    for (retired, _, inflight), (_, bubble, dispatched) in zip(
            retired_then_bubble, retired_then_bubble[1:]):
        if retired and inflight and dispatched:
            assert bubble == 1


def _scripted_pumps(fe, clk, stalls):
    """Idle pump iterations whose housekeeping takes ``stalls[i]``."""
    script = iter(stalls)
    fe._backpressure_spill = lambda: clk.jump(next(script))
    for _ in stalls:
        fe.pump()


def test_the_eight_longest_iterations_are_kept_with_their_split(rng):
    cfg, fe, clk = _clocked_frontend()
    stalls = [0.010 * ((7 * i) % 20 + 1) for i in range(20)]    # 10-200 ms
    _scripted_pumps(fe, clk, stalls)
    slowest = fe.stats()["pump.slowest"]
    assert len(slowest) == 8
    order = sorted(range(20), key=lambda i: -stalls[i])[:8]
    assert [r["iteration"] for r in slowest] == [i + 1 for i in order]
    for r, i in zip(slowest, order):
        assert r["host_ms"]["housekeeping"] == pytest.approx(
            stalls[i] * 1e3, abs=5.0)
        assert r["wall_ms"] == pytest.approx(stalls[i] * 1e3, abs=15.0)
        assert r["wall_ms"] >= sum(r["host_ms"].values()) + r["wait_ms"]
        assert set(r["host_ms"]) == {"dispatch", "harvest", "housekeeping",
                                     "admission"}
        assert (r["admitted"], r["retired"], r["wait_ms"]) == (0, 0, 0.0)
        assert r["compiles"] >= 0 and r["gc_ms"] == 0.0
    walls = [r["wall_ms"] for r in slowest]
    assert walls == sorted(walls, reverse=True)


def test_the_slowest_iterations_name_what_they_admitted_and_retired(rng):
    cfg, fe, clk = _clocked_frontend()
    for i, r in enumerate(_requests(rng, cfg, [(9, 3), (9, 3)])):
        fe.submit(r, request_id=i)
    fe.drain()
    slowest = fe.stats()["pump.slowest"]
    assert sum(r["admitted"] for r in slowest) == 2
    assert sum(r["retired"] for r in slowest) == 2
    assert any(r["wait_ms"] > 0.0 for r in slowest)
    assert any(r["host_ms"]["admission"] > 0.0 for r in slowest)


@pytest.mark.parametrize("end", ["shutdown", "death"])
def test_the_pumps_end_puts_its_slowest_iterations_in_the_event_ring(rng,
                                                                     end):
    """Once, at shutdown or at the pump's death: the postmortem dump (and
    through it the router's flight bundle) then carries them."""
    cfg, model, v = _model()
    if end == "death":
        fe = _killed_frontend(model, v, at=2, start=False)
    else:
        fe = ServingFrontend(PagedDecodeEngine(model, v, num_slots=2,
                                               page_size=8))
    [req] = _requests(rng, cfg, [(9, 6)])
    fe.submit(req, request_id=0)
    if end == "death":
        with pytest.raises(Exception):
            fe.drain()
    else:
        fe.drain()
    fe.shutdown()
    fe.shutdown()
    found = [e for e in fe.engine.events.tail()
             if e["kind"] == "pump_slowest"]
    assert len(found) == 1
    assert found[0]["iterations"] == fe.stats()["pump.slowest"]
    assert found[0]["iterations"][0]["wall_ms"] > 0.0


def test_the_collector_is_timed_while_the_background_pump_runs(rng):
    import gc

    cfg, model, v = _model()
    fe = ServingFrontend(PagedDecodeEngine(model, v, num_slots=2,
                                           page_size=8))
    hooks = len(gc.callbacks)
    fe.start()
    try:
        for _ in range(100):
            if len(gc.callbacks) > hooks:
                break
            time.sleep(0.01)
        assert len(gc.callbacks) == hooks + 1
        gc.collect()
        assert fe._gc_s > 0.0
        assert fe.counter_deltas()["gc_pause_seconds"] == pytest.approx(
            fe._gc_s)
    finally:
        fe.stop()
    assert len(gc.callbacks) == hooks


def test_a_cancel_while_waiting_to_resume_is_a_retirement(rng):
    """A request preempted, then cancelled while it waits for a slot
    again, was admitted and decoded: the frontend finishes its handle in
    the queue, and ``retired`` and the ``cancel`` event count it, once
    (the retirement that counted nowhere in
    ``test_concurrent_submit_cancel_stress``)."""
    cfg, model, v = _model()
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8,
                               prefix_cache=True)
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    low, hi, never = _requests(rng, cfg, [(24, 16), (16, 4), (9, 4)])
    low.priority, hi.priority, never.priority = 0, 5, 0
    h_low = fe.submit(low, request_id=0)
    for _ in range(4):
        fe.pump()
    h_hi = fe.submit(hi, request_id=1)
    while not fe.counter_deltas()["preemptions"]:
        fe.pump()
    assert not h_low.done and fe.queue_depth == 1
    h_low.cancel()                       # waits for a slot again: queued
    h_never = fe.submit(never, request_id=2)
    h_never.cancel()                     # cancelled before any admission
    fe.drain()
    assert h_hi.result(timeout=0).shape[0] == 4
    got = h_low.result(timeout=0)
    assert 0 < got.shape[0] < 16 and h_never.result(timeout=0).shape[0] == 0
    stats = fe.stats()
    assert stats["retired"] == 3
    cancels = [e for e in engine.events.tail() if e["kind"] == "cancel"]
    assert [(e["request"], e.get("queued"), e["new_tokens"])
            for e in cancels] == [(0, True, got.shape[0]), (2, True, 0)]
    assert fe.tracer.lifecycle(0)["preemptions"] == 1
    usable = engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(engine.cache)) == \
        usable - len(engine.prefix)
