"""Runner of the serving cells: ``ServingFrontend.submit`` over
``PagedDecodeEngine`` with the background pump, in this process, timed from
the client's side (``StreamHandle.set_listener``).

Set-up makes the weights from the seed, warms one admission program per
prompt length of the mix and the decode chunk, and ramps the load up; the
window measures the running system.  Once the window has closed, a sample
of the requests that finished in it (the longest among them) is kept, the
requests still in flight are cancelled, the engine leaves the device, and
the plain reference judges every served token of the sample.
"""

from __future__ import annotations

import functools
import gc
import queue
import time
from typing import List

import numpy as np

from benchmark import families
from benchmark.harness import flops, runtime, traffic, weights
from benchmark.harness.stats import RequestTimes, serve_metrics

CLOSE_S = 60.0          # how long cancelling what is in flight may take


def build_engine(cfg: dict, mix: dict, seed: int):
    """The family's model with weights from the seed and the engine of the
    mix."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import PagedDecodeEngine

    family = families.load(cfg)
    model = family.model(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    variables = {"params": weights.make_like(like["params"], seed)}
    eng = mix["engine"]
    pages = 1 + eng["pool_bytes"] // family.page_bytes(cfg, eng["page_size"])
    return PagedDecodeEngine(
        model, variables, num_slots=eng["num_slots"],
        page_size=eng["page_size"], num_pages=pages,
        sync_every=eng["sync_every"], prefix_cache=eng["prefix_cache"])


class Client:
    """The load: submits requests and stamps what comes back, by the host's
    clock, on the thread that delivers it."""

    def __init__(self, frontend, source: traffic.ServeTraffic):
        self.frontend = frontend
        self.source = source
        self.specs = iter(source)
        self.records: List[RequestTimes] = []
        self.kept: List[tuple] = []      # (record, spec, prompt, handle)
        self.ended: "queue.Queue" = queue.Queue()
        self._pending = None             # open loop: the next request due

    def submit(self, owner: int, due: float = None, spec=None):
        from apex_tpu.serving import Request

        spec = next(self.specs) if spec is None else spec
        prompt = self.source.prompt(spec)
        record = RequestTimes(due=time.perf_counter() if due is None else due)
        self.records.append(record)
        try:
            with runtime.annotate("submit"):
                handle = self.frontend.submit(
                    Request(prompt=prompt, max_new_tokens=spec.new_tokens),
                    request_id=spec.index)
        except Exception:               # refused: missed, and counted
            record.failed = True
            record.last = time.perf_counter()
            self.ended.put(owner)
            return
        self.kept.append((record, spec, prompt, handle))
        handle.set_listener(functools.partial(self._event, record, handle,
                                              owner))

    def _event(self, record: RequestTimes, handle, owner: int) -> None:
        now = time.perf_counter()
        record.deliver(now, len(handle.tokens_so_far()))
        if handle.done and not (record.done or record.failed):
            if handle.error is not None:
                record.failed = True
                record.last = now
            else:
                record.done = True
            self.ended.put(owner)

    # -- arrival processes -----------------------------------------------------

    def closed_loop(self, clients: int, think_s: float, until: float,
                    started: bool) -> None:
        """``clients`` callers, each with one request in flight, until the
        clock reads ``until``."""
        if not started:
            for owner in range(clients):
                self.submit(owner)
        while True:
            left = until - time.perf_counter()
            if left <= 0:
                return
            try:
                owner = self.ended.get(timeout=min(left, 0.05))
            except queue.Empty:
                continue
            if think_s:
                time.sleep(think_s)
            self.submit(owner)

    def open_loop(self, t0: float, until: float) -> None:
        """Requests at the mix's own due times, timed from when each was
        due, whatever the system does."""
        while True:
            if self._pending is None:
                self._pending = next(self.specs)
            due = t0 + self._pending.due_s
            if due >= until:
                time.sleep(max(0.0, until - time.perf_counter()))
                return
            time.sleep(max(0.0, due - time.perf_counter()))
            spec, self._pending = self._pending, None
            self.submit(-1, due=due, spec=spec)

    def drive(self, arrival: dict, t0: float, until: float,
              started: bool) -> None:
        if arrival["kind"] == "closed":
            self.closed_loop(arrival["clients"], arrival.get("think_s", 0.0),
                             until, started)
        else:
            self.open_loop(t0, until)


def warm_up(client: Client, mix: dict, timeout_s: float = 1200.0) -> int:
    """Every program the window uses runs here first: one request per prompt
    length of the mix, long enough to run the decode chunk; then, with the
    prefix cache on, requests of the longest prompt in waves of a slot each
    until the pool has filled and evicted radix pages once (eviction is a
    program of its own, and a pool that fills by the mix's own traffic takes
    half a minute).  Returns the number of requests it took."""
    from apex_tpu.serving import Request

    frontend = client.frontend
    need = 2 * mix["engine"]["sync_every"] + 1
    lengths = traffic.distinct_prompt_lengths(mix)
    tenant = 0 if client.source.prefixes else -1
    sent = 0

    def wave(prompt_lengths):
        nonlocal sent
        handles = []
        for length in prompt_lengths:
            spec = traffic.Spec(-1 - sent, length, need, tenant)
            handles.append(frontend.submit(
                Request(prompt=client.source.prompt(spec),
                        max_new_tokens=need), request_id=10 ** 9 + sent))
            sent += 1
        for h in handles:
            h.result(timeout=timeout_s)

    wave(lengths)
    while (mix["engine"]["prefix_cache"] and sent < mix["warm_up_max"]
           and not frontend.counter_deltas()["evicted_pages"]):
        wave([lengths[-1]] * mix["engine"]["num_slots"])
    return sent


def sample_finished(client: Client, t0: float, t1: float, count: int,
                    seed: int) -> List[tuple]:
    """(prompt, served tokens) of ``count`` requests that finished in the
    window, drawn from the seed, the longest among them."""
    done = [(spec, prompt, np.asarray(handle.result(timeout=1.0), np.int32))
            for record, spec, prompt, handle in client.kept
            if record.done and t0 <= record.last <= t1]
    if not done:
        return []
    done.sort(key=lambda x: x[0].index)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][1]) + len(done[i][2]))
    rng = weights.host_rng(seed, "sample")
    others = [i for i in rng.permutation(len(done)) if i != longest]
    picked = [longest] + [int(i) for i in others[:count - 1]]
    return [(done[i][1], done[i][2]) for i in picked]


def run(ctx) -> dict:
    """One run of a serving cell; ``ctx`` is ``run.Context``."""
    from apex_tpu.serving import ServingFrontend

    cfg, mix, seed = ctx.config, ctx.mix, ctx.seed
    family = families.load(cfg)
    arrival = mix["arrival"]
    phases = runtime.Phases()

    engine = build_engine(cfg, mix, seed)
    weight_bytes = flops.tree_bytes(engine.variables)
    frontend = ServingFrontend(engine)
    frontend.start()
    phases.mark("engine_s")
    try:
        client = Client(frontend, traffic.ServeTraffic(
            mix, family.drawn_vocab(cfg), seed))
        warm_requests = warm_up(client, mix)
        phases.mark("warm_up_s")
        t_ramp = time.perf_counter()
        client.drive(arrival, t_ramp, t_ramp + mix["ramp_s"], started=False)
        phases.mark("ramp_s")

        compiles0 = ctx.compiles.count
        ctx.window_opens()
        t0 = time.perf_counter()
        t1 = t0 + ctx.seconds
        traced, counters = None, None
        if ctx.trace:
            client.drive(arrival, t_ramp, t0 + ctx.seconds / 3.0, True)
            with runtime.TracedWindow(runtime.trace_dir()) as traced:
                before = frontend.counter_deltas()
                client.drive(arrival, t_ramp,
                             time.perf_counter() + mix["traced_s"], True)
                after = frontend.counter_deltas()
            counters = {k: after[k] - before[k] for k in after}
        client.drive(arrival, t_ramp, t1, True)
        t1 = time.perf_counter()
        lifetime = frontend.counter_deltas()
        compiles = ctx.compiles.count - compiles0
        peak_bytes = runtime.memory_peak_bytes(ctx.devices)
        samples = sample_finished(client, t0, t1, mix["sampled_requests"],
                                  seed)
    finally:
        # requests still in flight are cancelled, not failed: nothing of
        # theirs was due; one that failed inside the window is counted
        frontend.shutdown(CLOSE_S, mode="cancel")
    phases.mark("window_and_close_s")
    measured = serve_metrics(client.records, t0, t1)

    # the engine and its pool leave the device before the reference runs
    del frontend, engine, client
    gc.collect()
    judged = family.judge(cfg, seed, samples) if samples else \
        {"gap": float("inf"), "where": None, "tokens": 0}
    phases.mark("reference_s")

    numbers = {"served_logit_gap": judged["gap"],
               "failed_requests": float(measured["failed"])}
    reading = {
        "weight_bytes": weight_bytes,
        "num_slots": mix["engine"]["num_slots"],
        "sync_every": mix["engine"]["sync_every"],
        "client": measured,
        "forward_flops_per_token": family.forward_flops_per_token(cfg),
    }
    if traced is not None:
        reading.update(trace=traced.trace, window_s=traced.window_s,
                       counters=counters)
    metrics = {k: measured[k] for k in
               ("serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms")
               if k in measured}
    return {
        "metrics": metrics,
        "attempted": measured["completed"] + measured["failed"],
        "failed": measured["failed"], "numbers": numbers,
        "memory_peak_bytes": peak_bytes, "reading": reading,
        "samples": samples,
        "notes": {"completed": measured["completed"],
                  "window_compiles": compiles,
                  "warm_up_requests": warm_requests,
                  "ttft_p50_ms": measured.get("ttft_p50_ms"),
                  "tpot_p50_ms": measured.get("tpot_p50_ms"),
                  "ttft_samples": measured["ttft_samples"],
                  "tpot_samples": measured["tpot_samples"],
                  "judged_tokens": judged["tokens"],
                  "judged_where": judged["where"],
                  "reference_weights_s": judged.get("weights_s"),
                  "reference_judge_s": judged.get("judge_s"),
                  "phases": dict(phases),
                  "lifetime_counters": {k: lifetime[k] for k in (
                      "decode_steps", "busy_slot_steps", "admitted",
                      "retired", "prefix_hits", "evicted_pages",
                      "prefill_tokens_total",
                      "prefill_tokens_computed")}},
    }
