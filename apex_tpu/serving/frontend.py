"""Async serving front-end over the paged decode engine: streaming
ingest, priorities/deadlines, and page-spilling preemption.

``PagedDecodeEngine.run()`` drains a *fixed* request list; production
traffic is an open stream. :class:`ServingFrontend` is the layer that
turns the engine into a server:

- **Ingest** — ``submit(request)`` is thread-safe and returns a
  :class:`StreamHandle` immediately; per-token results are pushed to the
  handle as decode chunks retire, and ``result()`` blocks for the full
  output. The pump (below) may run on a background thread (``start()``)
  or be driven synchronously (``drain()`` — what ``run()`` does).
- **Priorities/deadlines** — the pending queue is ordered by the
  injected :class:`~apex_tpu.serving.policy.PriorityDeadlinePolicy`
  (priority desc, then earliest deadline, then arrival). ``deadline_ms``
  is a TTFT SLO; misses are counted (``serving.deadline_misses``), never
  dropped.
- **Preemption** — when a higher-priority request is blocked (no slot or
  pages) and the policy says it cannot wait, the lowest-priority active
  slot is stopped at a sync boundary and its FULL pages are released
  through the prefix-cache insert path (``release_slot`` with the tree's
  keep mask) — the victim's computed prefix survives as cached pages
  instead of being discarded. The victim re-enters the queue with its
  generated-so-far tokens folded into its prompt; its resume admission
  walks the radix tree, points its block table at the spilled pages, and
  re-prefills only the (≤ one page) tail — preemption-by-spill, cheaper
  than vLLM's discard-and-recompute whenever the cache survives. With
  ``prefix_cache=False`` preemption degrades to exactly that
  discard-and-recompute. Greedy outputs are token-identical with
  preemption on or off (the resume re-derives nothing: cached pages
  replay bitwise-stored K/V; the recompute path re-runs the same
  prefill).
- **The pump** — the engine's jitted ``sync_every``-step decode chunk is
  dispatched FIRST each iteration; the host then harvests the *previous*
  chunk's tokens, retires finished slots, streams results, and admits
  new work while the device executes — double-buffered host work. All
  cache mutations are async dispatches on one device stream, so program
  order keeps them correct: a retiring slot is done-frozen (EOS/budget
  masks flip on device) during the in-flight chunk, its writes land only
  at its frozen garbage position (never inside a cacheable full page),
  and its release/realloc are queued after the chunk. The price is that
  a slot freed by chunk N's harvest starts its next request at chunk
  N+2, not N+1 — one chunk of pipeline bubble per handoff, paid back by
  the device never idling through host bookkeeping.

The frontend owns no compiled programs and no pool state — it drives the
engine's (``_admit_fn`` / ``_admit_shared_fn`` / ``_step_fn``), so
``run()`` reimplemented over the frontend exercises the same compile-key
contracts the lint harness binds (``analysis_cases()`` traces
:meth:`ServingFrontend.admission_program` /
:meth:`ServingFrontend.decode_program` — shared accessors, not mirrors).
That program-seam discipline is also what makes tensor parallelism
transparent here: a :class:`~apex_tpu.serving.tp.TensorParallelPagedEngine`
hands the pump shard_map-wrapped programs over its mesh, the pump's
host-side reads (block tables, free counts, harvested tokens) see
replicated values, and nothing in this module knows the chip count
(``stats()`` reports it as ``tp_world`` so benches can divide through).
"""

from __future__ import annotations

import contextlib
import functools
import gc
import heapq
import itertools
import queue as _queue
import threading
import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.obs import compile_watch
from apex_tpu.obs import fleet
from apex_tpu.obs.spans import SpanTracer
from apex_tpu.ops._dispatch import round_up
from apex_tpu.ops._page_walk import pages_fetched
from apex_tpu.serving import kv_pool
from apex_tpu.serving.policy import PriorityDeadlinePolicy
from apex_tpu.serving.scheduler import (_RUN_COUNTERS, _RUN_HISTOGRAMS,
                                        SHARE_ROUTING_STATS, Request,
                                        _bucket_match_pages, prompt_bucket)
from apex_tpu.utils import metrics

__all__ = ["ServingError", "ServingFrontend", "StreamHandle"]

#: sentinel closing a handle's token stream
_END = object()


def _stack_tiles(payloads, chunk: int):
    """Stack per-page host-tier payloads (per-layer dicts of one page's
    K/V tiles + scales) into one ``kv_pool.promote_pages`` tile batch:
    per-layer arrays of leading dim ``chunk``, zero-padded past the live
    pages (the padded rows scatter to the null-page sink)."""
    out = []
    for li in range(len(payloads[0])):
        lc = {}
        for name in payloads[0][li]:
            a = np.stack([p[li][name] for p in payloads])
            if a.shape[0] < chunk:
                a = np.concatenate(
                    [a, np.zeros((chunk - a.shape[0],) + a.shape[1:],
                                 a.dtype)])
            lc[name] = a
        out.append(lc)
    return out


class ServingError(RuntimeError):
    """Terminal serving failure delivered to a :class:`StreamHandle`:
    the pump died (engine fault, injected kill, scheduler deadlock), the
    frontend refused the request (draining, fault-injected admission
    reject), or — at the router layer (``serving/router.py``) — every
    failover attempt was exhausted. A handle that fails raises this from
    ``result()`` AND from iteration/``get()``, so a streaming consumer
    can never block forever on a dead engine."""

#: pump pipeline timing series (run-local percentiles in ``stats()``;
#: cumulative distributions in the engine-labeled histograms):
#: ``dispatch_ready_ms`` = device wall time of one decode chunk from
#: dispatch to the host observing its tokens, ``host_work_ms`` = the
#: host side of one pump iteration NET of time blocked on the device
#: (every ``pump:wait_device`` phase: the chunk harvest AND an
#: admission's first-token sync), ``bubble_ms`` = device idle between a
#: chunk completing and the next dispatch — the direct measurement of
#: whether the double-buffered host work is actually hidden
#: (docs/frontend.md)
_PUMP_SERIES = ("pump.dispatch_ready_ms", "pump.host_work_ms",
                "pump.bubble_ms")

#: the pump's top-level phases, whose host seconds feed a ``serving.*``
#: counter on exit; with ``wait_device`` (every host block on a device
#: value, nested inside the others) they are an iteration's whole account.
#: Every phase is a ``pump:<name>`` span in an xprof capture; the second
#: level (``PUMP_SPANS``) is spans alone, read from the trace
_PHASE_COUNTERS = {"dispatch": "pump_dispatch_seconds",
                   "harvest": "pump_harvest_seconds",
                   "housekeeping": "pump_housekeeping_seconds",
                   "admission": "pump_admission_seconds",
                   "wait_device": "pump_blocked_seconds"}

#: every span the pump writes beside the device line (``"pump:" + name``;
#: docs/frontend.md "Measuring the pump" says what each wraps). The
#: benchmark's idle-gap metrics find them by these names
#: (``tests/test_kernel_labels.py`` pins them)
PUMP_SPANS = tuple(_PHASE_COUNTERS) + (
    "dispatch.launch", "dispatch.account",
    "harvest.account", "retire", "retire.release",
    "admission.request", "admission.match", "admission.pool",
    "admission.launch", "admission.join", "admission.feed")

#: how many of its longest iterations a pump keeps (``stats()
#: ["pump.slowest"]``)
_SLOWEST_KEPT = 8


class StreamHandle:
    """One submitted request's streaming view: tokens arrive in order as
    the pump harvests decode chunks; iteration ends when the request
    retires (EOS / token budget) or is cancelled. ``result()`` blocks
    for the complete generated-token array. All methods are thread-safe
    (the pump pushes from its thread, callers consume from theirs)."""

    def __init__(self, request_id):
        self.request_id = request_id
        self._q: "_queue.Queue" = _queue.Queue()
        self._tokens: List[int] = []
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._cancelled = threading.Event()
        self._output: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        # consumption cursor: tokens the consumer has actually taken
        # (``get``/iteration advance it implicitly; ``ack`` explicitly —
        # the HTTP writer acks only after the socket accepted the bytes,
        # so ``unread()`` is the per-connection in-flight-token window
        # the frontend's backpressure spill keys on)
        self._consumed = 0
        self._listener = None            # push/terminate notification

    # -- pump side -----------------------------------------------------------

    def _push(self, tok: int) -> None:
        with self._lock:
            self._tokens.append(tok)
            listener = self._listener
        self._q.put(tok)
        if listener is not None:
            listener()                   # outside the lock, by contract

    def _finish(self, output: np.ndarray) -> None:
        self._output = output
        self._done.set()
        self._q.put(_END)
        with self._lock:
            listener = self._listener
        if listener is not None:
            listener()

    def _fail(self, exc: BaseException) -> None:
        # terminal errors surface as ServingError everywhere (result,
        # get, iteration) with the original failure chained as the cause
        if not isinstance(exc, ServingError):
            wrapped = ServingError(
                f"request {self.request_id!r} failed: {exc!r}")
            wrapped.__cause__ = exc
            exc = wrapped
        self._error = exc
        self._done.set()
        self._q.put(_END)
        with self._lock:
            listener = self._listener
        if listener is not None:
            listener()

    # -- caller side ---------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done.is_set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def cancel(self) -> None:
        """Request cancellation: a pending request is dropped, an active
        one retires at the next sync boundary (its stream terminates and
        its pages free/spill normally). Idempotent; the already-streamed
        tokens remain the handle's output."""
        self._cancelled.set()

    def tokens_so_far(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    def unread(self) -> int:
        """Tokens pushed but not yet consumed — the per-consumer
        in-flight window. ``get``/iteration consume implicitly;
        adapters that read via :meth:`tokens_so_far` (the asyncio
        bridge) must :meth:`ack` explicitly."""
        with self._lock:
            return len(self._tokens) - self._consumed

    def ack(self, n: int) -> None:
        """Mark the first ``n`` streamed tokens consumed (monotonic;
        clamped to what has been pushed). The HTTP writer calls this
        after the socket accepted a token's bytes — a stalled reader
        stops acking and :meth:`unread` grows until the frontend spills
        the slot."""
        with self._lock:
            self._consumed = max(self._consumed,
                                 min(n, len(self._tokens)))

    def set_listener(self, fn) -> None:
        """Register one callback fired (outside the handle lock, on the
        pusher's thread) after every push/finish/fail — the seam the
        asyncio adapter uses to wake its event loop. Fires once
        immediately if the stream already has tokens or terminated, so
        a late registration can never miss the wake-up."""
        with self._lock:
            self._listener = fn
            pending = bool(self._tokens) or self._done.is_set()
        if pending and fn is not None:
            fn()

    @property
    def error(self) -> Optional[BaseException]:
        """The terminal :class:`ServingError`, if the request failed
        (readable once ``done``; ``result()``/iteration re-raise it)."""
        return self._error

    def get(self, timeout: Optional[float] = None) -> Optional[int]:
        """Next token, or None once the stream has terminated. Raises
        ``queue.Empty`` on timeout and the terminal
        :class:`ServingError` if the request failed — a consumer
        blocked on a stream whose engine died is woken and raised at,
        never left hanging."""
        tok = self._q.get(timeout=timeout)
        if tok is _END:
            self._q.put(_END)            # keep the stream terminated
            if self._error is not None:
                raise self._error
            return None
        with self._lock:
            self._consumed += 1          # queue order == push order
        return tok

    def __iter__(self):
        while True:
            tok = self.get()
            if tok is None:
                return
            yield tok

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request finishes; the generated tokens (up to
        and including EOS), truncated at the cancellation point for a
        cancelled request. Re-raises a pump failure."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id!r} still running")
        if self._error is not None:
            raise self._error
        return self._output


class _KvGroup(NamedTuple):
    """One group of layers as the byte counters see it."""

    group: kv_pool.LayerGroup
    ring: Optional[int]           # pages a slot holds of it, where a ring
    page_bytes: float             # one page over its layers and all chips
    pages_fetched: Callable       # ops._page_walk.pages_fetched, bound
    #                               to the group's pool and table


class _Entry:
    """Pump-internal request state, reused across preempt/resume cycles:
    ``prompt`` is the CURRENT segment's prompt (the original plus every
    previously generated token after a preemption), ``prev`` the tokens
    generated by earlier segments, ``seg_tokens`` the current segment's.
    ``joined`` is the first decode-chunk index whose harvested tokens
    belong to this segment (pipelining: a chunk dispatched before the
    admission carries the PREVIOUS occupant's frozen fill tokens)."""

    __slots__ = ("idx", "handle", "prompt", "total_new", "priority",
                 "deadline_at", "arrival", "seq", "resume", "prev",
                 "seg_tokens", "nodes", "n_private", "joined",
                 "first_token_seen", "tpot_slo", "deadline_missed",
                 "win_dropped", "prefilling", "pf_pos", "pf_key",
                 "pf_samp0", "t_enqueue", "t_admit")

    def __init__(self, idx, handle, prompt, total_new, priority,
                 deadline_at, arrival, seq):
        self.idx = idx
        self.handle = handle
        self.prompt = prompt
        self.total_new = total_new
        self.priority = priority
        self.deadline_at = deadline_at
        self.arrival = arrival
        self.seq = seq
        self.resume = False
        self.prev: List[int] = []
        self.seg_tokens: List[int] = []
        self.nodes: list = []
        self.n_private = 0
        self.joined = 0
        self.first_token_seen = False
        self.tpot_slo = None
        self.deadline_missed = False
        self.win_dropped = 0             # leading block-table entries
        #                                  already window-dropped
        self.prefilling = False          # chunked prefill in progress
        self.pf_pos = 0                  # prompt tokens fed so far
        self.pf_key = None               # req_key held until decode joins
        self.pf_samp0 = 0
        self.t_enqueue = 0.0             # the tracer's enqueue instant
        self.t_admit: Optional[float] = None     # its FIRST admit instant

    @property
    def s0(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def seg_new(self) -> int:
        """This segment's token budget (total minus earlier segments)."""
        return self.total_new - len(self.prev)

    @property
    def generated(self) -> int:
        return len(self.prev) + len(self.seg_tokens)


class _Chunk:
    """An in-flight (dispatched, unharvested) decode chunk.
    ``toks_np``/``t_done`` cache the materialized tokens and the moment
    the host first observed completion — stamped as early as possible
    (an admission syncing on the pool materializes the chunk first) so
    ``decode_step_ms`` measures the chunk, not later host work."""

    __slots__ = ("toks", "idx", "t0", "toks_np", "t_done", "routed",
                 "account")

    def __init__(self, toks, idx, t0, routed=()):
        self.toks = toks
        self.idx = idx
        self.t0 = t0
        self.toks_np = None
        self.t_done = None
        # per step what the routing did (ROUTING_STATS), device-side until
        # the harvest reads it with the tokens; () without routed experts
        self.routed = routed
        # the chunk's own account ({counter: amount}: steps, busy
        # slot-steps, K/V and state bytes), computed after the launch and
        # added with the routing at harvest: every per-chunk counter
        # describes the same completed chunks
        self.account: Dict[str, float] = {}


class ServingFrontend:
    """Streaming ingest + priority/deadline scheduling + preemption over
    one :class:`~apex_tpu.serving.scheduler.PagedDecodeEngine`.

    One frontend drives one engine; ``engine.run()`` constructs a fresh
    frontend per call (so its stats and tracer stay run-scoped), while a
    server holds a long-lived one with a background pump thread. The
    pump itself is single-threaded — only ``submit``/``cancel`` cross
    threads, through the ingest lock and the handles.
    """

    def __init__(self, engine, *, policy: Optional[PriorityDeadlinePolicy]
                 = None, tracer: Optional[SpanTracer] = None,
                 clock=time.perf_counter, fault_hook=None,
                 backpressure_window: Optional[int] = None):
        self.engine = engine
        # per-consumer in-flight-token bound (None = unbounded, the
        # pre-HTTP behavior): an active slot whose handle has more than
        # this many unconsumed tokens is spilled through the preemption
        # path — pages into the radix cache, slot freed — and held out
        # of re-admission until the consumer catches back up to half the
        # window. Pool pages are never pinned by a stalled socket.
        if backpressure_window is not None and backpressure_window < 1:
            raise ValueError("backpressure_window must be >= 1")
        self.backpressure_window = backpressure_window
        # fault-injection seam (serving/faults.py): an object with
        # ``on_pump(frontend)`` (start of every pump iteration — may
        # raise to kill the pump, or sleep to stall it) and
        # ``on_submit(frontend, request)`` (may raise ServingError to
        # reject the submission). First-class so chaos scenarios hook
        # the real seams instead of monkeypatching; None = no faults.
        self.fault_hook = fault_hook
        self.policy = policy if policy is not None \
            else PriorityDeadlinePolicy()
        self.clock = clock
        self.tracer = tracer if tracer is not None else SpanTracer()
        engine.tracer = self.tracer      # the engine's "last run" tracer
        n = engine.num_slots
        self._tok = jnp.zeros((n,), jnp.int32)
        self._done = jnp.ones((n,), bool)
        self._n_left = jnp.zeros((n,), jnp.int32)
        self._samp_i = jnp.zeros((n,), jnp.int32)
        self._req_keys = jnp.broadcast_to(engine.rng,
                                          (n,) + engine.rng.shape)
        self._ingest_lock = threading.Lock()
        self._ingest: deque = deque()
        self._pending: List[_Entry] = []
        self._active: Dict[int, _Entry] = {}
        self._inflight: Optional[_Chunk] = None
        self._chunk = 0
        self._submit_seq = itertools.count()
        self._pool_dirty = False
        self.peak_slots = 0
        self.peak_queue_depth = 0
        labels = engine.obs_labels
        self._C = {name: metrics.counter(f"serving.{name}", labels=labels)
                   for name in _RUN_COUNTERS}
        self._c0 = {name: c.value for name, c in self._C.items()}
        self._H = {name: metrics.histogram(f"serving.{name}", labels=labels)
                   for name in _RUN_HISTOGRAMS}
        self._per_run = {name: [] for name in _RUN_HISTOGRAMS
                         + _PUMP_SERIES}
        self._occ = metrics.gauge("serving.slots_in_use", labels=labels)
        self._qdepth = metrics.gauge("serving.queue_depth", labels=labels)
        # pump pipeline timing (docs/frontend.md "Measuring the pump"):
        # chunk device time is labeled by phase — a preempt-flush chunk
        # is harvested synchronously mid-iteration and must not pollute
        # the steady-state distribution
        self._pump_H = {
            (name, phase): metrics.histogram(
                name, labels={**labels, "phase": phase})
            for name in ("pump.dispatch_ready_ms",)
            for phase in ("steady", "preempt")}
        self._host_H = metrics.histogram("pump.host_work_ms",
                                         labels=labels)
        self._bubble = metrics.gauge("pump.bubble_ms", labels=labels)
        self._last_ready: Optional[float] = None
        self._wait_s = 0.0
        # this iteration's host seconds by top-level phase, and the
        # longest iterations of this frontend's life (a heap of
        # (wall_ms, iteration, record), the shortest kept on top)
        self._iter_s = dict.fromkeys(_PHASE_COUNTERS, 0.0)
        self._iterations = 0
        self._finished = 0               # handles finished (``retired``)
        self._slowest: List[tuple] = []
        self._slowest_emitted = False
        # seconds the process spent in Python's collector while the
        # background pump ran (gc.callbacks; any thread's collection
        # holds the interpreter, the pump's included); the counter
        # serving.gc_pause_seconds holds the same sum
        self._gc_s = 0.0
        self._gc_t0: Optional[float] = None
        # per group of layers (kv_pool.layer_groups; one, unless the
        # model mixes windowed and full layers): the bytes one page and
        # one context token cost across the group's layers and all chips
        # by the pool's own account of an entry (K and V per head, or one
        # latent entry; feeds serving.kv_bytes_attended and its split by
        # kind), and the pages the decode kernel's DMAs move for a slot of
        # a given length, as each chip's call tiles its local share of
        # the group's pool as held: rows (a head, or heads_per_row of
        # them side by side; a latent pool's one) of the pool's lanes,
        # over the group's own table (a ring group's is its R pages);
        # feeds serving.kv_bytes_fetched
        tp = int(getattr(engine, "tp_world", 1))
        self._kv_groups = []
        # a state group holds no page: a fixed number of bytes a slot, all
        # of them read and written by every decode step of the slot
        self._state_bytes_per_slot = kv_pool.state_bytes(engine.cfg)
        for g in engine.groups:
            if g.state:
                continue
            pool = kv_pool.a_pool(engine.cache, g.layers[0])
            ring = (kv_pool.ring_pages(g.window, engine.page_size)
                    if g.ring else None)
            self._kv_groups.append(_KvGroup(
                g, ring,
                kv_pool.page_bytes(engine.cfg, engine.page_size,
                                   kv_dtype=engine.kv_dtype,
                                   layers=len(g.layers)) * tp,
                functools.partial(
                    pages_fetched, kv_heads=pool.shape[1] // tp,
                    page_size=engine.page_size, head_dim=pool.shape[3],
                    dtype=pool.dtype,
                    max_pages=ring
                    or engine.cache["block_tables"].shape[1],
                    s_q=engine.draft_len + 1, window=g.window)))
        # TPOT-SLO burn rate: (time, missed) per SLO-carrying retirement
        # inside the policy's rolling window (pump-confined state)
        self._slo_window: deque = deque()
        self._slo_burn = metrics.gauge("serving.slo_burn", labels=labels)
        # recompile watcher (docs/observability.md): process-wide hooks,
        # per-frontend delta window for stats + storm warnings
        self._watch = compile_watch.watcher()
        self._jit0 = self._watch.counts()
        self._jit_totals0 = self._watch.totals()
        self._compiles_seen = self._jit_totals0[0]
        self._storm_seen: set = set()
        self._thread: Optional[threading.Thread] = None
        self._stop_evt = threading.Event()
        self._work_evt = threading.Event()
        self._failure: Optional[BaseException] = None
        self._accepting = True           # cleared by shutdown()

    # --- ingest -------------------------------------------------------------

    def submit(self, request: Request, *,
               request_id: Optional[int] = None) -> StreamHandle:
        """Enqueue one request; returns its streaming handle immediately.

        Thread-safe. Validates the request's position/page budget up
        front (``ValueError`` surfaces to the submitter, never to the
        pump). ``request_id`` defaults to a per-frontend sequence number;
        it keys the tracer's lifecycle AND the request's sampling stream
        (``fold_in(rng, request_id)``), so two frontends given the same
        ids and rng draw identical streams."""
        # lock-free fast-fail is intentional double-checked locking (one
        # snapshot read): the locked re-check below is authoritative;
        # this only saves validation work on an already-dead frontend
        # tpu-lint: disable=conc-unguarded-shared-field -- benign race
        failure = self._failure
        if failure is not None:
            raise ServingError("frontend pump has failed") from failure
        self.engine._validate_request(request)
        if self.fault_hook is not None:
            # admission-reject faults raise HERE, before any state is
            # touched — the submitter (or the router's retry path) sees
            # a clean ServingError and nothing dangles
            self.fault_hook.on_submit(self, request)
        seq = next(self._submit_seq)
        idx = request_id if request_id is not None else seq
        now = self.clock()
        arrival = request.arrival_time if request.arrival_time is not None \
            else now
        deadline_at = (arrival + request.deadline_ms * 1e-3
                       if request.deadline_ms is not None else None)
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        handle = StreamHandle(idx)
        entry = _Entry(idx, handle, prompt, request.max_new_tokens,
                       request.priority, deadline_at, arrival, seq)
        entry.tpot_slo = request.tpot_slo_ms
        # trace propagation (docs/observability.md "Fleet plane"): the
        # enqueue event binds this request id to its fleet-wide trace —
        # a routed request arrives with the router's mint, a direct
        # submit mints here, and stitch_traces() joins every replica's
        # spans on it
        trace_id = request.trace_id if request.trace_id is not None \
            else fleet.mint_trace_id()
        entry.t_enqueue = self.tracer.event(
            idx, "enqueue", prompt_tokens=int(prompt.shape[0]),
            max_new_tokens=request.max_new_tokens,
            priority=request.priority, deadline_ms=request.deadline_ms,
            trace_id=trace_id).t_start
        with self._ingest_lock:
            # re-check under the lock: a pump failure drains the ingest
            # queue under this lock, so an entry either lands before the
            # drain (and is failed with the rest) or raises here — a
            # handle can never be left dangling un-finished
            if self._failure is not None:
                raise ServingError("frontend pump has failed") \
                    from self._failure
            if not self._accepting:
                raise ServingError("frontend is shutting down")
            self._ingest.append(entry)
            depth = len(self._ingest) + len(self._pending)
            # peak tracking is a read-modify-write; two racing submits
            # outside the lock could each lose the other's peak
            self.peak_queue_depth = max(self.peak_queue_depth, depth)
        self._qdepth.set(depth)
        self._work_evt.set()
        return handle

    @property
    def queue_depth(self) -> int:
        with self._ingest_lock:
            return len(self._ingest) + len(self._pending)

    @property
    def active_slots(self) -> int:
        """Slots currently decoding (an instantaneous read — the pump
        owns ``_active``; ``len`` of a dict is atomic in CPython)."""
        return len(self._active)

    @property
    def pump_alive(self) -> bool:
        """True while the background pump thread is running (the
        ``/healthz`` liveness bit; a synchronously driven frontend
        reports False — its caller IS the pump)."""
        thread = self._thread
        return thread is not None and thread.is_alive()

    @property
    def failure(self) -> Optional[BaseException]:
        """The pump's terminal failure, if any (``/healthz`` surfaces
        its repr)."""
        with self._ingest_lock:
            return self._failure

    def _drain_ingest(self) -> None:
        with self._ingest_lock:
            while self._ingest:
                self._pending.append(self._ingest.popleft())

    # --- lint-harness accessors (shared with analysis/ir/harness.py) --------

    def admission_program(self, s0: int):
        """The compiled cold-admission program + compile-key bucket the
        pump uses for a raw prompt length. The IR lint harness traces
        THIS accessor at two same-bucket lengths
        (``ir-compile-key-cardinality``), so the contract binds the
        frontend's real bucketing — shared with ``scheduler.run()``'s
        path, never mirrored."""
        eng = self.engine
        bucket = prompt_bucket(s0, eng.page_size,
                               eng.cfg.max_position_embeddings)
        if eng.draft_len:
            return eng._spec_admit_fn(bucket), bucket
        return eng._admit_fn(bucket), bucket

    def decode_program(self):
        """The jitted ``sync_every``-step decode chunk the pump
        dispatches (the engine's ``_step_fn`` — or its speculative twin
        ``_spec_step_fn`` when the engine drafts — one program,
        shared)."""
        eng = self.engine
        return eng._spec_step_fn() if eng.draft_len else eng._step_fn()

    # --- the pump -----------------------------------------------------------

    # tpu-lint: host-boundary -- the pump is the host scheduling loop
    # driving the jitted admit/step programs; it syncs at every chunk
    # harvest by contract and is never traced
    def pump(self) -> bool:
        """One scheduler iteration: dispatch the next decode chunk, then
        (overlapping its device execution) harvest the previous chunk —
        retire/stream/spill — and run admission/preemption. Returns True
        while work remains. Raises ``RuntimeError`` on scheduler
        deadlock (a queued request that cannot be admitted even with
        every slot vacant and every evictable page evicted).

        Any exception out of the pump — an engine fault, a deadlock, an
        injected kill — is TERMINAL: the failure is published (later
        ``submit`` calls raise it) and every live handle fails with a
        :class:`ServingError` before the exception propagates, so a
        consumer blocked on ``result()``/iteration is woken within one
        boundary instead of hanging forever (the pump-death contract;
        same path for the synchronous and background drivers)."""
        try:
            if self.fault_hook is not None:
                self.fault_hook.on_pump(self)
            return self._pump_impl()
        except BaseException as exc:          # noqa: BLE001 — terminal
            self._fail_all(exc)
            raise

    def _fail_all(self, exc: BaseException) -> None:
        """Publish the pump's terminal failure and fail every live
        handle (ingest + pending + active). Idempotent — the first
        failure wins; the ingest queue is claimed atomically with the
        publication so ``submit`` can never leave a handle dangling."""
        with self._ingest_lock:
            if self._failure is not None:
                return
            self._failure = exc
            victims = list(self._ingest)
            self._ingest.clear()
        victims += list(self._pending) + list(self._active.values())
        self._pending.clear()
        self._active.clear()
        self._inflight = None
        for entry in victims:
            entry.handle._fail(exc)
        self._emit_slowest()

    # tpu-lint: host-boundary -- body of pump() (see above)
    def _pump_impl(self) -> bool:
        eng = self.engine
        t_iter0 = self.clock()
        self._wait_s = 0.0
        self._iter_s = dict.fromkeys(_PHASE_COUNTERS, 0.0)
        finished0, gc0 = self._finished, self._gc_s
        self._drain_ingest()
        prev, self._inflight = self._inflight, None
        if any(not e.prefilling for e in self._active.values()):
            # the device sat idle iff everything dispatched so far has
            # already completed: either nothing was in flight (the last
            # chunk's completion time is in _last_ready), or the chunk
            # still nominally in flight was materialized early by an
            # admission's pool read — and then the device went on to run
            # that admission's prefill, so it is idle from the
            # first-token sync (_last_ready moves there), not from the
            # chunk's end. The gap from that instant to this dispatch is
            # the pipeline bubble the double-buffering exists to hide —
            # pay attention when it grows.
            idle_since = self._last_ready \
                if prev is None or prev.t_done is not None else None
            with self._phase("dispatch"):
                self._dispatch()
            if idle_since is not None:
                bubble_ms = max(0.0,
                                (self._inflight.t0 - idle_since) * 1e3)
                self._bubble.set(bubble_ms)
                self._per_run["pump.bubble_ms"].append(bubble_ms)
                self._C["pump_bubble_seconds"].inc(bubble_ms * 1e-3)
                self._last_ready = None
        if eng.host_tier is not None:
            # demote copies dispatched at earlier boundaries ride the
            # double-buffered host-work slot: the next chunk is already
            # in flight above, so converting the gathered tiles to host
            # entries here overlaps the device, not the pipeline
            with self._phase("housekeeping"):
                eng.host_tier.drain()
        if prev is not None:
            with self._phase("harvest"):
                self._harvest(prev)
        with self._phase("housekeeping"):
            self._backpressure_spill()
            self._drop_window_pages()
        with self._phase("admission"):
            self._advance_prefills()
            admitted = self._admission()
        if (any(not self._bp_held(e) for e in self._pending)
                and not self._active and self._inflight is None
                and not admitted):
            raise RuntimeError(
                "scheduler deadlock: queued request cannot be admitted "
                "even with every slot vacant and every evictable cached "
                "page evicted (pool too small for its page demand?)")
        if self._pool_dirty:
            with self._phase("housekeeping"):
                # the gauges read the pool on the host: a wait for the
                # stream, named as one
                self._await_pool(kv_pool.free_page_count(eng.cache))
                kv_pool.observe_pool(eng.cache, labels=eng.obs_labels)
            self._pool_dirty = False
        self._qdepth.set(len(self._pending))
        wall_s = self.clock() - t_iter0
        if prev is not None or admitted:
            # host cost of this iteration net of time blocked on the
            # device (_wait_s: every pump:wait_device phase) — with the
            # chunk in flight, this is the work the pipeline hides
            # (bubble_ms above is what leaked through)
            host_ms = max(0.0, (wall_s - self._wait_s) * 1e3)
            self._host_H.observe(host_ms)
            self._per_run["pump.host_work_ms"].append(host_ms)
            self._C["pump_iterations"].inc()
            self._C["pump_host_seconds"].inc(host_ms * 1e-3)
        self._note_iteration(wall_s, admitted, finished0, gc0)
        self._check_compile_storm()
        # a pending entry held by backpressure does not count as live
        # work: the pump has nothing to do for it until its consumer
        # catches up, so the background loop falls back to its bounded
        # re-poll (work_evt wait) instead of busy-spinning, and a
        # synchronous drain() returns rather than hanging on a socket
        alive = bool(self._active or self._inflight
                     or any(not self._bp_held(e) for e in self._pending))
        if not alive:
            self._last_ready = None      # idle gaps are not bubbles
        return alive

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One pump phase: a ``pump:<name>`` host span on the profiler's
        clock (beside the device line in an xprof capture). Phases nest
        two levels deep, named where the work happens
        (``pump:admission.launch`` inside ``pump:admission``): a phase's
        seconds INCLUDE its children and are NET of every
        ``wait_device`` under it (every host block on a device value
        sums into ``_wait_s``); its self time is its seconds less its
        children's. A top-level phase adds its seconds to
        ``_PHASE_COUNTERS[name]`` and to this iteration's split on exit;
        the second level is read from the trace and feeds nothing."""
        counter = _PHASE_COUNTERS.get(name)
        with jax.profiler.TraceAnnotation("pump:" + name):
            t0, waited0 = self.clock(), self._wait_s
            try:
                yield
            finally:
                seconds = self.clock() - t0
                if name == "wait_device":
                    self._wait_s += seconds
                else:
                    seconds -= self._wait_s - waited0
                if counter is not None:
                    seconds = max(0.0, seconds)
                    self._C[counter].inc(seconds)
                    self._iter_s[name] += seconds

    def _await(self, value) -> np.ndarray:
        """Block for a device value, as a ``pump:wait_device`` phase."""
        with self._phase("wait_device"):
            return np.asarray(value)

    def _await_pool(self, value) -> np.ndarray:
        """Block for a value of ``eng.cache``. The read waits for
        everything queued on the stream, the chunk in flight included:
        that chunk is stamped FIRST, so ``decode_step_ms`` never charges
        later host work to it and the next dispatch finds the moment the
        device went idle (``pump.bubble_ms``)."""
        if self._inflight is not None:
            self._materialize(self._inflight)
        return self._await(value)

    def _note_iteration(self, wall_s: float, admitted: int, finished0: int,
                        gc0: float) -> None:
        """Keep this iteration if it is among the ``_SLOWEST_KEPT``
        longest of the frontend's life: what an untraced run can say of
        a stall (``stats()["pump.slowest"]``; docs/observability.md
        "Reading a postmortem"). One comparison an iteration."""
        self._iterations += 1
        compiles = self._watch.totals()[0]
        compiled, self._compiles_seen = (compiles - self._compiles_seen,
                                         compiles)
        wall_ms = wall_s * 1e3
        full = len(self._slowest) == _SLOWEST_KEPT
        if full and wall_ms <= self._slowest[0][0]:
            return
        host_ms = {name: s * 1e3 for name, s in self._iter_s.items()
                   if name != "wait_device"}
        record = {"iteration": self._iterations, "wall_ms": wall_ms,
                  "wait_ms": self._wait_s * 1e3, "host_ms": host_ms,
                  "admitted": admitted,
                  "retired": self._finished - finished0,
                  "compiles": compiled,
                  "gc_ms": (self._gc_s - gc0) * 1e3}
        (heapq.heapreplace if full else heapq.heappush)(
            self._slowest, (wall_ms, self._iterations, record))

    def _slowest_records(self) -> List[dict]:
        """The longest pump iterations so far, longest first."""
        return [record for _, _, record
                in sorted(self._slowest, reverse=True)]

    def _emit_slowest(self) -> None:
        """At the pump's end (death or shutdown), once: the longest
        iterations go into the engine's event ring, so the postmortem
        dump and the router's flight bundle carry them."""
        if self._slowest_emitted:
            return
        self._slowest_emitted = True
        self.engine.events.emit("pump_slowest",
                                iterations=self._slowest_records())

    def _gc_hook(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry while the background pump runs."""
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            pause = self.clock() - self._gc_t0
            self._gc_t0 = None
            self._gc_s += pause
            self._C["gc_pause_seconds"].inc(pause)

    def _await_first_token(self, tok) -> int:
        """Block for an admission's sampled token. The chunk in flight
        was stamped before the admission program was queued, so what is
        left to wait for is the prefill — the last thing on the stream:
        the device idles from here (``_last_ready``), not from the
        chunk's end."""
        tok0 = int(self._await(tok))
        self._last_ready = self.clock()
        return tok0

    # tpu-lint: host-boundary -- synchronous drive of the pump loop
    def drain(self) -> None:
        """Pump until every submitted request has retired (what
        ``engine.run()`` does); leaves the pool gauges fresh."""
        while self.pump():
            pass
        self._occ.set(0)
        kv_pool.observe_pool(self.engine.cache, labels=self.engine.obs_labels)

    def start(self) -> None:
        """Run the pump on a background thread until ``stop()``; a pump
        failure (e.g. deadlock) marks every live handle failed and is
        re-raised by later ``submit`` calls."""
        if self._thread is not None:
            raise RuntimeError("frontend already started")
        self._stop_evt.clear()

        def loop():
            # the collector's pauses, for the slowest iterations' record:
            # hooked while this thread runs, so stop() / shutdown() and a
            # pump's death all take it out again
            gc.callbacks.append(self._gc_hook)
            try:
                while not self._stop_evt.is_set():
                    if not self.pump():
                        self._work_evt.clear()
                        self._occ.set(0)
                        self._work_evt.wait(timeout=0.01)
            except BaseException as exc:          # noqa: BLE001
                # pump() already published the failure and failed every
                # live handle; this covers an exception in the loop
                # bookkeeping itself (idempotent either way)
                self._fail_all(exc)
            finally:
                gc.callbacks.remove(self._gc_hook)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serving-frontend-pump")
        self._thread.start()

    def stop(self, timeout: Optional[float] = 30.0) -> None:
        """Stop the background pump thread (in-flight device work is
        left to complete; pending requests stay queued). For a clean
        end-of-life under load — queued + active + mid-stream requests
        resolved, zero leaked pages, zero dangling threads — use
        :meth:`shutdown` instead."""
        if self._thread is None:
            return
        self._stop_evt.set()
        self._work_evt.set()
        self._thread.join(timeout)
        self._thread = None

    def _has_work(self) -> bool:
        return bool(self.queue_depth or self._active or self._inflight)

    def _cancel_live(self) -> None:
        """Cancel every live handle (ingest snapshot under the lock;
        pending/active are pump-confined lists — ``list()`` snapshots
        are safe to iterate from any thread)."""
        with self._ingest_lock:
            victims = [e.handle for e in self._ingest]
        victims += [e.handle for e in list(self._pending)]
        victims += [e.handle for e in list(self._active.values())]
        for handle in victims:
            handle.cancel()

    def shutdown(self, deadline_s: float = 30.0, *,
                 mode: str = "drain") -> None:
        """Graceful end-of-life under load: stop accepting (later
        ``submit`` raises :class:`ServingError`), then resolve every
        queued + active + mid-stream request deterministically —
        ``mode="drain"`` finishes them (falling back to cancellation
        once ``deadline_s`` expires), ``mode="cancel"`` cancels them
        up front (each stream terminates at its next sync boundary with
        the already-streamed tokens as its truncated output). Either
        way every handle reaches ``done``, every non-cached pool page
        returns to the free stack, and the background thread (if any)
        is joined — zero dangling threads. A pump failure during the
        wind-down has already failed the handles; shutdown still stops
        the thread and returns."""
        if mode not in ("drain", "cancel"):
            raise ValueError(f"shutdown mode must be 'drain' or "
                             f"'cancel', got {mode!r}")
        with self._ingest_lock:
            self._accepting = False
        deadline = self.clock() + deadline_s
        if mode == "cancel":
            self._cancel_live()
        if self._thread is not None:
            # the background pump drives itself; wait for quiescence,
            # cancelling the stragglers once the deadline passes
            cancelled = mode == "cancel"
            while (self._has_work() and self.pump_alive
                   and self.failure is None):
                if self.clock() >= deadline:
                    if cancelled:
                        break
                    self._cancel_live()
                    cancelled = True
                    deadline = self.clock() + max(deadline_s, 1.0)
                time.sleep(0.002)
            self.stop()
        # we own the pump now (or always did): drive what remains.
        # Draining is deadline-bounded; once everything is cancelled the
        # loop is bounded by a pump budget instead of wall time (cancels
        # resolve within ~two boundaries, and an injected test clock
        # never advances), so shutdown always terminates
        cancelled = mode == "cancel"
        budget: Optional[int] = None
        try:
            while self._has_work():
                if not cancelled and self.clock() >= deadline:
                    self._cancel_live()
                    cancelled = True
                if cancelled:
                    if budget is None:
                        budget = 4 * self.engine.num_slots + 16
                    budget -= 1
                    if budget < 0:
                        break
                if not self.pump():
                    # a drain can go idle with backpressure-HELD entries
                    # still pending (their consumers stalled): keep
                    # waiting for consumption until the deadline flips
                    # us to cancellation; anything else idle is done
                    if cancelled or not any(self._bp_held(e)
                                            for e in self._pending):
                        break
                    time.sleep(0.002)
        except Exception:                # noqa: BLE001 — handles already
            pass                         # failed by pump(); stop cleanly
        leftovers = []
        with self._ingest_lock:
            leftovers += list(self._ingest)
            self._ingest.clear()
        leftovers += list(self._pending) + list(self._active.items())
        self._pending.clear()
        if self._active and self.failure is None:
            # release the stragglers' pages before failing them — the
            # zero-leak contract holds even when the deadline expired
            # with slots still decoding
            for slot, entry in list(self._active.items()):
                self._release_pages(slot, entry)
                self._done = self._done.at[slot].set(True)
            self._active.clear()
        exc = ServingError(f"frontend shutdown ({mode}) deadline "
                           f"expired with requests unresolved")
        for item in leftovers:
            entry = item[1] if isinstance(item, tuple) else item
            entry.handle._fail(exc)
        self._occ.set(0)
        self._qdepth.set(0)
        self._emit_slowest()
        if self.failure is None:
            kv_pool.observe_pool(self.engine.cache,
                                 labels=self.engine.obs_labels)

    # --- device chunk dispatch/harvest --------------------------------------

    def _dispatch(self) -> None:
        """Launch the next decode chunk, THEN work out its account: with
        the device idle, nothing the host only measures stands between
        it and the launch."""
        eng = self.engine
        self._chunk += 1
        with self._phase("dispatch.launch"):
            t0 = self.clock()
            if eng.draft_len:
                # speculative chunk: the payload is (target predictions,
                # per-slot per-round acceptance counts) — the harvest
                # emits toks[r, slot, :counts[r, slot]]
                (eng.cache, eng.draft_cache, self._tok, self._done,
                 self._n_left, toks, counts) = eng._spec_step_fn()(
                    eng.cache, eng.draft_cache, eng.variables,
                    eng.draft_variables, self._tok, self._done,
                    self._n_left)
                self._inflight = _Chunk((toks, counts), self._chunk, t0)
            else:
                (eng.cache, self._tok, self._done, self._n_left,
                 self._samp_i, toks, routed) = eng._step_fn()(
                    eng.cache, eng.variables, self._tok, self._done,
                    self._n_left, self._req_keys, self._samp_i)
                self._inflight = _Chunk(toks, self._chunk, t0, routed)
        with self._phase("dispatch.account"):
            self._inflight.account = self._chunk_account()
        self.peak_slots = max(self.peak_slots, len(self._active))
        self._occ.set(len(self._active))

    def _chunk_account(self) -> Dict[str, float]:
        """What the chunk just launched does, by counter, from the
        entries' state as it entered: steps and busy slot-steps, the K/V
        bytes its live slot-steps attend (and their split by kind of
        layer), the bytes the kernel's page blocks move, the bytes the
        decoding slots hold beside the sum of their contexts, and the
        state groups' traffic. Added to the counters when the chunk is
        HARVESTED (``_harvest``), with its routing."""
        eng = self.engine
        decoding = [e for e in self._active.values()
                    if e.joined <= self._chunk]
        ps = eng.page_size
        full = banded = fetched = held = 0.0
        for kv in self._kv_groups:
            window = kv.group.window
            attended = kv.page_bytes / ps * sum(
                self._tokens_attended(e, window) for e in decoding)
            if window is None:
                full += attended
            else:
                banded += attended
            fetched += kv.page_bytes * sum(
                self._pages_moved(e, kv) for e in decoding)
            # pages the slots own: a ring group's R each, for ever; the
            # block table's as the entry holds them now
            held += kv.page_bytes * (
                kv.ring * len(decoding) if kv.ring
                else sum(e.n_private for e in decoding))
        # the state groups' bytes: held like a ring's pages, for ever, and
        # moved whole, in and out, by every step
        state = self._state_bytes_per_slot * len(decoding)
        held += state
        return {
            "decode_steps": eng.sync_every,
            "busy_slot_steps": len(decoding) * eng.sync_every,
            "state_bytes_moved": 2 * state * eng.sync_every,
            "kv_bytes_attended": full + banded,
            "kv_full_bytes_attended": full,
            "kv_window_bytes_attended": banded,
            "kv_bytes_fetched": fetched,
            "kv_bytes_held_steps": held * eng.sync_every,
            "context_token_steps": eng.sync_every * sum(
                e.s0 + (self._chunk - e.joined) * eng.sync_every + 1
                for e in decoding)}

    def _tokens_attended(self, entry: _Entry, window: Optional[int]) -> int:
        """Context tokens the chunk just launched attends for one
        decoding slot in a layer of the given ``window`` (``None``:
        full), summed over its LIVE steps: step ``j`` of a slot that has
        run ``ran`` steps since it joined reads the K/V of ``s0 + ran +
        j + 1`` tokens (its own included), banded to the window; steps
        past the token budget are frozen and read nothing the algorithm
        needs. What the algorithm must read — the kernel's grid may touch
        more. (A speculative chunk is counted one verify round per step
        at the least context the round can have.)"""
        eng = self.engine
        ran = (self._chunk - entry.joined) * eng.sync_every
        live = min(eng.sync_every, max(entry.seg_new - 1 - ran, 0))
        first = entry.s0 + ran + 1
        if window is not None:
            return sum(min(window, first + j) for j in range(live))
        return live * first + live * (live - 1) // 2

    def _pages_moved(self, entry: _Entry, kv: "_KvGroup") -> int:
        """Pages the decode kernel fetches for one decoding slot in a
        layer of group ``kv`` over the chunk just launched, summed
        over ALL its steps: the kernel moves whole page blocks
        (``ops._page_walk.pages_fetched``), and a frozen step still
        runs the forward at the slot's last length. Over
        ``_tokens_attended`` x page_size this is the rounding the tile
        costs. A ring group's call sees the slot from the band's first
        page on (``kv_pool.ring_view``)."""
        eng = self.engine
        ran = (self._chunk - entry.joined) * eng.sync_every
        moved = 0
        for j in range(eng.sync_every):
            length = entry.s0 + min(ran + j, max(entry.seg_new - 1, 0)) + 1
            if kv.ring:
                length -= (max(length - kv.group.window, 0)
                           // eng.page_size * eng.page_size)
            moved += kv.pages_fetched(length)
        return moved

    def _materialize(self, chunk: _Chunk) -> np.ndarray:
        """Block for the chunk's tokens (overlapping whatever device
        work was queued after it) and stamp its completion time, once —
        idempotent, so the earliest host sync that implies the chunk is
        done (harvest, or an admission's pool read) fixes the
        measurement before unrelated host work can inflate it."""
        if chunk.toks_np is None:
            # the blocked span counts as device wait, not host work
            with self._phase("wait_device"):
                chunk.toks_np = (tuple(np.asarray(t) for t in chunk.toks)
                                 if isinstance(chunk.toks, tuple)
                                 else np.asarray(chunk.toks))
                chunk.t_done = self.clock()
            self._last_ready = chunk.t_done
        return chunk.toks_np

    def _harvest(self, chunk: _Chunk, *, phase: str = "steady") -> None:
        eng = self.engine
        toks_np = self._materialize(chunk)
        chunk_ms = (chunk.t_done - chunk.t0) * 1e3
        step_ms = chunk_ms / eng.sync_every
        self._H["decode_step_ms"].observe(step_ms)
        self._per_run["decode_step_ms"].append(step_ms)
        self._pump_H[("pump.dispatch_ready_ms", phase)].observe(chunk_ms)
        if phase == "steady":
            # the run percentiles are the STEADY-state device time; a
            # preemption flush harvests mid-chunk and only its labeled
            # histogram keeps that wall time
            self._per_run["pump.dispatch_ready_ms"].append(chunk_ms)
        with self._phase("harvest.account"):
            # the ONE moment a chunk is counted: what it did by its own
            # account (_chunk_account) and what its routing did, so any
            # two snapshots of the counters hold the same whole chunks
            account = chunk.account
            if not isinstance(chunk.routed, tuple):
                # ready with the tokens (three numbers a step, or the
                # four of a layer that holds a share of its experts: zip
                # stops at the vector's end)
                routed = dict(zip(
                    SHARE_ROUTING_STATS,
                    np.asarray(chunk.routed).sum(axis=0).tolist()))
                account = dict(account, **routed, expert_bytes_read=(
                    routed["experts_hit"] * eng.cfg.routed_expert_bytes))
            for name, n in account.items():
                self._C[name].inc(n)
        eos = eng.eos_token_id
        spec = isinstance(toks_np, tuple)
        if spec:
            preds_np, counts_np = toks_np
        for slot in list(self._active):
            entry = self._active[slot]
            if entry.prefilling:
                continue                 # chunked prefill in progress —
            #                             cancellation is handled by
            #                             _advance_prefills
            if entry.handle.cancelled:
                self._retire(slot, cancelled=True)
                self._done = self._done.at[slot].set(True)
                continue
            if entry.joined > chunk.idx:
                continue                 # admitted after this chunk ran
            finished = False
            if spec:
                # per speculative round: the slot's first counts[r]
                # target predictions were accepted+emitted on device
                for r in range(preds_np.shape[0]):
                    cnt = int(counts_np[r, slot])
                    if cnt:
                        self._C["spec_rounds"].inc()
                        self._C["spec_tokens"].inc(cnt)
                    for t in preds_np[r, slot, :cnt]:
                        t = int(t)
                        entry.seg_tokens.append(t)
                        entry.handle._push(t)
                        if ((eos is not None and t == eos)
                                or entry.generated >= entry.total_new):
                            finished = True
                            break
                    if finished:
                        break
            else:
                for t in toks_np[:, slot]:
                    t = int(t)
                    entry.seg_tokens.append(t)
                    entry.handle._push(t)
                    if ((eos is not None and t == eos)
                            or entry.generated >= entry.total_new):
                        finished = True
                        break
            if finished:
                self._retire(slot)
                self._done = self._done.at[slot].set(True)

    def _drop_window_pages(self) -> None:
        """Sliding-window models only: free every active slot's pages
        that fell fully below the attention band — the rolling-cache
        eviction trick at page granularity (``kv_pool.drop_slot_pages``).
        Block-table entry ``j`` is dead once the NEXT query position
        ``p`` satisfies ``(j+1)*page_size - 1 <= p - window``; the band
        only moves forward, so a dead entry stays dead and each page
        frees exactly once. The drop is an async dispatch queued AFTER
        the in-flight decode chunk on the device stream, so program
        order keeps the chunk's banded reads ahead of it."""
        eng = self.engine
        window = eng.window
        if window is None:
            return
        ps = eng.page_size
        for slot, entry in self._active.items():
            # device len at the last harvested boundary = prompt + every
            # decode step run (tok0 samples at admit, writes at step 1);
            # the next query position equals that len
            nxt = entry.s0 + len(entry.seg_tokens) - 1
            upto = max((nxt + 1 - window) // ps, 0)
            if upto > entry.win_dropped:
                eng.cache = eng._drop_jit(eng.cache, jnp.int32(slot),
                                          jnp.int32(upto))
                freed = upto - entry.win_dropped
                entry.win_dropped = upto
                entry.n_private -= freed
                self._C["window_dropped_pages"].inc(freed)
                self._pool_dirty = True

    def _flush(self) -> None:
        """Synchronize the pipeline: harvest the in-flight chunk (if
        any) so every active record's token state is current — the
        precondition for a correct preemption spill."""
        prev, self._inflight = self._inflight, None
        if prev is not None:
            self._harvest(prev, phase="preempt")

    # --- retirement / preemption --------------------------------------------

    def _release_pages(self, slot: int, entry: _Entry) -> None:
        """Return slot ``slot``'s pages with the prefix-cache disposition:
        full written pages (prompt + fed tokens) move into the radix tree
        (so a later match — including this request's own resume — hits),
        the partial tail frees; without a prefix cache everything
        frees."""
        eng = self.engine
        with self._phase("retire.release"):
            if eng.prefix is None:
                eng.cache = eng._free_jit(eng.cache, jnp.int32(slot))
                if eng.draft_len:
                    # the draft pool mirrors the target pool slot-for-slot
                    eng.draft_cache = eng._draft_free_jit(
                        eng.draft_cache, jnp.int32(slot))
                return
            if entry.prefilling:
                # a mid-prefill release (cancel/shutdown): only the chunks
                # already fed are written — their full pages are cacheable
                written = entry.pf_pos
                seq = entry.prompt[:written]
            else:
                # written K/V = prompt + every token fed while alive (all
                # but the final sampled token); only full pages are
                # shareable
                written = entry.s0 + len(entry.seg_tokens) - 1
                seq = np.concatenate(
                    [entry.prompt, np.asarray(entry.seg_tokens[:-1],
                                              np.int32)])
            # the read waits for whatever produces ``eng.cache`` — with a
            # chunk in flight, for that whole chunk
            row = self._await_pool(eng.cache["block_tables"][slot])
            keep = eng.prefix.release_and_insert(seq, written, entry.nodes,
                                                 row)
            eng.cache = eng._release_jit(eng.cache, jnp.int32(slot),
                                         jnp.asarray(keep))

    def _observe_lifecycle(self, idx) -> dict:
        life = self.tracer.lifecycle(idx)
        for name in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
            if name in life:
                self._H[name].observe(life[name])
                self._per_run[name].append(life[name])
        return life

    def _observe_slo(self, entry: _Entry, life: dict, now: float) -> None:
        """The TPOT-SLO check (once, at retirement) + the rolling burn
        gauge over the policy's window: SLO-carrying retirements that
        missed either their TTFT deadline or their TPOT target, as a
        rate. Pump-confined — no lock."""
        missed_tpot = (entry.tpot_slo is not None
                       and life.get("tpot_ms") is not None
                       and life["tpot_ms"] > entry.tpot_slo)
        if missed_tpot:
            self._C["tpot_slo_misses"].inc()
            self.tracer.event(entry.idx, "tpot_slo_miss",
                              tpot_ms=life["tpot_ms"],
                              slo_ms=entry.tpot_slo)
            self.engine.events.emit("tpot_slo_miss", request=entry.idx,
                                    tpot_ms=round(life["tpot_ms"], 3),
                                    slo_ms=entry.tpot_slo)
        if entry.tpot_slo is None and entry.deadline_at is None:
            return
        self._slo_window.append(
            (now, bool(missed_tpot or entry.deadline_missed)))
        horizon = now - self.policy.slo_window_s
        while self._slo_window and self._slo_window[0][0] < horizon:
            self._slo_window.popleft()
        misses = sum(1 for _, m in self._slo_window if m)
        self._slo_burn.set(misses / len(self._slo_window))

    def _account_finish(self, entry: _Entry, *, slot: Optional[int],
                        cancelled: bool) -> np.ndarray:
        """The account of EVERY handle the frontend finishes, once,
        wherever it is finished (a decoding slot, a slot mid-prefill, or
        the queue: ``slot`` None): ``retired`` counts it, the tracer's
        ``retire`` instant and the ring's ``retire`` / ``cancel`` event
        name it. Returns the output; the caller hands it to the handle
        once the slot's pages are released."""
        output = np.asarray(entry.prev + entry.seg_tokens, np.int32)
        new_tokens = int(output.shape[0])
        self._finished += 1
        self._C["retired"].inc()
        self.tracer.event(entry.idx, "retire", slot=slot,
                          new_tokens=new_tokens, cancelled=cancelled)
        where = {"queued": True} if slot is None else {"slot": slot}
        self.engine.events.emit("cancel" if cancelled else "retire",
                                request=entry.idx, new_tokens=new_tokens,
                                **where)
        return output

    def _retire(self, slot: int, *, cancelled: bool = False) -> None:
        with self._phase("retire"):
            entry = self._active.pop(slot)
            if entry.prefilling:
                # cancelled mid-prefill: no decode state exists
                self.tracer.end(entry.idx, "prefill")
            else:
                self.tracer.end(entry.idx, "decode",
                                new_tokens=len(entry.seg_tokens))
            output = self._account_finish(entry, slot=slot,
                                          cancelled=cancelled)
            if not entry.prefilling:
                life = self._observe_lifecycle(entry.idx)
                if not cancelled:
                    self._observe_slo(entry, life, self.clock())
            self._release_pages(slot, entry)
            self._pool_dirty = True
            entry.handle._finish(output)

    def _preempt(self, slot: int) -> None:
        """Stop the victim at this (flushed) sync boundary, spill its
        full pages into the prefix cache, and requeue it for resumption
        with its generated tokens folded into the prompt — the resume
        admission re-prefills only the uncached tail."""
        eng = self.engine
        entry = self._active.pop(slot)
        self.tracer.end(entry.idx, "decode",
                        new_tokens=len(entry.seg_tokens))
        self.tracer.begin(entry.idx, "preempted")
        self.tracer.event(entry.idx, "preempt", slot=slot,
                          generated=entry.generated)
        self._C["preemptions"].inc()
        eng.events.emit("preempt", request=entry.idx, slot=slot,
                        generated=entry.generated)
        self._release_pages(slot, entry)
        self._pool_dirty = True
        self._done = self._done.at[slot].set(True)
        # fold the segment into the entry: the resume prompt carries
        # every generated token (incl. the never-written last one — its
        # K/V re-prefills), the budget shrinks by what was delivered
        entry.prompt = np.concatenate(
            [entry.prompt, np.asarray(entry.seg_tokens, np.int32)])
        entry.prev = entry.prev + entry.seg_tokens
        entry.seg_tokens = []
        entry.nodes = []
        entry.resume = True
        self._pending.append(entry)

    # --- consumption-aware backpressure (docs/http.md) ----------------------

    def _bp_stalled(self, entry: _Entry) -> bool:
        """An ACTIVE slot whose consumer is stalled past the window —
        a backpressure-spill victim. Cancelled handles are excluded
        (harvest retires them; their pages free anyway)."""
        w = self.backpressure_window
        return (w is not None and not entry.prefilling
                and not entry.handle.cancelled
                and entry.handle.unread() > w)

    def _bp_held(self, entry: _Entry) -> bool:
        """A PENDING entry whose consumer is still behind — held out of
        admission (re-admitting would spill again next boundary).
        Hysteresis: released once unread falls to half the window, so a
        resumed slot gets a full half-window of runway. Cancelled
        entries are never held (admission finishes them)."""
        w = self.backpressure_window
        return (w is not None and entry.resume
                and not entry.handle.cancelled
                and entry.handle.unread() > w // 2)

    def _backpressure_spill(self) -> None:
        """Spill every active slot whose reader stalled past the
        in-flight-token window through the PREEMPTION path: flush the
        pipeline, release the slot's full pages into the radix cache
        (partial tail frees), requeue the entry for resume-on-
        consumption. This bypasses the policy's ``wants_preempt`` gate —
        the victim is not losing its slot to a more urgent request, it
        is refusing to pin pool pages behind a dead socket."""
        if self.backpressure_window is None or not self._active:
            return
        victims = [s for s, e in self._active.items()
                   if self._bp_stalled(e)]
        if not victims:
            return
        self._flush()                    # victim state must be current
        for slot in victims:
            entry = self._active.get(slot)
            if entry is None or not self._bp_stalled(entry):
                continue                 # the flush retired/changed it
            self._C["backpressure_spills"].inc()
            self.tracer.event(entry.idx, "backpressure_spill",
                              slot=slot, unread=entry.handle.unread())
            self.engine.events.emit("backpressure_spill",
                                    request=entry.idx, slot=slot,
                                    unread=entry.handle.unread())
            self._preempt(slot)

    def _maybe_preempt(self, candidate: _Entry, now: float) -> bool:
        """Try to free a slot (and spill pages) for a blocked
        ``candidate``. True when the boundary state changed (a victim
        was preempted, or the flush itself retired slots) — the caller
        retries the candidate's admission."""
        eng = self.engine
        if not self.policy.wants_preempt(candidate, now):
            return False
        # a candidate the whole pool cannot hold is a deadlock, not a
        # preemption target — don't kill running work for it
        need_total = kv_pool.pages_for(candidate.s0 + candidate.seg_new,
                                       eng.page_size)
        if need_total > kv_pool.num_pages_of(eng.cache) - 1:
            return False
        # a mid-prefill slot has emitted nothing and holds no decode
        # state to fold back — never a preemption victim
        decoding = {s: e for s, e in self._active.items()
                    if not e.prefilling}
        victim_slot = self.policy.select_victim(candidate, decoding, now)
        if victim_slot is None:
            return False
        n_active = len(self._active)
        self._flush()                    # victim state must be current
        if victim_slot not in self._active:
            return True                  # the flush retired it — retry
        if len(self._active) < n_active and any(
                s not in self._active for s in range(eng.num_slots)):
            return True                  # flush freed another slot
        self._preempt(victim_slot)
        return True

    # --- tiered pool (docs/serving.md "Tiered KV pool") ---------------------

    def _demote(self, victims) -> None:
        """Dispatch the device->host gather of evicted pages about to be
        pushed onto the free stack: ``victims`` is the eviction sink's
        ``(path_keys, page)`` list. Each ``HOST_COPY_CHUNK`` batch is one
        async ``gather_pages`` call (null-padded row — depth is data);
        the tiles land in the tier as PENDING device arrays and convert
        to host entries at the pump's next host-work slot."""
        eng = self.engine
        C = kv_pool.HOST_COPY_CHUNK
        for i in range(0, len(victims), C):
            grp = victims[i:i + C]
            row = np.zeros((C,), np.int32)
            row[:len(grp)] = [page for _, page in grp]
            tiles = eng._gather_jit(eng.cache, jnp.asarray(row))
            eng.host_tier.put_pending([path for path, _ in grp], tiles,
                                      n=len(grp))

    def _try_promote(self, entry: _Entry, nodes: list) -> list:
        """Extend ``entry``'s tree match with consecutive host-resident
        pages: scatter their demoted bytes into freshly popped pages
        (``kv_pool.promote_pages`` — bit-stable, never a re-prefill) and
        graft them into the radix tree, returning the extended node path
        for the ordinary shared admission. The match FLOOR is computed
        first (a resume matches at its exact written depth, a cold
        admission at the power-of-two bucket) so only pages that survive
        the floor promote — a promoted-then-floored page would be a
        wasted copy. When the free stack cannot cover both the promoted
        pages and the admission's remaining private need, the tier
        SWAPS: refcount-0 LRU pages evict (demoting through the same
        sink — the matched path is pinned around the walk so the LRU
        cannot eat it) to make room. If eviction still leaves the stack
        short, the promotion skips (tier entries untouched) and the
        admission proceeds as if the tier had missed."""
        eng = self.engine
        tier = eng.host_tier
        ps = eng.page_size
        prompt, s0 = entry.prompt, entry.s0
        tier.drain()                     # pending demotes become hits
        floor = (lambda d: d) if entry.resume else _bucket_match_pages
        m0 = len(nodes)
        cap = max(s0 - 1, 0) // ps       # match()'s own depth cap
        if m0 >= cap:
            return nodes[:floor(m0)]
        base = tuple(n.key for n in nodes)
        keys = [tuple(int(t) for t in prompt[j * ps:(j + 1) * ps])
                for j in range(m0, cap)]
        r = tier.run_length(base, keys)
        target = floor(m0 + r)
        if target <= m0:
            return nodes[:target]
        h = target - m0
        free = int(self._await_pool(kv_pool.free_page_count(eng.cache)))
        need_after = kv_pool.pages_for(s0 + entry.seg_new, ps) - target
        if free < h + need_after:
            # the tier swap: in a thrashing pool the stack is never
            # free-handed, so evict cold refcount-0 pages (they demote
            # through the same sink) to make room for the hot ones. Pin
            # the matched path first — it is not acquired yet, and the
            # LRU walk must not evict it out from under the promotion.
            eng.prefix.acquire(nodes)
            victims: List[tuple] = []
            pages = eng.prefix.evict(
                h + need_after - free,
                sink=lambda path_, page: victims.append((path_, page)))
            eng.prefix.release(nodes)
            if victims:
                self._demote(victims)
            if pages:
                max_pages = eng.cache["block_tables"].shape[1]
                row = np.zeros((max_pages,), np.int32)
                row[:len(pages)] = pages
                eng.cache = eng._evict_jit(eng.cache, jnp.asarray(row),
                                           jnp.int32(len(pages)))
                self._C["evicted_pages"].inc(len(pages))
                eng.events.emit("evict", request=entry.idx,
                                pages=len(pages))
                free += len(pages)
            if free < h + need_after:
                return nodes[:floor(m0)]
        payloads = []
        path = base
        for i in range(h):
            path = path + (keys[i],)
            payloads.append(tier.pop(path))  # ownership: tier -> pool
        # destinations: the top h free-stack entries, host-read in the
        # same pop order alloc_slot uses — promote_pages decrements
        # free_top by exactly these pages
        stack = self._await(eng.cache["free_stack"])
        page_ids = stack[free - h:free][::-1].astype(np.int32)
        C = kv_pool.HOST_COPY_CHUNK
        t0 = self.clock()
        for i in range(0, h, C):
            n_g = min(C, h - i)
            row = np.zeros((C,), np.int32)
            row[:n_g] = page_ids[i:i + n_g]
            eng.cache = eng._promote_jit(
                eng.cache, jnp.asarray(row), jnp.int32(n_g),
                _stack_tiles(payloads[i:i + n_g], C))
        # block on the promoted pool's scalar: the measured span is the
        # host->device copy the admission program would wait on anyway
        self._await(eng.cache["free_top"])
        tier.observe_promote_ms((self.clock() - t0) * 1e3)
        for i in range(h):
            nodes.append(eng.prefix.insert_promoted(nodes, keys[i],
                                                    int(page_ids[i])))
        self.tracer.event(entry.idx, "promote", pages=h)
        eng.events.emit("promote", request=entry.idx, pages=h)
        self._pool_dirty = True
        return nodes

    # --- admission ----------------------------------------------------------

    def _room_for(self, need: int, idx) -> int:
        """Free pages for an admission that needs ``need``: the stack's
        count, and where that is short the radix tree's LRU pages evicted
        (demoted where there is a host tier) and, where the host's own
        count says pages leaked, a defrag. Returns the free count."""
        eng = self.engine
        free = int(self._await_pool(kv_pool.free_page_count(eng.cache)))
        if free < need and eng.prefix is not None:
            victims: List[tuple] = []
            sink = ((lambda path, page: victims.append((path, page)))
                    if eng.host_tier is not None else None)
            pages = eng.prefix.evict(need - free, sink=sink)
            if victims:
                # demote BEFORE the stack push: the gather is queued on
                # the device stream ahead of any program that could
                # re-allocate (and overwrite) the evicted pages
                self._demote(victims)
            if pages:
                row = np.zeros((eng.cache["block_tables"].shape[1],),
                               np.int32)
                row[:len(pages)] = pages
                eng.cache = eng._evict_jit(eng.cache, jnp.asarray(row),
                                           jnp.int32(len(pages)))
                self._C["evicted_pages"].inc(len(pages))
                eng.events.emit("evict", request=idx, pages=len(pages))
                free += len(pages)
        if free < need and eng._leak_suspected(free, self._active):
            eng._defrag_now()
            self._C["defrag_runs"].inc()
            eng.events.emit("defrag", request=idx)
            free = int(self._await_pool(kv_pool.free_page_count(eng.cache)))
        return free

    def _try_admit(self, entry: _Entry, slot: int, now: float) -> bool:
        """Admit ``entry`` into vacant ``slot`` if the pool can hold it
        (evicting/defragging as needed); False defers it (head-of-line:
        the caller stops the admission pass). Mirrors the engine's
        original admission exactly, plus the resume path: a resume's
        prefix match is NOT floored to a power of two pages — its depth
        is its own written length (already page-quantized), so the full
        spilled prefix is reused and only the ≤ one-page tail
        re-prefills."""
        eng = self.engine
        tr = self.tracer
        cfg, ps = eng.cfg, eng.page_size
        max_pages = eng.cache["block_tables"].shape[1]
        prompt, s0, idx = entry.prompt, entry.s0, entry.idx
        need_total = kv_pool.pages_for(s0 + entry.seg_new, ps)
        # prefix match BEFORE the page check: matched pages are shared,
        # not allocated, so they shrink the demand. Acquire immediately —
        # eviction below must see them pinned, not as LRU victims
        with self._phase("admission.match"):
            nodes = (eng.prefix.match(prompt) if eng.prefix is not None
                     else [])
            if eng.host_tier is not None:
                # tiered pool: extend the tree match with host-resident
                # pages (promote instead of re-prefill); applies the
                # match floor itself, so the plain floor below is the
                # tier-off path
                nodes = self._try_promote(entry, nodes)
            elif not entry.resume:
                nodes = nodes[:_bucket_match_pages(len(nodes))]
            if nodes:
                eng.prefix.acquire(nodes)
        m = len(nodes)
        need = need_total - m
        with self._phase("admission.pool"):
            free = self._room_for(need, idx)
        if free < need:
            if nodes:
                eng.prefix.release(nodes)
            self._C["deferred_admissions"].inc()
            eng.events.emit("defer", request=idx, need_pages=need,
                            free_pages=free)
            return False
        with self._phase("admission.launch"):
            if entry.resume:
                tr.end(idx, "preempted")
                tr.event(idx, "resume", slot=slot, cached_pages=m,
                         resumed_at=entry.generated)
                self._C["resumes"].inc()
                eng.events.emit("resume", request=idx, slot=slot,
                                cached_pages=m)
            t_admit = tr.event(idx, "admit", slot=slot, free_pages=free,
                               cached_pages=m).t_start
            if entry.t_admit is None:
                # first admission only, as lifecycle() anchors
                # queue_wait_ms
                entry.t_admit = t_admit
                self._C["queue_wait_seconds"].inc(
                    max(0.0, t_admit - entry.t_enqueue))
            req_key = jax.random.fold_in(eng.rng, idx)
        samp0 = len(entry.prev)          # resume continues the key stream
        # chunked prefill (docs/frontend.md): instead of one monolithic
        # contiguous prefill, allocate the pages now and feed the
        # uncached tail through the paged s>1 path one
        # ``prefill_chunk``-token piece per pump iteration, interleaved
        # with decode chunks — a long prompt never blocks the running
        # slots' next decode step. Short tails (<= one chunk) keep the
        # monolithic path: one program call either way.
        if (eng.prefill_chunk is not None and s0 - m * ps > eng.prefill_chunk
                and s0 + eng.prefill_chunk - 1 <= max_pages * ps):
            tr.begin(idx, "prefill", cached_tokens=m * ps,
                     computed_tokens=s0 - m * ps, chunked=True)
            with self._phase("admission.launch"):
                if m == 0:
                    eng.cache = eng._chunk_alloc_jit(
                        eng.cache, jnp.int32(slot), jnp.int32(need))
                else:
                    self._C["prefix_hits"].inc()
                    row = np.zeros((max_pages,), np.int32)
                    row[:m] = [n.page for n in nodes]
                    eng.cache = eng._chunk_alloc_shared_jit(
                        eng.cache, jnp.int32(slot), jnp.asarray(row),
                        jnp.int32(m), jnp.int32(need))
            with self._phase("admission.join"):
                self._C["admitted"].inc()
                self._C["chunked_prefills"].inc()
                self._C["prefill_tokens_total"].inc(s0)
                self._C["prefill_tokens_computed"].inc(s0 - m * ps)
                eng.events.emit("admit", request=idx, slot=slot,
                                prompt_tokens=s0, cached_tokens=m * ps,
                                priority=entry.priority, chunked=True)
                entry.nodes = nodes
                entry.n_private = need
                entry.win_dropped = 0
                entry.seg_tokens = []
                entry.prefilling = True
                entry.pf_pos = m * ps
                entry.pf_key = req_key
                entry.pf_samp0 = samp0
                # no harvestable decode tokens until the prefill finishes
                entry.joined = self._chunk + (1 << 30)
                self._active[slot] = entry
                self._pool_dirty = True
            self._feed_chunk(slot, entry)    # first chunk rides now
            return True
        # prefill span: covers the admission program AND the first-token
        # sync — its end IS the first token's arrival
        with tr.span(idx, "prefill", cached_tokens=m * ps,
                     computed_tokens=s0 - m * ps):
            with self._phase("admission.launch"):
                if m == 0:
                    admit_fn, bucket = self.admission_program(s0)
                    ids = np.zeros((1, bucket), np.int32)
                    ids[0, :s0] = prompt
                    if eng.draft_len:
                        # speculative admission prefills the draft pool
                        # too
                        eng.cache, eng.draft_cache, tok0 = admit_fn(
                            eng.cache, eng.draft_cache, eng.variables,
                            eng.draft_variables, jnp.asarray(ids),
                            jnp.int32(s0), jnp.int32(slot),
                            jnp.int32(need), req_key, jnp.int32(samp0))
                    else:
                        eng.cache, tok0 = admit_fn(
                            eng.cache, eng.variables, jnp.asarray(ids),
                            jnp.int32(s0), jnp.int32(slot),
                            jnp.int32(need), req_key, jnp.int32(samp0))
                else:
                    self._C["prefix_hits"].inc()
                    t_start = m * ps
                    tail_bucket = min(round_up(s0 - t_start, ps),
                                      cfg.max_position_embeddings - t_start)
                    ids = np.zeros((1, tail_bucket), np.int32)
                    ids[0, :s0 - t_start] = prompt[t_start:]
                    row = np.zeros((max_pages,), np.int32)
                    row[:m] = [n.page for n in nodes]
                    eng.cache, tok0 = eng._admit_shared_fn(
                        t_start, tail_bucket)(
                        eng.cache, eng.variables, jnp.asarray(ids),
                        jnp.int32(s0), jnp.int32(slot), jnp.asarray(row),
                        jnp.int32(need), req_key, jnp.int32(samp0))
            tok0 = self._await_first_token(tok0)
        with self._phase("admission.join"):
            self._first_token(entry, slot)
            tr.begin(idx, "decode", slot=slot)
            self._C["admitted"].inc()
            self._C["prefill_tokens_total"].inc(s0)
            self._C["prefill_tokens_computed"].inc(s0 - m * ps)
            eng.events.emit("admit", request=idx, slot=slot,
                            prompt_tokens=s0, cached_tokens=m * ps,
                            priority=entry.priority)
            entry.nodes = nodes
            entry.n_private = need
            entry.win_dropped = 0        # fresh row: nothing dropped yet
            entry.seg_tokens = [tok0]
            entry.joined = self._chunk + 1
            self._active[slot] = entry
            entry.handle._push(tok0)
            self._pool_dirty = True
            if ((eng.eos_token_id is not None and tok0 == eng.eos_token_id)
                    or entry.seg_new == 1):
                self._retire(slot)
                return True
            self._tok = self._tok.at[slot].set(tok0)
            self._done = self._done.at[slot].set(False)
            self._n_left = self._n_left.at[slot].set(entry.seg_new - 1)
            self._samp_i = self._samp_i.at[slot].set(samp0 + 1)
            self._req_keys = self._req_keys.at[slot].set(req_key)
        return True

    def _advance_prefills(self) -> bool:
        """Feed ONE ``prefill_chunk``-token chunk to every mid-prefill
        slot (an async dispatch each — interleaved on the device stream
        with the in-flight decode chunk); finish the ones whose prompt
        is exhausted into decoding slots. True when any slot advanced."""
        if self.engine.prefill_chunk is None:
            return False
        advanced = False
        for slot in list(self._active):
            entry = self._active.get(slot)
            if entry is None or not entry.prefilling:
                continue
            if entry.handle.cancelled:
                # no decode state exists: the pages go back (full fed
                # pages still cacheable) and the handle finishes with
                # the earlier segments' tokens
                self._retire(slot, cancelled=True)
                continue
            self._feed_chunk(slot, entry)
            advanced = True
        return advanced

    def _feed_chunk(self, slot: int, entry: _Entry) -> None:
        eng = self.engine
        C = eng.prefill_chunk
        with self._phase("admission.feed"):
            t, s0 = entry.pf_pos, entry.s0
            valid = min(C, s0 - t)
            ids = np.zeros((1, C), np.int32)     # final chunk zero-pads
            ids[0, :valid] = entry.prompt[t:t + valid]
            eng.cache, tok = eng._prefill_chunk_fn()(
                eng.cache, eng.variables, jnp.asarray(ids),
                jnp.int32(slot), jnp.int32(valid), entry.pf_key,
                jnp.int32(entry.pf_samp0))
            entry.pf_pos = t + valid
            self._C["prefill_chunks"].inc()
            if entry.pf_pos < s0:
                return
            # the first-token sync below waits on the whole stream —
            # stamp the in-flight decode chunk's completion first so
            # decode_step_ms never charges prefill work to it
            if self._inflight is not None:
                self._materialize(self._inflight)
            tok0 = self._await_first_token(tok)
        self._finish_prefill(slot, entry, tok0)

    def _first_token(self, entry: _Entry, slot: int) -> None:
        """The ``first_token`` instant and what hangs on it, exactly once
        per request — a resume's re-admission never re-counts."""
        if entry.first_token_seen:
            return
        entry.first_token_seen = True
        tr, idx = self.tracer, entry.idx
        t_first = tr.event(idx, "first_token", slot=slot).t_start
        # admit -> first token: the device's queue plus the prefill
        self._C["first_token_wait_seconds"].inc(
            max(0.0, t_first - entry.t_admit))
        # the TTFT SLO check
        if (entry.deadline_at is not None
                and self.clock() > entry.deadline_at):
            entry.deadline_missed = True
            self._C["deadline_misses"].inc()
            tr.event(idx, "deadline_miss")
            self.engine.events.emit("deadline_miss", request=idx)

    def _finish_prefill(self, slot: int, entry: _Entry, tok0: int) -> None:
        """The prompt's final chunk landed: sample arrived (``tok0`` off
        the last valid logit), so run the same post-admission wiring the
        monolithic path does and hand the slot to the decode chunk."""
        eng = self.engine
        tr = self.tracer
        idx = entry.idx
        with self._phase("admission.join"):
            entry.prefilling = False
            tr.end(idx, "prefill")
            self._first_token(entry, slot)
            tr.begin(idx, "decode", slot=slot)
            entry.seg_tokens = [tok0]
            entry.joined = self._chunk + 1
            entry.handle._push(tok0)
            self._pool_dirty = True
            if ((eng.eos_token_id is not None and tok0 == eng.eos_token_id)
                    or entry.seg_new == 1):
                self._retire(slot)
                return
            self._tok = self._tok.at[slot].set(tok0)
            self._done = self._done.at[slot].set(False)
            self._n_left = self._n_left.at[slot].set(entry.seg_new - 1)
            self._samp_i = self._samp_i.at[slot].set(entry.pf_samp0 + 1)
            self._req_keys = self._req_keys.at[slot].set(entry.pf_key)

    def _admission(self) -> int:
        """Fill vacant slots from the policy-ordered pending queue;
        preempt for the head when the policy demands it. Head-of-line
        blocking is preserved inside the order: if the most urgent
        pending request cannot get pages, nothing behind it jumps the
        queue (the engine's original FIFO fairness, generalized to the
        policy order)."""
        eng = self.engine
        now = self.clock()
        held: List[_Entry] = []
        if self.backpressure_window is not None and self._pending:
            # backpressure-held entries sit out this admission pass
            # entirely (they are waiting on their CONSUMER, not on
            # slots/pages) — and must not head-of-line-block the queue
            held = [e for e in self._pending if self._bp_held(e)]
            if held:
                held_ids = {id(e) for e in held}
                self._pending = [e for e in self._pending
                                 if id(e) not in held_ids]
        self._pending.sort(key=lambda e: self.policy.sort_key(e, now))
        admitted = 0
        preempts_left = eng.num_slots    # bound the preempt-retry loop
        while self._pending:
            entry = self._pending[0]
            if entry.handle.cancelled:
                # cancelled in the queue, before its first admission or
                # while it waits for a slot again after a preemption:
                # finished and counted like any other
                self._pending.pop(0)
                if entry.resume:
                    self.tracer.end(entry.idx, "preempted")
                entry.handle._finish(
                    self._account_finish(entry, slot=None, cancelled=True))
                continue
            free_slots = [s for s in range(eng.num_slots)
                          if s not in self._active]
            if not free_slots:
                if preempts_left > 0 and self._maybe_preempt(entry, now):
                    preempts_left -= 1
                    continue
                break
            with self._phase("admission.request"):
                done = self._try_admit(entry, free_slots[0], now)
            if done:
                self._pending.pop(0)
                admitted += 1
                continue
            # page-short: preemption can spill a lower-priority slot's
            # pages (they become evictable cached pages) — retry once
            # per victim, then defer head-of-line
            if preempts_left > 0 and self._maybe_preempt(entry, now):
                preempts_left -= 1
                continue
            break
        self._pending.extend(held)
        return admitted

    # --- recompile storm check ----------------------------------------------

    def _check_compile_storm(self) -> None:
        """Warn (once per function name, into the engine's postmortem
        ring) when one program recompiled storm-many times within this
        frontend's lifetime — a recompile inside the pump is a serving
        latency cliff the IR tier's cardinality lint can only bound
        statically (docs/observability.md)."""
        storms = self._watch.storms(
            self._jit0, threshold=compile_watch.DEFAULT_STORM_THRESHOLD)
        for name, n in storms.items():
            if name not in self._storm_seen:
                self._storm_seen.add(name)
                self.engine.events.emit("compile_storm", fn=name,
                                        compiles=n)

    # --- run-scoped stats ---------------------------------------------------

    def counter_deltas(self) -> Dict[str, float]:
        """This frontend's ``serving.*`` counter deltas since
        construction — the raw numbers ``stats()`` derives its view
        from, WITHOUT recording anything (safe to poll; the router's
        aggregate stats read replicas through this)."""
        return {name: c.value - self._c0[name]
                for name, c in self._C.items()}

    def stats(self) -> dict:
        """The engine-stats dict for this frontend's lifetime so far —
        counter deltas since construction plus run-local latency
        percentiles (the same shape ``engine.run()`` has always
        returned, grown by the frontend counters). Records every numeric
        stat as a ``serving.<name>`` raw series — call once per run."""
        eng = self.engine
        d = self.counter_deltas()
        with self._ingest_lock:      # peak is written under this lock
            peak_queue_depth = self.peak_queue_depth
        stats = {
            "decode_steps": int(d["decode_steps"]),
            "admitted": int(d["admitted"]),
            "retired": int(d["retired"]),
            "peak_slots_in_use": self.peak_slots,
            "slot_occupancy": (d["busy_slot_steps"]
                               / max(d["decode_steps"] * eng.num_slots,
                                     1)),
            "deferred_admissions": int(d["deferred_admissions"]),
            "defrag_runs": int(d["defrag_runs"]),
            # chips the engine's programs span (serving/tp.py) — 1 for
            # the single-chip engine; per-chip throughput = aggregate /
            # tp_world (the pool/weight shards each chip streams)
            "tp_world": int(getattr(eng, "tp_world", 1)),
            # heads one row of the pool it serves from holds side by side
            # (kv_pool.heads_per_row; 2 at a 64-wide head: the pool then
            # lies as the kernels read it and no program re-lays it)
            "pool_heads_per_row": kv_pool.heads_per_row_of(eng.cache,
                                                           eng.cfg),
            "preemptions": int(d["preemptions"]),
            "resumes": int(d["resumes"]),
            "backpressure_spills": int(d["backpressure_spills"]),
            "deadline_misses": int(d["deadline_misses"]),
            "tpot_slo_misses": int(d["tpot_slo_misses"]),
            "window_dropped_pages": int(d["window_dropped_pages"]),
            "slo_burn": self._slo_burn.value,
            "peak_queue_depth": peak_queue_depth,
            "prefix_cache_enabled": eng.prefix is not None,
            "prefix_hits": int(d["prefix_hits"]),
            "prefix_hit_rate": d["prefix_hits"] / max(d["admitted"], 1),
            "prefix_cached_pages": (len(eng.prefix)
                                    if eng.prefix is not None else 0),
            "evicted_pages": int(d["evicted_pages"]),
            "prefill_tokens_total": int(d["prefill_tokens_total"]),
            "prefill_tokens_computed": int(d["prefill_tokens_computed"]),
            "prefill_tokens_skipped": int(d["prefill_tokens_total"]
                                          - d["prefill_tokens_computed"]),
            # speculative decode: emitted tokens per verify round (1..k;
            # > 1 means the draft is paying for itself)
            "spec_rounds": int(d["spec_rounds"]),
            "spec_tokens": int(d["spec_tokens"]),
            "mean_acceptance_len": (d["spec_tokens"]
                                    / max(d["spec_rounds"], 1)),
            "chunked_prefills": int(d["chunked_prefills"]),
            "prefill_chunks": int(d["prefill_chunks"]),
        }
        # tiered pool (docs/serving.md "Tiered KV pool"): lifetime
        # demote/promote totals + the promote-hit rate, pool.host_tier_*
        # instruments' stats()-shape view
        stats["host_tier_enabled"] = eng.host_tier is not None
        if eng.host_tier is not None:
            stats.update(eng.host_tier.stats())
        # pump pipeline attribution + the recompile window
        # (docs/frontend.md "Measuring the pump"): bubble is the mean
        # device-idle gap per handoff — ~0 when double-buffering hides
        # the host work
        bubbles = self._per_run["pump.bubble_ms"]
        stats["pump.bubble_ms"] = float(np.mean(bubbles)) if bubbles \
            else 0.0
        compiles, trace_misses = self._watch.totals()
        stats["jit.compiles"] = compiles - self._jit_totals0[0]
        stats["jit.trace_cache_misses"] = \
            trace_misses - self._jit_totals0[1]
        # storm-many recompiles of one program within this frontend's
        # lifetime (the preemption-storm scenario pins this at 0: the
        # resume compile-key set must stay bounded)
        stats["compile_storms"] = len(self._storm_seen)
        # run-local latency percentiles (the global histograms hold the
        # engine-lifetime distributions; these are exact per run)
        for name, vals in self._per_run.items():
            if vals:
                stats[f"{name}_p50"] = float(np.percentile(vals, 50))
                stats[f"{name}_p95"] = float(np.percentile(vals, 95))
        for name, val in stats.items():
            if isinstance(val, bool):
                continue
            metrics.record(f"serving.{name}", val)
        # the groups of layers the pool holds (kv_pool.layer_groups):
        # which layers, how far back they read, and the pages the group's
        # pool holds for its slots (a ring group: R a slot, for ever; the
        # block table's group: what the free stack hands out; a state
        # group: no page, its tensors' bytes a slot)
        paged = {kv.group: kv for kv in self._kv_groups}
        stats["kv_groups"] = [
            {"layers": list(g.layers), "window": None,
             "ring_pages_per_slot": None, "pages_held": 0,
             "state": [t.name for t in g.state],
             "state_bytes_per_slot": kv_pool.state_bytes(eng.cfg, group=g)}
            if g.state else
            {"layers": list(g.layers), "window": g.window,
             "ring_pages_per_slot": paged[g].ring,
             "pages_held": (paged[g].ring * eng.num_slots if paged[g].ring
                            else kv_pool.num_pages_of(eng.cache) - 1)}
            for g in eng.groups]
        # the routed experts' widths as published and as served, a layer
        # (dropless.lay_out_experts), with the bytes of zeros the layout
        # added: static, so a reading and not a rate
        stats["expert_served_width"] = eng.expert_layout
        # and the rows their products are handed a layer, in the decode
        # chunk and each admit program (dropless.served_rows): static too
        stats["expert_served_rows"] = eng.expert_rows()
        # the longest iterations of this frontend's life, longest first:
        # what an untraced run can say of a stall
        stats["pump.slowest"] = self._slowest_records()
        return stats
