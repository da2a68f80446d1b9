"""HTTP/SSE serving surface (apex_tpu/serving/http.py + aio.py) —
ISSUE 15.

The acceptance bars, each proven over a REAL localhost socket (never a
mocked transport):

- ``POST /v1/generate`` streams greedy tokens identical to lock-step
  ``generate``; the observability endpoints (healthz / metrics /
  metrics.json / costs) ride the same port.
- a reader that stalls past the frontend's ``backpressure_window``
  SPILLS its slot through the preemption path (pages parked in the
  radix cache, never pinned by a socket) and the stream still completes
  token-identically on resume — the tier-1 backpressure/leak bar.
- a client disconnect cancels at the next sync boundary and frees every
  page; bad bodies get 400; overload gets 429 + Retry-After; drain gets
  503 and a clean shutdown leaves zero serving threads.
- a :class:`ReplicaRouter` supervising two REMOTE
  :class:`HttpReplicaClient` replicas recovers a killed replica's
  in-flight requests on the survivor token-identically — the networked
  twin of test_router's kill bar.
- slow tier: ≥1k truly concurrent streams through one server, zero
  hung handles / leaked pages / dangling threads after shutdown.
"""

import contextlib
import json
import socket
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.obs.fleet import (row_from_snapshot, stitch_traces,
                                validate_flight)
from apex_tpu.serving import (PagedDecodeEngine, ReplicaRouter, Request,
                              RouterPolicy, ServingFrontend,
                              free_page_count)
from apex_tpu.serving.faults import FaultInjector, FaultSpec
from apex_tpu.serving.http import (HttpReplicaClient, HttpServingServer,
                                   _iter_sse)
from apex_tpu.utils import metrics


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, v


def _ref(model, v, prompt, max_new):
    return np.asarray(generate(model, v, np.asarray(prompt)[None],
                               max_new_tokens=max_new)
                      )[0, np.asarray(prompt).shape[0]:]


@contextlib.contextmanager
def _serving(tiny, *, num_slots=2, num_pages=64, prefix_cache=True,
             fault_hook=None, backpressure_window=None, **server_kw):
    """A live engine + started frontend + started HTTP server, torn
    down server-first (the ownership order docs/http.md specifies)."""
    cfg, model, v = tiny
    engine = PagedDecodeEngine(model, v, num_slots=num_slots,
                               page_size=8, num_pages=num_pages,
                               prefix_cache=prefix_cache)
    fe = ServingFrontend(engine, fault_hook=fault_hook,
                         backpressure_window=backpressure_window)
    fe.start()
    srv = HttpServingServer(fe, **server_kw).start()
    try:
        yield engine, fe, srv
    finally:
        srv.shutdown(deadline_s=10.0)
        fe.shutdown(deadline_s=10.0)


def _open_stream(port, body, *, rcvbuf=None, timeout=60.0):
    """Raw POST /v1/generate; returns (sock, reader, status, headers)
    with the reader positioned at the body."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf is not None:
        # must precede connect: the TCP window scale is fixed at the
        # handshake (the backpressure test relies on a tiny window)
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    sock.settimeout(timeout)
    sock.connect(("127.0.0.1", port))
    raw = json.dumps(body).encode()
    sock.sendall((f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                  f"Content-Length: {len(raw)}\r\n\r\n").encode() + raw)
    f = sock.makefile("rb")
    status = int(f.readline().split()[1])
    headers = {}
    while True:
        line = f.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, val = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = val.strip()
    return sock, f, status, headers


def _stream(port, body):
    sock, f, status, _ = _open_stream(port, body)
    try:
        assert status == 200
        toks, finish = [], None
        for event, data in _iter_sse(f):
            if event == "token":
                toks.append(int(data["token"]))
            elif event == "done":
                finish = data.get("finish_reason")
                break
            elif event == "error":
                raise AssertionError(data)
        return toks, finish
    finally:
        sock.close()


def _get(port, path):
    sock = socket.create_connection(("127.0.0.1", port), timeout=10.0)
    try:
        sock.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        f = sock.makefile("rb")
        status = int(f.readline().split()[1])
        while f.readline() not in (b"\r\n", b"\n", b""):
            pass
        return status, f.read()
    finally:
        sock.close()


def _pool_settled(engine, deadline_s=10.0):
    """Poll for free + radix-cached == total pool pages (cancel retires
    at the pump's next sync boundary, so accounting may lag a moment)."""
    usable = engine.cache["free_stack"].shape[0] - 1
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        cached = len(engine.prefix) if engine.prefix is not None else 0
        if int(free_page_count(engine.cache)) + cached == usable:
            return True
        time.sleep(0.02)
    return False


# --------------------------------------------------------------------------
# the streaming contract + observability endpoints
# --------------------------------------------------------------------------

def test_stream_token_identical_and_endpoints(tiny, rng):
    cfg, model, v = tiny
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    with _serving(tiny) as (engine, fe, srv):
        toks, finish = _stream(srv.port, {"prompt": prompt.tolist(),
                                          "max_new_tokens": 6})
        np.testing.assert_array_equal(toks, _ref(model, v, prompt, 6))
        assert finish == "stop"
        # the unified port: health + metrics next to generate
        status, body = _get(srv.port, "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["ok"]
        assert doc["http"]["streams"] == 1
        assert doc["http"]["streams_active"] == 0
        status, body = _get(srv.port, "/metrics")
        assert status == 200 and b"http_tokens" in body
        status, body = _get(srv.port, "/metrics.json")
        assert status == 200 and "counters" in json.loads(body)
        # ``/costs`` went with the cost model: a 404 like any unknown
        # path, with the route named in the JSON body
        status, body = _get(srv.port, "/costs")
        assert status == 404
        assert json.loads(body)["error"] == "no route GET /costs"
        assert srv.http_counter_deltas()["tokens"] == 6


def test_bad_request_400_and_unknown_404(tiny):
    with _serving(tiny) as (_, __, srv):
        for body in ({"prompt": []},                  # empty prompt
                     {"prompt": [1, 2], "max_new_tokens": 0},
                     {"prompt": [1, 2], "request_id": "not-an-int"}):
            sock, f, status, _ = _open_stream(srv.port, body)
            assert status == 400, body
            sock.close()
        status, _ = _get(srv.port, "/nope")
        assert status == 404
        assert srv.http_counter_deltas()["errors"] == 0


def test_overload_429_retry_after(tiny):
    with _serving(tiny, max_queue_depth=0) as (_, __, srv):
        sock, f, status, headers = _open_stream(
            srv.port, {"prompt": [1, 2], "max_new_tokens": 2})
        assert status == 429
        assert float(headers["retry-after"]) > 0.0
        sock.close()
        assert srv.http_counter_deltas()["rejected"] == 1


# --------------------------------------------------------------------------
# the robustness contract: backpressure spill, disconnect, drain
# --------------------------------------------------------------------------

def test_backpressure_spill_resume_token_identical(tiny, rng):
    """THE tier-1 backpressure/leak bar: a reader stalled past the
    window spills its slot via the preemption path (pages parked in the
    radix cache — a socket pins nothing), then resumes to a
    token-identical completion once the client reads again."""
    cfg, model, v = tiny
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    with _serving(tiny, backpressure_window=8, sse_pad_bytes=2048,
                  sndbuf=4096) as (engine, fe, srv):
        sock, f, _, _ = _open_stream(
            srv.port, {"prompt": prompt.tolist(), "max_new_tokens": 64},
            rcvbuf=2048)
        toks = []
        try:
            for event, data in _iter_sse(f):
                if event == "token":
                    toks.append(int(data["token"]))
                    if len(toks) == 2:
                        time.sleep(1.5)   # stall: socket open, unread
                elif event == "done":
                    break
        finally:
            sock.close()
        np.testing.assert_array_equal(toks, _ref(model, v, prompt, 64))
        stats = fe.stats()
        assert stats["backpressure_spills"] >= 1
        assert stats["resumes"] >= 1
        assert _pool_settled(engine), "pages pinned after spill/resume"


def test_disconnect_cancels_and_frees_pages(tiny, rng):
    cfg, model, v = tiny
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    # slow the pump so the drop always lands mid-generation
    inj = FaultInjector((FaultSpec(kind="pump_stall", at=0,
                                   count=10_000, delay_ms=5.0),))
    with _serving(tiny, fault_hook=inj) as (engine, fe, srv):
        sock, f, _, _ = _open_stream(
            srv.port, {"prompt": prompt.tolist(),
                       "max_new_tokens": 100})
        n = 0
        for event, data in _iter_sse(f):
            if event == "token":
                n += 1
                if n == 2:
                    break
        # a REAL drop: close() alone defers the FIN while the makefile
        # reader holds the fd and the server would never notice
        sock.shutdown(socket.SHUT_RDWR)
        sock.close()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            doc = json.loads(_get(srv.port, "/healthz")[1])
            if (doc["http"]["streams_active"] == 0
                    and doc["http"]["disconnects"] >= 1):
                break
            time.sleep(0.05)
        else:
            raise AssertionError(f"disconnect unseen: {doc['http']}")
        assert _pool_settled(engine), "disconnect leaked pages"


def test_conn_reset_mid_request_survives(tiny):
    """A torn submit (half the bytes, then an RST) must not take the
    server down or leak a stream."""
    with _serving(tiny) as (_, __, srv):
        raw = json.dumps({"prompt": [1, 2, 3],
                          "max_new_tokens": 4}).encode()
        wire = (f"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                f"Content-Length: {len(raw)}\r\n\r\n").encode() + raw
        sock = socket.create_connection(("127.0.0.1", srv.port),
                                        timeout=10.0)
        sock.sendall(wire[:len(wire) // 2])
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                        struct.pack("ii", 1, 0))   # close -> RST
        sock.close()
        # the retry on a fresh connection completes normally
        toks, finish = _stream(srv.port, {"prompt": [1, 2, 3],
                                          "max_new_tokens": 4})
        assert len(toks) == 4 and finish == "stop"


def test_drain_503_then_clean_shutdown(tiny, rng):
    cfg, model, v = tiny
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    with _serving(tiny) as (_, fe, srv):
        toks, _ = _stream(srv.port, {"prompt": prompt.tolist(),
                                     "max_new_tokens": 4})
        assert len(toks) == 4
        srv.drain(deadline_s=10.0)
        sock, f, status, headers = _open_stream(
            srv.port, {"prompt": [1, 2], "max_new_tokens": 2})
        assert status == 503 and "retry-after" in headers
        sock.close()
        # observability keeps serving through the drain
        assert _get(srv.port, "/healthz")[0] == 200
    names = [t.name for t in threading.enumerate()]
    assert not any(n.startswith(("serving-http-loop",
                                 "serving-frontend-pump",
                                 "http-replica-stream"))
                   for n in names), names


# --------------------------------------------------------------------------
# fleet plane over the wire: /events cursor + scrape fidelity
# --------------------------------------------------------------------------

def test_events_endpoint_since_seq_cursor(tiny):
    """GET /events?since_seq= serves the engine ring incrementally: a
    cursor past the last seq yields nothing new, a stale cursor reports
    the gap as ``dropped``, and a malformed cursor is a 400 — the wire
    half of the federation cursor contract (docs/observability.md)."""
    with _serving(tiny) as (engine, _, srv):
        for i in range(3):
            engine.events.emit("probe", i=i)
        status, body = _get(srv.port, "/events?since_seq=-1")
        assert status == 200
        doc = json.loads(body)
        assert doc["kind"] == "event_log"
        assert doc["since_seq"] == -1 and doc["dropped"] == 0
        seqs = [e["seq"] for e in doc["events"]]
        assert seqs == sorted(seqs) and len(seqs) >= 3
        # incremental scrape from the last seen seq: empty, no gap
        status, body = _get(srv.port, f"/events?since_seq={seqs[-1]}")
        assert status == 200
        tail = json.loads(body)
        assert tail["events"] == [] and tail["dropped"] == 0
        # new events land past the cursor
        engine.events.emit("probe", i=99)
        status, body = _get(srv.port, f"/events?since_seq={seqs[-1]}")
        more = json.loads(body)["events"]
        assert [e["kind"] for e in more] == ["probe"]
        assert more[0]["seq"] == seqs[-1] + 1
        # a malformed cursor is the client's fault, not a crash
        assert _get(srv.port, "/events?since_seq=abc")[0] == 400


def test_remote_scrape_fidelity(tiny, rng):
    """The federated fleet row recomputed from a REMOTE replica's
    ``/metrics.json`` scrape equals the row the replica computes from
    its own in-process registry — p95s from wire-serialized buckets,
    gauges, and queue depth all match (the scrape-fidelity bar)."""
    cfg, model, v = tiny
    metrics.clear()              # only this replica's series in play
    try:
        with _serving(tiny) as (engine, fe, srv):
            for _ in range(3):
                prompt = rng.integers(0, cfg.vocab_size,
                                      (8,)).astype(np.int32)
                toks, finish = _stream(
                    srv.port, {"prompt": prompt.tolist(),
                               "max_new_tokens": 4})
                assert len(toks) == 4 and finish == "stop"
            client = HttpReplicaClient("127.0.0.1", srv.port)
            doc = client.fleet_scrape(-1)
            remote = row_from_snapshot(doc["metrics"])
            local = row_from_snapshot(metrics.snapshot(),
                                      labels=engine.obs_labels)
            local["queue_depth"] = fe.queue_depth
            assert set(remote) == set(local)
            for key, want in local.items():
                assert remote[key] == pytest.approx(want), key
            assert remote["ttft_ms_p95"] > 0.0
            # the event half of the scrape carries the engine ring
            edoc = doc["events"]
            assert edoc["kind"] == "event_log"
            assert edoc["total"] == engine.events.total
    finally:
        metrics.clear()


# --------------------------------------------------------------------------
# router over remote HTTP replicas — the networked kill bar
# --------------------------------------------------------------------------

def test_router_over_http_replicas_kill_recovers_token_identical(
        tiny, rng):
    """Two remote HTTP replicas behind one ReplicaRouter; replica 0's
    server dies mid-stream. Its in-flight requests must re-home to the
    survivor with delivered tokens folded in — outputs token-identical
    to an unfailed run, nothing hung, both pools clean.

    The fleet-plane half of the bar rides the same run: stitching the
    two replicas' span dumps yields ONE trace per request (same
    trace_id on both replicas for every failed-over request, zero
    orphans), stitched TTFT anchors at the FIRST replica's first token,
    ``preempted_ms`` covers the failover gap, and the death dumped a
    schema-valid flight bundle naming both replicas' event rings."""
    cfg, model, v = tiny
    backends = []
    for i in range(2):
        engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                                   num_pages=64, prefix_cache=True)
        # replica 0 decodes slowly so the kill lands mid-generation
        inj = (FaultInjector((FaultSpec(kind="pump_stall", at=0,
                                        count=10_000, delay_ms=20.0),))
               if i == 0 else None)
        fe = ServingFrontend(engine, fault_hook=inj)
        fe.start()
        srv = HttpServingServer(fe).start()
        backends.append((engine, fe, srv))
    clients = [HttpReplicaClient("127.0.0.1", srv.port)
               for _, __, srv in backends]
    router = ReplicaRouter(clients,
                           policy=RouterPolicy(backoff_base_ms=1.0))
    router.start()
    try:
        reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (12,)
                                            ).astype(np.int32),
                        max_new_tokens=8) for _ in range(4)]
        handles = [router.submit(r, request_id=i)
                   for i, r in enumerate(reqs)]
        # wait until replica 0 has delivered a first token, so the kill
        # lands mid-generation AND the stitched trace below has a
        # pre-kill TTFT anchor (the stall spec keeps its remaining
        # decode slow enough that the stream cannot finish first)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if any(s["name"] == "first_token"
                   for s in clients[0].tracer.to_dicts()):
                break
            time.sleep(0.01)
        else:
            pytest.fail("replica 0 never delivered a first token")
        backends[0][2].close()              # kill replica 0's server
        for h, r in zip(handles, reqs):
            np.testing.assert_array_equal(
                h.result(timeout=300.0),
                _ref(model, v, r.prompt, r.max_new_tokens))
    finally:
        router.stop()
        for _, fe, srv in backends:
            srv.close()
            fe.shutdown(deadline_s=10.0)
    stats = router.stats()
    assert stats["replica_deaths"] == 1
    assert stats["failovers"] >= 1
    assert stats["failover_recovered_rate"] == 1.0
    assert stats["completed"] == 4 and stats["failed"] == 0
    assert _pool_settled(backends[1][0]), "survivor pool not clean"
    assert stats["fleet"]["replicas"] == 2

    # -- one stitched trace per request across the failover ---------------
    dumps = {f"replica{i}": client.tracer.to_dicts()
             for i, client in enumerate(clients)}
    stitched = stitch_traces(dumps)
    assert stitched["orphans"] == [], stitched["orphans"][:3]
    assert len(stitched["traces"]) == 4
    crossed = [t for t in stitched["traces"].values()
               if len(t["replicas"]) == 2]
    assert crossed, "no request failed over across replicas"
    for trace in crossed:
        # the request started on replica 0 and finished on the survivor
        assert trace["replicas"] == ["replica0", "replica1"]
        assert len(trace["failovers"]) == 1
        fo = trace["failovers"][0]
        assert (fo["from_replica"], fo["to_replica"]) == ("replica0",
                                                          "replica1")
        # the time in limbo between the kill and the re-home is
        # preempted time, and nothing else was preempted here
        assert trace["preempted_ms"] == pytest.approx(fo["gap_ms"])
        # the same trace_id binds spans on BOTH replicas' dumps
        tid = trace["trace_id"]
        rid = trace["request_ids"][0]
        for name in ("replica0", "replica1"):
            bound = [s for s in dumps[name]
                     if s["request_id"] == rid
                     and (s.get("attrs") or {}).get("trace_id") == tid]
            assert bound, f"{name} has no span bound to {tid}"
    # TTFT anchors at the FIRST replica's first token (pre-failover),
    # not at the resumed stream's first token on the survivor
    anchored = 0
    for trace in crossed:
        rid = trace["request_ids"][0]
        r0 = {s["name"]: s for s in dumps["replica0"]
              if s["request_id"] == rid}
        if "first_token" not in r0:
            continue                     # killed before its first token
        anchored += 1
        want = (r0["first_token"]["t_start"]
                - r0["enqueue"]["t_start"]) * 1e3
        assert trace["ttft_ms"] == pytest.approx(want)
        assert trace["ttft_ms"] < (trace["failovers"][0]["resume_t"]
                                   - r0["enqueue"]["t_start"]) * 1e3
    assert anchored, "no failed-over request had a pre-kill first token"

    # -- the death dumped a flight bundle naming both replicas ------------
    flight = router.last_flight
    assert flight is not None, "replica death recorded no flight"
    validate_flight(flight)
    assert flight["reason"] == "replica_dead:0"
    assert set(flight["replicas"]) == {"replica0", "replica1"}
    for entry in flight["replicas"].values():
        assert isinstance(entry["events"], list)
    assert flight["replicas"]["replica0"]["alive"] is False
    assert flight["replicas"]["replica1"]["alive"] is True
    assert any(t["trace_id"] for t in flight["traces"].values())


# --------------------------------------------------------------------------
# slow tier: the 1k-concurrent-stream load bar
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_thousand_concurrent_streams_no_leaks(tiny, rng):
    """≥1k truly concurrent streams (every socket open at once) through
    one server: all complete, zero hung client threads, zero leaked
    pages, zero serving threads after shutdown."""
    cfg, model, v = tiny
    n = 1024
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
    ref = _ref(model, v, prompt, 2)
    with _serving(tiny, num_slots=8, num_pages=128) as (engine, fe, srv):
        results: dict = {}
        errors: list = []

        def client(i):
            try:
                toks, finish = _stream(
                    srv.port, {"prompt": prompt.tolist(),
                               "max_new_tokens": 2, "request_id": i})
                results[i] = (toks, finish)
            except BaseException as exc:   # noqa: BLE001 — re-raised
                errors.append((i, exc))

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600.0)
        assert not any(t.is_alive() for t in threads), "hung clients"
        assert not errors, errors[:3]
        assert len(results) == n
        for toks, finish in results.values():
            np.testing.assert_array_equal(toks, ref)
            assert finish == "stop"
        assert srv.http_counter_deltas()["streams"] == n
        assert _pool_settled(engine, deadline_s=30.0)
    names = [t.name for t in threading.enumerate()]
    assert not any(n_.startswith(("serving-http-loop",
                                  "serving-frontend-pump"))
                   for n_ in names), names
