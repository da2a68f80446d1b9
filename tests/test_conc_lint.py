"""tpu-lint concurrency tier (apex_tpu.analysis.conc) coverage.

Mirrors the PR 3/5 load-bearing pattern for the third tier, per ISSUE 7:

1. per-rule fixture pairs — a bad module that triggers EXACTLY its rule
   (and passes with the rule deselected), and a good twin that is clean;
2. machinery — thread coloring, GuardedBy inference, inline suppression,
   the tier-partitioned baseline, CLI usage errors, ``--diff`` coverage;
3. seeded mutations against the LIVE frontend: removing one
   ``with self._lock:`` fires ``conc-unguarded-shared-field``, and an
   inverted acquisition order fires ``conc-lock-order-cycle``;
4. end-to-end — ``--conc`` over the repo itself exits 0 at HEAD: the
   tier-1 twin of the ``--conc`` CI gate.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.analysis import cli                              # noqa: E402
from apex_tpu.analysis.conc import (CONC_RULES,                # noqa: E402
                                    analyze_conc_sources, build_model)
from apex_tpu.analysis.tiers import tier_of, tier_of_key       # noqa: E402

# --------------------------------------------------------------------------
# per-rule fixture pairs
# --------------------------------------------------------------------------

FIXTURES = {
    "conc-unguarded-shared-field": (
        """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def worker(self):
                self._items.append(1)

            def read(self):
                with self._lock:
                    return list(self._items)

            def also(self):
                with self._lock:
                    self._items.append(2)

            def spawn(self):
                threading.Thread(target=self.worker, name="w",
                                 daemon=True).start()
        """,
        """\
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def worker(self):
                with self._lock:
                    self._items.append(1)

            def read(self):
                with self._lock:
                    return list(self._items)

            def also(self):
                with self._lock:
                    self._items.append(2)

            def spawn(self):
                threading.Thread(target=self.worker, name="w",
                                 daemon=True).start()
        """,
    ),
    "conc-lock-order-cycle": (
        """\
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        return 1

            def rev(self):
                with self._b:
                    with self._a:
                        return 2
        """,
        """\
        import threading

        class AB:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a:
                    with self._b:
                        return 1

            def rev(self):
                with self._a:
                    with self._b:
                        return 2
        """,
    ),
    "conc-blocking-under-lock": (
        """\
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def poll(self):
                with self._lock:
                    return self._q.get()
        """,
        """\
        import queue
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def poll(self):
                item = self._q.get()
                with self._lock:
                    return item
        """,
    ),
    "conc-resource-leak": (
        """\
        from apex_tpu.serving import kv_pool

        def grab(cache, slot, n, ok):
            cache = kv_pool.alloc_slot(cache, slot, n)
            if not ok:
                raise RuntimeError("boom")
            return kv_pool.free_slot(cache, slot)
        """,
        """\
        from apex_tpu.serving import kv_pool

        def grab(cache, slot, n, ok):
            cache = kv_pool.alloc_slot(cache, slot, n)
            try:
                if not ok:
                    raise RuntimeError("boom")
            finally:
                cache = kv_pool.free_slot(cache, slot)
            return cache
        """,
    ),
    "conc-unreleased-lock": (
        """\
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def step(self, fail):
                self._lock.acquire()
                if fail:
                    return None
                self._lock.release()
                return 1
        """,
        """\
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def step(self, fail):
                with self._lock:
                    if fail:
                        return None
                    return 1
        """,
    ),
    "conc-double-acquire": (
        """\
        import threading

        class D:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    return self.inner()

            def inner(self):
                with self._lock:
                    return 1
        """,
        """\
        import threading

        class D:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    return self.inner()

            def inner(self):
                with self._lock:
                    return 1
        """,
    ),
    "conc-thread-leak": (
        """\
        import threading

        def fire(fn):
            t = threading.Thread(target=fn)
            t.start()
        """,
        """\
        import threading

        def fire(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            u = threading.Thread(target=fn)
            u.start()
            u.join()
        """,
    ),
    "conc-useless-local-lock": (
        """\
        import threading

        def guard(x):
            lock = threading.Lock()
            with lock:
                return x + 1
        """,
        """\
        import threading

        _LOCK = threading.Lock()

        def guard(x):
            with _LOCK:
                return x + 1
        """,
    ),
    "conc-await-under-lock": (
        """\
        import asyncio
        import threading

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()

            async def step(self):
                with self._lock:
                    await asyncio.sleep(0)
        """,
        # the good twin is ALSO the asyncio-primitive discrimination
        # test: `async with asyncio.Lock()` suspends instead of
        # blocking and must never register as a threading lock (if it
        # did, the await under it would fire)
        """\
        import asyncio
        import threading

        class Srv:
            def __init__(self):
                self._lock = threading.Lock()
                self._alock = asyncio.Lock()

            async def step(self):
                with self._lock:
                    n = 1
                async with self._alock:
                    await asyncio.sleep(0)
                return n
        """,
    ),
}


def _run(src, select=None):
    findings, suppressed = analyze_conc_sources(
        {"apex_tpu/mod.py": textwrap.dedent(src)}, select=select)
    return findings, suppressed


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_bad_module_triggers_exactly_its_rule(rule):
    findings, _ = _run(FIXTURES[rule][0])
    fired = [f.rule for f in findings]
    assert fired, f"bad module for {rule} produced no findings"
    assert set(fired) == {rule}, fired


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_good_module_is_clean(rule):
    findings, _ = _run(FIXTURES[rule][1])
    assert not findings, [(f.rule, f.message) for f in findings]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_conc_rules_individually_load_bearing(rule):
    """With the rule deselected (≈ deleted), its bad module passes: no
    other conc rule shadows it."""
    others = [r for r in CONC_RULES if r != rule]
    findings, _ = _run(FIXTURES[rule][0], select=others)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_every_conc_rule_has_a_fixture():
    assert set(CONC_RULES) == set(FIXTURES)


# --------------------------------------------------------------------------
# machinery: coloring, inference, suppression, tiers, CLI
# --------------------------------------------------------------------------

def _surface_sources():
    root = Path(REPO)
    return {cli._rel(root, p): p.read_text()
            for p in cli.discover(root, ())}


def test_pump_thread_coloring_on_live_frontend():
    """The background pump thread is discovered by its literal name and
    colors the whole pump-side call chain, including the handle-push
    path another thread consumes."""
    model, _ = build_model(_surface_sources())
    colored = {k.qualname for k, v in model.colors.items()
               if "serving-frontend-pump" in v}
    for fn in ("ServingFrontend.pump", "ServingFrontend._harvest",
               "ServingFrontend._try_admit", "StreamHandle._push"):
        assert fn in colored, sorted(colored)
    # ISSUE 17: the host-tier copy drain rides the pump's host-work
    # slot — no new thread, so the demote/promote chain must inherit
    # the pump color (the field rule sees tier-adjacent engine state
    # as pump-confined, same as the admission path)
    for fn in ("ServingFrontend._demote", "ServingFrontend._try_promote"):
        assert fn in colored, sorted(colored)
    # the /metrics endpoint's handler colors the exporter/registry reads
    http = {k.qualname for k, v in model.colors.items()
            if "http-handler" in v}
    assert "prometheus_text" in http and "snapshot" in http


def test_guardedby_inference_on_live_frontend():
    """The inference recovers the intended lock discipline of the
    serving stack (the docs/frontend.md thread-safety contract)."""
    model, _ = build_model(_surface_sources())
    guards = {(f[1], f[2]): lock.display()
              for f, (lock, _, _) in model.inferred_guards().items()}
    assert guards[("StreamHandle", "_tokens")] == "StreamHandle._lock"
    assert guards[("ServingFrontend", "_ingest")] == \
        "ServingFrontend._ingest_lock"
    assert guards[("ServingFrontend", "_failure")] == \
        "ServingFrontend._ingest_lock"
    assert guards[("SpanTracer", "_spans")] == "SpanTracer._lock"
    assert guards[("EventLog", "_buf")] == "EventLog._lock"
    assert guards[("Counter", "_value")] == "_LOCK"
    # ISSUE 8: the pump timing / SLO-window fields are pump-confined by
    # design — never locked anywhere, so the inference must NOT claim a
    # guard for them (a half-locked access pattern would fire the rule)
    for field in ("_last_ready", "_wait_s", "_slo_window",
                  "_storm_seen"):
        assert ("ServingFrontend", field) not in guards
    # the compile watcher's tables ARE locked everywhere
    assert guards[("CompileWatcher", "_compiles")] == \
        "CompileWatcher._lock"


def test_router_supervisor_thread_coloring():
    """ISSUE 11: the replica router's supervisor thread is discovered
    by its literal name and colors the whole supervision chain —
    failure detection, token forwarding, failover resubmission — so the
    field rule sees every router-state access as multi-thread."""
    model, _ = build_model(_surface_sources())
    colored = {k.qualname for k, v in model.colors.items()
               if "serving-router-supervisor" in v}
    for fn in ("ReplicaRouter._tick", "ReplicaRouter._service_locked",
               "ReplicaRouter._failover_locked", "ReplicaRouter._place",
               "ReplicaRouter._route_due",
               "ReplicaRouter._mark_dead_locked"):
        assert fn in colored, sorted(colored)


def test_router_guardedby_map_pinned():
    """ISSUE 11: the router's lock discipline is a CHECKED contract —
    the inference must recover exactly the intended GuardedBy map for
    the router's shared state and the fault injector's trigger
    counters (and the frontend's new shutdown flag)."""
    model, _ = build_model(_surface_sources())
    guards = {(f[1], f[2]): lock.display()
              for f, (lock, _, _) in model.inferred_guards().items()}
    for field in ("_entries", "_queued", "_records", "_accepting",
                  "_rr_next", "_sup_thread"):
        assert guards[("ReplicaRouter", field)] == \
            "ReplicaRouter._lock", (field, guards.get(
                ("ReplicaRouter", field)))
    for field in ("_pumps", "_submits", "_rejected", "fired"):
        assert guards[("FaultInjector", field)] == \
            "FaultInjector._lock"
    assert guards[("ServingFrontend", "_accepting")] == \
        "ServingFrontend._ingest_lock"


def test_host_tier_guardedby_map_pinned():
    """ISSUE 17: the host spill tier is one single-lock object shared
    between the pump (demote / drain / promote) and arbitrary caller
    threads reading ``stats()`` — the inference must recover
    ``HostPageTier._lock`` for every piece of tier state."""
    model, _ = build_model(_surface_sources())
    guards = {(f[1], f[2]): lock.display()
              for f, (lock, _, _) in model.inferred_guards().items()}
    for field in ("_entries", "_pending", "_resident_bytes"):
        assert guards[("HostPageTier", field)] == \
            "HostPageTier._lock", (field, guards.get(
                ("HostPageTier", field)))


def test_fleet_guardedby_map_pinned():
    """ISSUE 19: the fleet plane's lock discipline is a CHECKED
    contract. The collector merges scrape results under its OWN lock
    (never the router's — the scrape I/O itself runs lock-free), the
    alerter's sample window is single-lock, and the router's flight /
    tick bookkeeping joined the router-lock family."""
    model, _ = build_model(_surface_sources())
    guards = {(f[1], f[2]): lock.display()
              for f, (lock, _, _) in model.inferred_guards().items()}
    for field in ("_order", "_rows", "_tails", "_cursors",
                  "_scraped_at", "_storms", "_dropped", "_alive"):
        assert guards[("FleetCollector", field)] == \
            "FleetCollector._lock", (field, guards.get(
                ("FleetCollector", field)))
    for field in ("_samples", "_firing", "_fired"):
        assert guards[("BurnRateAlerter", field)] == \
            "BurnRateAlerter._lock", (field, guards.get(
                ("BurnRateAlerter", field)))
    for field in ("_flight_reason", "_last_tick_t", "last_flight"):
        assert guards[("ReplicaRouter", field)] == \
            "ReplicaRouter._lock", (field, guards.get(
                ("ReplicaRouter", field)))
    # the collector's read side is reachable from the supervisor color
    # (the flight path), so the field rule treats its state as shared
    colored = {k.qualname for k, v in model.colors.items()
               if "serving-router-supervisor" in v}
    for fn in ("FleetCollector.block", "FleetCollector.events_tail",
               "FleetCollector.scrape_ages"):
        assert fn in colored, sorted(c for c in colored if "Fleet" in c)


def test_fleet_collector_tick_coloring_fixture():
    """ISSUE 19: ``router._tick_impl`` invokes ``self.fleet.tick()``
    across a module boundary the call-graph cannot resolve, so the
    supervisor-coloring of the collector's tick is pinned on an inline
    fixture instead: a literal-named supervisor thread drives a mini
    collector whose tick scrapes LOCK-FREE and merges under its own
    lock. The good twin is clean; dropping the merge lock fires
    ``conc-unguarded-shared-field``."""
    good = """\
        import threading

        def scrape(fe):
            return fe.row()              # pure I/O — no lock held

        class MiniCollector:
            def __init__(self, targets):
                self._lock = threading.Lock()
                self._targets = targets
                self._rows = {}

            def tick(self):
                got = {n: scrape(fe) for n, fe in self._targets}
                with self._lock:
                    for name, row in got.items():
                        self._rows[name] = row

            def block(self):
                with self._lock:
                    return dict(self._rows)

        class Sup:
            def __init__(self, collector):
                self.fleet = collector

            def _loop(self):
                self.fleet.tick()

            def start(self):
                threading.Thread(target=self._loop,
                                 name="mini-fleet-supervisor",
                                 daemon=True).start()
    """
    findings, _ = _run(good)
    assert not findings, [(f.rule, f.message) for f in findings]
    src = {"apex_tpu/mod.py": textwrap.dedent(good)}
    model, _ = build_model(src)
    colored = {k.qualname for k, v in model.colors.items()
               if "mini-fleet-supervisor" in v}
    # the supervisor color reaches the tick AND its lock-free scrape
    for fn in ("Sup._loop", "MiniCollector.tick", "scrape"):
        assert fn in colored, sorted(colored)
    guards = {(f[1], f[2]): lock.display()
              for f, (lock, _, _) in model.inferred_guards().items()}
    assert guards[("MiniCollector", "_rows")] == "MiniCollector._lock"
    bad = good.replace("""\
                with self._lock:
                    for name, row in got.items():
                        self._rows[name] = row
""", """\
                for name, row in got.items():
                    self._rows[name] = row
""")
    assert bad != good, "mutation did not apply"
    findings, _ = _run(bad)
    assert "conc-unguarded-shared-field" in [f.rule for f in findings], \
        [(f.rule, f.message) for f in findings]


def test_promote_pairing_catches_dropped_promotion():
    """ISSUE 17: ``promote_pages`` pops device pages off the free stack
    exactly like an allocation; the obligation discharges when
    ``insert_promoted`` grafts the page into the radix tree. A path
    that promotes but exits before the graft silently leaks device
    pages — the conc-resource-leak pairing table must catch it."""
    bad = """\
        from apex_tpu.serving import kv_pool

        def promote(cache, tree, nodes, key, pages, n, tiles, ok):
            cache = kv_pool.promote_pages(cache, pages, n, tiles)
            if not ok:
                return cache
            tree.insert_promoted(nodes, key, int(pages[0]))
            return cache
    """
    findings, _ = _run(bad)
    assert [f.rule for f in findings] == ["conc-resource-leak"], \
        [(f.rule, f.message) for f in findings]
    good = """\
        from apex_tpu.serving import kv_pool

        def promote(cache, tree, nodes, key, pages, n, tiles):
            cache = kv_pool.promote_pages(cache, pages, n, tiles)
            tree.insert_promoted(nodes, key, int(pages[0]))
            return cache
    """
    findings, _ = _run(good)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_asyncio_task_coloring_on_live_http_server():
    """ISSUE 15: asyncio tasks are a thread color. The HTTP server's
    per-connection callback (handed to ``asyncio.start_server``) roots
    the ``asyncio`` color and it propagates through the whole
    connection-handling chain, including the disconnect watcher spawned
    via ``loop.create_task(...)``; the loop's own host thread keeps its
    literal-name color."""
    model, _ = build_model(_surface_sources())
    colored = {k.qualname for k, v in model.colors.items()
               if "asyncio" in v}
    for fn in ("HttpServingServer._handle", "HttpServingServer._dispatch",
               "HttpServingServer._generate",
               "HttpServingServer._stream_tokens",
               "HttpServingServer._watch_disconnect",
               "HttpServingServer._sse"):
        assert fn in colored, sorted(colored)
    loop_thread = {k.qualname for k, v in model.colors.items()
                   if "serving-http-loop" in v}
    assert "HttpServingServer._run" in loop_thread, sorted(loop_thread)
    # the client's per-request reader threads color the SSE parse chain
    reader = {k.qualname for k, v in model.colors.items()
              if "_stream" in v}
    assert "HttpReplicaClient._stream" in reader
    assert "_iter_sse" in reader


def test_docs_thread_safety_contract_matches_inference():
    """docs/frontend.md's contract table rows are cross-checked against
    the inferred GuardedBy map — the doc cannot drift from the code."""
    doc = Path(REPO, "docs", "frontend.md").read_text()
    rows = [line for line in doc.splitlines()
            if line.startswith("| `") and "`" in line[3:]]
    claimed = {}
    for line in rows:
        cells = [c.strip().strip("`") for c in line.strip("|").split("|")]
        if len(cells) >= 2 and "." in cells[0] and cells[1] != "—":
            claimed[cells[0]] = cells[1]
    assert claimed, "docs/frontend.md lost its thread-safety table"
    model, _ = build_model(_surface_sources())
    inferred = {f"{f[1]}.{f[2]}": lock.display()
                for f, (lock, _, _) in model.inferred_guards().items()}
    for field, lock in claimed.items():
        assert inferred.get(field) == lock, (
            f"doc claims {field} is guarded by {lock}; inference says "
            f"{inferred.get(field)}")


def test_blocking_in_nested_thread_target_not_flagged():
    """A nested def created under a lock runs when CALLED — on its own
    thread, lock-free. Its body must not inherit the enclosing
    function's lockset (code-review repro)."""
    src = """\
        import queue
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def helper(self):
                with self._lock:
                    def cb():
                        return self._q.get()
                    t = threading.Thread(target=cb, daemon=True)
                    t.start()
                    return t
    """
    findings, _ = _run(src)
    assert not findings, [(f.rule, f.message) for f in findings]


def test_conc_finding_is_inline_suppressible():
    src = FIXTURES["conc-useless-local-lock"][0].replace(
        "lock = threading.Lock()",
        "lock = threading.Lock()  "
        "# tpu-lint: disable=conc-useless-local-lock -- test")
    findings, suppressed = _run(src)
    assert not findings
    assert suppressed == 1


def test_tier_registry():
    assert tier_of("conc-lock-order-cycle") == "conc"
    assert tier_of("ir-x64-leak") == "ir"
    assert tier_of("host-sync-in-jit") == "ast"
    assert tier_of_key("a.py::conc-resource-leak::fn") == "conc"
    assert tier_of_key("a.py::host-sync-in-jit::fn") == "ast"
    assert tier_of_key("legacy-shape") == "ast"


def test_conc_write_baseline_keeps_other_tiers(tmp_path, monkeypatch):
    """--conc --write-baseline replaces only conc-* entries; AST and IR
    debt survives (the shared prefix registry, not string checks)."""
    from apex_tpu.analysis.walker import Finding

    baseline = tmp_path / "tpu_lint_baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": {
        "x.py::conc-blocking-under-lock::old": 1,
        "y.py::ir-dead-output::case_b": 2,
        "z.py::host-sync-in-jit::fn": 3,
    }}))
    fresh = Finding(rule="conc-resource-leak", severity="error",
                    path="x.py", line=1, col=1, message="m", scope="fn")
    import apex_tpu.analysis.conc as conc_pkg
    monkeypatch.setattr(conc_pkg, "analyze_conc",
                        lambda root, select=None: ([fresh], 0))
    assert cli.main(["--root", str(tmp_path), "--conc",
                     "--write-baseline"]) == 0
    counts = json.loads(baseline.read_text())["findings"]
    assert counts == {
        "x.py::conc-resource-leak::fn": 1,     # conc tier replaced
        "y.py::ir-dead-output::case_b": 2,     # IR kept
        "z.py::host-sync-in-jit::fn": 3,       # AST kept
    }


def test_conc_cli_usage_errors(capsys):
    assert cli.main(["--root", REPO, "--conc",
                     "--select", "no-such-conc-rule"]) == 2
    # AST rule names are not valid in conc mode
    assert cli.main(["--root", REPO, "--conc",
                     "--select", "host-sync-in-jit"]) == 2
    assert cli.main(["apex_tpu", "--root", REPO, "--conc"]) == 2
    assert cli.main(["--root", REPO, "--conc", "--ir"]) == 2
    assert cli.main(["--root", REPO, "--conc", "--diff", "HEAD"]) == 2


def test_list_rules_shows_all_tiers(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "conc:host" in out
    assert "conc-lock-order-cycle" in out
    assert "ir:jaxpr" in out


# --------------------------------------------------------------------------
# --diff covers the conc tier
# --------------------------------------------------------------------------

_DIFF_BASE = """\
import threading

def guard(x):
    lock = threading.Lock()
    with lock:
        return x + 1
"""

_DIFF_NEW = _DIFF_BASE + """\

def guard2(x):
    lock2 = threading.Lock()
    with lock2:
        return x + 2
"""


def _git(repo, *args):
    subprocess.run(["git", "-C", str(repo), *args], check=True,
                   capture_output=True,
                   env={**os.environ,
                        "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                        "GIT_COMMITTER_NAME": "t",
                        "GIT_COMMITTER_EMAIL": "t@t"})


def test_diff_covers_conc_tier(tmp_path, capsys):
    """A pre-existing conc finding at the base rev is absorbed; the one
    introduced since fails the diff gate."""
    _git(tmp_path, "init", "-q")
    mod = tmp_path / "tpu_scratch.py"
    mod.write_text(_DIFF_BASE)
    _git(tmp_path, "add", "tpu_scratch.py")
    _git(tmp_path, "commit", "-qm", "base")
    # unchanged tree: diff-clean even though the absolute gate would fire
    assert cli.main(["--root", str(tmp_path), "--diff", "HEAD"]) == 0
    capsys.readouterr()
    mod.write_text(_DIFF_NEW)
    rc = cli.main(["--root", str(tmp_path), "--diff", "HEAD"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "conc-useless-local-lock" in out
    assert "guard2" in out           # only the NEW finding is reported


# --------------------------------------------------------------------------
# seeded mutations against the live frontend
# --------------------------------------------------------------------------

_FE = "apex_tpu/serving/frontend.py"
_PUSH_LOCKED = ("    def _push(self, tok: int) -> None:\n"
                "        with self._lock:")
_INIT_ANCHOR = "        self._ingest_lock = threading.Lock()"
_INVERTED_METHODS = '''

    def _mut_fwd(self):
        with self._ingest_lock:
            with self._order_lock:
                return None

    def _mut_rev(self):
        with self._order_lock:
            with self._ingest_lock:
                return None
'''


def test_mutation_removed_lock_is_caught():
    """ISSUE 7 acceptance: deleting one ``with self._lock:`` from the
    live frontend fires conc-unguarded-shared-field on the lock-free
    site."""
    sources = _surface_sources()
    src = sources[_FE]
    assert src.count(_PUSH_LOCKED) == 1, "frontend._push anchor moved"
    sources[_FE] = src.replace(
        _PUSH_LOCKED, _PUSH_LOCKED.replace("with self._lock:", "if True:"))
    findings, _ = analyze_conc_sources(sources)
    hits = [f for f in findings
            if f.rule == "conc-unguarded-shared-field"
            and f.scope == "StreamHandle._push"]
    assert hits, [(f.rule, f.scope) for f in findings]
    # the unlocked _push body touches several guarded fields now
    # (_tokens plus ISSUE 15's consumption-listener seam) — every one
    # must be reported against the handle's lock
    msgs = " ".join(h.message for h in hits)
    assert "_tokens" in msgs
    assert "StreamHandle._lock" in msgs


def test_mutation_inverted_lock_order_is_caught():
    """ISSUE 7 acceptance: seeding an inverted acquisition order into
    the live frontend fires conc-lock-order-cycle naming both locks."""
    sources = _surface_sources()
    src = sources[_FE]
    assert _INIT_ANCHOR in src, "frontend __init__ anchor moved"
    sources[_FE] = src.replace(
        _INIT_ANCHOR,
        _INIT_ANCHOR + "\n        self._order_lock = threading.Lock()"
    ) + _INVERTED_METHODS
    findings, _ = analyze_conc_sources(sources)
    cycles = [f for f in findings if f.rule == "conc-lock-order-cycle"]
    assert cycles, [(f.rule, f.scope) for f in findings]
    assert "_ingest_lock" in cycles[0].message
    assert "_order_lock" in cycles[0].message


_HTTP = "apex_tpu/serving/http.py"
_GEN_ANCHOR = ("        with self._lock:\n"
               "            draining = self._draining\n")


def test_mutation_await_under_lock_is_caught():
    """ISSUE 15 acceptance: moving an ``await`` under the HTTP server's
    connection lock in the live source fires conc-await-under-lock on
    the coroutine — the rule is load-bearing against the real asyncio
    surface, not just the fixture."""
    sources = _surface_sources()
    src = sources[_HTTP]
    assert src.count(_GEN_ANCHOR) == 1, "http._generate anchor moved"
    sources[_HTTP] = src.replace(
        _GEN_ANCHOR, _GEN_ANCHOR + "            await asyncio.sleep(0)\n")
    findings, _ = analyze_conc_sources(sources)
    hits = [f for f in findings if f.rule == "conc-await-under-lock"
            and f.scope == "HttpServingServer._generate"]
    assert hits, [(f.rule, f.scope) for f in findings]
    assert "HttpServingServer._lock" in hits[0].message


def test_unmutated_frontend_scheduler_pair_is_clean():
    """The live frontend/scheduler pair carries no lock-order cycles or
    unguarded fields beyond the inline-suppressed intentional ones."""
    findings, suppressed = analyze_conc_sources(_surface_sources())
    assert not findings, [(f.rule, f.path, f.line) for f in findings]
    assert suppressed >= 1           # the _failure double-checked read


# --------------------------------------------------------------------------
# end-to-end: the repo is conc-clean at HEAD (tier-1 conc-gate twin)
# --------------------------------------------------------------------------

def test_repo_conc_is_clean_at_head(capsys):
    rc = cli.main(["--root", REPO, "--conc"])
    out = capsys.readouterr().out
    assert rc == 0, f"tpu-lint --conc found new issues in the repo:\n{out}"
