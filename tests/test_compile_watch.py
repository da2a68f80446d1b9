"""Recompile watcher (apex_tpu/obs/compile_watch.py).

The load-bearing scenario is the seeded recompile storm: a jitted
function called at shape-varying arguments must show up in the watcher's
per-name compile counts, trip ``storms()``, and — through the serving
frontend — land a ``compile_storm`` warning event in the engine's
postmortem ring. Install/uninstall must leave jax's internals exactly as
found.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.obs import compile_watch
from apex_tpu.utils import metrics


@pytest.fixture
def fresh_watcher():
    """An isolated watcher: the process-wide one (installed by any
    earlier test that built a ServingFrontend) is parked for the
    duration so its listener cannot double-count these tests' events."""
    proc = compile_watch._PROCESS_WATCHER
    if proc is not None:
        proc.uninstall()
    w = compile_watch.CompileWatcher().install()
    yield w
    w.uninstall()
    if proc is not None:
        proc.install()


def _storm(n=4, name="storm_fn"):
    def storm_fn(x):
        return x * 2 + 1
    storm_fn.__name__ = name
    jf = jax.jit(storm_fn)
    for i in range(1, n + 1):
        jf(jnp.zeros((i,)))            # every shape = retrace + compile


def test_seeded_recompile_storm_counted_and_detected(fresh_watcher):
    w = fresh_watcher
    base = w.counts()
    _storm(4, "storm_a")
    counts = w.counts()
    key = "jit(storm_a)"
    assert counts.get(key, 0) - base.get(key, 0) == 4
    assert w.trace_misses().get("storm_a", 0) >= 4
    storms = w.storms(base, threshold=3)
    assert key in storms and storms[key] == 4
    # below threshold: quiet
    assert key not in w.storms(w.counts(), threshold=1)


def test_instruments_keyed_by_function_name(fresh_watcher):
    _storm(2, "storm_b")
    snap = metrics.snapshot()
    compiles = {tuple(sorted(c["labels"].items())): c["value"]
                for c in snap["counters"] if c["name"] == "jit.compiles"}
    assert compiles[(("fn", "jit(storm_b)"),)] == 2.0
    hists = {tuple(sorted(h["labels"].items())): h
             for h in snap["histograms"]
             if h["name"] == "jit.compile_ms"}
    h = hists[(("fn", "jit(storm_b)"),)]
    assert h["count"] == 2 and h["sum"] > 0
    traces = {tuple(sorted(c["labels"].items())): c["value"]
              for c in snap["counters"]
              if c["name"] == "jit.trace_cache_misses"}
    assert traces[(("fn", "storm_b"),)] == 2.0


def test_totals_and_repeat_calls_do_not_recount(fresh_watcher):
    w = fresh_watcher
    c0, t0 = w.totals()

    def once(x):
        return x + 1

    jf = jax.jit(once)
    for _ in range(5):
        jf(jnp.ones((3,)))             # one compile, four cache hits
    c1, t1 = w.totals()
    assert c1 - c0 >= 1
    counts = w.counts()
    assert counts.get("jit(once)", 0) == 1


def test_uninstalled_watcher_stops_counting(fresh_watcher):
    """``uninstall()`` really unsubscribes: compiles after it are not
    counted (a leaked listener would keep feeding the instruments)."""
    w = fresh_watcher
    _storm(2, "storm_c")
    assert w.counts().get("jit(storm_c)", 0) == 2
    w.uninstall()
    _storm(3, "storm_c2")
    assert "jit(storm_c2)" not in w.counts()
    assert metrics.counter(
        "jit.compiles", labels={"fn": "jit(storm_c2)"}).value == 0


def test_install_uninstall_restore_jax_hooks():
    from jax._src import monitoring

    n_listeners = len(monitoring.get_event_duration_listeners())
    w = compile_watch.CompileWatcher().install()
    assert len(monitoring.get_event_duration_listeners()) \
        == n_listeners + 1
    w.install()                        # idempotent
    assert len(monitoring.get_event_duration_listeners()) \
        == n_listeners + 1
    w.uninstall()
    assert len(monitoring.get_event_duration_listeners()) == n_listeners
    w.uninstall()                      # idempotent


def test_process_watcher_is_shared():
    assert compile_watch.watcher() is compile_watch.watcher()


def test_frontend_emits_compile_storm_event(monkeypatch, rng):
    """A storm during a frontend's lifetime lands a compile_storm
    warning in the engine's event ring, once per function name."""
    from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
    from apex_tpu.serving import PagedDecodeEngine, Request
    from apex_tpu.serving.frontend import ServingFrontend

    monkeypatch.setattr(compile_watch, "DEFAULT_STORM_THRESHOLD", 3)
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    fe = ServingFrontend(engine)
    _storm(4, "storm_d")               # the "recompiling op" stand-in
    h = fe.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32),
        max_new_tokens=3))
    fe.drain()
    h.result(timeout=0)
    storms = [e for e in engine.events.tail()
              if e["kind"] == "compile_storm"]
    assert any(e["fn"] == "jit(storm_d)" for e in storms)
    # once per name, not once per pump iteration
    assert len([e for e in storms if e["fn"] == "jit(storm_d)"]) == 1
