"""The six per-layer metrics PR 39 added: four that split
``pump_host_ms.serve`` by the pump's top-level phase (``counter_ratio`` over
the phase counters) and two that give the device's idle gaps to the pump's
spans by name (the new reader ``idle_by_span``, over
``trace_reduce.idle_gaps``' own attribution)."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import trace_reduce
from benchmark.layer_metrics.readers import counter_ratio, idle_by_span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVING = ["gpt2-large.chat-closed16", "glm-4.7-flash.docqa-closed32",
           "mellum2-12b-a2.5b.ide-closed48",
           "qwen3-next-80b-a3b.longchat-closed64"]
PHASE_MS = ["pump_dispatch_ms.serve", "pump_harvest_ms.serve",
            "pump_admission_ms.serve", "pump_housekeeping_ms.serve"]
IDLE = ["idle_admission_share.serve", "idle_harvest_share.serve"]
US = 1000                       # the trace's clock is in ns


def spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def synthetic():
    """One chip busy in five intervals, and the pump's spans around the
    four gaps between them (us): 100-200 inside the nested child
    ``pump:admission.launch``; 300-400 over the end of one child and the
    start of the next, so their parent's; 500-600 inside ``pump:retire``
    within ``pump:harvest``; 700-703, under 5 us, the device's own."""
    busy = [(0, 100), (200, 300), (400, 500), (600, 700), (703, 800)]
    spans = [("pump:admission", 90, 330), ("pump:admission.launch", 95, 210),
             ("pump:admission.join", 210, 350), ("pump:admission.feed", 350,
                                                  420),
             ("pump:admission", 340, 425),
             ("pump:harvest", 480, 640), ("pump:retire", 490, 620),
             ("bench:submit", 0, 5)]
    return {
        "/device:TPU:0": {"XLA Ops": [["op", s * US, (e - s) * US]
                                       for s, e in busy]},
        "/host:CPU": {"pump": [[name, s * US, (e - s) * US]
                               for name, s, e in spans]}}


@pytest.mark.parametrize("name", PHASE_MS + IDLE)
def test_the_metric_is_declared_last_for_the_four_serving_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == SERVING
    assert (entry["layer"], entry["moves"], entry["better"]) == (
        "serving/frontend (pump, admission)", "serve_tokens_per_s", "lower")
    assert [m["name"] for m in bench["per_layer"][-6:]] == PHASE_MS + IDLE
    reader = spec(name)["reader"]
    assert reader == ("idle_by_span" if name in IDLE else "counter_ratio")
    assert entry["source"] == ("device_trace" if name in IDLE
                               else "program_counter")
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "readers", reader + ".py"))


def test_a_nested_childs_gap_is_the_childs_and_a_straddling_one_the_parents():
    trace = synthetic()
    gaps = dict(trace_reduce.idle_gaps(trace, idle_by_span.EVERY_NAME))
    assert gaps == {"pump:admission.launch": pytest.approx(100e-6),
                    "pump:admission": pytest.approx(100e-6),
                    "pump:retire": pytest.approx(100e-6),
                    "between ops": pytest.approx(3e-6)}
    reading = {"trace": trace, "window_s": 1e-3}
    assert idle_by_span.read(reading, r"^pump:admission\.") == \
        pytest.approx(10.0)
    assert idle_by_span.read(reading, **spec(IDLE[0])["args"]) == \
        pytest.approx(20.0)
    assert idle_by_span.read(reading, **spec(IDLE[1])["args"]) == \
        pytest.approx(10.0)
    # a program from before the second level has the parents alone
    flat = {plane: {line: [e for e in events if "." not in e[0]
                           and e[0] != "pump:retire"]
                    for line, events in lines.items()}
            for plane, lines in trace.items()}
    flat_reading = {"trace": flat, "window_s": 1e-3}
    assert idle_by_span.read(flat_reading, **spec(IDLE[0])["args"]) == \
        pytest.approx(20.0)
    assert idle_by_span.read(flat_reading, **spec(IDLE[1])["args"]) == \
        pytest.approx(10.0)


def test_the_two_shares_lie_inside_the_devices_idle_share():
    trace = synthetic()
    window_s = 800e-6
    reading = {"trace": trace, "window_s": window_s}
    idle = 100.0 * (1.0 - trace_reduce.busy_seconds(trace) / window_s)
    shares = [idle_by_span.read(reading, **spec(name)["args"])
              for name in IDLE]
    assert sum(shares) == pytest.approx(100.0 * 300e-6 / window_s)
    assert sum(shares) <= idle


# one traced window's counter deltas: 40 iterations that did work
COUNTERS = {"pump_iterations": 40.0, "pump_host_seconds": 0.512,
            "pump_dispatch_seconds": 0.048, "pump_harvest_seconds": 0.141,
            "pump_admission_seconds": 0.296,
            "pump_housekeeping_seconds": 0.019,
            "pump_blocked_seconds": 3.1}


def test_the_four_phases_sum_to_the_host_work_of_an_iteration():
    reading = {"trace": synthetic(), "window_s": 4.0, "counters": COUNTERS}
    phases = [counter_ratio.read(reading, **spec(name)["args"])
              for name in PHASE_MS]
    assert phases == pytest.approx([1.2, 3.525, 7.4, 0.475])
    host = counter_ratio.read(reading,
                              **spec("pump_host_ms.serve")["args"])
    # what lies between the phases is the rest: here 0.008 s of 0.512
    assert sum(phases) == pytest.approx(host - 0.2)
    assert sum(phases) == pytest.approx(host, rel=0.05)


@pytest.mark.parametrize("name", PHASE_MS + IDLE)
def test_without_a_device_plane_there_is_no_number(name):
    on_cpu = {"/host:CPU": synthetic()["/host:CPU"]}
    reader = {"counter_ratio": counter_ratio,
              "idle_by_span": idle_by_span}[spec(name)["reader"]]
    for reading in ({"trace": on_cpu, "window_s": 4.0,
                     "counters": COUNTERS},
                    {"trace": None, "window_s": 4.0, "counters": COUNTERS},
                    {"counters": COUNTERS}):
        assert reader.read(reading, **spec(name)["args"]) is None


@pytest.mark.parametrize("name", PHASE_MS)
def test_a_program_without_the_counter_reports_nothing(name):
    """The parent has ``pump_admission_seconds`` and none of the other
    three: their metrics are left out of its line and nothing raises."""
    parents = {k: v for k, v in COUNTERS.items() if k not in (
        "pump_dispatch_seconds", "pump_harvest_seconds",
        "pump_housekeeping_seconds")}
    reading = {"trace": synthetic(), "window_s": 4.0, "counters": parents}
    value = counter_ratio.read(reading, **spec(name)["args"])
    assert (value is None) == (name != "pump_admission_ms.serve")


@pytest.mark.parametrize("cell", SERVING)
def test_the_cells_line_carries_the_six(cell):
    """Through ``run.read_layer_metrics`` as a traced run does it."""
    loaded = bench_run.Cell.load(cell, ROOT)
    reading = {"trace": synthetic(), "window_s": 1e-3, "counters": COUNTERS,
               "sync_every": 4, "num_slots": 16, "chips": 1}
    loaded.per_layer = [m for m in loaded.per_layer
                        if m["name"] in PHASE_MS + IDLE]
    assert len(loaded.per_layer) == 6
    line = bench_run.read_layer_metrics(loaded, reading, ROOT)
    assert set(line) == set(PHASE_MS + IDLE)
    assert line["idle_admission_share.serve"] == {
        "value": pytest.approx(20.0), "unit": "%"}
    assert line["pump_admission_ms.serve"]["unit"] == "ms"
