"""The benchmark's arithmetic, its data files and its refusal to run without
a chip — all on the CPU, no model built."""

import json
import math
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench_fixtures import ROOT, tiny_root

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import (compare, flops, peaks, stats, trace_reduce,
                               traffic, weights)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


# -- peaks, flops, bytes --------------------------------------------------------

def test_peaks_known_chip_and_unknown_device_kind_raises():
    assert peaks.peak_for("TPU v5 lite").bf16_flops_per_s == 197e12
    assert peaks.peak_for("TPU v5 lite").hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("TPU v5")      # a v5p must never be priced as a v5e
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


def test_bert_flops_per_token_against_hand_worked_number():
    # per layer 8*1024^2 + 4*512*1024 + 4*1024*4096 = 27,262,976; x24;
    # head (2*1024^2 + 2*1024*30528) * 80/512 = 10,096,640; x3 (fwd + bwd)
    got = flops.bert_train_flops_per_token(
        hidden=1024, intermediate=4096, layers=24, vocab=30528, seq_len=512,
        mlm_k=80)
    assert got == 3.0 * (24 * 27_262_976 + 10_096_640)
    assert got == pytest.approx(1.993e9, rel=1e-3)


def test_flash_flops_and_bytes_against_hand_worked_numbers():
    shapes = dict(batch=8, heads=16, seq_len=512, head_dim=64, layers=24)
    # 12*512*512*64 per head = 201,326,592; x 8*16*24 heads
    assert flops.flash_train_flops(**shapes) == 201_326_592 * 3072
    # a tensor is 8*16*512*64*2 B = 8 MiB; 12 of them per layer, 24 layers
    assert flops.flash_train_bytes(**shapes) == 12 * 24 * 8 * 2 ** 20


def test_gpt_params_flops_and_weight_bytes_against_hand_worked_numbers():
    n = flops.gpt_param_count(hidden=1280, layers=36, vocab=50304,
                              positions=1024)
    # 36 * (12*1280^2 + 13*1280) + (50304 + 1024)*1280 + 2*1280
    assert n == 36 * 19_677_440 + 65_699_840 + 2_560 == 774_090_240
    assert flops.gpt_forward_flops_per_token(
        hidden=1280, layers=36, vocab=50304) == 2.0 * (
            36 * 12 * 1280 ** 2 + 50304 * 1280)
    tree = {"a": np.zeros((3, 5), np.float32), "b": np.zeros((7,), np.int8)}
    assert flops.tree_bytes(tree) == 3 * 5 * 4 + 7


# -- latency arithmetic -----------------------------------------------------------

def test_percentile_is_nearest_rank_and_a_miss_sorts_last():
    values = list(range(1, 21))                     # 1..20
    assert stats.percentile(values, 95) == 19
    assert stats.percentile(values, 50) == 10
    assert stats.percentile(values + [math.inf] * 2, 95) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_tpot_and_ttft_arithmetic_with_a_failed_request_counted_missed():
    ok = [stats.RequestTimes(due=1.0 + i, done=True) for i in range(19)]
    for i, r in enumerate(ok):
        r.deliver(1.1 + i, 1)
        r.deliver(2.1 + i, 11)
    assert ok[0].ttft_ms() == pytest.approx(100.0)
    assert ok[0].tpot_ms() == pytest.approx(100.0)   # 1.0 s over 10 gaps
    failed = stats.RequestTimes(due=5.0, last=5.5, failed=True)
    assert failed.ttft_ms() == math.inf and failed.tpot_ms() == math.inf
    m = stats.serve_metrics(ok + [failed], 0.0, 30.0)
    # 19 x 11 tokens delivered over the whole 30 s; the failed one adds none
    assert m["serve_tokens_per_s"] == pytest.approx(19 * 11 / 30.0)
    assert m["failed"] == 1 and m["completed"] == 19
    # 20 samples, one missed: p95 is the 19th, p-above would be inf
    assert m["ttft_p95_ms"] == pytest.approx(100.0)
    assert stats.percentile([r.ttft_ms() for r in ok + [failed]],
                            96) == math.inf
    two = stats.serve_metrics(ok[:9] + [failed], 0.0, 30.0)
    assert two["ttft_p95_ms"] == math.inf and two["tpot_p95_ms"] == math.inf
    assert bench_run.finite(math.inf) == 1e12


def test_requests_outside_the_window_do_not_count():
    early = stats.RequestTimes(due=0.0, done=True)
    early.deliver(0.5, 1)
    early.deliver(0.9, 5)
    inside = stats.RequestTimes(due=0.8, done=True)
    inside.deliver(1.5, 1)
    inside.deliver(2.5, 5)
    across = stats.RequestTimes(due=0.2)         # still running at the close
    across.deliver(0.7, 4)
    across.deliver(2.0, 8)
    across.deliver(3.5, 12)
    m = stats.serve_metrics([early, inside, across], 1.0, 3.0)
    assert m["completed"] == 1 and m["ttft_samples"] == 1
    # 5 of the finished request and the 4 the unfinished one got inside
    assert m["serve_tokens_per_s"] == pytest.approx((5 + 4) / 2.0)
    assert m["tpot_samples"] == 1


def test_iqr_share_is_the_contracts_spread():
    values = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    q = __import__("statistics").quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx((q[2] - q[0]) / 10.25)


# -- traffic and weights -----------------------------------------------------------

MIX = {"kind": "serve", "arrival": {"kind": "closed", "clients": 4},
       "prompt_lengths": {"96": 0.15, "160": 0.25, "256": 0.25, "384": 0.2,
                          "512": 0.1, "768": 0.05},
       "output_lengths": {"kind": "lognormal", "mean": 128, "sigma": 0.5,
                          "lo": 32, "hi": 256}, "cycle": 80}


def test_every_seed_serves_the_same_shapes_in_another_order():
    a = traffic.ServeTraffic(MIX, 1000, seed=1)
    b = traffic.ServeTraffic(MIX, 1000, seed=3_000_000_001)
    take = lambda t: [(s.prompt_len, s.new_tokens)
                      for s, _ in zip(iter(t), range(80))]
    sa, sb = take(a), take(b)
    assert sorted(sa) == sorted(sb) and sa != sb
    assert take(traffic.ServeTraffic(MIX, 1000, seed=1)) == sa
    lens = [p for p, _ in sa]
    assert {n: lens.count(n) for n in set(lens)} == {
        96: 12, 160: 20, 256: 20, 384: 16, 512: 8, 768: 4}
    outs = [o for _, o in sa]
    assert min(outs) >= 32 and max(outs) <= 256
    assert 110 <= sum(outs) / 80 <= 135
    assert traffic.distinct_prompt_lengths(MIX) == [96, 160, 256, 384, 512,
                                                    768]


def test_prompts_are_seeded_and_shared_prefixes_are_shared():
    mix = dict(MIX, shared_prefix={"tenants": 2, "tokens": 32})
    t = traffic.ServeTraffic(mix, 1000, seed=5)
    specs = [s for s, _ in zip(iter(t), range(12))]
    prompts = [t.prompt(s) for s in specs]
    same = [p for s, p in zip(specs, prompts) if s.tenant == specs[0].tenant]
    assert len(same) >= 2
    assert all(np.array_equal(p[:32], same[0][:32]) for p in same)
    assert all(len(p) == s.prompt_len and p.min() >= 4 and p.max() < 1000
               for s, p in zip(specs, prompts))
    poisson = dict(MIX, arrival={"kind": "poisson", "rate_rps": 10.0})
    dues = [s.due_s for s, _ in zip(iter(traffic.ServeTraffic(
        poisson, 1000, seed=5)), range(50))]
    assert dues == sorted(dues) and 2.0 < dues[-1] < 10.0


def test_train_batches_rows_all_differ_and_labels_match_positions():
    mix = {"batch": 4, "seq_len": 32, "mlm_per_seq": 8}
    batches = traffic.train_batches(mix, 250, 2, seed=9, count=3)
    rows = np.concatenate([b["input_ids"] for b in batches])
    assert len({r.tobytes() for r in rows}) == 12
    b = batches[0]
    assert b["mlm_positions"].shape == (4, 8)
    assert np.array_equal(
        np.take_along_axis(b["input_ids"], b["mlm_positions"], axis=1),
        b["mlm_gathered_labels"])
    assert (b["mlm_labels"] != 0).sum() == 32
    again = traffic.train_batches(mix, 250, 2, seed=9, count=1)[0]
    assert np.array_equal(again["input_ids"], b["input_ids"])


def test_weights_depend_on_seed_and_name_only():
    big = 2 ** 31 + 12345            # more than 32 signed bits hold
    one = weights.make_weights({"a/weight": ((4, 8), np.float32),
                                "a/bias": ((8,), np.float32),
                                "x_norm/weight": ((8,), np.float32)}, big)
    two = weights.make_weights({"a/weight": ((4, 8), np.float32),
                                "zzz": ((3, 3), np.float32)}, big)
    assert np.array_equal(one["a/weight"], two["a/weight"])
    other = weights.make_weights({"a/weight": ((4, 8), np.float32)}, big + 1)
    assert not np.array_equal(one["a/weight"], other["a/weight"])
    assert abs(float(np.mean(one["x_norm/weight"])) - 1.0) < 0.05
    assert float(np.abs(one["a/bias"]).max()) > 0.0      # not the zeros
    assert weights.leaf_kind("a/bias", 1) == "bias"


# -- trace reduction ---------------------------------------------------------------

def hand_worked_trace():
    with open(os.path.join(DATA, "trace_small.json"), encoding="utf-8") as f:
        return json.load(f)


def test_union_merges_overlaps_and_touching_intervals():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) \
        == [(0, 4), (5, 7), (10, 11)]


def test_trace_reduce_on_a_hand_worked_trace():
    doc = hand_worked_trace()
    trace, want = doc["trace"], doc["expected"]
    assert trace_reduce.device_planes(trace) == ["/device:TPU:0"]
    assert trace_reduce.busy_seconds(trace) == pytest.approx(
        want["busy_s"], rel=1e-9)
    for pattern, (seconds, count) in want["programs"].items():
        got = trace_reduce.program_seconds(trace, pattern)
        assert got[0] == pytest.approx(seconds, rel=1e-9)
        assert got[1] == count
    top = trace_reduce.top_ops(trace, 3)
    assert [name for name, _ in top] == want["top_ops"]
    idle = 1.0 - trace_reduce.busy_seconds(trace) / want["window_s"]
    assert idle == pytest.approx(want["idle_share"], rel=1e-9)
    gaps = dict(trace_reduce.idle_gaps(trace, 10))
    assert set(gaps) == set(want["idle_gap_names"])
    assert sum(gaps.values()) == pytest.approx(want["gap_s"], rel=1e-9)


def test_trace_reduce_on_a_slice_recorded_on_the_v5e():
    """30 ms of a traced BERT-Large step as the chip's profiler wrote it
    (``run.py --look``), against a sweep over the sorted edges."""
    with open(os.path.join(DATA, "trace_v5e_slice.json"),
              encoding="utf-8") as f:
        trace = json.load(f)
    assert trace_reduce.device_planes(trace) == ["/device:TPU:0"]
    lines = trace["/device:TPU:0"]
    assert {"XLA Modules", "XLA Ops"} <= set(lines)
    ops = lines["XLA Ops"]
    edges = sorted([(s, -1) for _, s, _ in ops]
                   + [(s + d, 1) for _, s, d in ops],
                   key=lambda e: (e[0], e[1]))
    busy = depth = 0
    last = edges[0][0]
    for t, closing in edges:
        if depth > 0:
            busy += t - last
        depth -= closing
        last = t
    assert trace_reduce.busy_seconds(trace) == pytest.approx(busy / 1e9,
                                                             rel=1e-12)
    assert 0 < busy <= max(s + d for _, s, d in ops) - min(
        s for _, s, _ in ops)
    # the slice keeps 100 characters of a name: the flash kernels' result
    # name, not their opcode
    flash = [d for name, _, d in ops if name.startswith("%attention.")]
    assert flash and trace_reduce.op_seconds(
        trace, r"^%attention\.\d+ = ") == (
            pytest.approx(sum(flash) / 1e9), len(flash))
    assert all(trace_reduce.short_name(name).split()[0] == "attention"
               for name, _, _ in ops if name.startswith("%attention."))
    assert any(len(trace_reduce.short_name(n).split()) == 2
               for n, _, _ in ops)


def test_short_name_groups_a_labelled_kernel_by_its_label():
    with open(os.path.join(DATA, "trace_labelled.json"),
              encoding="utf-8") as f:
        doc = json.load(f)
    ops = doc["train"]["trace"]["/device:TPU:0"]["XLA Ops"]
    labelled = [n for n, _, _ in ops if "kernel_metadata" in n]
    names = {trace_reduce.short_name(n) for n in labelled}
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "l2norm",
            "lamb_phase1", "lamb_phase2"} <= names
    assert not any(" " in n or n == "metadata" for n in names)
    # an op without a label keeps "<result> <opcode>"
    plain = [n for n, _, _ in ops if "kernel_metadata" not in n]
    assert plain and all(len(trace_reduce.short_name(n).split()) == 2
                         for n in plain)
    top = dict(trace_reduce.top_ops(doc["train"]["trace"], 10))
    by_hand = sum(d for n, _, d in ops if '"flash_fwd"' in n) / 1e9
    assert top["flash_fwd"] == pytest.approx(by_hand)
    serve = dict(trace_reduce.top_ops(doc["serve"]["trace"], 10))
    assert "paged_attention" in serve
    assert not any(k.startswith("layer_") for k in serve)


def test_load_keeps_the_benchmarks_spans_and_the_pumps():
    import inspect

    prefixes = inspect.signature(trace_reduce.load).parameters[
        "host_prefix"].default
    assert prefixes == ("bench:", "pump:")
    assert "pump:admission".startswith(prefixes)
    assert not "$core.py:42 backend_compile".startswith(prefixes)
    trace = {"/device:TPU:0": {"XLA Ops": [["%a = f32[] add()", 0, 100],
                                           ["%b = f32[] add()", 10_100, 100]]},
             "/host:CPU": {"pump": [["pump:admission", 50, 10_100]],
                           "main": [["bench:submit", 0, 200]]}}
    assert trace_reduce.idle_gaps(trace, 10) == [["pump:admission", 1e-5]]


def collective_trace():
    with open(os.path.join(DATA, "trace_dp4_small.json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_allreduce_exposed_ms_on_a_small_four_chip_trace():
    """Sync all-reduces whole, of an async pair the ``-done`` half, averaged
    over the four planes, per traced step: against a sum made here."""
    doc = collective_trace()
    trace, steps = doc["trace"], doc["steps"]
    planes = trace_reduce.device_planes(trace)
    assert len(planes) == 4
    counted = 0
    kinds = set()
    for plane in planes:
        for name, _, dur in trace[plane]["XLA Ops"]:
            if " all-reduce(" in name:
                counted += dur
                kinds.add(re.sub(r"[.\d]+$", "",
                                 name.split(" = ")[0].lstrip("%")))
    assert counted > 0 and kinds == {"all-reduce", "psum"}
    # neither an op that takes a collective's result as an operand nor the
    # half of an asynchronous pair that returns at once is read
    from benchmark.layer_metrics.readers import collective_ms
    rx = re.compile(collective_ms.pattern("all-reduce|all-reduce-done"))
    assert not rx.search("%gte.1 = f32[] get-tuple-element((f32[], f32[8]) "
                         "%all-reduce.99), index=0")
    assert not rx.search("%all-reduce-start.3 = f32[8] all-reduce-start("
                         "f32[8] %p), channel_id=1")
    assert rx.search("%all-reduce-done.3 = f32[8] all-reduce-done(f32[8] "
                     "%all-reduce-start.3)")
    cell = bench_run.Cell.load("bert-large.pretrain-dp4")
    reading = {"trace": trace, "steps": steps, "window_s": doc["window_s"],
               "tokens": 1, "chips": 4, "flops_per_token": 1.0,
               "shapes": {}, "peak": peaks.peak_for("TPU v5e")}
    got = bench_run.read_layer_metrics(cell, reading)
    assert got["allreduce_exposed_ms.train"] == {
        "value": pytest.approx(counted / 4 / steps / 1e6, rel=1e-12),
        "unit": "ms"}
    assert got["allreduce_exposed_ms.train"]["value"] == pytest.approx(
        doc["expected_allreduce_exposed_ms"], rel=1e-9)
    # one chip has no collective: nothing to read, and the metric is not
    # the one-chip cell's
    one = {"/device:TPU:0": {"XLA Ops": [
        e for e in trace[planes[0]]["XLA Ops"]
        if " all-reduce(" not in e[0]]}}
    assert collective_ms.read(dict(reading, trace=one),
                              "all-reduce|all-reduce-done") is None
    assert "allreduce_exposed_ms.train" not in [
        m["name"] for m in bench_run.Cell.load(
            "bert-large.pretrain-seq512").per_layer]


def test_readers_return_nothing_where_there_is_nothing_to_read():
    cell = bench_run.Cell.load("bert-large.pretrain-seq512")
    assert bench_run.read_layer_metrics(cell, {
        "peak": peaks.peak_for("TPU v5e"), "chips": 1, "shapes": {},
        "flops_per_token": 1.0, "tokens_per_step": 1}) == {}
    host_only = {"/host:CPU": {"thread": [["bench:grad_step", 0, 10]]}}
    assert bench_run.read_layer_metrics(cell, {
        "trace": host_only, "window_s": 1.0, "steps": 2, "tokens": 8,
        "peak": peaks.peak_for("TPU v5e"), "chips": 1, "shapes": {},
        "flops_per_token": 1.0}) == {}


# -- the comparison ----------------------------------------------------------------

def test_worst_leaf_gap_measures_against_the_leaf_or_the_median_leaf():
    ref = {"big": 10.0, "mid": 1.0, "tiny": 1e-6}
    got = {"big": 10.5, "mid": 1.0, "tiny": 3e-6}
    gap, leaf = compare.worst_leaf_gap(got, ref)
    assert leaf == "big" and gap == pytest.approx(0.05)   # tiny: 2e-6 / 1.0
    with pytest.raises(KeyError):
        compare.worst_leaf_gap({"big": 1.0}, ref)
    assert compare.moving_leaves({"a": 1.0, "b": 1.0, "c": 1e-5}) == ["a",
                                                                    "b"]
    many_ref = {f"l{i}": 1.0 for i in range(100)}
    many = {f"l{i}": 1.0 + i / 1000.0 for i in range(100)}
    assert compare.quantile_leaf_gap(many, many_ref, 0.95) == \
        pytest.approx(0.095)
    assert compare.worst_leaf_gap(many, many_ref)[0] == pytest.approx(0.099)


def test_verdict_fails_on_a_number_over_its_limit_or_not_finite():
    ok, checks = compare.verdict({"x": 0.5, "y": 2.0, "extra": 9.0},
                                 {"x": 1.0, "y": 3.0})
    assert ok and set(checks) == {"x", "y"}
    assert not compare.verdict({"x": 1.5}, {"x": 1.0})[0]
    assert not compare.verdict({"x": float("nan")}, {"x": 1.0})[0]
    with pytest.raises(KeyError):
        compare.verdict({}, {"x": 1.0})


# -- BENCHMARK.json and the data files -----------------------------------------------

def test_benchmark_json_names_units_and_keys_meet_the_contract():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in b[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in b["end_to_end"]}
    layers = {}
    for m in b["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= e2e[m["moves"]] <= cells
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        layers.setdefault(m["layer"], []).append(m["name"])
    for cell in cells:
        assert any(cell in m["workloads"] for m in b["per_layer"])
        assert sum(cell in ws for ws in e2e.values()) >= 2
    assert any("mfu" in m["name"].split(".")[0] for m in b["per_layer"])


def test_every_name_leads_to_its_files_under_paths():
    b = bench()
    for c in b["configs"]:
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        # the runner is a file of the benchmark's, the model a family found
        # by the source's own model_type, which exports that runner's contract
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "runners", cfg["runner"] + ".py"))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "families", cfg["model_type"] + ".py"))
        family = families.load(cfg)
        missing = [name for name in families.CONTRACT[cfg["runner"]]
                   if not callable(getattr(family, name, None))]
        assert not missing, (cfg["model_type"], missing)
    for w in b["workloads"]:
        cell = bench_run.Cell.load(w["name"])
        assert cell.mix["limits"] and cell.per_layer and cell.end_to_end
    for m in b["per_layer"]:
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", "readers",
            spec["reader"] + ".py"))
    assert not any(w.startswith("/") or ".." in w for w in b["command"])


def family_modules():
    folder = os.path.join(ROOT, "benchmark", "families")
    return sorted(f[:-3] for f in os.listdir(folder)
                  if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("model_type", family_modules())
def test_every_family_exports_the_contract_of_a_runner(model_type):
    """A family serves the runner(s) whose whole contract it exports, and
    at least one; some configuration of the benchmark names it."""
    family = families.load({"model_type": model_type})
    serves = [runner for runner, names in families.CONTRACT.items()
              if all(callable(getattr(family, n, None)) for n in names)]
    assert serves, f"{model_type} exports no runner's whole contract"
    for runner in serves:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "runners",
                                           runner + ".py"))
    used = [bench_run.load_json(os.path.join(ROOT, c["file"]))
            for c in bench()["configs"]]
    assert any(c["model_type"] == model_type and c["runner"] in serves
               for c in used)


def test_the_runners_and_run_py_name_no_model():
    """The model lives in ``families/`` and ``references/``: outside comments
    and docstrings the runners name no family and none of its keys."""
    import io
    import tokenize

    rx = re.compile(r"gpt2|GPT|bert|Bert|n_embd")
    for rel in ("run.py", "runners/serve.py", "runners/train.py"):
        with open(os.path.join(ROOT, "benchmark", rel),
                  encoding="utf-8") as f:
            source = f.read()
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                continue
            if tok.type == tokenize.STRING and tok.string.startswith(
                    ('"' * 3, "'" * 3)):
                continue
            assert not rx.search(tok.string), (rel, tok.start, tok.string)


def test_the_mix_gives_the_references_hyper_parameters():
    mix = bench_run.Cell.load("bert-large.pretrain-dp4").mix
    assert traffic.train_hyper(mix) == {
        "lr": 1e-3, "beta1": 0.9, "beta2": 0.999, "eps": 1e-6,
        "weight_decay": 0.01, "max_grad_norm": 1.0}
    one = bench_run.Cell.load("bert-large.pretrain-seq512").mix
    assert traffic.train_hyper(one) == traffic.train_hyper(mix)
    # the same recipe on four chips: four times the rows, a block a chip
    assert mix["batch"] == 4 * one["batch"] == 4 * mix["reference_block_rows"]
    assert {k: v for k, v in mix.items() if k not in (
        "batch", "recipe", "reference_block_rows", "limits",
        "limits_from")} == {k: v for k, v in one.items() if k not in (
            "batch", "recipe", "limits", "limits_from")}


def test_a_later_pr_adds_config_cell_and_metric_as_new_files_only(tmp_path):
    root = tiny_root(tmp_path)
    for rel in ("benchmark/run.py", "benchmark/harness/traffic.py",
                "benchmark/runners/serve.py", "benchmark/runners/train.py",
                "benchmark/families/__init__.py",
                "benchmark/workloads/gpt2-large.chat-closed16.json",
                "benchmark/layer_metrics/step_mfu.serve.json"):
        with open(os.path.join(root, rel), "rb") as a, \
                open(os.path.join(ROOT, rel), "rb") as b:
            assert a.read() == b.read()         # nothing there was edited
    cell = bench_run.Cell.load("tiny-gpt.chat", root)
    assert cell.config["n_embd"] == 64 and cell.mix["cycle"] == 8
    assert "admitted.serve" in [m["name"] for m in cell.per_layer]
    got = bench_run.read_layer_metrics(
        cell, {"counters": {"admitted": 7.0, "decode_steps": 10,
                            "busy_slot_steps": 20}, "num_slots": 4}, root)
    assert got["admitted.serve"] == {"value": 7.0, "unit": "requests"}
    assert got["slot_occupancy.serve"]["value"] == pytest.approx(50.0)
    old = bench_run.Cell.load("gpt2-large.chat-closed16", root)
    assert "admitted.serve" not in [m["name"] for m in old.per_layer]
    with pytest.raises(SystemExit):
        bench_run.Cell.load("no-such-cell", root)


# -- no chip, no result ----------------------------------------------------------------

def test_run_refuses_a_backend_that_is_not_a_tpu_and_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "bert-large.pretrain-seq512",
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "does not run without the chip" in out.err


def test_run_fails_in_a_directory_with_only_the_benchmarks_files(tmp_path):
    import shutil

    root = str(tmp_path / "bare")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "bert-large.pretrain-seq512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0 and done.stdout.strip() == ""
