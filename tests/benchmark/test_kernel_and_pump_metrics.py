"""The readers PR 27 added (``kernel_ms``, ``kernel_bytes_roofline``,
``counter_ratio``) and the eleven per-layer metrics that use them: on a
small trace whose op texts carry the kernel label as the v5e's profiler
wrote them (``data/trace_labelled.json``), on a counters dict, and on a
program from before the labels and the counters, which has nothing to read."""

import copy
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import peaks
from benchmark.layer_metrics.readers import (counter_ratio,
                                             kernel_bytes_roofline,
                                             kernel_ms)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRAIN, SERVE = "bert-large.pretrain-seq512", "gpt2-large.chat-closed16"
TRAIN_DP = "bert-large.pretrain-dp4"
NEW_TRAIN = {"flash_fwd_ms.train", "flash_bwd_ms.train",
             "lamb_kernels_ms.train", "layer_norm_ms.train",
             "xentropy_ms.train"}
NEW_SERVE = {"paged_attention_ms.serve", "paged_attention_roofline.serve",
             "pump_host_ms.serve", "first_token_wait_mean_ms.serve",
             "queue_wait_mean_ms.serve", "pump_bubble_share.serve"}
STEP = dict(per="event", event_pattern="^jit_step", event_steps="sync_every")


@pytest.fixture(scope="module")
def doc():
    with open(os.path.join(DATA, "trace_labelled.json"),
              encoding="utf-8") as f:
        return json.load(f)


def unlabelled(trace):
    """The same trace from a program without labels: the parent commit."""
    out = copy.deepcopy(trace)
    for lines in out.values():
        for events in lines.values():
            for event in events:
                event[0] = event[0].split(", frontend_attributes=")[0]
    return out


def test_the_recorded_op_texts_carry_their_labels(doc):
    for cell in ("train", "serve"):
        ops = doc[cell]["trace"]["/device:TPU:0"]["XLA Ops"]
        labelled = [name for name, _, _ in ops if "kernel_metadata" in name]
        assert labelled and len(labelled) < len(ops)
        # the result name is still the flax module or the Python closure
        assert all(name.startswith("%") and "custom-call(" in name
                   for name in labelled)


@pytest.mark.parametrize("labels", sorted(
    ["flash_fwd", "flash_bwd_dq|flash_bwd_dkv", "flash_bwd_dq",
     "l2norm|lamb_phase1|lamb_phase2", "layer_norm_fwd|layer_norm_bwd",
     "xentropy_fwd|xentropy_bwd"]))
def test_kernel_ms_per_traced_step(doc, labels):
    train = doc["train"]
    reading = {"trace": train["trace"], "steps": train["steps"]}
    assert kernel_ms.read(reading, labels) == pytest.approx(
        train["expected_ms"][labels], rel=1e-12)


def test_kernel_ms_counts_decode_steps_by_the_chunks_in_the_trace(doc):
    serve = doc["serve"]
    reading = {"trace": serve["trace"], "sync_every": serve["sync_every"]}
    assert kernel_ms.read(reading, "paged_attention", **STEP) == \
        pytest.approx(serve["expected_ms"]["paged_attention"], rel=1e-12)
    # a label of the training step is not in the serving programs
    assert kernel_ms.read(reading, "flash_bwd_dq", **STEP) is None


def test_a_label_is_matched_whole(doc):
    reading = {"trace": doc["train"]["trace"], "steps": 1}
    assert kernel_ms.read(reading, "flash") is None
    assert kernel_ms.read(reading, "flash_bwd") is None
    assert kernel_ms.read(reading, "lamb_phase") is None
    assert kernel_ms.read(reading, "flash_fwd") is not None


def test_label_absent_reads_nothing(doc):
    train, serve = doc["train"], doc["serve"]
    bare = {"trace": unlabelled(train["trace"]), "steps": train["steps"]}
    assert kernel_ms.read(bare, "flash_fwd") is None
    assert kernel_ms.read({"steps": 2}, "flash_fwd") is None       # no trace
    assert kernel_ms.read({"trace": train["trace"]}, "flash_fwd") is None
    bare = {"trace": unlabelled(serve["trace"]), "sync_every": 4,
            "counters": serve["counters"], "peak": peaks.peak_for("TPU v5e")}
    assert kernel_ms.read(bare, "paged_attention", **STEP) is None
    assert kernel_bytes_roofline.read(
        bare, "paged_attention", "kv_bytes_attended", "decode_steps",
        **STEP) is None


def test_kernel_bytes_roofline_is_per_step_on_both_sides(doc):
    serve = doc["serve"]
    peak = peaks.peak_for("TPU v5e")
    reading = {"trace": serve["trace"], "sync_every": serve["sync_every"],
               "counters": serve["counters"], "peak": peak}
    got = kernel_bytes_roofline.read(
        reading, "paged_attention", "kv_bytes_attended", "decode_steps",
        **STEP)
    assert got == pytest.approx(serve["expected_roofline"], rel=1e-12)
    assert 0.0 < got < 100.0
    # the host counts a chunk ahead of the device: twice the steps with
    # twice the bytes read the same
    ahead = dict(reading, counters={
        k: 2 * v for k, v in serve["counters"].items()})
    assert kernel_bytes_roofline.read(
        ahead, "paged_attention", "kv_bytes_attended", "decode_steps",
        **STEP) == pytest.approx(got, rel=1e-12)
    for counters in ({}, {"decode_steps": 8.0},
                     {"decode_steps": 0.0, "kv_bytes_attended": 1.0}):
        assert kernel_bytes_roofline.read(
            dict(reading, counters=counters), "paged_attention",
            "kv_bytes_attended", "decode_steps", **STEP) is None


def test_counter_ratio_over_a_counter_and_over_the_window(doc):
    trace = doc["serve"]["trace"]
    counters = {"pump_host_seconds": 0.03, "pump_iterations": 6.0,
                "pump_bubble_seconds": 0.08, "admitted": 0.0}
    reading = {"trace": trace, "window_s": 4.0, "counters": counters}
    assert counter_ratio.read(reading, "pump_host_seconds",
                              "pump_iterations", 1000.0) == \
        pytest.approx(5.0)
    assert counter_ratio.read(reading, "pump_bubble_seconds",
                              scale=100.0) == pytest.approx(2.0)
    # nothing admitted in the window, a program without the counter, a
    # rehearsal whose trace saw no device: nothing to read
    assert counter_ratio.read(reading, "pump_host_seconds",
                              "admitted") is None
    assert counter_ratio.read(reading, "queue_wait_seconds",
                              "pump_iterations") is None
    host_only = {"/host:CPU": {"t": [["bench:submit", 0, 10]]}}
    assert counter_ratio.read(dict(reading, trace=host_only),
                              "pump_bubble_seconds") is None
    assert counter_ratio.read({"counters": counters, "window_s": 4.0},
                              "pump_bubble_seconds") is None


def test_the_eleven_metrics_read_through_their_data_files(doc):
    train, serve = doc["train"], doc["serve"]
    peak = peaks.peak_for("TPU v5e")
    shapes = dict(batch=8, seq_len=512, heads=16, head_dim=64, layers=24)
    got = bench_run.read_layer_metrics(bench_run.Cell.load(TRAIN), {
        "trace": train["trace"], "steps": train["steps"], "peak": peak,
        "chips": 1, "shapes": shapes})
    assert NEW_TRAIN <= set(got)
    # the op's result name is left as it was: what found the flash kernels
    # by their flax module before the labels still finds them
    assert "flash_roofline.train" in got
    assert got["flash_bwd_ms.train"]["value"] == pytest.approx(
        train["expected_ms"]["flash_bwd_dq|flash_bwd_dkv"])
    assert all(got[name]["unit"] == "ms" for name in NEW_TRAIN)
    counters = dict(serve["counters"], pump_host_seconds=0.03,
                    pump_iterations=6.0, pump_bubble_seconds=0.04,
                    queue_wait_seconds=2.1, first_token_wait_seconds=0.15,
                    admitted=3.0, busy_slot_steps=100.0,
                    prefill_tokens_computed=900.0)
    got = bench_run.read_layer_metrics(bench_run.Cell.load(SERVE), {
        "trace": serve["trace"], "sync_every": serve["sync_every"],
        "num_slots": 16, "window_s": 4.0, "counters": counters,
        "peak": peak, "chips": 1, "client": {}, "weight_bytes": 3.1e9,
        "forward_flops_per_token": 1.5e9})
    assert NEW_SERVE <= set(got)
    assert got["queue_wait_mean_ms.serve"]["value"] == pytest.approx(700.0)
    assert got["first_token_wait_mean_ms.serve"]["value"] == \
        pytest.approx(50.0)
    assert got["pump_host_ms.serve"]["value"] == pytest.approx(5.0)
    assert got["pump_bubble_share.serve"] == {
        "value": pytest.approx(1.0), "unit": "%"}
    assert got["paged_attention_roofline.serve"]["value"] == pytest.approx(
        serve["expected_roofline"])


def test_the_parent_commit_reads_none_of_them_and_does_not_raise(doc):
    """The driver lays these files over the parent's checkout: there the ops
    carry no label and ``counter_deltas()`` lacks the new counters."""
    train, serve = doc["train"], doc["serve"]
    peak = peaks.peak_for("TPU v5e")
    got = bench_run.read_layer_metrics(bench_run.Cell.load(TRAIN), {
        "trace": unlabelled(train["trace"]), "steps": train["steps"],
        "peak": peak, "chips": 1, "window_s": 1.0, "tokens": 8,
        "flops_per_token": 1.0, "shapes": dict(
            batch=8, seq_len=512, heads=16, head_dim=64, layers=24)})
    assert "flash_roofline.train" in got
    assert not NEW_TRAIN & set(got)
    assert "grad_step_ms.train" in got          # what was there still reads
    old_counters = {"decode_steps": 24.0, "busy_slot_steps": 380.0,
                    "admitted": 3.0, "prefill_tokens_computed": 900.0}
    got = bench_run.read_layer_metrics(bench_run.Cell.load(SERVE), {
        "trace": unlabelled(serve["trace"]), "sync_every": 4,
        "num_slots": 16, "window_s": 4.0, "counters": old_counters,
        "peak": peak, "chips": 1, "client": {}, "weight_bytes": 3.1e9,
        "forward_flops_per_token": 1.5e9})
    assert not NEW_SERVE & set(got)
    assert {"decode_step_ms.serve", "slot_occupancy.serve"} <= set(got)


def test_new_entries_sit_at_the_end_with_the_layers_benchmark_json_had():
    bench = bench_run.load_json(os.path.join(bench_run.ROOT,
                                             "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    assert len(names) == 25 and set(names[13:24]) == NEW_TRAIN | NEW_SERVE
    old_layers = {m["layer"] for m in bench["per_layer"][:13]}
    assert {m["layer"] for m in bench["per_layer"][13:24]} <= old_layers
    for m in bench["per_layer"][13:24]:
        cells = [TRAIN, TRAIN_DP] if m["name"].endswith(".train") else [SERVE]
        assert m["workloads"] == cells
    # PR 29's, of a layer the benchmark had no metric of
    assert names[24] == "allreduce_exposed_ms.train"
    assert bench["per_layer"][24]["workloads"] == [TRAIN_DP]
    assert bench["per_layer"][24]["layer"] not in old_layers
