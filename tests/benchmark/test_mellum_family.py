"""The ``mellum`` family of the benchmark: its contract, its configuration
file against the catalog row it was copied from, the cell's traffic as the
issue names it, the metric files its cell adds, what ``BENCHMARK.json`` held
before it (in a form that survives any later addition), and the serving
runner rehearsed over it at a tiny size on the CPU."""

import json
import os
import shutil

import pytest

from bench_fixtures import ROOT, cpu_devices

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import peaks, runtime
from benchmark.layer_metrics.readers import (counter_ratio,
                                             kernel_bytes_roofline)

CELL = "mellum2-12b-a2.5b.ide-closed48"
CONFIG = "mellum2-12b-a2.5b"
S, F = "sliding_attention", "full_attention"

# the ``config`` of the catalog's row ``Mellum2-12B-A2.5B-Instruct`` (the
# model-configs guide's architectures.jsonl), as read from the model's
# public config.json
CATALOG_ROW = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [S, S, S, F] * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        F: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
          "main/config.json")

TINY = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=48, num_hidden_layers=4,
            layer_types=[S, S, S, F], mlp_layer_types=["sparse"] * 4,
            sliding_window=16, vocab_size=128, max_position_embeddings=128,
            compute_dtype="float32", param_dtype="float32")
TINY_LIMIT = 1e-4


@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell.load(CELL)


def test_the_family_exports_the_serving_contract(cell):
    family = families.load(cell.config)
    assert family.__name__ == "benchmark.families.mellum"
    assert all(callable(getattr(family, name))
               for name in families.CONTRACT["serve"])
    assert cell.config["runner"] == "serve"
    assert family.drawn_vocab(cell.config) == 98304
    # a page of the FULL layers' group: 2 layers x K and V x 4 heads x 128
    # x 2 B x 16 tokens; the mix's pool_bytes buys 40960 of them
    assert family.page_bytes(cell.config, 16) == 2 * 2 * 4 * 128 * 2 * 16
    engine = cell.mix["engine"]
    assert engine["pool_bytes"] // family.page_bytes(cell.config, 16) == 40960
    # the sliding layers' rings are the engine's own, on top: the null
    # page and 65 pages a slot, 196608 B a page over the 6 layers
    from apex_tpu.serving import kv_pool

    program = family.program_config(cell.config)
    assert kv_pool.ring_pages(cell.config["sliding_window"], 16) == 65
    assert (1 + 48 * 65) * kv_pool.page_bytes(program, 16, layers=6) == \
        (1 + 48 * 65) * 6 * 2 * 4 * 128 * 2 * 16
    assert program.layer_windows == (1024, 1024, 1024, None) * 2
    assert program.routed_expert_bytes == 3 * 2304 * 896 * 2
    assert dict(program.yarn)[F].attention_factor == 1.2772588722239782


def test_forward_flops_count_the_active_parameters(cell):
    family = families.load(cell.config)
    assert family.attention_params(cell.config) == 21233664
    expert = 3 * 2304 * 896
    assert expert == 6193152
    want = 2.0 * (8 * (21233664 + 64 * 2304 + 8 * expert) + 98304 * 2304)
    assert family.forward_flops_per_token(cell.config) == want


def test_configuration_is_the_catalog_row_but_for_what_it_lists(cell):
    cfg = cell.config
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/mellum2-12b-a2.5b.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "layer_types", "max_position_embeddings", "mlp_layer_types",
        "num_hidden_layers"]
    for key, published in CATALOG_ROW.items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
        else:
            assert cfg[key] == published, key
    for key in ("num_hidden_layers", "max_position_embeddings"):
        assert str(CATALOG_ROW[key]) in cfg["reduced"][key]
    # two whole periods of the published pattern, and the depth with them
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == CATALOG_ROW["layer_types"][:8]
    assert cfg["mlp_layer_types"] == CATALOG_ROW["mlp_layer_types"][:8]
    assert cfg["max_position_embeddings"] == 32768
    assert {"router", "qk_norm", "rope", "mtp", "norm_weights", "eos",
            "dtype", "deployment"} <= set(cfg["assumed"])


def test_the_cell_is_the_traffic_the_issue_names(cell):
    mix = cell.mix
    assert mix["arrival"] == {"kind": "closed", "clients": 48,
                              "think_s": 0.0}
    assert mix["prompt_lengths"] == {"512": 0.3, "4096": 0.3, "8192": 0.25,
                                     "16384": 0.15}
    assert mix["output_lengths"] == {"kind": "lognormal", "mean": 384,
                                     "sigma": 0.5, "lo": 64, "hi": 768}
    assert mix["engine"] == {"num_slots": 48, "page_size": 16,
                             "sync_every": 4, "prefix_cache": False,
                             "pool_bytes": 2684354560}
    assert (mix["cycle"], mix["ramp_s"], mix["traced_s"],
            mix["sampled_requests"]) == (20, 4.0, 4.0, 4)
    assert mix["limits"]["failed_requests"] == 0.0
    assert cell.chips == 1
    from benchmark.harness import traffic

    prompts = sorted(p for p, _, _ in traffic.cycle_shapes(mix))
    assert prompts == [512] * 6 + [4096] * 6 + [8192] * 5 + [16384] * 3
    outs = [o for _, o, _ in traffic.cycle_shapes(mix)]
    assert min(outs) >= 64 and max(outs) <= 768
    assert max(p + o for p, o, _ in traffic.cycle_shapes(mix)) <= 17152
    # supersets, so that a later PR may list this cell under more metrics
    # (PERF.md sections 6 and 7 say which it is off and why)
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "slot_occupancy.serve", "device_idle.serve", "pump_host_ms.serve",
        "pump_bubble_share.serve", "expert_load_imbalance.serve",
        "admit_share.serve", "paged_full_attention_roofline.serve",
        "paged_window_attention_ms.serve",
        "paged_window_attention_roofline.serve",
        "kv_bytes_per_context_token.serve", "experts_hit_share_8x64.serve",
        "decode_step_ms.rate.serve", "step_mfu.rate.serve",
        "paged_full_attention_ms.serve", "moe_experts_ms.rate.serve",
        "moe_experts_roofline.rate.serve"}
    assert {"serve_tokens_per_s", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}


# what ``BENCHMARK.json`` held at PR 34, before this cell: the per-layer
# metrics in order, the cells each listed, and the cells in order
# (tests/conftest.py says why this is held here)
TRAIN = ["bert-large.pretrain-seq512", "bert-large.pretrain-dp4"]
GPT = ["gpt2-large.chat-closed16"]
GLM = ["glm-4.7-flash.docqa-closed32"]
HAD = [("step_mfu.train", TRAIN), ("grad_step_ms.train", TRAIN),
       ("lamb_step_ms.train", TRAIN), ("flash_roofline.train", TRAIN),
       ("device_idle.train", TRAIN), ("slot_occupancy.serve", GPT + GLM),
       ("prefill_share.serve", GPT), ("decode_step_ms.serve", GPT + GLM),
       ("decode_roofline.serve", GPT), ("step_mfu.serve", GPT + GLM),
       ("device_idle.serve", GPT + GLM), ("ttft_p50_ms.serve", GPT),
       ("tpot_p50_ms.serve", GPT + GLM), ("flash_fwd_ms.train", TRAIN),
       ("flash_bwd_ms.train", TRAIN), ("lamb_kernels_ms.train", TRAIN),
       ("layer_norm_ms.train", TRAIN), ("xentropy_ms.train", TRAIN),
       ("paged_attention_ms.serve", GPT),
       ("paged_attention_roofline.serve", GPT),
       ("pump_host_ms.serve", GPT + GLM),
       ("first_token_wait_mean_ms.serve", GPT),
       ("queue_wait_mean_ms.serve", GPT),
       ("pump_bubble_share.serve", GPT + GLM),
       ("allreduce_exposed_ms.train", TRAIN[1:]),
       ("paged_latent_attention_ms.serve", GLM),
       ("paged_latent_attention_roofline.serve", GLM),
       ("moe_experts_ms.serve", GLM), ("moe_experts_roofline.serve", GLM),
       ("experts_hit_share.serve", GLM),
       ("expert_load_imbalance.serve", GLM), ("admit_share.serve", GLM)]
HAD_CELLS = ["bert-large.pretrain-seq512", "gpt2-large.chat-closed16",
             "bert-large.pretrain-dp4", "glm-4.7-flash.docqa-closed32"]
HAD_END_TO_END = {"train_tokens_per_s": (0.02, TRAIN),
                  "serve_tokens_per_s": (0.04, GPT + GLM),
                  "ttft_p95_ms": (0.1, GPT), "tpot_p95_ms": (0.1, GPT + GLM),
                  "setup_s": (0.1, None)}


@pytest.mark.parametrize("index", range(len(HAD)))
def test_a_per_layer_metric_the_benchmark_had_is_where_it_was(index):
    """Entry by entry, so that whatever a later PR appends — a cell to a
    list, a metric at the end — none of these changes colour: the first 32
    entries by name and order, each one's cells STARTING with those it had
    at PR 34."""
    name, cells = HAD[index]
    metric = bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"][index]
    assert metric["name"] == name
    assert metric["workloads"][:len(cells)] == cells
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_the_cells_and_bounds_the_benchmark_had_are_where_they_were():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]][:4] == HAD_CELLS
    assert [c["name"] for c in bench["configs"]][:3] == [
        "bert-large-uncased", "gpt2-large", "glm-4.7-flash"]
    assert bench["run_seconds"] == 50
    assert [m["name"] for m in bench["end_to_end"]] == list(HAD_END_TO_END)
    for m in bench["end_to_end"]:
        bound, cells = HAD_END_TO_END[m["name"]]
        assert m["bound"] == bound
        assert cells is None or m["workloads"][:len(cells)] == cells
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200
    # this PR's entries come after all of those and read for its cell
    names = [m["name"] for m in bench["per_layer"]]
    mine = ["paged_full_attention_roofline.serve",
            "paged_window_attention_ms.serve",
            "paged_window_attention_roofline.serve",
            "kv_bytes_per_context_token.serve",
            "experts_hit_share_8x64.serve"]
    assert names[32:37] == mine
    assert all(bench["per_layer"][i]["workloads"][0] == CELL
               for i in range(32, 37))
    assert [w["name"] for w in bench["workloads"]][4] == CELL


# -- the metric files the cell adds, over the readers that were there --------------

KERNEL = ('%%custom-call.%d = bf16[48,4,8,128]{3,2,1,0} custom-call(%%p), '
          'custom_call_target="tpu_custom_call", frontend_attributes='
          '{kernel_metadata={"kernel":"%s"}}')


def _reading():
    """Two decode chunks of 4 steps on one chip: in each, two unbanded
    paged calls of 1 ms and six banded ones of 0.25 ms."""
    ms = 1_000_000
    ops, t = [], 0
    for chunk in range(2):
        t = chunk * 20 * ms
        for n in range(2):
            ops.append([KERNEL % (n, "paged_attention"), t, ms])
            t += ms
        for n in range(6):
            ops.append([KERNEL % (n, "paged_window_attention"), t, ms // 4])
            t += ms // 4
    peak = peaks.PEAKS["TPU v5e"]
    per_step = peak.hbm_bytes_per_s * 1e-3
    return {"trace": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 10 * ms],
                        ["jit_step(1)", 20 * ms, 10 * ms]],
        "XLA Ops": ops}}, "sync_every": 4, "peak": peak, "window_s": 4.0,
        "counters": {"decode_steps": 8,
                     # 0.1 ms of bytes a step in the full layers, 0.075 ms
                     # in the windowed ones
                     "kv_full_bytes_attended": 8 * 0.1 * per_step,
                     "kv_window_bytes_attended": 8 * 0.075 * per_step,
                     "kv_bytes_attended": 8 * 0.175 * per_step,
                     "kv_bytes_held_steps": 8 * 48 * 6000.0 * 6144,
                     "context_token_steps": 8 * 48 * 6000.0,
                     "experts_hit": 8 * 500}}


def _spec(metric):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".json"))


@pytest.mark.parametrize("metric,want", [
    # 0.1 ms of bytes against (2 x 1 ms x 2 chunks) / 8 steps = 0.5 ms
    ("paged_full_attention_roofline.serve", 20.0),
    # 0.075 ms against (6 x 0.25 x 2) / 8 = 0.375 ms
    ("paged_window_attention_roofline.serve", 20.0),
    ("kv_bytes_per_context_token.serve", 6144.0),
    ("experts_hit_share_8x64.serve", 100.0 * 500 / 512),
])
def test_the_cells_own_metric_files_read_what_they_say(metric, want):
    spec = _spec(metric)
    reader = {"kernel_bytes_roofline": kernel_bytes_roofline,
              "counter_ratio": counter_ratio}[spec["reader"]]
    assert reader.read(_reading(), **spec["args"]) == pytest.approx(want)
    # a program without the counters (the parent) gives nothing to read,
    # and does not raise
    bare = dict(_reading(), counters={"decode_steps": 8})
    assert reader.read(bare, **spec["args"]) is None


def test_the_two_kinds_of_call_are_told_apart_by_their_labels():
    from benchmark.layer_metrics.readers import kernel_ms

    reading = _reading()
    full = kernel_ms.read(reading, **_spec("paged_attention_ms.serve")["args"])
    banded = kernel_ms.read(
        reading, **_spec("paged_window_attention_ms.serve")["args"])
    assert full == pytest.approx(0.5) and banded == pytest.approx(0.375)
    # the accepted roofline holds BOTH kinds' bytes against the unbanded
    # calls' time alone, which is why this cell is not on its list
    both = kernel_bytes_roofline.read(
        reading, **_spec("paged_attention_roofline.serve")["args"])
    assert both == pytest.approx(35.0)


# -- the runner over the family, tiny, on the CPU --------------------------------

@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(runtime, "require_tpu", cpu_devices)
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: "off")
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5e"])
    monkeypatch.setattr(runtime, "trace_dir",
                        lambda: str(tmp_path / "trace"))
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "configs", CONFIG + ".json"))
    cfg.update(TINY)
    for rope in cfg["rope_parameters"].values():
        rope["rope_theta"] = 10000
    cfg["rope_parameters"][F].update(factor=4,
                                     original_max_position_embeddings=32)
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json"))
    mix.update(prompt_lengths={"16": 0.5, "56": 0.5},
               output_lengths={"kind": "lognormal", "mean": 12, "sigma": 0.5,
                               "lo": 4, "hi": 24},
               cycle=8, ramp_s=0.5, traced_s=0.5, sampled_requests=3)
    mix["arrival"]["clients"] = 4
    mix["engine"].update(num_slots=4, page_size=8, pool_bytes=2 ** 18)
    mix["limits"]["served_logit_gap"] = TINY_LIMIT
    for name, obj in (("configs/tiny-mellum.json", cfg),
                      ("workloads/tiny-mellum.ide.json", mix)):
        with open(os.path.join(root, "benchmark", name), "w",
                  encoding="utf-8") as f:
            json.dump(obj, f)
    bench["configs"].append({"name": "tiny-mellum", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/tiny-mellum.json"})
    bench["workloads"].append({"name": "tiny-mellum.ide",
                               "config": "tiny-mellum", "traffic": "ide",
                               "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-mellum.ide")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_rehearsal_over_the_two_groups_of_pages(capsys, root, trace):
    """Prompts of one and of three and a half windows through the runner's
    own engine: rings of 3 pages a slot beside a block table."""
    rc = bench_run.main(["--workload", "tiny-mellum.ide", "--seed",
                         str(2 ** 31 + 4099), "--seconds", "1.5", "--trace",
                         str(trace)], root=root)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0, (
        line["checks"], line["notes"])
    assert line["checks"]["served_logit_gap"]["value"] <= TINY_LIMIT
    assert line["notes"]["window_compiles"] == 0
    assert line["notes"]["judged_tokens"] > 0
    if trace:
        # no device in a CPU trace: only the host's counters read
        assert set(line["metrics"]) == {"slot_occupancy.serve"}
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
