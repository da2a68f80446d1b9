"""The ``qwen3_next`` family of the benchmark: its contract, its configuration
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
                                             kernel_bytes_roofline,
                                             kernel_ms)

CELL = "qwen3-next-80b-a3b.longchat-closed64"
CONFIG = "qwen3-next-80b-a3b"

# the ``config`` of the catalog's row ``Qwen3-Next-80B-A3B-Instruct`` (the
# model-configs guide's architectures.jsonl), as read from the model's
# public config.json
CATALOG_ROW = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/"
          "main/config.json")
REDUCED = ["max_position_embeddings", "num_experts", "num_hidden_layers",
           "vocab_size"]

TINY = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, partial_rotary_factor=0.5,
            rope_theta=10000, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=8, num_experts=8, router_experts=16,
            first_expert=4, num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, num_hidden_layers=4,
            vocab_size=128, max_position_embeddings=128,
            compute_dtype="float32", param_dtype="float32")
TINY_LIMIT = 1e-4


@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell.load(CELL)


def test_the_family_exports_the_serving_contract(cell):
    family = families.load(cell.config)
    assert family.__name__ == "benchmark.families.qwen3_next"
    assert all(callable(getattr(family, name))
               for name in families.CONTRACT["serve"])
    assert cell.config["runner"] == "serve"
    assert family.drawn_vocab(cell.config) == 37984 == 151936 // 4
    # a page of the FULL layers' group: 2 layers x K and V x 2 heads x 256
    # x 2 B x 16 tokens; the mix's pool_bytes buys 32768 of them
    assert family.page_bytes(cell.config, 16) == 2 * 2 * 2 * 256 * 2 * 16
    engine = cell.mix["engine"]
    assert engine["pool_bytes"] // family.page_bytes(cell.config, 16) == 32768
    # the linear layers' state is the engine's own, on top: per layer and
    # slot a float32 state of 32 x 128 x 128 and the last 3 inputs of the
    # convolution over 8192 channels in bfloat16
    from apex_tpu.serving import kv_pool

    program = family.program_config(cell.config)
    assert kv_pool.state_bytes(program) == 6 * 2_146_304 == 12_877_824
    assert kv_pool.state_bytes(program, 64) == 824_180_736
    assert program.layer_types == ("linear_attention",) * 3 \
        + ("full_attention",) + ("linear_attention",) * 3 \
        + ("full_attention",)
    assert (program.num_experts, program.experts_held,
            program.first_expert, program.num_experts_per_tok) == (
        512, 128, 0, 10)
    assert program.routed_expert_bytes == 3 * 2048 * 512 * 2 == 6_291_456
    assert program.vocab_size == 37984
    assert program.max_position_embeddings == 32768


def test_forward_flops_count_the_active_parameters(cell):
    """The mixers, the whole router, the shared expert and its gate, 2.5 of
    the ten routed experts a token by expectation, the sliced head."""
    family = families.load(cell.config)
    linear = 2048 * (2 * 2048 + 2 * 4096 + 64) + 4096 * 2048
    full = 2048 * (2 * 16 + 2 * 2) * 256 + 16 * 256 * 2048
    assert family.mixer_params(cell.config, "linear_attention") == linear \
        == 33_718_464 - (8192 * 4 + 32 + 32 + 128)
    assert family.mixer_params(cell.config, "full_attention") == full \
        == 27_263_488 - 512
    expert = 3 * 2048 * 512
    moe = 2048 * 512 + 2.5 * expert + expert + 2048
    want = 2.0 * (6 * linear + 2 * full + 8 * moe + 37984 * 2048)
    assert family.forward_flops_per_token(cell.config) == want


def test_configuration_is_the_catalog_row_but_for_what_it_lists(cell):
    cfg = cell.config
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
    for key, published in CATALOG_ROW.items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
            assert str(published) in cfg["reduced"][key]
            assert cfg["published_" + key] == published
        else:
            assert cfg[key] == published, key
    assert cfg["num_hidden_layers"] == 8
    assert cfg["num_experts"] == 128 and cfg["router_experts"] == 512
    assert cfg["first_expert"] == 0
    assert cfg["vocab_size"] == 37984
    assert cfg["max_position_embeddings"] == 32768
    # no width among what is reduced
    assert not any(key.endswith(("_dim", "_rank", "_size")) and key
                   != "vocab_size" for key in cfg["reduced"])
    assert {"deployment", "fused_projections", "norm_weights", "decay",
            "state", "router", "rope", "mtp", "eos", "dtype"} <= set(
        cfg["assumed"])
    for said in ("4 chips", "12877824", "65536"):
        assert said in cfg["assumed"]["deployment"]


def test_the_cell_is_the_traffic_the_issue_names(cell):
    mix = cell.mix
    assert mix["arrival"] == {"kind": "closed", "clients": 64,
                              "think_s": 0.0}
    assert mix["prompt_lengths"] == {"1024": 0.4, "4096": 0.3, "8192": 0.2,
                                     "16384": 0.1}
    assert mix["output_lengths"] == {"kind": "lognormal", "mean": 512,
                                     "sigma": 0.5, "lo": 128, "hi": 1024}
    assert mix["engine"] == {"num_slots": 64, "page_size": 16,
                             "sync_every": 4, "prefix_cache": False,
                             "pool_bytes": 2147483648}
    assert (mix["cycle"], mix["ramp_s"], mix["traced_s"],
            mix["sampled_requests"], mix["warm_up_max"]) == (
        20, 4.0, 4.0, 4, 0)
    # between the program's largest reading and the nearest fault's
    # (PERF.md section 6 has the table)
    assert mix["limits"] == {"served_logit_gap": 0.125,
                             "failed_requests": 0.0}
    assert cell.chips == 1
    from benchmark.harness import traffic

    prompts = sorted(p for p, _, _ in traffic.cycle_shapes(mix))
    assert prompts == [1024] * 8 + [4096] * 6 + [8192] * 4 + [16384] * 2
    outs = [o for _, o, _ in traffic.cycle_shapes(mix)]
    assert min(outs) >= 128 and max(outs) <= 1024
    assert max(p + o for p, o, _ in traffic.cycle_shapes(mix)) <= 17408
    # supersets, so that a later PR may list this cell under more metrics
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        "slot_occupancy.serve", "device_idle.serve", "pump_host_ms.serve",
        "pump_bubble_share.serve", "admit_share.serve",
        "decode_step_ms.rate.serve", "step_mfu.rate.serve",
        "moe_experts_ms.rate.serve", "moe_experts_roofline.rate.serve",
        "paged_full_attention_ms.serve",
        "paged_full_attention_roofline.serve", "gated_delta_step_ms.serve",
        "gated_delta_step_roofline.serve",
        "cache_bytes_per_context_token.serve",
        "experts_hit_share_8x128.serve", "expert_load_imbalance_128.serve"}
    # scales of 64 experts, and a note that states Mellum's numbers
    assert not names & {"expert_load_imbalance.serve",
                        "experts_hit_share_8x64.serve",
                        "kv_bytes_per_context_token.serve"}
    assert {"serve_tokens_per_s", "setup_s"} <= {
        m["name"] for m in cell.end_to_end}
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer)


# what ``BENCHMARK.json`` held at PR 36, after the 32 entries
# ``test_mellum_family.py`` pins: the per-layer metrics in order, the cells
# each listed, and the cells in order
MELLUM = ["mellum2-12b-a2.5b.ide-closed48"]
HAD = [("paged_full_attention_roofline.serve", MELLUM),
       ("paged_window_attention_ms.serve", MELLUM),
       ("paged_window_attention_roofline.serve", MELLUM),
       ("kv_bytes_per_context_token.serve", MELLUM),
       ("experts_hit_share_8x64.serve", MELLUM),
       ("decode_step_ms.rate.serve", MELLUM),
       ("step_mfu.rate.serve", MELLUM),
       ("paged_full_attention_ms.serve", MELLUM),
       ("moe_experts_ms.rate.serve", MELLUM),
       ("moe_experts_roofline.rate.serve", MELLUM)]
MINE = ["gated_delta_step_ms.serve", "gated_delta_step_roofline.serve",
        "cache_bytes_per_context_token.serve",
        "experts_hit_share_8x128.serve", "expert_load_imbalance_128.serve"]


@pytest.mark.parametrize("index", range(len(HAD)))
def test_a_per_layer_metric_the_benchmark_had_is_where_it_was(index):
    """Entries 32-41, one case an entry, each one's cells STARTING with
    those it had at PR 36: whatever a later PR appends, none changes
    colour."""
    name, cells = HAD[index]
    metric = bench_run.load_json(
        os.path.join(ROOT, "BENCHMARK.json"))["per_layer"][32 + index]
    assert metric["name"] == name
    assert metric["workloads"][:len(cells)] == cells
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".json"))


def test_this_prs_entries_come_after_all_the_benchmark_had():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]][:6] == [
        "bert-large.pretrain-seq512", "gpt2-large.chat-closed16",
        "bert-large.pretrain-dp4", "glm-4.7-flash.docqa-closed32",
        MELLUM[0], CELL]
    assert [c["name"] for c in bench["configs"]][:5] == [
        "bert-large-uncased", "gpt2-large", "glm-4.7-flash",
        "mellum2-12b-a2.5b", CONFIG]
    assert bench["run_seconds"] == 50
    names = [m["name"] for m in bench["per_layer"]]
    assert names[42:47] == MINE
    assert all(bench["per_layer"][i]["workloads"][0] == CELL
               for i in range(42, 47))
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert rate["bound"] == 0.04 and rate["workloads"][:4] == [
        "gpt2-large.chat-closed16", "glm-4.7-flash.docqa-closed32",
        MELLUM[0], CELL]
    # four-chip cells: the one the benchmark had
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bert-large.pretrain-dp4"]


# -- the metric files the cell adds, over the readers that were there --------------

KERNEL = ('%%custom-call.%d = f32[64,32,128,128]{3,2,1,0} custom-call(%%p), '
          'custom_call_target="tpu_custom_call", frontend_attributes='
          '{kernel_metadata={"kernel":"%s"}}')


def _reading():
    """Two decode chunks of 4 steps on one chip: in each, six state updates
    of 0.5 ms and two paged calls of 1 ms."""
    ms = 1_000_000
    ops = []
    for chunk in range(2):
        t = chunk * 20 * ms
        for n in range(6):
            ops.append([KERNEL % (n, "gated_delta_step"), t, ms // 2])
            t += ms // 2
        for n in range(2):
            ops.append([KERNEL % (n, "paged_attention"), t, ms])
            t += ms
    peak = peaks.PEAKS["TPU v5e"]
    per_step = peak.hbm_bytes_per_s * 1e-3
    return {"trace": {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 10 * ms],
                        ["jit_step(1)", 20 * ms, 10 * ms]],
        "XLA Ops": ops}}, "sync_every": 4, "peak": peak, "window_s": 4.0,
        "counters": {"decode_steps": 8,
                     # 0.3 ms of state bytes a step
                     "state_bytes_moved": 8 * 0.3 * per_step,
                     "kv_bytes_held_steps": 8 * 64 * 5000.0 * 6500,
                     "context_token_steps": 8 * 64 * 5000.0,
                     "experts_hit": 8 * 700,
                     "expert_load_max": 8 * 8 * 6,
                     "expert_pairs_routed": 8 * 8 * 160}}


def _spec(metric):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".json"))


READERS = {"kernel_bytes_roofline": kernel_bytes_roofline,
           "counter_ratio": counter_ratio, "kernel_ms": kernel_ms}


@pytest.mark.parametrize("metric,want", [
    # (6 x 0.5 ms x 2 chunks) / 8 steps
    ("gated_delta_step_ms.serve", 0.75),
    # 0.3 ms of bytes against 0.75 ms
    ("gated_delta_step_roofline.serve", 40.0),
    ("cache_bytes_per_context_token.serve", 6500.0),
    ("experts_hit_share_8x128.serve", 100.0 * 700 / 1024),
    # the fullest held expert's 6 rows over the mean's 160 / 128
    ("expert_load_imbalance_128.serve", 6 * 128 / 160),
])
def test_the_cells_own_metric_files_read_what_they_say(metric, want):
    spec = _spec(metric)
    reader = READERS[spec["reader"]]
    assert reader.read(_reading(), **spec["args"]) == pytest.approx(want)
    # a program without the kernel and the counters (the parent) gives
    # nothing to read, and does not raise
    bare = dict(_reading(), counters={"decode_steps": 8})
    bare["trace"] = {"/device:TPU:0": {
        "XLA Modules": bare["trace"]["/device:TPU:0"]["XLA Modules"],
        "XLA Ops": []}}
    assert reader.read(bare, **spec["args"]) is None


def test_every_metric_file_of_the_cell_names_a_reader_that_exists(cell):
    for metric in cell.per_layer:
        spec = _spec(metric["name"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", "readers",
            spec["reader"] + ".py")), metric["name"]


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
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json"))
    # prompts off the page grid, one longer than a chunk of the rule
    mix.update(prompt_lengths={"13": 0.5, "75": 0.5},
               output_lengths={"kind": "lognormal", "mean": 12, "sigma": 0.5,
                               "lo": 4, "hi": 24},
               cycle=8, ramp_s=0.5, traced_s=0.5, sampled_requests=3)
    mix["arrival"]["clients"] = 4
    mix["engine"].update(num_slots=4, page_size=8, pool_bytes=2 ** 18)
    mix["limits"]["served_logit_gap"] = TINY_LIMIT
    for name, obj in (("configs/tiny-qwen3-next.json", cfg),
                      ("workloads/tiny-qwen3-next.longchat.json", mix)):
        with open(os.path.join(root, "benchmark", name), "w",
                  encoding="utf-8") as f:
            json.dump(obj, f)
    bench["configs"].append({
        "name": "tiny-qwen3-next", "source": "test", "reduced": [],
        "why": "tiny", "file": "benchmark/configs/tiny-qwen3-next.json"})
    bench["workloads"].append({
        "name": "tiny-qwen3-next.longchat", "config": "tiny-qwen3-next",
        "traffic": "longchat", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-qwen3-next.longchat")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_rehearsal_over_a_state_group_and_a_share(capsys, root, trace):
    """Through the runner's own engine: a state group beside the block
    table, experts 4-11 of 16 held, prompts that pad in their page
    bucket."""
    rc = bench_run.main(["--workload", "tiny-qwen3-next.longchat", "--seed",
                         str(2 ** 31 + 4111), "--seconds", "1.5", "--trace",
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
