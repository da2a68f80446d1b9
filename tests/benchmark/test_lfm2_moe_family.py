"""The ``lfm2_moe`` family of the benchmark: its contract, its configuration
file against the catalog row it was copied from, the cell's traffic, the
metric files its cell reads, where its entries sit in ``BENCHMARK.json``,
and the serving runner rehearsed over it at a tiny size on the CPU."""

import json
import os
import shutil

import pytest

from bench_fixtures import ROOT, cpu_devices

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import peaks, runtime, traffic

CELL = "lfm2-24b-a2b.chat-closed128"
CONFIG = "lfm2-24b-a2b"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"

_CONV, _FULL = "conv", "full_attention"
# the ``config`` of the catalog's row ``LFM2-24B-A2B`` (the model-configs
# guide's architectures.jsonl), as read from the model's public config.json
CATALOG_ROW = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": [_CONV, _CONV] + [_FULL, _CONV, _CONV, _CONV] * 9
    + [_FULL, _CONV],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = ["layer_types", "max_position_embeddings", "num_dense_layers",
           "num_hidden_layers"]

#: the accepted metrics this PR lists the cell under (their readers are
#: generic)
APPENDED = [
    "decode_step_ms.rate.serve", "step_mfu.rate.serve",
    "moe_experts_ms.rate.serve", "moe_experts_roofline.rate.serve",
    "experts_hit_share_8x64.serve", "expert_load_imbalance.serve",
    "paged_full_attention_ms.serve", "paged_full_attention_roofline.serve",
    "cache_bytes_per_context_token.serve", "admit_share.serve",
    "slot_occupancy.serve", "device_idle.serve", "pump_bubble_share.serve",
    "pump_host_ms.serve"]

TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            layer_types=[_CONV, _FULL, _CONV, _CONV], num_dense_layers=1,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_hidden_layers=4, vocab_size=128, max_position_embeddings=128,
            compute_dtype="float32", param_dtype="float32")
TINY_LIMIT = 1e-4


@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell.load(CELL)


def test_the_family_exports_the_serving_contract(cell):
    family = families.load(cell.config)
    assert family.__name__ == "benchmark.families.lfm2_moe"
    assert all(callable(getattr(family, name))
               for name in families.CONTRACT["serve"])
    assert cell.config["runner"] == "serve"
    assert family.drawn_vocab(cell.config) == 65536
    program = family.program_config(cell.config)
    assert program.layer_types == (_CONV, _FULL, _CONV, _CONV, _CONV, _FULL,
                                   _CONV, _CONV, _CONV)
    assert program.num_dense_layers == 1 and program.head_dim == 64
    assert program.router_norm_eps == 1e-6
    assert program.max_position_embeddings == 8192


def test_page_and_state_bytes_are_the_configurations_numbers(cell):
    """A page of the attention layers' group: 2 layers x K and V x 8 heads
    x 64 x 2 B x 16 tokens = 65536 B, 4096 B a token; the mix's 2 GiB buy
    32768 of them. The windows are the engine's own on top: 7 conv layers
    x 2 x 2048 x 2 B = 57344 B a slot, 7340032 B at 128 slots."""
    from apex_tpu.serving import kv_pool

    family = families.load(cell.config)
    assert family.page_bytes(cell.config, 16) == 2 * 2 * 8 * 64 * 2 * 16 \
        == 65536
    engine = cell.mix["engine"]
    assert engine["pool_bytes"] // family.page_bytes(cell.config, 16) \
        == 32768
    program = family.program_config(cell.config)
    assert kv_pool.state_bytes(program) == 7 * 2 * 2048 * 2 == 57344
    assert kv_pool.state_bytes(program, 128) == 7_340_032
    deployment = cell.config["assumed"]["deployment"]
    for said in ("65536", "57344", "7340032", "10.36 GB"):
        assert said in deployment


def test_forward_flops_count_the_active_parameters(cell):
    """The mixers, the dense SwiGLU, per routed layer the router and four
    experts, the tied head: 648,019,968 parameters' matrices, as the
    configuration's ``assumed`` states."""
    family = families.load(cell.config)
    conv, attn = 4 * 2048 * 2048, 2048 * 80 * 64
    assert family.mixer_params(cell.config, _CONV) == conv == 16_777_216
    assert family.mixer_params(cell.config, _FULL) == attn == 10_485_760
    routed = 2048 * 64 + 4 * 3 * 2048 * 1536
    active = 7 * conv + 2 * attn + 3 * 2048 * 11776 + 8 * routed \
        + 65536 * 2048
    assert active == 648_019_968
    assert family.forward_flops_per_token(cell.config) == 2.0 * active \
        == 1_296_039_936
    said = cell.config["assumed"]["active_parameters"]
    assert "648019968" in said and "1296039936" in said


def test_configuration_is_the_catalog_row_but_for_what_it_lists(cell):
    cfg = cell.config
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
    for key, published in CATALOG_ROW.items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
            assert cfg["published_" + key] == published
        else:
            assert cfg[key] == published, key
    for key in ("num_hidden_layers", "num_dense_layers",
                "max_position_embeddings"):
        assert str(CATALOG_ROW[key]) in cfg["reduced"][key]
    assert cfg["num_hidden_layers"] == 9 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"] == CATALOG_ROW["layer_types"][1:10]
    assert cfg["max_position_embeddings"] == 8192
    # no width among what is reduced
    assert not any(key.endswith(("_dim", "_rank", "_size"))
                   for key in cfg["reduced"])
    assert {"deployment", "tied_head", "dense_ffn", "window", "expert_bias",
            "norm_weights", "router", "rope", "active_parameters", "eos",
            "dtype"} <= set(cfg["assumed"])


def test_the_cell_is_the_traffic_its_file_states(cell):
    mix = cell.mix
    assert mix["arrival"] == {"kind": "closed", "clients": 128,
                              "think_s": 0.0}
    assert mix["prompt_lengths"] == {"256": 0.25, "512": 0.3, "1024": 0.25,
                                     "2048": 0.15, "4096": 0.05}
    assert mix["output_lengths"] == {"kind": "lognormal", "mean": 384,
                                     "sigma": 0.5, "lo": 96, "hi": 1024}
    assert mix["engine"] == {"num_slots": 128, "page_size": 16,
                             "sync_every": 4, "prefix_cache": False,
                             "pool_bytes": 2147483648}
    assert (mix["cycle"], mix["ramp_s"], mix["traced_s"],
            mix["sampled_requests"], mix["warm_up_max"]) == (
        20, 4.0, 4.0, 4, 0)
    assert mix["limits"] == {"served_logit_gap": 0.125,
                             "failed_requests": 0.0}
    assert cell.chips == 1
    shapes = traffic.cycle_shapes(mix)
    prompts = sorted(p for p, _, _ in shapes)
    assert prompts == [256] * 5 + [512] * 6 + [1024] * 5 + [2048] * 3 \
        + [4096]
    assert sum(prompts) / 20 == 985.6
    outs = [o for _, o, _ in shapes]
    assert min(outs) >= 96 and max(outs) <= 1024
    assert max(p + o for p, o, _ in shapes) <= 5120 <= 8192
    names = {m["name"] for m in cell.per_layer}
    assert set(APPENDED) <= names
    # GLM's scale of 5 x 64, the state cell's 128 held, every expert's
    # bytes, both kinds' bytes over the unbanded calls: not this cell's
    assert not names & {"experts_hit_share.serve",
                        "experts_hit_share_8x128.serve",
                        "expert_load_imbalance_128.serve",
                        "decode_roofline.serve",
                        "paged_attention_roofline.serve"}
    assert {"serve_tokens_per_s", "setup_s"} <= {m["name"]
                                                 for m in cell.end_to_end}
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer)


@pytest.mark.parametrize("name", APPENDED)
def test_the_cell_is_listed_under_the_metric(name):
    """Each accepted metric this PR lists the cell under lists it, after
    the cells it had, and moves the rate the cell reports."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    metric = next(m for m in bench["per_layer"] if m["name"] == name)
    assert CELL in metric["workloads"]
    assert metric["workloads"].index(CELL) >= 1
    assert metric["moves"] == "serve_tokens_per_s"


def test_this_prs_entries_sit_at_the_end():
    """The configuration and the cell after the entries the benchmark had
    (held as prefixes, so a later addition keeps this true), the cell on
    one chip and under the serving rate."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]][:7] == [
        "bert-large.pretrain-seq512", "gpt2-large.chat-closed16",
        "bert-large.pretrain-dp4", "glm-4.7-flash.docqa-closed32",
        "mellum2-12b-a2.5b.ide-closed48",
        "qwen3-next-80b-a3b.longchat-closed64", CELL]
    assert [c["name"] for c in bench["configs"]][5] == CONFIG
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "chat-closed128", 1)
    assert 1 <= len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"]


# -- the metric files the cell reads, over the readers that were there --------------

def _spec(metric):
    return bench_run.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", metric + ".json"))


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
    # prompts off the page grid, one shorter than the window
    mix.update(prompt_lengths={"2": 0.25, "13": 0.25, "45": 0.5},
               output_lengths={"kind": "lognormal", "mean": 12, "sigma": 0.5,
                               "lo": 4, "hi": 24},
               cycle=8, ramp_s=0.5, traced_s=0.5, sampled_requests=3)
    mix["arrival"]["clients"] = 4
    mix["engine"].update(num_slots=4, page_size=8, pool_bytes=2 ** 18)
    mix["limits"]["served_logit_gap"] = TINY_LIMIT
    for name, obj in (("configs/tiny-lfm2-moe.json", cfg),
                      ("workloads/tiny-lfm2-moe.chat.json", mix)):
        with open(os.path.join(root, "benchmark", name), "w",
                  encoding="utf-8") as f:
            json.dump(obj, f)
    bench["configs"].append({
        "name": "tiny-lfm2-moe", "source": "test", "reduced": [],
        "why": "tiny", "file": "benchmark/configs/tiny-lfm2-moe.json"})
    bench["workloads"].append({
        "name": "tiny-lfm2-moe.chat", "config": "tiny-lfm2-moe",
        "traffic": "chat", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-lfm2-moe.chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_rehearsal_over_a_window_group(capsys, root, trace):
    """Through the runner's own engine: a state group of windows beside the
    block table, prompts that pad in their page bucket."""
    rc = bench_run.main(["--workload", "tiny-lfm2-moe.chat", "--seed",
                         str(2 ** 31 + 4141), "--seconds", "1.5", "--trace",
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
        assert "slot_occupancy.serve" in line["metrics"]
        assert "decode_step_ms.rate.serve" not in line["metrics"]
    else:
        assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
