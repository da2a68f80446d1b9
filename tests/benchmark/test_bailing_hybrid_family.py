"""The ``bailing_hybrid`` family of the benchmark: its contract, its
configuration file against the catalog row it was copied from, the cell's
traffic, the accepted metrics it lists the cell under, and where its
entries sit in ``BENCHMARK.json``. (The engine over the family's model at a
tiny size is ``tests/test_bailing_hybrid.py``'s; the runner is rehearsed
over the other serving families.)"""

import os

import pytest

from bench_fixtures import ROOT

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import traffic

CELL = "ling-3.0-flash.reason-closed128"
CONFIG = "ling-3.0-flash"
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
          "config.json")

# the ``config`` of the catalog's row ``Ling-3.0-flash`` (the model-configs
# guide's architectures.jsonl), as read from the model's public config.json
CATALOG_ROW = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "model_type": "bailing_hybrid",
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184}
REDUCED = ["first_k_dense_replace", "max_position_embeddings", "num_experts",
           "num_hidden_layers", "num_nextn_predict_layers", "vocab_size"]

#: the accepted metrics this PR lists the cell under (their readers are
#: generic)
APPENDED = [
    "slot_occupancy.serve", "device_idle.serve", "pump_host_ms.serve",
    "pump_bubble_share.serve", "admit_share.serve",
    "decode_step_ms.rate.serve", "step_mfu.rate.serve",
    "moe_experts_ms.rate.serve", "moe_experts_roofline.rate.serve",
    "expert_load_imbalance_128.serve",
    "cache_bytes_per_context_token.serve", "gated_delta_step_ms.serve",
    "gated_delta_step_roofline.serve"]



@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell.load(CELL)


def test_the_family_exports_the_serving_contract(cell):
    family = families.load(cell.config)
    assert family.__name__ == "benchmark.families.bailing_hybrid"
    assert all(callable(getattr(family, name))
               for name in families.CONTRACT["serve"])
    assert cell.config["runner"] == "serve"
    assert family.drawn_vocab(cell.config) == 39296 == 157184 // 4
    program = family.program_config(cell.config)
    assert program.layer_types == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    assert program.first_k_dense_replace == 1
    assert (program.num_experts, program.experts_held, program.first_expert,
            program.n_group, program.topk_group) == (512, 128, 0, 8, 4)
    assert program.max_position_embeddings == 8192
    assert program.dt_bias_center == -6.0
    for key, value in (("use_kda_lora", True), ("rope_interleave", False),
                       ("moe_shared_expert_intermediate_size", 1536)):
        with pytest.raises(ValueError):
            family.program_config(dict(cell.config, **{key: value}))


def test_page_and_state_bytes_are_the_configurations_numbers(cell):
    """A page of the MLA layer's latent group: one layer x 576 values x 2 B
    x 16 tokens = 18432 B, 1152 B a token; the mix's 1 GiB buys 58254 of
    them. The KDA layers' state is the engine's own on top: 6 layers x
    (32 x 128 x 128 x 4 B + 3 x 12288 x 2 B) = 13025280 B a slot."""
    from apex_tpu.serving import kv_pool

    family = families.load(cell.config)
    assert family.page_bytes(cell.config, 16) == 576 * 2 * 16 == 18432
    engine = cell.mix["engine"]
    assert engine["pool_bytes"] // family.page_bytes(cell.config, 16) \
        == 58254
    program = family.program_config(cell.config)
    assert kv_pool.state_bytes(program) == 6 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 13_025_280
    assert kv_pool.state_bytes(program, 128) == 1_667_235_840
    deployment = cell.config["assumed"]["deployment"]
    for said in ("13,025,280", "1.67 GB", "10.46 GB", "1,152 B"):
        assert said in deployment


def test_forward_flops_count_the_active_parameters(cell):
    """The mixers, the dense SwiGLU, per routed layer the whole router, the
    shared expert and two of the eight routed experts (128 of 512 held),
    the sliced head."""
    family = families.load(cell.config)
    e, w = 2560, 32 * 128
    kda, mla = 6 * e * w + e * 32, (e * 32 * 192 + e * 576 + 512 * 32 * 256
                                    + e * 32 + 32 * 128 * e)
    assert family.mixer_params(cell.config, "kda") == kda == 62_996_480
    assert family.mixer_params(cell.config, "mla") == mla == 31_965_184
    routed = e * 512 + 2 * 3 * e * 768 + 3 * e * 768
    active = 6 * kda + mla + 3 * e * 6144 + 6 * routed + 39296 * e
    assert family.forward_flops_per_token(cell.config) == 2.0 * active


def test_configuration_is_the_catalog_row_but_for_what_it_lists(cell):
    cfg = cell.config
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/ling-3.0-flash.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == REDUCED
    for key, published in CATALOG_ROW.items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
            assert cfg["published_" + key] == published
            assert str(published) in cfg["reduced"][key]
        else:
            assert cfg[key] == published, key
    assert (cfg["num_hidden_layers"], cfg["first_layer"],
            cfg["first_k_dense_replace"], cfg["num_experts"],
            cfg["router_experts"], cfg["first_expert"]) == (7, 1, 1, 128,
                                                           512, 0)
    # no width, and not the experts a token is sent to, among what is
    # reduced (the vocabulary's slice is a share of rows, not a width)
    assert not any(key.endswith(("_dim", "_rank")) or key in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok")
        for key in cfg["reduced"])
    assert {"deployment", "kda_gate", "decay", "qk_norm", "head_gate",
            "rope", "router", "e_score_correction_bias", "norm_weights",
            "swiglu_limits", "eos", "dtype"} <= set(cfg["assumed"])


def test_the_seeded_decay_keeps_a_memory_of_tens_to_hundreds_of_tokens():
    """``dt_bias_center`` -6 with ``W_f x`` of unit spread: a token's
    per-channel decay at two spreads either side, 0.914 to 0.998."""
    import math

    def decay(z):
        return math.exp(-5 / (1 + math.exp(-z)))

    assert 0.91 < decay(-4) < decay(-6) < decay(-8) < 0.999
    assert round(decay(-6), 3) == 0.988


def test_the_cell_is_the_traffic_its_file_states(cell):
    mix = cell.mix
    assert mix["arrival"] == {"kind": "closed", "clients": 128,
                              "think_s": 0.0}
    assert mix["prompt_lengths"] == {"512": 0.25, "1024": 0.35,
                                     "2048": 0.25, "4096": 0.15}
    assert mix["output_lengths"] == {"kind": "lognormal", "mean": 1024,
                                     "sigma": 0.5, "lo": 256, "hi": 2048}
    assert mix["engine"] == {"num_slots": 128, "page_size": 16,
                             "sync_every": 4, "prefix_cache": False,
                             "pool_bytes": 1073741824}
    assert (mix["cycle"], mix["ramp_s"], mix["traced_s"],
            mix["sampled_requests"], mix["warm_up_max"]) == (
        20, 4.0, 4.0, 4, 0)
    # between the program's widest mean gap over its seeds (0.0230) and the
    # float8 control's (0.176), with the faults of the program's own above
    # it (PERF.md section 6)
    assert mix["limits"] == {"served_logit_gap": 0.06,
                             "failed_requests": 0.0}
    assert cell.chips == 1
    shapes = traffic.cycle_shapes(mix)
    prompts = sorted(p for p, _, _ in shapes)
    assert prompts == [512] * 5 + [1024] * 7 + [2048] * 5 + [4096] * 3
    assert sum(prompts) / 20 == 1612.8
    outs = [o for _, o, _ in shapes]
    assert min(outs) >= 256 and max(outs) <= 2048
    assert max(p + o for p, o, _ in shapes) <= 6144 <= 8192
    names = {m["name"] for m in cell.per_layer}
    assert set(APPENDED) <= names
    # scales of 8 routed layers, a page walk this cell has no kernel of:
    # not this cell's
    assert not names & {"experts_hit_share_8x128.serve",
                        "experts_hit_share_8x64.serve",
                        "paged_full_attention_ms.serve"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    assert all(m["moves"] == "serve_tokens_per_s" for m in cell.per_layer)


@pytest.mark.parametrize("name", APPENDED)
def test_the_cell_is_listed_under_the_metric(name):
    """Each accepted metric this PR lists the cell under lists it, after
    the cells it had, and moves the rate the cell reports."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    metric = next(m for m in bench["per_layer"] if m["name"] == name)
    assert metric["workloads"].index(CELL) >= 1
    assert metric["moves"] == "serve_tokens_per_s"


def test_this_prs_entries_sit_at_the_end():
    """The configuration and the cell after the entries the benchmark had
    (held as prefixes, so a later addition keeps this true), the cell on
    one chip and under the serving rate."""
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert [w["name"] for w in bench["workloads"]][:8] == [
        "bert-large.pretrain-seq512", "gpt2-large.chat-closed16",
        "bert-large.pretrain-dp4", "glm-4.7-flash.docqa-closed32",
        "mellum2-12b-a2.5b.ide-closed48",
        "qwen3-next-80b-a3b.longchat-closed64",
        "lfm2-24b-a2b.chat-closed128", CELL]
    assert [c["name"] for c in bench["configs"]][6] == CONFIG
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "reason-closed128", 1)
    assert 1 <= len(entry["why"]) <= 200
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s")
    assert CELL in rate["workloads"]


def test_every_metric_file_of_the_cell_names_a_reader_that_exists(cell):
    for metric in cell.per_layer:
        spec = bench_run.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric["name"] + ".json"))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", "readers",
            spec["reader"] + ".py")), metric["name"]
