"""The ``glm4_moe_lite`` family of the benchmark: its contract, its
configuration file against the catalog row it was copied from, the readers
its metrics add, and the serving runner rehearsed over it at a tiny size on
the CPU."""

import json
import os
import shutil

import pytest

from bench_fixtures import ROOT, cpu_devices

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import peaks, runtime
from benchmark.layer_metrics.readers import op_bytes_roofline, op_ms

CELL = "glm-4.7-flash.docqa-closed32"

# the ``config`` of the catalog's row ``GLM-4.7-Flash`` (the model-configs
# guide's architectures.jsonl), as read from the model's public config.json
CATALOG_ROW = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"

TINY = dict(hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
            num_hidden_layers=3, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            vocab_size=128, max_position_embeddings=128,
            compute_dtype="float32", param_dtype="float32")
TINY_LIMIT = 1e-4


@pytest.fixture(scope="module")
def cell():
    return bench_run.Cell.load(CELL)


def test_the_family_exports_the_serving_contract(cell):
    family = families.load(cell.config)
    assert family.__name__ == "benchmark.families.glm4_moe_lite"
    assert all(callable(getattr(family, name))
               for name in families.CONTRACT["serve"])
    assert cell.config["runner"] == "serve"
    assert family.drawn_vocab(cell.config) == 154880
    assert family.page_bytes(cell.config, 16) == 6 * 576 * 2 * 16
    pages = cell.mix["engine"]["pool_bytes"] // family.page_bytes(
        cell.config, cell.mix["engine"]["page_size"])
    assert pages == 38836


def test_forward_flops_count_the_active_parameters(cell):
    family = families.load(cell.config)
    assert family.attention_params(cell.config) == 21757952
    expert = 3 * 2048 * 1536
    assert expert == 9437184
    want = 2.0 * (6 * 21757952 + 3 * 2048 * 10240
                  + 5 * (64 * 2048 + 5 * expert) + 154880 * 2048)
    assert family.forward_flops_per_token(cell.config) == want


def test_configuration_is_the_catalog_row_but_for_what_it_lists(cell):
    cfg = cell.config
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == "glm-4.7-flash")
    assert entry["source"] == cfg["source"] == SOURCE
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
        "num_nextn_predict_layers"]
    for key, published in CATALOG_ROW.items():
        if key in cfg["reduced"]:
            assert cfg[key] != published
            assert str(published) in cfg["reduced"][key]
        else:
            assert cfg[key] == published, key
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"],
            cfg["max_position_embeddings"]) == (6, 0, 32768)
    assert {"rope", "e_score_correction_bias", "dtype"} <= set(cfg["assumed"])


def test_the_cell_is_the_traffic_the_issue_names(cell):
    mix = cell.mix
    assert mix["arrival"] == {"kind": "closed", "clients": 32,
                              "think_s": 0.0}
    assert mix["prompt_lengths"] == {"1024": 0.25, "2048": 0.3,
                                     "4096": 0.25, "8192": 0.15,
                                     "16384": 0.05}
    assert mix["output_lengths"] == {"kind": "lognormal", "mean": 128,
                                     "sigma": 0.5, "lo": 32, "hi": 256}
    assert mix["engine"] == {"num_slots": 32, "page_size": 16,
                             "sync_every": 4, "prefix_cache": True,
                             "pool_bytes": 4294967296}
    assert (mix["cycle"], mix["ramp_s"], mix["traced_s"],
            mix["sampled_requests"]) == (20, 4.0, 4.0, 4)
    assert cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"paged_latent_attention_ms.serve",
            "paged_latent_attention_roofline.serve", "moe_experts_ms.serve",
            "moe_experts_roofline.serve", "experts_hit_share.serve",
            "expert_load_imbalance.serve", "admit_share.serve",
            "decode_step_ms.serve", "step_mfu.serve",
            "device_idle.serve"} <= names
    assert not {"decode_roofline.serve", "paged_attention_ms.serve",
                "paged_attention_roofline.serve"} & names
    # ``ttft_p95_ms`` is not reported here (it spreads 11-14 % by seed, the
    # check admits 5 %: PERF.md section 6), so neither is a per-layer
    # metric that moves it
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "tpot_p95_ms", "setup_s"}
    assert all(m["moves"] != "ttft_p95_ms" for m in cell.per_layer)


# what ``BENCHMARK.json`` held before this cell: the per-layer metrics in
# order, and the cells each listed (conftest.py says why this is held here)
HAD_TRAIN = ["bert-large.pretrain-seq512", "bert-large.pretrain-dp4"]
HAD_SERVE = ["gpt2-large.chat-closed16"]
HAD = ["step_mfu.train", "grad_step_ms.train", "lamb_step_ms.train",
       "flash_roofline.train", "device_idle.train", "slot_occupancy.serve",
       "prefill_share.serve", "decode_step_ms.serve",
       "decode_roofline.serve", "step_mfu.serve", "device_idle.serve",
       "ttft_p50_ms.serve", "tpot_p50_ms.serve", "flash_fwd_ms.train",
       "flash_bwd_ms.train", "lamb_kernels_ms.train", "layer_norm_ms.train",
       "xentropy_ms.train", "paged_attention_ms.serve",
       "paged_attention_roofline.serve", "pump_host_ms.serve",
       "first_token_wait_mean_ms.serve", "queue_wait_mean_ms.serve",
       "pump_bubble_share.serve", "allreduce_exposed_ms.train"]


def test_what_the_benchmark_had_is_there_unchanged_but_for_appended_cells():
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    per_layer = bench["per_layer"]
    assert [m["name"] for m in per_layer[:25]] == HAD
    old_layers = {m["layer"] for m in per_layer[:13]}
    assert {m["layer"] for m in per_layer[13:24]} <= old_layers
    for m in per_layer[:24]:
        had = HAD_TRAIN if m["name"].endswith(".train") else HAD_SERVE
        assert m["workloads"][:len(had)] == had
        assert set(m["workloads"][len(had):]) <= {CELL}
    assert per_layer[24]["workloads"] == ["bert-large.pretrain-dp4"]
    # this PR's entries come last and read for its cell alone
    assert [m["workloads"] for m in per_layer[25:]] == [[CELL]] * 7
    assert [w["name"] for w in bench["workloads"]][:3] == [
        "bert-large.pretrain-seq512", "gpt2-large.chat-closed16",
        "bert-large.pretrain-dp4"]
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200


# -- the readers the cell adds ---------------------------------------------------

RAGGED = ('%ragged-dot-none.7 = bf16[128,1536]{1,0:T(8,128)(2,1)} '
          'custom-call(%get-tuple-element), custom_call_target='
          '"tpu_custom_call"')
META = ('%ragged-dot-metadata = (s32[65]{0}) custom-call(%gs), '
        'custom_call_target="tpu_custom_call"')


def _trace():
    """Two decode chunks of 4 steps and one admission on one chip; ragged
    products in all three programs."""
    ms = 1_000_000
    return {"/device:TPU:0": {
        "XLA Modules": [["jit_step(1)", 0, 10 * ms],
                        ["jit_admit(2)", 10 * ms, 30 * ms],
                        ["jit_step(1)", 40 * ms, 10 * ms]],
        "XLA Ops": [[RAGGED, 1 * ms, 2 * ms], [META, 3 * ms, ms // 2],
                    ["%fusion.1 = f32[8]{0} fusion(%p)", 4 * ms, 5 * ms],
                    [RAGGED, 12 * ms, 20 * ms],      # the admission's
                    [RAGGED, 41 * ms, 3 * ms], [META, 45 * ms, ms // 2]]}}


def test_op_ms_counts_only_inside_the_decode_chunk():
    reading = {"trace": _trace(), "sync_every": 4}
    args = dict(pattern="^%ragged-dot", event_pattern="^jit_step",
                event_steps="sync_every")
    # (2 + 0.5 + 3 + 0.5) ms over 2 chunks x 4 steps
    assert op_ms.read(reading, **args) == pytest.approx(6.0 / 8)
    assert op_ms.read({"trace": None}, **args) is None
    assert op_ms.read(reading, pattern="^%no-such-op",
                      event_pattern="^jit_step") is None
    assert op_ms.read(reading, pattern="^%ragged-dot",
                      event_pattern="^jit_other") is None


def test_op_bytes_roofline_holds_the_counter_against_the_time():
    peak = peaks.PEAKS["TPU v5e"]
    reading = {"trace": _trace(), "sync_every": 4, "peak": peak,
               "counters": {"expert_bytes_read": 8 * 0.3e-3
                            * peak.hbm_bytes_per_s, "decode_steps": 8}}
    args = dict(pattern="^%ragged-dot", event_pattern="^jit_step",
                event_steps="sync_every", bytes_counter="expert_bytes_read",
                steps_counter="decode_steps")
    # 0.3 ms of bytes a step against 0.75 ms of products a step
    assert op_bytes_roofline.read(reading, **args) == pytest.approx(40.0)
    reading["counters"] = {"decode_steps": 8}       # a program without it
    assert op_bytes_roofline.read(reading, **args) is None


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
        ROOT, "benchmark", "configs", "glm-4.7-flash.json"))
    cfg.update(TINY)
    mix = bench_run.load_json(os.path.join(
        ROOT, "benchmark", "workloads", CELL + ".json"))
    mix.update(prompt_lengths={"16": 0.5, "40": 0.5},
               output_lengths={"kind": "lognormal", "mean": 12, "sigma": 0.5,
                               "lo": 4, "hi": 24},
               cycle=8, ramp_s=0.5, warm_up_max=40, traced_s=0.5,
               sampled_requests=3)
    mix["arrival"]["clients"] = 4
    mix["engine"].update(num_slots=4, pool_bytes=2 ** 19)
    mix["limits"]["served_logit_gap"] = TINY_LIMIT
    for name, obj in (("configs/tiny-glm.json", cfg),
                      ("workloads/tiny-glm.docqa.json", mix)):
        with open(os.path.join(root, "benchmark", name), "w",
                  encoding="utf-8") as f:
            json.dump(obj, f)
    bench["configs"].append({"name": "tiny-glm", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/tiny-glm.json"})
    bench["workloads"].append({"name": "tiny-glm.docqa", "config": "tiny-glm",
                               "traffic": "docqa", "chips": 1, "why": "tiny"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-glm.docqa")
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)
    return root


def _last_line(capsys, root, trace):
    rc = bench_run.main(["--workload", "tiny-glm.docqa", "--seed",
                         str(2 ** 31 + 4099), "--seconds", "1.5", "--trace",
                         str(trace)], root=root)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_serve_rehearsal_over_the_latent_pool(capsys, root, trace):
    line = _last_line(capsys, root, trace)
    assert line["correct"] is True and line["failed"] == 0, (
        line["checks"], line["notes"])
    assert line["checks"]["served_logit_gap"]["value"] <= TINY_LIMIT
    assert line["notes"]["window_compiles"] == 0
    assert line["notes"]["judged_tokens"] > 0
    if trace:
        # no device in a CPU trace: only the host's counters read
        assert set(line["metrics"]) == {"slot_occupancy.serve",
                                        "tpot_p50_ms.serve"}
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "tpot_p95_ms",
                                        "setup_s"}


@pytest.mark.parametrize("variant", ["fp8", "no_selection_bias",
                                     "k_rope_unrotated"])
def test_control_and_the_named_faults_move_the_references_logits(root,
                                                                 variant):
    """What the comparison can put in the reference's place is another
    computation: float8 matmul inputs, the selection bias left out,
    ``k_rope`` left unrotated each move the logits by far more than the
    tiny limit, and the token each puts first is judged without error (on
    the chip, at the published sizes, each has to come out over the cell's
    limit: PERF.md section 6)."""
    import functools

    import numpy as np

    from benchmark.harness import weights
    from benchmark.references import glm4_moe_lite as reference

    cfg = bench_run.Cell.load("tiny-glm.docqa", root).config
    family = families.load(cfg)
    seed = 2 ** 31 + 4099
    rng = np.random.default_rng(5)
    samples = [(rng.integers(4, 128, 24).astype(np.int32),
                rng.integers(4, 128, 12).astype(np.int32))
               for _ in range(3)]
    judged = family.judge(cfg, seed, samples, variant)
    assert judged["tokens"] == 36 and 0.0 <= judged["gap"] <= judged["widest"]
    make = functools.partial(weights.make_weights, seed=seed)
    sequences = [np.concatenate(s) for s in samples]
    positions = [np.arange(len(s)) for s in sequences]
    plain = reference.logits_at(make, cfg, sequences, positions)
    moved = reference.logits_at(make, cfg, sequences, positions, variant)
    assert max(float(abs(np.asarray(a) - np.asarray(b)).max())
               for a, b in zip(plain, moved)) > 10 * TINY_LIMIT
    with pytest.raises(ValueError, match="unknown variant"):
        family.judge(cfg, seed, samples, "float16")
