"""The bench scripts off the chip: bench.py must refuse a device whose peak
it does not know (a CPU included) instead of inventing one, and
tpu_decode_bench.py's CPU mechanics check must stamp every record with the
device it ran on.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_refuses_a_device_without_a_known_peak():
    env = dict(os.environ, JAX_PLATFORMS="cpu", APEX_TPU_BENCH_STEPS="1")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "no peak FLOP/s known for device kind" in r.stderr
    assert not [line for line in r.stdout.splitlines()
                if line.startswith("{")], "a record was printed on the CPU"


def test_decode_bench_smoke_emits_json(tmp_path):
    """tpu_decode_bench.py in smoke mode prints its parseable JSON
    records (lock-step, paged, int8-kv paged, w8 weight-streaming,
    tp=2, prefix-cached, host-tier churn,
    async frontend, speculative, chunked-prefill TTFT A/B), the paged
    record carries the TTFT/decode-step percentile fields (ISSUE 4), the
    frontend record carries the open-loop TTFT/TPOT/deadline-miss fields
    with preemptions > 0 under the adversarial burst (ISSUE 6), and the
    metrics snapshot artifact lands where APEX_TPU_METRICS_OUT points."""
    env = dict(os.environ)
    env["APEX_TPU_DECODE_SMOKE"] = "1"
    # the tp=2 section needs >= 2 devices
    if "host_platform_device_count" not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
    snap_path = tmp_path / "metrics_snapshot.json"
    env["APEX_TPU_METRICS_OUT"] = str(snap_path)
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tpu_decode_bench.py")],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    recs = {}
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            rec = json.loads(line)
            recs[rec["metric"]] = rec

    # every record names the device it was taken on: a CPU smoke record
    # cannot pass for a chip measurement
    for rec in recs.values():
        assert rec["platform"] == "cpu" and rec["n_devices"] == 8
        assert rec["device_kind"]

    rec = recs["gpt2_decode_tokens_per_sec_per_chip"]
    assert rec["value"] > 0
    assert rec["unit"] == "tokens/s/chip"
    # speedup may round toward 0 under extreme CPU scheduler noise —
    # assert presence/sanity, not a ratio
    assert rec["int8_tokens_per_sec"] > 0 and rec["int8_speedup"] >= 0

    paged = recs["gpt2_paged_decode_tokens_per_sec_per_chip"]
    assert paged["gpt2_paged_decode_ttft_ms_p50"] > 0
    assert (paged["gpt2_paged_decode_ttft_ms_p95"]
            >= paged["gpt2_paged_decode_ttft_ms_p50"])
    assert paged["decode_step_ms_p50"] > 0
    assert paged["decode_step_ms_p95"] >= paged["decode_step_ms_p50"]
    assert paged["queue_wait_ms_p50"] >= 0
    assert paged["tpot_ms_p50"] > 0

    # the quantized KV-page engine's record (ISSUE 14, docs/serving.md
    # "Quantized KV pages"): throughput parses, the slot-capacity
    # telemetry carries the >= 1.9x fixed-budget win, and — asserted
    # inside the bench itself — every request's shape and first token
    # match the fp paged engine (full token parity is tolerance-pinned
    # in tests/test_quantized_kv.py, not an exact-identity bench gate)
    q8 = recs["gpt2_int8kv_paged_decode_tokens_per_sec_per_chip"]
    assert q8["value"] > 0
    assert q8["unit"] == "tokens/s/chip"
    assert q8["kv_dtype"] == "int8"
    assert q8["generated_tokens"] == paged["generated_tokens"]
    assert q8["page_bytes_int8"] < q8["page_bytes_fp"]
    assert q8["int8_slot_capacity"] >= 1.9 * q8["fp_slot_capacity"]
    assert q8["slot_capacity_ratio"] >= 1.9
    assert q8["gpt2_int8kv_paged_decode_ttft_ms_p50"] > 0
    assert (q8["gpt2_int8kv_paged_decode_ttft_ms_p95"]
            >= q8["gpt2_int8kv_paged_decode_ttft_ms_p50"])
    assert q8["tpot_ms_p50"] > 0

    # the quantized WEIGHT-streaming record (ISSUE 16, docs/serving.md
    # "Quantized weight streaming"): throughput parses, the weight-tree
    # byte telemetry shows the quantized tree genuinely below the fp
    # tree, and — asserted inside the bench itself — every request's
    # shape and first token match the fp paged engine (fixed-seed pin;
    # tolerance parity lives in tests/test_quantized_weights.py)
    w8 = recs["gpt2_w8_paged_decode_tokens_per_sec_per_chip"]
    assert w8["value"] > 0
    assert w8["unit"] == "tokens/s/chip"
    assert w8["weight_dtype"] == "int8"
    assert w8["generated_tokens"] > 0
    assert w8["w8_weight_bytes"] < w8["fp_weight_bytes"]
    assert 0.0 < w8["weight_bytes_ratio_vs_fp"] < 1.0
    assert w8["gpt2_w8_paged_decode_ttft_ms_p50"] > 0
    assert (w8["gpt2_w8_paged_decode_ttft_ms_p95"]
            >= w8["gpt2_w8_paged_decode_ttft_ms_p50"])
    assert w8["tpot_ms_p50"] > 0

    # the tensor-parallel paged engine's record (ISSUE 10,
    # docs/tp_serving.md): the tp=2 run must have actually happened
    # (conftest forces 8 virtual CPU devices into this subprocess's
    # env), carry the per-chip headline + TTFT/TPOT percentiles, and —
    # asserted inside the bench itself — be greedy token-identical to
    # the single-chip paged engine on the same workload
    tp = recs["gpt2_tp2_paged_decode_tokens_per_sec_per_chip"]
    assert tp["value"] > 0
    assert tp["tp_world"] == 2
    assert tp["gpt2_tp2_paged_decode_ttft_ms_p50"] > 0
    assert (tp["gpt2_tp2_paged_decode_ttft_ms_p95"]
            >= tp["gpt2_tp2_paged_decode_ttft_ms_p50"])
    assert tp["gpt2_tp2_paged_decode_tpot_ms_p50"] > 0
    assert tp["aggregate_tokens_per_sec"] >= tp["value"]

    pc = recs["gpt2_prefix_cached_decode_tokens_per_sec_per_chip"]
    assert pc["ttft_ms_p50"] > 0 and pc["decode_step_ms_p50"] > 0

    # the tiered KV pool's record (ISSUE 17, docs/serving.md "Tiered KV
    # pool"): the churn workload at a thrash-sized pool actually
    # demoted AND promoted, the promote-hit rate parses, and — asserted
    # inside the bench itself — the tier-on run is token-identical to
    # the tier-off engine with strictly more prefix hits
    ht = recs["gpt2_host_tier_decode_tokens_per_sec_per_chip"]
    assert ht["value"] > 0
    assert ht["unit"] == "tokens/s/chip"
    assert ht["host_tier_enabled"] is True
    assert ht["host_tier_budget_bytes"] > 0
    assert ht["host_tier_demotes"] > 0
    assert ht["host_tier_promotes"] > 0
    assert 0.0 < ht["host_tier_promote_hit_rate"] <= 1.0
    assert ht["evicted_pages"] > 0            # the pool really thrashed
    assert ht["prefill_tokens_skipped"] > 0

    # the async front-end's open-loop record (docs/frontend.md): TTFT /
    # TPOT percentiles + deadline accounting parse, and the adversarial
    # burst (slots pinned low-priority, high-priority arrival) actually
    # exercised the preempt/spill/resume path
    fe = recs["gpt2_frontend_decode_tokens_per_sec_per_chip"]
    assert fe["value"] > 0
    assert fe["gpt2_frontend_ttft_ms_p50"] > 0
    assert (fe["gpt2_frontend_ttft_ms_p95"]
            >= fe["gpt2_frontend_ttft_ms_p50"])
    assert fe["gpt2_frontend_tpot_ms_p50"] > 0
    assert (fe["gpt2_frontend_tpot_ms_p95"]
            >= fe["gpt2_frontend_tpot_ms_p50"])
    assert 0.0 <= fe["gpt2_frontend_deadline_miss_rate"] <= 1.0
    assert (fe["gpt2_frontend_deadline_misses"]
            <= fe["deadlined_requests"])
    assert fe["preemptions"] > 0
    assert fe["resumes"] > 0
    assert fe["peak_queue_depth"] >= 1
    assert fe["prefill_tokens_skipped"] > 0   # resume = a cache hit
    # pump pipeline attribution + recompile window (ISSUE 8): the
    # acceptance fields, present and sane
    assert fe["pump.bubble_ms"] >= 0.0
    assert fe["pump.dispatch_ready_ms_p50"] > 0
    assert fe["pump.host_work_ms_p50"] >= 0
    assert fe["jit.compiles"] >= 0
    assert fe["jit.trace_cache_misses"] >= 0
    assert fe["tpot_slo_misses"] >= 0 and 0.0 <= fe["slo_burn"] <= 1.0

    # the in-engine speculative record (ISSUE 13, docs/serving.md):
    # throughput parses, the self-draft run actually ran speculative
    # rounds, and acceptance telemetry exceeds 1 token per round —
    # token identity against the plain paged engine is asserted inside
    # the bench itself
    sp = recs["gpt2_spec_decode_tokens_per_sec_per_chip"]
    assert sp["value"] > 0
    assert sp["unit"] == "tokens/s/chip"
    assert sp["draft_len"] >= 1 and sp["self_draft"] is True
    assert sp["spec_rounds"] >= 1
    assert sp["spec_tokens"] >= sp["spec_rounds"]
    assert sp["mean_acceptance_len"] > 1.0
    assert sp["mean_acceptance_len"] <= sp["draft_len"] + 1
    assert sp["generated_tokens"] > 0

    # the chunked-prefill TTFT A/B (ISSUE 13, docs/frontend.md): both
    # variants' percentile fields parse, the chunk path engaged on the
    # long prompt (many chunks per chunked admission), and the bench
    # itself asserted token identity between the two runs — the p95
    # reduction is an on-chip number, not a CPU-smoke assert
    cp = recs["gpt2_frontend_chunked_ttft_ms_p95"]
    assert cp["value"] == cp["gpt2_frontend_chunked_ttft_ms_p95"]
    assert cp["gpt2_frontend_chunked_ttft_ms_p50"] > 0
    assert (cp["gpt2_frontend_chunked_ttft_ms_p95"]
            >= cp["gpt2_frontend_chunked_ttft_ms_p50"])
    assert cp["gpt2_frontend_monolithic_ttft_ms_p50"] > 0
    assert (cp["gpt2_frontend_monolithic_ttft_ms_p95"]
            >= cp["gpt2_frontend_monolithic_ttft_ms_p50"])
    assert cp["prefill_chunk"] == cp["page_size"]
    assert cp["chunked_prefills"] >= 1
    assert cp["prefill_chunks"] > cp["chunked_prefills"]

    # the APEX_TPU_METRICS_OUT artifact: a strict-JSON registry
    # snapshot holding the serving histograms
    with open(snap_path) as f:
        snap = json.load(f)
    hist_names = {h["name"] for h in snap["histograms"]}
    assert {"serving.ttft_ms", "serving.decode_step_ms",
            "serving.queue_wait_ms"} <= hist_names
    assert snap["source"] == "tpu_decode_bench"
