"""On-chip decode-throughput harvest: GPT-2 small autoregressive generation.

Runs on a TPU; refuses any other backend unless ``APEX_TPU_DECODE_SMOKE=1``
asks for the tiny-model mechanics check on the CPU. Every record carries
``platform``, ``device_kind`` and ``n_devices``, so a CPU smoke record cannot
pass for a chip measurement. Measures steady-state single-token decode steps/s
of `apex_tpu.models.generation.generate` on BASELINE config #4's GPT-2
small (beyond-reference: apex has no inference path, so this metric has
no reference analog — it documents the KV-cache design's throughput).

Method: jit two generate programs at the same prompt — one with
`max_new_tokens=1` (prefill + 1 step) and one with `N` steps — and take
``(N-1) * batch / (t_N - t_1)``: pure decode-step throughput with the
prefill and sampling epilogue differenced out. Greedy decode (argmax),
bf16 model, batch 8, prompt 128, N=128.

Emits one JSON line: {"metric": "gpt2_decode_tokens_per_sec_per_chip", ...}.

Also measures the SERVING path (apex_tpu/serving): a mixed-length request
set through the paged-KV continuous-batching engine, emitting a second
line {"metric": "gpt2_paged_decode_tokens_per_sec_per_chip", ...} with
the engine's decode-step count next to the steps lock-step generate would
have padded to — the Orca/vLLM win this harness exists to document.

The serving workloads are no longer inline generators: the mixed-length
and shared-system-prompt request sets are the scenario library's
``bench-mixed-length`` / ``bench-shared-prefix`` catalog entries
(apex_tpu/serving/scenarios, docs/scenarios.md), materialized from a
fixed seed — the bench keeps only the measurement loops and asserts.

After the paged line: the QUANTIZED KV-PAGE engine — the same workload
with ``kv_dtype='int8'`` (int8 pages + per-(page, kv_head) f32 scales,
dequant inside the kernel), emitting
{"metric": "gpt2_int8kv_paged_decode_tokens_per_sec_per_chip", ...}
with slot-capacity telemetry (``kv_pool.max_slots_for_pool_bytes`` at a
fixed pool-byte budget: int8 admits ~2x the slots); the smoke run
asserts per-request shapes and first tokens match the fp engine (full
token-level parity is tolerance-pinned in tests/test_quantized_kv.py)
and the >= 1.9x capacity ratio.

After the int8-KV line: the QUANTIZED WEIGHT-STREAMING engine — the
same workload with the int8 ``WeightPrecisionPolicy`` model (block
linears int8 + per-channel f32 scales, fused in-kernel dequant;
docs/serving.md "Quantized weight streaming"), emitting
{"metric": "gpt2_w8_paged_decode_tokens_per_sec_per_chip", ...} with
TTFT/TPOT percentiles and the weight-tree byte split; the smoke run
asserts per-request shapes, first-token identity vs the fp paged engine
(fixed-seed pin — prefill runs the quantized weights), and that the
quantized tree's bytes genuinely drop below the fp tree's.

Between the paged and prefix-cached lines: the TENSOR-PARALLEL paged
engine (serving/tp.py, docs/tp_serving.md) — the same mixed-length
workload through a tp=2 ``TensorParallelPagedEngine`` (head-sharded
pool + Megatron weight shards over a 2-device mesh), emitting
{"metric": "gpt2_tp2_paged_decode_tokens_per_sec_per_chip", ...} with
TTFT/TPOT percentiles; the smoke run asserts greedy token identity
against the single-chip engine. On one device the section cannot run and
says so in a record that carries no metric.

Third line: the PREFIX-CACHED serving path — a shared-system-prompt
workload (every request = one common header + a private tail, the
dominant multi-user pattern) through the engine with
``prefix_cache=True``, emitting
{"metric": "gpt2_prefix_cached_decode_tokens_per_sec_per_chip", ...}
with the radix-cache hit rate and prefill-tokens-skipped counters next to
the total. The smoke run asserts the reduction: every request past the
first concurrent wave must skip the full shared-header prefill.

After it: the TIERED KV POOL (docs/serving.md "Tiered KV pool") — the
catalogued ``host-tier-churn`` workload (more cacheable header pages
than the thrash-sized pool holds) through the engine with
``host_tier_bytes`` set, emitting
{"metric": "gpt2_host_tier_decode_tokens_per_sec_per_chip", ...} with
the demote/promote counters and promote-hit rate next to the total. The
smoke run asserts promotes > 0, strictly more prefix hits than the
tier-off engine at the same pool, and token identity vs tier-off.

Fourth line: the ASYNC FRONT-END (docs/frontend.md) — an open-loop
Poisson arrival stream with mixed priorities and TTFT deadlines through
``ServingFrontend``, closed by an adversarial burst that forces the
preemption/spill/resume path, emitting
{"metric": "gpt2_frontend_decode_tokens_per_sec_per_chip", ...} with
``gpt2_frontend_ttft/tpot`` percentiles and deadline-miss counts from
the metrics registry plus preemption/resume counters. The smoke run
asserts preemptions > 0 and resumes > 0 under the burst.

Last two lines (the s>1 paged query block, docs/serving.md): the
IN-ENGINE SPECULATIVE path — the mixed-length workload with a
self-draft (acceptance ceiling k), emitting
{"metric": "gpt2_spec_decode_tokens_per_sec_per_chip", ...} with
round/acceptance telemetry, smoke-asserted token-identical to the plain
paged engine — and the CHUNKED-PREFILL TTFT A/B — one long prompt plus
short traffic through monolithic vs ``prefill_chunk`` admission,
emitting {"metric": "gpt2_frontend_chunked_ttft_ms_p95", ...} with both
variants' TTFT percentiles so the ledger banks the tail reduction.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def time_best(fn, repeats=3):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def emit(rec: dict) -> None:
    """Print one record, stamped with the device it was taken on."""
    dev = jax.devices()[0]
    print(json.dumps({**rec, "platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "n_devices": len(jax.devices())}), flush=True)


def main():
    import functools
    import os

    from apex_tpu.models.generation import generate
    from apex_tpu.models.gpt import GPTModel, gpt2_small_config, gpt_tiny_config

    if os.environ.get("APEX_TPU_DECODE_SMOKE") == "1":
        # CPU smoke: interpret-mode flash prefill at GPT-2 shapes is far
        # too slow; prove the harness mechanics on the tiny model instead.
        # n_new=16 keeps the differenced step window wide enough that
        # scheduler noise can't zero the speedup ratio
        jax.config.update("jax_platforms", "cpu")
        batch, prompt_len, n_new = 2, 8, 16
        cfg = gpt_tiny_config()
    else:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SystemExit(
                f"tpu_decode_bench: measures on a TPU; jax found "
                f"{dev.platform} ({dev.device_kind}). "
                f"APEX_TPU_DECODE_SMOKE=1 runs the CPU mechanics check.")
        batch, prompt_len, n_new = 8, 128, 128
        cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, prompt_len)),
                         jnp.int32)
    v = model.init(jax.random.PRNGKey(0), prompt[:, :8])

    def measure(m, variables):
        gen_1 = jax.jit(functools.partial(generate, m, max_new_tokens=1,
                                          max_len=prompt_len + n_new,
                                          axis_name="unbound"))
        gen_n = jax.jit(functools.partial(generate, m,
                                          max_new_tokens=n_new,
                                          max_len=prompt_len + n_new,
                                          axis_name="unbound"))
        jax.block_until_ready(gen_1(variables, prompt))   # compile
        jax.block_until_ready(gen_n(variables, prompt))
        t1 = time_best(lambda: gen_1(variables, prompt))
        tn = time_best(lambda: gen_n(variables, prompt))
        steps = n_new - 1
        return steps * batch / max(tn - t1, 1e-9), t1, tn, steps

    toks_per_s, t1, tn, steps = measure(model, v)

    # int8 W8A8 serving pass (docs/quantization.md): same weights,
    # post-training-quantized — decode is weight-fetch bound, so this
    # measures the HBM-bandwidth story directly
    import dataclasses

    from apex_tpu.models.quantize import quantize_model_params

    qmodel = GPTModel(dataclasses.replace(cfg, quantize_int8=True))
    qparams = quantize_model_params(qmodel, v, prompt[:, :8])
    q_toks_per_s, _, _, _ = measure(qmodel, {"params": qparams})

    rec = {
        "metric": "gpt2_decode_tokens_per_sec_per_chip",
        "value": round(toks_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "batch": batch, "prompt_len": prompt_len, "new_tokens": n_new,
        "step_ms": round(1e3 * (tn - t1) / steps, 3),
        "prefill_plus_one_s": round(t1, 3),
        "int8_tokens_per_sec": round(q_toks_per_s, 1),
        "int8_speedup": round(q_toks_per_s / max(toks_per_s, 1e-9), 3),
    }
    emit(rec)

    # --- paged continuous-batching serving metric ---------------------------
    # the workload DEFINITION lives in the scenario library
    # (apex_tpu/serving/scenarios, docs/scenarios.md): the bench
    # materializes the catalogued ``bench-mixed-length`` trace (seeded —
    # reproducible request set) and keeps only the measurement loop here
    import dataclasses as _dc

    from apex_tpu.serving import PagedDecodeEngine, Request
    from apex_tpu.serving.scenarios import (Lengths, materialize,
                                            scenario_spec,
                                            trace_requests)

    smoke = os.environ.get("APEX_TPU_DECODE_SMOKE") == "1"
    if smoke:
        ml_spec = scenario_spec("bench-mixed-length", seed=1)
    else:
        base = scenario_spec("bench-mixed-length", seed=1)
        ml_spec = _dc.replace(
            base, n_requests=3 * batch,
            prompt_lens=Lengths(kind="uniform", lo=32, hi=128),
            output_lens=Lengths(kind="uniform", lo=32, hi=128),
            engine=_dc.replace(base.engine, model="gpt2-small",
                               num_slots=batch, page_size=16))
    num_slots, page_size = ml_spec.engine.num_slots, \
        ml_spec.engine.page_size
    ml_trace = materialize(ml_spec)
    requests = trace_requests(ml_trace)
    n_req = len(requests)
    prompt_lens = [len(e.prompt) for e in ml_trace.events]
    new_tokens = [e.max_new_tokens for e in ml_trace.events]

    engine = PagedDecodeEngine(model, v, num_slots=num_slots,
                               page_size=page_size)
    engine.run(requests)                                 # compile + warm
    t0 = time.perf_counter()
    outs, stats = engine.run(requests)
    elapsed = time.perf_counter() - t0
    gen_tokens = int(sum(o.shape[0] for o in outs))
    # lock-step at the same slot capacity pads every batch of num_slots
    # requests to the batch's longest token budget
    order = sorted(range(n_req), key=lambda i: -int(new_tokens[i]))
    lockstep_steps = sum(
        max(int(new_tokens[i]) for i in order[g:g + num_slots])
        for g in range(0, n_req, num_slots))
    if smoke and stats["decode_steps"] >= lockstep_steps:
        raise SystemExit(
            f"continuous batching regressed: {stats['decode_steps']} engine "
            f"steps vs {lockstep_steps} lock-step steps")
    prec = {
        "metric": "gpt2_paged_decode_tokens_per_sec_per_chip",
        "value": round(gen_tokens / max(elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_req, "num_slots": num_slots, "page_size": page_size,
        "prompt_lens": [int(x) for x in prompt_lens],
        "new_tokens": [int(x) for x in new_tokens],
        "generated_tokens": gen_tokens,
        "decode_steps": stats["decode_steps"],
        "lockstep_steps": lockstep_steps,
        "step_savings": round(1.0 - stats["decode_steps"]
                              / max(lockstep_steps, 1), 3),
        # per-request latency percentiles from the engine's span tracer
        # (docs/observability.md): TTFT = enqueue -> first token,
        # decode-step = per-step wall time at the sync boundary
        "gpt2_paged_decode_ttft_ms_p50": round(stats["ttft_ms_p50"], 3),
        "gpt2_paged_decode_ttft_ms_p95": round(stats["ttft_ms_p95"], 3),
        "decode_step_ms_p50": round(stats["decode_step_ms_p50"], 3),
        "decode_step_ms_p95": round(stats["decode_step_ms_p95"], 3),
        "queue_wait_ms_p50": round(stats["queue_wait_ms_p50"], 3),
        "tpot_ms_p50": round(stats["tpot_ms_p50"], 3),
    }
    emit(prec)

    # --- quantized (int8) KV-page serving metric ----------------------------
    # the SAME mixed-length workload through the engine with
    # ``kv_dtype='int8'`` (docs/serving.md "Quantized KV pages"): K/V
    # pages live in the pool as int8 with per-(page, kv_head) f32 scales
    # and dequantize inside the paged-attention kernel. The headline
    # rides next to the slot-capacity telemetry — at a FIXED pool-byte
    # budget the int8 pool admits ~2x the slots of the bf16 pool
    # (kv_pool.max_slots_for_pool_bytes), which is the actual win:
    # more concurrent sequences per chip, not a faster single step.
    from apex_tpu.serving import kv_pool as _kvp

    q_engine = PagedDecodeEngine(model, v, num_slots=num_slots,
                                 page_size=page_size, kv_dtype="int8")
    q_engine.run(requests)                               # compile + warm
    t0 = time.perf_counter()
    q_outs, q_stats = q_engine.run(requests)
    q_elapsed = time.perf_counter() - t0
    q_tokens = int(sum(o.shape[0] for o in q_outs))
    if smoke:
        # NOT exact token identity: quantization legitimately perturbs
        # logits by more than a tiny random-init model's argmax gaps
        # (the tolerance-pinned parity lives in
        # tests/test_quantized_kv.py). What IS exact: request shapes,
        # and each request's FIRST token — it comes off the prefill
        # forward pass's own logits, before any quantized-pool read
        for i, (a, b) in enumerate(zip(outs, q_outs)):
            a, b = np.asarray(a), np.asarray(b)
            if a.shape != b.shape:
                raise SystemExit(
                    f"int8-kv engine changed request {i}'s output shape: "
                    f"{a.shape} vs fp {b.shape}")
            if a.shape[0] and a[0] != b[0]:
                raise SystemExit(
                    f"int8-kv engine flipped request {i}'s FIRST token "
                    f"({b[0]} vs fp {a[0]}) — prefill logits never touch "
                    f"the quantized pool, so this is a real bug")
    # slot capacity at a fixed budget: what one fp pool's bytes would
    # buy in each dtype (pages_per_slot from the bench's own shapes)
    pps = max((max(prompt_lens) + max(new_tokens) + page_size - 1)
              // page_size, 1)
    fp_pool_bytes = _kvp.page_bytes(cfg, page_size) * (
        num_slots * pps + 1)
    fp_cap = _kvp.max_slots_for_pool_bytes(cfg, fp_pool_bytes,
                                           pages_per_slot=pps,
                                           page_size=page_size)
    q_cap = _kvp.max_slots_for_pool_bytes(cfg, fp_pool_bytes,
                                          pages_per_slot=pps,
                                          page_size=page_size,
                                          kv_dtype="int8")
    if smoke and q_cap < 1.9 * fp_cap:
        raise SystemExit(
            f"int8-kv slot capacity regressed: {q_cap} slots vs "
            f"{fp_cap} fp slots at a fixed pool budget (< 1.9x)")
    q_rec = {
        "metric": "gpt2_int8kv_paged_decode_tokens_per_sec_per_chip",
        "value": round(q_tokens / max(q_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_req, "num_slots": num_slots, "page_size": page_size,
        "kv_dtype": "int8",
        "generated_tokens": q_tokens,
        "decode_steps": q_stats["decode_steps"],
        "fp_tokens_per_sec": prec["value"],
        # capacity telemetry: slots a fixed pool-byte budget admits
        "pool_bytes_budget": int(fp_pool_bytes),
        "pages_per_slot": int(pps),
        "fp_slot_capacity": int(fp_cap),
        "int8_slot_capacity": int(q_cap),
        "slot_capacity_ratio": round(q_cap / max(fp_cap, 1), 3),
        "page_bytes_fp": int(_kvp.page_bytes(cfg, page_size)),
        "page_bytes_int8": int(_kvp.page_bytes(cfg, page_size,
                                               kv_dtype="int8")),
        "gpt2_int8kv_paged_decode_ttft_ms_p50": round(
            q_stats["ttft_ms_p50"], 3),
        "gpt2_int8kv_paged_decode_ttft_ms_p95": round(
            q_stats["ttft_ms_p95"], 3),
        "tpot_ms_p50": round(q_stats["tpot_ms_p50"], 3),
    }
    emit(q_rec)

    # --- quantized WEIGHT streaming serving metric --------------------------
    # the SAME mixed-length workload through the paged engine over the
    # int8-policy model (docs/serving.md "Quantized weight streaming"):
    # every block linear's weight lives in HBM as int8 with a per-channel
    # f32 scale and dequantizes inside the fused dequant-matmul kernel,
    # next to the contraction — decode is weight-fetch bound, so the
    # per-step weight stream roughly halves (cost.decode.w8.*). Unlike
    # the KV record above, prefill itself runs the quantized weights, so
    # the first-token identity asserted here is an empirical fixed-seed
    # pin (deterministic per build), not a structural guarantee; the
    # tolerance-pinned parity lives in tests/test_quantized_weights.py.
    def _tree_bytes(tree):
        return int(sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree)))

    w8_engine = PagedDecodeEngine(qmodel, {"params": qparams},
                                  num_slots=num_slots, page_size=page_size)
    w8_engine.run(requests)                              # compile + warm
    t0 = time.perf_counter()
    w8_outs, w8_stats = w8_engine.run(requests)
    w8_elapsed = time.perf_counter() - t0
    w8_tokens = int(sum(o.shape[0] for o in w8_outs))
    fp_weight_bytes = _tree_bytes(v["params"])
    w8_weight_bytes = _tree_bytes(qparams)
    if smoke:
        for i, (a, b) in enumerate(zip(outs, w8_outs)):
            a, b = np.asarray(a), np.asarray(b)
            if a.shape != b.shape:
                raise SystemExit(
                    f"w8 engine changed request {i}'s output shape: "
                    f"{b.shape} vs fp {a.shape}")
            if a.shape[0] and a[0] != b[0]:
                raise SystemExit(
                    f"w8 engine flipped request {i}'s FIRST token "
                    f"({b[0]} vs fp {a[0]}) — the fixed-seed first-token "
                    f"pin regressed (tests/test_quantized_weights.py "
                    f"holds the tolerance parity)")
        if w8_weight_bytes >= fp_weight_bytes:
            raise SystemExit(
                f"w8 weight stream regressed: {w8_weight_bytes} quantized "
                f"tree bytes >= {fp_weight_bytes} fp bytes")
    w8_rec = {
        "metric": "gpt2_w8_paged_decode_tokens_per_sec_per_chip",
        "value": round(w8_tokens / max(w8_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_req, "num_slots": num_slots, "page_size": page_size,
        "weight_dtype": "int8",
        "generated_tokens": w8_tokens,
        "decode_steps": w8_stats["decode_steps"],
        "fp_tokens_per_sec": prec["value"],
        # streaming telemetry: each tree's bytes at its ACTUAL leaf
        # dtypes (scales included) — the gpt2s ratio is pinned exactly
        # by the cost model (cost.decode.w8.weight_bytes_ratio_vs_bf16)
        "fp_weight_bytes": fp_weight_bytes,
        "w8_weight_bytes": w8_weight_bytes,
        "weight_bytes_ratio_vs_fp": round(
            w8_weight_bytes / max(fp_weight_bytes, 1), 3),
        "gpt2_w8_paged_decode_ttft_ms_p50": round(
            w8_stats["ttft_ms_p50"], 3),
        "gpt2_w8_paged_decode_ttft_ms_p95": round(
            w8_stats["ttft_ms_p95"], 3),
        "tpot_ms_p50": round(w8_stats["tpot_ms_p50"], 3),
    }
    emit(w8_rec)

    # --- tensor-parallel paged serving metric -------------------------------
    # the SAME mixed-length workload through a tp=2
    # TensorParallelPagedEngine (serving/tp.py, docs/tp_serving.md): the
    # pool's kv heads and the Megatron weight shards split over a
    # 2-device mesh, the scheduler/block tables stay replicated, and
    # greedy outputs must be token-identical to the single-chip engine
    # above (asserted in smoke). The headline divides by tp — per-CHIP
    # throughput, comparable against the single-chip paged number
    # (aggregate bandwidth scales with the mesh; per-chip should hold
    # roughly steady once the model is big enough to stream).
    if len(jax.devices()) >= 2:
        from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                         shard_model_variables, tp_mesh)

        tp = 2
        tp_cfg = dataclasses.replace(cfg, tensor_parallel_size=tp)
        tp_model = GPTModel(tp_cfg)
        tp_m = tp_mesh(tp)
        tp_vars, _ = shard_model_variables(tp_model, v, tp_m)
        tp_engine = TensorParallelPagedEngine(
            tp_model, tp_vars, mesh=tp_m, num_slots=num_slots,
            page_size=page_size)
        tp_engine.run(requests)                          # compile + warm
        t0 = time.perf_counter()
        tp_outs, tp_stats = tp_engine.run(requests)
        tp_elapsed = time.perf_counter() - t0
        tp_tokens = int(sum(o.shape[0] for o in tp_outs))
        if smoke:
            for i, (a, b) in enumerate(zip(outs, tp_outs)):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    raise SystemExit(
                        f"tp=2 greedy decode diverged from the "
                        f"single-chip engine on request {i}: "
                        f"{np.asarray(a)[:8]}... vs {np.asarray(b)[:8]}...")
        tp_rec = {
            "metric": "gpt2_tp2_paged_decode_tokens_per_sec_per_chip",
            "value": round(tp_tokens / max(tp_elapsed, 1e-9) / tp, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": 0.0,
            "tp_world": tp_stats["tp_world"],
            "requests": n_req, "num_slots": num_slots,
            "page_size": page_size,
            "generated_tokens": tp_tokens,
            "decode_steps": tp_stats["decode_steps"],
            "aggregate_tokens_per_sec": round(
                tp_tokens / max(tp_elapsed, 1e-9), 1),
            "gpt2_tp2_paged_decode_ttft_ms_p50": round(
                tp_stats["ttft_ms_p50"], 3),
            "gpt2_tp2_paged_decode_ttft_ms_p95": round(
                tp_stats["ttft_ms_p95"], 3),
            "gpt2_tp2_paged_decode_tpot_ms_p50": round(
                tp_stats["tpot_ms_p50"], 3),
            "gpt2_tp2_paged_decode_tpot_ms_p95": round(
                tp_stats["tpot_ms_p95"], 3),
            "decode_step_ms_p50": round(
                tp_stats["decode_step_ms_p50"], 3),
            }
        emit(tp_rec)
    else:
        # one device cannot run the tp=2 engine: say so, under no metric
        emit({"section": "gpt2_tp2_paged_decode",
              "not_run": "needs >= 2 devices"})

    # --- shared-prefix (radix) cached serving metric ------------------------
    # every request: one shared system header + a private tail (the
    # catalogued ``bench-shared-prefix`` scenario — one tenant whose
    # deterministic system prompt every request shares). Requests
    # admitted after the first concurrent wave point their block tables
    # at the header's cached pages and prefill only the tail.
    from apex_tpu.serving.scenarios import Tenant

    if smoke:
        pc_spec = scenario_spec("bench-shared-prefix", seed=2)
    else:
        pc_base = scenario_spec("bench-shared-prefix", seed=2)
        pc_spec = _dc.replace(
            pc_base, n_requests=3 * batch,
            prompt_lens=Lengths(kind="uniform", lo=16, hi=64),
            output_lens=Lengths(kind="uniform", lo=32, hi=128),
            tenants=(Tenant("shared",
                            system_prompt_tokens=16 * 16),),
            engine=_dc.replace(pc_base.engine, model="gpt2-small",
                               num_slots=num_slots, page_size=16))
    pc_slots = pc_spec.engine.num_slots
    sys_len = pc_spec.tenants[0].system_prompt_tokens
    pc_trace = materialize(pc_spec)
    pc_requests = trace_requests(pc_trace)
    n_pc = len(pc_requests)
    pc_tails = [len(e.prompt) - sys_len for e in pc_trace.events]
    pc_new = [e.max_new_tokens for e in pc_trace.events]

    pc_engine = PagedDecodeEngine(model, v, num_slots=pc_slots,
                                  page_size=pc_spec.engine.page_size,
                                  prefix_cache=True)
    pc_engine.run(pc_requests)          # cold: populate the radix cache
    pc_engine.run(pc_requests)          # warm: compile the hit-depth
    #                                     admission programs the timed
    #                                     (steady-state) run replays
    t0 = time.perf_counter()
    pc_outs, pc_stats = pc_engine.run(pc_requests)
    pc_elapsed = time.perf_counter() - t0
    pc_tokens = int(sum(o.shape[0] for o in pc_outs))
    if smoke:
        # warm-cache floor (pc_stats is the third run): EVERY request's
        # full prompt is already cached, so every one must hit and at
        # least skip the shared header. (The cold-run floor is weaker:
        # inserts happen at retirement, so the first pc_slots-wide
        # concurrent wave misses — (n_pc - pc_slots) * sys_len.)
        floor = n_pc * sys_len
        if pc_stats["prefill_tokens_skipped"] < floor:
            raise SystemExit(
                f"prefix cache regressed: skipped "
                f"{pc_stats['prefill_tokens_skipped']} prefill tokens < "
                f"the {floor} the warm shared header guarantees")
        if pc_stats["prefix_hits"] < n_pc:
            raise SystemExit(
                f"prefix cache regressed: {pc_stats['prefix_hits']}/{n_pc} "
                f"hits on a warm shared-system-prompt workload")
    pc_rec = {
        "metric": "gpt2_prefix_cached_decode_tokens_per_sec_per_chip",
        "value": round(pc_tokens / max(pc_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_pc, "num_slots": pc_slots, "page_size": page_size,
        "shared_prefix_tokens": sys_len,
        "tail_lens": [int(x) for x in pc_tails],
        "new_tokens": [int(x) for x in pc_new],
        "generated_tokens": pc_tokens,
        # engine counters (the serving-observability tier): the third —
        # timed, warm-cache — run's stats, i.e. steady-state hit behavior
        **{k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in pc_stats.items()},
    }
    emit(pc_rec)

    # --- tiered (host-RAM spill) KV pool serving metric ---------------------
    # the catalogued ``host-tier-churn`` workload (docs/serving.md
    # "Tiered KV pool"): more cacheable header pages than the pool
    # holds, so tier-off every revisited header re-prefills while
    # tier-on demotes it to host RAM and promotes it back over the host
    # link. The smoke run asserts promotes actually fired AND that the
    # tier changed no output token vs the tier-off engine at the same
    # thrash-sized pool.
    from apex_tpu.serving.scenarios.tenants import churn_tenants

    if smoke:
        ht_spec = scenario_spec("host-tier-churn", seed=3)
    else:
        ht_base = scenario_spec("host-tier-churn", seed=3)
        ht_spec = _dc.replace(
            ht_base, n_requests=3 * batch,
            output_lens=Lengths(kind="uniform", lo=16, hi=64),
            tenants=churn_tenants(8, 4, 16),
            engine=_dc.replace(ht_base.engine, model="gpt2-small",
                               num_slots=num_slots, page_size=16,
                               num_pages=24, host_tier_bytes=1 << 30))
    ht_es = ht_spec.engine
    ht_trace = materialize(ht_spec)
    ht_requests = trace_requests(ht_trace)
    n_ht = len(ht_requests)

    ht_engine = PagedDecodeEngine(model, v, num_slots=ht_es.num_slots,
                                  page_size=ht_es.page_size,
                                  num_pages=ht_es.num_pages,
                                  prefix_cache=True,
                                  host_tier_bytes=ht_es.host_tier_bytes)
    ht_engine.run(ht_requests)          # compile + populate tier
    t0 = time.perf_counter()
    ht_outs, ht_stats = ht_engine.run(ht_requests)
    ht_elapsed = time.perf_counter() - t0
    ht_tokens = int(sum(o.shape[0] for o in ht_outs))
    tier = ht_engine.host_tier.stats()
    if smoke:
        if tier["host_tier_promotes"] < 1:
            raise SystemExit(
                "host tier regressed: the churn workload never promoted "
                f"a demoted page ({tier})")
        off_engine = PagedDecodeEngine(model, v,
                                       num_slots=ht_es.num_slots,
                                       page_size=ht_es.page_size,
                                       num_pages=ht_es.num_pages,
                                       prefix_cache=True)
        off_engine.run(ht_requests)
        off_outs, off_stats = off_engine.run(ht_requests)
        for i, (a, b) in enumerate(zip(ht_outs, off_outs)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise SystemExit(
                    f"host tier regressed: request {i} diverged from the "
                    "tier-off engine (promote must be bit-stable)")
        if ht_stats["prefix_hits"] <= off_stats["prefix_hits"]:
            raise SystemExit(
                f"host tier regressed: {ht_stats['prefix_hits']} hits "
                f"tier-on <= {off_stats['prefix_hits']} tier-off on the "
                "churn workload")
    ht_rec = {
        "metric": "gpt2_host_tier_decode_tokens_per_sec_per_chip",
        "value": round(ht_tokens / max(ht_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_ht, "num_slots": ht_es.num_slots,
        "page_size": ht_es.page_size, "num_pages": ht_es.num_pages,
        "host_tier_budget_bytes": ht_es.host_tier_bytes,
        "generated_tokens": ht_tokens,
        # lifetime tier counters (both runs): the churn evidence
        "host_tier_demotes": tier["host_tier_demotes"],
        "host_tier_promotes": tier["host_tier_promotes"],
        "host_tier_promote_hit_rate":
            round(tier["host_tier_promote_hit_rate"], 3),
        "host_tier_resident_bytes": tier["host_tier_resident_bytes"],
        **{k: (round(v, 3) if isinstance(v, float) else v)
           for k, v in ht_stats.items()},
    }
    emit(ht_rec)

    # --- open-loop async frontend workload (Poisson arrivals) ---------------
    # the serving FRONT-END under an open arrival stream (docs/frontend.md):
    # requests are submitted at Poisson arrival times regardless of
    # completion (open loop — queueing is real, unlike the closed run()
    # batches above), with mixed priorities and TTFT deadlines, followed
    # by an adversarial burst (slots pinned by low-priority work, then a
    # high-priority arrival) that FORCES the preemption/spill/resume
    # path. Emits gpt2_frontend_* TTFT/TPOT/deadline-miss fields from the
    # metrics registry; the smoke run asserts preemptions actually fired.
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.policy import PriorityDeadlinePolicy

    wl3 = np.random.default_rng(3)
    if smoke:
        fe_slots, n_fe = 2, 8
        fe_prompts = wl3.integers(8, 49, n_fe)
        fe_new = wl3.integers(6, 15, n_fe)
        mean_gap_s, fe_deadline_ms = 0.004, 2000.0
        burst_prompt, burst_new = 24, 20
    else:
        fe_slots, n_fe = num_slots, 3 * batch
        fe_prompts = wl3.integers(32, 129, n_fe)
        fe_new = wl3.integers(32, 129, n_fe)
        mean_gap_s, fe_deadline_ms = 0.01, 500.0
        burst_prompt, burst_new = 128, 96
    arrivals = np.cumsum(wl3.exponential(mean_gap_s, n_fe))
    fe_priorities = wl3.integers(0, 3, n_fe)
    fe_reqs = [
        Request(prompt=wl3.integers(0, cfg.vocab_size, int(L)).astype(
            np.int32), max_new_tokens=int(m), priority=int(p),
            deadline_ms=fe_deadline_ms if p == 2 else None)
        for L, m, p in zip(fe_prompts, fe_new, fe_priorities)]

    fe_engine = PagedDecodeEngine(model, v, num_slots=fe_slots,
                                  page_size=page_size, prefix_cache=True)
    fe_engine.run(fe_reqs)      # warm: compile buckets, seed the cache
    fe = ServingFrontend(fe_engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    handles = []
    t0 = time.perf_counter()
    i = 0
    while i < n_fe:
        now = time.perf_counter() - t0
        while i < n_fe and arrivals[i] <= now:
            handles.append(fe.submit(fe_reqs[i], request_id=i))
            i += 1
        if not fe.pump() and i < n_fe:
            # idle before the next arrival — nap up to it (bounded so a
            # late-arriving burst still sees a responsive pump)
            time.sleep(min(max(arrivals[i] - (time.perf_counter() - t0),
                               0.0), 0.002))
    fe.drain()
    # adversarial burst: pin every slot with low-priority long work,
    # give it a little progress, then land a high-priority deadline
    # arrival — with no vacancy the policy MUST preempt-and-spill
    burst_low = [
        Request(prompt=wl3.integers(0, cfg.vocab_size, burst_prompt
                                    ).astype(np.int32),
                max_new_tokens=burst_new, priority=0)
        for _ in range(fe_slots)]
    for j, r in enumerate(burst_low):
        handles.append(fe.submit(r, request_id=n_fe + j))
    while fe.queue_depth:
        fe.pump()
    for _ in range(3):
        fe.pump()
    handles.append(fe.submit(
        Request(prompt=wl3.integers(0, cfg.vocab_size, burst_prompt
                                    ).astype(np.int32),
                max_new_tokens=max(burst_new // 4, 2), priority=9,
                deadline_ms=fe_deadline_ms),
        request_id=n_fe + fe_slots))
    fe.drain()
    fe_elapsed = time.perf_counter() - t0
    fe_stats = fe.stats()
    fe_tokens = int(sum(h.result().shape[0] for h in handles))
    n_deadlined = sum(1 for r in fe_reqs if r.deadline_ms is not None) + 1
    if smoke and fe_stats["preemptions"] < 1:
        raise SystemExit(
            "frontend preemption regressed: the adversarial burst (all "
            "slots pinned low-priority, high-priority arrival, "
            "preempt_on_priority policy) produced 0 preemptions")
    if smoke and fe_stats["resumes"] < 1:
        raise SystemExit("frontend resume regressed: preempted work was "
                         "never resumed")
    fe_rec = {
        "metric": "gpt2_frontend_decode_tokens_per_sec_per_chip",
        "value": round(fe_tokens / max(fe_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_fe, "num_slots": fe_slots, "page_size": page_size,
        "open_loop_mean_gap_ms": round(mean_gap_s * 1e3, 3),
        "deadline_ms": fe_deadline_ms,
        "deadlined_requests": n_deadlined,
        "generated_tokens": fe_tokens,
        # TTFT/TPOT percentiles + deadline misses, from the instrument
        # registry (serving.* histograms/counters, docs/observability.md)
        "gpt2_frontend_ttft_ms_p50": round(fe_stats["ttft_ms_p50"], 3),
        "gpt2_frontend_ttft_ms_p95": round(fe_stats["ttft_ms_p95"], 3),
        "gpt2_frontend_tpot_ms_p50": round(fe_stats["tpot_ms_p50"], 3),
        "gpt2_frontend_tpot_ms_p95": round(fe_stats["tpot_ms_p95"], 3),
        "gpt2_frontend_deadline_misses": fe_stats["deadline_misses"],
        "gpt2_frontend_deadline_miss_rate": round(
            fe_stats["deadline_misses"] / max(n_deadlined, 1), 3),
        "preemptions": fe_stats["preemptions"],
        "resumes": fe_stats["resumes"],
        "peak_queue_depth": fe_stats["peak_queue_depth"],
        "prefix_hits": fe_stats["prefix_hits"],
        "prefill_tokens_skipped": fe_stats["prefill_tokens_skipped"],
        # pump pipeline attribution + recompile window (PR 8,
        # docs/observability.md): bubble_ms ≈ 0 means the double-buffered
        # host work is actually hidden behind the decode chunks;
        # jit.compiles during the measured window should be ~0 after the
        # warm run (a recompile storm here is a served-latency cliff)
        "pump.bubble_ms": round(fe_stats["pump.bubble_ms"], 3),
        "pump.host_work_ms_p50": round(
            fe_stats.get("pump.host_work_ms_p50", 0.0), 3),
        "pump.dispatch_ready_ms_p50": round(
            fe_stats.get("pump.dispatch_ready_ms_p50", 0.0), 3),
        "jit.compiles": fe_stats["jit.compiles"],
        "jit.trace_cache_misses": fe_stats["jit.trace_cache_misses"],
        "tpot_slo_misses": fe_stats["tpot_slo_misses"],
        "slo_burn": round(fe_stats["slo_burn"], 3),
    }
    emit(fe_rec)

    # --- in-engine speculative decode metric --------------------------------
    # the SAME mixed-length workload through the engine's speculative
    # mode (docs/serving.md): every step drafts ``draft_len`` tokens per
    # slot through a draft pool and verifies the block in ONE
    # s = draft_len + 1 paged target step. SELF-DRAFT here (draft =
    # target): acceptance hits the ceiling k = draft_len + 1, so this
    # measures the mechanism's best case — a real small draft lands
    # mean acceptance somewhere in 1..k and scales the win by the
    # cost model's per-acceptance split (cost.spec_decode.*). The smoke
    # run asserts greedy token identity against the non-speculative
    # paged engine and that acceptance telemetry actually exceeds 1.
    spec_draft_len = 3
    spec_engine = PagedDecodeEngine(model, v, num_slots=num_slots,
                                    page_size=page_size,
                                    draft_model=model, draft_variables=v,
                                    draft_len=spec_draft_len)
    spec_engine.run(requests)                            # compile + warm
    t0 = time.perf_counter()
    spec_outs, spec_stats = spec_engine.run(requests)
    spec_elapsed = time.perf_counter() - t0
    spec_gen = int(sum(o.shape[0] for o in spec_outs))
    if smoke:
        for i, (a, b) in enumerate(zip(outs, spec_outs)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise SystemExit(
                    f"speculative decode diverged from the greedy paged "
                    f"engine on request {i}: {np.asarray(a)[:8]}... vs "
                    f"{np.asarray(b)[:8]}...")
        if spec_stats["mean_acceptance_len"] <= 1.0:
            raise SystemExit(
                f"speculative acceptance regressed: self-draft mean "
                f"acceptance {spec_stats['mean_acceptance_len']} <= 1.0 "
                f"(every round should accept the whole block)")
    spec_rec = {
        "metric": "gpt2_spec_decode_tokens_per_sec_per_chip",
        "value": round(spec_gen / max(spec_elapsed, 1e-9), 1),
        "unit": "tokens/s/chip",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": n_req, "num_slots": num_slots, "page_size": page_size,
        "draft_len": spec_draft_len, "self_draft": True,
        "generated_tokens": spec_gen,
        "decode_steps": spec_stats["decode_steps"],
        "spec_rounds": spec_stats["spec_rounds"],
        "spec_tokens": spec_stats["spec_tokens"],
        "mean_acceptance_len": round(spec_stats["mean_acceptance_len"], 3),
        "paged_tokens_per_sec": prec["value"],
    }
    emit(spec_rec)

    # --- chunked-prefill TTFT re-measure ------------------------------------
    # the frontend-TTFT claim of docs/frontend.md as an A/B: one long
    # prompt plus a tail of short ones through two otherwise-identical
    # engines — monolithic admission (the long prefill runs whole
    # between two decode chunks) vs ``prefill_chunk=page_size``
    # (Sarathi-style: the long prompt enters in page-sized pieces
    # interleaved with everyone else's decode). Chunking bounds the
    # pause any single admission can inject, so the SHORT requests'
    # TTFT tail (p95) is the number that moves. The smoke run asserts
    # the chunk path actually engaged and that both runs are
    # greedy token-identical; the p95 reduction itself is only
    # meaningful on-chip (CPU smoke timing is scheduler noise).
    wl4 = np.random.default_rng(4)
    if smoke:
        cp_slots, n_short = 2, 6
        cp_long, cp_short, cp_new = 61, 6, 8
    else:
        cp_slots, n_short = num_slots, 3 * batch
        cp_long, cp_short, cp_new = 512, 24, 32
    cp_reqs = [Request(prompt=wl4.integers(0, cfg.vocab_size, cp_long
                                           ).astype(np.int32),
                       max_new_tokens=cp_new)]
    cp_reqs += [Request(prompt=wl4.integers(0, cfg.vocab_size, cp_short
                                            ).astype(np.int32),
                        max_new_tokens=cp_new) for _ in range(n_short)]

    def ttft_ab(chunk):
        eng = PagedDecodeEngine(
            model, v, num_slots=cp_slots, page_size=page_size,
            prefill_chunk=page_size if chunk else None)
        eng.run(cp_reqs)                                 # compile + warm
        ab = ServingFrontend(eng)
        hs = [ab.submit(r, request_id=j)
              for j, r in enumerate(cp_reqs)]           # all arrive at t0
        ab.drain()
        return [np.asarray(h.result()) for h in hs], ab.stats()

    mono_outs, mono_stats = ttft_ab(chunk=False)
    ck_outs, ck_stats = ttft_ab(chunk=True)
    if smoke:
        for i, (a, b) in enumerate(zip(mono_outs, ck_outs)):
            if not np.array_equal(a, b):
                raise SystemExit(
                    f"chunked prefill diverged from monolithic admission "
                    f"on request {i}: {a[:8]}... vs {b[:8]}...")
        if ck_stats["chunked_prefills"] < 1:
            raise SystemExit(
                "chunked prefill never engaged: the long prompt should "
                "have been admitted through the chunk path")
        if ck_stats["prefill_chunks"] <= ck_stats["chunked_prefills"]:
            raise SystemExit(
                f"chunked prefill degenerate: {ck_stats['prefill_chunks']} "
                f"chunks for {ck_stats['chunked_prefills']} chunked "
                f"admissions — the long prompt should span many chunks")
    cp_rec = {
        "metric": "gpt2_frontend_chunked_ttft_ms_p95",
        "value": round(ck_stats["ttft_ms_p95"], 3),
        "unit": "ms",
        "vs_baseline": 0.0,  # no reference analog (apex ships no inference)
        "requests": len(cp_reqs), "num_slots": cp_slots,
        "page_size": page_size, "prefill_chunk": page_size,
        "long_prompt": cp_long, "short_prompt": cp_short,
        "gpt2_frontend_chunked_ttft_ms_p50": round(
            ck_stats["ttft_ms_p50"], 3),
        "gpt2_frontend_chunked_ttft_ms_p95": round(
            ck_stats["ttft_ms_p95"], 3),
        "gpt2_frontend_monolithic_ttft_ms_p50": round(
            mono_stats["ttft_ms_p50"], 3),
        "gpt2_frontend_monolithic_ttft_ms_p95": round(
            mono_stats["ttft_ms_p95"], 3),
        "ttft_p95_reduction": round(
            1.0 - ck_stats["ttft_ms_p95"]
            / max(mono_stats["ttft_ms_p95"], 1e-9), 3),
        "chunked_prefills": ck_stats["chunked_prefills"],
        "prefill_chunks": ck_stats["prefill_chunks"],
    }
    emit(cp_rec)

    # --- metrics snapshot artifact (docs/observability.md) ------------------
    # APEX_TPU_METRICS_OUT banks the full instrument registry (serving
    # histograms + pool gauges) next to the bench JSON — the postmortem
    # counterpart of the headline numbers
    out_path = os.environ.get("APEX_TPU_METRICS_OUT")
    if out_path:
        from apex_tpu.obs import export
        export.write_snapshot(out_path, extra={"source": "tpu_decode_bench"})
        print(f"[metrics] snapshot written to {out_path}", flush=True)


if __name__ == "__main__":
    main()
