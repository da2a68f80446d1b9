"""Offline AOT-Mosaic sweep: compile for a described v5e, run nothing.

Compiles every Pallas kernel at the on-chip suite's exact shapes — plus the
full BERT-Large train step at the benchmark's batch and the multi-chip
sharded programs — against a TPU topology that is described, not attached
(``jax.experimental.topologies``).
The installed TPU compiler does the work on the CPU host: Mosaic block-rule
violations, illegal layouts, scoped-VMEM overflows and HBM blowups all
surface at this compile/memory level. A compile that passes is not a chip
run; ``chip_smoke.py`` is.

Recipe:
  - ``get_topology_desc(platform="tpu", topology_name="v5e:2x4")``
  - wrap the kernel in ``shard_map`` with fully-replicated ``P()`` specs
    (plain jit hits "Mosaic kernels cannot be automatically partitioned");
    every device then runs the FULL arrays, so ``memory_analysis()`` is the
    single-chip memory picture
  - ``APEX_TPU_FORCE_MOSAIC=1`` (set by ``run()`` for its own duration) so
    ``ops._dispatch.interpret()`` picks the Mosaic path even though the
    default backend is the CPU
  - assert ``tpu_custom_call`` present in the compiled text and
    argument+output+temp bytes under the v5e 16 GiB HBM budget

No persistent compilation cache here: an executable compiled for a
described topology cannot be read back without a chip.

Writes ``AOT_<tag>.json`` and prints one summary JSON line; exits non-zero
when the sweep raised or any case failed.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import re
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
HBM_BUDGET = 16 * 1024 ** 3  # v5e HBM per chip

SEQ, HIDDEN, VOCAB = 512, 1024, 30528


def log(*a):
    print(*a, file=sys.stderr, flush=True)


#: the described chip: 8 devices, enough for every multi-chip case here
TOPOLOGY_NAME = "v5e:2x4"


def _topology(name: str = TOPOLOGY_NAME):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    return topologies.get_topology_desc(platform="tpu", topology_name=name)


def _mesh(topo):
    from jax.experimental import topologies

    return topologies.make_mesh(topo, (len(topo.devices),), ("data",))


def compile_replicated(mesh, fn, arg_structs, donate=()):
    """shard_map(fn) with all-replicated specs, AOT-compiled for the topology.

    Returns the compiled executable (callers read the lowered text via
    ``compiled.as_text()``). Each device runs the full arrays, so
    per-device memory_analysis == the single-chip footprint.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sm = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    repl = NamedSharding(mesh, P())

    def stamp(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl)

    args = jax.tree.map(stamp, tuple(arg_structs))
    compiled = jax.jit(sm, donate_argnums=donate).lower(*args).compile()
    return compiled


_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s16": 2, "u16": 2,
                "s64": 8, "u64": 8, "c64": 8, "c128": 16}


def hlo_red_flags(txt, threshold_bytes=256 * 1024 * 1024):
    """Static perf-lint over compiled HLO: copy/transpose ops whose RESULT
    exceeds ``threshold_bytes``. The r3 86 GB incident was exactly this
    class — a relayout intermediate far larger than any program tensor —
    and it is visible in compiled text before a chip ever runs. Returns a
    list of {op, bytes} (empty = clean).

    Scans only the ENTRY computation: ops inside fusion bodies never
    materialize their own buffers, so a big fused transpose is not a red
    flag (code-review r5)."""
    entry = txt.find("\nENTRY ")
    if entry >= 0:
        txt = txt[entry:]
    flags = []
    pat = re.compile(r"= (\w+)\[([\d,]*)\][^ ]* (copy|transpose)\(")
    for m in pat.finditer(txt):
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _DTYPE_BYTES.get(dt, 4)
        if b > threshold_bytes:
            flags.append({"op": op, "dtype": dt, "shape": dims, "bytes": b})
    flags.sort(key=lambda f: -f["bytes"])
    return flags[:8]


def case_result(mesh, fn, arg_structs, donate=()):
    import jax  # noqa: F401

    t0 = time.perf_counter()
    compiled = compile_replicated(mesh, fn, arg_structs, donate)
    dt = time.perf_counter() - t0
    txt = compiled.as_text()
    ma = compiled.memory_analysis()
    arg_b = int(ma.argument_size_in_bytes)
    out_b = int(ma.output_size_in_bytes)
    tmp_b = int(ma.temp_size_in_bytes)
    alias_b = int(getattr(ma, "alias_size_in_bytes", 0))
    # donated inputs alias outputs — don't double count them
    peak = arg_b + out_b + tmp_b - alias_b
    return {
        "ok": True,
        "tpu_custom_call_sites": txt.count("tpu_custom_call"),
        "argument_bytes": arg_b,
        "output_bytes": out_b,
        "temp_bytes": tmp_b,
        "alias_bytes": alias_b,
        "peak_estimate_bytes": peak,
        "peak_estimate_gib": round(peak / 1024 ** 3, 3),
        "under_16gib_budget": peak < HBM_BUDGET,
        "giant_copy_flags": hlo_red_flags(txt),
        "compile_s": round(dt, 1),
    }


# ---------------------------------------------------------------------------
# kernel cases — shapes mirror tests/test_real_tpu_kernels.py exactly
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def kernel_cases():
    """Yield (name, fn, arg_structs[, donate]) for every on-chip test config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import (flash_attention, flash_attention_with_lse,
                              flat_buffer, optim_kernels,
                              softmax_cross_entropy)
    from apex_tpu.ops.group_norm import group_norm_nhwc
    from apex_tpu.ops.layer_norm import layer_norm
    from apex_tpu.ops.scaled_softmax import scaled_upper_triang_masked_softmax

    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32

    # -- test_layer_norm_fwd_bwd_bench_shapes
    ln = functools.partial(layer_norm, eps=1e-12)
    yield ("layer_norm_fwd", ln,
           [_sds((8 * SEQ, HIDDEN), f32), _sds((HIDDEN,), f32),
            _sds((HIDDEN,), f32)])
    yield ("layer_norm_bwd",
           jax.grad(lambda x, g, b: jnp.sum(ln(x, g, b) ** 2),
                    argnums=(0, 1, 2)),
           [_sds((8 * SEQ, HIDDEN), f32), _sds((HIDDEN,), f32),
            _sds((HIDDEN,), f32)])

    # -- test_flash_attention_fwd_bwd_seq512
    qkv = [_sds((2, 16, SEQ, 64), bf16)] * 3
    yield ("flash_fwd_seq512", flash_attention, qkv)
    yield ("flash_bwd_seq512",
           jax.grad(lambda q, k, v: jnp.sum(
               flash_attention(q, k, v).astype(f32) ** 2),
               argnums=(0, 1, 2)), qkv)

    # -- test_flash_attention_causal_and_dropout_compile
    q8 = _sds((2, 8, SEQ, 64), bf16)
    cd = functools.partial(flash_attention, causal=True, dropout_rate=0.1,
                           dropout_seed=7)
    yield ("flash_causal_dropout_fwd", lambda q: cd(q, q, q), [q8])
    yield ("flash_causal_dropout_bwd",
           jax.grad(lambda q: jnp.sum(cd(q, q, q).astype(f32))), [q8])

    # -- test_xentropy_vocab30528
    n = 2 * SEQ
    yield ("xentropy_fwd", softmax_cross_entropy,
           [_sds((n, VOCAB), f32), _sds((n,), i32)])
    yield ("xentropy_bwd",
           jax.grad(lambda l, y: softmax_cross_entropy(l, y).sum()),
           [_sds((n, VOCAB), f32), _sds((n,), i32)])

    # -- test_scaled_masked_softmax_seq512
    yield ("scaled_upper_triang_softmax",
           functools.partial(scaled_upper_triang_masked_softmax, scale=0.125),
           [_sds((64, SEQ, SEQ), bf16)])

    # -- test_fused_optimizer_kernels_bert_large_size
    opt_shapes = {"emb": (VOCAB, 64), "w1": (HIDDEN, HIDDEN),
                  "w2": (4 * HIDDEN, HIDDEN), "b": (HIDDEN,)}
    opt_tree = {k: _sds(s, f32) for k, s in opt_shapes.items()}
    spec = flat_buffer.build_spec(opt_tree)
    seg = np.asarray(spec.segment_rows())
    buf = _sds((spec.total_rows, flat_buffer.LANE), f32)
    yield ("optim_adam_bert_large_buffer",
           functools.partial(optim_kernels.adam_update, beta1=0.9, beta2=0.999,
                             eps=1e-8, weight_decay=0.01, lr=1e-3, step=1),
           [buf] * 4, (1, 2, 3))
    yield ("optim_lamb_bert_large_buffer",
           lambda g, p, m, v: optim_kernels.lamb_update(
               g, p, m, v, jnp.asarray(seg), spec.num_tensors, beta1=0.9,
               beta2=0.999, eps=1e-6, weight_decay=0.01, lr=1e-3, step=1),
           [buf] * 4, (1, 2, 3))
    # LAMB at more shapes (ADVICE r5): its phase-1 kernel holds 7 big
    # (blk, LANE) buffers — the Adam-class scoped-VMEM risk — so sweep a
    # GPT-2-small-sized buffer and an odd-row tail, not just BERT-Large
    for lamb_tag, lamb_tree in (
            ("gpt2s", {"emb": (50304, 16), "w1": (768, 768),
                       "w2": (3072, 768), "b": (768,)}),
            ("odd_tail", {"w": (1000, 1001), "b": (7,)}),
    ):
        lspec = flat_buffer.build_spec(
            {k: _sds(s, f32) for k, s in lamb_tree.items()})
        lseg = np.asarray(lspec.segment_rows())
        lbuf = _sds((lspec.total_rows, flat_buffer.LANE), f32)
        yield (f"optim_lamb_{lamb_tag}_buffer",
               lambda g, p, m, v, lseg=lseg, lspec=lspec:
               optim_kernels.lamb_update(
                   g, p, m, v, jnp.asarray(lseg), lspec.num_tensors,
                   beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01,
                   lr=1e-3, step=1),
               [lbuf] * 4, (1, 2, 3))
    yield ("optim_global_grad_norm",
           lambda g: optim_kernels.global_grad_norm_and_finite(
               g, jnp.asarray(seg), spec.num_tensors),
           [buf])

    # -- test_group_norm_kernel_path / _backward_kernel_path
    # custom_vjp nondiff_argnums must stay positional
    gn = lambda x, w, b: group_norm_nhwc(x, w, b, 4, 1e-5, "silu")  # noqa: E731
    yield ("group_norm_fwd_bf16", gn,
           [_sds((4, 16, 16, 512), bf16), _sds((512,), f32),
            _sds((512,), f32)])
    yield ("group_norm_bwd_fp32",
           jax.grad(lambda x, w, b: jnp.sum(gn(x, w, b) ** 2),
                    argnums=(0, 1, 2)),
           [_sds((2, 16, 16, 512), f32), _sds((512,), f32),
            _sds((512,), f32)])

    # -- test_flash_attention_with_lse_on_chip
    yield ("flash_lse_fwd", flash_attention_with_lse,
           [q8, q8, q8])
    yield ("flash_lse_bwd_with_lse_cotangent",
           jax.grad(lambda q, k, v: (
               lambda o_lse: jnp.sum(o_lse[1]) +
               jnp.sum(o_lse[0].astype(f32)))(
               flash_attention_with_lse(q, k, v))),
           [q8, q8, q8])

    # -- test_flash_attention_sliding_window
    yield ("flash_window_wide_fwd",
           lambda q: flash_attention(q, q, q, causal=True, window=SEQ), [q8])
    yield ("flash_window128_bwd",
           jax.grad(lambda q: jnp.sum(flash_attention(
               q, q, q, causal=True, window=128).astype(f32) ** 2)), [q8])

    # -- paged-attention serving decode kernel (apex_tpu/serving): GPT-2
    # small pool at 8 slots — 512 usable pages of 16 tokens (+ null page),
    # 32-page tables (512-token sequences). Scalar-prefetch block tables
    # are the new Mosaic feature this case gates. The pool as the engine
    # holds it: two 64-wide heads a 128-lane row (kv_pool.heads_per_row),
    # the queries per head
    from apex_tpu.ops.paged_attention import paged_attention

    yield ("paged_attention_gpt2s_decode", paged_attention,
           [_sds((8, 12, 1, 64), bf16), _sds((513, 6, 16, 128), bf16),
            _sds((513, 6, 16, 128), bf16), _sds((8, 32), i32),
            _sds((8,), i32)])

    # -- the benchmark's serving cell (gpt2-large.chat-closed16): 16 slots,
    # 20 heads of 64 held two a row, 64-page tables over the 2 GiB pool;
    # one grid step takes all 10 rows of 8 pages, so 16 page operands of
    # (1, 10, 16, 128) a tensor ride one call
    yield ("paged_attention_gpt2l_cell", paged_attention,
           [_sds((16, 20, 1, 64), bf16), _sds((729, 10, 16, 128), bf16),
            _sds((729, 10, 16, 128), bf16), _sds((16, 64), i32),
            _sds((16,), i32)])

    # -- the latent serving cell (glm-4.7-flash.docqa-closed32): 32 slots,
    # 20 query heads over ONE shared 640-lane entry a token (512 of them
    # the values), 2048-page tables (32,768 positions: the published
    # 202,752 would put 1.6 MB of resolved table into 1 MiB of SMEM), the
    # 4 GiB pool's 38,837 pages
    from apex_tpu.ops.paged_latent_attention import paged_latent_attention

    yield ("paged_latent_attention_glm_cell",
           functools.partial(paged_latent_attention, value_width=512),
           [_sds((32, 20, 1, 640), bf16), _sds((38837, 1, 16, 640), bf16),
            _sds((32, 2048), i32), _sds((32,), i32)])

    # -- the same cell's routed experts inside the decode chunk: 32 rows,
    # top 4 of 64 experts of 2048 x 1536; jax.lax.ragged_dot has to come
    # out as XLA's own Mosaic calls (%ragged-dot-*), which the benchmark's
    # moe_experts_* metrics find by that name
    from apex_tpu.transformer.moe import grouped_experts

    yield ("moe_grouped_experts_glm_decode", grouped_experts,
           [_sds((32, 2048), bf16), _sds((32, 4), i32), _sds((32, 4), f32),
            _sds((64, 2048, 1536), bf16), _sds((64, 2048, 1536), bf16),
            _sds((64, 1536, 2048), bf16)])

    # -- the routed experts of mellum2-12b-a2.5b.ide-closed48's decode step:
    # 48 slots x top 8 of 64 experts = 384 rows, at the width the engine
    # serves Mellum's 2304 x 896 stacks (dropless.served_width: 1024, where
    # XLA's ragged-dot tiles the hidden axis 512 wide and not 128)
    from apex_tpu.transformer.moe.dropless import served_width

    w = served_width(2304, 896)
    yield ("moe_grouped_experts_mellum_decode", grouped_experts,
           [_sds((48, 2304), bf16), _sds((48, 8), i32), _sds((48, 8), f32),
            _sds((64, 2304, w), bf16), _sds((64, 2304, w), bf16),
            _sds((64, w, 2304), bf16)])

    # -- the routed experts of lfm2-24b-a2b.chat-closed128's decode step:
    # 128 slots x top 4 = 512 rows over GLM's 64 x 2048 x 1536 stack, ~8 a
    # group; handed 640 rows (dropless.served_rows), so the row tile is 128
    # and not the 512 that 512 rows would get
    yield ("moe_grouped_experts_lfm2_decode", grouped_experts,
           [_sds((128, 2048), bf16), _sds((128, 4), i32), _sds((128, 4), f32),
            _sds((64, 2048, 1536), bf16), _sds((64, 2048, 1536), bf16),
            _sds((64, 1536, 2048), bf16)])

    # -- the s>1 query-block generalization (ISSUE 13): the speculative
    # verify step reads a 4-token block (draft_len 3 + 1 pending) per
    # slot through the SAME kernel — the per-row causal band
    # (len - s + i) is the only new Mosaic surface, so one s=4 case
    # gates it at the gpt2s pool shape.
    yield ("gpt2s_paged_spec_verify", paged_attention,
           [_sds((8, 12, 4, 64), bf16), _sds((513, 6, 16, 128), bf16),
            _sds((513, 6, 16, 128), bf16), _sds((8, 32), i32),
            _sds((8,), i32)])

    # -- quantized KV pages (docs/serving.md "Quantized KV pages"): the
    # SAME decode step over an int8 pool with per-(page, kv_head) f32
    # scales dequantized inside the kernel. The new Mosaic surfaces this
    # case gates: int8 page tiles at the (1, 1, page, d) block shape and
    # the (1, 1) scale blocks indexed through the prefetched table.
    yield ("gpt2s_paged_decode_int8kv",
           lambda q, k, v, bt, ln, ks, vs: paged_attention(
               q, k, v, bt, ln, k_scales=ks, v_scales=vs),
           [_sds((8, 12, 1, 64), bf16), _sds((513, 12, 16, 64), jnp.int8),
            _sds((513, 12, 16, 64), jnp.int8), _sds((8, 32), i32),
            _sds((8,), i32), _sds((513, 12), f32), _sds((513, 12), f32)])

    # -- serving path (r5): lock-step ``generate`` — flash prefill +
    # lax.scan single-token decode + argmax, GPT-2 small (batch 8,
    # prompt 128, 128 new tokens, bf16), fp AND int8 W8A8.
    import dataclasses

    from apex_tpu.models.generation import generate
    from apex_tpu.models.gpt import GPTModel, gpt2_small_config

    dcfg = gpt2_small_config(dtype=bf16)
    dmodel = GPTModel(dcfg)
    prompt_s = _sds((8, 128), i32)
    dvars = jax.eval_shape(
        lambda: dmodel.init(jax.random.PRNGKey(0), jnp.zeros((8, 8), i32)))

    def decode_fp(variables, prompt):
        return generate(dmodel, variables, prompt, max_new_tokens=128,
                        max_len=256, axis_name="unbound")

    yield ("gpt2_small_decode128_fp", decode_fp, [dvars, prompt_s])

    qmodel = GPTModel(dataclasses.replace(dcfg, quantize_int8=True))
    qvars = jax.eval_shape(
        lambda: qmodel.init(jax.random.PRNGKey(0), jnp.zeros((8, 8), i32)))

    def decode_int8(variables, prompt):
        return generate(qmodel, variables, prompt, max_new_tokens=128,
                        max_len=256, axis_name="unbound")

    yield ("gpt2_small_decode128_int8", decode_int8, [qvars, prompt_s])

    # -- prefix-cached serving admission (apex_tpu/serving/prefix_cache):
    # the shared-prefix admission program at GPT-2 small shapes — gather 8
    # cached pages (128 shared-header tokens) from the pool into the
    # contiguous buffer, run the 128-token tail forward against it (dense
    # cached attention + the Pallas layer-norm kernels), pop private
    # pages with refcount bookkeeping, scatter the tail K/V. This is the
    # one program prefix caching adds to the serving path; the decode
    # step itself is the (already-swept) paged program.
    from apex_tpu.serving import kv_pool as _kv_pool
    from apex_tpu.serving.scheduler import make_shared_admit

    pcache_abs = jax.eval_shape(
        lambda: _kv_pool.init_paged_cache(dcfg, 8, num_pages=513,
                                          page_size=16))
    pc_max_pages = pcache_abs["block_tables"].shape[1]
    prefix_admit = make_shared_admit(dmodel, t_start=128, tail_bucket=128,
                                     axis_name="unbound")

    yield ("gpt2s_prefix_cached_admit", prefix_admit,
           [pcache_abs, dvars, _sds((1, 128), i32), _sds((), i32),
            _sds((), i32), _sds((pc_max_pages,), i32), _sds((), i32),
            _sds((2,), jnp.uint32)])

    # -- chunked-prefill step (ISSUE 13): one 16-token prompt chunk of
    # one slot rides the paged s>1 path straight into the slot's pages
    # (no contiguous staging, no scatter) — the program the frontend
    # interleaves between decode chunks to bound TTFT.
    from apex_tpu.serving.scheduler import make_prefill_chunk

    chunk_step = make_prefill_chunk(dmodel, chunk=16, axis_name="unbound")

    yield ("gpt2s_chunked_prefill_step", chunk_step,
           [pcache_abs, dvars, _sds((1, 16), i32), _sds((), i32),
            _sds((), i32), _sds((2,), jnp.uint32), _sds((), i32)])

    # -- quantized weight streaming (docs/serving.md "Quantized weight
    # streaming"): the paged decode chunk over a gpt2-small built with
    # the int8 WeightPrecisionPolicy — every block linear stages the
    # fused dequant-matmul kernel (int8 weight + f32 scale operands,
    # dequant in VMEM next to the contraction) alongside the paged
    # attention gather. The new Mosaic surfaces: int8 weight tiles at
    # (block_out, in) and the degenerate (1, block_out) scale blocks.
    from apex_tpu.ops.quant import WeightPrecisionPolicy
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    wmodel = GPTModel(dataclasses.replace(
        dcfg, weight_policy=WeightPrecisionPolicy("int8")))
    wengine = PagedDecodeEngine(wmodel, variables=None, num_slots=8,
                                page_size=16, num_pages=513,
                                max_pages_per_seq=32, sync_every=4)
    wcache_abs = jax.tree.map(lambda x: _sds(x.shape, x.dtype),
                              wengine.cache)
    wvars = jax.eval_shape(
        lambda: wmodel.init(jax.random.PRNGKey(0), jnp.zeros((8, 8), i32)))

    yield ("gpt2s_paged_decode_w8", wengine._step_fn(),
           [wcache_abs, wvars, _sds((8,), i32), _sds((8,), jnp.bool_),
            _sds((8,), i32), _sds((8, 2), jnp.uint32), _sds((8,), i32)])

    # -- the int4 half of the same kernel, raw, at the gpt2s block-linear
    # shape: packed nibbles (out, in/2) uint8 + per-(group, out) f32
    # scales — gates the nibble-extract widening and the sub-sublane
    # (n_groups, block_out) scale block under Mosaic's tiling rules.
    from apex_tpu.ops.quant import fused_dequant_matmul

    yield ("gpt2s_fused_dequant_w4", fused_dequant_matmul,
           [_sds((8, 768), bf16), _sds((768, 384), jnp.uint8),
            _sds((6, 768), f32)])

    # -- tiered KV pool (ISSUE 17): the demote-side page gather (pure
    # read — cache NOT donated) and the promote-side scatter (cache
    # donated, pops the free stack like an allocation). Both are plain
    # XLA data movers by design — no Mosaic kernel, a fixed null-padded
    # HOST_COPY_CHUNK page row, depth as a traced scalar — so the pin
    # is the inverse of the others: zero tpu_custom_call sites, and no
    # giant-copy flags (a relayout sneaking into the copy path would be
    # pure overhead on the host-link DMA).
    chunk_row = _sds((_kv_pool.HOST_COPY_CHUNK,), i32)
    tiles_abs = jax.eval_shape(_kv_pool.gather_pages, pcache_abs,
                               chunk_row)

    yield ("gpt2s_host_tier_gather", _kv_pool.gather_pages,
           [pcache_abs, chunk_row])

    yield ("gpt2s_host_tier_promote", _kv_pool.promote_pages,
           [pcache_abs, chunk_row, _sds((), i32), tiles_abs], (0,))


def bert_cell_flash_case():
    """Flash attention forward + backward as ``bert-large.pretrain-seq512``
    calls it: b8 h16 s512 d64, bfloat16, segment ids, no tile asked for.
    ``tests/test_aot_mosaic.py`` pins what the rule in
    ``ops/flash_attention.py`` picked there (``mosaic_calls``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import flash_attention

    qkv = [_sds((8, 16, SEQ, 64), jnp.bfloat16)] * 3

    def fwd_bwd(q, k, v, seg):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, segment_ids=seg).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    return ("flash_bert_cell_fwd_bwd", fwd_bwd,
            qkv + [_sds((8, SEQ), jnp.int32)])


def mosaic_calls(txt):
    """``[(label, grid, [block shape, ...]), ...]`` of a compiled program's
    labelled Mosaic calls, read off each call's serialized kernel: the grid
    is the body's ``iteration_bounds``, the blocks its operands' and
    results' ``window_bounds`` in order."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def ints(found):
        return tuple(int(x) for x in found.split(","))

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    calls = []
    for chunk in txt.split('custom_call_target="tpu_custom_call"')[1:]:
        label = re.search(r'"kernel":"(\w+)"', chunk)
        body = re.search(r'"body":"([^"]+)"', chunk)
        if not (label and body):
            continue
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        grid = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>", asm)
        blocks = re.findall(r"window_bounds = array<i64: ([\d, ]+)>", asm)
        calls.append((label.group(1), ints(grid.group(1)),
                      [ints(b) for b in blocks]))
    return calls


def moe_case():
    import jax
    import jax.numpy as jnp

    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 1024, 4096, 8, 2, 2048
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=1.25, expert_world_size=1,
                   axis_name="nope")
    x_s = _sds((t, d), jnp.bfloat16)
    abs_vars = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0),
                           jnp.zeros((t, d), jnp.bfloat16)))
    params_abs = abs_vars["params"]

    def loss_and_grad(p, xx):
        def f(pp):
            y, aux = layer.apply({"params": pp}, xx)
            return jnp.sum(y.astype(jnp.float32) ** 2) + aux.total
        return jax.value_and_grad(f)(p)

    return ("moe_dense_dispatch_grad", loss_and_grad, [params_abs, x_s])


def bert_train_step_case(batch_per_chip=8, remat=False):
    """The whole training program: BERT-Large loss+grads+FusedLAMB update at
    batch ``batch_per_chip``, seq 512 — all kernels in one compiled program.
    Params/optimizer state are abstract (eval_shape + a field-initialized
    FusedLAMB), so no 1.4 GB host arrays are materialized."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import (BertForPreTraining, bert_large_config,
                                 make_pretrain_step, synthetic_batch)
    from apex_tpu.ops import flat_buffer
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.optimizers.common import path_name

    cfg = bert_large_config()
    if remat:
        cfg = dataclasses.replace(cfg, remat=True)
    model = BertForPreTraining(cfg)
    rng = np.random.default_rng(0)
    batch = synthetic_batch(rng, cfg, batch_per_chip, SEQ)
    abs_params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch["input_ids"],
                           batch["token_type_ids"],
                           batch["attention_mask"])["params"])
    spec = flat_buffer.build_spec(abs_params)
    seg_rows = spec.segment_rows()

    # field-initialize the optimizer facade (the ctor would materialize the
    # master/state buffers; only spec/seg_rows/defaults matter for tracing)
    opt = object.__new__(FusedLAMB)
    opt.defaults = dict(lr=1e-4, beta1=0.9, beta2=0.999, eps=1e-6,
                        weight_decay=0.01, max_grad_norm=1.0)
    opt.spec = spec
    opt.seg_rows = seg_rows
    opt.bias_correction = True
    opt.grad_averaging = True
    opt.use_nvlamb = False
    exclude = lambda n: "bias" in n or "norm" in n.lower()  # noqa: E731
    paths, _ = jax.tree_util.tree_flatten_with_path(abs_params)
    opt.wd_per_segment = np.asarray(
        [0.0 if exclude(path_name(p)) else 0.01 for p, _ in paths],
        np.float32)

    step_fn = make_pretrain_step(model)
    hyper = {k: jnp.float32(v) for k, v in opt.defaults.items()}

    def train_step(params, master, m, v, stepc, batch, i):
        loss, grads = step_fn(params, batch, i)
        g_flat = flat_buffer.flatten(grads, spec)
        new_step = stepc + 1
        new_master, new_state = opt._update(
            g_flat, master, {"m": m, "v": v}, new_step,
            dict(hyper, grad_scale=jnp.float32(1.0), noop=jnp.float32(0.0),
                 wd_per_segment=jnp.asarray(opt.wd_per_segment)))
        params_out = flat_buffer.unflatten(new_master, spec)
        return loss, params_out, new_master, new_state["m"], new_state["v"], new_step

    buf = _sds((spec.total_rows, flat_buffer.LANE), jnp.float32)
    batch_s = {k: _sds(tuple(np.shape(val)), jnp.asarray(val).dtype)
               for k, val in batch.items()}
    args = [abs_params, buf, buf, buf, _sds((), jnp.int32), batch_s,
            _sds((), jnp.int32)]
    name = f"bert_large_train_step_b{batch_per_chip}" + (
        "_remat" if remat else "")
    # donate master/m/v — mirrors FusedOptimizerBase's donate_argnums=(1, 2)
    return (name, train_step, args, (1, 2, 3))


# ---------------------------------------------------------------------------
# multi-chip sharded programs (r5): the dryrun cases only ever RUN on the
# virtual CPU mesh in interpret mode — here the same sharded programs
# (ring-attention CP, zigzag CP + window, Megatron TP, T5 TP + cached
# decode, MoE EP x expert-TP, 1F1B pipeline) are Mosaic-compiled for the
# real v5e topology, proving the multi-chip path compiles for TPU hardware
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _host_interpret():
    """Temporarily drop FORCE_MOSAIC for code that EXECUTES on the CPU host
    (e.g. building real param trees) — Mosaic lowering is compile-only."""
    prior = os.environ.get("APEX_TPU_FORCE_MOSAIC")
    os.environ["APEX_TPU_FORCE_MOSAIC"] = "0"
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop("APEX_TPU_FORCE_MOSAIC", None)
        else:
            os.environ["APEX_TPU_FORCE_MOSAIC"] = prior


def _topo_mesh(topo, shape, names=("data", "stage", "context", "model")):
    import numpy as np
    from jax.sharding import Mesh

    n = 1
    for s in shape:
        n *= s
    return Mesh(np.asarray(topo.devices[:n]).reshape(shape), names)


MULTICHIP_CASE_NAMES = (
    "cp2_ring_attention_grad",
    "cp2_zigzag_window_grad",
    "tp2_megatron_gpt_grad",
    "tp2_t5_grad_and_cached_decode",
    "ep2_etp2_moe_grad",
    "pp2_tp2_1f1b_pipeline_step",
    "tp4_paged_engine_admit",
    "tp4_paged_engine_decode_chunk",
    "tp4_paged_engine_decode_w8",
)

#: the tensor-parallel serving acceptance shape (docs/tp_serving.md):
#: 384 slots x 32 pages of a GPT at hidden 1024 / 8 heads — head_dim
#: 128, so page tiles are (32, 128): lane-exact, NO tiled-layout
#: padding, and the unpadded byte accounting below IS the physical HBM
#: footprint. 12289 pages x 1.5 MiB = 18.0 GiB UNSHARDED — over one
#: v5e chip's 16 GiB — sharded tp=4 over the v5e:2x4 topology (4.5 GiB
#: head shard per chip) the admit+decode programs compile under the
#: per-chip budget. tests/test_aot_mosaic.py asserts both halves of
#: that inequality. Two shape lessons are baked in here (both found by
#: this case's own compile failures): (a) GPT-2 small's head_dim 64
#: pads 2x in TPU tiled layout — the first 512-slot d=64 attempt OOM'd
#: at 25.6 GiB from padding alone; lane-align the head dim; (b) the
#: decode chunk's lax.scan DOUBLE-BUFFERS the pool carry in XLA, so a
#: chip needs ~2x its pool shard transient — which is why 18 GiB
#: shards over four chips, not two (2 x 9 GiB + weights > 16 GiB).
#: Both lessons are now lint rules (mem-padding-blowup and
#: mem-scan-carry-double-buffer, `python -m apex_tpu.analysis --mem`):
#: the next pool that repeats either mistake dies in the CPU-only mem
#: gate, and tests/test_aot_mosaic.py pins the lint tier's static
#: per-chip peaks within +/-20% of this sweep's memory_analysis() so
#: the two accountings cannot silently drift apart.
TP_SERVING_SLOTS = 384
TP_SERVING_PAGE_SIZE = 32
TP_SERVING_MAX_PAGES_PER_SEQ = 32
TP_SERVING_TP = 4


def tp_serving_config(weight_policy=None):
    """The acceptance model: GPT-2-small depth at hidden 1024 / 8 heads
    (head_dim 128 — lane-exact page tiles), tp=4, bf16. Pass
    ``weight_policy="int8"`` for the quantized-weight-streaming variant
    (every block linear narrow + scale, fused in-kernel dequant)."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import gpt2_small_config

    pol = None
    if weight_policy is not None:
        from apex_tpu.ops.quant import WeightPrecisionPolicy
        pol = WeightPrecisionPolicy(weight_policy)
    return gpt2_small_config(hidden_size=1024, num_heads=8,
                             dtype=jnp.bfloat16,
                             tensor_parallel_size=TP_SERVING_TP,
                             weight_policy=pol)


def tp_serving_pool_bytes() -> int:
    """The UNSHARDED pool's bytes at the TP acceptance shape (what a
    single chip would have to hold)."""
    cfg = tp_serving_config()
    num_pages = 1 + TP_SERVING_SLOTS * TP_SERVING_MAX_PAGES_PER_SEQ
    kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
    # k + v, bf16
    return (num_pages * cfg.num_layers * 2 * kv_heads
            * TP_SERVING_PAGE_SIZE * cfg.head_dim * 2)


def multichip_cases(topo):
    """Yield (name, build) mirroring __graft_entry__'s dryrun cases (same
    tiny shapes). ``build()`` is LAZY — it constructs (mesh, fn,
    arg_structs) only when called, so filtered-out cases cost nothing and a
    broken case can't abort the others (code-review r5)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu.mesh import CONTEXT_AXIS, DATA_AXIS, MODEL_AXIS, STAGE_AXIS

    i32 = jnp.int32
    seq_sh = P(None, CONTEXT_AXIS)

    def build_cp_ring():
        from apex_tpu.models.gpt import GPTModel, gpt_loss, gpt_tiny_config

        mesh = _topo_mesh(topo, (4, 1, 2, 1))
        model = GPTModel(gpt_tiny_config(context_parallel=True))
        ids_s = _sds((2, 32), i32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 32), i32))["params"])

        def cp_grad(p, ii, ll):
            body = jax.shard_map(
                lambda pp_, i_, l_: gpt_loss(model, {"params": pp_}, i_, l_),
                mesh=mesh, in_specs=(P(), seq_sh, seq_sh), out_specs=P(),
                check_vma=False)
            return jax.value_and_grad(lambda q: body(q, ii, ll))(p)

        return mesh, cp_grad, [params, ids_s, ids_s]

    def build_cp_zigzag():
        from apex_tpu.models.llama import (LlamaModel, llama_loss,
                                           llama_tiny_config)

        mesh = _topo_mesh(topo, (4, 1, 2, 1))
        model = LlamaModel(llama_tiny_config(
            context_parallel=True, context_parallel_zigzag=True,
            sliding_window=12))
        ids_s = _sds((2, 32), i32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0),
                               jnp.zeros((2, 32), i32))["params"])

        def zigzag_grad(p, ii, ll):
            body = jax.shard_map(
                lambda pp_, i_, l_: llama_loss(model, {"params": pp_},
                                               i_, l_),
                mesh=mesh, in_specs=(P(), seq_sh, seq_sh), out_specs=P(),
                check_vma=False)
            return jax.value_and_grad(lambda q: body(q, ii, ll))(p)

        return mesh, zigzag_grad, [params, ids_s, ids_s]

    def build_tp_megatron():
        from apex_tpu.models.gpt import GPTModel, gpt_loss, gpt_tiny_config

        mesh = _topo_mesh(topo, (4, 1, 1, 2))
        model = GPTModel(gpt_tiny_config(tensor_parallel_size=2))

        def tp_step(ii, ll):
            def body(i_, l_):
                v = model.init(jax.random.PRNGKey(0), i_)
                loss, _ = jax.value_and_grad(
                    lambda p: gpt_loss(model, {"params": p}, i_, l_))(
                    v["params"])
                return loss.reshape(1)
            return jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(MODEL_AXIS),
                                 check_vma=False)(ii, ll)

        ids16 = _sds((2, 16), i32)
        return mesh, tp_step, [ids16, ids16]

    def build_tp_t5():
        from apex_tpu.models.t5 import (T5Model, t5_generate, t5_loss,
                                        t5_tiny_config)

        mesh = _topo_mesh(topo, (4, 1, 1, 2))
        model = T5Model(t5_tiny_config(tensor_parallel_size=2))

        def t5_step(ei, di, ll):
            def body(e_, d_, l_):
                v = model.init(jax.random.PRNGKey(0), e_, d_)
                loss, _ = jax.value_and_grad(lambda p: t5_loss(
                    model, {"params": p}, e_, d_, l_))(v["params"])
                toks = t5_generate(model, v, e_, 3)
                return loss.reshape(1), toks
            return jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=(P(MODEL_AXIS), P()),
                                 check_vma=False)(ei, di, ll)

        return mesh, t5_step, [_sds((2, 12), i32), _sds((2, 8), i32),
                               _sds((2, 8), i32)]

    def build_moe():
        from apex_tpu.transformer.moe import MoEMLP

        mesh = _topo_mesh(topo, (2, 1, 1, 2))
        d, ff, e, k, t_per = 16, 32, 4, 2, 8
        layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e,
                       k=k, capacity_factor=float(e) / k + 1.0,
                       activation="swiglu", expert_world_size=2,
                       axis_name=DATA_AXIS, tensor_world_size=2,
                       tensor_parallel_axis="model")

        def moe_step(xx):
            def body(x_):
                v = layer.init(jax.random.PRNGKey(0), x_)

                def loss_fn(p):
                    y, aux = layer.apply({"params": p}, x_)
                    return jnp.mean(y * y) + aux.total

                loss, g = jax.value_and_grad(loss_fn)(v["params"])
                gnorm = sum(jnp.sum(l * l)
                            for l in jax.tree_util.tree_leaves(g))
                loss = jax.lax.pmean(jax.lax.pmean(loss, DATA_AXIS), "model")
                gnorm = jax.lax.psum(jax.lax.psum(gnorm, DATA_AXIS), "model")
                return loss, gnorm
            return jax.shard_map(body, mesh=mesh, in_specs=P(DATA_AXIS),
                                 out_specs=P(), check_vma=False)(xx)

        return mesh, moe_step, [_sds((t_per * 2, d), jnp.float32)]

    def build_pipeline():
        import __graft_entry__ as ge
        from apex_tpu.models.gpt_pipeline import make_gpt_pipeline_fns
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_pipelining_without_interleaving as fwd_bwd)

        mesh = _topo_mesh(topo, (2, 2, 1, 2))
        with _host_interpret():   # builds REAL param trees on the CPU host
            cfg, mbs, labels, stacked = ge._build_stacked_gpt_pipeline(
                2, 2, m=4, b=2, s=16)
        first_fn, stage_fn, loss_fn = make_gpt_pipeline_fns(cfg)

        def pipe_step(p_stacked, mb, lb):
            def body(ps, m_, l_):
                local = jax.tree.map(lambda t: t[0, 0], ps)
                loss, grads = fwd_bwd(stage_fn, loss_fn, local, m_,
                                      loss_aux=l_, first_fn=first_fn,
                                      loss_with_params=True)
                new_p = jax.tree.map(lambda pi, gi: pi - 0.1 * gi,
                                     local, grads)
                return loss.reshape(1), jax.tree.map(
                    lambda t: t[None, None], new_p)
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(STAGE_AXIS, MODEL_AXIS), P(), P()),
                out_specs=(P(STAGE_AXIS), P(STAGE_AXIS, MODEL_AXIS)),
                check_vma=False)(p_stacked, mb, lb)

        stacked_s = jax.tree.map(
            lambda a: _sds(np.shape(a), jnp.asarray(a).dtype), stacked)
        return mesh, pipe_step, [stacked_s, _sds(mbs.shape, i32),
                                 _sds(labels.shape, i32)]

    def _build_tp_serving(kind, weight_policy=None):
        # the tensor-parallel PAGED SERVING programs (serving/tp.py):
        # the tp=TP_SERVING_TP engine's shard_map admission + decode
        # chunk with the pool's kv-head axis REALLY sharded over the
        # topology mesh — per-chip memory_analysis then proves a pool
        # one chip cannot hold (tp_serving_pool_bytes() > 16 GiB)
        # compiles under the per-chip budget when sharded
        from jax.sharding import Mesh, NamedSharding

        from apex_tpu.models.gpt import GPTModel
        from apex_tpu.serving.scheduler import prompt_bucket
        from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                         infer_variable_specs)

        mesh = Mesh(np.asarray(topo.devices[:TP_SERVING_TP]),
                    (MODEL_AXIS,))
        cfg = tp_serving_config(weight_policy=weight_policy)
        model = GPTModel(cfg)
        engine = TensorParallelPagedEngine(
            model, variables=None, mesh=mesh, abstract=True,
            num_slots=TP_SERVING_SLOTS,
            page_size=TP_SERVING_PAGE_SIZE,
            max_pages_per_seq=TP_SERVING_MAX_PAGES_PER_SEQ,
            sync_every=4)
        dvars_abs, var_specs = infer_variable_specs(model)
        dvars = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            dvars_abs, var_specs)
        repl = NamedSharding(mesh, P())

        def rsds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

        n = TP_SERVING_SLOTS
        # donate the cache (arg 0): in production the pool updates in
        # place; without it the in/out pool shards double-count and no
        # 16 GiB chip could ever hold a >8 GiB-sharded program
        if kind == "decode":
            args = [engine.cache, dvars, rsds((n,), i32),
                    rsds((n,), jnp.bool_), rsds((n,), i32),
                    rsds((n, 2), jnp.uint32), rsds((n,), i32)]
            return mesh, engine._step_fn(), args, (0,)
        bucket = prompt_bucket(128, TP_SERVING_PAGE_SIZE,
                               cfg.max_position_embeddings)
        args = [engine.cache, dvars, rsds((1, bucket), i32), rsds((), i32),
                rsds((), i32), rsds((), i32), rsds((2,), jnp.uint32),
                rsds((), i32)]
        return mesh, engine._admit_fn(bucket), args, (0,)

    def build_tp_paged_admit():
        return _build_tp_serving("admit")

    def build_tp_paged_decode():
        return _build_tp_serving("decode")

    def build_tp_paged_decode_w8():
        # the quantized-weight variant of the decode chunk: same sharded
        # pool, but every block linear's weight rides int8 (+ f32 scale)
        # through the fused dequant-matmul kernel — the per-chip peak
        # bytes must DROP vs the bf16 case (tests/test_aot_mosaic.py
        # asserts the inequality)
        return _build_tp_serving("decode", weight_policy="int8")

    builders = (build_cp_ring, build_cp_zigzag, build_tp_megatron,
                build_tp_t5, build_moe, build_pipeline,
                build_tp_paged_admit, build_tp_paged_decode,
                build_tp_paged_decode_w8)
    for name, build in zip(MULTICHIP_CASE_NAMES, builders):
        yield name, build


def multichip_aot(topo, only=None):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = {}
    for name, build in multichip_cases(topo):
        if only and name not in only:
            continue
        log(f"multichip case {name}...")
        try:
            t0 = time.perf_counter()
            built = build()               # lazy: inside the per-case try
            mesh, fn, structs = built[:3]
            donate = built[3] if len(built) > 3 else ()
            repl = NamedSharding(mesh, P())
            # a builder may pre-stamp per-arg shardings (the TP serving
            # cases shard the pool's head axis); only default-stamp the
            # unstamped leaves as replicated
            args = jax.tree.map(
                lambda s: s if getattr(s, "sharding", None) is not None
                else jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=repl),
                tuple(structs))
            compiled = jax.jit(fn, donate_argnums=donate
                               ).lower(*args).compile()
            txt = compiled.as_text()
            ma = compiled.memory_analysis()
            arg_b = int(ma.argument_size_in_bytes)
            out_b = int(ma.output_size_in_bytes)
            tmp_b = int(ma.temp_size_in_bytes)
            alias_b = int(getattr(ma, "alias_size_in_bytes", 0))
            peak = arg_b + out_b + tmp_b - alias_b   # PER-CHIP bytes
            out[name] = {
                "ok": True,
                "tpu_custom_call_sites": txt.count("tpu_custom_call"),
                "collective_permutes": txt.count("collective-permute"),
                "all_to_alls": txt.count("all-to-all"),
                "all_reduces": txt.count("all-reduce"),
                "argument_bytes": arg_b,
                "output_bytes": out_b,
                "temp_bytes": tmp_b,
                "alias_bytes": alias_b,
                "peak_estimate_bytes": peak,
                "peak_estimate_gib": round(peak / 1024 ** 3, 3),
                "under_16gib_budget": peak < HBM_BUDGET,
                "giant_copy_flags": hlo_red_flags(txt),
                "compile_s": round(time.perf_counter() - t0, 1),
            }
            r = out[name]
            log(f"  ok: {r['tpu_custom_call_sites']} kernels, "
                f"{r['collective_permutes']} ppermutes, "
                f"{r['all_reduces']} all-reduces, {r['compile_s']}s")
        except Exception as e:  # noqa: BLE001
            log(traceback.format_exc())
            out[name] = {"ok": False,
                         "error": f"{type(e).__name__}: {str(e)[:300]}"}
    return out


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(only=None):
    import jax

    # constants materialize on the host; every TPU compile here is for the
    # described topology
    jax.config.update("jax_platforms", "cpu")
    from apex_tpu.ops._dispatch import forced_mosaic

    with forced_mosaic():
        return _run(only)


def _run(only):
    topo = _topology()
    mesh = _mesh(topo)
    log(f"topology {TOPOLOGY_NAME}: {len(topo.devices)} devices")

    results = {}

    def run_case(name, fn, structs, donate=()):
        if only and name not in only:
            return
        log(f"case {name}...")
        try:
            results[name] = case_result(mesh, fn, structs, donate)
            r = results[name]
            log(f"  ok: {r['tpu_custom_call_sites']} custom-call sites, "
                f"peak {r['peak_estimate_gib']} GiB, {r['compile_s']}s")
        except Exception as e:  # noqa: BLE001
            log(traceback.format_exc())
            results[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {str(e)[:300]}"}

    for case in kernel_cases():
        run_case(*case)

    run_case(*bert_cell_flash_case())

    try:
        run_case(*moe_case())
    except Exception as e:  # noqa: BLE001
        results["moe_dense_dispatch_grad"] = {
            "ok": False, "error": f"{type(e).__name__}: {str(e)[:300]}"}

    for bpc, remat in ((8, False), (32, True)):
        try:
            run_case(*bert_train_step_case(bpc, remat))
        except Exception as e:  # noqa: BLE001
            log(traceback.format_exc())
            results[f"bert_large_train_step_b{bpc}"] = {
                "ok": False, "error": f"{type(e).__name__}: {str(e)[:300]}"}

    out = {
        "metric": "aot_mosaic_sweep",
        "topology": TOPOLOGY_NAME,
        # the chip the compiler targeted: described, not attached
        "platform": topo.devices[0].platform,
        "device_kind": topo.devices[0].device_kind,
        "n_devices": len(topo.devices),
        "attached": False,
        "hbm_budget_bytes": HBM_BUDGET,
        "cases": results,
    }

    mc_only = None
    if only:
        mc_only = [n for n in only if n in MULTICHIP_CASE_NAMES]
        unmatched = [n for n in only
                     if n not in MULTICHIP_CASE_NAMES and n not in results]
        if unmatched:
            log(f"WARNING: --only names matched nothing: {unmatched}")
    if not only or mc_only:
        log("multi-chip sharded-program compile sweep...")
        try:
            out["multichip"] = multichip_aot(topo, only=mc_only)
        except Exception as e:  # noqa: BLE001
            log(traceback.format_exc())
            out["multichip_error"] = f"{type(e).__name__}: {str(e)[:300]}"
        mc = out.get("multichip", {})
        out["multichip_ok"] = sum(1 for r in mc.values() if r.get("ok"))
        out["multichip_fail"] = len(mc) - out["multichip_ok"]

    n_ok = sum(1 for r in results.values() if r.get("ok"))
    n_over = sum(1 for r in results.values()
                 if r.get("ok") and not r.get("under_16gib_budget", True))
    out["n_ok"] = n_ok
    out["n_fail"] = len(results) - n_ok
    out["n_over_budget"] = n_over
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="run only the named cases (smoke/debug)")
    args = ap.parse_args()

    tag = os.environ.get("APEX_TPU_TAG", "session")
    out = run(args.only)
    path = os.path.join(REPO, f"AOT_{tag}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    log(f"wrote {path}")
    summary = {
        "metric": "aot_mosaic_sweep",
        "platform": out["platform"], "device_kind": out["device_kind"],
        "n_devices": out["n_devices"], "attached": False,
        "n_ok": out["n_ok"], "n_fail": out["n_fail"],
        "n_over_budget": out["n_over_budget"],
        "multichip_ok": out.get("multichip_ok", 0),
        "multichip_fail": out.get("multichip_fail", 0),
        "sweep_errors": sorted(k for k in out if k.endswith("_error")),
        "wrote": os.path.basename(path),
    }
    print(json.dumps(summary))
    failed = (summary["n_fail"] or summary["multichip_fail"]
              or summary["sweep_errors"])
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
