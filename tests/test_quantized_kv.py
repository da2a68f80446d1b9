"""Quantized KV pages (docs/serving.md "Quantized KV pages"): int8/fp8
K/V in the page pool with per-(page, kv_head) symmetric f32 scales,
dequantized inside the paged-attention kernel.

Invariant tier (fast): the dtype-resolution contract and its NAMED
errors (no silent fp32 fallback), the >= 1.9x fixed-budget slot-capacity
pin (the acceptance number), ``page_bytes`` against the bytes the pool
holds per page (int8 <= 0.55x), kernel parity against the
dequantizing reference at s=1 and s>1, prefill/append quantization error
bounds, requantize-on-grow's full-page bit-stability (the invariant
prefix sharing and preemption spill lean on), defrag's exact scale
remap, and shared-allocation scale semantics (shared pages keep their
scales, fresh private pages reset to 0).

Engine tier (slow): greedy decode through the real engines — int8 and
fp8 pools vs the fp pool on GPT (s=1, speculative s>1, chunked prefill),
windowed Llama, TP=2 token identity vs the single-chip int8 engine, and
the frontend's preemption spill -> resume path over a quantized pool.
Token-level agreement with the fp engine is TOLERANCE-pinned (first
tokens exact — they come off the prefill forward pass, which never reads
the pool — plus a floor on fully-identical requests): quantization
legitimately perturbs logits by more than a tiny random-init model's
argmax gaps, so exact identity across dtypes is not the contract.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import (generate, layer_cache,
                                        update_paged_layer_cache)
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.ops.paged_attention import (paged_attention,
                                          paged_attention_reference)
from apex_tpu.ops.quant import (is_quantized_kv, kv_qmax, kv_quantize,
                                resolve_kv_dtype)
from apex_tpu.serving import (PagedDecodeEngine, Request,
                              alloc_slot, alloc_slot_shared,
                              init_paged_cache, prefill_into_pages,
                              release_slot)
from apex_tpu.serving.kv_pool import (defrag_map, max_slots_for_pool_bytes,
                                      page_bytes)
from apex_tpu.serving.scheduler import generate_paged

PS = 8

_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")


def _dequant_layer(lc):
    """Full-precision view of a (possibly quantized) layer's pool."""
    k, v = lc["k_pages"], lc["v_pages"]
    if "k_scales" not in lc:
        return np.asarray(k, np.float32), np.asarray(v, np.float32)
    return (np.asarray(k, np.float32)
            * np.asarray(lc["k_scales"])[:, :, None, None],
            np.asarray(v, np.float32)
            * np.asarray(lc["v_scales"])[:, :, None, None])


# --- invariant tier ----------------------------------------------------------


def test_resolve_kv_dtype_contract():
    assert resolve_kv_dtype(None) is None
    dt, qmax = resolve_kv_dtype("int8")
    assert dt == jnp.int8 and qmax == 127.0
    assert resolve_kv_dtype(jnp.int8) == (jnp.int8, 127.0)
    if _HAS_FP8:
        for alias in ("fp8", "e4m3", jnp.float8_e4m3fn):
            dt, qmax = resolve_kv_dtype(alias)
            assert dt == jnp.float8_e4m3fn and qmax == 448.0
    # NAMED error, never a silent full-precision fallback
    with pytest.raises(ValueError, match="kv-dtype-unsupported"):
        resolve_kv_dtype("int4")
    with pytest.raises(ValueError, match="kv-dtype-unsupported"):
        kv_qmax(jnp.bfloat16)
    assert is_quantized_kv(jnp.int8)
    assert not is_quantized_kv(jnp.bfloat16)


def test_kv_quantize_roundtrip_bounds():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((5, 4, PS, 16)).astype(np.float32) * 3.0
    q, scale = kv_quantize(x, jnp.int8, 127.0, axes=(2, 3))
    deq = np.asarray(q, np.float32) * np.asarray(scale)
    # symmetric int8: error bounded by half an LSB of each group's grid
    assert np.all(np.abs(deq - x) <= np.asarray(scale) / 2 + 1e-7)
    # an all-zero group round-trips exactly through scale 0
    z, zscale = kv_quantize(np.zeros((1, 1, PS, 16), np.float32),
                            jnp.int8, 127.0, axes=(2, 3))
    assert float(np.abs(np.asarray(z)).max()) == 0.0
    assert float(np.asarray(zscale).max()) == 0.0


def test_named_errors_no_silent_fallback(rng):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    # kv_dtype without the paged path has no pool to quantize
    with pytest.raises(ValueError, match="kv-dtype-unsupported"):
        generate(model, v, prompt, max_new_tokens=2, kv_dtype="int8")
    # a quantized pool's page dtype IS the quantized dtype
    with pytest.raises(ValueError, match="kv-dtype-conflict"):
        init_paged_cache(cfg, num_slots=2, num_pages=8, page_size=PS,
                         dtype=jnp.bfloat16, kv_dtype="int8")
    # the engine rejects bad dtypes EAGERLY, at construction
    with pytest.raises(ValueError, match="kv-dtype-unsupported"):
        PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                          kv_dtype="int4")
    # speculative decode: the draft pool must mirror the target pool
    with pytest.raises(ValueError, match="kv-dtype-mismatch"):
        PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                          draft_model=model, draft_variables=v,
                          draft_len=2, kv_dtype="int8",
                          draft_kv_dtype=None)


def test_slot_capacity_and_page_byte_pins():
    """The acceptance numbers: at a FIXED pool-byte budget the int8 pool
    admits >= 1.9x the slots of the bf16 pool, and one int8 page (scales
    included) costs <= 0.55x a bf16 page."""
    from apex_tpu.models.gpt import gpt2_small_config

    for cfg in (gpt_tiny_config(), gpt2_small_config(dtype=jnp.bfloat16)):
        fp_page = page_bytes(cfg, 16)
        q_page = page_bytes(cfg, 16, kv_dtype="int8")
        assert q_page <= 0.55 * fp_page, (q_page, fp_page)
        pps = 32
        budget = fp_page * (64 * pps + 1)       # what 64 fp slots cost
        fp_slots = max_slots_for_pool_bytes(cfg, budget, pages_per_slot=pps)
        q_slots = max_slots_for_pool_bytes(cfg, budget, pages_per_slot=pps,
                                           kv_dtype="int8")
        assert fp_slots >= 64
        assert q_slots >= 1.9 * fp_slots, (q_slots, fp_slots)
        if _HAS_FP8:
            f8_slots = max_slots_for_pool_bytes(
                cfg, budget, pages_per_slot=pps, kv_dtype="fp8")
            assert f8_slots == q_slots          # same 1-byte pages


def test_page_bytes_is_what_the_pool_holds_per_page():
    """``kv_pool.page_bytes`` against the ACTUAL pool avals: the bytes
    every layer's tensors hold for one page (an int8 pool's scale rows
    included) are exactly what it states, and the int8 page costs
    <= 0.55x the unquantized one."""
    cfg = gpt_tiny_config()
    num_pages = 33

    def held_per_page(kv_dtype):
        pool = jax.eval_shape(
            lambda: init_paged_cache(cfg, num_slots=4, num_pages=num_pages,
                                     page_size=16, max_pages_per_seq=16,
                                     kv_dtype=kv_dtype))
        held = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(pool["layers"]))
        assert held % num_pages == 0
        return held // num_pages

    fp_bytes, q_bytes = held_per_page(None), held_per_page("int8")
    assert fp_bytes == page_bytes(cfg, 16)
    assert q_bytes == page_bytes(cfg, 16, kv_dtype="int8")
    assert q_bytes <= 0.55 * fp_bytes, (q_bytes, fp_bytes)


@pytest.mark.parametrize("kv_dtype,s_q",
                         [("int8", 1), ("int8", 4), ("fp8", 1)])
def test_kernel_parity_vs_dequant_reference(kv_dtype, s_q):
    """The Pallas kernel's in-VMEM dequant matches the dense reference
    that dequantizes the gathered pages in fp32 — s=1 decode and the
    s>1 spec-verify/chunked-prefill query block."""
    if kv_dtype == "fp8" and not _HAS_FP8:
        pytest.skip("no float8_e4m3fn in this build")
    dt, qmax = resolve_kv_dtype(kv_dtype)
    rng = np.random.default_rng(1)
    b, h, kv, d, npg, mp = 3, 8, 4, 64, 25, 6
    q = jnp.asarray(rng.standard_normal((b, h, s_q, d)), jnp.float32)
    kq, ks = kv_quantize(rng.standard_normal((npg, kv, 16, d)), dt, qmax,
                         axes=(2, 3))
    vq, vs = kv_quantize(rng.standard_normal((npg, kv, 16, d)), dt, qmax,
                         axes=(2, 3))
    ks, vs = ks[:, :, 0, 0], vs[:, :, 0, 0]
    bt = jnp.asarray(rng.integers(1, npg, (b, mp)), jnp.int32)
    ln = jnp.asarray([37, 80, 12], jnp.int32)
    out = paged_attention(q, kq, vq, bt, ln, k_scales=ks, v_scales=vs)
    ref = paged_attention_reference(q, kq, vq, bt, ln,
                                    k_scales=ks, v_scales=vs)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_prefill_quantizes_and_append_requantizes(rng):
    """Prefill scatters an exact per-page quantization (fresh pages have
    scale 0 = empty); the decode append requantizes-on-grow with bounded
    error; FULL pages never change under appends to other slots — the
    bit-stability invariant prefix sharing and preemption spill need."""
    cfg = gpt_tiny_config()
    kv, d = cfg.num_kv_heads if hasattr(cfg, "num_kv_heads") \
        else cfg.num_heads, cfg.head_dim
    cache = init_paged_cache(cfg, num_slots=2, num_pages=12, page_size=PS,
                             kv_dtype="int8")
    cache = alloc_slot(cache, 0, 4)              # 4th page for the spill
    s0 = 2 * PS + 3                              # 2 full pages + 3 tail
    contig = [{"k": jnp.asarray(rng.standard_normal((1, kv, 3 * PS, d)),
                                jnp.float32),
               "v": jnp.asarray(rng.standard_normal((1, kv, 3 * PS, d)),
                                jnp.float32)}
              for _ in cache["layers"]]
    cache = prefill_into_pages(cache, 0, contig, s0)
    row = np.asarray(cache["block_tables"][0])
    for li, lc0 in enumerate(cache["layers"]):
        kd, _ = _dequant_layer(lc0)
        ref = np.asarray(contig[li]["k"][0], np.float32)   # (kv, 3ps, d)
        scale = np.asarray(lc0["k_scales"])[row[:3]]       # (3, kv)
        for pg in range(3):
            n = min(s0 - pg * PS, PS)
            got = kd[row[pg], :, :n, :]
            want = ref[:, pg * PS:pg * PS + n, :].transpose(0, 1, 2)
            err = np.abs(got - want.reshape(got.shape))
            assert np.all(err <= scale[pg][:, None, None] / 2 + 1e-6)

    # decode append across the page-2 boundary (3 tail slots + spill)
    lc = layer_cache(cache, 0)
    before_full = np.asarray(lc["k_pages"])[row[:2]].copy()
    chunk_k = jnp.asarray(rng.standard_normal((2, kv, 6, d)), jnp.float32)
    chunk_v = jnp.asarray(rng.standard_normal((2, kv, 6, d)), jnp.float32)
    lc2 = update_paged_layer_cache(lc, chunk_k, chunk_v)
    kd2, _ = _dequant_layer(lc2)
    sc2 = np.asarray(lc2["k_scales"])
    # the 3 new tokens in page 2 and 3 in page 3 round-trip within their
    # page's (possibly grown) grid
    for i in range(6):
        pos = s0 + i
        pg, off = row[pos // PS], pos % PS
        err = np.abs(kd2[pg, :, off, :]
                     - np.asarray(chunk_k[0, :, i, :], np.float32))
        assert np.all(err <= sc2[pg][:, None] / 2 + 1e-6), (i, err.max())
    # slot 0's FULL pages are bit-identical after its own boundary
    # append (entries below len // ps are never members of the grow set)
    np.testing.assert_array_equal(
        np.asarray(lc2["k_pages"])[row[:2]], before_full)


def test_full_pages_bitstable_under_other_slots(rng):
    """Appending to slot 1 never perturbs slot 0's pages OR scales —
    quantized pages a prefix cache (or a preemption spill) holds are
    immutable no matter what the rest of the pool does."""
    cfg = gpt_tiny_config()
    kv, d = cfg.num_heads, cfg.head_dim
    cache = init_paged_cache(cfg, num_slots=2, num_pages=12, page_size=PS,
                             kv_dtype="int8")
    cache = alloc_slot(cache, 0, 2)
    cache = alloc_slot(cache, 1, 2)
    contig = [{"k": jnp.asarray(rng.standard_normal((1, kv, 2 * PS, d)),
                                jnp.float32),
               "v": jnp.asarray(rng.standard_normal((1, kv, 2 * PS, d)),
                                jnp.float32)}
              for _ in cache["layers"]]
    cache = prefill_into_pages(cache, 0, contig, 2 * PS)
    cache = prefill_into_pages(cache, 1, contig, PS + 1)
    row0 = np.asarray(cache["block_tables"][0])
    lc = layer_cache(cache, 0)
    pages0 = np.asarray(lc["k_pages"])[row0[:2]].copy()
    scales0 = np.asarray(lc["k_scales"])[row0[:2]].copy()

    # grow slot 1 only: mask slot 0 out by pointing its chunk at len 0
    # via a null-page table row — the engine's real masking; here simply
    # append a chunk whose slot-0 rows duplicate slot 1's (slot 0's len
    # advances but its writes land at its own tail pages, not pages0)
    chunk = jnp.asarray(rng.standard_normal((2, kv, 4, d)), jnp.float32)
    lc2 = update_paged_layer_cache(lc, chunk, chunk)
    np.testing.assert_array_equal(np.asarray(lc2["k_pages"])[row0[:2]],
                                  pages0)
    np.testing.assert_array_equal(np.asarray(lc2["k_scales"])[row0[:2]],
                                  scales0)


def test_defrag_remaps_scales_with_pages(rng):
    """defrag_map's permutation moves each page's scale with its
    contents: the dequantized pool is BIT-identical before and after
    compaction (for live pages, through the remap)."""
    cfg = gpt_tiny_config()
    kv, d = cfg.num_heads, cfg.head_dim
    cache = init_paged_cache(cfg, num_slots=2, num_pages=16, page_size=PS,
                             kv_dtype="int8")
    cache = alloc_slot(cache, 0, 3)
    contig = [{"k": jnp.asarray(rng.standard_normal((1, kv, 3 * PS, d)),
                                jnp.float32),
               "v": jnp.asarray(rng.standard_normal((1, kv, 3 * PS, d)),
                                jnp.float32)}
              for _ in cache["layers"]]
    cache = prefill_into_pages(cache, 0, contig, 3 * PS)
    row = np.asarray(cache["block_tables"][0])
    lc = layer_cache(cache, 0)
    kd_before, vd_before = _dequant_layer(lc)

    new_cache, new_idx = defrag_map(cache)
    new_idx = np.asarray(new_idx)
    new_row = np.asarray(new_cache["block_tables"][0])
    np.testing.assert_array_equal(new_row[:3], new_idx[row[:3]])
    lc2 = layer_cache(new_cache, 0)
    kd_after, vd_after = _dequant_layer(lc2)
    np.testing.assert_array_equal(kd_after[new_row[:3]], kd_before[row[:3]])
    np.testing.assert_array_equal(vd_after[new_row[:3]], vd_before[row[:3]])
    # raw pages and scales followed the same permutation
    np.testing.assert_array_equal(
        np.asarray(lc2["k_scales"])[new_row[:3]],
        np.asarray(lc["k_scales"])[row[:3]])


def test_shared_alloc_scale_semantics(rng):
    """alloc_slot_shared on a quantized pool: shared prefix pages KEEP
    their scales (shared pages are shared scales — sharing stays
    dtype-blind), fresh private pages reset to scale 0; release_slot's
    keep-mask spill leaves kept pages' contents and scales untouched, so
    a resume (re-share) reads bit-identical K/V — the preemption
    spill -> resume invariant at pool level."""
    cfg = gpt_tiny_config()
    kv, d = cfg.num_heads, cfg.head_dim
    cache = init_paged_cache(cfg, num_slots=2, num_pages=12, page_size=PS,
                             kv_dtype="int8")
    cache = alloc_slot(cache, 0, 2)
    contig = [{"k": jnp.asarray(rng.standard_normal((1, kv, 2 * PS, d)),
                                jnp.float32),
               "v": jnp.asarray(rng.standard_normal((1, kv, 2 * PS, d)),
                                jnp.float32)}
              for _ in cache["layers"]]
    cache = prefill_into_pages(cache, 0, contig, 2 * PS)
    row = np.asarray(cache["block_tables"][0])
    lc = layer_cache(cache, 0)
    pages = np.asarray(lc["k_pages"])[row[:2]].copy()
    scales = np.asarray(lc["k_scales"])[row[:2]].copy()

    # spill: keep both full pages (they become prefix-cache property)
    keep = np.zeros((cache["block_tables"].shape[1],), bool)
    keep[:2] = True
    cache = release_slot(cache, 0, jnp.asarray(keep))

    # resume: share the spilled pages back into a slot + 1 private page
    shared_row = jnp.zeros((cache["block_tables"].shape[1],), jnp.int32)
    shared_row = shared_row.at[0].set(int(row[0])).at[1].set(int(row[1]))
    cache = alloc_slot_shared(cache, 1, shared_row, 2, 1)
    assert np.asarray(cache["page_ref"])[row[:2]].tolist() == [1, 1]
    lc2 = layer_cache(cache, 0)
    np.testing.assert_array_equal(np.asarray(lc2["k_pages"])[row[:2]],
                                  pages)
    np.testing.assert_array_equal(np.asarray(lc2["k_scales"])[row[:2]],
                                  scales)
    # the fresh PRIVATE page's scale reset to 0 ("holds nothing yet")
    priv = int(np.asarray(cache["block_tables"][1])[2])
    assert float(np.abs(np.asarray(lc2["k_scales"])[priv]).max()) == 0.0


# --- engine tier -------------------------------------------------------------


def _tiny_engine_setup(rng, seed=0):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(seed),
                   jnp.zeros((1, 8), jnp.int32))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, (n,))))
               for n in (9, 17, 5, 26)]
    return cfg, model, v, prompts


def _agreement(fp, q):
    """(all first tokens equal, count of fully-identical requests)."""
    firsts = all(int(np.asarray(a)[0]) == int(np.asarray(b)[0])
                 for a, b in zip(fp, q))
    ident = sum(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                for a, b in zip(fp, q))
    return firsts, ident


@pytest.mark.slow
def test_engine_greedy_parity_tolerance(rng):
    """int8 and fp8 engines vs the fp engine on the same mixed-length
    workload: every request's FIRST token is exact (prefill logits never
    read the pool) and at least 3 of 4 requests decode token-identically
    at tiny-GPT scale — the tolerance pin, not exact identity."""
    cfg, model, v, prompts = _tiny_engine_setup(rng)
    kw = dict(max_new_tokens=12, num_slots=4, page_size=PS, num_pages=40)
    fp = generate_paged(model, v, prompts, **kw)
    for kv_dtype in ("int8",) + (("fp8",) if _HAS_FP8 else ()):
        q = generate_paged(model, v, prompts, kv_dtype=kv_dtype, **kw)
        firsts, ident = _agreement(fp, q)
        assert firsts, f"{kv_dtype}: first token flipped"
        assert ident >= 3, f"{kv_dtype}: only {ident}/4 identical"


@pytest.mark.slow
def test_engine_s_gt_1_paths_int8(rng):
    """The s>1 query-block paths over a quantized pool: in-engine
    speculative decode (self-draft) and chunked prefill, vs the plain
    int8 engine. Both share the pool dtype; outputs agree at the same
    tolerance bar (requantize-on-grow quantizes on a different chunk
    grid than monolithic prefill, so exact identity is not guaranteed)."""
    cfg, model, v, prompts = _tiny_engine_setup(rng)
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=10)
            for p in prompts]
    plain = PagedDecodeEngine(model, v, num_slots=4, page_size=PS,
                              num_pages=40, kv_dtype="int8")
    outs, _ = plain.run(reqs)

    spec = PagedDecodeEngine(model, v, num_slots=4, page_size=PS,
                             num_pages=40, kv_dtype="int8",
                             draft_model=model, draft_variables=v,
                             draft_len=2)
    s_outs, s_stats = spec.run(reqs)
    assert s_stats["spec_rounds"] >= 1
    firsts, ident = _agreement(outs, s_outs)
    assert firsts and ident >= 3, f"spec: {ident}/4"

    chunked = PagedDecodeEngine(model, v, num_slots=4, page_size=PS,
                                num_pages=40, kv_dtype="int8",
                                prefill_chunk=PS)
    c_outs, _ = chunked.run(reqs)
    firsts, ident = _agreement(outs, c_outs)
    assert firsts and ident >= 3, f"chunked: {ident}/4"


@pytest.mark.slow
def test_llama_windowed_int8(rng):
    """generate(paged=True, kv_dtype=...) through Llama's GQA + sliding
    window band: the quantized run matches the fp paged run at the
    tolerance bar on a rectangular batch."""
    from apex_tpu.models.llama import LlamaModel, llama_tiny_config

    cfg = dataclasses.replace(llama_tiny_config(), sliding_window=PS)
    model = LlamaModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 12)),
                         jnp.int32)
    fp = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                             paged=True, page_size=PS))
    q8 = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                             paged=True, page_size=PS, kv_dtype="int8"))
    assert fp.shape == q8.shape
    np.testing.assert_array_equal(fp[:, :13], q8[:, :13])  # prompt+first
    ident = sum(bool(np.array_equal(a, b)) for a, b in zip(fp, q8))
    assert ident >= 2, f"windowed llama: {ident}/3 rows identical"


@pytest.mark.slow
def test_tp2_int8_token_identity(rng):
    """TP=2 over the quantized pool (scales sharded P(None, model) with
    the head-sharded pages): token-IDENTICAL to the single-chip int8
    engine — sharding must not change the numerics at all."""
    from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                     shard_model_variables, tp_mesh)

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    cfg = gpt_tiny_config()
    if cfg.num_heads % 2:
        pytest.skip("tiny config heads not divisible by 2")
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, (n,))))
               for n in (9, 17, 5)]
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=8)
            for p in prompts]
    single = PagedDecodeEngine(model, v, num_slots=3, page_size=PS,
                               num_pages=33, kv_dtype="int8")
    outs, _ = single.run(reqs)

    tp_cfg = dataclasses.replace(cfg, tensor_parallel_size=2)
    tp_model = GPTModel(tp_cfg)
    mesh = tp_mesh(2)
    tp_vars, _ = shard_model_variables(tp_model, v, mesh)
    tp_engine = TensorParallelPagedEngine(
        tp_model, tp_vars, mesh=mesh, num_slots=3, page_size=PS,
        num_pages=33, kv_dtype="int8")
    tp_outs, _ = tp_engine.run(reqs)
    for i, (a, b) in enumerate(zip(outs, tp_outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"request {i}")


@pytest.mark.slow
def test_prefix_cache_hit_and_evict_int8(rng):
    """The radix prefix cache over an int8 pool: cache hits skip the
    shared pages, pool pressure evicts refcount-0 quantized pages, and a
    re-populated prefix hits again — and EVERY run is token-IDENTICAL to
    the uncached int8 engine (sharing and eviction move page *ids*;
    quantized full pages are bit-stable, so same-dtype identity is
    exact, unlike the cross-dtype tolerance bar)."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sys_p = rng.integers(0, cfg.vocab_size, (2 * PS,)).astype(np.int32)

    def _req(tail_len, max_new):
        return Request(prompt=np.concatenate(
            [sys_p, rng.integers(0, cfg.vocab_size,
                                 (tail_len,)).astype(np.int32)]),
            max_new_tokens=max_new)

    reqs = [_req(int(t), int(m))
            for t, m in zip(rng.integers(3, 12, 4), rng.integers(3, 8, 4))]
    base, _ = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                                num_pages=8, kv_dtype="int8").run(reqs)

    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                               num_pages=8, prefix_cache=True,
                               kv_dtype="int8")
    outs, stats = engine.run(reqs)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert stats["prefix_hits"] >= len(reqs) - 1
    assert stats["prefill_tokens_skipped"] >= (len(reqs) - 1) * 2 * PS

    # pool pressure: a fat distinct-prefix request must evict the cached
    # quantized pages to fit (usable pool is 7 pages)
    fat = Request(prompt=rng.integers(0, cfg.vocab_size,
                                      (5 * PS,)).astype(np.int32),
                  max_new_tokens=PS)
    (fat_base,), _ = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                                       num_pages=8, kv_dtype="int8"
                                       ).run([fat])
    (fat_out,), s_fat = engine.run([fat])
    np.testing.assert_array_equal(np.asarray(fat_base), np.asarray(fat_out))
    assert s_fat["evicted_pages"] >= 1

    # re-populate, then hit again — still bit-identical to uncached
    _, _ = engine.run([reqs[0]])
    (out2,), s2 = engine.run([reqs[0]])
    np.testing.assert_array_equal(np.asarray(base[0]), np.asarray(out2))
    assert s2["prefix_hits"] == 1


@pytest.mark.slow
def test_frontend_preemption_over_quantized_pool(rng):
    """The preemption spill -> resume path over an int8 pool: pin every
    slot with low-priority work, land a high-priority arrival, and the
    policy must preempt-and-spill (quantized pages move INTO the prefix
    cache by page id — scales ride along, nothing is copied) and later
    resume to completion with full-length outputs."""
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.policy import PriorityDeadlinePolicy

    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                               num_pages=40, prefix_cache=True,
                               kv_dtype="int8")
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, 24).astype(
        np.int32), max_new_tokens=16, priority=0) for _ in range(2)]
    engine.run(low)                                    # warm the buckets
    fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(low)]
    while fe.queue_depth:
        fe.pump()
    for _ in range(3):
        fe.pump()
    handles.append(fe.submit(
        Request(prompt=rng.integers(0, cfg.vocab_size, 24).astype(
            np.int32), max_new_tokens=4, priority=9, deadline_ms=2000.0),
        request_id=99))
    fe.drain()
    stats = fe.stats()
    assert stats["preemptions"] >= 1
    assert stats["resumes"] >= 1
    want = [16, 16, 4]
    for h, n in zip(handles, want):
        assert np.asarray(h.result()).shape == (n,)
