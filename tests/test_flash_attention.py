"""Flash attention kernel vs unfused reference.

Mirrors the reference test strategy (SURVEY.md §4): fused kernel vs pure
framework implementation over dtype/shape/flag grids
(apex/contrib/test/multihead_attn/, apex/contrib/test/fmha/test_fmha.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import (
    _VMEM_BUDGET,
    _block_sizes,
    _head_pad,
    _vmem_bytes,
    flash_attention,
    mha_reference,
)

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(rng, b, h, sq, sk, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, h, sk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 2, 64, 64, 32), (2, 2, 100, 100, 64),
                                   (1, 1, 72, 136, 40)])
def test_forward_matches_reference(rng, dtype, causal, shape):
    b, h, sq, sk, d = shape
    q, k, v = _qkv(rng, b, h, sq, sk, d, dtype)
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32),
        atol=TOLS[dtype], rtol=TOLS[dtype])


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(rng, causal):
    q, k, v = _qkv(rng, 2, 2, 72, 72, 32, jnp.float32)

    g = jax.grad(lambda *a: (flash_attention(*a, causal=causal) ** 2).sum(),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (mha_reference(*a, causal=causal) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=5e-4)


@pytest.mark.slow
def test_bias_and_cross_attention(rng):
    b, h, sq, sk, d = 2, 2, 40, 88, 32
    q, k, v = _qkv(rng, b, h, sq, sk, d, jnp.float32)
    bias = jnp.asarray(rng.standard_normal((1, h, sq, sk)), jnp.float32)
    out = flash_attention(q, k, v, bias=bias)
    ref = mha_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda q: (flash_attention(q, k, v, bias=bias) ** 2).sum())(q)
    gr = jax.grad(lambda q: (mha_reference(q, k, v, bias=bias) ** 2).sum())(q)
    np.testing.assert_allclose(g, gr, atol=5e-5, rtol=5e-4)


def test_segment_ids_varlen(rng):
    """Packed-sequence masking (reference fmha cu_seqlens equivalent)."""
    b, h, s, d = 2, 2, 96, 32
    q, k, v = _qkv(rng, b, h, s, s, d, jnp.float32)
    seg = jnp.asarray(rng.integers(0, 3, (b, s)), jnp.int32)
    seg = jnp.sort(seg, axis=1)  # packed layout: contiguous segments
    out = flash_attention(q, k, v, segment_ids=seg)
    ref = mha_reference(q, k, v, segment_ids=seg)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_block_size_invariance(rng):
    q, k, v = _qkv(rng, 1, 2, 256, 256, 32, jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    b_ = flash_attention(q, k, v, causal=True, block_q=64, block_k=256)
    np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-5)


# (sq, sk, head width, itemsize, bias) -> the tile the rule picks, for every
# flash call the benchmark's cells make and the rule's edges. A change of
# any tile is a change of the traced program: a ``perf_opt`` PR's, measured
# (PERF.md section 6, PR 36 has the sweep these came from).
_RULE_CASES = {
    "bert_b8h16_s512_d64_segment_ids": (512, 512, 64, 2, False, (512, 512)),
    "gpt2_prompt_96": (96, 96, 64, 2, False, (96, 128)),
    "gpt2_prompt_160": (160, 160, 64, 2, False, (160, 256)),
    "gpt2_prompt_256": (256, 256, 64, 2, False, (256, 256)),
    "gpt2_prompt_384": (384, 384, 64, 2, False, (384, 384)),
    "gpt2_prompt_512": (512, 512, 64, 2, False, (512, 512)),
    "gpt2_prompt_768": (768, 768, 64, 2, False, (384, 384)),
    **{f"glm_prompt_{n}_d256": (n, n, 256, 2, False, (512, 512))
       for n in (1024, 2048, 4096, 8192, 16384)},
    **{f"mellum_prompt_{n}_d128_window1024": (n, n, 128, 2, False, (512, 512))
       for n in (512, 4096, 8192, 16384)},
    # edges
    "length_512_does_not_divide_640": (640, 640, 64, 2, False, (128, 128)),
    "length_512_does_not_divide_1152": (1152, 1152, 64, 2, False, (384, 384)),
    "length_512_does_not_divide_1280": (1280, 1280, 128, 2, False, (256, 256)),
    "ragged_100": (100, 100, 64, 2, False, (104, 128)),
    "ragged_200_pads_to_256_as_before": (200, 200, 64, 2, False, (200, 256)),
    "cross_attention_40_over_88": (40, 88, 32, 4, False, (40, 128)),
    "cross_attention_long_keys": (64, 4096, 64, 2, False, (64, 512)),
    "head_width_40_is_no_multiple_of_8": (512, 512, 40, 2, False, (512, 512)),
    "bias_d64": (512, 512, 64, 2, True, (512, 512)),
    "bias_d256_fills_the_budget": (2048, 2048, 256, 2, True, (512, 512)),
    "bias_d256_float32_gives_up_q_rows": (2048, 2048, 256, 4, True, (256, 512)),
    "backward_d256": (4096, 4096, 256, 2, False, (512, 512)),
    "float32_d128": (1024, 1024, 128, 4, False, (512, 512)),
    "head_width_512_gives_up_q_rows": (1024, 1024, 512, 2, False, (256, 512)),
    "head_width_2048_gives_up_both": (1024, 1024, 2048, 2, False, (128, 128)),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_tile_rule_at_the_cells_shapes_and_its_edges(case):
    sq, sk, d, itemsize, bias, want = _RULE_CASES[case]
    bq, bk = _block_sizes(sq, sk, d=d, itemsize=itemsize, bias=bias)
    assert (bq, bk) == want
    assert bq % 8 == 0 and bk % 128 == 0
    # pads no further than tiles of 128 did
    was_q = min(128, -(-sq // 8) * 8)
    assert -(-sq // bq) * bq <= -(-sq // was_q) * was_q
    assert -(-sk // bk) * bk <= -(-sk // 128) * 128
    d_pad = _head_pad(d)
    assert d_pad % 128 == 0 and 0 <= d_pad - d < 128
    if case != "head_width_2048_gives_up_both":   # nothing fits: smallest
        assert _vmem_bytes(bq, bk, d_pad, itemsize, bias) <= _VMEM_BUDGET


def test_tile_rule_takes_a_callers_tile_as_given():
    assert _block_sizes(2048, 2048, 64, 256, d=64) == (64, 256)
    assert _block_sizes(2048, 2048, None, 256, d=64) == (512, 256)
    assert _block_sizes(2048, 2048, 1024, None, d=256) == (1024, 128)


def _grads(fn, q, k, v):
    return jax.grad(lambda *a: (fn(*a).astype(jnp.float32) ** 2).sum(),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", ["segment_ids_512", "causal_384",
                                  "window_200_of_768", "dropout_512"])
def test_default_tile_parity_fwd_dq_dk_dv(rng, case):
    """Forward, dq, dk and dv at the tile the RULE picks (one 512- or
    384-long tile a side where the old default cut 128 x 128) against the
    dense reference; dropout, whose mask the reference cannot draw, against
    the same call on 128 x 128 tiles (the mask is a hash of positions, so
    it must not know the tile)."""
    s, tile = {"segment_ids_512": (512, 512), "causal_384": (384, 384),
               "window_200_of_768": (768, 384),
               "dropout_512": (512, 512)}[case]
    q, k, v = _qkv(rng, 1, 2, s, s, 64, jnp.float32)
    kw = {}
    if case == "segment_ids_512":
        cut = jnp.asarray(rng.integers(s // 2, s, (1, 1)))
        kw["segment_ids"] = (jnp.arange(s)[None] >= cut).astype(jnp.int32)
    elif case == "causal_384":
        kw["causal"] = True
    elif case == "window_200_of_768":
        kw.update(causal=True, window=200)
    else:
        kw.update(dropout_rate=0.2, dropout_seed=11)
    assert _block_sizes(s, s, d=64, itemsize=4) == (tile, tile)
    fused = lambda *a: flash_attention(*a, **kw)
    if case == "dropout_512":
        ref = lambda *a: flash_attention(*a, block_q=128, block_k=128, **kw)
    else:
        ref = lambda *a: mha_reference(*a, **kw)
    np.testing.assert_allclose(fused(q, k, v), ref(q, k, v),
                               atol=3e-5, rtol=3e-5)
    for a, b_ in zip(_grads(fused, q, k, v), _grads(ref, q, k, v)):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=5e-4)


def _np_keep(bh, s1, s2, rate, seed):
    """Reimplementation of the kernel's counter-based dropout hash."""
    rows = np.arange(s1, dtype=np.uint32)[:, None] * np.uint32(0x9E3779B1)
    cols = np.arange(s2, dtype=np.uint32)[None, :] * np.uint32(0x85EBCA77)
    with np.errstate(over="ignore"):
        x = rows + cols + np.uint32(bh) * np.uint32(0xC2B2AE3D) + np.uint32(seed)
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    thr = np.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return (x >= thr).astype(np.float32) / (1.0 - rate)


@pytest.mark.slow
def test_dropout_exact_vs_explicit_mask(rng):
    """Fwd AND bwd must equal an explicitly-masked softmax with the same
    keep mask (reference: fused softmax-dropout in fast_multihead_attn)."""
    b, h, s, d = 1, 2, 64, 32
    rate, seed = 0.3, 7
    q, k, v = _qkv(rng, b, h, s, s, d, jnp.float32)
    keep = jnp.stack([
        jnp.stack([jnp.asarray(_np_keep(bi * h + hi, s, s, rate, seed))
                   for hi in range(h)]) for bi in range(b)])

    def ref_drop(q, k, v):
        p = jax.nn.softmax(
            jnp.einsum("bhqd,bhkd->bhqk", q, k) / (d ** 0.5), -1) * keep
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    fused = lambda q, k, v: flash_attention(
        q, k, v, dropout_rate=rate, dropout_seed=seed)
    np.testing.assert_allclose(fused(q, k, v), ref_drop(q, k, v),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda *a: (fused(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: (ref_drop(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=5e-4)


def test_dropout_traced_seed_jit(rng):
    """Seed is a traced scalar: varying it must not recompile or freeze."""
    q, k, v = _qkv(rng, 1, 1, 32, 32, 16, jnp.float32)

    @jax.jit
    def run(seed):
        return flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=seed)

    a = run(jnp.int32(1))
    b_ = run(jnp.int32(1))
    c = run(jnp.int32(2))
    assert jnp.array_equal(a, b_)
    assert not jnp.array_equal(a, c)


def test_fully_masked_rows_output_zero(rng):
    """Rows with no live keys must output exactly 0 (and zero grads), not a
    uniform average over padded keys — regression for the finite-fill
    degenerate case."""
    # causal cross-attention with q_len > kv_len: first rows see no keys
    q, k, v = _qkv(rng, 1, 1, 64, 32, 16, jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(out[:, :, :31] == 0.0))  # offset = kv-q = -32

    # segment id present in q but absent in kv
    sq = jnp.zeros((1, 64), jnp.int32).at[:, -8:].set(9)
    sk_ids = jnp.zeros((1, 32), jnp.int32)
    out = flash_attention(q, k, v, segment_ids=sq, kv_segment_ids=sk_ids)
    ref = mha_reference(q, k, v, segment_ids=sq, kv_segment_ids=sk_ids)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    assert bool(jnp.all(out[:, :, -8:] == 0.0))
    g = jax.grad(lambda v: (flash_attention(
        q, k, v, segment_ids=sq, kv_segment_ids=sk_ids)[:, :, -8:] ** 2).sum())(v)
    assert bool(jnp.all(g == 0.0))


def test_long_sequence_no_cap(rng):
    """The reference fmha caps seqlen at 512; this kernel must not."""
    q, k, v = _qkv(rng, 1, 1, 2048, 2048, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("kvh,causal", [(1, False), (2, True)])
def test_gqa_native_kv_heads(rng, kvh, causal):
    """GQA/MQA: kv_heads < heads handled by kernel index maps (no repeated
    K/V in HBM). Forward vs the repeat-based reference; grads vs the
    jnp.repeat formulation (whose VJP is the same per-group sum)."""
    b, h, s, d = 2, 4, 64, 32
    rep = h // kvh
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)

    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_native(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_repeat(q, k, v):
        return jnp.sum(flash_attention(q, jnp.repeat(k, rep, axis=1),
                                       jnp.repeat(v, rep, axis=1),
                                       causal=causal) ** 2)

    gn = jax.grad(loss_native, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_repeat, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gn, gr):
        assert a.shape == r.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_gqa_rejects_non_divisible(rng):
    q = jnp.zeros((1, 6, 16, 32), jnp.float32)
    k = jnp.zeros((1, 4, 16, 32), jnp.float32)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, k)


@pytest.mark.slow
@pytest.mark.parametrize("window,s", [(16, 128), (64, 200), (1, 64)])
def test_sliding_window_matches_reference(rng, window, s):
    """Mistral-style causal sliding window: parity vs the masked dense
    reference in fwd AND grads (the block-skip must not drop live tiles)."""
    b, h, d = 1, 2, 32
    q, k, v = _qkv(rng, b, h, s, s, d, jnp.float32)

    out = flash_attention(q, k, v, causal=True, window=window)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_k(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       window=window) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True,
                                     window=window) ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_sliding_window_with_gqa(rng):
    """window composes with GQA kv-head indexing."""
    b, h, kvh, s, d = 1, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=32)
    ref = mha_reference(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_sliding_window_requires_causal(rng):
    q, k, v = _qkv(rng, 1, 1, 16, 16, 32, jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)


@pytest.mark.slow
@pytest.mark.parametrize("window", [8, 24, 56, 200])
def test_sliding_window_banded_grid_small_blocks(rng, window):
    """Small blocks force multi-block bands with edge clamping: the
    band-restricted grid (dead blocks don't exist, saving DMA too) must
    match the dense reference in fwd and all grads."""
    b, h, s, d = 1, 2, 256, 32
    q, k, v = _qkv(rng, b, h, s, s, d, jnp.float32)
    kw = dict(causal=True, window=window, block_q=32, block_k=32)

    out = flash_attention(q, k, v, **kw)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    gk = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True, window=window) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.slow
def test_flash_property_fuzz_vs_reference(rng):
    """Property fuzz (hypothesis): random (shape, causal, window, kv_heads,
    block sizes) must match the dense reference in forward. Catches band /
    GQA / padding edge interactions no enumerated grid covers."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None, derandomize=True)
    @given(
        b=st.integers(1, 2),
        h_pow=st.integers(0, 2),          # heads in {1, 2, 4}
        kv_div=st.integers(0, 2),         # kv_heads = heads / 2**kv_div
        sq=st.integers(9, 150),
        d=st.sampled_from([8, 32, 40]),
        causal=st.booleans(),
        window=st.one_of(st.none(), st.integers(1, 200)),
        bq=st.sampled_from([None, 16, 32]),
    )
    def check(b, h_pow, kv_div, sq, d, causal, window, bq):
        h = 2 ** h_pow
        kvh = max(1, h >> kv_div)   # power-of-two divisor of h by construction
        if window is not None and not causal:
            causal = True
        local = np.random.default_rng(b * 1000 + sq)
        q = jnp.asarray(local.standard_normal((b, h, sq, d)), jnp.float32)
        k = jnp.asarray(local.standard_normal((b, kvh, sq, d)), jnp.float32)
        v = jnp.asarray(local.standard_normal((b, kvh, sq, d)), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bq)
        ref = mha_reference(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    check()
