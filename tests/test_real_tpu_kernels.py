"""On-chip (Mosaic-compiled) Pallas kernel suite at bench-relevant shapes.

VERDICT round-1 weakness 4: all CPU tests run the kernels in interpret
mode, which validates numerics but not Mosaic compilation, layouts, or
VMEM limits — the bug class that bit on-chip in round 1 (M5 VMEM fixes).
This suite runs ONLY with ``APEX_TPU_REAL=1`` on a real TPU backend and
compiles every Pallas kernel at the flagship benchmark's shapes
(seq 512, hidden 1024, vocab 30528, BERT-Large-sized flat buffers),
asserting parity against pure-jnp references computed on the same chip.

    APEX_TPU_REAL=1 python -m pytest tests/test_real_tpu_kernels.py -v
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("APEX_TPU_REAL") != "1",
    reason="real-TPU kernel suite (set APEX_TPU_REAL=1 on a TPU host)")


@pytest.fixture(scope="module")
def tpu():
    dev = jax.devices()[0]
    assert dev.platform != "cpu", (
        "APEX_TPU_REAL=1 but the backend is CPU — kernels would run "
        "interpreted and prove nothing")
    return dev


@pytest.fixture
def rng():
    return np.random.default_rng(0)


SEQ, HIDDEN, VOCAB = 512, 1024, 30528


def test_layer_norm_fwd_bwd_bench_shapes(tpu, rng):
    from apex_tpu.ops.layer_norm import layer_norm

    x = jnp.asarray(rng.standard_normal((8 * SEQ, HIDDEN)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((HIDDEN,)) * 0.1 + 1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((HIDDEN,)) * 0.1, jnp.float32)

    def ref(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-12) * g + b

    y = jax.jit(lambda x: layer_norm(x, g, b, eps=1e-12))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, g, b)),
                               rtol=2e-4, atol=2e-4)

    def loss_k(x, g, b):
        return jnp.sum(layer_norm(x, g, b, eps=1e-12) ** 2)

    def loss_r(x, g, b):
        return jnp.sum(ref(x, g, b) ** 2)

    gk = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(x, g, b)
    gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(x, g, b)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-3, atol=2e-2)


def test_flash_attention_fwd_bwd_seq512(tpu, rng):
    from apex_tpu.ops import flash_attention

    b, h, d = 2, 16, 64
    q = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)

    def ref(q, k, v):
        s = (q.astype(jnp.float32) @ k.astype(jnp.float32).transpose(
            0, 1, 3, 2)) / np.sqrt(d)
        p = jax.nn.softmax(s, axis=-1)
        return (p @ v.astype(jnp.float32)).astype(q.dtype)

    y = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref(q, k, v), np.float32),
                               rtol=5e-2, atol=5e-2)

    def loss_k(q, k, v):
        return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(ref(q, k, v).astype(jnp.float32) ** 2)

    gk = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=1e-1, atol=1e-1)


def test_flash_attention_causal_and_dropout_compile(tpu, rng):
    from apex_tpu.ops import flash_attention

    b, h, d = 2, 8, 64
    q = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    y = jax.jit(lambda q: flash_attention(q, q, q, causal=True,
                                          dropout_rate=0.1,
                                          dropout_seed=7))(q)
    assert np.isfinite(np.asarray(y, np.float32)).all()
    # backward through in-kernel dropout must also compile
    g = jax.jit(jax.grad(lambda q: jnp.sum(
        flash_attention(q, q, q, causal=True, dropout_rate=0.1,
                        dropout_seed=7).astype(jnp.float32))))(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()


def test_xentropy_vocab30528(tpu, rng):
    from apex_tpu.ops import softmax_cross_entropy

    n = 2 * SEQ
    logits = jnp.asarray(rng.standard_normal((n, VOCAB)), jnp.float32)
    labels = jnp.asarray(rng.integers(1, VOCAB, (n,)), jnp.int32)

    out = jax.jit(lambda l: softmax_cross_entropy(l, labels))(logits)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ref = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    g = jax.jit(jax.grad(lambda l: softmax_cross_entropy(l, labels).sum()))(
        logits)
    gr = jax.jit(jax.grad(
        lambda l: (-jnp.take_along_axis(jax.nn.log_softmax(l, -1),
                                        labels[:, None], 1)[:, 0]).sum()))(
        logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-3, atol=1e-4)


def test_scaled_masked_softmax_seq512(tpu, rng):
    from apex_tpu.ops.scaled_softmax import (
        scaled_upper_triang_masked_softmax)

    b, h = 4, 16
    # reference API: 3D (attn_batches, sq, sk) — apex ScaledUpperTriangMaskedSoftmax
    x = jnp.asarray(rng.standard_normal((b * h, SEQ, SEQ)), jnp.bfloat16)
    y = jax.jit(lambda x: scaled_upper_triang_masked_softmax(
        x, scale=0.125))(x)
    y32 = np.asarray(y, np.float32)
    np.testing.assert_allclose(y32.sum(-1), 1.0, rtol=2e-2, atol=2e-2)
    # causal: strictly-upper triangle is zero
    iu = np.triu_indices(SEQ, 1)
    assert np.abs(y32[..., iu[0], iu[1]]).max() < 1e-3


def test_fused_optimizer_kernels_bert_large_size(tpu, rng):
    """Adam + LAMB on a BERT-Large-sized flat buffer (~340M fp32 elems is
    too big for one CPU-style test; use ~32M rows-worth which still spans
    many row tiles and VMEM windows)."""
    from apex_tpu.ops import flat_buffer, optim_kernels

    params = {
        "emb": jnp.asarray(rng.standard_normal((VOCAB, 64)), jnp.float32),
        "w1": jnp.asarray(rng.standard_normal((HIDDEN, HIDDEN)), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal((4 * HIDDEN, HIDDEN)),
                          jnp.float32),
        "b": jnp.asarray(rng.standard_normal((HIDDEN,)), jnp.float32),
    }
    spec = flat_buffer.build_spec(params)
    seg = jnp.asarray(spec.segment_rows())
    p = flat_buffer.flatten(params, spec)
    g = flat_buffer.flatten(
        jax.tree.map(lambda x: 0.01 * jnp.ones_like(x), params), spec)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)

    p2, m2, v2 = jax.jit(lambda g, p, m, v: optim_kernels.adam_update(
        g, p, m, v, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01,
        lr=1e-3, step=1))(g, p, m, v)
    assert np.isfinite(np.asarray(p2)).all()
    # adam step-1 with bias correction: update = g/(|g|+eps) + wd*p
    expect = np.asarray(p) - 1e-3 * (
        0.01 / (0.01 + 1e-8) + 0.01 * np.asarray(p))
    np.testing.assert_allclose(np.asarray(p2), expect, rtol=1e-4, atol=1e-5)

    pl_, ml_, vl_ = jax.jit(
        lambda g, p, m, v: optim_kernels.lamb_update(
            g, p, m, v, seg, spec.num_tensors, beta1=0.9, beta2=0.999,
            eps=1e-6, weight_decay=0.01, lr=1e-3, step=1))(g, p, m, v)
    assert np.isfinite(np.asarray(pl_)).all()

    gnorm, finite, _ = jax.jit(
        lambda g: optim_kernels.global_grad_norm_and_finite(
            g, seg, spec.num_tensors))(g)
    np.testing.assert_allclose(
        float(gnorm), 0.01 * np.sqrt(spec.total_elements), rtol=1e-3)
    assert bool(finite)


def test_group_norm_kernel_path(tpu, rng):
    from apex_tpu.ops.group_norm import group_norm_nhwc, group_norm_reference

    x = jnp.asarray(rng.standard_normal((4, 16, 16, 512)), jnp.bfloat16)
    w = jnp.ones((512,), jnp.float32)
    b = jnp.zeros((512,), jnp.float32)
    y = jax.jit(lambda x: group_norm_nhwc(x, w, b, 4, 1e-5, "silu"))(x)
    ref = group_norm_reference(x, w, b, 4, 1e-5, "silu")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_bert_large_single_train_step(tpu, rng):
    """One full BERT-Large step on-chip: every kernel at exactly the bench
    shapes in one compiled program."""
    from apex_tpu.models import (BertForPreTraining, bert_large_config,
                                 make_pretrain_step, synthetic_batch)
    from apex_tpu.optimizers import FusedLAMB

    cfg = bert_large_config()
    model = BertForPreTraining(cfg)
    batch = synthetic_batch(rng, cfg, 2, SEQ)
    params = model.init(jax.random.PRNGKey(0), batch["input_ids"],
                        batch["token_type_ids"],
                        batch["attention_mask"])["params"]
    step = make_pretrain_step(model)
    opt = FusedLAMB(params, lr=1e-4, weight_decay=0.01)
    loss, grads = step(params, batch, 0)
    params = opt.step(grads)
    jax.block_until_ready(params)
    assert np.isfinite(float(loss))


def test_flash_attention_with_lse_on_chip(tpu, rng):
    """Round-3: the (o, lse) variant that ring attention composes — forward
    parity, and the backward with an lse cotangent (delta_adjust path)."""
    from apex_tpu.ops import flash_attention, flash_attention_with_lse

    b, h, d = 2, 8, 64
    q = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)

    o, lse = jax.jit(flash_attention_with_lse)(q, k, v)
    o_ref = jax.jit(flash_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(lse)).all()

    def f(q):
        o, lse = flash_attention_with_lse(q, k, v)
        return jnp.sum(lse) + jnp.sum(o.astype(jnp.float32))

    g = jax.jit(jax.grad(f))(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()


def test_group_norm_backward_kernel_path(tpu, rng):
    """Round-3: the Pallas GroupNorm backward (one-pass slab kernel) at a
    kernel-eligible diffusion shape, vs autodiff of the jnp reference."""
    from apex_tpu.ops.group_norm import group_norm_nhwc, group_norm_reference

    x = jnp.asarray(rng.standard_normal((2, 16, 16, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512,)) * 0.1 + 1.0, jnp.float32)
    b = jnp.asarray(rng.standard_normal((512,)) * 0.1, jnp.float32)

    gk = jax.jit(jax.grad(
        lambda *a: jnp.sum(group_norm_nhwc(*a, 4, 1e-5, "silu") ** 2),
        argnums=(0, 1, 2)))(x, w, b)
    gr = jax.jit(jax.grad(
        lambda *a: jnp.sum(group_norm_reference(*a, 4, 1e-5, "silu") ** 2),
        argnums=(0, 1, 2)))(x, w, b)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("masking", ["segment_ids", "causal"])
def test_flash_attention_head64_default_tile(tpu, rng, masking):
    """What the rule picks at the training cell's shape (PR 36): one
    (512, 512) tile a head, the 64-wide head padded to 128 lanes, q's
    segment ids as a column. Forward and dq, dk, dv against the same call
    on the 128 x 128 tiles every earlier chip run used."""
    from apex_tpu.ops import flash_attention

    b, h, d = 2, 8, 64
    q = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    if masking == "segment_ids":
        cut = rng.integers(SEQ // 2, SEQ, (b, 1))
        kw = {"segment_ids": jnp.asarray(
            (np.arange(SEQ)[None] >= cut).astype(np.int32))}
    else:
        kw = {"causal": True}

    def run(**tile):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, **kw, **tile
                                           ).astype(jnp.float32) ** 2)
        out = jax.jit(functools.partial(flash_attention, **kw, **tile))(
            q, k, v)
        return out, jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    out, grads = run()
    ref, g_ref = run(block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    for g, r in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_moe_dense_dispatch_compiles(tpu, rng):
    """Round-3: the MoE dispatch/combine einsums + batched expert einsums
    (apex_tpu/transformer/moe/layer.py) compile and differentiate on-chip
    at a realistic token count. Single-chip => dense-dispatch path (the
    all_to_all EP path needs a multi-device axis and is covered by the
    CPU-mesh suite + dryrun)."""
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 1024, 4096, 8, 2, 2048
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=1.25, expert_world_size=1,
                   axis_name="nope")
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.bfloat16)
    v = layer.init(jax.random.PRNGKey(0), x)

    @jax.jit
    def loss_and_grad(p, xx):
        def f(pp):
            y, aux = layer.apply({"params": pp}, xx)
            return jnp.sum(y.astype(jnp.float32) ** 2) + aux.total
        return jax.value_and_grad(f)(p)

    loss, g = loss_and_grad(v["params"], x)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss))
    assert float(jnp.sum(jnp.abs(g["router"]["weight"]))) > 0.0


def test_flash_attention_sliding_window(tpu, rng):
    """Round-3: sliding-window block skipping must compile under Mosaic
    (the extra block_live predicate) and match full-causal where the
    window covers everything."""
    from apex_tpu.ops import flash_attention

    b, h, d = 2, 8, 64
    q = jnp.asarray(rng.standard_normal((b, h, SEQ, d)), jnp.bfloat16)
    full = jax.jit(lambda q: flash_attention(q, q, q, causal=True))(q)
    wide = jax.jit(lambda q: flash_attention(q, q, q, causal=True,
                                             window=SEQ))(q)
    np.testing.assert_allclose(np.asarray(wide, np.float32),
                               np.asarray(full, np.float32),
                               rtol=2e-2, atol=2e-2)
    g = jax.jit(jax.grad(lambda q: jnp.sum(flash_attention(
        q, q, q, causal=True, window=128).astype(jnp.float32) ** 2)))(q)
    assert np.isfinite(np.asarray(g, np.float32)).all()


#: the page write at the serving cells' widths: pool AS HELD (GPT-2
#: large: two 64-wide heads a 128-lane row), the chunk's (heads, width)
#: where a row packs, slots, table width, s, and an admission's bounds
_PAGED_WRITE_CASES = {
    "gpt2l_decode": dict(pool=(729, 10, 16, 128), chunk=(20, 64), n=2,
                         slots=16, mp=64, s=1),
    "gpt2l_verify_s4": dict(pool=(729, 10, 16, 128), chunk=(20, 64), n=2,
                            slots=16, mp=64, s=4),
    "gpt2l_chunk_s16": dict(pool=(729, 10, 16, 128), chunk=(20, 64), n=2,
                            slots=1, mp=64, s=16),
    "gpt2l_admit256": dict(pool=(729, 10, 16, 128), chunk=(20, 64), n=2,
                           slots=1, mp=64, s=256, start=32, stop=201),
    "one_head_a_row_decode": dict(pool=(729, 20, 16, 64), n=2, slots=16,
                                  mp=64, s=1),
    "glm_decode": dict(pool=(4097, 1, 16, 640), n=1, slots=32, mp=128, s=1),
    "glm_admit16k": dict(pool=(4097, 1, 16, 640), n=1, slots=1, mp=2048,
                         s=16384, stop=16001),
    "f32_decode": dict(pool=(257, 8, 16, 128), n=2, slots=8, mp=32, s=1,
                       dtype=jnp.float32),
}


@pytest.mark.parametrize("name", list(_PAGED_WRITE_CASES))
def test_paged_write_matches_the_scatter_on_chip(name, tpu, rng):
    """What the interpreter cannot show: the pipeline prefetches the next
    grid step's page while this one's is written back, in place. Live
    slots own distinct pages (neighbours included), idle slots all name
    page 0; four writes in a row as a decode chunk makes them, then every
    page but 0 against the scatter (into a pool of one head a row), bit
    for bit."""
    from test_paged_write import scatter_reference

    from apex_tpu.ops.paged_write import paged_write, unpack_heads

    c = _PAGED_WRITE_CASES[name]
    num_pages, _, ps, _ = c["pool"]
    heads, d = c.get("chunk", (c["pool"][1], c["pool"][3]))
    pack = c["pool"][3] // d
    slots, mp, s = c["slots"], c["mp"], c["s"]
    dtype = c.get("dtype", jnp.bfloat16)
    bounds = {k: c[k] for k in ("start", "stop") if k in c}
    # consecutive pages, so that neighbours in HBM belong to different
    # slots' steps; slots 1 and 5 idle where there are that many
    per = (num_pages - 1) // slots
    tables = (1 + np.arange(slots)[:, None] + slots * np.arange(
        min(per, mp))[None, :]).astype(np.int32)
    tables = np.pad(tables, ((0, 0), (0, mp - tables.shape[1])))
    lengths = rng.integers(0, ps * min(per, mp) - 4 * s, (slots,)) \
        if s <= ps else np.zeros((slots,), np.int64)
    idle = [b for b in (1, 5) if b < slots and slots > 2]
    tables[idle] = 0
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    pools = [jnp.asarray(rng.standard_normal(c["pool"]), dtype)
             for _ in range(c["n"])]
    rounds = 4 if s <= ps else 1
    chunks = [[jnp.asarray(rng.standard_normal((slots, heads, s, d)), dtype)
               for _ in range(c["n"])] for _ in range(rounds)]

    @functools.partial(jax.jit, static_argnums=0)
    def run(write, pools, chunks):
        for r, chunk in enumerate(chunks):
            pools = write(pools, chunk, tables, lengths + r * s, **bounds)
        return pools

    def reference(pools, chunk, *a, **kw):
        return [scatter_reference(p, x, *a, **kw)
                for p, x in zip(pools, chunk)]

    got = run(paged_write, pools, chunks)
    want = run(reference, [unpack_heads(p, pack) for p in pools], chunks)
    for out, ref, pages in zip(got, want, pools):
        assert out.shape == pages.shape
        np.testing.assert_array_equal(
            np.asarray(unpack_heads(out, pack)[1:], np.float32),
            np.asarray(ref[1:], np.float32))
        assert not np.array_equal(np.asarray(out[1:], np.float32),
                                  np.asarray(pages[1:], np.float32))


@pytest.mark.parametrize("s,window", [(1, None), (4, None), (1, 200)])
def test_packed_paged_attention_matches_reference_on_chip(s, window, tpu,
                                                          rng):
    """GPT-2 large's decode read over the pool as held, two 64-wide heads
    a 128-lane row: the block-diagonal queries must lower under Mosaic
    and give each head its own scores."""
    from apex_tpu.ops.paged_attention import (paged_attention,
                                              paged_attention_reference)
    from apex_tpu.ops.paged_write import unpack_heads

    slots, mp, ps = 16, 64, 16
    k_held, v_held = (jnp.asarray(rng.standard_normal((729, 10, ps, 128)),
                                  jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, 729))[
        :slots * 45].reshape(slots, 45), jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, mp - 45)))
    lengths = jnp.asarray(rng.integers(s, 45 * ps, (slots,)), jnp.int32)
    lengths = lengths.at[3].set(0).at[7].set(45 * ps)
    q = jnp.asarray(rng.standard_normal((slots, 20, s, 64)), jnp.bfloat16)
    got = jax.jit(lambda *a: paged_attention(*a, window=window))(
        q, k_held, v_held, tables, lengths)
    want = paged_attention_reference(
        q, unpack_heads(k_held, 2), unpack_heads(v_held, 2), tables,
        lengths, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)



def _delta_inputs(rng, b, s, hk, h, dk, dv):
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(rng.standard_normal((b, s, hk, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((b, s, hk, dk)))
    v = rng.standard_normal((b, s, h, dv))
    g = -np.log1p(np.exp(rng.standard_normal((b, s, h))))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((b, s, h))))
    state = rng.standard_normal((b, h, dk, dv))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


def test_gated_delta_step_matches_the_recurrence_on_chip(tpu, rng):
    """The decode step's kernel at Qwen3-Next's shapes (32 value heads over
    16 key heads of 128 x 128, a 2 MiB state a slot) under Mosaic: a lane
    of a column broadcast over the tile, reductions over sublanes, the
    state written where it was read. float32 vector work: it is the
    recurrence to rounding."""
    from apex_tpu.ops.gated_delta import (gated_delta_reference,
                                          gated_delta_step)

    q, k, v, g, beta, state = _delta_inputs(rng, 8, 1, 16, 32, 128, 128)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = gated_delta_reference(q, k, v, g, beta, state)
    step = jax.jit(gated_delta_step, donate_argnums=(0,))
    got_o, got_s = step(state + 0.0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                        beta[:, 0])
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o[:, 0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=1e-5, atol=1e-5)
    # four more steps through a scan that carries the state, as the decode
    # chunk does
    def chunk(state):
        def one(c, _):
            o, c = gated_delta_step(c, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                    beta[:, 0])
            return c, o
        return jax.lax.scan(one, state, None, length=4)
    got_s4, _ = jax.jit(chunk, donate_argnums=(0,))(state + 0.0)
    with jax.default_matmul_precision("highest"):
        rep = lambda x: jnp.repeat(x, 4, axis=1)  # noqa: E731
        _, want_s4 = gated_delta_reference(rep(q), rep(k), rep(v), rep(g),
                                           rep(beta), state)
    np.testing.assert_allclose(np.asarray(got_s4), np.asarray(want_s4),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("precision,tol", [("highest", 2e-5),
                                           (None, 2e-2)])
def test_gated_delta_chunk_matches_the_recurrence_on_chip(precision, tol,
                                                          tpu, rng):
    """The chunked rule over 1100 tokens (17 chunks and a bit, two blocks
    of chunks, the second row 700 true tokens long) against the recurrence:
    with float32 products at full precision to rounding; at the chip's
    default (one bfloat16 pass a float32 product) to what that pass
    costs."""
    from apex_tpu.ops.gated_delta import (gated_delta_chunk,
                                          gated_delta_reference)

    q, k, v, g, beta, state = _delta_inputs(rng, 2, 1100, 16, 32, 128, 128)
    lengths = jnp.asarray([1100, 700])
    live = (jnp.arange(1100)[None] < lengths[:, None])[..., None]
    with jax.default_matmul_precision("highest"):
        want_o, want_s = jax.jit(gated_delta_reference)(
            q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0),
            state)

    def run():
        return jax.jit(lambda *a: gated_delta_chunk(
            *a, initial_state=state, lengths=lengths))(q, k, v, g, beta)
    if precision:
        with jax.default_matmul_precision(precision):
            got_o, got_s = run()
    else:
        got_o, got_s = run()
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(got_o[0] - want_o[0]).max()) < tol * scale
    assert float(jnp.abs(got_o[1, :700] - want_o[1, :700]).max()) \
        < tol * scale
    assert float(jnp.abs(got_s - want_s).max()) \
        < tol * float(jnp.abs(want_s).max())


@pytest.mark.parametrize("s", [1, 4])
def test_paged_attention_head256_rep8_matches_reference_on_chip(s, tpu, rng):
    """Qwen3-Next's full layers: 16 query heads over 2 key/value heads of
    256, one head a pool row, 64 slots behind 2048-entry tables."""
    from apex_tpu.ops.paged_attention import (paged_attention,
                                              paged_attention_reference)

    slots, mp, ps, live = 64, 2048, 16, 70
    k_held, v_held = (jnp.asarray(rng.standard_normal((4609, 2, ps, 256)),
                                  jnp.bfloat16) for _ in range(2))
    tables = jnp.asarray(rng.permutation(np.arange(1, 4609))[
        :slots * live].reshape(slots, live), jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, mp - live)))
    lengths = jnp.asarray(rng.integers(s, live * ps, (slots,)), jnp.int32)
    lengths = lengths.at[3].set(0).at[7].set(live * ps)
    q = jnp.asarray(rng.standard_normal((slots, 16, s, 256)), jnp.bfloat16)
    got = jax.jit(paged_attention)(q, k_held, v_held, tables, lengths)
    want = jax.jit(paged_attention_reference)(
        q, k_held, v_held, tables[:, :live], lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_paged_attention_walks_the_newest_cells_contexts_on_chip(tpu, rng):
    """``qwen3-next-80b-a3b.longchat-closed64``'s full-attention call: 64
    slots behind 2048-entry tables, 2 key/value heads of 256, contexts of
    the cell's four prompt lengths (some a few decoded tokens on, some at
    the block's exact edge) and idle slots between them — a work list of
    about a sixth of the table's blocks under a traced grid bound
    (``ops/_page_walk.py``). The reference gathers whole tables, so it runs
    eight slots at a time over the entries any slot holds."""
    from apex_tpu.ops.paged_attention import (paged_attention,
                                              paged_attention_reference)

    slots, mp, ps = 64, 2048, 16
    lengths = rng.choice([0, 1024, 4096, 8192, 16384], slots)
    lengths[:5] = [0, 1024, 4096, 8192, 16384]          # every kind is there
    lengths[-1] = 0                                     # idle first and last
    lengths[8::2] += rng.integers(1, 300, len(lengths[8::2])) * (
        lengths[8::2] > 0)
    held = -(-lengths // ps)
    num_pages = 1 + int(held.sum())
    k_held, v_held = (jnp.asarray(
        rng.standard_normal((num_pages, 2, ps, 256)), jnp.bfloat16)
        for _ in range(2))
    own = iter(rng.permutation(np.arange(1, num_pages)))
    tables = np.zeros((slots, mp), np.int32)
    for slot, n in enumerate(held):
        tables[slot, :n] = [next(own) for _ in range(n)]
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.standard_normal((slots, 16, 1, 256)), jnp.bfloat16)
    got = np.asarray(jax.jit(paged_attention)(q, k_held, v_held, tables,
                                              lengths), np.float32)
    reference = jax.jit(paged_attention_reference)
    live = int(held.max())
    for lo in range(0, slots, 8):
        want = reference(q[lo:lo + 8], k_held, v_held,
                         tables[lo:lo + 8, :live], lengths[lo:lo + 8])
        np.testing.assert_allclose(got[lo:lo + 8],
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)
    assert (got[np.asarray(lengths) == 0] == 0).all()
