"""Quantized weight streaming (docs/serving.md "Quantized weight
streaming"): int8/fp8 per-channel and int4-grouped weight buffers for
the block linears, dequantized inside the fused dequant-matmul Pallas
kernel, selected per layer CLASS by ``WeightPrecisionPolicy`` (the
``apex.amp`` opt-level analog — embeddings/norms/biases/head stay fp).

Invariant tier (fast): the dtype-resolution and policy contracts with
their NAMED errors (no silent fp fallback, no silent legacy-flag pick),
the group-local int4 pack/unpack round trip and its TP-sharding slice
invariant, quantization error bounds per kind, fused-kernel parity
against the dequantizing reference for all three kinds, the policy
round trip leaving fp leaves untouched (bit-identical embeddings/norms/
biases), and the per-step weight-byte ratio pins at real gpt2-small
shapes (w8 <= 0.55x fp, w4 <= 0.35x fp — scale reads included).

Engine tier (slow): greedy decode through the real engines — int8, fp8
and int4-grouped weight trees vs the fp tree on GPT and windowed Llama,
TP=2 w8 token identity vs the single-chip w8 engine (group-local
packing makes contiguous shard slices exact, so sharding must not
change the numerics), speculative decode with a MORE aggressively
quantized draft (int4 draft / int8 target), and the frontend path over
a quantized tree. Unlike KV quantization, prefill itself runs the
quantized weights, so even first tokens are an empirical fixed-seed pin
rather than a structural guarantee — at tiny-GPT scale they hold, and
full streams are pinned per kind (identity counts + greedy
common-prefix floors): EVERY matmul is perturbed here, so the
tests/test_quantized_kv.py identity bar does not transfer.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.models.quantize import (assert_quantized_loaded,
                                      quantize_model_params)
from apex_tpu.ops.quant import (WeightPrecisionPolicy, dequantize_weight,
                                fused_dequant_matmul, pack_int4,
                                quantize_weight, quantize_weight_fp8,
                                quantize_weight_int4, resolve_weight_dtype,
                                unpack_int4, validate_int4_group,
                                weight_storage_dtype)
from apex_tpu.serving import PagedDecodeEngine, Request
from apex_tpu.serving.scheduler import generate_paged

PS = 8

_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")

# tiny-GPT block linears have in_features 64 and 256 — group 8 divides
# both (the gpt2s default 128 does not divide 64)
TINY_GS = 8


# --- invariant tier ----------------------------------------------------------


def test_resolve_weight_dtype_contract():
    assert resolve_weight_dtype(None) is None
    assert resolve_weight_dtype(False) is None
    assert resolve_weight_dtype(True) == "int8"      # quantize_int8 alias
    assert resolve_weight_dtype("int8") == "int8"
    assert resolve_weight_dtype(jnp.int8) == "int8"
    assert resolve_weight_dtype("int4") == "int4"
    if _HAS_FP8:
        for alias in ("fp8", "e4m3", jnp.float8_e4m3fn):
            assert resolve_weight_dtype(alias) == "fp8"
        assert weight_storage_dtype("fp8") == jnp.float8_e4m3fn
    assert weight_storage_dtype("int8") == jnp.int8
    assert weight_storage_dtype("int4") == jnp.uint8   # packed nibbles
    # NAMED error, never a silent full-precision fallback
    with pytest.raises(ValueError, match="weight-dtype-unsupported"):
        resolve_weight_dtype("int2")
    with pytest.raises(ValueError, match="weight-dtype-unsupported"):
        resolve_weight_dtype(jnp.bfloat16)


def test_weight_policy_contract():
    pol = WeightPrecisionPolicy()
    assert pol.linears == "int8" and pol.group_size == 128
    assert WeightPrecisionPolicy(None).linears is None
    assert WeightPrecisionPolicy(True).linears == "int8"
    assert WeightPrecisionPolicy("int4", group_size=8).linears == "int4"
    with pytest.raises(ValueError, match="weight-dtype-unsupported"):
        WeightPrecisionPolicy("int2")
    with pytest.raises(ValueError, match="int4-group-invalid"):
        WeightPrecisionPolicy("int4", group_size=12)
    # the ONE resolution rule for policy x legacy quantize_int8 flag
    assert WeightPrecisionPolicy.resolve(None, False) is None
    assert WeightPrecisionPolicy.resolve(None, True).linears == "int8"
    assert WeightPrecisionPolicy.resolve(
        WeightPrecisionPolicy(None), True).linears == "int8"
    kept = WeightPrecisionPolicy.resolve(WeightPrecisionPolicy("int8"), True)
    assert kept.linears == "int8"
    with pytest.raises(ValueError, match="weight-policy-conflict"):
        WeightPrecisionPolicy.resolve(
            WeightPrecisionPolicy("int4", group_size=8), True)


def test_validate_int4_group_named_errors():
    validate_int4_group(64, 8)
    with pytest.raises(ValueError, match="int4-group-invalid"):
        validate_int4_group(64, 12)            # not a power of two
    with pytest.raises(ValueError, match="int4-group-invalid"):
        validate_int4_group(64, 1)             # too small
    with pytest.raises(ValueError, match="int4-group-invalid"):
        validate_int4_group(60, 8)             # not a multiple


def test_pack_int4_roundtrip_and_shard_slice_invariant(rng):
    q = rng.integers(-8, 8, (6, 64)).astype(np.int8)
    gs = 16
    packed = pack_int4(jnp.asarray(q), group_size=gs)
    assert packed.shape == (6, 32) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(packed, group_size=gs)), q)
    # GROUP-LOCAL packing: a contiguous slice of whole groups along the
    # packed axis IS the packed form of those groups — the invariant
    # that lets tensor-parallel row-sharding slice packed weights
    # (and their contiguous scale rows) with zero repacking
    half = 32 // 2                              # 2 of 4 groups
    np.testing.assert_array_equal(
        np.asarray(packed[:, :half]),
        np.asarray(pack_int4(jnp.asarray(q[:, :32]), group_size=gs)))
    np.testing.assert_array_equal(
        np.asarray(packed[:, half:]),
        np.asarray(pack_int4(jnp.asarray(q[:, 32:]), group_size=gs)))


def test_quantize_roundtrip_bounds(rng):
    w = rng.standard_normal((12, 64)).astype(np.float32) * 3.0
    q, s = quantize_weight(jnp.asarray(w))
    err = np.abs(np.asarray(dequantize_weight(q, s)) - w)
    assert np.all(err <= np.asarray(s)[:, None] / 2 + 1e-7)

    qp, sg = quantize_weight_int4(jnp.asarray(w), group_size=16)
    assert qp.shape == (12, 32) and sg.shape == (4, 12)
    err4 = np.abs(np.asarray(dequantize_weight(qp, sg)) - w)
    # per-(channel, group) grid: half an LSB of each group's scale
    bound = np.asarray(sg).T.repeat(16, axis=1) / 2 + 1e-6
    assert np.all(err4 <= bound)

    if _HAS_FP8:
        q8, s8 = quantize_weight_fp8(jnp.asarray(w))
        assert q8.dtype == jnp.float8_e4m3fn
        deq = np.asarray(dequantize_weight(q8, s8))
        # e4m3 keeps ~2-3 mantissa bits: relative error under ~1/8 of
        # each channel's amax-normalized grid
        assert np.all(np.abs(deq - w)
                      <= np.abs(w) * 0.13 + np.asarray(s8)[:, None])


@pytest.mark.parametrize("kind", ["int8", "fp8", "int4"])
def test_fused_kernel_parity_vs_dequant_reference(kind, rng):
    """The Pallas kernel's in-VMEM dequant + contraction matches
    ``x @ dequant(qw).T`` to f32 dot accuracy — no activation
    quantization roundtrip (weight-only, W8A16-style)."""
    if kind == "fp8" and not _HAS_FP8:
        pytest.skip("no float8_e4m3fn in this build")
    w = rng.standard_normal((128, 64)).astype(np.float32)
    x = rng.standard_normal((5, 64)).astype(np.float32)
    if kind == "int8":
        qw, s = quantize_weight(jnp.asarray(w))
    elif kind == "fp8":
        qw, s = quantize_weight_fp8(jnp.asarray(w))
    else:
        qw, s = quantize_weight_int4(jnp.asarray(w), group_size=16)
    got = np.asarray(fused_dequant_matmul(jnp.asarray(x), qw, s))
    want = x @ np.asarray(dequantize_weight(qw, s)).T
    assert got.shape == (5, 128)
    assert float(np.abs(got - want).max()) < 1e-4
    # leading-dims flattening: (b, t, in) agrees with the 2D path
    got3 = np.asarray(fused_dequant_matmul(
        jnp.asarray(x.reshape(5, 1, 64)), qw, s))
    np.testing.assert_allclose(got3.reshape(5, 128), got, atol=1e-5)


def test_policy_roundtrip_leaves_fp_untouched(rng):
    """quantize_model_params under a policy: block-linear weights land
    narrow with sibling scales; embeddings, norms, biases and every
    other fp leaf pass through BIT-identical."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    for pol in (WeightPrecisionPolicy("int8"),
                WeightPrecisionPolicy("int4", group_size=TINY_GS)):
        qmodel = GPTModel(dataclasses.replace(cfg, weight_policy=pol))
        qparams = quantize_model_params(qmodel, v, jnp.zeros((1, 8),
                                                            jnp.int32))
        assert_quantized_loaded(qparams)       # narrow leaves, non-zero
        flat_fp = dict(jax.tree_util.tree_flatten_with_path(v["params"])[0])
        flat_q = dict(jax.tree_util.tree_flatten_with_path(qparams)[0])
        narrow = {jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8)}
        n_narrow = n_fp = 0
        for path, leaf in flat_q.items():
            if jnp.dtype(leaf.dtype) in narrow:
                n_narrow += 1
                continue
            if path not in flat_fp:
                assert path[-1].key == "scale"     # produced with weight
                continue
            n_fp += 1
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(flat_fp[path]))
        assert n_narrow == 4 * cfg.num_layers      # qkv/out/mlp_in/mlp_out
        assert n_fp > 0                            # embeddings et al.


def test_weight_bytes_ratio_pins():
    """The acceptance numbers at REAL gpt2-small shapes, straight off
    the abstract param trees (per-LEAF dtype bytes, scale reads
    included): int8 policy <= 0.55x the fp tree, int4 policy (+ bf16 fp
    leaves, the documented aggressive pairing) <= 0.35x."""
    from apex_tpu.models.gpt import gpt2_small_config

    def tree_bytes(cfg):
        model = GPTModel(cfg)
        tree = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree))

    base = gpt2_small_config(dtype=jnp.bfloat16)
    fp = tree_bytes(base)
    w8 = tree_bytes(dataclasses.replace(
        base, weight_policy=WeightPrecisionPolicy("int8")))
    w4 = tree_bytes(dataclasses.replace(
        base, weight_policy=WeightPrecisionPolicy("int4"),
        param_dtype=jnp.bfloat16))
    assert w8 <= 0.55 * fp, (w8, fp)
    assert w4 <= 0.35 * fp, (w4, fp)


def test_assert_quantized_loaded_named_errors():
    cfg = gpt_tiny_config(
        weight_policy=WeightPrecisionPolicy("int4", group_size=TINY_GS))
    qmodel = GPTModel(cfg)
    placeholders = qmodel.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="all zeros"):
        assert_quantized_loaded(placeholders)   # init() placeholders
    fp_model = GPTModel(gpt_tiny_config())
    fp = fp_model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="no int8"):
        assert_quantized_loaded(fp)             # not a quantized tree


# --- engine tier -------------------------------------------------------------


def _tiny_quantized_setup(rng, pol):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, (n,))))
               for n in (9, 17, 5, 26)]
    qmodel = GPTModel(dataclasses.replace(cfg, weight_policy=pol))
    qv = {"params": quantize_model_params(qmodel, v,
                                          jnp.zeros((1, 8), jnp.int32))}
    return cfg, model, v, qmodel, qv, prompts


def _agreement(fp, q):
    """(all first tokens equal, count of fully-identical requests)."""
    firsts = all(int(np.asarray(a)[0]) == int(np.asarray(b)[0])
                 for a, b in zip(fp, q))
    ident = sum(bool(np.array_equal(np.asarray(a), np.asarray(b)))
                for a, b in zip(fp, q))
    return firsts, ident


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["int8", "fp8", "int4"])
def test_engine_greedy_parity_tolerance(kind, rng):
    """Quantized-weight engines vs the fp engine on the same
    mixed-length workload. Every request's FIRST token matches (the
    fixed-seed pin — prefill runs the quantized weights, so this is
    empirical, not structural). Full streams diverge once a perturbed
    logit crosses an argmax gap, and unlike KV quantization EVERY
    matmul is perturbed — so the bar is per-kind: int8/fp8 keep >= 2/4
    requests fully identical, and every kind keeps a mean greedy
    common-prefix of generated tokens above its pin (int4-grouped at
    group 8 is the aggressive end and diverges earliest)."""
    if kind == "fp8" and not _HAS_FP8:
        pytest.skip("no float8_e4m3fn in this build")
    pol = WeightPrecisionPolicy(kind, group_size=TINY_GS)
    cfg, model, v, qmodel, qv, prompts = _tiny_quantized_setup(rng, pol)
    kw = dict(max_new_tokens=12, num_slots=4, page_size=PS, num_pages=40)
    fp = generate_paged(model, v, prompts, **kw)
    q = generate_paged(qmodel, qv, prompts, **kw)
    firsts, ident = _agreement(fp, q)
    assert firsts, f"{kind}: first token flipped"
    gen_prefix = []
    for p, a, b in zip(prompts, fp, q):
        a, b = np.asarray(a), np.asarray(b)
        n = 0
        while n < len(a) and n < len(b) and a[n] == b[n]:
            n += 1
        gen_prefix.append(n - len(p))          # agreed GENERATED tokens
    min_ident = {"int8": 2, "fp8": 2, "int4": 0}[kind]
    min_mean_prefix = {"int8": 4.0, "fp8": 4.0, "int4": 2.0}[kind]
    assert ident >= min_ident, f"{kind}: only {ident}/4 identical"
    mean_prefix = sum(gen_prefix) / len(gen_prefix)
    assert all(n >= 1 for n in gen_prefix), (kind, gen_prefix)
    assert mean_prefix >= min_mean_prefix, (kind, gen_prefix)


@pytest.mark.slow
def test_llama_windowed_w8(rng):
    """generate(paged=True) through Llama's GQA + sliding-window band
    with the int8 weight policy: matches the fp paged run at the
    tolerance bar on a rectangular batch."""
    from apex_tpu.models.llama import LlamaModel, llama_tiny_config

    cfg = dataclasses.replace(llama_tiny_config(), sliding_window=PS)
    model = LlamaModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    qmodel = LlamaModel(dataclasses.replace(
        cfg, weight_policy=WeightPrecisionPolicy("int8")))
    qv = {"params": quantize_model_params(qmodel, v,
                                          jnp.zeros((1, 8), jnp.int32))}
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 12)),
                         jnp.int32)
    fp = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                             paged=True, page_size=PS))
    q8 = np.asarray(generate(qmodel, qv, prompt, max_new_tokens=6,
                             paged=True, page_size=PS))
    assert fp.shape == q8.shape
    np.testing.assert_array_equal(fp[:, :13], q8[:, :13])  # prompt+first
    ident = sum(bool(np.array_equal(a, b)) for a, b in zip(fp, q8))
    assert ident >= 2, f"windowed llama w8: {ident}/3 rows identical"


@pytest.mark.slow
def test_tp2_w8_token_identity(rng):
    """TP=2 over the int8 weight tree: token-IDENTICAL to the
    single-chip w8 engine. Column shards slice int8 channels exactly;
    the row-parallel per-channel scale is replicated — so the sharded
    dequantized weights are bit-identical to the unsharded ones and
    greedy argmax cannot move (the group-local-packing design claim of
    serving/tp.py, exercised end to end)."""
    from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                     shard_model_variables, tp_mesh)

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    pol = WeightPrecisionPolicy("int8")
    cfg, model, v, qmodel, qv, prompts = _tiny_quantized_setup(rng, pol)
    if cfg.num_heads % 2:
        pytest.skip("tiny config heads not divisible by 2")
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=8)
            for p in prompts[:3]]
    single = PagedDecodeEngine(qmodel, qv, num_slots=3, page_size=PS,
                               num_pages=33)
    outs, _ = single.run(reqs)

    tp_cfg = dataclasses.replace(cfg, tensor_parallel_size=2,
                                 weight_policy=pol)
    tp_model = GPTModel(tp_cfg)
    mesh = tp_mesh(2)
    tp_vars, _ = shard_model_variables(tp_model, qv, mesh)
    tp_engine = TensorParallelPagedEngine(
        tp_model, tp_vars, mesh=mesh, num_slots=3, page_size=PS,
        num_pages=33)
    tp_outs, _ = tp_engine.run(reqs)
    for i, (a, b) in enumerate(zip(outs, tp_outs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"request {i}")


@pytest.mark.slow
def test_spec_decode_int4_draft_int8_target(rng):
    """Speculative decode composes with an at-least-as-aggressive draft:
    int4-grouped draft weights proposing for an int8 target. Outputs
    agree with the plain int8 engine at the tolerance bar and the
    acceptance telemetry is live (a cross-precision draft accepts less
    than the self-draft ceiling but must still draft usefully)."""
    pol8 = WeightPrecisionPolicy("int8")
    cfg, model, v, qmodel, qv, prompts = _tiny_quantized_setup(rng, pol8)
    d_model = GPTModel(dataclasses.replace(
        cfg, weight_policy=WeightPrecisionPolicy("int4",
                                                 group_size=TINY_GS)))
    dv = {"params": quantize_model_params(d_model, v,
                                          jnp.zeros((1, 8), jnp.int32))}
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=10)
            for p in prompts]
    plain = PagedDecodeEngine(qmodel, qv, num_slots=4, page_size=PS,
                              num_pages=40)
    outs, _ = plain.run(reqs)

    spec = PagedDecodeEngine(qmodel, qv, num_slots=4, page_size=PS,
                             num_pages=40, draft_model=d_model,
                             draft_variables=dv, draft_len=2)
    s_outs, s_stats = spec.run(reqs)
    assert s_stats["spec_rounds"] >= 1
    assert s_stats["mean_acceptance_len"] >= 1.0
    firsts, ident = _agreement(outs, s_outs)
    assert firsts and ident >= 3, f"spec int4-draft: {ident}/4"


@pytest.mark.slow
def test_frontend_over_quantized_weights(rng):
    """The async frontend path over a w8 engine: submit/pump/drain
    completes with full-length outputs identical to the engine's
    batch run — the serving surface accepts quantized trees whole."""
    from apex_tpu.serving.frontend import ServingFrontend

    pol = WeightPrecisionPolicy("int8")
    cfg, model, v, qmodel, qv, prompts = _tiny_quantized_setup(rng, pol)
    reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=6)
            for p in prompts]
    engine = PagedDecodeEngine(qmodel, qv, num_slots=4, page_size=PS,
                               num_pages=40)
    base, _ = engine.run(reqs)
    fe = ServingFrontend(engine)
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(reqs)]
    fe.drain()
    for h, b in zip(handles, base):
        np.testing.assert_array_equal(np.asarray(h.result()),
                                      np.asarray(b))
