"""``models/mellum.py`` at a small size on the CPU, seeded weights: one
period of four layers (sliding, sliding, sliding, full), 8 experts top 2,
window 8 — against the plain reference ``benchmark/references/mellum.py``,
model-only and through ``PagedDecodeEngine``'s own admission and pool; the
pool's groups of layers (``serving/kv_pool.layer_groups``); the router and
the YaRN table; the engine's refusals.

The page is 8 tokens and not the issue's 4: the pool refuses a page that is
no sublane multiple (``init_paged_cache``), so the window group's ring is
``ceil(8 / 8) + 1 = 2`` pages a slot, and 4 at the window of 20 the second
engine case runs.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.mellum import (FULL, SLIDING, MellumConfig, MellumModel,
                                    YarnScaling, mellum_tiny_config)
from apex_tpu.serving import PagedDecodeEngine, Request, kv_pool
from apex_tpu.serving.scheduler import prompt_bucket
from apex_tpu.transformer.functional.fused_rope import (rope_inv_freq,
                                                        yarn_inv_freq)
from benchmark.families import mellum as family
from benchmark.harness import weights
from benchmark.references import mellum as reference

SEED = 2 ** 31 + 35

#: float32 on both sides: what is left is the order of the sums (flash
#: tiles and page blocks against one dense softmax; the grouped products
#: against one product a expert), a few 1e-6 of logits of order 0.1. The
#: same model computed in bfloat16 reads 2e-3 and more: a hundred times
#: the limit (``test_bfloat16_fails_the_float32_tolerance``)
TOL = 2e-5


def tiny_cfg(**over) -> dict:
    """The tiny configuration as a configuration FILE (the reference and
    the benchmark's family read this form)."""
    cfg = dict(
        model_type="mellum", hidden_size=64, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=48,
        norm_topk_prob=True, num_hidden_layers=4,
        layer_types=[SLIDING] * 3 + [FULL], sliding_window=8,
        rms_norm_eps=1e-6, vocab_size=128, max_position_embeddings=128,
        rope_parameters={
            FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                   "original_max_position_embeddings": 32, "beta_fast": 32,
                   "beta_slow": 1,
                   "attention_factor": 0.1 * math.log(4) + 1},
            SLIDING: {"rope_type": "default", "rope_theta": 10000}},
        compute_dtype="float32", param_dtype="float32")
    cfg.update(over)
    return cfg


@functools.lru_cache(maxsize=None)
def _built(window=8, layer_types=None, dtype="float32"):
    cfg = tiny_cfg(sliding_window=window, compute_dtype=dtype)
    if layer_types is not None:
        cfg["layer_types"] = list(layer_types)
    model = family.model(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    variables = {"params": weights.make_like(like["params"], SEED)}
    return cfg, model, variables


def _reference_logits(cfg, sequences):
    make = functools.partial(weights.make_weights, seed=SEED)
    return [np.asarray(x) for x in reference.logits_at(
        make, cfg, sequences, [np.arange(len(s)) for s in sequences])]


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(4, 128, n).astype(np.int32)


# -- the model alone --------------------------------------------------------------

def test_the_tiny_preset_is_the_tiny_configuration_file():
    cfg, model, _ = _built()
    assert model.config == mellum_tiny_config(
        rope_theta=10000.0, max_position_embeddings=128)
    assert model.config.layer_windows == (8, 8, 8, None)
    assert MellumConfig().layer_windows == ((1024,) * 3 + (None,)) * 7
    assert MellumConfig().routed_expert_bytes == 3 * 2304 * 896 * 2
    with pytest.raises(ValueError, match="layer_types has 3 entries"):
        MellumConfig(num_layers=4, layer_types=(SLIDING,) * 3)
    with pytest.raises(ValueError, match="unknown layer types"):
        mellum_tiny_config(layer_types=("linear_attention",) * 4)


@pytest.mark.parametrize("length", [5, 40])
def test_full_forward_matches_the_references_logits(length):
    """Shorter than the window and five windows long."""
    cfg, model, variables = _built()
    ids = _ids(length)
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None])[0])
    want = _reference_logits(cfg, [ids])[0]
    assert np.abs(got - want).max() < TOL


def test_bfloat16_fails_the_float32_tolerance():
    cfg, _, variables = _built()
    _, model16, _ = _built(dtype="bfloat16")
    ids = _ids(40)
    got = np.asarray(model16.apply(variables, jnp.asarray(ids)[None])[0],
                     np.float32)
    assert np.abs(got - _reference_logits(cfg, [ids])[0]).max() > 50 * TOL


@pytest.mark.parametrize("variant", ["fp8", "no_band", "no_yarn",
                                     "no_renorm"])
def test_each_named_fault_moves_the_references_logits(variant):
    """What the cell's comparison can put in the reference's place is
    another computation (on the chip each has to read over the cell's
    limit: PERF.md section 6)."""
    cfg = tiny_cfg()
    make = functools.partial(weights.make_weights, seed=SEED)
    seqs = [_ids(40, seed=3)]
    pos = [np.arange(40)]
    plain = reference.logits_at(make, cfg, seqs, pos)[0]
    moved = reference.logits_at(make, cfg, seqs, pos, variant)[0]
    assert float(jnp.abs(plain - moved).max()) > 50 * TOL
    if variant == "no_band":
        # the band changes nothing a query within the first window sees
        assert float(jnp.abs(plain[:8] - moved[:8]).max()) < TOL
    judged = family.judge(cfg, SEED, [(seqs[0][:24], seqs[0][24:])], variant)
    assert judged["tokens"] == 16 and 0 <= judged["gap"] <= judged["widest"]
    with pytest.raises(ValueError, match="unknown variant"):
        family.judge(cfg, SEED, [(seqs[0][:24], seqs[0][24:])], "float16")


# -- admission, then paged decode, through the engine's programs and pool ---------

def _engine(model, variables, **kw):
    kw = dict(dict(num_slots=3, page_size=8, num_pages=40, sync_every=2), **kw)
    return PagedDecodeEngine(model, variables, **kw)


def _paged_logits(engine, sequences, prompt_lens):
    """Teacher-forced logits of ``sequences`` through the engine's own
    admit programs (one slot each) and then the model's paged step over the
    engine's pool, a token a step: ``[(position, logits [V])]`` a sequence."""
    model, variables = engine.model, engine.variables
    ps, cache = engine.page_size, engine.cache
    first = []
    for slot, (seq, n) in enumerate(zip(sequences, prompt_lens)):
        bucket = prompt_bucket(n, ps, engine.cfg.max_position_embeddings)
        admit = engine._admit_fn(bucket)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = seq[:n]
        cache, tok0 = admit(cache, variables, jnp.asarray(ids),
                            jnp.int32(n), jnp.int32(slot),
                            jnp.int32(-(-len(seq) // ps)),
                            jax.random.PRNGKey(0), jnp.int32(0))
        first.append(int(tok0))
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
    out = [[] for _ in sequences]
    for j in range(max(len(s) - n for s, n in zip(sequences, prompt_lens))):
        tok = np.zeros((engine.num_slots, 1), np.int32)
        live = [i for i, (s, n) in enumerate(zip(sequences, prompt_lens))
                if n + j < len(s)]
        for i in live:
            tok[i, 0] = sequences[i][prompt_lens[i] + j]
        before = cache["len"]
        logits, cache = step(cache, jnp.asarray(tok))
        # as the engine's step does: a slot that is not decoding stays put
        keep = np.zeros((engine.num_slots,), bool)
        keep[live] = True
        cache = dict(cache, len=jnp.where(keep, cache["len"], before))
        for i in live:
            out[i].append((prompt_lens[i] + j, np.asarray(logits[i, 0])))
    engine.cache = cache
    return first, out


@pytest.mark.parametrize("window,layer_types", [
    (8, None),                       # rings of 2 pages beside the block table
    (20, None),                      # rings of 4; the band straddles pages
    (8, (SLIDING,) * 4),             # ONE windowed group: no ring, the
                                     # block table and the drop behind the band
])
def test_admission_then_paged_decode_matches_the_references_logits(
        window, layer_types):
    """Contexts of several windows (a prompt of 37 and 21 more tokens) and
    one shorter than the window (3 and 4 more), in one engine, logits and
    not tokens."""
    cfg, model, variables = _built(window, layer_types)
    engine = _engine(model, variables)
    seqs, prompts = [_ids(58, 1), _ids(7, 2), _ids(30, 4)], [37, 3, 16]
    want = _reference_logits(cfg, seqs)
    first, got = _paged_logits(engine, seqs, prompts)
    for seq, n, ref, tok0, steps in zip(seqs, prompts, want, first, got):
        assert tok0 == int(ref[n - 1].argmax())
        assert [p for p, _ in steps] == list(range(n, len(seq)))
        for p, logits in steps:
            assert np.abs(logits - ref[p]).max() < TOL, (len(seq), p)


def test_engine_run_serves_what_the_reference_puts_first():
    """The whole path (frontend, pump, the decode chunk's scan, slots
    re-used by later requests): every served token is the float32
    reference's first choice."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    reqs = [Request(prompt=_ids(n, 10 + n), max_new_tokens=m)
            for n, m in [(5, 9), (37, 20), (20, 30), (3, 4), (50, 12)]]
    outs, stats = engine.run(reqs)
    judged = family.judge(cfg, SEED, [(r.prompt, o)
                                      for r, o in zip(reqs, outs)])
    assert judged["tokens"] == 75 and judged["widest"] < TOL
    ring = kv_pool.ring_pages(8, 8)
    assert stats["kv_groups"] == [
        {"layers": [0, 1, 2], "window": 8, "ring_pages_per_slot": ring,
         "pages_held": 3 * ring},
        {"layers": [3], "window": None, "ring_pages_per_slot": None,
         "pages_held": 39}]
    assert stats["window_dropped_pages"] == 0


# -- the pool's groups of layers --------------------------------------------------

def test_layer_groups_state_what_each_kind_of_layer_holds():
    cfg = mellum_tiny_config()
    layout = kv_pool.layout_of(cfg)
    assert kv_pool.layer_groups(cfg) == (
        kv_pool.LayerGroup(layout, 8, (0, 1, 2), True),
        kv_pool.LayerGroup(layout, None, (3,), False))
    # one kind of layer is one group, held as it always was
    alike = mellum_tiny_config(layer_types=(SLIDING,) * 4)
    assert kv_pool.layer_groups(alike) == (
        kv_pool.LayerGroup(layout, 8, (0, 1, 2, 3), False),)
    assert kv_pool.ring_pages(1024, 16) == 65
    assert kv_pool.ring_pages(8, 8) == 2 and kv_pool.ring_pages(20, 8) == 4
    published = kv_pool.layer_groups(MellumConfig())
    assert [(g.window, len(g.layers), g.ring) for g in published] == [
        (1024, 21, True), (None, 7, False)]


def test_a_ring_view_holds_every_position_of_the_band():
    """Whatever the length, the view's pages are distinct pages of the
    slot's own ring and cover the band's first position to the token being
    written."""
    window, ps = 20, 8
    ring = kv_pool.ring_pages(window, ps)
    lengths = jnp.arange(0, 100, dtype=jnp.int32)
    table, rel = jax.jit(functools.partial(
        kv_pool.ring_view, window=window, page_size=ps))(lengths)
    table, rel = np.asarray(table), np.asarray(rel)
    assert table.shape == (100, ring)
    for slot, t in enumerate(range(100)):
        own = set(range(1 + slot * ring, 1 + (slot + 1) * ring))
        assert set(table[slot]) == own
        first_page = (t - rel[slot]) // ps
        assert (t - rel[slot]) % ps == 0
        assert first_page * ps <= max(t - window + 1, 0)
        assert rel[slot] // ps < ring               # the write fits
        for page in range(first_page, t // ps + 1):
            assert table[slot, page - first_page] == \
                1 + slot * ring + page % ring


def test_the_window_group_owns_its_ring_and_retirement_leaves_both_groups():
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    ring = kv_pool.ring_pages(8, 8)
    shapes = jax.tree.map(lambda x: x.shape, engine.cache)
    assert [lc["k_pages"].shape[0] for lc in engine.cache["layers"]] == [
        1 + 3 * ring] * 3 + [40]
    before = jax.tree.map(np.asarray, engine.cache)
    # one request of nine windows on slot 0 wrote its own ``ring`` pages
    # and no other; an idle slot's steps write their fill token where its
    # frozen length of 0 points, in its OWN ring (the block table's group
    # sinks them to the null page), and touch nothing else
    engine.run([Request(prompt=_ids(50), max_new_tokens=24)])
    after = jax.tree.map(np.asarray, engine.cache)
    assert jax.tree.map(lambda x: x.shape, engine.cache) == shapes
    for i in (0, 1, 2):
        for key in ("k_pages", "v_pages"):
            changed = np.flatnonzero(
                (before["layers"][i][key] != after["layers"][i][key])
                .any(axis=(1, 2, 3)))
            idle = {1 + slot * ring for slot in (1, 2)}
            assert set(changed) <= {0} | set(range(1, 1 + ring)) | idle
            assert set(range(1, 1 + ring)) <= set(changed)
    # retirement: the block table's group is whole again, and the window
    # group never had anything to give back
    assert int(after["free_top"]) == 39
    assert not after["block_tables"].any() and not after["len"].any()
    assert not after["alloc_pages"].any() and not after["page_ref"].any()
    assert sorted(after["free_stack"][:39]) == list(range(1, 40))


# -- the pools that exist do not change -------------------------------------------

def _one_group_cases():
    from apex_tpu.models.glm4_moe_lite import glm4_moe_lite_tiny_config
    from apex_tpu.models.gpt import gpt_tiny_config
    from apex_tpu.models.llama import llama_tiny_config

    return {
        # 4 heads of 16: one head a row
        "gpt": (gpt_tiny_config(), 2, {"k_pages": (11, 4, 8, 16),
                                       "v_pages": (11, 4, 8, 16)}),
        # two 64-wide heads a 128-lane row
        "gpt-packed": (gpt_tiny_config(hidden_size=128, num_heads=2), 2,
                       {"k_pages": (11, 1, 8, 128),
                        "v_pages": (11, 1, 8, 128)}),
        "llama-window": (llama_tiny_config(sliding_window=16), 2,
                         {"k_pages": (11, 2, 8, 16),
                          "v_pages": (11, 2, 8, 16)}),
        "glm4_moe_lite": (glm4_moe_lite_tiny_config(), 3,
                          {"latent_pages": (11, 1, 8, 128)}),
    }


@pytest.mark.parametrize("name", ["gpt", "gpt-packed", "llama-window",
                                  "glm4_moe_lite"])
def test_a_one_group_models_cache_is_what_it_was(name):
    """Keys and shapes of the pytree as PR 34 left them, written out."""
    cfg, layers, layer = _one_group_cases()[name]
    groups = kv_pool.layer_groups(cfg)
    assert len(groups) == 1 and not groups[0].ring
    assert groups[0].window == getattr(cfg, "sliding_window", None)
    cache = kv_pool.init_paged_cache(cfg, 3, num_pages=11, page_size=8,
                                     max_pages_per_seq=5)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "layers": [layer] * layers, "block_tables": (3, 5), "len": (3,),
        "alloc_pages": (3,), "shared_pages": (3,), "page_ref": (11,),
        "free_stack": (11,), "free_top": ()}
    assert kv_pool.num_pages_of(cache) == 11
    assert kv_pool.page_size_of(cache) == 8


# -- the router and the YaRN table ------------------------------------------------

@pytest.mark.parametrize("norm", [True, False])
def test_softmax_router_against_the_references(norm):
    from apex_tpu.transformer.moe import SoftmaxTopKRouter

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(33, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    router = SoftmaxTopKRouter(8, 2, norm_topk_prob=norm)
    idx, weights_ = router.apply({"params": {"weight": w}}, x)
    cfg = dict(num_experts_per_tok=2, norm_topk_prob=True)
    dense = reference.route(x, {"moe/router/weight": w}, cfg, jnp.matmul,
                            "float32" if norm else "no_renorm")
    probs = jax.nn.softmax(x @ w.T, axis=-1)
    top2 = np.sort(np.asarray(probs), axis=-1)[:, -2:]
    assert (top2[:, 1] - top2[:, 0]).min() > 1e-6          # no ties
    got = np.zeros((33, 8), np.float32)
    got[np.arange(33)[:, None], np.asarray(idx)] = np.asarray(weights_)
    np.testing.assert_allclose(got, np.asarray(dense), rtol=1e-6, atol=1e-7)
    assert np.allclose(np.asarray(weights_).sum(-1), 1.0) == norm


def test_dropless_layer_is_told_which_router_it_has():
    from apex_tpu.transformer.moe import DroplessMoEMLP

    x = jnp.ones((2, 3, 16), jnp.float32)
    kw = dict(hidden_size=16, ffn_hidden_size=8, num_experts=4, k=2)
    soft = DroplessMoEMLP(router="softmax", **kw)
    params = soft.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params["router"]) == {"weight"}
    sig = DroplessMoEMLP(**kw).init(jax.random.PRNGKey(0), x)["params"]
    assert set(sig["router"]) == {"weight", "e_score_correction_bias"}
    assert set(sig) == set(params) == {"router", "experts"}
    with pytest.raises(ValueError, match="unknown router 'argmax'"):
        DroplessMoEMLP(router="argmax", **kw).init(jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("dim,theta,yarn", [
    (128, 500000.0, YarnScaling()),                      # as published
    (16, 10000.0, YarnScaling(factor=4.0,
                              original_max_position_embeddings=32)),
])
def test_yarn_table_against_the_formula_written_out(dim, theta, yarn):
    got = yarn_inv_freq(
        dim, theta, factor=yarn.factor,
        original_max_position_embeddings=(
            yarn.original_max_position_embeddings),
        beta_fast=yarn.beta_fast, beta_slow=yarn.beta_slow)

    def pair_that_turns(turns):      # in float64, pair by pair
        return dim * math.log(yarn.original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(yarn.beta_slow)), dim - 1)
    want = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(plain / yarn.factor * ramp + plain * (1 - ramp))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    plain = rope_inv_freq(dim, theta)
    np.testing.assert_allclose(plain, [theta ** (-2.0 * i / dim)
                                       for i in range(dim // 2)], rtol=2e-6)
    # fast pairs rotate as published, slow pairs ``factor`` times slower
    assert (got[:low + 1] == plain[:low + 1]).all()
    np.testing.assert_allclose(got[high:], plain[high:] / yarn.factor,
                               rtol=1e-6)
    # and the reference's own table, written separately, is the same one
    rope = dict(rope_type="yarn", rope_theta=theta,
                **{k: getattr(yarn, k) for k in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow")})
    assert (reference.inv_freq(dim, rope) == got).all()
    assert yarn.attention_factor == pytest.approx(
        0.1 * math.log(16.0) + 1) or dim == 16


# -- what the engine refuses, by name ---------------------------------------------

def _refusals():
    cfg, model, variables = _built()
    full = dataclasses.replace(model.config, layer_types=(FULL,) * 4)
    return {
        "prefix_cache": (dict(prefix_cache=True), ValueError,
                         r"prefix_cache does not compose with "
                         r"sliding-window layers \(layers \[0, 1, 2\] read "
                         r"a window of 8\)"),
        "speculation": (dict(draft_model=MellumModel(full),
                             draft_variables=variables, draft_len=2),
                        ValueError,
                        r"speculative decode does not support "
                        r"sliding-window layers \(layers \[0, 1, 2\]"),
        "chunked_prefill": (dict(prefill_chunk=8), ValueError,
                            r"chunked prefill does not support "
                            r"sliding-window layers yet \(layers \[0, 1, "
                            r"2\]"),
        "quantized_pages": (dict(kv_dtype="int8"),
                            kv_pool.RingGroupUnsupported,
                            "ring-group-unsupported: kv_dtype='int8'"),
    }


@pytest.mark.parametrize("what", ["prefix_cache", "speculation",
                                  "chunked_prefill", "quantized_pages"])
def test_the_engine_refuses_by_group_and_by_name(what):
    _, model, variables = _built()
    kw, error, message = _refusals()[what]
    with pytest.raises(error, match=message):
        _engine(model, variables, **kw)


def test_a_tensor_parallel_pool_refuses_a_ring_group():
    from apex_tpu.serving.tp import tp_mesh

    cfg = mellum_tiny_config(tensor_parallel_size=2)
    with pytest.raises(kv_pool.RingGroupUnsupported,
                       match="a tensor-parallel mesh"):
        kv_pool.init_paged_cache(cfg, 2, num_pages=9, page_size=8,
                                 mesh=tp_mesh(2))


def test_a_chunk_of_several_tokens_cannot_ride_a_ring():
    _, model, variables = _built()
    cache = kv_pool.init_paged_cache(model.config, 2, num_pages=9,
                                     page_size=8)
    with pytest.raises(NotImplementedError, match="one token a slot"):
        model.apply(variables, jnp.zeros((2, 3), jnp.int32), cache=cache)
