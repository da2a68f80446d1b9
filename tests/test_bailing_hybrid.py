"""``models/bailing_hybrid.py`` at a small size on the CPU, seeded weights:
a dense KDA layer, then routed KDA, MLA, KDA over 16 experts top 4 in 4
groups — against the plain reference ``benchmark/references/
bailing_hybrid.py``, model-only and through ``PagedDecodeEngine``'s own
admission, latent pages and state group; the gated delta rule with a decay
a key channel (``ops/gated_delta.py``); the group-limited router; a share of
the experts.

The page is 8 tokens (the pool refuses a page that is no sublane
multiple). Every prompt here is OFF the page grid, so every admission pads,
and the padding must not reach a state. One engine serves the module, and
the decode step is compiled once.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.bailing_hybrid import (KDA, MLA, BailingHybridConfig,
                                            BailingHybridModel,
                                            bailing_hybrid_tiny_config)
from apex_tpu.ops.gated_delta import (CHUNK, DIAGONAL_G_FLOOR, SUB,
                                      gated_delta_chunk,
                                      gated_delta_reference,
                                      gated_delta_step)
from apex_tpu.serving import (PagedDecodeEngine, Request, ServingFrontend,
                              kv_pool)
from apex_tpu.serving.scheduler import prompt_bucket
from apex_tpu.transformer.moe.dropless import DroplessMoEMLP
from apex_tpu.transformer.moe.router import SigmoidBiasTopKRouter
from benchmark.families import bailing_hybrid as family
from benchmark.harness import weights
from benchmark.references import bailing_hybrid as reference

SEED = 2 ** 31 + 43

#: float32 on both sides: what is left is the order of the sums (the
#: chunked rule against the recurrence, flash tiles and latent pages
#: against one dense softmax, the grouped products against one product an
#: expert), under 1e-6 of logits of order 1 here; the reference with its
#: matmuls' inputs rounded to float8 moves them by over 50 times this
#: (``test_each_named_fault_moves_the_references_logits[fp8]``)
TOL = 1e-5


@pytest.fixture(autouse=True, scope="module")
def _each_group_of_weights_made_once():
    """``weights.make_weights`` jits its function anew at every call; a
    leaf's values depend on the seed and its name alone, so each (table,
    seed) is made once here."""
    made, plain = {}, weights.make_weights

    def once(table, seed):
        key = (seed, tuple(sorted((name, tuple(shape), str(dtype))
                                  for name, (shape, dtype) in table.items())))
        if key not in made:
            made[key] = plain(table, seed)
        return made[key]

    patch = pytest.MonkeyPatch()
    patch.setattr(weights, "make_weights", once)
    yield
    patch.undo()


def tiny_cfg(**over) -> dict:
    """The tiny configuration as a configuration FILE (the reference and
    the benchmark's family read this form)."""
    cfg = dict(
        model_type="bailing_hybrid", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, first_layer=0,
        layer_group_size=3, first_k_dense_replace=1, num_attention_heads=4,
        head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5,
        dt_bias_center=-6.0, kda_safe_gate=True, use_kda_lora=False,
        no_kda_lora=True, linear_silu=True, group_norm_size=1,
        q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        rope_scaling=None, rope_interleave=True, use_qk_norm=True,
        gated_attention_proj_granularity_type="head_wise",
        topk_method="noaux_tc", score_function="sigmoid", num_experts=16,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, norm_topk_prob=True, num_shared_experts=1,
        moe_shared_expert_intermediate_size=32,
        moe_router_enable_expert_bias=True, scale_router_input=False,
        up_proj_norm=False, use_mla_nope=False, value_norm=False,
        use_nGPT=False,
        use_bias=False, use_qkv_bias=False, tie_word_embeddings=False,
        vocab_size=128, max_position_embeddings=256, rms_norm_eps=1e-6,
        compute_dtype="float32", param_dtype="float32")
    cfg.update(over)
    return cfg


@functools.lru_cache(maxsize=None)
def _built_from(over):
    cfg = tiny_cfg(**dict(over))
    model = family.model(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    variables = {"params": weights.make_like(like["params"], SEED)}
    return cfg, model, variables


def _built(**over):
    return _built_from(tuple(sorted(over.items())))


def _make(seed=SEED):
    return functools.partial(weights.make_weights, seed=seed)


#: every sequence the reference reads here is padded to this many tokens
#: (the model is causal: what follows a position changes nothing before
#: it), so that each of its layer programs compiles once a variant
REF_LEN = 80


@functools.lru_cache(maxsize=None)
def _reference_logits_of(seqs, variant="float32"):
    padded = [np.asarray(s + (5,) * (REF_LEN - len(s)), np.int32)
              for s in seqs]
    return [np.asarray(x)[:len(s)] for s, x in zip(seqs, reference.logits_at(
        _make(), tiny_cfg(), padded, [np.arange(REF_LEN)] * len(seqs),
        variant))]


def _reference_logits(sequences, variant="float32"):
    return _reference_logits_of(tuple(tuple(int(t) for t in s)
                                      for s in sequences), variant)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(4, 128, n).astype(np.int32)


# -- the gated delta rule with a decay a key channel --------------------------

def _inputs(b, s, h, dk, dv, *, floor=-5.0, seed=0, scalar=False):
    """Unit keys, queries of norm ``dk^-0.5``, Kimi's safe gate ``g =
    floor sigmoid(z)`` a key channel (or one a head), ``beta`` in (0, 1),
    a state to start from."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    shape = (b, s, h) if scalar else (b, s, h, dk)
    g = floor * jax.nn.sigmoid(2 * jax.random.normal(ks[3], shape) - 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, state


#: the rule's forms, each jitted (one compile a shape, where op-by-op
#: dispatch of the same would compile every op)
_chunk = jax.jit(gated_delta_chunk)
_recurrence = jax.jit(gated_delta_reference)


def _close(got, want, tol=TOL):
    """Within ``tol`` of the reference's scale (a state carried through
    hundreds of tokens at a decay near 1 grows past 1)."""
    scale = max(1.0, float(jnp.abs(want).max()))
    return float(jnp.abs(got - want).max()) < tol * scale


@pytest.mark.parametrize("length,bound", [(CHUNK + 7, False),
                                          (17 * CHUNK + 6, True)])
def test_a_per_channel_decay_chunked_is_the_recurrence(length, bound):
    """Lengths off the chunk, off the sub-chunk and past one block of chunks
    computed at once, with padded rows: what lies past a row's length
    changes neither its state nor its outputs. The long case holds every
    channel at Kimi's bound of -5 a token but one near 0: over a chunk of
    64 the cumulative decay reaches -315, whose ``exp(-G)`` is past
    float32, and the rule stays finite and agrees with the recurrence (a
    sub-chunk's largest factor is ``exp(15 x 5)``, with room to the floor
    of -5.9 the module states)."""
    assert (SUB - 1) * -DIAGONAL_G_FLOOR < np.log(np.finfo(np.float32).max)
    q, k, v, g, beta, state = _inputs(2, length, 2, 16, 8, seed=length)
    if bound:
        g = jnp.full(g.shape, -5.0).at[..., 0].set(-1e-3)
    true = max(1, length - 9)
    lengths = jnp.asarray([true, length])
    got_o, got_s = _chunk(q, k, v, g, beta, initial_state=state,
                          lengths=lengths)
    assert np.isfinite(np.asarray(got_o)).all()
    cut = lambda x: x[:1, :true]  # noqa: E731
    want_o, want_s = _recurrence(
        cut(q), cut(k), cut(v), cut(g), cut(beta), state[:1])
    assert _close(got_o[:1, :true], want_o) and _close(got_s[:1], want_s)
    whole_o, whole_s = _recurrence(q, k, v, g, beta, state)
    assert _close(got_o[1], whole_o[1]) and _close(got_s[1], whole_s[1])


def test_the_one_token_kernel_takes_a_decay_a_channel():
    """A decay a key channel is one step of the recurrence (a decay a head,
    broadcast down the same column, is ``tests/test_gated_delta.py``'s and
    the scalar pin below); a column of the wrong length is refused."""
    q, k, v, g, beta, state = _inputs(3, 1, 4, 16, 8, seed=2)
    want_o, want_s = gated_delta_reference(q, k, v, g, beta, state)
    got_o, got_s = gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0],
                                    g[:, 0], beta[:, 0])
    assert float(jnp.abs(got_o - want_o[:, 0]).max()) < 1e-6
    assert float(jnp.abs(got_s - want_s).max()) < 1e-6
    with pytest.raises(ValueError, match="does not go with"):
        gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, :3],
                         beta[:, 0])


def test_a_head_decay_is_the_column_of_its_channels():
    """The scalar decay of Gated DeltaNet is the per-channel rule with the
    same value in every channel (the chunked forms: the shapes of the first
    case above)."""
    q, k, v, g, beta, state = _inputs(2, CHUNK + 7, 2, 16, 8, seed=4,
                                      scalar=True)
    column = jnp.broadcast_to(g[..., None], g.shape + (16,))
    a, b = (_chunk(q, k, v, decay, beta, initial_state=state)
            for decay in (g, column))
    assert _close(a[0], b[0]) and _close(a[1], b[1])


#: sums of outputs and states of the scalar path at fixed inputs as the
#: program computed them before it took a decay a channel (float32, the
#: CPU, eager and jitted alike): the scalar path computes as it did, bit
#: for bit
_SCALAR_PIN = {"chunk_o_row0": "0x1.87d3b80000000p-3",
               "chunk_o_row1": "-0x1.f8bab40000000p+1",
               "chunk_state": "0x1.5aec9e0000000p+2",
               "chunk_state_sq": "0x1.35f4f20000000p+4",
               "step_o": "0x1.24ec680000000p-2",
               "step_state": "-0x1.43dd680000000p+0"}


def test_the_scalar_path_computes_as_it_did():
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (2, 150, 2, 16))) * 16 ** -0.5
    k = unit(jax.random.normal(ks[1], (2, 150, 2, 16)))
    v = jax.random.normal(ks[2], (2, 150, 4, 8))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (2, 150, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, 150, 4)))
    state = jax.random.normal(ks[5], (2, 4, 16, 8))
    o, s1 = _chunk(q, k, v, g, beta, initial_state=state,
                   lengths=jnp.asarray([97, 150]))
    o2, s2 = gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0])
    got = {"chunk_o_row0": o[0, :97].sum(), "chunk_o_row1": o[1].sum(),
           "chunk_state": s1.sum(), "chunk_state_sq": (s1 * s1).sum(),
           "step_o": o2.sum(), "step_state": s2.sum()}
    assert {name: float(x).hex() for name, x in got.items()} == _SCALAR_PIN


# -- the group-limited router -------------------------------------------------

def _router_params(e, d, seed):
    rng = np.random.default_rng(seed)
    return {"params": {
        "weight": jnp.asarray(0.3 * rng.normal(size=(e, d)), jnp.float32),
        "e_score_correction_bias":
            jnp.asarray(0.1 * rng.normal(size=e), jnp.float32)}}


def _noaux_tc(x, w, b, k, n_group, topk_group):
    """DeepSeek-V3's ``noaux_tc`` as it is written down, token by token in
    numpy."""
    idx, weights_ = [], []
    for row in np.asarray(x, np.float64):
        s = 1 / (1 + np.exp(-(np.asarray(w, np.float64) @ row)))
        choice = s + np.asarray(b, np.float64)
        groups = choice.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[kept] = groups[kept]
        top = np.argsort(-masked.reshape(-1), kind="stable")[:k]
        chosen = s[top] / (s[top].sum() + 1e-20)
        idx.append(sorted(top))
        weights_.append(dict(zip(top, chosen * 2.5)))
    return idx, weights_


@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 1), (2, 2)])
def test_the_group_router_is_noaux_tc(n_group, topk_group):
    x = jnp.asarray(np.random.default_rng(1).normal(size=(48, 24)),
                    jnp.float32)
    params = _router_params(64, 24, seed=n_group)
    router = SigmoidBiasTopKRouter(64, 8, routed_scaling_factor=2.5,
                                   n_group=n_group, topk_group=topk_group)
    idx, w = router.apply(params, x)
    p = params["params"]
    want_idx, want_w = _noaux_tc(x, p["weight"], p["e_score_correction_bias"],
                                 8, n_group, topk_group)
    for t in range(48):
        assert sorted(np.asarray(idx[t]).tolist()) == want_idx[t]
        for e, wt in zip(np.asarray(idx[t]), np.asarray(w[t])):
            assert abs(wt - want_w[t][e]) < 1e-5
        # every choice inside the kept groups
        assert len({int(e) // (64 // n_group) for e in idx[t]}) <= topk_group


def test_the_default_router_is_the_one_group_router_bit_for_bit():
    """(1, 1) adds nothing: GLM's and LFM2's routers compute the top k of
    ``s + b`` as before; a group count that does not divide the experts is
    refused, and the softmax router takes no groups."""
    x = jnp.asarray(np.random.default_rng(2).normal(size=(32, 24)),
                    jnp.float32)
    params = _router_params(16, 24, seed=3)
    idx, w = SigmoidBiasTopKRouter(16, 4).apply(params, x)
    p = params["params"]
    s = jax.nn.sigmoid(x @ p["weight"].T)
    _, want = jax.lax.top_k(s + p["e_score_correction_bias"], 4)
    chosen = jnp.take_along_axis(s, want, axis=-1)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(w), np.asarray(chosen / (chosen.sum(-1, keepdims=True)
                                            + 1e-20)))
    with pytest.raises(ValueError, match="do not split"):
        SigmoidBiasTopKRouter(16, 4, n_group=3).apply(params, x)
    with pytest.raises(ValueError, match="sigmoid"):
        DroplessMoEMLP(hidden_size=24, ffn_hidden_size=8, num_experts=16,
                       k=4, router="softmax", n_group=4).init(
            jax.random.PRNGKey(0), x)


def test_four_shares_of_the_experts_add_up_to_the_whole_layer():
    """Each chip of the deployment holds a quarter of the experts: the four
    shares' routed parts, plus the shared expert once, are the reference's
    layer with every expert held. The router is whole on every chip, so
    each share's pairs are the whole layer's that land on it."""
    cfg = tiny_cfg()
    n = 1                                       # a routed layer
    p = reference._f32(_make()(reference.layer_table(cfg, n)),
                       f"layer_{n}/")
    x = jnp.asarray(np.random.default_rng(4).normal(size=(40, 64)),
                    jnp.float32)
    mm = functools.partial(jnp.matmul, precision="highest")
    with jax.default_matmul_precision("highest"):
        want = reference.expert_layer(x, p, cfg, mm, "float32")
        shared = reference._swiglu(mm, x, p, "moe/shared")
        total = -3 * shared
        for first in (0, 4, 8, 12):
            layer = DroplessMoEMLP(
                hidden_size=64, ffn_hidden_size=32, num_experts=16, k=4,
                shared_experts=1, routed_scaling_factor=2.5, n_group=4,
                topk_group=2, held=4, first=first)
            tree = {
                "router": {"weight": p["moe/router/weight"],
                           "e_score_correction_bias":
                           p["moe/router/e_score_correction_bias"]},
                "experts": {m: p[f"moe/experts/{m}"][first:first + 4]
                            for m in ("gate_proj", "up_proj",
                                      "down_proj")},
                "shared": {m: {"weight": p[f"moe/shared/{m}/weight"]}
                           for m in ("gate_proj", "up_proj",
                                     "down_proj")}}
            total = total + layer.apply({"params": tree}, x)
    assert float(jnp.abs(total - want).max()) < TOL


# -- the model alone ----------------------------------------------------------

def test_the_tiny_preset_is_the_tiny_configuration_file():
    _, model, _ = _built()
    cfg = model.config
    assert cfg == bailing_hybrid_tiny_config()
    assert cfg.layer_types == (KDA, KDA, MLA, KDA)
    states = cfg.layer_states
    assert states[2] is None
    assert [(t.name, t.shape) for t in states[0]] == [
        ("delta_state", (4, 16, 16)), ("conv_state", (3, 192))]
    assert cfg.kv_latent_width == 40
    published = BailingHybridConfig()
    kinds = published.layer_types
    assert kinds.count(MLA) == 7 and kinds.count(KDA) == 35
    assert [i for i, t in enumerate(kinds) if t == MLA] == list(
        range(5, 42, 6))
    held = BailingHybridConfig(num_layers=7, first_layer=1)
    assert held.layer_types == (KDA,) * 4 + (MLA,) + (KDA,) * 2


def test_a_clamped_swiglu_is_refused_by_name():
    limits = (0,) * 35 + (4,) * 7
    BailingHybridConfig(num_layers=7, first_layer=1,
                        expert_swiglu_limit=limits)
    with pytest.raises(ValueError, match="expert_swiglu_limit_list is 4 at "
                                         "published layer 35"):
        BailingHybridConfig(num_layers=7, first_layer=30,
                            expert_swiglu_limit=limits)


def test_the_cuts_layers_count_their_parameters():
    """The configuration's arithmetic, from the program's own parameter
    shapes at the published widths: a KDA mixer 63,049,888, an MLA mixer
    31,965,888, the dense KDA layer 110,240,928, a routed KDA layer
    825,239,200 and the routed MLA layer 794,155,200 at 128 experts held;
    with a quarter of the vocabulary, 10.46 GB in bfloat16."""
    cfg = BailingHybridConfig(num_layers=5, first_layer=1,
                              first_k_dense_replace=1, experts_held=128,
                              vocab_size=39296)
    like = jax.eval_shape(BailingHybridModel(cfg).init,
                          jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert cfg.layer_types[4] == MLA
    assert count(like["layer_0"]["mixer"]) == 63_049_888
    assert count(like["layer_4"]["attn"]) == 31_965_888
    assert count(like["layer_0"]) == 110_240_928
    assert count(like["layer_1"]) == 825_239_200
    assert count(like["layer_4"]) == 794_155_200
    vocab = count(like["embed_tokens"]) + count(like["lm_head"]) \
        + count(like["final_norm"])
    assert vocab == 201_198_080
    cut = 110_240_928 + 5 * 825_239_200 + 794_155_200 + vocab
    assert cut == 5_231_790_208 and round(2 * cut / 1e9, 2) == 10.46
    assert kv_pool.state_bytes(cfg) == 4 * 2_170_880


def test_full_forward_matches_the_references_logits():
    _, model, variables = _built()
    ids = _ids(70)
    got = np.asarray(jax.jit(model.apply)(variables,
                                          jnp.asarray(ids)[None])[0])
    assert np.abs(got - _reference_logits([ids])[0]).max() < TOL


@pytest.mark.parametrize("variant", ["fp8", "state_zeroed",
                                     "scalar_decay"])
def test_each_named_fault_moves_the_references_logits(variant):
    """What the comparison can put in the reference's place is another
    computation (``state_zeroed`` from the hand-over on, here the second
    position, and at the seeded decay, a memory of tens of tokens, to the
    end)."""
    seq = _ids(40, seed=3)
    plain = _reference_logits([seq])[0]
    moved = _reference_logits([seq], variant)[0]
    assert np.abs(plain - moved).max() > 50 * TOL
    if variant == "state_zeroed":
        assert np.abs(plain - moved)[0].max() == 0
        assert np.abs(plain - moved)[-1].max() > 50 * TOL


# -- admission, then paged decode, through the engine's programs and pool -----


@pytest.fixture(scope="module")
def engine():
    _, model, variables = _built()
    return PagedDecodeEngine(model, variables, num_slots=3, page_size=8,
                             num_pages=60, sync_every=2)


def _admit(engine, cache, slot, seq, n):
    ps = engine.page_size
    bucket = prompt_bucket(n, ps, engine.cfg.max_position_embeddings)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seq[:n]
    # what follows the prompt in its page bucket is NOT zeros: whatever the
    # padding holds must not reach a state
    ids[0, n:] = 77
    return engine._admit_fn(bucket)(
        cache, engine.variables, jnp.asarray(ids), jnp.int32(n),
        jnp.int32(slot), jnp.int32(-(-len(seq) // ps)),
        jax.random.PRNGKey(0), jnp.int32(0))


@functools.lru_cache(maxsize=None)
def _step(model):
    _, _, variables = _built()
    return jax.jit(lambda c, t: model.apply(variables, t, cache=c))


def _paged_logits(engine, sequences, prompt_lens, fault=None, slots=None,
                  cache=None):
    """Teacher-forced logits of ``sequences`` through the engine's own
    admit programs (one slot each: ``slots``, else the first ones) and then
    the model's paged step over the engine's pool, a token a step:
    ``[(position, logits [V])]`` a sequence, and the cache after. ``fault``
    may change the cache once, between admission and decode."""
    cache = engine.cache if cache is None else cache
    slots = list(range(len(sequences))) if slots is None else slots
    first = []
    for slot, seq, n in zip(slots, sequences, prompt_lens):
        cache, tok0 = _admit(engine, cache, slot, seq, n)
        first.append(int(tok0))
    if fault is not None:
        cache = fault(cache)
    step = _step(engine.model)
    out = [[] for _ in sequences]
    for j in range(max(len(s) - n for s, n in zip(sequences, prompt_lens))):
        tok = np.zeros((engine.num_slots, 1), np.int32)
        live = [i for i, (s, n) in enumerate(zip(sequences, prompt_lens))
                if n + j < len(s)]
        for i in live:
            tok[slots[i], 0] = sequences[i][prompt_lens[i] + j]
        before = cache["len"]
        logits, cache = step(cache, jnp.asarray(tok))
        keep = np.zeros((engine.num_slots,), bool)
        keep[[slots[i] for i in live]] = True
        cache = dict(cache, len=jnp.where(keep, cache["len"], before))
        for i in live:
            out[i].append((prompt_lens[i] + j,
                           np.asarray(logits[slots[i], 0])))
    return first, out, cache


#: prompts of 37, 3 and 21 tokens at a page of 8 (buckets 40, 8 and 24:
#: none on the grid), then 12, 9 and 10 decoded tokens, in one engine
SEQS = ((49, 1, 37), (12, 2, 3), (31, 4, 21))


def _sequences():
    return [_ids(n, s) for n, s, _ in SEQS], [p for _, _, p in SEQS]


@functools.lru_cache(maxsize=None)
def _clean_run(engine):
    seqs, prompts = _sequences()
    return _paged_logits(engine, seqs, prompts)


def test_admission_then_paged_decode_matches_the_references_logits(engine):
    seqs, prompts = _sequences()
    want = _reference_logits(seqs)
    first, got, cache = _clean_run(engine)
    for seq, n, ref, tok0, steps in zip(seqs, prompts, want, first, got):
        assert tok0 == int(ref[n - 1].argmax())
        assert [p for p, _ in steps] == list(range(n, len(seq)))
        for p, logits in steps:
            assert np.abs(logits - ref[p]).max() < TOL, (len(seq), p)
    # the MLA layer's entries went to latent pages, the KDA layers' to
    # their state rows
    assert sorted(cache["layers"][2]) == ["latent_pages"]
    assert all(sorted(cache["layers"][i]) == ["conv_state", "delta_state"]
               for i in (0, 1, 3))


def _zeroed(cache):
    return dict(cache, layers=[
        {k: jnp.zeros_like(x) if k == "delta_state" else x
         for k, x in lc.items()} for lc in cache["layers"]])


def _swapped(cache):
    """Slot 0's state handed to slot 2 and slot 2's to slot 0."""
    order = jnp.array([2, 1, 0])
    return dict(cache, layers=[
        {k: x[order] if k == "delta_state" else x for k, x in lc.items()}
        for lc in cache["layers"]])


@pytest.mark.parametrize("fault", [_zeroed, _swapped],
                         ids=["zeroed", "wrong_slot"])
def test_a_lost_or_misplaced_state_fails_the_logits(engine, fault):
    """The hand-over from admission to decode: a recurrent state zeroed, or
    handed to another slot, fails the first decoded positions' logits of
    every slot whose state was wrong."""
    seqs, prompts = _sequences()
    want = _reference_logits(seqs)
    _, got, _ = _paged_logits(engine, seqs, prompts, fault=fault)
    for i in (0, 2):
        worst = max(np.abs(logits - want[i][p]).max()
                    for p, logits in got[i][:2])
        assert worst > 100 * TOL, i


@pytest.mark.parametrize("stale", [False, True], ids=["overwritten", "stale"])
def test_a_retired_slots_state_does_not_reach_its_next_request(engine,
                                                                stale):
    """A slot retired and reused: the first request of ``_clean_run`` held
    slot 0 and retires; B is admitted to the same slot. The admission
    overwrites the slot's state whole, so B's logits are the reference's
    at every position; a row left as A ended it (what an admission that
    wrote no state would hand over) fails them."""
    _, _, cache = _clean_run(engine)
    keep = jnp.zeros(cache["block_tables"].shape[1], bool)
    cache = kv_pool.release_slot(cache, 0, keep)
    rows = {i: (lc["delta_state"][0], lc["conv_state"][0])
            for i, lc in enumerate(cache["layers"]) if "delta_state" in lc}
    assert sorted(rows) == [0, 1, 3] and all(
        r[0].any() for r in rows.values())

    def left_as_it_was(c):
        return dict(c, layers=[
            dict(lc, delta_state=lc["delta_state"].at[0].set(rows[i][0]),
                 conv_state=lc["conv_state"].at[0].set(rows[i][1]))
            if i in rows else lc for i, lc in enumerate(c["layers"])])

    seq_b, n_b = _ids(31, 8), 19
    _, (got,), _ = _paged_logits(engine, [seq_b], [n_b], slots=[0],
                                 cache=cache,
                                 fault=left_as_it_was if stale else None)
    want = _reference_logits([seq_b])[0]
    gaps = [np.abs(logits - want[p]).max() for p, logits in got]
    if stale:
        assert min(gaps[:2]) > 100 * TOL
    else:
        assert max(gaps) < TOL


def test_engine_run_serves_what_the_reference_puts_first(engine):
    """The whole path (frontend, pump, the decode chunk's scan, slots
    re-used by later requests): every served token is the float32
    reference's first choice, and the counters know both groups."""
    reqs = [Request(prompt=_ids(n, 10 + n), max_new_tokens=m)
            for n, m in [(5, 9), (37, 20), (20, 30), (3, 4), (39, 12)]]
    frontend = ServingFrontend(engine)
    handles = [frontend.submit(r, request_id=i) for i, r in enumerate(reqs)]
    frontend.drain()
    outs = [np.asarray(h.result(timeout=0), np.int32) for h in handles]
    stats, counted = frontend.stats(), frontend.counter_deltas()
    for r, out in zip(reqs, outs):
        n = len(r.prompt)
        ref = _reference_logits([np.concatenate([r.prompt, out[:-1]])])[0]
        assert len(out) == r.max_new_tokens
        np.testing.assert_array_equal(out, ref[n - 1:].argmax(-1))
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert kv_pool.state_bytes(engine.cfg) == per_slot
    groups = stats["kv_groups"]
    assert groups[0]["layers"] == [0, 1, 3]
    assert groups[0]["state"] == ["delta_state", "conv_state"]
    assert groups[0]["state_bytes_per_slot"] == per_slot
    assert groups[1]["layers"] == [2] and groups[1]["pages_held"] == 59
    assert counted["state_bytes_moved"] == \
        2 * per_slot * counted["busy_slot_steps"]
    # whole latent entries of 40 float32 values, in the one MLA layer
    assert counted["kv_bytes_attended"] > 0
    assert counted["kv_bytes_attended"] % (40 * 4) == 0
    assert counted["expert_pairs_routed"] == \
        4 * 3 * 3 * counted["decode_steps"]     # top 4 x slots x 3 layers


def test_what_a_state_group_or_a_latent_pool_cannot_do_is_refused():
    _, model, variables = _built()
    with pytest.raises(kv_pool.StateGroupUnsupported, match="prefix_cache"):
        PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                          num_pages=20, prefix_cache=True)
    with pytest.raises(kv_pool.LatentPoolUnsupported):
        kv_pool.init_paged_cache(model.config, 2, num_pages=20, page_size=8,
                                 kv_dtype="int8")
