"""``models/lfm2_moe.py`` at a small size on the CPU, seeded weights: one
dense conv layer, then conv, attention, conv over 8 experts top 4 — against
the plain reference ``benchmark/references/lfm2_moe.py``, model-only and
through ``PagedDecodeEngine``'s own admission and pool; the short
convolution (``ops/short_conv.py``) over a prompt and a token at a time; the
state group a window alone fills, and a slot's window handed from request to
request; the router's renormalisation epsilon.

The page is 8 tokens (the pool refuses a page that is no sublane
multiple). Every prompt here is OFF the page grid, so every admission
pads, and the padding must not reach a window.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import init_cache
from apex_tpu.models.lfm2_moe import (CONV, FULL, PUBLISHED_LAYER_TYPES,
                                      Lfm2MoeConfig, Lfm2MoeModel,
                                      lfm2_moe_tiny_config)
from apex_tpu.ops.short_conv import short_conv
from apex_tpu.serving import (PagedDecodeEngine, Request, ServingFrontend,
                              kv_pool)
from apex_tpu.serving.scheduler import prompt_bucket
from apex_tpu.transformer.moe.router import SigmoidBiasTopKRouter
from benchmark.families import lfm2_moe as family
from benchmark.harness import weights
from benchmark.references import lfm2_moe as reference

SEED = 2 ** 31 + 41

#: float32 on both sides: what is left is the order of the sums (flash
#: tiles and page blocks against one dense softmax, the grouped products
#: against one product an expert, the convolution's three terms), a few
#: 1e-7 of logits of order 0.1. The same model computed in bfloat16 reads
#: 1e-3 and more (``test_bfloat16_fails_the_float32_tolerance``)
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
        model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, layer_types=[CONV, FULL, CONV, CONV],
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        conv_L_cache=3, conv_bias=False, num_experts=8,
        num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1,
        use_expert_bias=True, num_hidden_layers=4, norm_eps=1e-5,
        vocab_size=128, max_position_embeddings=256,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
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


def _reference_logits(cfg, sequences, variant="float32"):
    make = functools.partial(weights.make_weights, seed=SEED)
    return [np.asarray(x) for x in reference.logits_at(
        make, cfg, sequences, [np.arange(len(s)) for s in sequences],
        variant)]


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(4, 128, n).astype(np.int32)


# -- the model alone --------------------------------------------------------------

def test_the_tiny_preset_is_the_tiny_configuration_file():
    _, model, _ = _built()
    assert model.config == lfm2_moe_tiny_config()
    published = Lfm2MoeConfig()
    assert published.layer_types == PUBLISHED_LAYER_TYPES
    assert published.num_layers == 40 and published.head_dim == 64
    assert published.layer_types.count(FULL) == 10
    assert [i for i, t in enumerate(published.layer_types) if t == FULL] \
        == list(range(2, 40, 4))
    states = published.layer_states
    assert states[2] is None and [t.name for t in states[0]] == ["conv_state"]
    assert states[0][0].shape == (2, 2048)
    assert published.routed_expert_bytes == 3 * 2048 * 1536 * 2
    with pytest.raises(ValueError, match="layer_types holds"):
        Lfm2MoeConfig(layer_types=(CONV, "sliding_attention"))


def test_the_published_layers_count_their_parameters():
    """The configuration's arithmetic, from the program's own parameter
    shapes at the published widths: a conv mixer 16,783,360, an attention mixer
    10,485,888, a routed conv layer 620,898,368, a routed attention layer
    614,600,896, the dense conv layer 89,139,200, the tied embedding
    134,217,728; the cut's nine layers 10.36 GB in bfloat16."""
    cfg = Lfm2MoeConfig(layer_types=(CONV, FULL, CONV), num_dense_layers=1)
    like = jax.eval_shape(Lfm2MoeModel(cfg).init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert count(like["layer_0"]["conv"]) == 16_783_360
    assert count(like["layer_1"]["self_attn"]) == 10_485_888
    assert count(like["layer_1"]["moe"]["router"]) == 131_136
    assert count(like["layer_0"]) == 89_139_200
    assert count(like["layer_1"]) == 614_600_896
    assert count(like["layer_2"]) == 620_898_368
    assert count(like["embed_tokens"]) == 134_217_728
    assert set(like) == {"embed_tokens", "embedding_norm", "layer_0",
                         "layer_1", "layer_2"}          # the head is tied
    cut = 134_217_728 + 89_139_200 + 2 * 614_600_896 + 6 * 620_898_368
    assert round(2 * cut / 1e9, 2) == 10.36
    # the published 40: 24B total
    total = 134_217_728 + 2 * 89_139_200 + 10 * 614_600_896 \
        + 28 * 620_898_368
    assert round(total / 1e9, 1) == 23.8


@pytest.mark.parametrize("length", [5, 40, 130])
def test_full_forward_matches_the_references_logits(length):
    cfg, model, variables = _built()
    ids = _ids(length)
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None])[0])
    want = _reference_logits(cfg, [ids])[0]
    assert np.abs(got - want).max() < TOL


def test_bfloat16_fails_the_float32_tolerance():
    """The program computed in bfloat16 (the cell's precision) departs from
    the float32 reference by far more than the tolerance, so the tolerance
    is one a precision below float32 cannot meet."""
    cfg, _, variables = _built()
    _, model16, _ = _built(compute_dtype="bfloat16")
    ids = _ids(40)
    got = np.asarray(model16.apply(variables, jnp.asarray(ids)[None])[0],
                     np.float32)
    assert np.abs(got - _reference_logits(cfg, [ids])[0]).max() > 50 * TOL


@pytest.mark.parametrize("variant", [v for v in reference.VARIANTS
                                     if v != "float32"])
def test_each_named_fault_moves_the_references_logits(variant):
    """What the comparison can put in the reference's place is another
    computation."""
    cfg = tiny_cfg()
    make = functools.partial(weights.make_weights, seed=SEED)
    seqs = [_ids(40, seed=3)]
    pos = [14 + np.arange(26)]
    plain = reference.logits_at(make, cfg, seqs, pos)[0]
    moved = reference.logits_at(make, cfg, seqs, pos, variant)[0]
    assert float(jnp.abs(plain - moved).max()) > 50 * TOL
    judged = family.judge(cfg, SEED, [(seqs[0][:15], seqs[0][15:])], variant)
    assert judged["tokens"] == 25 and 0 <= judged["gap"] <= judged["widest"]
    with pytest.raises(ValueError, match="unknown variant"):
        family.judge(cfg, SEED, [(seqs[0][:15], seqs[0][15:])], "float16")


def test_the_window_zeroed_moves_only_the_first_two_decoded_tokens():
    """A window is two tokens: losing it at the hand-over changes the
    logits of the first two decoded positions and no later one, which is
    why the cell's mean gap cannot see it and the engine tests below
    compare logits."""
    cfg = tiny_cfg()
    make = functools.partial(weights.make_weights, seed=SEED)
    seq, n = _ids(30, seed=6), 12
    pos = [n - 1 + np.arange(len(seq) - n + 1)]
    plain = np.asarray(reference.logits_at(make, cfg, [seq], pos)[0])
    moved = np.asarray(reference.logits_at(make, cfg, [seq], pos,
                                           "window_zeroed")[0])
    diff = np.abs(plain - moved).max(-1)
    # position n - 1 is the admission's own; n and n + 1 read the window
    # (4.6e-4 and 6.2e-4 here); later positions see it only through the
    # attention layer's keys of those two (under 1e-4)
    assert diff[0] == 0 and (diff[1:3] > 10 * TOL).all()
    assert diff[1:3].min() > diff[3:].max()


# -- the short convolution over a prompt and a token at a time ---------------------

@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("slots", [1, 3, 128])
def test_one_token_a_call_is_the_prompt_form_bit_for_bit(dtype, slots):
    """The decode step's call (one token a slot, the window carried) gives
    the same window bits as one call over the whole chunk, and in the
    cell's bfloat16 the same output bits: the window's ``u`` is rounded to
    its dtype before either form reads it. In float32 ``y`` is within the
    rounding of its three-term sum (1e-6 of terms of order 1): the CPU
    fuses the chunk's products into multiply-adds where it rounds the one
    token's apart."""
    rng = np.random.default_rng(slots)
    dt = jnp.dtype(dtype)
    seq = jnp.asarray(rng.normal(size=(slots, 9, 3 * 128)), dt)
    w = jnp.asarray(0.3 * rng.normal(size=(3, 128)), dt)
    y, window = short_conv(seq, w)
    assert y.dtype == dt and window.dtype == dt
    assert window.shape == (slots, 2, 128)
    step = jax.jit(short_conv)
    win = jnp.zeros((slots, 2, 128), dt)
    for t in range(9):
        y_t, new = step(seq[:, t:t + 1], w, win)
        np.testing.assert_allclose(np.asarray(y_t[:, 0], np.float32),
                                   np.asarray(y[:, t], np.float32), rtol=0,
                                   atol=0 if dtype == "bfloat16" else 1e-6)
        # the window shifted by one: the older row out, the newest u in
        np.testing.assert_array_equal(np.asarray(new[:, 0], np.float32),
                                      np.asarray(win[:, 1], np.float32))
        win = new
    np.testing.assert_array_equal(np.asarray(win, np.float32),
                                  np.asarray(window, np.float32))


def test_the_prompt_form_and_the_steps_agree_and_take_the_true_end():
    """A row padded past its length hands over the window of its true end
    (the steps' agreement with the prompt form is the test above); shapes
    that do not go together are refused."""
    rng = np.random.default_rng(0)
    bf = jnp.bfloat16
    seq = jnp.asarray(rng.normal(size=(1, 9, 3 * 128)), bf)
    w = jnp.asarray(0.3 * rng.normal(size=(3, 128)), bf)
    padded = jnp.concatenate([seq[:, :5], seq[:, :4] * 3], axis=1)
    _, at_end = short_conv(padded, w, lengths=jnp.array([5]))
    _, alone = short_conv(seq[:, :5], w)
    np.testing.assert_array_equal(np.asarray(at_end, np.float32),
                                  np.asarray(alone, np.float32))
    with pytest.raises(ValueError, match="do not go together"):
        short_conv(seq[:, :1], w, jnp.zeros((1, 3, 128), bf))


# -- admission, then paged decode, through the engine's programs and pool ---------

def _engine(model, variables, **kw):
    kw = dict(dict(num_slots=3, page_size=8, num_pages=60, sync_every=2), **kw)
    return PagedDecodeEngine(model, variables, **kw)


def _admit(engine, cache, slot, seq, n):
    ps = engine.page_size
    bucket = prompt_bucket(n, ps, engine.cfg.max_position_embeddings)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seq[:n]
    # what follows the prompt in its page bucket is NOT zeros: whatever the
    # padding holds must not reach the window
    ids[0, n:] = 77
    return engine._admit_fn(bucket)(
        cache, engine.variables, jnp.asarray(ids), jnp.int32(n),
        jnp.int32(slot), jnp.int32(-(-len(seq) // ps)),
        jax.random.PRNGKey(0), jnp.int32(0))


def _paged_logits(engine, sequences, prompt_lens, fault=None, slots=None):
    """Teacher-forced logits of ``sequences`` through the engine's own
    admit programs (one slot each: ``slots``, else the first ones) and then
    the model's paged step over the engine's pool, a token a step:
    ``[(position, logits [V])]`` a sequence. ``fault`` may change the cache
    once, between admission and decode."""
    model, variables, cache = engine.model, engine.variables, engine.cache
    slots = list(range(len(sequences))) if slots is None else slots
    first = []
    for slot, seq, n in zip(slots, sequences, prompt_lens):
        cache, tok0 = _admit(engine, cache, slot, seq, n)
        first.append(int(tok0))
    if fault is not None:
        cache = fault(cache)
    step = jax.jit(lambda c, t: model.apply(variables, t, cache=c))
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
    engine.cache = cache
    return first, out


#: prompts of 37, 3 and 21 tokens at a page of 8 (buckets 40, 8 and 24:
#: none is on the grid, one is shorter than the window), then 12, 9 and 10
#: decoded tokens, in one engine
SEQS = ((58, 1, 37), (12, 2, 3), (31, 4, 21))


def _sequences():
    return [_ids(n, s) for n, s, _ in SEQS], [p for _, _, p in SEQS]


def test_admission_then_paged_decode_matches_the_references_logits():
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    seqs, prompts = _sequences()
    want = _reference_logits(cfg, seqs)
    first, got = _paged_logits(engine, seqs, prompts)
    for seq, n, ref, tok0, steps in zip(seqs, prompts, want, first, got):
        assert len(seq) - n >= 9
        assert tok0 == int(ref[n - 1].argmax())
        assert [p for p, _ in steps] == list(range(n, len(seq)))
        for p, logits in steps:
            assert np.abs(logits - ref[p]).max() < TOL, (len(seq), p)


def _zeroed(cache):
    return dict(cache, layers=[
        {k: jnp.zeros_like(x) if k == "conv_state" else x
         for k, x in lc.items()} for lc in cache["layers"]])


def _swapped(cache):
    """Slot 0's window handed to slot 2 and slot 2's to slot 0."""
    order = jnp.array([2, 1, 0])
    return dict(cache, layers=[
        {k: x[order] if k == "conv_state" else x for k, x in lc.items()}
        for lc in cache["layers"]])


@pytest.mark.parametrize("fault", [_zeroed, _swapped],
                         ids=["zeroed", "wrong_slot"])
def test_a_lost_or_misplaced_window_fails_the_logits(fault):
    """The hand-over the cell's MEAN gap cannot see (PERF.md section 7): a
    window zeroed, or handed to another slot, between admission and the
    first decode step. The logits of the first decoded positions do, for
    every slot whose window was wrong (by 4e-4 and more: the conv layers'
    share of a seeded stream)."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    seqs, prompts = _sequences()
    want = _reference_logits(cfg, seqs)
    _, got = _paged_logits(engine, seqs, prompts, fault=fault)
    for i in (0, 2):
        worst = max(np.abs(logits - want[i][p]).max()
                    for p, logits in got[i][:2])
        assert worst > 10 * TOL, i


def _with_rows(rows, slot):
    """A fault: slot ``slot``'s window rows set back to ``rows`` (what an
    admission that wrote no window would leave)."""
    def fault(cache):
        return dict(cache, layers=[
            dict(lc, conv_state=lc["conv_state"].at[slot].set(rows[i]))
            if i in rows else lc for i, lc in enumerate(cache["layers"])])
    return fault


@pytest.mark.parametrize("stale", [False, True], ids=["overwritten", "stale"])
def test_a_retired_slots_window_does_not_reach_its_next_request(stale):
    """A slot retired and reused: request A is admitted to slot 1, decodes
    and retires; B is admitted to the same slot. The admission overwrites
    the slot's window whole, so B's logits are the reference's at every
    position. A row left as A ended it (``stale``: what an admission that
    wrote no window would hand over) fails B's first decoded positions,
    which the cell's mean gap would not see (PERF.md section 7)."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    (seq_a, n_a), (seq_b, n_b) = (_ids(30, 7), 11), (_ids(25, 8), 13)
    _paged_logits(engine, [seq_a], [n_a], slots=[1])
    keep = jnp.zeros(engine.cache["block_tables"].shape[1], bool)
    engine.cache = kv_pool.release_slot(engine.cache, 1, keep)
    rows = {i: lc["conv_state"][1]
            for i, lc in enumerate(engine.cache["layers"])
            if "conv_state" in lc}
    assert sorted(rows) == [0, 2, 3] and all(r.any() for r in rows.values())
    _, (got,) = _paged_logits(engine, [seq_b], [n_b], slots=[1],
                              fault=_with_rows(rows, 1) if stale else None)
    want = _reference_logits(cfg, [seq_b])[0]
    gaps = [np.abs(logits - want[p]).max() for p, logits in got]
    assert [p for p, _ in got] == list(range(n_b, len(seq_b)))
    if stale:
        assert min(gaps[:2]) > 10 * TOL
    else:
        assert max(gaps) < TOL


@pytest.mark.parametrize("n", [1, 2, 3, 21])
def test_the_padded_tail_does_not_reach_the_window(n):
    """An admission at its page bucket leaves in the slot's row exactly the
    window the ``n`` true tokens alone leave (one and two tokens: shorter
    than the window, whose older rows stay zeros)."""
    _, model, variables = _built()
    engine = _engine(model, variables)
    seq = _ids(n + 4, 5)
    cache, _ = _admit(engine, engine.cache, 1, seq, n)
    contig = init_cache(model.config, 1, 64)
    _, contig = model.apply(variables, jnp.asarray(seq[:n])[None],
                            cache=contig)
    for i in (0, 2, 3):
        got = np.asarray(cache["layers"][i]["conv_state"][1])
        want = np.asarray(contig["layers"][i]["conv_state"][0])
        # within rounding: the bucket's products and flash tiles are not
        # the true length's
        assert np.abs(got - want).max() < 1e-6, i
        assert not np.asarray(cache["layers"][i]["conv_state"][0]).any()
        if n == 1:
            assert not got[0].any() and got[1].any()


def test_engine_run_serves_what_the_reference_puts_first():
    """The whole path (frontend, pump, the decode chunk's scan, slots
    re-used by later requests): every served token is the float32
    reference's first choice, and the counters know the window."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    reqs = [Request(prompt=_ids(n, 10 + n), max_new_tokens=m)
            for n, m in [(5, 9), (37, 20), (20, 30), (3, 4), (50, 12)]]
    frontend = ServingFrontend(engine)
    handles = [frontend.submit(r, request_id=i) for i, r in enumerate(reqs)]
    frontend.drain()
    outs = [np.asarray(h.result(timeout=0), np.int32) for h in handles]
    stats, counted = frontend.stats(), frontend.counter_deltas()
    judged = family.judge(cfg, SEED, [(r.prompt, o)
                                      for r, o in zip(reqs, outs)])
    assert judged["tokens"] == 75 and judged["widest"] < TOL
    # the window: two rows of 64 float32 a conv layer, three conv layers
    per_slot = 3 * 2 * 64 * 4
    assert kv_pool.state_bytes(model.config) == per_slot
    assert stats["kv_groups"] == [
        {"layers": [0, 2, 3], "window": None, "ring_pages_per_slot": None,
         "pages_held": 0, "state": ["conv_state"],
         "state_bytes_per_slot": per_slot},
        {"layers": [1], "window": None, "ring_pages_per_slot": None,
         "pages_held": 59}]
    # the state group's counter covers the window unchanged: read and
    # written by every decoding slot-step
    assert counted["state_bytes_moved"] == \
        2 * per_slot * counted["busy_slot_steps"]
    assert counted["expert_pairs_routed"] == \
        4 * 3 * 3 * counted["decode_steps"]     # top 4 x slots x 3 layers


def test_the_stats_name_the_rows_the_products_are_handed():
    """``stats()["expert_served_rows"]``: for the decode step and each admit
    program built so far, the rows a layer routes (tokens x top 4), the
    rows ``dropless.served_rows`` hands its products at the tiny stack's
    shapes (8 experts, 64 x 32, float32) and the row tile of that count. A
    decode step's 12 rows (a 4-row tile) are handed 128, the 116 past them
    in no group."""
    _, model, variables = _built()
    engine = _engine(model, variables)
    for bucket in (8, 40):
        engine._admit_fn(bucket)
    rows = ServingFrontend(engine).stats()["expert_served_rows"]
    assert rows == {"decode": (12, 128, 128),
                    "admit": {8: (32, 32, 32), 40: (160, 256, 256)}}


# -- the router ---------------------------------------------------------------------

def _route(eps=None, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    kw = {} if eps is None else {"norm_eps": eps}
    router = SigmoidBiasTopKRouter(8, 4, **kw)
    params = {"params": {
        "weight": jnp.asarray(0.3 * rng.normal(size=(8, 32)), jnp.float32),
        "e_score_correction_bias":
            jnp.asarray(0.02 * rng.normal(size=8), jnp.float32)}}
    return x, params, router.apply(params, x)


def test_the_routers_epsilon_leaves_glms_weights_bitwise_unchanged():
    """The default is GLM's 1e-20, and the weights are bit for bit what the
    router computed before it had the field; LFM2's 1e-6 gives its own."""
    assert SigmoidBiasTopKRouter(8, 4).norm_eps == 1e-20
    from apex_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig
    assert not hasattr(Glm4MoeLiteConfig(), "router_norm_eps")
    x, params, (idx, w) = _route()
    p = params["params"]
    scores = jax.nn.sigmoid(x @ p["weight"].T)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    before = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(before))
    _, _, (idx6, w6) = _route(1e-6)
    np.testing.assert_array_equal(np.asarray(idx6), np.asarray(idx))
    lfm = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_array_equal(np.asarray(w6), np.asarray(lfm))
    assert (np.asarray(w6) != np.asarray(w)).any()
    assert Lfm2MoeConfig().router_norm_eps == reference.ROUTER_EPS == 1e-6


def test_seeded_expert_bias_changes_the_choice_of_a_tenth_of_the_tokens():
    """At the harness's one scale (N(0, 0.02)) and the published router
    width (64 experts over 2048), the seeded expert bias changes the top-4
    set of 44.5 % of these 512 tokens: leaving it out must show in the
    comparison."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(512, 2048)).astype(np.float32)
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    w = weights.STD * rng.normal(size=(64, 2048)).astype(np.float32)
    b = weights.STD * rng.normal(size=64).astype(np.float32)
    scores = 1 / (1 + np.exp(-(x @ w.T)))
    with_b = np.sort(np.argsort(-(scores + b), axis=1)[:, :4], axis=1)
    without = np.sort(np.argsort(-scores, axis=1)[:, :4], axis=1)
    assert (with_b != without).any(axis=1).mean() == 0.4453125
