"""``models/qwen3_next.py`` at a small size on the CPU, seeded weights: one
period of four layers (linear, linear, linear, full), 16 experts top 4 —
against the plain reference ``benchmark/references/qwen3_next.py``,
model-only and through ``PagedDecodeEngine``'s own admission and pool; the
pool's state group (``serving/kv_pool.layer_groups``); the share of the
experts; the engine's refusals.

The page is 8 tokens and not the issue's 4: the pool refuses a page that is
no sublane multiple (``init_paged_cache``).  Every prompt here is OFF the
page grid, so every admission pads, and the padding must not reach a state.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.qwen3_next import (FULL, LINEAR, Qwen3NextConfig,
                                        Qwen3NextModel,
                                        qwen3_next_tiny_config)
from apex_tpu.serving import (PagedDecodeEngine, PriorityDeadlinePolicy,
                              Request, ServingFrontend, kv_pool)
from apex_tpu.serving.scheduler import prompt_bucket
from apex_tpu.transformer.moe import (ROUTING_COLLECTION,
                                      SHARE_ROUTING_STATS, DroplessMoEMLP,
                                      grouped_experts)
from benchmark.families import qwen3_next as family
from benchmark.harness import weights
from benchmark.references import qwen3_next as reference

SEED = 2 ** 31 + 37

#: float32 on both sides: what is left is the order of the sums (the chunked
#: rule's products against the reference's token-by-token recurrence, flash
#: tiles and page blocks against one dense softmax, the grouped products
#: against one product an expert), a few 1e-7 of logits of order 0.5. The
#: same model computed in bfloat16 reads 3e-3 and more
#: (``test_bfloat16_fails_the_float32_tolerance``)
TOL = 1e-5


@pytest.fixture(autouse=True, scope="module")
def _each_group_of_weights_made_once():
    """``weights.make_weights`` jits its builder anew at every call: a
    second of CPU compile a group of leaves, six groups a pass of the
    reference, some fifty passes in this module. A leaf's values depend on
    the seed and its name alone, so each (table, seed) is made once here."""
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
        model_type="qwen3_next", hidden_size=64, head_dim=16,
        num_attention_heads=4, num_key_value_heads=2,
        partial_rotary_factor=0.5, rope_theta=10000, rope_scaling=None,
        full_attention_interval=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, norm_topk_prob=True,
        num_hidden_layers=4, rms_norm_eps=1e-6, vocab_size=128,
        max_position_embeddings=256, compute_dtype="float32",
        param_dtype="float32")
    cfg.update(over)
    return cfg


def _freeze(over):
    return tuple(sorted(over.items()))


@functools.lru_cache(maxsize=None)
def _built_from(over):
    cfg = tiny_cfg(**dict(over))
    model = family.model(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    variables = {"params": weights.make_like(like["params"], SEED)}
    return cfg, model, variables


def _built(**over):
    return _built_from(_freeze(over))


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
    assert model.config == qwen3_next_tiny_config(rope_theta=10000.0)
    assert model.config.layer_types == (LINEAR,) * 3 + (FULL,)
    published = Qwen3NextConfig()
    assert published.layer_types == ((LINEAR,) * 3 + (FULL,)) * 12
    assert published.conv_dim == 8192 and published.value_dim == 4096
    assert published.routed_expert_bytes == 3 * 2048 * 512 * 2
    states = published.layer_states
    assert states[3] is None and [t.name for t in states[0]] == [
        "delta_state", "conv_state"]
    assert states[0][0].shape == (32, 128, 128)
    assert states[0][1].shape == (3, 8192)
    with pytest.raises(ValueError, match="must divide"):
        Qwen3NextConfig(linear_num_key_heads=3)


def test_the_published_layers_count_the_issues_parameters():
    """ISSUE 37's arithmetic, from the program's own parameter shapes: a
    linear layer's mixer 33,718,464, a full layer's 27,263,488, a layer
    with 128 experts held 440,572,096 and 434,117,120."""
    cfg = Qwen3NextConfig(num_layers=4, vocab_size=37984, experts_held=128)
    like = jax.eval_shape(Qwen3NextModel(cfg).init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))  # noqa: E731
                             for x in jax.tree.leaves(tree))
    assert count(like["layer_0"]["mixer"]) == 33_718_464
    assert count(like["layer_3"]["attn"]) == 27_263_488
    assert count(like["layer_0"]) == 440_572_096
    assert count(like["layer_3"]) == 434_117_120
    assert count(like["embed_tokens"]) + count(like["lm_head"]) \
        == 155_582_464
    assert kv_pool.state_bytes(cfg) == 3 * 2_146_304
    assert kv_pool.page_bytes(cfg, 16) == 32_768        # one full layer


@pytest.mark.parametrize("length", [5, 70, 130])
def test_full_forward_matches_the_references_logits(length):
    """Shorter than a chunk of the rule, a chunk and a bit, two and a
    bit."""
    cfg, model, variables = _built()
    ids = _ids(length)
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None])[0])
    want = _reference_logits(cfg, [ids])[0]
    assert np.abs(got - want).max() < TOL


def test_bfloat16_fails_the_float32_tolerance():
    cfg, _, variables = _built()
    _, model16, _ = _built(compute_dtype="bfloat16")
    ids = _ids(70)
    got = np.asarray(model16.apply(variables, jnp.asarray(ids)[None])[0],
                     np.float32)
    assert np.abs(got - _reference_logits(cfg, [ids])[0]).max() > 50 * TOL


#: a share of the experts for the fault that only a share can show
SHARE = dict(num_experts=8, router_experts=16, first_expert=4)


@pytest.mark.parametrize("variant", [v for v in reference.VARIANTS
                                     if v != "float32"])
def test_each_named_fault_moves_the_references_logits(variant):
    """What the cell's comparison can put in the reference's place is
    another computation (on the chip each has to read over the cell's
    limit: PERF.md section 6)."""
    cfg = tiny_cfg(**(SHARE if variant == "renorm_held" else {}))
    make = functools.partial(weights.make_weights, seed=SEED)
    seqs = [_ids(70, seed=3)]
    pos = [24 + np.arange(46)]
    plain = reference.logits_at(make, cfg, seqs, pos)[0]
    moved = reference.logits_at(make, cfg, seqs, pos, variant)[0]
    assert float(jnp.abs(plain - moved).max()) > 50 * TOL
    judged = family.judge(cfg, SEED, [(seqs[0][:25], seqs[0][25:])], variant)
    assert judged["tokens"] == 45 and 0 <= judged["gap"] <= judged["widest"]
    with pytest.raises(ValueError, match="unknown variant"):
        family.judge(cfg, SEED, [(seqs[0][:25], seqs[0][25:])], "float16")


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
    # padding holds must not reach the state
    ids[0, n:] = 77
    return engine._admit_fn(bucket)(
        cache, engine.variables, jnp.asarray(ids), jnp.int32(n),
        jnp.int32(slot), jnp.int32(-(-len(seq) // ps)),
        jax.random.PRNGKey(0), jnp.int32(0))


def _paged_logits(engine, sequences, prompt_lens):
    """Teacher-forced logits of ``sequences`` through the engine's own
    admit programs (one slot each) and then the model's paged step over the
    engine's pool, a token a step: ``[(position, logits [V])]`` a sequence."""
    model, variables, cache = engine.model, engine.variables, engine.cache
    first = []
    for slot, (seq, n) in enumerate(zip(sequences, prompt_lens)):
        cache, tok0 = _admit(engine, cache, slot, seq, n)
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
        keep = np.zeros((engine.num_slots,), bool)
        keep[live] = True
        cache = dict(cache, len=jnp.where(keep, cache["len"], before))
        for i in live:
            out[i].append((prompt_lens[i] + j, np.asarray(logits[i, 0])))
    engine.cache = cache
    return first, out


def test_admission_then_paged_decode_matches_the_references_logits():
    """Prompts of 37, 3 and 65 tokens at a page of 8 (buckets 40, 8 and
    72: none is on the grid; the last is a chunk of the rule and one token),
    then 21, 4 and 12 decoded tokens, in one engine; logits and not
    tokens."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    seqs, prompts = [_ids(58, 1), _ids(7, 2), _ids(77, 4)], [37, 3, 65]
    want = _reference_logits(cfg, seqs)
    first, got = _paged_logits(engine, seqs, prompts)
    for seq, n, ref, tok0, steps in zip(seqs, prompts, want, first, got):
        assert tok0 == int(ref[n - 1].argmax())
        assert [p for p, _ in steps] == list(range(n, len(seq)))
        for p, logits in steps:
            assert np.abs(logits - ref[p]).max() < TOL, (len(seq), p)


def test_the_state_zeroed_at_the_hand_over_fails_the_logits():
    """The fault the cell's MEAN gap may not see (PERF.md section 7): the
    admission's state lost before the first decode step. The logits do."""
    cfg, model, variables = _built()
    engine = _engine(model, variables)
    seq, n = _ids(58, 1), 37
    want = _reference_logits(cfg, [seq])[0]
    cache, _ = _admit(engine, engine.cache, 0, seq, n)
    cache = dict(cache, layers=[
        {k: jnp.zeros_like(x) if k == "delta_state" else x
         for k, x in lc.items()} for lc in cache["layers"]])
    tok = np.zeros((3, 1), np.int32)
    tok[0, 0] = seq[n]
    logits, _ = model.apply(variables, jnp.asarray(tok), cache=cache)
    assert np.abs(np.asarray(logits[0, 0]) - want[n]).max() > 50 * TOL


@pytest.mark.parametrize("n", [1, 2, 3, 37])
def test_the_padded_tail_does_not_reach_the_state(n):
    """An admission at its page bucket leaves in the slot's row exactly the
    state and the convolution's window that the ``n`` true tokens alone
    leave (prompts shorter than the window too)."""
    _, model, variables = _built()
    engine = _engine(model, variables)
    seq = _ids(n + 4, 5)
    cache, _ = _admit(engine, engine.cache, 1, seq, n)
    from apex_tpu.models.generation import init_cache

    contig = init_cache(model.config, 1, 64)
    _, contig = model.apply(variables, jnp.asarray(seq[:n])[None],
                            cache=contig)
    for i in range(3):
        for name in ("delta_state", "conv_state"):
            got = np.asarray(cache["layers"][i][name][1])
            want = np.asarray(contig["layers"][i][name][0])
            assert np.abs(got - want).max() < 1e-6, (i, name)
            # the other slots' rows are as they were: zeros
            assert not np.asarray(cache["layers"][i][name][0]).any()


def test_engine_run_serves_what_the_reference_puts_first():
    """The whole path (frontend, pump, the decode chunk's scan, slots
    re-used by later requests, whose rows hold what the last request left
    until the admission overwrites them): every served token is the
    float32 reference's first choice, and the counters know the state."""
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
    per_slot = 3 * (4 * 16 * 8 * 4 + 3 * 96 * 4)
    assert kv_pool.state_bytes(model.config) == per_slot
    assert stats["kv_groups"] == [
        {"layers": [0, 1, 2], "window": None, "ring_pages_per_slot": None,
         "pages_held": 0, "state": ["delta_state", "conv_state"],
         "state_bytes_per_slot": per_slot},
        {"layers": [3], "window": None, "ring_pages_per_slot": None,
         "pages_held": 59}]
    # every decoding slot-step reads and writes its state whole
    assert counted["state_bytes_moved"] == \
        2 * per_slot * counted["busy_slot_steps"]
    page = kv_pool.page_bytes(model.config, 8)
    assert page == 2 * 2 * 16 * 8 * 4                   # the one full layer
    assert counted["kv_bytes_held_steps"] > \
        per_slot * counted["busy_slot_steps"]
    assert (counted["kv_bytes_held_steps"]
            - per_slot * counted["busy_slot_steps"]) % page == 0
    assert counted["expert_pairs_elsewhere"] == 0       # all 16 are held
    assert counted["expert_pairs_routed"] == \
        4 * 3 * 4 * counted["decode_steps"]     # layers x slots x top 4


def test_a_slot_admitted_again_carries_nothing_over():
    """Retirement does not clear a state group's rows; the next admission
    overwrites them whole. The same request served first on a fresh engine
    and then again after two others have used every slot: the same tokens,
    and the same rows after its admission."""
    _, model, variables = _built()
    engine = _engine(model, variables, num_slots=1)
    target = Request(prompt=_ids(21, 31), max_new_tokens=10)
    alone, _ = engine.run([target])
    after, _ = engine.run([Request(prompt=_ids(45, 32), max_new_tokens=17),
                           Request(prompt=_ids(9, 33), max_new_tokens=5),
                           target])
    np.testing.assert_array_equal(alone[0], after[2])
    dirty = engine.cache
    assert any(np.asarray(lc["delta_state"]).any()
               for lc in dirty["layers"][:3])
    fresh = _engine(model, variables, num_slots=1)
    seq = np.asarray(target.prompt)
    a, _ = _admit(engine, dirty, 0, seq, 21)
    b, _ = _admit(fresh, fresh.cache, 0, seq, 21)
    for i in range(3):
        for name in ("delta_state", "conv_state"):
            np.testing.assert_array_equal(np.asarray(a["layers"][i][name]),
                                          np.asarray(b["layers"][i][name]))


def test_preemption_and_resume_reproduce_the_uninterrupted_run():
    """Preemption resumes by re-prefilling the folded prompt, which rebuilds
    the state; with the prefix cache refused that is the whole prompt. Both
    slots busy with low-priority work, a priority-5 arrival: it preempts,
    every request's tokens are those of the undisturbed engine, and every
    served token is the float32 reference's first choice."""
    cfg, model, variables = _built()
    low = [Request(prompt=_ids(21 + i, 40 + i), max_new_tokens=16,
                   priority=0) for i in range(2)]
    hi = Request(prompt=_ids(13, 44), max_new_tokens=8, priority=5)
    engine = _engine(model, variables, num_slots=2)
    fe = ServingFrontend(
        engine, policy=PriorityDeadlinePolicy(preempt_on_priority=True))
    handles = [fe.submit(r, request_id=i) for i, r in enumerate(low)]
    while fe.queue_depth:
        fe.pump()
    for _ in range(3):
        fe.pump()
    handles.append(fe.submit(hi, request_id=2))
    fe.drain()
    stats = fe.stats()
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    assert stats["prefill_tokens_skipped"] == 0
    outs = [np.asarray(h.result(timeout=0), np.int32) for h in handles]
    plain, _ = _engine(model, variables, num_slots=3).run(low + [hi])
    for got, want in zip(outs, plain):
        np.testing.assert_array_equal(got, want)
    judged = family.judge(cfg, SEED, [(r.prompt, o)
                                      for r, o in zip(low + [hi], outs)])
    assert judged["tokens"] == 40 and judged["widest"] < TOL


# -- the pool's state group -------------------------------------------------------

def test_layer_groups_state_what_each_kind_of_layer_holds():
    cfg = qwen3_next_tiny_config()
    layout = kv_pool.layout_of(cfg)
    state = cfg.layer_states[0]
    assert kv_pool.layer_groups(cfg) == (
        kv_pool.LayerGroup(None, None, (0, 1, 2), False, state),
        kv_pool.LayerGroup(layout, None, (3,), False))
    assert kv_pool.state_layers(cfg) == {0: state, 1: state, 2: state}
    published = kv_pool.layer_groups(Qwen3NextConfig())
    assert [(len(g.layers), bool(g.state), g.ring) for g in published] == [
        (36, True, False), (12, False, False)]
    # 2 MiB of float32 state and 48 KiB of bfloat16 window a layer and slot
    assert kv_pool.state_bytes(Qwen3NextConfig(num_layers=8), 64) \
        == 64 * 6 * 2_146_304
    # a page counts the layers that hold pages, and those alone
    assert kv_pool.page_bytes(Qwen3NextConfig(num_layers=8), 16) == 65_536
    with pytest.raises(ValueError, match="layer_states has 3 entries"):
        kv_pool.layer_groups(dataclasses.replace(
            _Stated(cfg.layer_states[:3]), num_layers=4))


@dataclasses.dataclass(frozen=True)
class _Stated:
    """The least a config states for ``layer_groups``."""

    layer_states: tuple
    num_layers: int = 4
    num_heads: int = 4
    head_dim: int = 16


def test_the_state_group_is_no_part_of_the_page_bookkeeping():
    """Rows sized by the slots alone; allocation, release and defrag move
    pages of the block table's group and leave every state row where and as
    it was; ``observe_pool`` reports the group's bytes."""
    cfg = qwen3_next_tiny_config()
    cache = kv_pool.init_paged_cache(cfg, 3, num_pages=11, page_size=8,
                                     max_pages_per_seq=5)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "layers": [{"delta_state": (3, 4, 16, 8),
                    "conv_state": (3, 3, 96)}] * 3
        + [{"k_pages": (11, 2, 8, 16), "v_pages": (11, 2, 8, 16)}],
        "block_tables": (3, 5), "len": (3,), "alloc_pages": (3,),
        "shared_pages": (3,), "page_ref": (11,), "free_stack": (11,),
        "free_top": ()}
    assert cache["layers"][0]["delta_state"].dtype == jnp.float32
    assert kv_pool.page_size_of(cache) == 8
    assert kv_pool.num_pages_of(cache) == 11
    assert kv_pool.heads_per_row_of(cache, cfg) == 1
    marked = dict(cache, layers=[
        {k: x + 1 + i for k, x in lc.items()} if i < 3 else lc
        for i, lc in enumerate(cache["layers"])])
    moved = kv_pool.alloc_slot(marked, 1, 3)
    moved = kv_pool.alloc_slot(moved, 2, 2)
    moved = kv_pool.free_slot(moved, 1)
    moved = kv_pool.defrag(moved)
    assert int(moved["free_top"]) == 8
    for i in range(3):
        for name, x in moved["layers"][i].items():
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(marked["layers"][i][name]))
    seen = kv_pool.observe_pool(cache, labels={"engine": "qwen3-test"})
    assert seen["kv_pool.state_bytes"] == kv_pool.state_bytes(cfg, 3)
    from apex_tpu.models.gpt import gpt_tiny_config

    plain = kv_pool.init_paged_cache(gpt_tiny_config(), 2, num_pages=5,
                                     page_size=8)
    assert "kv_pool.state_bytes" not in kv_pool.observe_pool(
        plain, labels={"engine": "gpt-test"})


# -- the pools that exist do not change -------------------------------------------

def _one_kind_cases():
    from apex_tpu.models.glm4_moe_lite import glm4_moe_lite_tiny_config
    from apex_tpu.models.gpt import gpt_tiny_config
    from apex_tpu.models.llama import llama_tiny_config
    from apex_tpu.models.mellum import mellum_tiny_config

    ring = (1 + 3 * 2, 2, 8, 16)
    return {
        "GPTConfig": (gpt_tiny_config(), [
            {"k_pages": (11, 4, 8, 16), "v_pages": (11, 4, 8, 16)}] * 2),
        "LlamaConfig": (llama_tiny_config(), [
            {"k_pages": (11, 2, 8, 16), "v_pages": (11, 2, 8, 16)}] * 2),
        "Glm4MoeLiteConfig": (glm4_moe_lite_tiny_config(), [
            {"latent_pages": (11, 1, 8, 128)}] * 3),
        "MellumConfig": (mellum_tiny_config(), [
            {"k_pages": ring, "v_pages": ring}] * 3 + [
            {"k_pages": (11, 2, 8, 16), "v_pages": (11, 2, 8, 16)}]),
    }


@pytest.mark.parametrize("name", ["GPTConfig", "LlamaConfig",
                                  "Glm4MoeLiteConfig", "MellumConfig"])
def test_a_model_without_a_state_keeps_its_cache(name):
    """Keys and shapes of the pytree as PR 36 left them, written out; no
    group has a state, ``state_bytes`` is 0, and a page counts every
    layer."""
    cfg, layers = _one_kind_cases()[name]
    assert not any(g.state for g in kv_pool.layer_groups(cfg))
    assert kv_pool.state_layers(cfg) == {} and kv_pool.state_bytes(cfg) == 0
    cache = kv_pool.init_paged_cache(cfg, 3, num_pages=11, page_size=8,
                                     max_pages_per_seq=5)
    assert jax.tree.map(lambda x: x.shape, cache) == {
        "layers": layers, "block_tables": (3, 5), "len": (3,),
        "alloc_pages": (3,), "shared_pages": (3,), "page_ref": (11,),
        "free_stack": (11,), "free_top": ()}
    assert kv_pool.page_bytes(cfg, 8) == \
        kv_pool.page_bytes(cfg, 8, layers=cfg.num_layers)
    from apex_tpu.models.generation import init_cache

    contig = init_cache(cfg, 1, 16)
    assert [sorted(lc) for lc in contig["layers"]] == [
        sorted(k[:-len("_pages")] for k in lc) for lc in layers]


# -- a share of the experts -------------------------------------------------------

def _moe(**kw):
    return DroplessMoEMLP(hidden_size=64, ffn_hidden_size=32, num_experts=16,
                          k=4, shared_experts=1, router="softmax",
                          shared_gate=True, **kw)


def _moe_params():
    table = {f"layer_0/{k}": v for k, v in {
        "moe/router/weight": ((16, 64), jnp.float32),
        "moe/experts/gate_proj": ((16, 64, 32), jnp.float32),
        "moe/experts/up_proj": ((16, 64, 32), jnp.float32),
        "moe/experts/down_proj": ((16, 32, 64), jnp.float32),
        "moe/shared/gate_proj/weight": ((32, 64), jnp.float32),
        "moe/shared/up_proj/weight": ((32, 64), jnp.float32),
        "moe/shared/down_proj/weight": ((64, 32), jnp.float32),
        "moe/shared_gate/weight": ((1, 64), jnp.float32)}.items()}
    flat = {k[len("layer_0/moe/"):]: 5.0 * v for k, v in
            weights.make_weights(table, SEED).items()}
    tree = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return flat, tree


def test_the_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: what the four chips of a layer
    compute (experts 0-3, 4-7, 8-11, 12-15, each the routed part alone)
    plus the gated shared expert counted ONCE is the uncut layer, and the
    uncut reference; a share's grouped products get no row for an absent
    expert."""
    flat, tree = _moe_params()
    x = jnp.asarray(np.random.default_rng(11).normal(size=(2, 19, 64)),
                    jnp.float32)
    whole = _moe().apply({"params": tree}, x)
    cfg = dict(num_experts=16, num_experts_per_tok=4, norm_topk_prob=True)
    want = reference.expert_layer(
        x.reshape(-1, 64), {"moe/" + k: v for k, v in flat.items()}, cfg,
        jnp.matmul, "float32").reshape(x.shape)
    assert float(jnp.abs(whole - want).max()) < 1e-5
    shared_only = dict(tree, experts=jax.tree.map(jnp.zeros_like,
                                                  tree["experts"]))
    shared = _moe().apply({"params": shared_only}, x)
    parts, pairs = [], []
    for first in (0, 4, 8, 12):
        share = dict(tree, experts={k: v[first:first + 4]
                                    for k, v in tree["experts"].items()})
        y, sown = _moe(held=4, first=first).apply(
            {"params": share}, x, mutable=[ROUTING_COLLECTION])
        parts.append(y - shared)
        stats = dict(zip(SHARE_ROUTING_STATS, np.asarray(
            jax.tree.leaves(sown)[0]).tolist()))
        assert stats["expert_pairs_routed"] \
            + stats["expert_pairs_elsewhere"] == 2 * 19 * 4
        assert 0 < stats["experts_hit"] <= 4
        pairs.append(stats["expert_pairs_routed"])
    assert sum(pairs) == 2 * 19 * 4          # every pair is held somewhere
    assert float(jnp.abs(sum(parts) + shared - whole).max()) < 1e-5
    # the products' groups count the held pairs and nothing else
    idx = jnp.asarray([[0, 5, 9, 15], [4, 5, 6, 7], [1, 2, 3, 12]])
    w = jnp.full((3, 4), 0.25)
    e = tree["experts"]
    y, sizes = grouped_experts(
        x[0, :3], idx, w, e["gate_proj"][4:8], e["up_proj"][4:8],
        e["down_proj"][4:8], first=4)
    assert sizes.tolist() == [1, 2, 1, 1] and int(sizes.sum()) == 5
    assert not np.asarray(y[2]).any()        # a token with no expert here


def test_the_weights_are_renormalised_over_the_ten_never_over_the_held():
    """A share's routed part is the uncut layer's routed part restricted to
    its experts: were the weights renormalised over the held experts, a
    token with one held expert would give it weight 1."""
    flat, tree = _moe_params()
    x = jnp.asarray(np.random.default_rng(12).normal(size=(23, 64)),
                    jnp.float32)
    cfg = dict(num_experts=8, router_experts=16, first_expert=4,
               num_experts_per_tok=4, norm_topk_prob=True)
    p = {"moe/router/weight": flat["router/weight"]}
    held = np.asarray(reference.route(x, p, cfg, jnp.matmul, "float32"))
    full = np.asarray(reference.route(
        x, p, dict(cfg, num_experts=16, first_expert=0, router_experts=16),
        jnp.matmul, "float32"))
    np.testing.assert_array_equal(held, full[:, 4:12])
    assert (held.sum(-1) < 0.999).any()
    wrong = np.asarray(reference.route(x, p, cfg, jnp.matmul, "renorm_held"))
    some = held.sum(-1) > 0
    np.testing.assert_allclose(wrong[some].sum(-1), 1.0, rtol=1e-6)


def test_a_share_of_the_model_against_the_reference():
    """The tiny model holding experts 4-11 of 16, full forward: the absent
    experts are left out in program and reference alike."""
    cfg, model, variables = _built(**SHARE)
    assert model.config.experts_held == 8 and model.config.first_expert == 4
    assert model.config.num_experts == 16
    ids = _ids(70, 6)
    got = np.asarray(model.apply(variables, jnp.asarray(ids)[None])[0])
    want = _reference_logits(cfg, [ids])[0]
    assert np.abs(got - want).max() < TOL
    whole = _reference_logits(tiny_cfg(), [ids])[0]
    assert np.abs(whole - want).max() > 50 * TOL
    with pytest.raises(ValueError, match="are not among"):
        DroplessMoEMLP(hidden_size=8, ffn_hidden_size=8, num_experts=16,
                       k=2, held=8, first=9).init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8)))


# -- what the engine refuses, by name ---------------------------------------------

def _refusals():
    _, model, variables = _built()
    layers = r"\(layers \[0, 1, 2\] keep delta_state, conv_state a slot\)"
    return {
        "prefix_cache": (dict(prefix_cache=True),
                         "state-group-unsupported: prefix_cache does not "
                         "compose .* the state at a prefix's end is not "
                         "kept " + layers),
        "speculation": (dict(draft_model=model, draft_variables=variables,
                             draft_len=2),
                        r"state-group-unsupported: speculative decode "
                        r"\(draft_len\) .* cannot be rolled back out of a "
                        r"state " + layers),
        "chunked_prefill": (dict(prefill_chunk=8),
                            "state-group-unsupported: prefill_chunk .* "
                            "from chunk to chunk through the paged s > 1 "
                            "path " + layers),
        "host_tier": (dict(host_tier_bytes=1 << 20),
                      "state-group-unsupported: host_tier_bytes .* "
                      + layers),
        "quantized_pages": (dict(kv_dtype="int8"),
                            "state-group-unsupported: kv_dtype='int8'"),
    }


@pytest.mark.parametrize("what", ["prefix_cache", "speculation",
                                  "chunked_prefill", "host_tier",
                                  "quantized_pages"])
def test_the_engine_refuses_by_group_and_by_name(what):
    _, model, variables = _built()
    kw, message = _refusals()[what]
    with pytest.raises(kv_pool.StateGroupUnsupported, match=message):
        _engine(model, variables, **kw)


def test_a_stateful_draft_model_is_refused_too():
    from apex_tpu.models.gpt import GPTModel, gpt_tiny_config

    _, draft, draft_variables = _built()
    target = GPTModel(gpt_tiny_config(vocab_size=128))
    with pytest.raises(kv_pool.StateGroupUnsupported,
                       match="a draft model for speculative decode"):
        PagedDecodeEngine(target, None, num_slots=2, page_size=8,
                          draft_model=draft, draft_variables=draft_variables,
                          draft_len=2)


def test_a_tensor_parallel_pool_refuses_a_state_group():
    from apex_tpu.serving.tp import tp_mesh

    cfg = qwen3_next_tiny_config(tensor_parallel_size=2)
    with pytest.raises(kv_pool.StateGroupUnsupported,
                       match="a tensor-parallel mesh"):
        kv_pool.init_paged_cache(cfg, 2, num_pages=9, page_size=8,
                                 mesh=tp_mesh(2))
    with pytest.raises(kv_pool.StateGroupUnsupported,
                       match="a tensor-parallel mesh"):
        kv_pool.cache_specs(qwen3_next_tiny_config())
