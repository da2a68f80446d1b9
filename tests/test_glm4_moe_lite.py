"""The ``glm4_moe_lite`` decoder on the CPU at a small size with seeded
weights: the full forward pass, prefill and paged decode through the latent
cache, the latent decode kernel, the dropless routed layer, the cache-layout
seam and the routing counters, each against the plain reference
(``benchmark/references/glm4_moe_lite.py``) or a hand count."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import init_cache
from apex_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                           Glm4MoeLiteModel,
                                           glm4_moe_lite_tiny_config)
from apex_tpu.ops import (paged_latent_attention,
                          paged_latent_attention_reference)
from apex_tpu.serving import PagedDecodeEngine, Request, kv_pool
from apex_tpu.serving.frontend import ServingFrontend
from apex_tpu.transformer.moe import (ROUTING_COLLECTION, ROUTING_STATS,
                                      DroplessMoEMLP)
from benchmark.harness import weights
from benchmark.references import glm4_moe_lite as reference

SEED = 20261001


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def reference_config(cfg: Glm4MoeLiteConfig) -> dict:
    """The program's tiny config under the configuration file's keys."""
    return dict(
        hidden_size=cfg.hidden_size, intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size,
        num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        n_routed_experts=cfg.n_routed_experts,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense_replace,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        rms_norm_eps=cfg.rms_eps, rope_theta=cfg.rope_theta,
        vocab_size=cfg.vocab_size)


def make_reference_weights(table, seed=SEED):
    return weights.make_weights(
        {k: (shape, jnp.float32) for k, (shape, _) in table.items()}, seed)


@pytest.fixture(scope="module")
def tiny():
    cfg = glm4_moe_lite_tiny_config()
    model = Glm4MoeLiteModel(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    return cfg, model, {"params": weights.make_like(like["params"], SEED)}


def reference_logits(cfg, ids):
    return np.asarray(reference.logits_at(
        make_reference_weights, reference_config(cfg), [ids],
        [np.arange(len(ids))])[0])


def test_param_tree_is_the_references_table(tiny):
    cfg, _, variables = tiny
    mine = weights.table_of(variables["params"])
    table = reference.param_table(reference_config(cfg))
    assert set(mine) == set(table)
    assert all(tuple(mine[k][0]) == tuple(table[k][0]) for k in table)


def test_full_forward_matches_the_reference(tiny):
    cfg, model, variables = tiny
    ids = np.random.default_rng(0).integers(4, cfg.vocab_size, (2, 40))
    logits = model.apply(variables, jnp.asarray(ids, jnp.int32))
    for row in range(2):
        np.testing.assert_allclose(np.asarray(logits[row]),
                                   reference_logits(cfg, ids[row]),
                                   atol=2e-5)


def test_a_long_prefill_rides_512_blocks_to_the_same_logits():
    """Chunks that are a multiple of 512 take 512 x 512 flash tiles (the
    cell's prompt buckets all are); the logits are the reference's."""
    cfg = glm4_moe_lite_tiny_config(max_position_embeddings=1024,
                                    num_layers=2)
    model = Glm4MoeLiteModel(cfg)
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jax.ShapeDtypeStruct((1, 8), jnp.int32))
    variables = {"params": weights.make_like(like["params"], SEED)}
    ids = np.random.default_rng(9).integers(4, cfg.vocab_size, 1024)
    logits = model.apply(variables, jnp.asarray(ids[None], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]),
                               reference_logits(cfg, ids), atol=2e-5)


def test_logits_positions_runs_the_head_at_those_positions(tiny):
    cfg, model, variables = tiny
    ids = jnp.asarray(np.random.default_rng(1).integers(
        4, cfg.vocab_size, (1, 24)), jnp.int32)
    cache = init_cache(cfg, 1, 32)
    whole, _ = model.apply(variables, ids, cache=cache)
    some, _ = model.apply(variables, ids, cache=cache,
                          logits_positions=jnp.asarray([[5, 23]]))
    np.testing.assert_allclose(np.asarray(some[0]),
                               np.asarray(whole[0, [5, 23]]), atol=1e-6)


@pytest.mark.parametrize("prompt_len,new", [(13, 12), (16, 9)])
def test_prefill_then_paged_decode_matches_the_reference(tiny, prompt_len,
                                                         new):
    """Teacher-forced: the prompt through the contiguous prefill and the
    page scatter, then every further token through the paged latent cache
    (the absorbed form), against the reference's full forward pass at every
    position; page size 8, so the decode crosses page boundaries."""
    cfg, model, variables = tiny
    ps = 8
    ids = np.random.default_rng(prompt_len).integers(
        4, cfg.vocab_size, prompt_len + new).astype(np.int32)
    want = reference_logits(cfg, ids)
    contig = init_cache(cfg, 1, 16)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :prompt_len] = ids[:prompt_len]
    logits, contig = model.apply(variables, jnp.asarray(padded), cache=contig)
    np.testing.assert_allclose(np.asarray(logits[0, :prompt_len]),
                               want[:prompt_len], atol=2e-5)
    cache = kv_pool.init_paged_cache(cfg, 2, num_pages=12, page_size=ps)
    assert set(cache["layers"][0]) == {"latent_pages"}
    cache = kv_pool.alloc_slot(cache, 1, 4)
    cache = kv_pool.prefill_into_pages(cache, 1, contig["layers"],
                                       prompt_len)
    for t in range(prompt_len, prompt_len + new):
        tok = jnp.asarray([[0], [ids[t]]], jnp.int32)
        logits, cache = model.apply(variables, tok, cache=cache)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), want[t],
                                   atol=2e-5)
    assert int(cache["len"][1]) == prompt_len + new


def test_engine_with_a_prefix_cache_hit_serves_the_references_tokens(tiny):
    """The prefix cache keys on pages and works unchanged over a latent
    pool: a second wave admitted over cached pages (the tail attends the
    gathered latent entries in the expanded form) decodes the tokens the
    reference puts first, and the pool comes back whole."""
    cfg, model, variables = tiny
    rng = np.random.default_rng(3)
    head = rng.integers(4, cfg.vocab_size, 16).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(
        4, cfg.vocab_size, n).astype(np.int32)]) for n in (5, 13, 3, 9)]
    engine = PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                               num_pages=40, sync_every=2, prefix_cache=True)
    outs, stats = engine.run([Request(prompt=p, max_new_tokens=10)
                              for p in prompts])
    assert stats["prefix_hits"] >= 2
    judged = reference.mean_gap(
        make_reference_weights,
        [(p, np.asarray(o, np.int32)) for p, o in zip(prompts, outs)],
        reference_config(cfg))
    assert judged["tokens"] == 40 and judged["widest"] <= 1e-5
    assert int(np.asarray(engine.cache["page_ref"]).sum()) == 0
    assert int(engine.cache["free_top"]) == 39 - len(engine.prefix)


@pytest.mark.parametrize("s_q", [1, 3])
def test_latent_kernel_matches_its_twin_and_expanded_attention(s_q):
    rng = np.random.default_rng(7)
    heads, nope, rope, vd, rank, stored, ps = 4, 24, 8, 16, 128, 256, 8
    slots, max_pages = 3, 5
    lengths = np.asarray([19, 0, 33], np.int32)
    tables = np.zeros((slots, max_pages), np.int32)
    tables[0, :3] = [4, 9, 2]
    tables[2, :5] = [7, 1, 8, 3, 6]
    entries = rng.normal(size=(12, 1, ps, stored)).astype(np.float32)
    entries[..., rank + rope:] = 0.0
    w_ukv = rng.normal(size=(heads, nope + vd, rank)).astype(np.float32) / 8
    q_nope = rng.normal(size=(slots, heads, s_q, nope)).astype(np.float32)
    q_rope = rng.normal(size=(slots, heads, s_q, rope)).astype(np.float32)
    scale = 1.0 / np.sqrt(nope + rope)
    q_abs = np.zeros((slots, heads, s_q, stored), np.float32)
    q_abs[..., :rank] = np.einsum("bhsn,hnr->bhsr", q_nope, w_ukv[:, :nope])
    q_abs[..., rank:rank + rope] = q_rope
    args = (jnp.asarray(q_abs), jnp.asarray(entries), jnp.asarray(tables),
            jnp.asarray(lengths))
    got = np.asarray(paged_latent_attention(*args, value_width=rank,
                                            scale=scale))
    twin = np.asarray(paged_latent_attention_reference(
        *args, value_width=rank, scale=scale))
    np.testing.assert_allclose(got, twin, atol=2e-5)
    assert not got[1].any()             # the idle slot outputs exactly 0
    # expanded attention, per slot, from the same entries
    for b in (0, 2):
        n = lengths[b]
        ent = entries[tables[b], 0].reshape(-1, stored)[:n]
        kv = np.einsum("tr,hdr->htd", ent[:, :rank], w_ukv)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        s = (np.einsum("hsn,htn->hst", q_nope[b], k_nope)
             + np.einsum("hsr,tr->hst", q_rope[b],
                         ent[:, rank:rank + rope])) * scale
        qpos = n - s_q + np.arange(s_q)
        s = np.where(np.arange(n)[None, None] <= qpos[None, :, None], s,
                     -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = np.einsum("hst,htv->hsv", p, v)
        absorbed = np.einsum("hsr,hvr->hsv", got[b], w_ukv[:, nope:])
        np.testing.assert_allclose(absorbed, want, atol=5e-5)


#: lengths over 40-entry tables of 8-token pages, 16 pages a block: the
#: latent kernel's walk over live blocks (ISSUE 38, ops/_page_walk.py)
_LATENT_WALKS = {
    "ragged_with_idle_slots_first_last_and_between":
        dict(lens=[0, 300, 0, 130, 17, 0]),
    "a_block_edge_and_one_past_it": dict(lens=[128, 129, 256, 257]),
    "every_slot_full": dict(lens=[320, 320, 320]),
    "one_live_slot": dict(lens=[0, 0, 200, 0]),
    "every_slot_idle": dict(lens=[0, 0]),
    "query_block_of_4": dict(lens=[0, 131, 4, 260, 128], s_q=4),
    "query_block_of_a_page": dict(lens=[8, 136, 0, 129, 264], s_q=8),
    "bf16_entries": dict(lens=[300, 0, 129, 16], dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("name", list(_LATENT_WALKS))
def test_latent_kernel_walks_the_live_blocks(name):
    """Against the twin over whole tables: dead entries hold pages of NaN
    (past a slot's end, and all of an idle slot's table), which a walk
    that named one would carry into the result."""
    case = _LATENT_WALKS[name]
    lens, s_q = case["lens"], case.get("s_q", 1)
    dtype = case.get("dtype", jnp.float32)
    rng = np.random.default_rng(11)
    heads, rank, stored, ps, mp = 3, 128, 256, 8, 40
    slots = len(lens)
    held = [-(-n // ps) for n in lens]
    entries = rng.normal(size=(2 + sum(held), 1, ps, stored)).astype(
        np.float32)
    entries[..., rank + 8:] = 0.0
    own = iter(rng.permutation(np.arange(2, len(entries))))
    tables = np.zeros((slots, mp), np.int32)
    for slot, n in enumerate(held):
        tables[slot, :n] = [next(own) for _ in range(n)]
    q = rng.normal(size=(slots, heads, s_q, stored)).astype(np.float32)
    q[..., rank + 8:] = 0.0
    args = (jnp.asarray(q, dtype), jnp.asarray(entries, dtype),
            jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
    want = np.asarray(paged_latent_attention_reference(
        *args, value_width=rank, scale=0.1), np.float32)
    poisoned = np.where(tables == 0, 1, tables)
    got = np.asarray(jax.jit(functools.partial(
        paged_latent_attention, value_width=rank, scale=0.1))(
        args[0], args[1].at[:2].set(jnp.nan), jnp.asarray(poisoned),
        args[3]), np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    for slot, n in enumerate(lens):
        if n == 0:
            assert not got[slot].any()


def _routed_layer(t=12, d=32, m=24, e=8, k=4, shared=1):
    layer = DroplessMoEMLP(hidden_size=d, ffn_hidden_size=m, num_experts=e,
                           k=k, shared_experts=shared,
                           routed_scaling_factor=1.8)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(t, d)),
                    jnp.float32)
    like = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    params = weights.make_like(like["params"], SEED)
    params = jax.tree.map(lambda a: a * 8.0, params)   # scores off 0.5
    return layer, x, params


def _per_token_loop(x, params, k, bias=None, scaling=1.8):
    """The routed layer as a loop over tokens and their experts."""
    p = jax.tree.map(np.asarray, params)
    x = np.asarray(x)

    def swiglu(row, gate, up, down):
        g = row @ gate
        return ((g / (1 + np.exp(-g))) * (row @ up)) @ down

    scores = 1 / (1 + np.exp(-(x @ p["router"]["weight"].T)))
    b = p["router"]["e_score_correction_bias"] if bias is None else bias
    out, chosen = np.zeros_like(x), []
    for t, row in enumerate(x):
        idx = np.argsort(-(scores[t] + b), kind="stable")[:k]
        w = scores[t, idx] / (scores[t, idx].sum() + 1e-20) * scaling
        chosen.append(set(idx.tolist()))
        for i, wi in zip(idx, w):
            ex = p["experts"]
            out[t] += wi * swiglu(row, ex["gate_proj"][i], ex["up_proj"][i],
                                  ex["down_proj"][i])
        sh = p["shared"]
        out[t] += swiglu(row, sh["gate_proj"]["weight"].T,
                         sh["up_proj"]["weight"].T,
                         sh["down_proj"]["weight"].T)
    return out, chosen


def test_routed_layer_matches_a_per_token_loop_over_experts():
    layer, x, params = _routed_layer()
    y, sown = layer.apply({"params": params}, x,
                          mutable=[ROUTING_COLLECTION])
    want, chosen = _per_token_loop(x, params, 4)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)
    stats = dict(zip(ROUTING_STATS,
                     np.asarray(sown[ROUTING_COLLECTION]["stats"][0])))
    loads = np.bincount([e for c in chosen for e in c], minlength=8)
    assert stats == {"expert_pairs_routed": 48,
                     "experts_hit": int((loads > 0).sum()),
                     "expert_load_max": int(loads.max())}


def test_nothing_is_dropped_when_every_token_chooses_the_same_experts():
    """A selection bias that sends all 12 tokens to experts 1, 3, 4 and 6:
    the fullest expert holds every token (a capacity would have dropped
    most), the bias changes the CHOICE against the unbiased router, and the
    weights of the chosen are their scores without it."""
    layer, x, params = _routed_layer()
    bias = np.zeros(8, np.float32)
    bias[[1, 3, 4, 6]] = 10.0
    forced = jax.tree.map(lambda a: a, params)
    forced["router"]["e_score_correction_bias"] = jnp.asarray(bias)
    y, sown = layer.apply({"params": forced}, x,
                          mutable=[ROUTING_COLLECTION])
    want, chosen = _per_token_loop(x, forced, 4, bias=bias)
    assert all(c == {1, 3, 4, 6} for c in chosen)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-4)
    assert np.asarray(sown[ROUTING_COLLECTION]["stats"][0]).tolist() == \
        [48, 4, 12]
    _, unbiased = _per_token_loop(x, params, 4, bias=np.zeros(8))
    assert any(c != {1, 3, 4, 6} for c in unbiased)
    # the weights carry no trace of the bias: a bias ten times as large
    # picks the same experts and gives the same output
    forced["router"]["e_score_correction_bias"] = jnp.asarray(bias * 10)
    again = layer.apply({"params": forced}, x)
    np.testing.assert_allclose(np.asarray(again), np.asarray(y), atol=1e-6)


def test_seeded_selection_bias_changes_a_tenth_of_the_choices(tiny):
    """At the harness's one scale (N(0, 0.02)) and the published router
    width, leaving ``b`` out must show: it changes the top-4 set of at
    least a tenth of the tokens."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(512, 2048)).astype(np.float32)
    x /= np.sqrt((x * x).mean(-1, keepdims=True))
    w = weights.STD * rng.normal(size=(64, 2048)).astype(np.float32)
    b = weights.STD * rng.normal(size=64).astype(np.float32)
    scores = 1 / (1 + np.exp(-(x @ w.T)))
    with_b = np.sort(np.argsort(-(scores + b), axis=1)[:, :4], axis=1)
    without = np.sort(np.argsort(-scores, axis=1)[:, :4], axis=1)
    assert (with_b != without).any(axis=1).mean() >= 0.1


# -- the cache-layout seam ------------------------------------------------------

def _old_page_bytes(cfg, page_size, itemsize):
    kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
    return 2 * kv * page_size * cfg.head_dim * itemsize * cfg.num_layers


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_per_head_layout_is_todays_bit_for_bit(family):
    if family == "gpt":
        from apex_tpu.models.gpt import gpt_tiny_config as tiny_config
    else:
        from apex_tpu.models.llama import llama_tiny_config as tiny_config
    cfg = tiny_config()
    kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
    layout = kv_pool.layout_of(cfg)
    assert (layout.tensors, layout.heads, layout.width, layout.stored) == \
        (("k", "v"), kv, cfg.head_dim, cfg.head_dim) and not layout.latent
    for ps in (8, 16):
        assert kv_pool.page_bytes(cfg, ps) == _old_page_bytes(cfg, ps, 4)
        assert kv_pool.page_bytes(cfg, ps, dtype=jnp.bfloat16) == \
            _old_page_bytes(cfg, ps, 2)
        assert kv_pool.page_bytes(cfg, ps, kv_dtype="int8") == \
            _old_page_bytes(cfg, ps, 1) + 2 * kv * 4 * cfg.num_layers
    cache = kv_pool.init_paged_cache(cfg, 3, num_pages=7, page_size=8)
    assert len(cache["layers"]) == cfg.num_layers
    for lc in cache["layers"]:
        assert {k: v.shape for k, v in lc.items()} == {
            "k_pages": (7, kv, 8, cfg.head_dim),
            "v_pages": (7, kv, 8, cfg.head_dim)}
    quant = kv_pool.init_paged_cache(cfg, 3, num_pages=7, page_size=8,
                                     kv_dtype="int8")
    assert {k: v.shape for k, v in quant["layers"][0].items()} == {
        "k_pages": (7, kv, 8, cfg.head_dim), "k_scales": (7, kv),
        "v_pages": (7, kv, 8, cfg.head_dim), "v_scales": (7, kv)}
    contig = init_cache(cfg, 2, 24)
    assert {k: v.shape for k, v in contig["layers"][0].items()} == {
        "k": (2, kv, 24, cfg.head_dim), "v": (2, kv, 24, cfg.head_dim)}


def test_latent_layout_one_entry_per_token():
    published = Glm4MoeLiteConfig(num_layers=6)
    assert kv_pool.layout_of(published) == kv_pool.CacheLayout(
        ("latent",), 1, 576, 640)
    assert kv_pool.page_bytes(published, 16) == 6 * 576 * 2 * 16 == 110592
    cfg = glm4_moe_lite_tiny_config()
    assert kv_pool.page_bytes(cfg, 8) == cfg.num_layers * 40 * 4 * 8
    cache = kv_pool.init_paged_cache(cfg, 2, num_pages=5, page_size=8)
    assert {k: v.shape for k, v in cache["layers"][0].items()} == {
        "latent_pages": (5, 1, 8, 128)}
    assert kv_pool.page_size_of(cache) == 8
    assert kv_pool.num_pages_of(cache) == 5
    assert init_cache(cfg, 1, 16)["layers"][0]["latent"].shape == \
        (1, 1, 16, 128)


def test_latent_pool_refuses_tp_and_quantized_pages_by_one_named_error(tiny):
    cfg, model, variables = tiny
    with pytest.raises(kv_pool.LatentPoolUnsupported,
                       match="latent-pool-unsupported.*kv_dtype='int8'"):
        PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                          num_pages=8, kv_dtype="int8")
    from apex_tpu.serving.tp import TensorParallelPagedEngine, tp_mesh

    two = Glm4MoeLiteModel(dataclasses.replace(cfg, tensor_parallel_size=2))
    with pytest.raises(kv_pool.LatentPoolUnsupported,
                       match="latent-pool-unsupported"):
        TensorParallelPagedEngine(two, variables, mesh=tp_mesh(2),
                                  num_slots=2, page_size=8, num_pages=8)
    assert issubclass(kv_pool.LatentPoolUnsupported, ValueError)


# -- counters ---------------------------------------------------------------------

def test_routing_and_latent_byte_counters_against_a_hand_count(tiny):
    """Two slots, two requests of 5 new tokens: tok0 comes from the
    admission, so each request decodes 4 live steps; every step the engine
    ran routed both slots' rows, live or idle."""
    cfg, model, variables = tiny
    engine = PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                               num_pages=16, sync_every=2)
    frontend = ServingFrontend(engine)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(4, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 12)]
    handles = [frontend.submit(Request(prompt=p, max_new_tokens=5))
               for p in prompts]
    frontend.drain()
    assert all(len(h.result(timeout=5)) == 5 for h in handles)
    c = frontend.counter_deltas()
    steps, slots = int(c["decode_steps"]), 2
    expert_layers = cfg.num_layers - cfg.first_k_dense_replace
    k, experts = cfg.num_experts_per_tok, cfg.n_routed_experts
    calls = steps * expert_layers
    assert steps >= 4 and steps % 2 == 0
    assert c["expert_pairs_routed"] == calls * slots * k
    assert calls * k <= c["experts_hit"] <= calls * min(experts, slots * k)
    assert calls * -(-slots * k // experts) <= c["expert_load_max"] \
        <= calls * slots
    assert c["expert_bytes_read"] == \
        c["experts_hit"] * cfg.routed_expert_bytes
    assert cfg.routed_expert_bytes == \
        3 * cfg.hidden_size * cfg.moe_intermediate_size * 4
    # a context token costs one latent entry a layer, by the pool's account
    token_bytes = cfg.num_layers * cfg.kv_latent_width * 4
    (kv,) = frontend._kv_groups                # layers alike: one group
    assert kv.page_bytes / engine.page_size == token_bytes
    attended = sum(sum(len(p) + j + 1 for j in range(4)) for p in prompts)
    assert c["kv_bytes_attended"] == attended * token_bytes
    assert c["kv_bytes_fetched"] >= c["kv_bytes_attended"]


def test_a_model_without_routed_experts_hands_back_no_routing():
    from apex_tpu.models.gpt import GPTModel, gpt_tiny_config

    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))
    engine = PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                               sync_every=2)
    _, stats = engine.run([Request(prompt=np.arange(4, 12, dtype=np.int32),
                                   max_new_tokens=4)])
    out = jax.eval_shape(
        engine._step_fn(), engine.cache, variables,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 2), jnp.uint32),
        jnp.zeros((2,), jnp.int32))
    assert out[-1] == ()
    assert stats["decode_steps"] > 0
