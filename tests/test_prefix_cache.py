"""Shared-prefix KV cache (apex_tpu/serving/prefix_cache.py).

Invariant tier (no model): radix-tree match/insert/evict semantics
(page-granular keys, LRU leaf-only eviction, refcount pinning, duplicate
dedup) and the kv_pool sharing ops (``alloc_slot_shared`` /
``release_slot`` / ``evict_pages`` refcount + free-stack bookkeeping).

Engine tier (tiny GPT / Llama): greedy outputs are TOKEN-IDENTICAL with
``prefix_cache`` on vs off — including partial-match, hit-after-evict,
and post-defrag-remap admissions — while the hit/skip counters prove the
prefill actually shrank. Plus the two safety valves: pool exhaustion
defers admission (free stack intact, request completes after a
retirement), and a free-page leak provokes ``defrag`` at the sync
boundary (stack rebuilt from liveness, radix tree remapped)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.serving import (PagedDecodeEngine, PrefixCache, Request,
                              alloc_slot_shared, free_page_count, free_slot,
                              init_paged_cache, release_slot)
from apex_tpu.utils import metrics

PS = 8


def _lockstep(model, v, req, eos=None):
    ref = np.asarray(generate(model, v, np.asarray(req.prompt)[None],
                              max_new_tokens=req.max_new_tokens,
                              eos_token_id=eos))[0, req.prompt.shape[0]:]
    if eos is not None:
        hit = np.where(ref == eos)[0]
        if hit.size:
            ref = ref[:hit[0] + 1]
    return ref


def _req(rng, prefix, tail_len, max_new):
    tail = rng.integers(0, 100, (tail_len,)).astype(np.int32)
    return Request(prompt=np.concatenate([prefix, tail]).astype(np.int32),
                   max_new_tokens=max_new)


# --- invariant tier ----------------------------------------------------------


def test_radix_match_insert_dedup_evict():
    pc = PrefixCache(page_size=4)
    toks = np.arange(14, dtype=np.int32)          # 3 full pages + 2 tail
    row = np.asarray([11, 12, 13, 14, 0, 0], np.int32)

    # cold: no match; retirement inserts the full-page prefix only
    assert pc.match(toks) == []
    keep = pc.release_and_insert(toks, 14, [], row)
    assert keep.tolist() == [True, True, True, False, False, False]
    assert len(pc) == 3 and sorted(pc.pages()) == [11, 12, 13]

    # match is capped at (len-1)//ps so >= 1 token always prefills
    assert [n.page for n in pc.match(toks)] == [11, 12, 13]
    assert [n.page for n in pc.match(toks[:12])] == [11, 12]  # exact-page cap
    assert [n.page for n in pc.match(toks[:5])] == [11]
    # divergence inside a page: no match for that page
    div = toks.copy()
    div[5] = 99
    assert [n.page for n in pc.match(div)] == [11]

    # duplicate insert (a concurrent twin): existing nodes win, our
    # copies free
    keep2 = pc.release_and_insert(toks, 14, [], np.asarray(
        [21, 22, 23, 24, 0, 0], np.int32))
    assert not keep2.any()
    assert len(pc) == 3

    # refs pin; eviction is LRU and leaf-only
    nodes = pc.match(toks)
    pc.acquire(nodes)
    assert pc.evict(3) == []                      # everything pinned
    pc.release(nodes)
    pc.match(toks[:9])                            # bump page 11's chain
    assert pc.evict(1) == [13]                    # deepest leaf, LRU
    assert pc.evict(5) == [12, 11]                # parent exposed next
    assert len(pc) == 0


def test_kv_pool_shared_ops_refcounts():
    cfg = gpt_tiny_config()
    cache = init_paged_cache(cfg, num_slots=2, num_pages=12, page_size=PS)
    cache = free_slot(cache, 0)                   # no-op on an empty slot
    assert int(free_page_count(cache)) == 11

    # pretend pages [1, 2] are cache-held: share them into slot 0 + 2
    # private pages
    shared_row = jnp.zeros((cache["block_tables"].shape[1],), jnp.int32)
    shared_row = shared_row.at[0].set(1).at[1].set(2)
    cache["free_stack"] = jnp.asarray(
        [3, 4, 5, 6, 7, 8, 9, 10, 11, 0, 0, 0], jnp.int32)
    cache["free_top"] = jnp.asarray(9, jnp.int32)
    cache = alloc_slot_shared(cache, 0, shared_row, 2, 2)
    assert int(free_page_count(cache)) == 7
    assert int(cache["shared_pages"][0]) == 2
    assert int(cache["alloc_pages"][0]) == 2
    assert cache["page_ref"][jnp.asarray([1, 2])].tolist() == [1, 1]
    row = np.asarray(cache["block_tables"][0])
    assert row[:2].tolist() == [1, 2] and (row[2:4] > 2).all()

    # a second reader of the same shared pages
    cache = alloc_slot_shared(cache, 1, shared_row, 2, 1)
    assert cache["page_ref"][jnp.asarray([1, 2])].tolist() == [2, 2]

    # free_slot: owned pages return, shared only drop their refcount
    cache = free_slot(cache, 1)
    assert cache["page_ref"][jnp.asarray([1, 2])].tolist() == [1, 1]
    assert int(free_page_count(cache)) == 7      # 1 owned back, none shared

    # release_slot with a keep mask: entry 2 (first private page)
    # transfers to the cache, entry 3 frees, shared entries decref
    keep = np.zeros((row.shape[0],), bool)
    keep[:3] = True
    cache = release_slot(cache, 0, jnp.asarray(keep))
    assert cache["page_ref"][jnp.asarray([1, 2])].tolist() == [0, 0]
    assert int(free_page_count(cache)) == 8      # only entry 3's page back
    assert int(cache["shared_pages"][0]) == 0
    free = set(np.asarray(
        cache["free_stack"][:int(cache["free_top"])]).tolist())
    assert row[2] not in free                    # kept page stayed out
    assert row[3] in free


# --- engine tier -------------------------------------------------------------


def test_prefix_cache_token_identical_and_skips(rng):
    """The acceptance bar: a shared-system-prompt workload decodes
    token-identically with prefix caching on vs off, skipping the shared
    pages' prefill for every request past the first concurrent wave."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # 4 pages: a power-of-two header, so the admission's match-depth
    # bucketing (compile-count bound) never drops below the full header
    sys_p = rng.integers(0, cfg.vocab_size, (4 * PS,)).astype(np.int32)
    reqs = [_req(rng, sys_p, int(t), int(m))
            for t, m in zip(rng.integers(3, 12, 6), rng.integers(3, 8, 6))]

    e_off = PagedDecodeEngine(model, v, num_slots=2, page_size=PS)
    o_off, s_off = e_off.run(reqs)
    e_on = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                             prefix_cache=True)
    o_on, s_on = e_on.run(reqs)
    for a, b in zip(o_off, o_on):
        np.testing.assert_array_equal(a, b)

    assert not s_off["prefix_cache_enabled"]
    assert s_off["prefill_tokens_skipped"] == 0
    # the first wave (2 slots) prefills cold; everyone after shares the
    # 4 system-prompt pages at minimum
    assert s_on["prefix_hits"] >= len(reqs) - 2
    assert s_on["prefill_tokens_skipped"] >= (len(reqs) - 2) * 4 * PS
    assert (s_on["prefill_tokens_computed"]
            + s_on["prefill_tokens_skipped"]) == s_on["prefill_tokens_total"]
    # pool bookkeeping after the drain: no active readers, and the free
    # stack + cached pages partition the usable pool
    assert int(e_on.cache["page_ref"].sum()) == 0
    usable = e_on.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(e_on.cache)) == usable - len(e_on.prefix)

    # a warm second run: every request hits
    o2, s2 = e_on.run(reqs)
    for a, b in zip(o_off, o2):
        np.testing.assert_array_equal(a, b)
    assert s2["prefix_hits"] == len(reqs)


def test_prefix_cache_partial_match(rng):
    """A prompt diverging inside the cached prefix shares only the pages
    before the divergence — mid-page divergence drops that whole page
    (copy-on-write at page granularity) — and still decodes identically."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    base = rng.integers(0, cfg.vocab_size, (2 * PS,)).astype(np.int32)

    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                               prefix_cache=True)
    warm = Request(prompt=np.concatenate(
        [base, rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)]),
        max_new_tokens=4)
    engine.run([warm])

    # diverges at token 12 (inside page 1): only page 0 can match
    part = warm.prompt.copy()
    part[PS + 4] = (part[PS + 4] + 1) % cfg.vocab_size
    partial = Request(prompt=part, max_new_tokens=4)
    # diverges at token 2 (inside page 0): no match at all
    miss = warm.prompt.copy()
    miss[2] = (miss[2] + 1) % cfg.vocab_size
    miss_req = Request(prompt=miss, max_new_tokens=4)

    outs, stats = engine.run([partial, miss_req])
    np.testing.assert_array_equal(outs[0], _lockstep(model, v, partial))
    np.testing.assert_array_equal(outs[1], _lockstep(model, v, miss_req))
    assert stats["prefix_hits"] == 1
    assert stats["prefill_tokens_skipped"] == PS   # page 0 only


def test_prefix_cache_hit_after_evict(rng):
    """Pool pressure evicts LRU refcount-0 cached pages to replenish the
    free stack; a later request re-populates the prefix and hits again —
    token-identical throughout."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sys_p = rng.integers(0, cfg.vocab_size, (2 * PS,)).astype(np.int32)

    # usable pool of 7 pages: request A (3 pages) caches 2-3 pages; the
    # fat request B (6 pages, distinct prefix) must evict to fit
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                               num_pages=8, prefix_cache=True)
    req_a = _req(rng, sys_p, 3, 4)
    (out_a,), _ = engine.run([req_a])
    np.testing.assert_array_equal(out_a, _lockstep(model, v, req_a))
    cached_before = len(engine.prefix)
    assert cached_before >= 2

    fat = Request(prompt=rng.integers(0, cfg.vocab_size,
                                      (5 * PS,)).astype(np.int32),
                  max_new_tokens=PS)
    (out_f,), s_fat = engine.run([fat])
    np.testing.assert_array_equal(out_f, _lockstep(model, v, fat))
    assert s_fat["evicted_pages"] >= 1

    # the shared prefix was (at least partly) evicted: re-run the
    # A-shaped request twice — first re-populates, second hits again
    req_c = _req(rng, sys_p, 4, 4)
    (out_c,), s_c = engine.run([req_c])
    np.testing.assert_array_equal(out_c, _lockstep(model, v, req_c))
    req_d = _req(rng, sys_p, 6, 4)
    (out_d,), s_d = engine.run([req_d])
    np.testing.assert_array_equal(out_d, _lockstep(model, v, req_d))
    assert s_d["prefix_hits"] == 1
    assert s_d["prefill_tokens_skipped"] >= 2 * PS


def test_pool_exhaustion_defers_until_retirement(rng):
    """Admission with insufficient free pages DEFERS the request (free
    stack untouched) and admits it once a retirement returns pages —
    with and without the prefix cache."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (2 * PS,)).astype(np.int32),
                    max_new_tokens=PS) for _ in range(2)]  # 3 pages each

    for prefix_cache in (False, True):
        engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                                   num_pages=6, prefix_cache=prefix_cache)
        outs, stats = engine.run(reqs)           # 5 usable pages: one at
        for req, out in zip(reqs, outs):         # a time
            np.testing.assert_array_equal(out, _lockstep(model, v, req))
        assert stats["deferred_admissions"] >= 1
        assert stats["peak_slots_in_use"] == 1
        assert stats["retired"] == 2
        assert int(engine.cache["page_ref"].sum()) == 0
        cached = len(engine.prefix) if prefix_cache else 0
        assert int(free_page_count(engine.cache)) == 5 - cached


def test_defrag_provoked_by_leak(rng):
    """A free-page leak (free stack shorter than liveness implies) makes
    admission invoke ``defrag`` at the sync boundary: the stack rebuilds
    from actual liveness and the deferred request completes."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                               num_pages=9)
    req1 = Request(prompt=rng.integers(0, cfg.vocab_size,
                                       (PS,)).astype(np.int32),
                   max_new_tokens=4)
    engine.run([req1])
    # simulate a miscounted free: drop 4 pages off the stack top
    engine.cache["free_top"] = engine.cache["free_top"] - 4
    assert int(free_page_count(engine.cache)) == 4
    req2 = Request(prompt=rng.integers(0, cfg.vocab_size,
                                       (4 * PS,)).astype(np.int32),
                   max_new_tokens=PS)              # needs 5 pages
    (out2,), stats = engine.run([req2])
    np.testing.assert_array_equal(out2, _lockstep(model, v, req2))
    assert stats["defrag_runs"] == 1
    assert int(free_page_count(engine.cache)) == 8   # leak collected


def test_defrag_remaps_prefix_cache(rng):
    """defrag while the radix tree holds pages (some pinned by an active
    request): cached pages survive as extra liveness, the tree follows
    the compaction remap, and a post-defrag admission still HITS the
    remapped pages with token-identical output."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    sys_p = rng.integers(0, cfg.vocab_size, (2 * PS,)).astype(np.int32)

    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                               num_pages=20, prefix_cache=True)
    # seed the tree with EXACTLY the 2 system pages (written length 20
    # -> 2 full pages)
    seed = Request(prompt=np.concatenate(
        [sys_p, rng.integers(0, cfg.vocab_size, (1,)).astype(np.int32)]),
        max_new_tokens=4)
    engine.run([seed])
    assert len(engine.prefix) == 2

    # leak 12 pages, then co-admit X (pins the system pages, long decode)
    # and Y (distinct prefix, needs more than the leaked stack holds):
    # eviction finds nothing (tree fully pinned by X) -> defrag recovers
    engine.cache["free_top"] = engine.cache["free_top"] - 12
    req_x = _req(rng, sys_p, 5, 12)
    req_y = Request(prompt=rng.integers(0, cfg.vocab_size,
                                        (21,)).astype(np.int32),
                    max_new_tokens=4)
    outs, stats = engine.run([req_x, req_y])
    np.testing.assert_array_equal(outs[0], _lockstep(model, v, req_x))
    np.testing.assert_array_equal(outs[1], _lockstep(model, v, req_y))
    assert stats["defrag_runs"] >= 1
    assert stats["evicted_pages"] == 0

    # the remapped tree still serves hits, token-identically
    req_z = _req(rng, sys_p, 4, 3)
    (out_z,), s_z = engine.run([req_z])
    np.testing.assert_array_equal(out_z, _lockstep(model, v, req_z))
    assert s_z["prefix_hits"] == 1


def test_llama_paged_and_prefix_cache(rng):
    """generate(paged=True) now covers Llama (GQA + per-slot RoPE
    gather): token-identical to lock-step, with and without the prefix
    cache; sliding-window paged decode raises cleanly."""
    import dataclasses

    from apex_tpu.models.llama import LlamaModel, llama_tiny_config

    cfg = llama_tiny_config()
    model = LlamaModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 12)), jnp.int32)

    ref = np.asarray(generate(model, v, prompt, max_new_tokens=5))
    out = np.asarray(generate(model, v, prompt, max_new_tokens=5,
                              paged=True, page_size=PS))
    np.testing.assert_array_equal(out, ref)

    # shared-prefix engine workload over the Llama paged path
    sys_p = rng.integers(0, cfg.vocab_size, (2 * PS,)).astype(np.int32)
    reqs = [_req(rng, sys_p, int(t), 4) for t in rng.integers(2, 9, 4)]
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                               prefix_cache=True)
    outs, stats = engine.run(reqs)
    for req, out in zip(reqs, outs):
        np.testing.assert_array_equal(out, _lockstep(model, v, req))
    assert stats["prefix_hits"] >= len(reqs) - 2

    # sliding-window Llama is paged now (ISSUE 9): the band rides the
    # paged kernel; the prefix cache is the one combination refused
    # (dropped-below-window pages can't be shared cache property)
    wmodel = LlamaModel(dataclasses.replace(cfg, sliding_window=PS))
    wout = np.asarray(generate(wmodel, v, prompt, max_new_tokens=3,
                               paged=True, page_size=PS))
    wref = np.asarray(generate(wmodel, v, prompt, max_new_tokens=3))
    np.testing.assert_array_equal(wout, wref)
    with pytest.raises(ValueError):
        PagedDecodeEngine(wmodel, v, num_slots=2, page_size=PS,
                          prefix_cache=True)


def test_engine_counters_reach_metrics_registry(rng):
    """The serving-observability satellite: engine counters land in
    utils.metrics under serving.* names."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=PS,
                               prefix_cache=True)
    metrics.clear()
    try:
        _, stats = engine.run([Request(
            prompt=rng.integers(0, cfg.vocab_size, (10,)).astype(np.int32),
            max_new_tokens=3)])
        for name in ("decode_steps", "admitted", "retired",
                     "slot_occupancy", "prefix_hit_rate",
                     "prefill_tokens_skipped", "evicted_pages"):
            assert metrics.get(f"serving.{name}") == [
                float(stats[name])], name
    finally:
        metrics.clear()


def test_prefix_cache_requires_paged(rng):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError):
        generate(model, v, jnp.zeros((1, 8), jnp.int32), max_new_tokens=2,
                 prefix_cache=True)


# --- over a pool held two (or four) heads a 128-lane row --------------------
#
# docs/serving.md "Page-pool layout". The shared-admit program gathers
# page tiles into a per-head contiguous buffer (it unpacks), the tail is
# written through the packed page write, and a resume re-admits over
# pages the preempted segment wrote. The baseline is the same engine over
# the pool as it was held before, one head a row; the test steers that,
# the program has no option for it.

def _packed_model(heads):
    cfg = gpt_tiny_config(hidden_size=128, num_heads=heads, num_layers=1)
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, v


@pytest.mark.parametrize("heads,pack", [(2, 2), (4, 4)])
def test_shared_prefix_admission_over_a_packed_pool(rng, monkeypatch, heads,
                                                    pack):
    from apex_tpu.serving import kv_pool

    cfg, model, v = _packed_model(heads)
    sys_p = rng.integers(0, cfg.vocab_size, (4 * PS,)).astype(np.int32)
    reqs = [_req(rng, sys_p, int(t), int(m))
            for t, m in zip(rng.integers(3, 12, 4), rng.integers(3, 6, 4))]

    e_on = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                             prefix_cache=True)
    assert kv_pool.a_pool(e_on.cache).shape[1:] == (heads // pack, PS, 128)
    o_on, s_on = e_on.run(reqs)
    assert s_on["pool_heads_per_row"] == pack
    assert s_on["prefix_hits"] >= len(reqs) - 2
    assert s_on["prefill_tokens_skipped"] >= (len(reqs) - 2) * 4 * PS

    with monkeypatch.context() as m:
        m.setattr(kv_pool, "heads_per_row", lambda *a, **k: 1)
        e_flat = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                                   prefix_cache=True)
        assert kv_pool.a_pool(e_flat.cache).shape[1:] == (heads, PS,
                                                          128 // pack)
        o_flat, s_flat = e_flat.run(reqs)
    assert s_flat["pool_heads_per_row"] == 1
    for key in ("prefix_hits", "prefill_tokens_skipped", "decode_steps"):
        assert s_on[key] == s_flat[key], key
    for a, b in zip(o_on, o_flat):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(o_on[-1], _lockstep(model, v, reqs[-1]))
    assert int(e_on.cache["page_ref"].sum()) == 0
    usable = e_on.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(e_on.cache)) == usable - len(e_on.prefix)


@pytest.mark.parametrize("heads,pack", [(2, 2), (4, 4)])
def test_preempt_and_resume_over_a_packed_pool(rng, monkeypatch, heads,
                                               pack):
    """A high-priority arrival evicts a low-priority slot; the victim
    resumes over the pages its first segment wrote (a shared admission
    whose prefix the decode steps' page writes filled). Token for token
    the one-head-a-row pool's run, and the counters the decode kernel's
    roofline reads count the same bytes however a row is held."""
    from apex_tpu.serving import (PriorityDeadlinePolicy, ServingFrontend,
                                  kv_pool)

    cfg, model, v = _packed_model(heads)
    low = [Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                       ).astype(np.int32),
                   max_new_tokens=12, priority=0) for _ in range(2)]
    hi = Request(prompt=rng.integers(0, cfg.vocab_size, (24,)
                                     ).astype(np.int32),
                 max_new_tokens=4, priority=5)

    def serve():
        engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS,
                                   prefix_cache=True)
        fe = ServingFrontend(engine, policy=PriorityDeadlinePolicy(
            preempt_on_priority=True))
        handles = [fe.submit(r, request_id=i) for i, r in enumerate(low)]
        while fe.queue_depth:
            fe.pump()
        for _ in range(3):                # give the victims some progress
            fe.pump()
        handles.append(fe.submit(hi, request_id=2))
        fe.drain()
        return fe, [np.asarray(h.result()) for h in handles]

    fe, outs = serve()
    stats = fe.stats()
    assert stats["pool_heads_per_row"] == pack
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    assert stats["prefill_tokens_skipped"] >= PS
    with monkeypatch.context() as m:
        m.setattr(kv_pool, "heads_per_row", lambda *a, **k: 1)
        fe_flat, outs_flat = serve()
    flat = fe_flat.stats()
    assert flat["pool_heads_per_row"] == 1
    for a, b in zip(outs, outs_flat):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(outs[0], _lockstep(model, v, low[0]))
    for key in ("preemptions", "resumes", "prefill_tokens_skipped",
                "decode_steps"):
        assert stats[key] == flat[key], key
    d, d_flat = fe.counter_deltas(), fe_flat.counter_deltas()
    for key in ("kv_bytes_attended", "kv_bytes_fetched"):
        assert d[key] == d_flat[key] > 0, key
    usable = fe.engine.cache["free_stack"].shape[0] - 1
    assert int(free_page_count(fe.engine.cache)) == \
        usable - len(fe.engine.prefix)
    assert int(fe.engine.cache["page_ref"].sum()) == 0


def test_llama_gqa_windowed_over_a_packed_pool(rng, monkeypatch):
    """Two kv heads of 64 in one row, two query heads to each (the packed
    read's GQA rows), a sliding window (the band and the dropped pages):
    token for token the one-head-a-row pool's run."""
    from apex_tpu.models.llama import LlamaModel, llama_tiny_config
    from apex_tpu.serving import kv_pool

    cfg = llama_tiny_config(hidden_size=256, intermediate_size=352,
                            num_heads=4, num_kv_heads=2, num_layers=1,
                            sliding_window=12)
    assert cfg.head_dim == 64
    model = LlamaModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (L,)
                                        ).astype(np.int32),
                    max_new_tokens=m)
            for L, m in zip([5, 19], [18, 6])]
    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=PS)
    assert kv_pool.a_pool(engine.cache).shape[1:] == (1, PS, 128)
    outs, stats = engine.run(reqs)
    assert stats["pool_heads_per_row"] == 2
    assert stats["window_dropped_pages"] > 0
    with monkeypatch.context() as m:
        m.setattr(kv_pool, "heads_per_row", lambda *a, **k: 1)
        flat = PagedDecodeEngine(model, v, num_slots=2, page_size=PS)
        assert kv_pool.a_pool(flat.cache).shape[1:] == (2, PS, 64)
        flat_outs, flat_stats = flat.run(reqs)
    assert flat_stats["window_dropped_pages"] == stats["window_dropped_pages"]
    for a, b in zip(outs, flat_outs):
        np.testing.assert_array_equal(a, b)
