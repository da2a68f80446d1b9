"""Paged KV pool + continuous-batching engine (apex_tpu/serving).

Invariant tier (no model): block-table alloc/free/defrag keep the pool
consistent — disjoint ownership, exact free counts, null page never
handed out, defrag preserves page contents under remapping.

Engine tier (tiny GPT): greedy outputs are token-identical to per-request
lock-step ``generate`` on a mixed-length workload with more requests than
slots; EOS retirement frees slots early; and the whole set completes in
FEWER decode steps than lock-step padding to the longest request (the
acceptance bar for the continuous-batching design)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.serving import (PagedDecodeEngine, Request, alloc_slot, defrag,
                              free_page_count, free_slot, init_paged_cache,
                              pages_for, prefill_into_pages)


def _owned_pages(cache, slot):
    n = int(cache["alloc_pages"][slot])
    return set(np.asarray(cache["block_tables"][slot][:n]).tolist())


def test_alloc_free_invariants():
    cfg = gpt_tiny_config()
    cache = init_paged_cache(cfg, num_slots=3, num_pages=12, page_size=8)
    assert int(free_page_count(cache)) == 11      # page 0 reserved

    cache = alloc_slot(cache, 0, 3)
    cache = alloc_slot(cache, 1, 4)
    cache = alloc_slot(cache, 2, 2)
    assert int(free_page_count(cache)) == 11 - 9
    own = [_owned_pages(cache, s) for s in range(3)]
    assert all(0 not in o for o in own)           # null page never allocated
    assert len(own[0] | own[1] | own[2]) == 9     # disjoint ownership
    # free stack + owned pages partition pages 1..11
    free = set(np.asarray(
        cache["free_stack"][:int(cache["free_top"])]).tolist())
    assert free | own[0] | own[1] | own[2] == set(range(1, 12))

    cache["len"] = cache["len"].at[1].set(13)     # slot 1 wrote 13 tokens
    cache = free_slot(cache, 1)
    assert int(free_page_count(cache)) == 11 - 9 + 4   # ALL owned pages back
    assert int(cache["len"][1]) == 0
    assert int(cache["alloc_pages"][1]) == 0
    assert (np.asarray(cache["block_tables"][1]) == 0).all()
    # freed pages are re-allocatable and still disjoint from survivors
    cache = alloc_slot(cache, 1, 4)
    own = [_owned_pages(cache, s) for s in range(3)]
    assert len(own[0] | own[1] | own[2]) == 9


def test_alloc_free_jittable():
    cfg = gpt_tiny_config()
    cache = init_paged_cache(cfg, num_slots=2, num_pages=8, page_size=8)
    cache = jax.jit(alloc_slot)(cache, jnp.int32(0), jnp.int32(3))
    assert int(free_page_count(cache)) == 4
    cache = jax.jit(free_slot)(cache, jnp.int32(0))
    assert int(free_page_count(cache)) == 7


def test_defrag_preserves_contents_and_collects(rng):
    cfg = gpt_tiny_config()
    cache = init_paged_cache(cfg, num_slots=2, num_pages=16, page_size=8)
    # fill the pool with recognizable per-page values
    shape = cache["layers"][0]["k_pages"].shape
    marks = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    cache["layers"] = [{"k_pages": marks, "v_pages": -marks}
                       for _ in cache["layers"]]
    cache = alloc_slot(cache, 0, 3)
    cache = alloc_slot(cache, 1, 2)      # then free -> fragmentation holes
    cache["len"] = cache["len"].at[0].set(20)
    cache = free_slot(cache, 1)
    cache = alloc_slot(cache, 1, 4)
    cache["len"] = cache["len"].at[1].set(9)

    def gather(cache, slot, layer=0):
        n = int(cache["alloc_pages"][slot])
        bt = np.asarray(cache["block_tables"][slot][:n])
        return np.asarray(cache["layers"][layer]["k_pages"])[bt]

    before = [gather(cache, s) for s in range(2)]
    free_before = int(free_page_count(cache))
    cache = defrag(cache)
    after = [gather(cache, s) for s in range(2)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a, b)       # contents follow the remap
    assert int(free_page_count(cache)) == free_before
    # compaction: live pages (null + 7 owned) occupy the low ids
    own = _owned_pages(cache, 0) | _owned_pages(cache, 1)
    assert own == set(range(1, 8))
    # defrag is jittable (pure index ops)
    cache2 = jax.jit(defrag)(cache)
    np.testing.assert_array_equal(np.asarray(cache2["block_tables"]),
                                  np.asarray(cache["block_tables"]))


def test_prefill_scatter_roundtrip(rng):
    """prefill_into_pages places position p at table entry p//ps, offset
    p%ps — gathering the pages back must reproduce the contiguous K/V."""
    cfg = gpt_tiny_config()
    ps, s0, bucket = 8, 13, 16
    cache = init_paged_cache(cfg, num_slots=1, num_pages=8, page_size=ps)
    cache = alloc_slot(cache, 0, pages_for(s0, ps))
    kv = cache["layers"][0]["k_pages"].shape[1]
    d = cache["layers"][0]["k_pages"].shape[3]
    contig = [{"k": jnp.asarray(rng.standard_normal((1, kv, bucket, d)),
                                jnp.float32),
               "v": jnp.asarray(rng.standard_normal((1, kv, bucket, d)),
                                jnp.float32)}
              for _ in range(cfg.num_layers)]
    cache = prefill_into_pages(cache, 0, contig, jnp.int32(s0))
    assert int(cache["len"][0]) == s0
    bt = np.asarray(cache["block_tables"][0])
    for li in range(cfg.num_layers):
        pages = np.asarray(cache["layers"][li]["k_pages"])
        want = np.asarray(contig[li]["k"][0])     # (kv, bucket, d)
        for p in range(s0):
            np.testing.assert_array_equal(pages[bt[p // ps], :, p % ps, :],
                                          want[:, p, :])


def test_engine_matches_lockstep_mixed_lengths(rng):
    """The acceptance bar: mixed-length prompts, more requests than
    slots — greedy outputs token-identical to per-request lock-step
    generate, AND fewer engine decode steps than lock-step padding."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    init_ids = jnp.zeros((1, 8), jnp.int32)
    v = model.init(jax.random.PRNGKey(0), init_ids)

    lengths = [5, 16, 9, 23, 12]
    max_new = [6, 3, 8, 4, 7]
    reqs = [Request(prompt=np.asarray(
                rng.integers(0, cfg.vocab_size, (L,)), np.int32),
                max_new_tokens=m)
            for L, m in zip(lengths, max_new)]

    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    outs, stats = engine.run(reqs)

    for req, out in zip(reqs, outs):
        ref = np.asarray(generate(model, v, np.asarray(req.prompt)[None],
                                  max_new_tokens=req.max_new_tokens))
        np.testing.assert_array_equal(out, ref[0, req.prompt.shape[0]:])

    # lock-step at the same 2-slot capacity pads every batch to the
    # longest member's budget: 3 batches x max(max_new) worst case; even
    # the best static grouping can't beat per-slot retirement + refill
    lockstep_steps = int(np.ceil(len(reqs) / 2)) * max(max_new)
    assert stats["decode_steps"] < lockstep_steps
    assert stats["peak_slots_in_use"] == 2
    # every page returned after the queue drains
    assert int(free_page_count(engine.cache)) == \
        engine.cache["free_stack"].shape[0] - 1


def test_engine_eos_retirement_and_refill(rng):
    """A request whose first greedy token is EOS retires at admission (0
    decode steps) and its slot/pages immediately serve the next request."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    v = model.init(jax.random.PRNGKey(0), prompt)
    free = np.asarray(generate(model, v, prompt, max_new_tokens=4))
    eos = int(free[0, 8])

    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8,
                               eos_token_id=eos)
    other = np.asarray(rng.integers(0, cfg.vocab_size, (6,)), np.int32)
    outs, stats = engine.run([
        Request(prompt=np.asarray(prompt[0]), max_new_tokens=4),
        Request(prompt=other, max_new_tokens=3),
    ])
    assert outs[0].tolist() == [eos]
    ref = np.asarray(generate(model, v, other[None], max_new_tokens=3,
                              eos_token_id=eos))[0, 6:]
    first = np.where(ref == eos)[0]
    want = ref[:first[0] + 1] if first.size else ref
    np.testing.assert_array_equal(outs[1], want)
    assert int(free_page_count(engine.cache)) == \
        engine.cache["free_stack"].shape[0] - 1


@pytest.mark.slow
def test_generate_paged_rectangular_matches_generate(rng):
    """generate(paged=True) on a rectangular batch returns the exact
    lock-step array (prompt + tokens, EOS padding semantics)."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 16)), jnp.int32)
    v = model.init(jax.random.PRNGKey(0), prompt)

    ref = np.asarray(generate(model, v, prompt, max_new_tokens=6))
    out = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                              paged=True, page_size=8))
    np.testing.assert_array_equal(out, ref)

    # and with EOS: lock-step pads EOS rows; the engine retires them —
    # same output array either way
    eos = int(ref[0, 17])
    ref_e = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                                eos_token_id=eos))
    out_e = np.asarray(generate(model, v, prompt, max_new_tokens=6,
                                eos_token_id=eos, paged=True, page_size=8))
    np.testing.assert_array_equal(out_e, ref_e)


@pytest.mark.slow
def test_engine_sync_every_and_sampling_invariance(rng):
    """sync_every > 1 batches steps between host syncs without changing
    greedy output; sampled decode keys derive from the request index, so
    outputs are invariant to slot count / scheduling."""
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    init_ids = jnp.zeros((1, 8), jnp.int32)
    v = model.init(jax.random.PRNGKey(0), init_ids)
    reqs = [Request(prompt=np.asarray(
                rng.integers(0, cfg.vocab_size, (L,)), np.int32),
                max_new_tokens=5)
            for L in (6, 9, 14)]

    e_sync = PagedDecodeEngine(model, v, num_slots=2, page_size=8,
                               sync_every=4)
    outs, _ = e_sync.run(reqs)
    for req, out in zip(reqs, outs):
        ref = np.asarray(generate(model, v, np.asarray(req.prompt)[None],
                                  max_new_tokens=5))
        np.testing.assert_array_equal(out, ref[0, req.prompt.shape[0]:])

    key = jax.random.PRNGKey(3)
    kw = dict(page_size=8, temperature=1.0, top_k=8, rng=key)
    o1, _ = PagedDecodeEngine(model, v, num_slots=1, **kw).run(reqs)
    o2, _ = PagedDecodeEngine(model, v, num_slots=3, **kw).run(reqs)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)


def test_engine_validates_requests(rng):
    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    init_ids = jnp.zeros((1, 8), jnp.int32)
    v = model.init(jax.random.PRNGKey(0), init_ids)
    engine = PagedDecodeEngine(model, v, num_slots=1, page_size=8)
    with pytest.raises(ValueError):      # position cap
        engine.run([Request(prompt=np.zeros((8,), np.int32),
                            max_new_tokens=cfg.max_position_embeddings)])
    with pytest.raises(ValueError):
        engine.run([Request(prompt=np.zeros((8,), np.int32),
                            max_new_tokens=0)])
    # a request whose page demand exceeds the whole pool deadlocks loudly
    small = PagedDecodeEngine(model, v, num_slots=1, page_size=8,
                              num_pages=3)
    with pytest.raises(RuntimeError):
        small.run([Request(prompt=np.zeros((30,), np.int32),
                           max_new_tokens=10)])

# --- the pool is held 128 lanes wide where it can be ------------------------
#
# docs/serving.md "Page-pool layout": ``kv_pool.heads_per_row`` decides, in
# one place and from what the pool is, how many heads a pool row holds
# side by side.

@pytest.mark.parametrize("width,kv_local,quantized,want", [
    (64, 20, False, 2),       # GPT-2 large: two heads a 128-lane row
    (64, 5, False, 1),        # tp=4 of it: 5 heads a chip, 2 does not divide
    (64, 10, False, 2),       # tp=2 of it
    (64, 2, False, 2),
    (64, 1, False, 1),        # one 64-wide head cannot fill a row
    (32, 8, False, 4),
    (32, 6, False, 1),        # 4 does not divide 6: no half-measure of 2
    (16, 16, False, 8),
    (16, 4, False, 1),        # the tiny test configs: 4 heads of 16
    (128, 8, False, 1),       # Llama's heads: nothing changes
    (256, 20, False, 1),
    (96, 4, False, 1),        # no divisor of 128
    (576, 1, False, 1),       # a latent entry as stated
    (640, 1, False, 1),       # and as stored
    (64, 20, True, 1),        # a quantized pool: scales are per head
    (32, 8, True, 1),
])
def test_heads_per_row_table(width, kv_local, quantized, want):
    from apex_tpu.serving import kv_pool

    assert kv_pool.heads_per_row(width, kv_local,
                                 quantized=quantized) == want
    shape = kv_pool._pool_shape(7, kv_local, 16, width, want)
    # the same values in the same bytes, a row 128 lanes where it packs
    assert shape == (7, kv_local // want, 16, width * want)
    assert shape[1] * shape[3] == kv_local * width
    assert want == 1 or shape[3] == 128


@pytest.mark.parametrize("make,pool_row,pack", [
    (lambda: gpt_tiny_config(hidden_size=128, num_heads=2), (1, 128), 2),
    (lambda: gpt_tiny_config(hidden_size=128, num_heads=4), (1, 128), 4),
    (lambda: gpt_tiny_config(), (4, 16), 1),
    (lambda: gpt_tiny_config(hidden_size=192, num_heads=3), (3, 64), 1),
])
def test_init_paged_cache_holds_the_pool_packed(make, pool_row, pack):
    from apex_tpu.serving import kv_pool

    cfg = make()
    cache = init_paged_cache(cfg, num_slots=2, num_pages=6, page_size=8)
    for lc in cache["layers"]:
        assert lc["k_pages"].shape == (6, pool_row[0], 8, pool_row[1])
        assert lc["v_pages"].shape == lc["k_pages"].shape
    assert kv_pool.heads_per_row_of(cache, cfg) == pack
    # what a token stores does not change with how a row is held
    assert kv_pool.page_bytes(cfg, 8) == (
        2 * cfg.num_heads * cfg.head_dim * 8 * 4 * cfg.num_layers)
    # a quantized pool keeps one head a row beside its per-head scales
    q = init_paged_cache(cfg, num_slots=2, num_pages=6, page_size=8,
                         kv_dtype="int8")
    assert q["layers"][0]["k_pages"].shape == (6, cfg.num_heads, 8,
                                               cfg.head_dim)
    assert q["layers"][0]["k_scales"].shape == (6, cfg.num_heads)


def test_prefill_scatter_roundtrip_packed_pool(rng):
    """``prefill_into_pages`` takes the contiguous buffer per HEAD and the
    pool holds two heads a row: position p of head ``2j + q`` lands at
    table entry p//ps, offset p%ps, row j, lanes [64q, 64q + 64)."""
    cfg = gpt_tiny_config(hidden_size=256, num_heads=4)
    ps, s0, bucket = 8, 13, 16
    cache = init_paged_cache(cfg, num_slots=1, num_pages=8, page_size=ps)
    assert cache["layers"][0]["k_pages"].shape == (8, 2, ps, 128)
    cache = alloc_slot(cache, 0, pages_for(s0, ps))
    contig = [{n: jnp.asarray(rng.standard_normal((1, 4, bucket, 64)),
                              jnp.float32) for n in ("k", "v")}
              for _ in range(cfg.num_layers)]
    cache = prefill_into_pages(cache, 0, contig, jnp.int32(s0))
    bt = np.asarray(cache["block_tables"][0])
    for li in range(cfg.num_layers):
        for n in ("k", "v"):
            pages = np.asarray(cache["layers"][li][n + "_pages"])
            want = np.asarray(contig[li][n][0])       # (heads, bucket, d)
            for p in range(s0):
                got = pages[bt[p // ps], :, p % ps, :].reshape(4, 64)
                np.testing.assert_array_equal(got, want[:, p, :])


def _one_head_a_row(monkeypatch):
    """The pool as it was held before: the baseline a packed pool must
    serve the same tokens as. Steered here, in the test: the program has
    no option that chooses the pool's shape."""
    from apex_tpu.serving import kv_pool

    monkeypatch.setattr(kv_pool, "heads_per_row", lambda *a, **k: 1)


@pytest.mark.parametrize("heads,pack", [(2, 2), (4, 4)])
def test_engine_over_a_packed_pool_serves_the_unpacked_pools_tokens(
        rng, monkeypatch, heads, pack):
    """Mixed lengths over fewer slots than requests (admissions through
    the prompt write, decode steps through the page write and the packed
    read): token for token what the pool of one head a row serves, and
    what lock-step ``generate`` gives."""
    cfg = gpt_tiny_config(hidden_size=128, num_heads=heads, num_layers=1)
    model = GPTModel(cfg)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    reqs = [Request(prompt=np.asarray(
                rng.integers(0, cfg.vocab_size, (L,)), np.int32),
                max_new_tokens=m)
            for L, m in zip([5, 16, 23], [6, 3, 5])]

    engine = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
    assert engine.cache["layers"][0]["k_pages"].shape[1:] == (
        heads // pack, 8, 128)
    outs, stats = engine.run(reqs)
    assert stats["pool_heads_per_row"] == pack

    with monkeypatch.context() as m:
        _one_head_a_row(m)
        flat = PagedDecodeEngine(model, v, num_slots=2, page_size=8)
        assert flat.cache["layers"][0]["k_pages"].shape[1:] == (
            heads, 8, 128 // pack)
        flat_outs, flat_stats = flat.run(reqs)
    assert flat_stats["pool_heads_per_row"] == 1
    for out, one in zip(outs, flat_outs):
        np.testing.assert_array_equal(out, one)
    req = reqs[-1]
    ref = np.asarray(generate(model, v, np.asarray(req.prompt)[None],
                              max_new_tokens=req.max_new_tokens))
    np.testing.assert_array_equal(outs[-1], ref[0, req.prompt.shape[0]:])
    assert stats["decode_steps"] == flat_stats["decode_steps"]
    assert int(free_page_count(engine.cache)) == \
        engine.cache["free_stack"].shape[0] - 1
