"""Tensor-parallel paged serving (serving/tp.py, docs/tp_serving.md).

The acceptance pins of ISSUE 10: TP=2 paged decode is greedy
TOKEN-IDENTICAL to the single-chip engine (and to lock-step
``generate``) on the forced 8-CPU-device mesh; the tp=1 TP engine
reduces to the current engine exactly; sampled decode through the TP
engine stays SCHEDULING-INVARIANT (slot count, sync_every, arrival
pacing); and the frontend/prefix-cache/scenario stack composes with the
sharded engine transparently. Also covers the variable-sharding helper
(Megatron fused-projection interleave) and the trace-only AbstractMesh
form the lint harness uses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import generate
from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
from apex_tpu.serving.scheduler import PagedDecodeEngine, Request
from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                 abstract_tp_mesh, infer_variable_specs,
                                 shard_model_variables, tp_mesh)

EOS = 1


@pytest.fixture(scope="module")
def tp_setup():
    """One weight set, three views: the tp=1 model/variables, and the
    tp=2 model with the SAME weights sharded over a 2-device mesh."""
    cfg1 = gpt_tiny_config()
    m1 = GPTModel(cfg1)
    v1 = m1.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    cfg2 = gpt_tiny_config(tensor_parallel_size=2)
    m2 = GPTModel(cfg2)
    mesh = tp_mesh(2)
    v2, specs = shard_model_variables(m2, v1, mesh)
    return m1, v1, m2, v2, mesh, specs


def _requests(rng, n=4, eos_free=True):
    lo = 2 if eos_free else 0
    sizes = ((5, 6), (12, 4), (3, 8), (20, 5), (9, 7))[:n]
    return [Request(prompt=rng.integers(lo, 128, s).astype(np.int32),
                    max_new_tokens=m) for s, m in sizes]


def test_tp2_greedy_token_identical_to_single_chip(tp_setup, rng):
    """The acceptance pin: the tp=2 engine's greedy outputs equal the
    single-chip engine's AND lock-step ``generate``'s, request by
    request, token by token."""
    m1, v1, m2, v2, mesh, _ = tp_setup
    reqs = _requests(rng)
    e1 = PagedDecodeEngine(m1, v1, num_slots=2, page_size=8,
                           eos_token_id=EOS)
    o1, s1 = e1.run(reqs)
    e2 = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS)
    o2, s2 = e2.run(reqs)
    assert s2["tp_world"] == 2 and s1["tp_world"] == 1
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and both match the lock-step reference for a couple of requests
    for r, out in list(zip(reqs, o2))[:2]:
        ref = np.asarray(generate(m1, v1, r.prompt[None],
                                  max_new_tokens=r.max_new_tokens,
                                  eos_token_id=EOS))
        ref_gen = ref[0, r.prompt.shape[0]:]
        n = np.asarray(out).shape[0]
        np.testing.assert_array_equal(np.asarray(out), ref_gen[:n])


@pytest.mark.slow
def test_tp2_s_gt_1_programs_token_identical(tp_setup, rng):
    """ISSUE 13: the s>1 paged programs run UNCHANGED under the TP=2
    shard_map seam — in-engine speculative decode (draft pool + s=k
    verify sharded over the same mesh) and chunked prefill both stay
    token-identical to the single-chip non-speculative engine. (Slow
    tier: three engine compiles (~25 s) don't fit the tier-1 wall
    budget; the single-chip s>1 identity pins stay in tier-1 via
    test_spec_chunked_serving.py.)"""
    m1, v1, m2, v2, mesh, _ = tp_setup
    reqs = _requests(rng)
    base, _ = PagedDecodeEngine(m1, v1, num_slots=2, page_size=8,
                                eos_token_id=EOS).run(reqs)
    # self-draft: the tp=2 model doubles as its own draft (full
    # acceptance; the point here is the shard_map seam, not speedup)
    es = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS,
                                   draft_model=m2, draft_variables=v2,
                                   draft_len=2)
    outs, stats = es.run(reqs)
    assert stats["mean_acceptance_len"] > 1.0
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ec = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS,
                                   prefill_chunk=8)
    outc, statc = ec.run(reqs)
    assert statc["chunked_prefills"] >= 1
    for a, b in zip(base, outc):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_tp1_engine_reduces_to_single_chip_exactly(tp_setup, rng):
    """tp=1 must reduce to the current engine token-identically: the
    size-1 mesh's collectives are identity, so the outputs are equal
    EXACTLY (same floats, same argmaxes). (Slow tier: the tp=1 engine
    compile duplicates the single-chip programs; the tier-1 wall budget
    keeps the tp=2 identity pin and the preemption composition.)"""
    m1, v1, _, _, _, _ = tp_setup
    reqs = _requests(rng)
    mesh1 = tp_mesh(1)
    v1s, _ = shard_model_variables(m1, v1, mesh1)
    er = TensorParallelPagedEngine(m1, v1s, mesh=mesh1, num_slots=2,
                                   page_size=8, eos_token_id=EOS)
    outs_r, _ = er.run(reqs)
    e1 = PagedDecodeEngine(m1, v1, num_slots=2, page_size=8,
                           eos_token_id=EOS)
    outs_1, _ = e1.run(reqs)
    for a, b in zip(outs_r, outs_1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_tp2_sampled_scheduling_invariance(tp_setup, rng):
    """Sampled decode through the TP engine draws from per-request key
    streams — outputs must not depend on slot count or chunk size.
    (Slow tier: two extra TP engine compiles; the single-chip sampled
    invariance pin stays tier-1.)"""
    _, _, m2, v2, mesh, _ = tp_setup
    reqs = _requests(rng, n=3)
    key = jax.random.PRNGKey(7)
    ea = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS,
                                   temperature=0.9, top_k=16, rng=key,
                                   sync_every=1)
    eb = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=3,
                                   page_size=8, eos_token_id=EOS,
                                   temperature=0.9, top_k=16, rng=key,
                                   sync_every=3)
    oa, _ = ea.run(reqs)
    ob, _ = eb.run(reqs)
    for a, b in zip(oa, ob):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_tp2_prefix_cache_hits_and_identity(tp_setup, rng):
    """The radix prefix cache shares head-SHARDED pages: warm-cache
    admissions hit, skip the shared-header prefill, and stay
    token-identical to the cache-off single-chip engine. (Slow tier:
    heavy composition variant; the single-chip prefix-cache pins and
    the tp2 greedy identity stay tier-1.)"""
    m1, v1, m2, v2, mesh, _ = tp_setup
    hdr = rng.integers(2, 128, 16).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
        [hdr, rng.integers(2, 128, 4).astype(np.int32)]),
        max_new_tokens=5) for _ in range(4)]
    ec = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS,
                                   prefix_cache=True)
    ec.run(reqs)                       # cold: populate the tree
    outs, stats = ec.run(reqs)         # warm: every admission hits
    assert stats["prefix_hits"] >= len(reqs)
    assert stats["prefill_tokens_skipped"] > 0
    ref_engine = PagedDecodeEngine(m1, v1, num_slots=2, page_size=8,
                                   eos_token_id=EOS)
    ref, _ = ref_engine.run(reqs)
    for a, b in zip(outs, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_tp_shared_prefix_scenario_checks(rng):
    """The catalogued ``tp-shared-prefix`` scenario replays the
    multi-tenant radix workload through the tp=2 engine via the
    FRONTEND (streaming submit, policy, pump) with both amplifiers on:
    per-request greedy identity vs tp=1 lock-step ``generate``, and
    scheduling invariance at a different ``sync_every``."""
    from apex_tpu.serving.scenarios import run_scenario, scenario_spec

    spec = scenario_spec("tp-shared-prefix", seed=5, n_requests=8)
    assert spec.engine.tensor_parallel == 2
    res = run_scenario(spec, check=True)
    assert res.report["checks"]["scheduling_invariance"] is True
    assert res.report["checks"]["greedy_identity_requests"] >= 1
    assert res.stats["tp_world"] == 2
    assert res.stats["retired"] == 8


def test_shard_model_variables_layout(tp_setup):
    """Sharded-variable layout: a rank's shard of the fused qkv weight
    is ITS heads' q,k,v (Megatron interleave), plain column/vocab splits
    are contiguous, and replicated leaves are whole on every device."""
    m1, v1, m2, v2, mesh, specs = tp_setup
    p2 = v2["params"]
    p1 = v1["params"]
    qkv2 = p2["layer_0"]["qkv"]["weight"]
    qkv1 = np.asarray(p1["layer_0"]["qkv"]["weight"])
    e = qkv1.shape[0] // 3
    per = e // 2
    q, k, v = qkv1[:e], qkv1[e:2 * e], qkv1[2 * e:]
    for r in range(2):
        shard = np.asarray(
            [s.data for s in qkv2.addressable_shards
             if s.device == mesh.devices.flat[r]][0])
        expect = np.concatenate([q[r * per:(r + 1) * per],
                                 k[r * per:(r + 1) * per],
                                 v[r * per:(r + 1) * per]])
        np.testing.assert_array_equal(shard, expect)
    # vocab-parallel embedding: contiguous row split
    emb2 = p2["word_embeddings"]["weight"]
    emb1 = np.asarray(p1["word_embeddings"]["weight"])
    half = emb1.shape[0] // 2
    shard0 = np.asarray(
        [s.data for s in emb2.addressable_shards
         if s.device == mesh.devices.flat[0]][0])
    np.testing.assert_array_equal(shard0, emb1[:half])
    # replicated leaf (final norm): full copy, P() spec
    spec = specs["params"]["final_norm"]["weight"]
    assert not any(s is not None for s in spec)
    np.testing.assert_array_equal(
        np.asarray(p2["final_norm"]["weight"]),
        np.asarray(p1["final_norm"]["weight"]))


def test_tp_engine_validates_mesh_and_checkpoint(tp_setup):
    """Misconfigurations fail loudly: a mesh whose axis size disagrees
    with ``tensor_parallel_size``, and a pre-sharded (local-shape)
    checkpoint passed where the full one is expected."""
    m1, v1, m2, _, mesh, _ = tp_setup
    with pytest.raises(ValueError, match="tensor_parallel_size"):
        TensorParallelPagedEngine(m1, v1, mesh=mesh, num_slots=2,
                                  page_size=8)
    local = jax.tree.map(
        lambda s: np.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: m2.init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 8), jnp.int32))))
    with pytest.raises(ValueError, match="FULL shape"):
        shard_model_variables(m2, local, mesh)


def test_abstract_mesh_engine_is_trace_only(tp_setup):
    """An ``AbstractMesh`` engine (the lint-harness/cost-model form)
    builds ShapeDtypeStruct state and traces its programs devicelessly
    — the TP cases must lint on any host, any device count."""
    _, _, m2, _, _, _ = tp_setup
    eng = TensorParallelPagedEngine(
        m2, None, mesh=abstract_tp_mesh(2), num_slots=2, page_size=8,
        num_pages=17, max_pages_per_seq=8, sync_every=2)
    assert eng.abstract
    assert isinstance(eng.cache["layers"][0]["k_pages"],
                      jax.ShapeDtypeStruct)
    # the GLOBAL pool holds the full head count; the spec shards dim 1
    kv_heads = m2.config.num_heads
    assert eng.cache["layers"][0]["k_pages"].shape[1] == kv_heads
    dvars, _ = infer_variable_specs(m2)
    i32 = jnp.int32
    jx = jax.make_jaxpr(eng._step_fn())(
        eng.cache, dvars, jax.ShapeDtypeStruct((2,), i32),
        jax.ShapeDtypeStruct((2,), jnp.bool_),
        jax.ShapeDtypeStruct((2,), i32),
        jax.ShapeDtypeStruct((2, 2), jnp.uint32),
        jax.ShapeDtypeStruct((2,), i32))
    assert jx.eqns, "decode chunk failed to stage"


def test_tp2_frontend_preemption_composes(tp_setup, rng):
    """Preempt-and-spill through the TP engine: pin every slot with
    low-priority work, land a high-priority arrival, and require the
    preemption/resume path to fire with all results intact."""
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.policy import PriorityDeadlinePolicy

    _, _, m2, v2, mesh, _ = tp_setup
    eng = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                    page_size=8, eos_token_id=EOS,
                                    prefix_cache=True)
    fe = ServingFrontend(eng, policy=PriorityDeadlinePolicy(
        preempt_on_priority=True))
    low = [fe.submit(Request(prompt=rng.integers(2, 128, 12).astype(
        np.int32), max_new_tokens=12, priority=0)) for _ in range(2)]
    for _ in range(3):
        fe.pump()
    hi = fe.submit(Request(prompt=rng.integers(2, 128, 6).astype(
        np.int32), max_new_tokens=3, priority=9))
    fe.drain()
    stats = fe.stats()
    assert stats["preemptions"] >= 1 and stats["resumes"] >= 1
    assert hi.result(timeout=0).shape[0] >= 1
    for h in low:
        assert h.result(timeout=0).shape[0] >= 1


@pytest.mark.parametrize("heads,tp_rows,pack", [
    (4, 1, 2),    # 2 heads of 64 a chip: one 128-lane row a chip
    (2, 1, 1),    # 1 head of 64 a chip: a row would straddle the two chips
])
def test_tp2_over_a_pool_of_64_wide_heads(rng, heads, tp_rows, pack):
    """The pool's head axis counts rows of ``pack`` heads, and ``pack`` is
    decided from ONE chip's head count (``kv_pool.heads_per_row``): where
    it divides, each chip's shard is whole rows of its own heads; where
    it does not, the pool keeps one head a row. Either way the tp=2
    engine serves the single-chip engine's tokens (which packs by ITS
    head count)."""
    from apex_tpu.serving import kv_pool

    cfg1 = gpt_tiny_config(hidden_size=64 * heads, num_heads=heads,
                           num_layers=1)
    m1 = GPTModel(cfg1)
    v1 = m1.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    m2 = GPTModel(gpt_tiny_config(hidden_size=64 * heads, num_heads=heads,
                                  num_layers=1, tensor_parallel_size=2))
    mesh = tp_mesh(2)
    v2, _ = shard_model_variables(m2, v1, mesh)
    reqs = _requests(rng, n=2)
    e1 = PagedDecodeEngine(m1, v1, num_slots=2, page_size=8,
                           eos_token_id=EOS, prefix_cache=True)
    assert kv_pool.a_pool(e1.cache).shape[3] == 128
    o1, _ = e1.run(reqs)
    e2 = TensorParallelPagedEngine(m2, v2, mesh=mesh, num_slots=2,
                                   page_size=8, eos_token_id=EOS,
                                   prefix_cache=True)
    pool = kv_pool.a_pool(e2.cache)
    assert pool.shape[1:] == (heads // pack, 8, 64 * pack)
    assert {s.data.shape[1] for s in pool.addressable_shards} == {tp_rows}
    o2, s2 = e2.run(reqs)
    assert s2["tp_world"] == 2 and s2["pool_heads_per_row"] == pack
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a warm second run admits through the shared-prefix gather
    o3, s3 = e2.run(reqs)
    assert s3["prefix_hits"] > 0
    for a, b in zip(o1, o3):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
