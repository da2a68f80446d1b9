"""Paged-attention decode kernel (ops/paged_attention.py).

Parity contract: the Pallas kernel (run through the interpreter on CPU —
the same code Mosaic compiles on chip) must match (a) the pure-jnp
gather-based reference and (b) the dense ``cached_attention`` decode path
it replaces, across MHA/GQA, page-boundary lengths, and scattered
(non-contiguous, permuted) page assignments."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.generation import cached_attention
from apex_tpu.ops.paged_attention import (paged_attention,
                                          paged_attention_reference)

TOL = dict(rtol=2e-5, atol=2e-5)


def _pool(rng, num_pages, kv, ps, d, dtype=jnp.float32):
    k = jnp.asarray(rng.standard_normal((num_pages, kv, ps, d)), dtype)
    v = jnp.asarray(rng.standard_normal((num_pages, kv, ps, d)), dtype)
    return k, v


def _tables(rng, b, max_pages, num_pages):
    """Disjoint, scrambled page assignments (pages 1..num_pages-1)."""
    perm = rng.permutation(np.arange(1, num_pages))[:b * max_pages]
    return jnp.asarray(perm.reshape(b, max_pages), jnp.int32)


def test_matches_reference_mha(rng):
    P, kv, ps, d, b, mp = 24, 4, 8, 16, 3, 4
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, kv, 1, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray([5, 17, 32], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, bt, lens)
    ref = paged_attention_reference(q, k_pages, v_pages, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_matches_reference_gqa(rng):
    """kv=2 < h=6 (rep=3): grouped queries contract against the
    unexpanded kv-head pages."""
    P, kv, h, ps, d, b, mp = 20, 2, 6, 8, 32, 2, 3
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray([9, 24], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, bt, lens)
    ref = paged_attention_reference(q, k_pages, v_pages, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_page_boundary_lengths(rng):
    """Exact-multiple, one-past, one-short, single-token, and zero
    lengths: the per-position mask and the dead-page skip must agree at
    every boundary."""
    P, kv, ps, d, mp = 40, 2, 8, 16, 4
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    lens = jnp.asarray([ps, ps + 1, ps - 1, 1, 0, mp * ps], jnp.int32)
    b = lens.shape[0]
    q = jnp.asarray(rng.standard_normal((b, 4, 1, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens))
    ref = np.asarray(paged_attention_reference(q, k_pages, v_pages, bt,
                                               lens))
    np.testing.assert_allclose(out, ref, **TOL)
    assert (out[4] == 0).all()          # length 0 -> exactly zero output


def test_matches_dense_cached_attention(rng):
    """Cross-validation against the lock-step decode path: scatter a
    contiguous cache into pages, then the paged kernel at length t+1 must
    equal cached_attention at offset t over the contiguous buffer."""
    b, kv, h, t_max, d, ps = 2, 2, 4, 24, 16, 8
    t = 19                                        # mid-page position
    k = jnp.asarray(rng.standard_normal((b, kv, t_max, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv, t_max, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)

    dense = cached_attention(q, {"k": k, "v": v, "len": jnp.int32(t)})

    mp = t_max // ps
    P = 1 + b * mp
    bt = jnp.arange(1, P, dtype=jnp.int32).reshape(b, mp)
    # pages[(bt[b, j]), :, o, :] = contiguous[b, :, j*ps + o, :]
    contig = k.transpose(0, 2, 1, 3).reshape(b * mp, ps, kv, d)
    k_pages = jnp.zeros((P, kv, ps, d)).at[bt.reshape(-1)].set(
        contig.transpose(0, 2, 1, 3))
    contig_v = v.transpose(0, 2, 1, 3).reshape(b * mp, ps, kv, d)
    v_pages = jnp.zeros((P, kv, ps, d)).at[bt.reshape(-1)].set(
        contig_v.transpose(0, 2, 1, 3))

    lens = jnp.full((b,), t + 1, jnp.int32)
    paged = paged_attention(q, k_pages, v_pages, bt, lens)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense), **TOL)


def test_kernel_is_jittable(rng):
    P, kv, ps, d, b, mp = 12, 2, 8, 16, 2, 2
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, kv, 1, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray([3, 12], jnp.int32)
    out = np.asarray(jax.jit(paged_attention)(q, k_pages, v_pages, bt, lens))
    ref = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens))
    np.testing.assert_array_equal(out, ref)


def test_validation_errors(rng):
    P, kv, ps, d = 8, 2, 8, 16
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    bt = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    good_q = jnp.zeros((2, 2, 1, d))
    with pytest.raises(ValueError):      # query block wider than a page
        paged_attention(jnp.zeros((2, 2, ps + 1, d)), k_pages, v_pages,
                        bt, lens)
    with pytest.raises(ValueError):      # heads not a kv multiple
        paged_attention(jnp.zeros((2, 3, 1, d)), k_pages, v_pages, bt, lens)
    with pytest.raises(ValueError):      # head_dim mismatch
        paged_attention(jnp.zeros((2, 2, 1, d * 2)), k_pages, v_pages, bt,
                        lens)
    with pytest.raises(ValueError):      # lengths shape
        paged_attention(good_q, k_pages, v_pages, bt, jnp.zeros((3,),
                                                               jnp.int32))
    with pytest.raises(ValueError):      # non-sublane page size
        paged_attention(good_q, jnp.zeros((P, kv, 12, d)),
                        jnp.zeros((P, kv, 12, d)), bt, lens)


def test_windowed_matches_reference_and_rolling_band(rng):
    """ISSUE 9: `window=` bands the kernel to the exact rolling-cache
    attention set — kernel vs reference vs the dense window mask, across
    boundary-page offsets (window straddling a page edge) and lengths
    shorter than the window."""
    P, kv, ps, d, mp = 40, 2, 8, 16, 4
    W = 11                               # deliberately page-misaligned
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    lens = jnp.asarray([5, W, W + 1, 2 * ps, mp * ps, 0], jnp.int32)
    b = lens.shape[0]
    q = jnp.asarray(rng.standard_normal((b, 4, 1, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens,
                                     window=W))
    ref = np.asarray(paged_attention_reference(q, k_pages, v_pages, bt,
                                               lens, window=W))
    np.testing.assert_allclose(out, ref, **TOL)
    assert (out[5] == 0).all()           # idle slot stays exactly zero
    # against the dense cached band: gather the pages contiguous and run
    # cached_attention with the same window at offset len-1
    for i in range(b - 1):
        t1 = int(lens[i])
        if t1 < 1:
            continue
        kc = jnp.take(k_pages, bt[i], axis=0).transpose(
            1, 0, 2, 3).reshape(1, kv, mp * ps, d)
        vc = jnp.take(v_pages, bt[i], axis=0).transpose(
            1, 0, 2, 3).reshape(1, kv, mp * ps, d)
        dense = cached_attention(q[i:i + 1], {"k": kc, "v": vc,
                                              "len": jnp.int32(t1 - 1)},
                                 window=W)
        np.testing.assert_allclose(out[i], np.asarray(dense)[0], **TOL)


def test_windowed_dropped_pages_leave_the_result_unchanged(rng):
    """The engine's page-drop contract: nulling a block-table entry whose
    page sits fully below the band (and even poisoning the null page's
    contents) must not change the output — dead pages are skipped, not
    masked-after-read."""
    P, kv, ps, d, mp = 24, 2, 8, 16, 4
    W = 10
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    lens = jnp.asarray([4 * ps], jnp.int32)      # band covers pages 2..3
    q = jnp.asarray(rng.standard_normal((1, 4, 1, d)), jnp.float32)
    bt = _tables(rng, 1, mp, P)
    ref = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens,
                                     window=W))
    # drop pages 0 and 1 (fully below the band floor 32-1-10=21 ... page
    # 1 ends at 15 <= 21) and poison the null page
    bt_dropped = bt.at[0, 0].set(0).at[0, 1].set(0)
    k_bad = k_pages.at[0].set(1e9)
    v_bad = v_pages.at[0].set(-1e9)
    out = np.asarray(paged_attention(q, k_bad, v_bad, bt_dropped, lens,
                                     window=W))
    np.testing.assert_array_equal(out, ref)
    with pytest.raises(ValueError):      # non-positive window
        paged_attention(q, k_pages, v_pages, bt, lens, window=0)
    with pytest.raises(ValueError):      # non-static (array) window
        paged_attention(q, k_pages, v_pages, bt, lens,
                        window=jnp.int32(W))


# --------------------------------------------------------------------------
# s > 1 query blocks (ISSUE 13: speculative verify / chunked prefill)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s_q", [2, 4, 8])
def test_query_block_matches_reference(rng, s_q):
    """The kernel generalized to a static query block: position ``i`` of
    the block attends causally up to ``lengths[b] - s_q + i`` — parity
    against the reference at every s, over boundary lengths including
    ``len < s_q`` (admission never produces it, but the mask must stay
    sane) and ``len = 0``."""
    P, kv, ps, d, mp = 40, 2, 8, 16, 4
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    lens = jnp.asarray([s_q, ps, ps + 1, 2 * ps - 1, mp * ps,
                        max(s_q - 1, 0), 0], jnp.int32)
    b = lens.shape[0]
    q = jnp.asarray(rng.standard_normal((b, 4, s_q, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens))
    ref = np.asarray(paged_attention_reference(q, k_pages, v_pages, bt,
                                               lens))
    np.testing.assert_allclose(out, ref, **TOL)
    assert out.shape == (b, 4, s_q, d)
    assert (out[6] == 0).all()           # length 0 -> exactly zero block


def test_query_block_gqa_matches_reference(rng):
    """GQA grouping under an s=4 block: each kv head serves rep=3 query
    heads at every block position."""
    P, kv, h, ps, d, b, mp, s_q = 20, 2, 6, 8, 32, 2, 3, 4
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, h, s_q, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray([9, 24], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, bt, lens)
    ref = paged_attention_reference(q, k_pages, v_pages, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_query_block_last_row_matches_s1(rng):
    """Consistency across block widths: the LAST row of an s-block at
    length t equals the s=1 call at length t (same query, same visible
    set ``<= t - 1``) — the property that makes a chunked prefill's
    final logit interchangeable with a decode step's."""
    P, kv, ps, d, b, mp, s_q = 24, 2, 8, 16, 2, 3, 4
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, 4, s_q, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray([13, 2 * ps], jnp.int32)
    block = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens))
    single = np.asarray(paged_attention(q[:, :, -1:], k_pages, v_pages,
                                        bt, lens))
    np.testing.assert_allclose(block[:, :, -1:], single, **TOL)


def test_query_block_windowed_matches_reference(rng):
    """The window band composes with s>1: block position ``i`` sees
    exactly ``(qpos_i - W, qpos_i]`` — parity at a page-misaligned
    window, including lengths inside the first window."""
    P, kv, ps, d, mp, s_q = 40, 2, 8, 16, 4, 4
    W = 11
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    lens = jnp.asarray([s_q, W, W + s_q, 2 * ps, mp * ps], jnp.int32)
    b = lens.shape[0]
    q = jnp.asarray(rng.standard_normal((b, 4, s_q, d)), jnp.float32)
    bt = _tables(rng, b, mp, P)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, lens,
                                     window=W))
    ref = np.asarray(paged_attention_reference(q, k_pages, v_pages, bt,
                                               lens, window=W))
    np.testing.assert_allclose(out, ref, **TOL)


# --------------------------------------------------------------------------
# the page-block tile (ISSUE 28): one grid step serves all kv heads of a
# slot over a block of consecutive table entries (128 tokens: 16 pages of
# 8, 8 pages of 16), bounded by the slot's live pages
# --------------------------------------------------------------------------

def _scattered_tables(rng, lens, max_pages, num_pages, ps, dead="null",
                      below_band=None):
    """Each slot's live entries hold pages of its own (scrambled); its
    dead entries hold the null page 0, or — ``dead="stolen"`` — live
    pages of the OTHER slots, which the kernel must never read as this
    slot's. ``dead="dropped"`` also hands the entries wholly under the
    earliest query's band (``below_band = s_q + window - 1`` positions
    under the length) back to the null page, as the engine does
    (``kv_pool.drop_slot_pages``)."""
    b = len(lens)
    free = list(rng.permutation(np.arange(1, num_pages)))
    bt = np.zeros((b, max_pages), np.int32)
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // ps)):
            bt[i, j] = free.pop()
    if dead == "stolen":
        live = [p for p in bt.reshape(-1) if p]
        for i, n in enumerate(lens):
            mine = set(bt[i, :-(-int(n) // ps)])
            others = [p for p in live if p not in mine]
            for j in range(-(-int(n) // ps), max_pages):
                bt[i, j] = others[(i + j) % len(others)]
    if dead == "dropped":
        for i, n in enumerate(lens):
            bt[i, :max(int(n) - below_band, 0) // ps] = 0
    return jnp.asarray(bt)


_BLOCK_CASES = {
    # the serving cell's call: GPT-2 large, 16 slots, 64-page tables
    "cell_shape_bf16": dict(kv=20, rep=1, d=64, ps=16, mp=64,
                            lens=[110, 180, 200, 290, 300, 420, 450, 560,
                                  600, 800, 150, 260, 330, 480, 130, 1024],
                            dtype=jnp.bfloat16),
    # 20 entries against blocks of 16: the second block's tail entries
    # lie past the table (clamped index, masked positions)
    "max_pages_no_multiple_of_the_block": dict(
        kv=2, rep=2, d=16, ps=8, mp=20, lens=[160, 129, 100, 7]),
    "lengths_at_the_block_edges": dict(
        kv=2, rep=1, d=16, ps=8, mp=32, lens=[0, 1, 127, 128, 129, 256]),
    "slots_end_in_different_blocks": dict(
        kv=2, rep=2, d=16, ps=8, mp=48, lens=[5, 384, 130, 250, 0, 300]),
    "dead_entries_hold_other_slots_pages": dict(
        kv=2, rep=1, d=16, ps=8, mp=32, lens=[3, 128, 131, 200],
        dead="stolen"),
    "query_block_straddles_a_block_edge": dict(
        kv=2, rep=2, d=16, ps=8, mp=32, s_q=4, lens=[130, 129, 128, 131, 2]),
    "window_across_blocks": dict(
        kv=2, rep=1, d=16, ps=8, mp=40, window=37,
        lens=[300, 165, 128, 36, 1]),
    "window_query_block_across_blocks": dict(
        kv=2, rep=2, d=16, ps=8, mp=40, window=130, s_q=3,
        lens=[300, 258, 131, 3]),
    "tp_local_heads_kv5": dict(kv=5, rep=1, d=64, ps=16, mp=24,
                               lens=[383, 129, 16, 300],
                               dtype=jnp.bfloat16),
    "gqa_rep4_d128": dict(kv=2, rep=4, d=128, ps=16, mp=20,
                          lens=[320, 17, 128, 250]),
    # the work list of live blocks (ISSUE 38, ops/_page_walk.py): 16
    # pages of 8 a block, the grid's last axis as long as the list
    "idle_slots_first_last_and_between": dict(
        kv=2, rep=2, d=16, ps=8, mp=48, lens=[0, 300, 0, 0, 130, 17, 0]),
    "a_block_edge_and_one_past_it": dict(
        kv=2, rep=1, d=16, ps=8, mp=48, lens=[128, 129, 256, 257, 384]),
    "every_slot_full": dict(                 # the list at its static length
        kv=2, rep=1, d=16, ps=8, mp=32, lens=[256, 256, 256, 256]),
    "every_slot_full_of_a_table_no_multiple_of_the_block": dict(
        kv=2, rep=2, d=16, ps=8, mp=20, lens=[160, 160, 160]),
    "one_live_slot": dict(kv=2, rep=1, d=16, ps=8, mp=48,
                          lens=[0, 0, 0, 300, 0]),
    "every_slot_idle": dict(kv=2, rep=1, d=16, ps=8, mp=32, lens=[0, 0, 0]),
    "window_with_dropped_leading_pages": dict(
        kv=2, rep=1, d=16, ps=8, mp=40, window=37, dead="dropped",
        lens=[300, 0, 165, 128, 36, 1, 0]),
    "window_query_block_of_a_page_dropped_leading_pages": dict(
        kv=2, rep=2, d=16, ps=8, mp=40, window=50, s_q=8, dead="dropped",
        lens=[300, 264, 0, 136, 8]),
    "query_block_of_4_ragged": dict(
        kv=2, rep=2, d=16, ps=8, mp=40, s_q=4, lens=[0, 131, 4, 260, 0, 128]),
    "query_block_of_a_page_ragged": dict(
        kv=2, rep=1, d=16, ps=8, mp=40, s_q=8, lens=[8, 136, 0, 129, 264]),
    "head_block_of_a_vmem_split": dict(      # two head blocks walk one list
        kv=64, rep=1, d=256, ps=64, mp=4, lens=[70, 0, 256, 129]),
}


@pytest.mark.parametrize("name", list(_BLOCK_CASES))
def test_page_block_tile_matches_reference(rng, name):
    c = dict(_BLOCK_CASES[name])
    kv, rep, d, ps, mp = c["kv"], c["rep"], c["d"], c["ps"], c["mp"]
    dtype = c.get("dtype", jnp.float32)
    s_q, window = c.get("s_q", 1), c.get("window")
    lens = c["lens"]
    b = len(lens)
    P = 2 + sum(-(-n // ps) for n in lens)
    k_pages, v_pages = _pool(rng, P, kv, ps, d, dtype)
    q = jnp.asarray(rng.standard_normal((b, kv * rep, s_q, d)), dtype)
    bt = _scattered_tables(rng, lens, mp, P, ps, c.get("dead", "null"),
                           below_band=s_q + (window or 0) - 1)
    ln = jnp.asarray(lens, jnp.int32)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, ln,
                                     window=window), np.float32)
    ref = np.asarray(paged_attention_reference(
        q, k_pages, v_pages, bt, ln, window=window), np.float32)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(out, ref, **tol)
    for i, n in enumerate(lens):
        if n == 0:
            assert (out[i] == 0).all()   # an idle slot stays exactly zero


@pytest.mark.parametrize("window", [None, 37])
def test_page_block_tile_never_reads_a_dead_entry(rng, window):
    """Dead entries are clamped away, not masked after the read: NaN in
    the null page, and in every page a dead entry names, leaves the
    result as it was — below the band, past the end, in a live block's
    tail and in wholly dead blocks alike."""
    kv, d, ps, mp = 2, 16, 8, 40
    lens = [300, 165, 128, 36, 1, 0]
    b = len(lens)
    P = 3 + sum(-(-n // ps) for n in lens)
    k_pages, v_pages = _pool(rng, P, kv, ps, d)
    q = jnp.asarray(rng.standard_normal((b, kv, 1, d)), jnp.float32)
    bt = np.array(_scattered_tables(rng, lens, mp, P, ps))
    ln = jnp.asarray(lens, jnp.int32)
    want = np.asarray(paged_attention(q, k_pages, v_pages, jnp.asarray(bt),
                                      ln, window=window))
    poison = min(set(range(1, P)) - set(bt.reshape(-1).tolist()))  # unowned
    for i, n in enumerate(lens):
        first = max(n - 1 - window + 1, 0) // ps if window else 0
        bt[i, -(-n // ps):] = poison     # past the end
        bt[i, :first] = 0                # dropped below the band
    k_bad = k_pages.at[0].set(jnp.nan).at[poison].set(jnp.nan)
    v_bad = v_pages.at[0].set(jnp.nan).at[poison].set(jnp.nan)
    out = np.asarray(paged_attention(q, k_bad, v_bad, jnp.asarray(bt), ln,
                                     window=window))
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("lens", [
    [300, 129, 128, 9, 0], [0, 320, 0, 320, 0], [0, 0, 257, 0, 0],
    [320, 320, 320, 320, 0]], ids=["ragged", "idle_between_full_slots",
                                   "one_live_slot", "full_but_the_last"])
@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_page_block_tile_quantized_pool(rng, kv_dtype, lens):
    """A quantized pool across block edges: the per-page per-head scales
    ride the same clamped entries as the pages, gathered a work item at a
    time (a NaN scale on the null page is never gathered into a live
    position's arithmetic)."""
    kv, rep, d, ps, mp = 2, 2, 16, 8, 40
    b = len(lens)
    P = 2 + sum(-(-n // ps) for n in lens)
    kf, vf = _pool(rng, P, kv, ps, d)
    if kv_dtype == "int8":
        qmax, dt = 127.0, jnp.int8
        narrow = lambda x: jnp.round(x).astype(dt)
    else:
        qmax, dt = 448.0, jnp.float8_e4m3fn
        narrow = lambda x: x.astype(dt)
    ks = jnp.max(jnp.abs(kf), axis=(2, 3)) / qmax
    vs = jnp.max(jnp.abs(vf), axis=(2, 3)) / qmax
    k_pages = narrow(kf / ks[:, :, None, None])
    v_pages = narrow(vf / vs[:, :, None, None])
    q = jnp.asarray(rng.standard_normal((b, kv * rep, 1, d)), jnp.float32)
    bt = _scattered_tables(rng, lens, mp, P, ps)
    ln = jnp.asarray(lens, jnp.int32)
    out = np.asarray(paged_attention(q, k_pages, v_pages, bt, ln,
                                     k_scales=ks, v_scales=vs))
    ref = np.asarray(paged_attention_reference(q, k_pages, v_pages, bt, ln,
                                               k_scales=ks, v_scales=vs))
    np.testing.assert_allclose(out, ref, **TOL)
    assert (out[4] == 0).all()
    bad = np.asarray(paged_attention(
        q, k_pages, v_pages, bt, ln, k_scales=ks.at[0].set(jnp.nan),
        v_scales=vs.at[0].set(jnp.nan)))
    np.testing.assert_array_equal(bad, out)


@pytest.mark.parametrize("length,window,s_q,want", [
    (0, None, 1, 8), (1, None, 1, 8), (128, None, 1, 8), (129, None, 1, 16),
    (450, None, 1, 32), (1024, None, 1, 64),
    # band of 100 under position 449: pages 21..28, blocks 2 and 3
    (450, 100, 1, 16),
    # the earliest of 4 queries reaches one page further down
    (261, 5, 1, 8), (261, 5, 4, 16),
])
def test_pages_fetched_counts_whole_live_blocks(length, window, s_q, want):
    """What ``serving.kv_bytes_fetched`` charges a slot: every block of
    8 pages (the cell's tile: page 16, 20 heads of 64, bf16) that holds
    a live page, whole."""
    from apex_tpu.ops._page_walk import pages_fetched

    assert pages_fetched(length, kv_heads=20, page_size=16, head_dim=64,
                         dtype=jnp.bfloat16, max_pages=64, s_q=s_q,
                         window=window) == want


@pytest.mark.parametrize("window,s_q", [(None, 1), (100, 1), (5, 4),
                                        (None, 16), (300, 16)])
def test_the_walk_is_the_blocks_pages_fetched_charges(window, s_q):
    """``ops/_page_walk.page_walk``: its traced bound ``n_work`` is the
    count of blocks ``pages_fetched`` charges over the same lengths, so
    ``serving.kv_bytes_fetched`` over a window is the grid steps the
    kernels ran; and the list is what a loop over the slots writes —
    slot-major, ascending in the block, every entry clamped into its
    slot's live pages, an idle slot one item."""
    from apex_tpu.ops._page_walk import _live_pages, page_walk
    from apex_tpu.ops._page_walk import pages_fetched

    ps, pages, mp = 16, 8, 64
    lens = [0, 1, 127, 128, 129, 0, 450, 1024, 261, 16, 0]
    bt = np.arange(1, len(lens) * mp + 1, dtype=np.int32).reshape(-1, mp)

    @jax.jit
    def walked(tables, lengths):
        walk = page_walk(tables, lengths, page_size=ps, pages=pages,
                         s_q=s_q, window=window)
        return walk.prefetch, walk.n_work

    prefetch, n_work = walked(jnp.asarray(bt), jnp.asarray(lens, jnp.int32))
    n_work = int(n_work)
    assert n_work == sum(pages_fetched(
        n, kv_heads=20, page_size=ps, head_dim=64, dtype=jnp.bfloat16,
        max_pages=mp, s_q=s_q, window=window) for n in lens) // pages
    slot_of, block_of, phys, starts = [], [], [], [0]
    for slot, n in enumerate(lens):
        first, last = _live_pages(n, ps, s_q, window)
        for block in range(first // pages, last // pages + 1):
            slot_of.append(slot)
            block_of.append(block)
            phys.append([bt[slot, min(max(block * pages + i, first), last)]
                         for i in range(pages)])
        starts.append(len(slot_of))
    got_phys, got_slot, got_block, got_starts, got_len = map(
        np.asarray, prefetch)
    assert n_work == len(slot_of) == got_starts[-1]
    assert got_slot.shape == (len(lens) * mp // pages,)   # the worst case
    np.testing.assert_array_equal(got_slot[:n_work], slot_of)
    np.testing.assert_array_equal(got_block[:n_work], block_of)
    np.testing.assert_array_equal(
        got_phys.reshape(-1, pages)[:n_work], phys)
    np.testing.assert_array_equal(got_starts, starts)
    np.testing.assert_array_equal(got_len, lens)
    # what lies past the bound is never run, and names a page all the same
    assert set(got_slot[n_work:]) <= {len(lens) - 1}
    assert np.isin(got_phys, bt).all()


@pytest.mark.parametrize("kv,ps,d,dtype,mp,want", [
    (20, 16, 64, jnp.bfloat16, 64, (8, 20)),      # the serving cell
    (5, 16, 64, jnp.bfloat16, 64, (8, 5)),        # its tp=4 shard
    (12, 16, 64, jnp.int8, 32, (8, 12)),          # quantized gpt2-small
    (2, 8, 16, jnp.float32, 4, (4, 2)),           # table shorter than a block
    (8, 16, 128, jnp.bfloat16, 512, (8, 8)),      # llama GQA
    (64, 64, 256, jnp.float32, 64, (1, 32)),      # VMEM forces a head block
])
def test_tile_is_derived_from_the_shapes(kv, ps, d, dtype, mp, want):
    from apex_tpu.ops._page_walk import _tile

    assert _tile(kv, ps, d, dtype, mp) == want


# --- a pool that holds ``pack`` heads side by side in a 128-lane row --------
#
# (``kv_pool.heads_per_row``; docs/serving.md "Page-pool layout"). The
# wrapper reads ``pack`` off the shapes; the pool as the engine would hold
# it is built here the way ``kv_pool`` builds it, so a head count that
# ``pack`` does not divide falls back to one head a row in the test as in
# the engine.

#: name -> kv heads, q heads, head width, s, lengths (INCLUDING the s
#: current tokens), window, the pack the pool must come out with
_PACKED_CASES = {
    "pack2_s1": dict(kv=4, h=4, d=64, s=1, lens=[5, 17, 32, 0], pack=2),
    "pack2_gqa_rep3_s1": dict(kv=2, h=6, d=64, s=1, lens=[9, 24], pack=2),
    "pack2_verify_s4": dict(kv=4, h=4, d=64, s=4, lens=[4, 13, 27],
                            pack=2),
    "pack2_chunk_s8_straddles_pages": dict(kv=2, h=4, d=64, s=8,
                                           lens=[11, 20, 29], pack=2),
    "pack2_window": dict(kv=4, h=8, d=64, s=1, lens=[7, 23, 40], window=11,
                         pack=2),
    "pack2_window_s4": dict(kv=2, h=2, d=64, s=4, lens=[9, 30], window=6,
                            pack=2),
    "pack4_s1": dict(kv=4, h=4, d=32, s=1, lens=[3, 16, 31], pack=4),
    "pack4_gqa_rep2_s3": dict(kv=8, h=16, d=32, s=3, lens=[6, 19], pack=4),
    "pack4_bf16": dict(kv=4, h=8, d=32, s=2, lens=[10, 25], pack=4,
                       dtype=jnp.bfloat16),
    # what cannot pack keeps one head a row
    "odd_head_count_falls_back": dict(kv=5, h=5, d=64, s=1, lens=[8, 21],
                                      pack=1),
    "three_heads_of_32_fall_back": dict(kv=3, h=6, d=32, s=2, lens=[5, 18],
                                        pack=1),
    "width_96_falls_back": dict(kv=2, h=2, d=96, s=1, lens=[12, 30],
                                pack=1),
    "width_128_is_one_head_a_row": dict(kv=2, h=4, d=128, s=1,
                                        lens=[12, 30], pack=1),
    # tables of three blocks of 16 pages: the work list under a packed row
    "pack2_idle_slots_between_blocks": dict(
        kv=4, h=8, d=64, s=1, mp=40, lens=[0, 300, 0, 129, 128, 0], pack=2),
    "pack2_every_slot_full_s4": dict(kv=2, h=2, d=64, s=4, mp=32,
                                     lens=[256, 256, 256], pack=2),
    "pack2_window_across_blocks_s8": dict(
        kv=2, h=4, d=64, s=8, mp=40, lens=[300, 0, 140, 8], window=41,
        pack=2),
}


@pytest.mark.parametrize("name", list(_PACKED_CASES))
def test_packed_pool_matches_reference_and_the_unpacked_pool(rng, name):
    from apex_tpu.ops.paged_write import pack_heads, unpack_heads
    from apex_tpu.serving import kv_pool

    case = _PACKED_CASES[name]
    kv, h, d, s = case["kv"], case["h"], case["d"], case["s"]
    dtype = case.get("dtype", jnp.float32)
    window = case.get("window")
    ps, mp = 8, case.get("mp", 5)
    b = len(case["lens"])
    P = b * mp + 2
    pack = kv_pool.heads_per_row(d, kv)
    assert pack == case["pack"]
    k_pages, v_pages = _pool(rng, P, kv, ps, d, dtype)
    held = kv_pool._pool_shape(P, kv, ps, d, pack)
    k_held, v_held = pack_heads(k_pages, pack), pack_heads(v_pages, pack)
    assert k_held.shape == held and held[1] * held[3] == kv * d
    assert pack == 1 or held[3] == 128
    np.testing.assert_array_equal(np.asarray(unpack_heads(k_held, pack)),
                                  np.asarray(k_pages))
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    bt = _tables(rng, b, mp, P)
    lens = jnp.asarray(case["lens"], jnp.int32)

    out = jax.jit(lambda *a: paged_attention(*a, window=window))(
        q, k_held, v_held, bt, lens)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = paged_attention_reference(q, k_pages, v_pages, bt, lens,
                                    window=window)
    tol = TOL if dtype == jnp.float32 else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)
    # and the packed read is the unpacked read: the other heads' lanes
    # meet zeros
    one = paged_attention(q, k_pages, v_pages, bt, lens, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(one, np.float32), **tol)


def test_packed_pool_refuses_scales_and_a_width_that_is_no_divisor(rng):
    k_pages, v_pages = _pool(rng, 6, 2, 8, 128)
    bt = jnp.ones((1, 2), jnp.int32)
    lens = jnp.asarray([3], jnp.int32)
    q = jnp.zeros((1, 4, 1, 64), jnp.float32)
    sc = jnp.ones((6, 2), jnp.float32)
    with pytest.raises(ValueError, match="one head a row"):
        paged_attention(q, k_pages, v_pages, bt, lens, k_scales=sc,
                        v_scales=sc)
    with pytest.raises(ValueError, match="head_dim mismatch"):
        paged_attention(jnp.zeros((1, 2, 1, 96), jnp.float32), k_pages,
                        v_pages, bt, lens)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        paged_attention(jnp.zeros((1, 6, 1, 64), jnp.float32), k_pages,
                        v_pages, bt, lens)
    # the reference is per head: a pool as held is unpacked before it
    with pytest.raises(ValueError, match="heads only"):
        paged_attention_reference(q, k_pages, v_pages, bt, lens)
