"""``ops.paged_write`` (the in-place, row-major page write every pool
writer goes through) against the scatter it replaced, kept here as the
plain ``jax.numpy`` reference: the same values in the same ``(page,
offset)`` cells, bit for bit. Runs the real kernel through the Pallas
interpreter. Page 0 is the null page: both writers sink there what they
do not write, and nobody reads it, so it is left out of the comparison.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.paged_write import paged_write

PS = 8


def scatter_reference(pages, chunk, block_tables, lengths, start=None,
                      stop=None):
    """The write as it was: one ``(heads, d)`` slab per position, at
    ``[page, :, offset, :]``; what is not to be written goes to page 0."""
    ps, max_pages = pages.shape[2], block_tables.shape[1]
    s = chunk.shape[2]
    pos = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    ent = pos // ps
    valid = ent < max_pages
    if start is not None:
        valid &= pos >= start
    if stop is not None:
        valid &= pos < stop
    page = jnp.where(valid, jnp.take_along_axis(
        block_tables, jnp.clip(ent, 0, max_pages - 1), axis=1), 0)
    return pages.at[page, :, pos % ps, :].set(
        chunk.transpose(0, 2, 1, 3).astype(pages.dtype))


def _tables(slots, max_pages, rng, num_pages):
    """Distinct pages for every slot, shuffled, none of them page 0."""
    perm = rng.permutation(np.arange(1, num_pages))[:slots * max_pages]
    return perm.reshape(slots, max_pages).astype(np.int32)


#: name -> heads, stored width(s), dtype, s, lengths, and what the case
#: bends: the tables, or the admission's bounds
CASES = {
    "decode_s1": dict(heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=1,
                      lengths=[0, 5, 7, 8, 23]),
    "verify_s4_straddles_a_page": dict(
        heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=4,
        lengths=[6, 5, 4, 13, 16]),
    "chunk_s8_whole_and_split_pages": dict(
        heads=2, widths=(64, 64), dtype=jnp.bfloat16, s=8,
        lengths=[0, 8, 3, 15]),
    "idle_slot_lands_in_page_0_alone": dict(
        heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=1,
        lengths=[3, 0, 9], idle=[1]),
    "neighbouring_pages_of_two_live_slots": dict(
        heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=1,
        lengths=[3, 3, 11], tables=[[5, 7, 9], [6, 8, 10], [11, 12, 13]]),
    "gqa_2_kv_heads": dict(heads=2, widths=(128, 128), dtype=jnp.bfloat16,
                           s=1, lengths=[1, 30]),
    "latent_one_tensor_640_lanes": dict(
        heads=1, widths=(640,), dtype=jnp.bfloat16, s=1,
        lengths=[2, 17, 31]),
    "latent_s4": dict(heads=1, widths=(640,), dtype=jnp.bfloat16, s=4,
                      lengths=[6, 17]),
    "f32_pool_s1": dict(heads=2, widths=(128, 128), dtype=jnp.float32, s=1,
                        lengths=[0, 9]),
    "f32_pool_s4": dict(heads=2, widths=(128, 128), dtype=jnp.float32, s=4,
                        lengths=[5, 14]),
    "past_the_table_is_dropped": dict(
        heads=2, widths=(64, 64), dtype=jnp.bfloat16, s=4,
        lengths=[30, 32], max_pages=4),
    # an admitted prompt: one slot, positions from 0, the bucket's padding
    # past ``stop`` and a shared prefix below ``start`` left alone
    "prompt_40_of_a_48_bucket": dict(
        heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=48, lengths=[0],
        stop=40, max_pages=8),
    "prompt_tail_after_a_shared_prefix": dict(
        heads=4, widths=(64, 64), dtype=jnp.bfloat16, s=48, lengths=[0],
        start=16, stop=43, max_pages=8),
    "prompt_bucket_no_page_multiple": dict(
        heads=2, widths=(64, 64), dtype=jnp.float32, s=20, lengths=[0],
        stop=19, max_pages=4),
    "latent_prompt": dict(heads=1, widths=(640,), dtype=jnp.bfloat16, s=32,
                          lengths=[0], stop=27, max_pages=4),
    "prompt_shorter_than_a_page": dict(
        heads=2, widths=(64, 64), dtype=jnp.bfloat16, s=8, lengths=[0],
        start=0, stop=5, max_pages=2),
}


@pytest.mark.parametrize("name", list(CASES))
def test_paged_write_matches_the_scatter_bit_for_bit(name):
    case = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    heads, dtype, s = case["heads"], case["dtype"], case["s"]
    lengths = jnp.asarray(case["lengths"], jnp.int32)
    slots = len(case["lengths"])
    max_pages = case.get("max_pages", 5)
    num_pages = slots * max_pages + 3
    tables = np.asarray(case["tables"], np.int32) if "tables" in case \
        else _tables(slots, max_pages, rng, num_pages)
    for b in case.get("idle", ()):
        tables[b] = 0                    # an idle slot's row: all null page
    tables = jnp.asarray(tables)
    pools = [jnp.asarray(rng.standard_normal((num_pages, heads, PS, w)),
                         dtype) for w in case["widths"]]
    chunks = [jnp.asarray(rng.standard_normal((slots, heads, s, w)), dtype)
              for w in case["widths"]]
    bounds = {k: case[k] for k in ("start", "stop") if k in case}

    got = jax.jit(lambda p, c: paged_write(p, c, tables, lengths, **bounds))(
        pools, chunks)
    assert len(got) == len(pools)
    for out, pages, chunk in zip(got, pools, chunks):
        want = scatter_reference(pages, chunk, tables, lengths, **bounds)
        assert out.dtype == pages.dtype and out.shape == pages.shape
        np.testing.assert_array_equal(
            np.asarray(out[1:].astype(jnp.float32)),
            np.asarray(want[1:].astype(jnp.float32)))
        # something was written (the case is no empty comparison), and an
        # idle slot's row landed nowhere but in page 0
        changed = np.flatnonzero(np.any(
            np.asarray(out != pages).reshape(num_pages, -1), axis=1))
        assert len(changed[changed > 0]) > 0
        live = np.delete(np.asarray(tables), case.get("idle", ()), axis=0)
        assert set(changed) <= set(live.ravel()) | {0}


def test_paged_write_refuses_mismatched_operands():
    pages = jnp.zeros((5, 2, PS, 64), jnp.bfloat16)
    chunk = jnp.zeros((2, 2, 1, 64), jnp.bfloat16)
    bt, ln = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="one chunk per pool tensor"):
        paged_write([pages, pages], [chunk], bt, ln)
    with pytest.raises(ValueError, match="does not match"):
        paged_write([pages], [chunk[:, :1]], bt, ln)
    with pytest.raises(ValueError, match="do not match 2 slot"):
        paged_write([pages], [chunk], bt[:1], ln)


# --- a pool that holds ``pack`` heads side by side in a 128-lane row --------
#
# (``kv_pool.heads_per_row``; docs/serving.md "Page-pool layout"): the
# callers still hand chunks per head, the wrapper reads ``pack`` off the
# two shapes, and every head's values land in the same ``(page, offset)``
# cell as in a pool of one head a row, bit for bit.

#: name -> heads, head width, s, lengths, the pack the pool must come out
#: with, and the admission's bounds
PACKED_CASES = {
    "pack2_decode_s1": dict(heads=4, d=64, s=1, lengths=[0, 5, 7, 8, 23],
                            pack=2),
    "pack2_verify_s4_straddles_a_page": dict(
        heads=4, d=64, s=4, lengths=[6, 5, 4, 13, 16], pack=2),
    "pack2_chunk_s8_whole_and_split_pages": dict(
        heads=2, d=64, s=8, lengths=[0, 8, 3, 15], pack=2),
    "pack2_idle_slot": dict(heads=4, d=64, s=1, lengths=[3, 0, 9],
                            idle=[1], pack=2),
    "pack2_f32": dict(heads=2, d=64, s=4, lengths=[5, 14], pack=2,
                      dtype=jnp.float32),
    "pack4_decode_s1": dict(heads=8, d=32, s=1, lengths=[1, 30, 12],
                            pack=4),
    "pack4_verify_s3": dict(heads=4, d=32, s=3, lengths=[7, 14], pack=4),
    "pack2_prompt_40_of_a_48_bucket": dict(
        heads=4, d=64, s=48, lengths=[0], stop=40, max_pages=8, pack=2),
    "pack2_prompt_tail_after_a_shared_prefix": dict(
        heads=4, d=64, s=48, lengths=[0], start=16, stop=43, max_pages=8,
        pack=2),
    "pack4_prompt_bucket_no_page_multiple": dict(
        heads=4, d=32, s=20, lengths=[0], stop=19, max_pages=4, pack=4),
    # what cannot pack keeps one head a row
    "odd_head_count_falls_back": dict(heads=5, d=64, s=1, lengths=[2, 17],
                                      pack=1),
    "width_96_falls_back": dict(heads=2, d=96, s=4, lengths=[6, 9],
                                pack=1),
}


@pytest.mark.parametrize("name", list(PACKED_CASES))
def test_packed_pool_holds_what_the_unpacked_pool_holds(name):
    from apex_tpu.ops.paged_write import pack_heads, unpack_heads
    from apex_tpu.serving import kv_pool

    case = PACKED_CASES[name]
    rng = np.random.default_rng(100 + sorted(PACKED_CASES).index(name))
    heads, d, s = case["heads"], case["d"], case["s"]
    dtype = case.get("dtype", jnp.bfloat16)
    pack = kv_pool.heads_per_row(d, heads)
    assert pack == case["pack"]
    lengths = jnp.asarray(case["lengths"], jnp.int32)
    slots = len(case["lengths"])
    max_pages = case.get("max_pages", 5)
    num_pages = slots * max_pages + 3
    tables = _tables(slots, max_pages, rng, num_pages)
    for b in case.get("idle", ()):
        tables[b] = 0
    tables = jnp.asarray(tables)
    pools = [jnp.asarray(rng.standard_normal((num_pages, heads, PS, d)),
                         dtype) for _ in range(2)]
    held = [pack_heads(p, pack) for p in pools]
    assert held[0].shape == kv_pool._pool_shape(num_pages, heads, PS, d,
                                                pack)
    chunks = [jnp.asarray(rng.standard_normal((slots, heads, s, d)), dtype)
              for _ in range(2)]
    bounds = {k: case[k] for k in ("start", "stop") if k in case}

    got = jax.jit(lambda p, c: paged_write(p, c, tables, lengths, **bounds))(
        held, chunks)
    one = paged_write(pools, chunks, tables, lengths, **bounds)
    for out, pool_held, flat, pages, chunk in zip(got, held, one, pools,
                                                  chunks):
        assert out.dtype == pages.dtype and out.shape == pool_held.shape
        out = np.asarray(unpack_heads(out, pack).astype(jnp.float32))
        np.testing.assert_array_equal(
            out[1:], np.asarray(flat[1:].astype(jnp.float32)))
        want = scatter_reference(pages, chunk, tables, lengths, **bounds)
        np.testing.assert_array_equal(
            out[1:], np.asarray(want[1:].astype(jnp.float32)))
        assert np.any(out[1:] != np.asarray(pages[1:].astype(jnp.float32)))


def test_packed_write_refuses_a_chunk_that_is_no_whole_row():
    pages = jnp.zeros((5, 2, PS, 128), jnp.bfloat16)
    bt, ln = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    # 4 heads of 64 fill two rows of 128; 3 do not, nor do 4 of 96
    paged_write([pages], [jnp.zeros((2, 4, 1, 64), jnp.bfloat16)], bt, ln)
    with pytest.raises(ValueError, match="does not match"):
        paged_write([pages], [jnp.zeros((2, 3, 1, 64), jnp.bfloat16)], bt,
                    ln)
    with pytest.raises(ValueError, match="does not match"):
        paged_write([pages], [jnp.zeros((2, 4, 1, 96), jnp.bfloat16)], bt,
                    ln)
