"""Pallas page-write kernel: put new tokens into the paged KV pool in
place, in the layout the decode kernels read.

Every program that writes the pool (``models/generation.
update_paged_layer_cache``: a decode step's token, a speculative verify
block, a prefill chunk; ``serving/kv_pool.prefill_into_pages``: an
admitted prompt) writes it through this kernel. The pool is
``(num_pages, rows, page_size, lanes)`` and ``paged_attention`` /
``paged_latent_attention`` take it row-major, a page's rows contiguous,
as every Mosaic call takes its operands. A pool ROW is one latent entry,
one head of 128 values or more, or ``pack`` narrower heads side by side
(``serving/kv_pool.heads_per_row``: two 64-wide heads in 128 lanes, so
that row-major is also the layout the device gives the pool between
programs; docs/serving.md "Page-pool layout"). Callers hand chunks per
HEAD, ``(slots, heads, s, d)``; the wrapper reads ``pack`` off the shapes
(``lanes // d``) and lays ``pack`` heads side by side
(:func:`pack_heads`, a reshape and a transpose of the small chunk), so
the kernel below sees rows and never a head. A ``.at[page, :, off, :].set``
over the head axis is a scatter that XLA lays out with a token's
``(heads, stored)`` slab contiguous (``{3,1,2,0}``); that became the
layout of the whole program's pool, and a ``copy`` of every layer's
whole K and V pool stood in front of every kernel of every decode step
(PERF.md, PR 32: 18 of a 33 ms step). This kernel's operands are
row-major like the readers', its output ALIASES its pool operand
(``input_output_aliases``), and one grid step moves one page: so the
``lax.scan`` of the decode chunk carries the pool as the kernels take
it and nothing re-lays it (``tests/test_aot_mosaic.py`` pins the
compiled text).

One grid step ``(b, j)`` reads one whole page ``(1, rows, page_size,
lanes)`` of every tensor, merges the rows that land in it by a row mask
(``where(row == shift + i, new row i, page)``: no dynamic sublane store,
a bf16 sublane packs two rows) and writes the page back. The page is
named by a scalar-prefetched table the wrapper resolves from the block
tables exactly as the scatter did: chunk position ``i`` of slot ``b``
sits at ``lengths[b] + i`` and lands in table entry ``pos // page_size``
at offset ``pos % page_size``. Two shapes of chunk, told apart by ``s``:

* ``s <= page_size`` (decode ``s = 1``, speculative verify ``s = k``, a
  prefill chunk): the slot's whole chunk is one source block, and it
  touches the page its first position is in and, where it straddles a
  boundary, the next: ``j`` runs over those two (one for ``s = 1``).
* ``s > page_size`` (an admitted prompt): ``lengths`` are page multiples
  by the caller's promise (a prompt's buffer starts at position 0), so
  source block ``j`` is page ``j`` of the chunk, whole.

A step with nothing to write (an idle slot, whose table row is all null
page; the second page of a chunk that does not straddle; bucket padding
and a shared prefix in an admission; a position past the table) names
the NULL PAGE 0, which no sequence ever reads, and merges no row.

Why the pipeline's overlap is safe: the next step's page is prefetched
while this step's is written back, and a step keeps its buffers where
its page index repeats the step before. Live slots own distinct pages
and a slot's entries are distinct pages, so two steps that name the same
page both name page 0: there a stale read or a lost write changes
nothing anyone reads. The grid is ``"arbitrary"`` on both axes for the
same reason (page 0 may be written by many steps).

Tensor parallelism: inside ``serving/tp.py``'s ``shard_map`` the pool is
sharded on its row axis and this kernel sees one chip's rows (its
``heads / tp`` heads, ``pack`` to a row); heads never interact in a
write.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch

_INTERPRET = _dispatch.interpret


def pack_heads(x, pack: int):
    """``(a, heads, t, d)`` per head -> ``(a, heads // pack, t, d * pack)``
    as a pool row holds it: row ``j`` carries heads ``j*pack .. j*pack +
    pack - 1`` side by side, head ``j*pack + p`` in lanes ``[p*d,
    (p+1)*d)``. ``pack == 1`` is the identity."""
    if pack == 1:
        return x
    a, heads, t, d = x.shape
    return (x.reshape(a, heads // pack, pack, t, d).transpose(0, 1, 3, 2, 4)
            .reshape(a, heads // pack, t, d * pack))


def unpack_heads(x, pack: int):
    """:func:`pack_heads` undone: ``(a, rows, t, d * pack)`` -> ``(a, rows
    * pack, t, d)`` (page tiles ``(pages, rows, page_size, lanes)`` and
    whole pools alike)."""
    if pack == 1:
        return x
    a, rows, t, lanes = x.shape
    return (x.reshape(a, rows, t, pack, lanes // pack)
            .transpose(0, 1, 3, 2, 4)
            .reshape(a, rows * pack, t, lanes // pack))


def _write_kernel(phys_ref, shift_ref, lo_ref, hi_ref, *refs, n, rows):
    del phys_ref                       # read by the index maps alone
    page_refs, src_refs, out_refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    b, j = pl.program_id(0), pl.program_id(1)
    shift, lo, hi = shift_ref[b, j], lo_ref[b, j], hi_ref[b, j]
    for page_ref, src_ref, out_ref in zip(page_refs, src_refs, out_refs):
        page = page_ref[0]                        # (heads, page_size, d)
        src = src_ref[0]                          # (heads, rows, d)
        # ("heads" here and below: the pool's rows, each ``pack`` heads
        # wide; the body never tells them apart)
        row = lax.broadcasted_iota(jnp.int32, page.shape, 1)
        live = jnp.logical_and(row >= lo, row < hi)
        for i in range(rows):
            # source row i sits at page row shift + i; rows that fall
            # outside this page match no page row
            page = jnp.where(jnp.logical_and(live, row == shift + i),
                             src[:, i:i + 1, :], page)
        out_ref[0] = page


def paged_write(pools: Sequence, chunks: Sequence, block_tables, lengths, *,
                start=None, stop=None):
    """Write one chunk per pool tensor into the page pool, in place.

    Args:
      pools: the layer's pool tensors, each ``(num_pages, heads // pack,
        page_size, d * pack)`` (per-head K and V, ``pack`` heads to a
        row; the one latent entry with ``heads = pack = 1``); all written
        at the same ``(page, offset)``.
      chunks: one ``(slots, heads, s, d)`` chunk per pool tensor, per
        HEAD whatever the pool packs: ``pack`` is read off the two
        shapes.
      block_tables: int32 ``(slots, max_pages)``.
      lengths: int32 ``(slots,)``: chunk position ``i`` of slot ``b`` is
        absolute position ``lengths[b] + i``. With ``s > page_size``
        they must be page multiples.
      start / stop: optional bounds (scalars or ``(slots,)``): only
        positions ``start <= pos < stop`` are written.

    Returns the updated pool tensors, a list in ``pools``' order; every
    cell not written keeps its value (page 0, the null page, excepted).
    """
    # one jitted function for all layers: a model calls this once a layer
    # with the same shapes, and the table arithmetic and the kernel are
    # then traced and lowered once a program, not once a layer (the admit
    # programs' set-up time: PERF.md, PR 32). ``interpret`` is static, so
    # the Mosaic and the interpreter forms never share a trace
    return _write(tuple(pools), tuple(chunks), block_tables, lengths, start,
                  stop, interpret=_INTERPRET())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write(pools, chunks, block_tables, lengths, start, stop, *, interpret):
    if len(pools) != len(chunks) or not pools:
        raise ValueError(f"paged_write needs one chunk per pool tensor, "
                         f"got {len(pools)} pool(s), {len(chunks)} chunk(s)")
    num_pages, heads, ps, _ = pools[0].shape       # heads: the pool's rows
    slots, _, s, _ = chunks[0].shape
    packed = []
    for pages, chunk in zip(pools, chunks):
        if pages.shape[:3] != (num_pages, heads, ps) or pages.ndim != 4:
            raise ValueError(f"pool tensors must share (num_pages, heads, "
                             f"page_size): {pages.shape} vs "
                             f"{pools[0].shape}")
        # heads one pool row holds side by side, from the shapes alone
        pack = max(pages.shape[3] // chunk.shape[3], 1)
        if chunk.shape != (slots, heads * pack, s, pages.shape[3] // pack):
            raise ValueError(
                f"chunk {chunk.shape} does not match (slots, heads, s, d) "
                f"= {(slots, heads * pack, s, pages.shape[3] // pack)} of "
                f"pool {pages.shape} ({pack} head(s) a row)")
        packed.append(pack_heads(chunk.astype(pages.dtype), pack))
    max_pages = block_tables.shape[1]
    if block_tables.shape[0] != slots or lengths.shape != (slots,):
        raise ValueError(f"block_tables {block_tables.shape} / lengths "
                         f"{lengths.shape} do not match {slots} slot(s)")
    if s <= ps:
        # the whole chunk is the source block of both pages it can touch
        rows, stride, n_j = s, 0, (1 if s == 1 else 2)
    else:
        rows, stride, n_j = ps, 1, _dispatch.cdiv(s, ps)

    t = lengths.astype(jnp.int32)[:, None]                   # (slots, 1)
    j = jnp.arange(n_j, dtype=jnp.int32)[None, :]
    ent = t // ps + j                                        # (slots, n_j)
    page0 = ent * ps                         # position of the page's row 0
    # page row of the source block's row 0 (negative: it starts in the
    # page before)
    shift = t + j * (stride * ps) - page0
    first = t if start is None else jnp.maximum(
        t, jnp.asarray(start, jnp.int32).reshape(-1, 1))
    last = t + s if stop is None else jnp.minimum(
        t + s, jnp.asarray(stop, jnp.int32).reshape(-1, 1))
    lo = jnp.clip(first - page0, 0, ps)
    hi = jnp.clip(last - page0, 0, ps)
    has = jnp.logical_and(
        jnp.maximum(lo, shift) < jnp.minimum(hi, shift + rows),
        ent < max_pages)
    phys = jnp.where(has, jnp.take_along_axis(
        block_tables.astype(jnp.int32), jnp.clip(ent, 0, max_pages - 1),
        axis=1), 0)
    hi = jnp.where(has, hi, 0)           # (slots, n_j), like shift and lo

    page_specs = [pl.BlockSpec(
        (1, heads, ps, p.shape[3]),
        lambda b, j, phys, *_: (phys[b, j], 0, 0, 0)) for p in pools]
    src_specs = [pl.BlockSpec(
        (1, heads, rows, p.shape[3]),
        lambda b, j, *_: (b, 0, j * stride, 0)) for p in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(slots, n_j),
        in_specs=page_specs + src_specs,
        out_specs=page_specs,
    )
    out = _dispatch.pallas_call(
        functools.partial(_write_kernel, n=len(pools), rows=rows),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operand 4 + i (after the four scalar tables) is pool i
        input_output_aliases={4 + i: i for i in range(len(pools))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        kernel="paged_write",
        interpret=interpret,
    )(phys, shift, lo, hi, *pools, *packed)
    return list(out)

