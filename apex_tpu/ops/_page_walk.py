"""The page walk of the paged decode kernels: a work list of LIVE blocks.

``ops/paged_attention.py`` and ``ops/paged_latent_attention.py`` attend a
slot's context a BLOCK of consecutive block-table entries at a time
(:func:`_tile`: as many pages as hold 128 tokens). A table is sized by the
longest context the model admits (2048 entries of 16 tokens at 32,768
positions) and a sequence fills a fraction of it, so a grid over every
block of every table spends its time on steps that hold nothing: a dead
step fetches nothing and does nothing, but the scalar core still evaluates
every index map for it (PERF.md, PR 38: 0.4-0.7 us a step, five steps in
six dead).

This module writes the walk once, for both kernels. :func:`page_walk`
builds, on the device and inside the caller's jit, the list of the blocks
that hold a live page — slot-major, ascending in the block — and the two
kernels' grids end in ONE sequential axis over that list whose bound
``n_work`` is traced (Mosaic takes a dynamic grid bound beside
``PrefetchScalarGridSpec``). Work item ``w`` names its slot
(``slot_of[w]``), its block (``block_of[w]``) and the ``pages`` physical
pages of that block (``phys[w * pages + i]``), each table entry clamped
into its slot's live pages first: a dead entry inside a live block — past
the sequence end, below the sliding-window band, past the table where
``max_pages`` is no multiple of ``pages`` — repeats a live one, so what
the table holds there is never read and the position band in the kernel
body masks what the clamp repeats. An idle slot (length 0) keeps one item,
so that it still writes its zeros; its body is skipped.

The static length of the list is the worst case, ``batch * n_blocks``
items, so the resolved table is the size it always was; the slot and the
block of an item add two words an item (128 KiB of SMEM at 64 slots of
256 blocks beside the table's 512 KiB; ``tests/test_aot_mosaic.py`` holds
the sum under what the v5e compiles).

Resolving everything outside the kernel keeps an index map to one or two
SMEM loads: the scalar core evaluates ``2 * pages + 2`` of them every grid
step, and at this tile that walk, not the DMA or the dots, is most of a
live step.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch

#: context tokens one grid step attends: one 128-lane tile of scores
_STEP_TOKENS = 128
#: VMEM the K and V page buffers of one grid step may take — both
#: tensors, double-buffered by the pipeline, tile padding included (a
#: page narrower than 128 lanes pads to them: a pool that could not pack
#: its heads, ``serving/kv_pool.heads_per_row``; a packed row of two
#: 64-wide heads fills its lanes). Half of Mosaic's 16 MiB scoped stack;
#: the rest holds q, the (m, l, acc) carry and the step's f32 scores
_KV_VMEM_BUDGET = 8 * 1024 * 1024


@functools.cache
def _tile(kv: int, page_size: int, d: int, dtype, max_pages: int):
    """``(pages, heads)`` of one grid step, from the shapes alone
    (``kv`` and ``d`` are the POOL's: its rows and their lanes, whatever
    heads a row packs): as many consecutive table entries as fill
    :data:`_STEP_TOKENS` (never
    more than the table has) for all ``kv`` heads; where that overflows
    :data:`_KV_VMEM_BUDGET` the page block halves first, then the heads
    split into the largest divisor of ``kv`` that fits."""
    item = jnp.dtype(dtype).itemsize
    page_vmem = (4 * _dispatch.round_up(page_size, 32 // item)
                 * _dispatch.round_up(d, 128) * item)   # per kv head
    pages = max(1, min(max_pages, _STEP_TOKENS // page_size))
    while pages > 1 and pages * kv * page_vmem > _KV_VMEM_BUDGET:
        pages //= 2
    heads = next(h for h in range(kv, 0, -1)
                 if kv % h == 0 and (h == 1 or pages * h * page_vmem
                                     <= _KV_VMEM_BUDGET))
    return pages, heads


def _live_pages(length, page_size: int, s_q: int, window, maximum=max):
    """``(first, last)`` table entries of a slot that hold a position
    some query of the block attends (``first == last == 0`` for an empty
    slot). Pure arithmetic: the walk evaluates it on the traced
    lengths (``maximum=jnp.maximum``), the serving host on ints."""
    last = maximum(_dispatch.cdiv(length, page_size) - 1, 0)
    if window is None:
        return 0, last
    # the earliest query sits at length - s_q and attends down to
    # length - s_q - window + 1; pages wholly below that are dead for
    # every query of the block and every later step
    return maximum(length - s_q - window + 1, 0) // page_size, last


def pages_fetched(length: int, *, kv_heads: int, page_size: int,
                  head_dim: int, dtype, max_pages: int, s_q: int = 1,
                  window: Optional[int] = None) -> int:
    """Pages of K (and as many of V) one call DMAs for a slot of
    ``length`` positions: the kernel walks the blocks that hold a live
    page, one grid step a block, so every such block counts whole (feeds
    ``serving.kv_bytes_fetched``; per kv-head block the same count of
    narrower pages). ``kv_heads`` and ``head_dim`` are the pool's own
    axes 1 and 3 (rows and lanes), so that this tiles as the call
    does."""
    pages, _ = _tile(kv_heads, page_size, head_dim, dtype, max_pages)
    first, last = _live_pages(length, page_size, s_q, window)
    return (last // pages - first // pages + 1) * pages


def _index_map(fn):
    """An index map over a walk's grid ``(*outer, w)``: Pallas hands it
    the grid indices and then the prefetch refs; ``fn(w, refs, *outer)``."""
    n = PageWalk.num_prefetch
    return lambda *a: fn(a[-n - 1], a[-n:], *a[:-n - 1])


@dataclasses.dataclass(frozen=True)
class PageWalk:
    """The work list of one call (:func:`page_walk`) and the grid over it.

    ``prefetch`` are the scalar-prefetch operands, in the order a kernel
    body receives their refs (:meth:`item` reads them): ``phys``
    (``n_items * pages``), ``slot_of`` and ``block_of`` (``n_items``),
    ``starts`` (``batch + 1`` running offsets: slot ``b`` owns items
    ``[starts[b], starts[b + 1])``) and ``lengths`` (``batch``).
    ``n_work = starts[batch]`` is the traced bound of the walk axis, the
    LAST grid axis; ``outer`` axes before it are static and parallel."""

    pages: int
    prefetch: tuple
    n_work: jax.Array

    #: refs a kernel body receives before its operands
    num_prefetch = 5

    @property
    def phys(self):
        """``(n_items, pages)`` physical page of every entry of an item."""
        return self.prefetch[0].reshape(-1, self.pages)

    def slot_spec(self, block_shape, index):
        """An operand blocked by SLOT (the queries, the output):
        ``index(slot, *outer)`` is its block index."""
        return pl.BlockSpec(block_shape, _index_map(
            lambda w, refs, *outer: index(refs[1][w], *outer)))

    def item_spec(self, block_shape, index):
        """An operand blocked by WORK ITEM (what the wrapper gathered
        through :attr:`phys`): ``index(w, *outer)`` is its block index."""
        return pl.BlockSpec(block_shape, _index_map(
            lambda w, refs, *outer: index(w, *outer)))

    def page_spec(self, i: int, block_shape, index):
        """Page ``i`` of the item's block, out of the pool:
        ``index(page, *outer)`` is its block index. One SMEM load."""
        pages = self.pages
        return pl.BlockSpec(block_shape, _index_map(
            lambda w, refs, *outer: index(refs[0][w * pages + i], *outer)))

    def grid_spec(self, outer, *, in_specs, out_specs, scratch_shapes):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=self.num_prefetch,
            grid=(*outer, self.n_work),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes)

    @staticmethod
    def compiler_params(outer):
        return pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(outer) + ("arbitrary",))

    @classmethod
    def item(cls, refs, axis: int):
        """Inside a kernel body, from ALL its refs: ``(block, length,
        first, last)`` of this grid step's work item — its block of its
        slot's table (positions ``[block * pages * page_size, ...)``), the
        slot's length, and whether it is the first / the last item of its
        slot (where the online-softmax carry starts and where it is
        written out) — and the refs after the walk's own (the body's
        operands, outputs and scratch). ``axis`` is the walk axis (the
        grid's last)."""
        _, slot_ref, block_ref, start_ref, len_ref = refs[:cls.num_prefetch]
        w = pl.program_id(axis)
        slot = slot_ref[w]
        return (block_ref[w], len_ref[slot], w == start_ref[slot],
                w == start_ref[slot + 1] - 1), refs[cls.num_prefetch:]


def page_walk(block_tables, lengths, *, page_size: int, pages: int,
              s_q: int = 1, window: Optional[int] = None) -> PageWalk:
    """The live blocks of ``block_tables`` (``(batch, max_pages)``) under
    ``lengths``, ``pages`` table entries a block, for a block of ``s_q``
    queries a slot and an optional sliding ``window``.

    Finding an item's slot and carrying the slot's values to it compare
    every item with every slot: ``batch * n_blocks * batch`` work, QUADRATIC
    in the slot count. Timed on the v5e at the cells' shapes only (PERF.md,
    PR 38): 0.097 ms a decode step at 64 slots of 2048 entries (1M
    compares, 5M selects), 0.013 ms at 16 of 64. Four times the slots
    would pay sixteen times that; past a few hundred slots a
    ``searchsorted`` over ``ends`` and gathers are the cheaper form."""
    b, max_pages = block_tables.shape
    n_blocks = _dispatch.cdiv(max_pages, pages)
    tables = block_tables.astype(jnp.int32)
    ln = lengths.astype(jnp.int32)
    first, last = _live_pages(ln, page_size, s_q, window, jnp.maximum)
    last = jnp.minimum(last, max_pages - 1)
    first = jnp.minimum(jnp.asarray(first, jnp.int32), last)
    ends = jnp.cumsum(last // pages - first // pages + 1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    w = jnp.arange(b * n_blocks, dtype=jnp.int32)
    # items past n_work repeat the last slot's last block: never run,
    # always in range
    slot_of = jnp.sum(w[:, None] >= ends[None, :-1], axis=1,
                      dtype=jnp.int32)
    # what an item needs of its slot — the live entries' bounds, the
    # slot's first item, the pages the clamp repeats — as ONE masked sum
    # over the slots: five gathers of 16k indices cost the chip twenty
    # times as much (PERF.md, PR 38)
    of_slot = jnp.stack([
        first, last, starts[:-1],
        jnp.take_along_axis(tables, first[:, None], axis=1)[:, 0],
        jnp.take_along_axis(tables, last[:, None], axis=1)[:, 0]], axis=1)
    mine = slot_of[:, None] == jnp.arange(b, dtype=jnp.int32)
    first, last, start, first_page, last_page = jnp.sum(
        jnp.where(mine[..., None], of_slot, 0), axis=1).T[..., None]
    block_of = jnp.minimum(first // pages + w[:, None] - start,
                           last // pages)
    # the block's entries as the table holds them, a ROW of ``pages`` an
    # item (a gather of rows is a sixteenth of a gather of their
    # entries), then the clamp as a select
    rows = jnp.pad(tables, ((0, 0), (0, n_blocks * pages - max_pages)))
    rows = jnp.take(rows.reshape(b * n_blocks, pages),
                    slot_of * n_blocks + block_of[:, 0], axis=0)
    entry = block_of * pages + jnp.arange(pages, dtype=jnp.int32)
    phys = jnp.where(entry < first, first_page,
                     jnp.where(entry > last, last_page, rows))
    return PageWalk(pages, (phys.reshape(-1), slot_of, block_of[:, 0],
                            starts, ln), ends[-1])
