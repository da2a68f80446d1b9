"""Pallas decode attention over LATENT pages (MLA, absorbed form).

A latent pool (``serving/kv_pool.py``, the cache-layout seam) holds ONE
entry per token for all heads: ``[c_kv (rank); k_rope; zero padding]``, a
row of ``stored`` lanes. With the up-projections absorbed into the query
and the output (``models/glm4_moe_lite.py``), decode attention is
multi-query attention whose keys are the whole entry and whose values are
its first ``value_width`` columns:

    s[h, t] = q[h] . entry[t] * scale        q = [q_nope W_uk^T; q_rope; 0]
    o[h]    = softmax_t(s[h]) @ entry[:, :value_width]

so every page is read ONCE and used twice. The tile and the walk are
``ops/paged_attention.py``'s, written once in ``ops/_page_walk.py``: one
grid step serves ALL query heads of a slot over a block of consecutive
pages (``_tile`` with one kv head of ``stored`` lanes); the grid is ONE
axis over the work list of blocks that hold a live page, slot after slot,
its bound traced; the page operands' index maps read the list's
scalar-prefetched physical pages, every dead entry inside a live block
clamped onto a live one; online softmax carries across a slot's items in
fp32. This module holds the kernel body, its operands' shapes and the
reference. The row axis is position-major (row ``i * heads + h`` is query
position ``i`` of head ``h``), so a block of ``s`` queries per slot —
chunked prefill, a speculative verify — is the same kernel.

The padding lanes are zeros in the pool and in the query, so the score
contraction runs over whole 128-lane tiles; ``value_width`` is a lane
multiple in every real configuration (512), so the value slice is whole
tiles too. Off-TPU the kernel runs through the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch
from apex_tpu.ops._page_walk import PageWalk, _tile, page_walk
from apex_tpu.ops.flash_attention import DEFAULT_MASK_VALUE

_INTERPRET = _dispatch.interpret


def _latent_kernel(*refs, scale, page_size, pages, s_q, heads, value_width):
    # the grid walks the live blocks of every slot in turn: block j of
    # the slot holds positions [j*block, (j+1)*block)
    (j, seq_len, first, last), (q_ref, *rest) = PageWalk.item(refs, axis=0)
    page_refs, (o_ref, acc_ref, m_ref, l_ref) = rest[:pages], rest[pages:]
    block = pages * page_size

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # an idle slot's one item names whatever its table holds: not read
    @pl.when(seq_len > 0)
    def _body():
        q = q_ref[0]                                     # (rows, stored)
        entries = jnp.concatenate([r[0, 0] for r in page_refs], axis=0)
        s = lax.dot_general(q, entries, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        pos = lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block
        qpos = (seq_len - s_q
                + lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads)
        # also masks what a clamped entry repeats: a position past the
        # sequence end is past every query
        live = pos <= qpos
        s = jnp.where(live, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        values = entries[:, :value_width]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        l = l_ref[...]
        # a zero-length slot (idle serving slot) outputs exactly 0
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def _validate(q, latent_pages, block_tables, lengths, value_width):
    if q.ndim != 4:
        raise ValueError(f"q must be (batch, heads, s, stored) absorbed "
                         f"queries, got {q.shape}")
    if latent_pages.ndim != 4 or latent_pages.shape[1] != 1:
        raise ValueError(f"latent_pages must be (num_pages, 1, page_size, "
                         f"stored), got {latent_pages.shape}")
    _, _, page_size, stored = latent_pages.shape
    b, _, s_q, qd = q.shape
    if qd != stored:
        raise ValueError(f"q width {qd} != the pool's stored width "
                         f"{stored}: pad the absorbed query with zeros")
    if not 1 <= value_width <= stored:
        raise ValueError(f"value_width {value_width} outside the entry "
                         f"(1..{stored})")
    if not 1 <= s_q <= page_size:
        raise ValueError(
            f"paged attention takes query blocks of 1..page_size "
            f"({page_size}) positions per step, got s={s_q}")
    if page_size % 8 != 0:
        raise ValueError(f"page_size must be a sublane multiple (8), got "
                         f"{page_size}")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(f"block_tables must be (batch, max_pages), got "
                         f"{block_tables.shape} for batch {b}")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be ({b},), got {lengths.shape}")


def paged_latent_attention(q, latent_pages, block_tables, lengths, *,
                           value_width: int, scale: Optional[float] = None):
    """Decode-block multi-query attention over a paged latent pool.

    Args:
      q: ``(batch, heads, s, stored)`` absorbed queries, zero in the pool's
        padding lanes; query ``i`` of a slot sits at absolute position
        ``lengths[b] - s + i``.
      latent_pages: ``(num_pages, 1, page_size, stored)`` shared pool.
      block_tables / lengths: as ``ops.paged_attention`` (``lengths``
        INCLUDES the ``s`` current tokens, already written; length 0
        outputs exactly 0).
      value_width: leading columns of an entry that are its values.
      scale: softmax scale (the model's: ``1/sqrt(qk_nope + qk_rope)``, the
        EXPANDED key width — not a function of ``stored``); default
        ``1/sqrt(stored)``.

    Returns ``(batch, heads, s, value_width)`` in ``q.dtype``.
    """
    _validate(q, latent_pages, block_tables, lengths, value_width)
    _, _, page_size, stored = latent_pages.shape
    b, heads, s_q, _ = q.shape
    rows = s_q * heads
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (stored ** 0.5)
    pages, _ = _tile(1, page_size, stored, latent_pages.dtype, max_pages)

    qr = q.transpose(0, 2, 1, 3).reshape(b, rows, stored)   # position-major
    walk = page_walk(block_tables, lengths, page_size=page_size,
                     pages=pages, s_q=s_q)
    grid_spec = walk.grid_spec(
        (),
        in_specs=[walk.slot_spec((1, rows, stored),
                                 lambda slot: (slot, 0, 0))] + [
            walk.page_spec(i, (1, 1, page_size, stored),
                           lambda page: (page, 0, 0, 0))
            for i in range(pages)],
        out_specs=walk.slot_spec((1, rows, value_width),
                                 lambda slot: (slot, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, value_width), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ])
    out = _dispatch.pallas_call(
        functools.partial(_latent_kernel, scale=float(scale),
                          page_size=page_size, pages=pages, s_q=s_q,
                          heads=heads, value_width=value_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, value_width), q.dtype),
        compiler_params=walk.compiler_params(()),
        kernel="paged_latent_attention",
        interpret=_INTERPRET(),
    )(*walk.prefetch, qr, *([latent_pages] * pages))
    return out.reshape(b, s_q, heads, value_width).transpose(0, 2, 1, 3)


def paged_latent_attention_reference(q, latent_pages, block_tables, lengths,
                                     *, value_width: int,
                                     scale: Optional[float] = None):
    """Pure-jnp twin: gather every table entry into a contiguous
    ``(b, max_pages*page_size, stored)`` view and run dense masked
    multi-query attention over it."""
    _validate(q, latent_pages, block_tables, lengths, value_width)
    _, _, page_size, stored = latent_pages.shape
    b, _, s_q, _ = q.shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (stored ** 0.5)
    ent = jnp.take(latent_pages[:, 0], block_tables, axis=0).astype(
        jnp.float32).reshape(b, max_pages * page_size, stored)
    s = jnp.einsum("bhsd,btd->bhst", q.astype(jnp.float32), ent,
                   preferred_element_type=jnp.float32) * jnp.float32(scale)
    pos = jnp.arange(max_pages * page_size, dtype=jnp.int32)[None, None, None]
    qpos = (lengths[:, None, None, None] - s_q
            + jnp.arange(s_q, dtype=jnp.int32)[None, None, :, None])
    mask = pos <= qpos
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    p = jnp.where(mask, p, 0.0)     # all-dead rows: softmax(-inf row) -> NaN
    out = jnp.einsum("bhst,btv->bhsv", p, ent[..., :value_width],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
