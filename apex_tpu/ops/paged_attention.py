"""Pallas paged-attention decode kernel (vLLM-style, Kwon et al. 2023).

Serving keeps each sequence's KV cache in fixed-size PAGES of a shared
static pool (``apex_tpu/serving/kv_pool.py``) instead of one contiguous
``(batch, kv, max_len, d)`` buffer per request batch: a sequence owns
``ceil(len/page_size)`` pages named by its int32 block table, so HBM is
allocated by actual length, freed pages are reusable the moment a request
retires, and admission never reshapes anything.

This kernel computes GQA attention for a small static block of ``s``
decode queries per slot (``s=1`` is plain decode; ``s=k`` verifies a
speculative draft chunk in one pass; ``s``-sized chunks carry interleaved
prefill) directly against the page pool. The ``s`` queries of a slot
occupy positions ``lengths[b] - s + i`` (``i`` in ``0..s-1``), so the
causal/window mask is a per-query-position band.

The tile: ONE GRID STEP SERVES ALL KV HEADS OF ONE SLOT OVER A BLOCK OF
CONSECUTIVE PAGES, ``pages`` and ``heads`` derived from the shapes alone
(``_page_walk._tile``: as many pages as hold 128 tokens for every kv
head, cut down only where the K and V buffers would pass a VMEM budget).
The step's K and V are ``pages`` operands a tensor, each one whole page
``(1, heads, page_size, d)`` of the pool as it lies in HBM — row-major,
a page's heads contiguous, so a page is fetched whole with no copy in
front of the call. What keeps that true is the WRITE: every program
that writes the pool goes through ``ops.paged_write``, which leaves it
row-major (a scatter over the head axis made XLA carry the pool with a
token's heads contiguous and re-lay every layer's whole pool before
every call of this kernel; ``tests/test_aot_mosaic.py`` pins the
compiled decode chunk). At GPT-2 large (16 slots, 20 heads of 64) a step
moves 16 pages of 40 KB a tensor where a one-page one-head tile took
160 steps of 2 KB for them and spent its time on the steps, never the
bytes (PERF.md, PR 28).

The walk: THE GRID IS ``(kv // heads, n_work)``, ITS LAST AXIS ONE STEP
FOR EVERY BLOCK THAT HOLDS A LIVE PAGE, slot after slot, and ``n_work``
is traced (``ops/_page_walk.py``, shared with the latent kernel; PERF.md,
PR 38). The wrapper builds that work list once per call from the block
tables and the lengths — for item ``w`` its slot, its block and the
block's ``pages`` physical pages, every table entry clamped into its
slot's live pages first — and hands it over as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``) that the index maps read: a page
operand's is one SMEM load (``phys[w * pages + i]``), the queries' and
the output's read the item's slot. A dead entry inside a live block —
past the sequence end, below the sliding-window band, past the table
where ``max_pages`` is no multiple of ``pages`` — repeats a live one:
what the table holds there is never read, and the position band in the
body masks whatever the clamp repeats. A block with no live page is no
item at all, so a table sized for 32,768 positions costs a 4k context
what a 4k table would (a grid over every block of every table paid the
scalar core's ``2*pages + 2`` index maps for each dead one: 0.4-0.7 us
a step, five steps in six). An idle slot keeps one item, whose body is
skipped, so that it still writes its zeros.

Online softmax ``(m, l, acc)`` carries across a slot's items exactly
like flash_attention's k-block axis (it starts at the slot's first item
and is written out at its last), shaped for the step's heads; fp32
scores and accumulation (same numerics contract). Scores are one batched
contraction over the head axis, ``(heads, s*rep, d) . (heads,
pages*page_size, d)``: on the MXU at every ``s*rep``.

Layout: the pool is ``(num_pages, kv_heads // pack, page_size, head_dim *
pack)`` — a page operand's minor two dims are the array's own
``(page_size, lanes)``, legal under Mosaic's block rule at every page
size that is a sublane multiple. GQA queries reshape to ``(b, kv, s*rep,
d)`` and contract against the UNexpanded kv-head pages, the same
no-repeat discipline as flash_attention and cached_attention.

``pack`` heads to a pool row (``serving/kv_pool.heads_per_row``: two
64-wide heads in 128 lanes, so that the pool's row-major layout is the
one the device holds it in between programs and nothing re-lays it where
a program begins or ends; docs/serving.md "Page-pool layout"). The
wrapper reads ``pack`` off the shapes (``pool lanes // q's head_dim``)
and the kernel body never learns of it: it runs over ``kv // pack`` rows
of ``d * pack`` lanes with ``rep * pack`` query rows a position. The
queries of a row's head ``p`` lie in lanes ``[p*d, (p+1)*d)`` and are
zero elsewhere (block-diagonal), so the one contraction over all 128
lanes gives each head its own scores exactly — the other heads' lanes
meet zeros, where a 64-wide page used to meet VMEM padding, and the MXU
pass is as wide as it was. The value product gives every query row all
of the row's lanes, and the wrapper keeps head ``p``'s rows' lanes
``[p*d, (p+1)*d)``.

Off-TPU the kernel runs through the Pallas interpreter
(``ops/_dispatch.interpret``), so CPU tests cover the real kernel code.

Tensor parallelism (``serving/tp.py``, docs/tp_serving.md): the kernel
is TP-native by shape, not by flag. Heads never interact — the batched
contraction's head axis is embarrassingly parallel — so inside ``shard_map``
with the pool sharded along its kv-head axis, each chip calls this
kernel on its LOCAL shard (its ``kv_heads/tp`` heads, ``pack`` to a
row: ``pack`` divides one chip's heads, so a row never straddles two)
with its local query heads and the REPLICATED block tables / lengths:
the same ``h % kv == 0`` GQA contract holds locally (both counts divide
by ``tp`` — GQA groups partition whole), no collective appears here,
and the single TP all-reduce happens after the attention out-projection
(the Megatron row-parallel layer), never inside the kernel.
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


def _paged_kernel(*refs, scale, page_size, pages, s_q, rep, window=None,
                  quantized=False):
    (j, seq_len, first, last), (q_ref, *rest) = PageWalk.item(refs, axis=1)
    k_refs, v_refs, rest = rest[:pages], rest[pages:2 * pages], \
        rest[2 * pages:]
    if quantized:
        # two extra operands: the per-token dequant scales of this
        # item's pages, gathered through the same clamped table entries
        # as the page tiles (docs/serving.md "Quantized KV pages")
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    # the grid's last axis walks the live blocks of every slot in turn
    # (ops/_page_walk.py): block j of the slot holds absolute positions
    # [j*block, (j+1)*block) and at least one of them is live. With a
    # window the walk starts at the block of the EARLIEST query's band
    # floor (seq_len - s_q) - window + 1: what lies below is dead for
    # every query of the block and every later step (the band only moves
    # forward) — the serving engine drops such pages from the block
    # table entirely (kv_pool.drop_slot_pages), and the walk never names
    # what a dropped entry now points at (the null page)
    block = pages * page_size

    @pl.when(first)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # an idle slot's one item names whatever its table holds: not read
    @pl.when(seq_len > 0)
    def _body():
        q = q_ref[0]                                  # (heads, s_q*rep, d)
        # the step's pages side by side: (heads, block, d). Quantized
        # pages widen one by one first, so the concatenation is of whole
        # tiles of q's dtype. int8 (<=127) and e4m3 (<=448) values are
        # exact in bf16/f32, and the f32 pool never exists: dequant is a
        # fold of the scales, the k-scale into the scores (q.k * sk ==
        # q.(k*sk)), the v-scale into p before the value dot
        k = jnp.concatenate(
            [r[0].astype(q.dtype) if quantized else r[0] for r in k_refs],
            axis=1)
        s = jnp.einsum("hqd,htd->hqt", q, k,
                       preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * ks_ref[0]                         # (heads, 1, block)
        pos = lax.broadcasted_iota(jnp.int32, s.shape, 2) + j * block
        # rows are position-major: row r is query position seq_len - s_q
        # + r // rep (each query's rep GQA heads are adjacent rows)
        qpos = (seq_len - s_q
                + lax.broadcasted_iota(jnp.int32, s.shape, 1) // rep)
        # also masks what a clamped entry repeats: a position past the
        # sequence end is past every query
        live = pos <= qpos
        if window is not None:
            # positions inside a live block but below a query's band
            # floor mask out — exactly cached_attention_rolling's band,
            # per query position
            live = jnp.logical_and(live, pos > qpos - window)
        s = jnp.where(live, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        m_ref[...] = m_new
        if quantized:
            p_in = p * vs_ref[0]
            v = jnp.concatenate([r[0].astype(jnp.float32) for r in v_refs],
                                axis=1)
        else:
            v = jnp.concatenate([r[0] for r in v_refs], axis=1)
            p_in = p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hqt,htd->hqd", p_in, v, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        l = l_ref[...]
        # a zero-length slot (idle serving slot) outputs exactly 0
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _validate(q, k_pages, v_pages, block_tables, lengths, window=None,
              k_scales=None, v_scales=None):
    if window is not None and (not isinstance(window, int) or window < 1):
        raise ValueError(f"window must be a static positive int, got "
                         f"{window!r}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together "
                         "(a quantized pool quantizes both tensors)")
    if k_scales is not None:
        want = k_pages.shape[:2]
        for name, sc in (("k_scales", k_scales), ("v_scales", v_scales)):
            if sc.shape != want:
                raise ValueError(
                    f"{name} must be (num_pages, kv_heads) = {want} "
                    f"per-page/per-kv-head scales, got {sc.shape}")
            if not jnp.issubdtype(sc.dtype, jnp.floating):
                raise ValueError(f"{name} must be float scales, got "
                                 f"{sc.dtype}")
    if q.ndim != 4:
        raise ValueError(f"q must be (batch, heads, s, d) decode-block "
                         f"queries, got {q.shape}")
    if k_pages.shape != v_pages.shape:
        raise ValueError(f"k_pages {k_pages.shape} != v_pages "
                         f"{v_pages.shape}")
    num_pages, kv_rows, page_size, lanes = k_pages.shape
    b, h, s_q, d = q.shape
    if not 1 <= s_q <= page_size:
        # the block's s queries live inside the last ceil(s/ps)+1 pages;
        # bounding s by the page size keeps the per-page band mask a
        # single iota comparison and the VMEM q tile small. Larger
        # chunks belong to the prefill path (flash attention), the same
        # split cached_attention_rolling documents for the rolling cache
        raise ValueError(
            f"paged attention takes query blocks of 1..page_size "
            f"({page_size}) positions per step, got s={s_q}; longer "
            f"chunks must use the contiguous prefill path")
    if lanes % d != 0:
        raise ValueError(f"head_dim mismatch: q {d} vs pages {lanes} "
                         f"(a pool row holds whole heads side by side)")
    pack = lanes // d
    if pack > 1 and k_scales is not None:
        raise ValueError(
            f"a quantized pool holds one head a row (its scales are per "
            f"(page, kv_head)), got rows of {pack} heads")
    if h % (kv_rows * pack) != 0:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads "
                         f"({kv_rows * pack})")
    if page_size % 8 != 0:
        raise ValueError(f"page_size must be a sublane multiple (8), got "
                         f"{page_size}")
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(f"block_tables must be (batch, max_pages), got "
                         f"{block_tables.shape} for batch {b}")
    if lengths.shape != (b,):
        raise ValueError(f"lengths must be ({b},), got {lengths.shape}")


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    k_scales=None, v_scales=None):
    """Decode-block GQA attention over a paged KV pool.

    Args:
      q: ``(batch, heads, s, head_dim)`` — this step's query block,
        ``s`` consecutive tokens per sequence slot (``1 <= s <=
        page_size``; ``s=1`` is plain decode, ``s=k`` verifies a
        speculative draft chunk, ``s``-sized chunks carry interleaved
        prefill). Query ``i`` sits at absolute position
        ``lengths[b] - s + i``.
      k_pages / v_pages: ``(num_pages, kv_heads // pack, page_size,
        head_dim * pack)`` shared page pool, ``pack`` heads side by side
        in a row (1 for a head of 128 values or more; read off the
        shapes as ``lanes // head_dim``; ``kv_heads`` divides ``heads``;
        GQA never expands). Inside a tensor-parallel ``shard_map`` region both
        counts are the LOCAL per-chip head shard (``serving/tp.py``) —
        the kernel is chip-count-blind.
      block_tables: int32 ``(batch, max_pages)``; entry ``[b, j]`` is the
        physical page holding slot ``b``'s positions
        ``[j*page_size, (j+1)*page_size)``. Entries past a sequence's
        allocation must hold a VALID page id (the pool reserves page 0 as
        a null page) — the kernel clamps them away and never reads them.
      lengths: int32 ``(batch,)`` — valid positions per slot INCLUDING
        all ``s`` current tokens (their K/V must already be written to
        the pool). Length 0 (idle slot) outputs exactly 0; a slot whose
        length is shorter than ``s`` zeroes the leading (pre-sequence)
        query rows.
      scale: softmax scale; default ``1/sqrt(head_dim)``.
      window: optional STATIC sliding-window band (Mistral-style): the
        query at position ``p_i = lengths[b] - s + i`` attends only
        positions ``(p_i - window, p_i]`` — the exact band
        ``cached_attention``/``cached_attention_rolling`` mask applied
        per query position, so a windowed model's paged decode is
        token-identical to its contiguous/rolling decode. Pages fully
        below every query's band are never fetched (and may be dropped
        from the block table entirely — the serving engine's
        O(window)-HBM trick, ``kv_pool.drop_slot_pages``).
      k_scales / v_scales: f32 ``(num_pages, kv_heads)`` per-page,
        per-kv-head symmetric dequant scales of a QUANTIZED pool
        (int8 / fp8 e4m3 pages, ``kv_pool.init_paged_cache(kv_dtype=)``)
        — ``true_k[p, h] = k_pages[p, h].astype(f32) * k_scales[p, h]``.
        Both or neither. They are gathered through the same clamped
        table entries as the page operands and folded into the score /
        value dots, so the dequantized pool is never materialized. Under
        TP they shard along the kv-head axis with the pages.

    Returns ``(batch, heads, s, head_dim)`` in ``q.dtype``.
    """
    _validate(q, k_pages, v_pages, block_tables, lengths, window,
              k_scales, v_scales)
    quantized = k_scales is not None
    # the kernel's kv, rep and d are the POOL's: its rows, the query
    # heads that read one row (``pack`` kv heads of ``rep`` each) and its
    # lanes. ``pack == 1`` is the kernel as it always was
    num_pages, kv, page_size, d = k_pages.shape
    b, h, s_q, head_dim = q.shape
    pack = d // head_dim
    rep = h // kv
    rows = s_q * rep
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)
    pages, heads = _tile(kv, page_size, d, k_pages.dtype, max_pages)

    # position-major row layout: row i*rep + r is query position i of
    # GQA group-member r, so the kernel recovers the position as
    # row // rep with the group's rows adjacent (one contraction per kv
    # head for all s*rep rows against the step's pages)
    qr = q.reshape(b, kv, rep, s_q, head_dim).transpose(0, 1, 3, 2, 4)
    if pack > 1:
        # group-member r = p*(rep/pack) + r' reads head p of the row:
        # its query goes to lanes [p*head_dim, (p+1)*head_dim), zeros to
        # the other heads' lanes
        own = jnp.eye(pack, dtype=jnp.bool_)[:, None, :, None]
        qr = jnp.where(
            own, qr.reshape(b, kv, s_q, pack, rep // pack, 1, head_dim),
            jnp.zeros((), q.dtype))
    qr = qr.reshape(b, kv, rows, d)
    # the live blocks of every slot, one grid step each, and the physical
    # pages of each resolved once per call (ops/_page_walk.py)
    walk = page_walk(block_tables, lengths, page_size=page_size,
                     pages=pages, s_q=s_q, window=window)
    outer = (kv // heads,)
    q_spec = walk.slot_spec((1, heads, rows, d),
                            lambda slot, g: (slot, g, 0, 0))
    in_specs = [q_spec] + [
        walk.page_spec(i, (1, heads, page_size, d),
                       lambda page, g: (page, g, 0, 0))
        for i in range(pages)] * 2
    operands = [qr] + [k_pages] * pages + [v_pages] * pages
    if quantized:
        # the (num_pages, kv) scales, gathered through the same clamped
        # entries and spread over each page's positions:
        # (n_items, kv, 1, block) f32, one (heads, 1, block) tile a step
        # that broadcasts over the rows
        def per_token(scales):
            sc = jnp.take(scales.astype(jnp.float32), walk.phys, axis=0)
            sc = jnp.repeat(sc.transpose(0, 2, 1), page_size, axis=2)
            return sc[:, :, None]

        in_specs += [walk.item_spec((1, heads, 1, pages * page_size),
                                    lambda w, g: (w, g, 0, 0))] * 2
        operands += [per_token(k_scales), per_token(v_scales)]
    grid_spec = walk.grid_spec(
        outer, in_specs=in_specs, out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((heads, rows, d), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
            pltpu.VMEM((heads, rows, 1), jnp.float32),
        ])
    out = _dispatch.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale),
                          page_size=page_size, pages=pages,
                          s_q=s_q, rep=rep, window=window,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, d), q.dtype),
        compiler_params=walk.compiler_params(outer),
        # a banded call carries a label of its own, so that a trace tells
        # a windowed layer's calls from a full layer's in one program
        kernel=("paged_attention" if window is None
                else "paged_window_attention"),
        interpret=_INTERPRET(),
    )(*walk.prefetch, *operands)
    if pack > 1:
        # every row came back with all of the pool row's lanes: head p's
        # rows keep their own
        out = out.reshape(b, kv, s_q, pack, rep // pack, pack, head_dim)
        out = jnp.stack([out[:, :, :, p, :, p] for p in range(pack)], axis=3)
    return (out.reshape(b, kv, s_q, rep, head_dim).transpose(0, 1, 3, 2, 4)
            .reshape(b, h, s_q, head_dim))


def paged_attention_reference(q, k_pages, v_pages, block_tables, lengths, *,
                              scale: Optional[float] = None,
                              window: Optional[int] = None,
                              k_scales=None, v_scales=None):
    """Pure-jnp ground truth: gather every table entry into a contiguous
    ``(b, kv, max_pages*page_size, d)`` view (dequantizing with the
    gathered per-page scales when given) and run dense masked GQA
    attention — O(batch * max_len) HBM, exactly what the kernel avoids."""
    _validate(q, k_pages, v_pages, block_tables, lengths, window,
              k_scales, v_scales)
    num_pages, kv, page_size, d = k_pages.shape
    if d != q.shape[3]:
        raise ValueError(
            f"the reference knows heads only: hand it the pool one head a "
            f"row (lanes {d} vs head_dim {q.shape[3]})")
    b, h, s_q = q.shape[0], q.shape[1], q.shape[2]
    rep = h // kv
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def contig(pages, scales=None):
        g = jnp.take(pages, block_tables, axis=0)      # (b, mp, kv, ps, d)
        g = g.astype(jnp.float32)
        if scales is not None:
            sc = jnp.take(scales, block_tables, axis=0)      # (b, mp, kv)
            g = g * sc.astype(jnp.float32)[..., None, None]
        return g.transpose(0, 2, 1, 3, 4).reshape(b, kv, max_pages * page_size, d)

    k = contig(k_pages, k_scales)
    v = contig(v_pages, v_scales)
    qf = q.reshape(b, kv, rep, s_q, d).astype(jnp.float32)
    s = jnp.einsum("bkrsd,bktd->bkrst", qf, k,
                   preferred_element_type=jnp.float32) * jnp.float32(scale)
    pos = jnp.arange(max_pages * page_size, dtype=jnp.int32)[
        None, None, None, None]                        # (1,1,1,1,T)
    # query i of the block sits at absolute position lengths[b] - s + i
    qpos = (lengths[:, None, None, None, None] - s_q
            + jnp.arange(s_q, dtype=jnp.int32)[None, None, None, :, None])
    mask = pos <= qpos
    if window is not None:
        mask = jnp.logical_and(mask, pos > qpos - window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)  # all-dead rows: softmax(-inf row) -> NaN
    ctx = jnp.einsum("bkrst,bktd->bkrsd", p, v,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, h, s_q, d).astype(q.dtype)
