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
prefill) directly against the page pool. The block table rides in as a
SCALAR-PREFETCH operand (``pltpu.PrefetchScalarGridSpec``) so the k/v
BlockSpec index maps resolve the physical page for grid step ``j`` —
``block_tables[b, j]`` — before the body runs: each (page_size, d) page
tile is DMA'd HBM->VMEM exactly once, and the gather never materializes a
contiguous copy of the sequence. Online softmax (m, l, acc) carries across
the sequential page axis exactly like flash_attention's k-block axis; fp32
scores and accumulation (same numerics contract). The ``s`` queries of a
slot occupy positions ``lengths[b] - s + i`` (``i`` in ``0..s-1``), so the
causal/window mask is a per-query-position band — the grid, the page
skip, and the softmax carry are untouched by the generalization.

Layout: the pool is ``(num_pages, kv_heads, page_size, head_dim)`` — the
page tile's minor two dims are then ``(page_size, head_dim)``, which
satisfies Mosaic's (sublane, lane)-or-full-dim block rule for
``page_size`` a sublane multiple and the usual head dims (64 = full minor
dim, 128 = lane multiple). GQA queries reshape to ``(b, kv, rep, d)`` and
contract against the UNexpanded kv-head pages (``rep`` = full dim), the
same no-repeat discipline as flash_attention and cached_attention.

Off-TPU the kernel runs through the Pallas interpreter
(``ops/_dispatch.interpret``), so CPU tests cover the real kernel code.

Tensor parallelism (``serving/tp.py``, docs/tp_serving.md): the kernel
is TP-native by shape, not by flag. Heads never interact — the grid's
``kv_head`` axis is embarrassingly parallel — so inside ``shard_map``
with the pool sharded along its kv-head axis, each chip calls this
kernel on its LOCAL ``(num_pages, kv_heads/tp, page_size, d)`` shard
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
from apex_tpu.ops.flash_attention import DEFAULT_MASK_VALUE

_INTERPRET = _dispatch.interpret


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest, scale,
                  page_size, max_pages, s_q, rep, window=None,
                  quantized=False):
    if quantized:
        # two extra scalar operands: this page's per-kv-head symmetric
        # dequant scales, prefetched by the same bt[b, j] index map as
        # the page tiles (docs/serving.md "Quantized KV pages")
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = len_ref[b]

    # page j holds absolute positions [j*ps, (j+1)*ps): dead pages (at or
    # past the sequence end) skip both their FLOPs and their accumulator
    # update; their DMA fetched whatever page id the table holds (0 = the
    # reserved null page) — never read, so never wrong
    page_live = j * page_size < seq_len
    if window is not None:
        # sliding-window band: query i of the block sits at position
        # seq_len - s_q + i and attends (pos_i - window, pos_i]. A page
        # whose LAST position is at or below the EARLIEST query's band
        # floor (seq_len - s_q) - window is dead for every query in the
        # block and every later step (the band only moves forward) — the
        # serving engine drops such pages from the block table entirely
        # (kv_pool.drop_slot_pages), and this gate skips whatever the
        # dropped entry now points at (the null page)
        page_live = jnp.logical_and(
            page_live, (j + 1) * page_size + window + s_q - 1 > seq_len)

    @pl.when(page_live)
    def _body():
        q = q_ref[0, 0]                                   # (s_q*rep, d)
        k = k_ref[0, 0]                                   # (ps, d)
        if quantized:
            # dequant is a SCALAR fold, never a widened tensor: the
            # page's k-scale rides the score multiply (q.k * sk == q.
            # (k*sk)), the v-scale rides p before the value dot — the
            # narrow page is cast in VMEM, the f32 pool never exists.
            # int8 (<=127) and e4m3 (<=448) values are exact in bf16/f32
            k = k.astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (s_q*rep, ps)
        if quantized:
            # keep the scale a (1, 1) array and broadcast — extracting a
            # true scalar from a VMEM tile is an unsupported shape cast
            s = s * ks_ref[0, 0]
        pos = lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page_size
        # rows are position-major: row r is query position seq_len - s_q
        # + r // rep (each query's rep GQA heads are adjacent rows)
        qpos = (seq_len - s_q
                + lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep)
        live = pos <= qpos
        if window is not None:
            # positions inside a live page but below a query's band
            # floor mask out — exactly cached_attention_rolling's band,
            # per query position
            live = jnp.logical_and(live, pos > qpos - window)
        s = jnp.where(live, s, DEFAULT_MASK_VALUE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0]
        if quantized:
            p_in, v_in = p * vs_ref[0, 0], v.astype(jnp.float32)
        else:
            p_in, v_in = p.astype(v.dtype), v
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p_in, v_in, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == max_pages - 1)
    def _finish():
        l = l_ref[...]
        # a zero-length slot (idle serving slot) outputs exactly 0
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


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
    num_pages, kv, page_size, d = k_pages.shape
    b, h, s_q, qd = q.shape
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
    if qd != d:
        raise ValueError(f"head_dim mismatch: q {qd} vs pages {d}")
    if h % kv != 0:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads "
                         f"({kv})")
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
      k_pages / v_pages: ``(num_pages, kv_heads, page_size, head_dim)``
        shared page pool (``kv_heads`` divides ``heads``; GQA never
        expands). Inside a tensor-parallel ``shard_map`` region both
        counts are the LOCAL per-chip head shard (``serving/tp.py``) —
        the kernel is chip-count-blind.
      block_tables: int32 ``(batch, max_pages)``; entry ``[b, j]`` is the
        physical page holding slot ``b``'s positions
        ``[j*page_size, (j+1)*page_size)``. Entries past a sequence's
        allocation must hold a VALID page id (the pool reserves page 0 as
        a null page) — they are fetched by the pipeline but never read.
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
        below every query's band skip their FLOPs (and may be dropped
        from the block table entirely — the serving engine's
        O(window)-HBM trick, ``kv_pool.drop_slot_pages``).
      k_scales / v_scales: f32 ``(num_pages, kv_heads)`` per-page,
        per-kv-head symmetric dequant scales of a QUANTIZED pool
        (int8 / fp8 e4m3 pages, ``kv_pool.init_paged_cache(kv_dtype=)``)
        — ``true_k[p, h] = k_pages[p, h].astype(f32) * k_scales[p, h]``.
        Both or neither. The kernel prefetches each page's two scalars
        through the same ``bt[b, j]`` index map as the page tiles and
        folds them into the score / value dots, so the dequantized pool
        is never materialized. Under TP they shard along the kv-head
        axis with the pages.

    Returns ``(batch, heads, s, head_dim)`` in ``q.dtype``.
    """
    _validate(q, k_pages, v_pages, block_tables, lengths, window,
              k_scales, v_scales)
    quantized = k_scales is not None
    num_pages, kv, page_size, d = k_pages.shape
    b, h, s_q = q.shape[0], q.shape[1], q.shape[2]
    rep = h // kv
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    # position-major row layout: row i*rep + r is query position i of
    # GQA group-member r, so the kernel recovers the position as
    # row // rep with the group's rows adjacent (one contraction for
    # all s*rep rows against the page tile — same dot shape as s=1,
    # just taller)
    qr = (q.reshape(b, kv, rep, s_q, d).transpose(0, 1, 3, 2, 4)
          .reshape(b, kv, s_q * rep, d))
    bt = block_tables.astype(jnp.int32)
    ln = lengths.astype(jnp.int32)

    in_specs = [
        pl.BlockSpec((1, 1, s_q * rep, d),
                     lambda b, h, j, bt, ln: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d),
                     lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
    ]
    operands = [bt, ln, qr, k_pages, v_pages]
    if quantized:
        # one scalar scale block per (page, kv_head) grid step, resolved
        # by the SAME scalar-prefetched bt[b, j] map as the page tiles.
        # The (pages, kv) array is viewed as (pages, kv, 1, 1) so the
        # block's last two dims EQUAL the array's — the only legal shape
        # for a sub-(8, 128) VMEM block under Mosaic's tiling rules
        # (same trick as the upstream quantized paged-attention kernels)
        in_specs += [
            pl.BlockSpec((1, 1, 1, 1),
                         lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1),
                         lambda b, h, j, bt, ln: (bt[b, j], h, 0, 0)),
        ]
        operands += [k_scales.astype(jnp.float32)[:, :, None, None],
                     v_scales.astype(jnp.float32)[:, :, None, None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, s_q * rep, d),
                               lambda b, h, j, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((s_q * rep, d), jnp.float32),
            pltpu.VMEM((s_q * rep, 1), jnp.float32),
            pltpu.VMEM((s_q * rep, 1), jnp.float32),
        ],
    )
    out = _dispatch.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale),
                          page_size=page_size, max_pages=max_pages,
                          s_q=s_q, rep=rep, window=window,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, s_q * rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        kernel="paged_attention",
        interpret=_INTERPRET(),
    )(*operands)
    return (out.reshape(b, kv, s_q, rep, d).transpose(0, 1, 3, 2, 4)
            .reshape(b, h, s_q, d))


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
