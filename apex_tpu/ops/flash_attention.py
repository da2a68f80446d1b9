"""Pallas flash attention (fwd + bwd) — the TPU answer to the reference's
attention kernel zoo.

One kernel family subsumes four reference CUDA extensions (SURVEY.md §2.2):
- ``fast_multihead_attn`` (apex/contrib/csrc/multihead_attn/*.cu — fused QKV
  GEMM + masked softmax + dropout + AV GEMM, self & enc-dec variants)
- ``fmhalib`` (apex/contrib/csrc/fmha/ — flash-style MHA, fp16, seqlen <= 512,
  varlen via cu_seqlens; here varlen = segment_ids and there is NO seqlen cap)
- ``scaled_masked_softmax_cuda`` / ``scaled_upper_triang_masked_softmax_cuda``
  (csrc/megatron/ — the softmax is folded into the attention kernel; a
  standalone fused softmax lives in apex_tpu/ops/scaled_softmax.py)
- attention dropout (``philox.h``) — threaded TPU PRNG seeded per block so the
  backward regenerates the identical keep-mask without storing it.

Algorithm: FlashAttention-2 style. Forward tiles (Bq x Bk) with online
softmax carrying (m, l, acc) in VMEM scratch across the sequential k-block
grid axis; saves only O and LSE. Backward recomputes P from (q, k, LSE) and
accumulates dq over k-blocks and (dk, dv) over q-blocks in two kernels.
All matmuls hit the MXU in the input dtype with fp32 accumulation; softmax
math is fp32 on the VPU.

Layout: q [B, H, Sq, D], k/v [B, Hkv, Sk, D] where Hkv divides H (GQA/MQA:
the kernels index the kv head as ``h // (H/Hkv)`` in their block index maps
— never materialize repeated K/V at a call site). Batch-first; module
facades adapt the reference's seq-first [S, B, H*D] layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch

_INTERPRET = _dispatch.interpret

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _head_pad(d: int) -> int:
    """The head width the kernels' blocks hold: ``d`` rounded up to 128
    lanes. A 64-wide head left unpadded (legal: the block's minor dimension
    then equals the array's) made every kernel slower on the chip, 255 /
    182 / 261 us a call against 249 / 162 / 225 at b8 h16 s512 and 57.3
    against 50.9 at GPT-2's 768-token admission, for ~2 ms less ``pad`` and
    ``slice`` traffic around a training step's 72 calls: a tie end to end
    (PERF.md section 6, PR 36). Padded is the layout every head width
    compiles in."""
    return _dispatch.round_up(d, 128)


#: the longest tile side. One (512, 512) tile a grid step took the three
#: kernels at b8 h16 s512 d64 from 1216 / 822 / 1142 us a call on
#: (128, 128) to 249 / 162 / 225 (PERF.md section 6, PR 36), and
#: ``flash_fwd`` at head width 256 from 8.6 % to 49 % of the chip's peak
#: (PR 30); (1024, 1024) at that width overflows Mosaic's stack.
_MAX_BLOCK = 512

#: what a tile may take of Mosaic's 16 MiB scoped VMEM stack; the rest is
#: the compiler's (iotas, masks, the relayouts of a transposed product)
_VMEM_BUDGET = 12 * 2 ** 20


def _vmem_bytes(bq: int, bk: int, d_pad: int, itemsize: int,
                bias: bool) -> int:
    """VMEM one grid step of the widest of the three kernels (``dkv``) is
    budgeted: the score, ``p``, ``dp`` and ``ds`` tiles in float32 and the
    two copies the products take in the input dtype; q, do, k, v and the
    two outputs double-buffered; the two float32 accumulators; the
    ``(bq, 1)`` blocks of ``lse`` and ``delta`` (one lane of 128 each,
    double-buffered); a float32 ``(bq, bk)`` bias block when there is one.
    An upper bound: the compiler reuses tiles whose lives do not overlap."""
    tile, rows = bq * bk, max(bq, bk) * d_pad
    total = tile * (4 * 4 + 2 * itemsize)
    total += 2 * itemsize * 2 * (bq + bk) * d_pad    # q, do, k, v
    total += 2 * itemsize * 2 * rows + 4 * 2 * rows  # outputs, accumulators
    total += 2 * 2 * bq * 128 * 4                    # lse, delta
    if bias:
        total += 2 * tile * 4
    return total


def _tile_options(n: int, align: int):
    """Tile lengths for a sequence of ``n``, the one to prefer first: the
    whole sequence where ``_MAX_BLOCK`` holds it, then the multiples of 128
    that divide its 128-rounded length, so no choice pads further than
    tiles of 128 did."""
    whole = _dispatch.round_up(n, align)
    n128 = _dispatch.round_up(n, 128)
    opts = [t for t in range(_MAX_BLOCK, 0, -128) if n128 % t == 0]
    if whole <= _MAX_BLOCK:
        opts = [whole] + [t for t in opts if t < whole]
    return opts


def _block_sizes(sq: int, sk: int, block_q: Optional[int] = None,
                 block_k: Optional[int] = None, *, d: int,
                 itemsize: int = 2, bias: bool = False):
    """The ``(bq, bk)`` tile of all three kernels, from the call's static
    shapes alone: the largest tile up to ``_MAX_BLOCK`` a side that divides
    the padded lengths and whose ``_vmem_bytes`` stay inside
    ``_VMEM_BUDGET``, giving up q rows before k columns ((256, 512) beat
    (512, 256) in every kernel). A grid step costs 0.5-0.6 us whatever its
    tile, so a causal or banded call wants the large tile too, dead corner
    included. ``block_q`` / ``block_k`` are for callers that tile on
    purpose (the ring, the tests) and are taken as given."""
    q_opts = [block_q] if block_q else _tile_options(sq, 8)
    k_opts = [block_k] if block_k else _tile_options(sk, 128)
    d_pad = _head_pad(d)
    for bk in k_opts:
        for bq in q_opts:
            if _vmem_bytes(bq, bk, d_pad, itemsize, bias) <= _VMEM_BUDGET:
                return bq, bk
    return q_opts[-1], k_opts[-1]   # nothing fits: the compiler will say


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = _dispatch.round_up(size, mult) - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _mask_block(s, *, b_q, b_k, bq, bk, q_len, kv_len, causal, causal_offset,
                q_seg, kv_seg, window=None):
    """Padding / causal / segment masking for one (bq, bk) score tile.

    Returns (s_filled, live): masked entries get the finite
    DEFAULT_MASK_VALUE (NaN-free max), and callers must ALSO zero their
    exp() by ``live`` — otherwise a fully-masked row degenerates to a
    uniform distribution over every key including the block padding
    (fully-masked rows here output exactly 0, like the padded rows of the
    reference's varlen fmha).
    """
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 0) + b_q * bq
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 1) + b_k * bk
    mask = cols < kv_len
    if causal:
        mask &= (rows + causal_offset) >= cols
    if window is not None:
        # sliding window (Mistral-style): query r sees keys in
        # [r + offset - (window-1), r + offset]
        mask &= cols >= (rows + causal_offset - (window - 1))
    if q_seg is not None:
        mask &= q_seg == kv_seg     # a (bq, 1) column against a (1, bk) row
    del q_len  # padded q rows produce garbage that the caller slices away
    return jnp.where(mask, s, DEFAULT_MASK_VALUE), mask


def _dropout_keep(shape, rate, seed, bh, row0, col0):
    """Deterministic keep mask / (1-rate) scale for one score tile.

    Counter-based (Philox-spirit, reference: multihead_attn philox.h): each
    global (batch*head, row, col) position hashes to a uniform u32 via murmur3
    finalizer mixing, so forward and both backward kernels regenerate the
    identical mask from the seed alone — nothing is stored, and the mask is
    independent of block shape / grid order. Runs on any backend (the VPU cost
    is a handful of integer ops per element).
    """
    rows = lax.broadcasted_iota(jnp.uint32, shape, 0) + jnp.uint32(row0)
    cols = lax.broadcasted_iota(jnp.uint32, shape, 1) + jnp.uint32(col0)
    x = (rows * jnp.uint32(0x9E3779B1)
         + cols * jnp.uint32(0x85EBCA77)
         + jnp.uint32(bh) * jnp.uint32(0xC2B2AE3D)
         + jnp.uint32(seed))
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    threshold = jnp.uint32(min(int(rate * (2.0 ** 32)), 2 ** 32 - 1))
    return (x >= threshold).astype(jnp.float32) / (1.0 - rate)


# =============================================================================
# forward
# =============================================================================

def _band_width_blocks(span: int, other_block: int, n_total: int) -> int:
    """Blocks needed to cover a sliding band of ``span`` positions when the
    band start is not block-aligned: ceil(span/blk) + 1, capped at n_total."""
    return min(n_total, (span + other_block - 1) // other_block + 1)


def _global_block_ids(i_grid, j_grid, *, bq, bk, causal_offset,
                      window, band_over):
    """Map grid ids to GLOBAL (q-block, k-block) ids.

    With ``window`` set, the dead-block grid dimension is shrunk to the
    band (``band_over`` = "k" for fwd/dq, "q" for dkdv) and the band-local
    id offsets by the band start — so skipped blocks cost neither FLOPs
    nor DMA (grid cells outside the band simply don't exist). Callers
    clamp the returned ids in their BlockSpec index maps; the kernels use
    the UNclamped ids to compute liveness."""
    if window is None or band_over is None:
        return i_grid, j_grid
    if band_over == "k":
        lo = jnp.maximum(
            0, (i_grid * bq + causal_offset - (window - 1)) // bk)
        return i_grid, lo + j_grid
    lo = jnp.maximum(0, (j_grid * bk - causal_offset) // bq)
    return lo + i_grid, j_grid


def _band_index_map(*, bq, bk, n_limit, causal_offset, window, band_over):
    """Clamped grid->global block map for BlockSpec index maps: identity
    when no window, else the band-offset id clamped into [0, n_limit-1]
    (dead cells may DMA a duplicate edge block; the kernels' UNclamped ids
    mark them dead so they never contribute)."""
    if window is None:
        return lambda i_grid, j_grid: (j_grid if band_over == "k"
                                       else i_grid)

    def f(i_grid, j_grid):
        i_g, j_g = _global_block_ids(
            i_grid, j_grid, bq=bq, bk=bk, causal_offset=causal_offset,
            window=window, band_over=band_over)
        return jnp.minimum(j_g if band_over == "k" else i_g, n_limit - 1)

    return f


def _block_live(i_g, j_g, *, bq, bk, nq, nk, causal, causal_offset, window):
    """Liveness of global block (i_g, j_g): inside array bounds, on/below
    the causal diagonal, and inside the sliding-window band."""
    live = True
    if causal:
        live = (i_g * bq + bq - 1 + causal_offset) >= j_g * bk
    if window is not None:
        live &= (j_g * bk + bk - 1
                 >= i_g * bq + causal_offset - (window - 1))
        live &= (i_g < nq) & (j_g < nk)   # band ids can run past the edge
    return live


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, seed_ref,
                off_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, causal_offset, q_len, kv_len, bq, bk, nk,
                nq, dropout_rate, window=None, banded=True):
    b, h, i, j = (pl.program_id(d) for d in range(4))
    # a DYNAMIC offset (ring steps whose upstream distance depends on the
    # device index — zigzag CP) arrives as an SMEM scalar; the band-grid
    # restriction needs a static offset, so dynamic callers run unbanded
    # (``banded=False``) and dead blocks are skipped by ``block_live``
    off = off_ref[0, 0] if off_ref is not None else causal_offset
    # under a (static-offset) window the j grid spans only the band;
    # recover global ids
    i_g, j_g = _global_block_ids(i, j, bq=bq, bk=bk,
                                 causal_offset=causal_offset,
                                 window=window if banded else None,
                                 band_over="k")

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    block_live = _block_live(i_g, j_g, bq=bq, bk=bk, nq=nq, nk=nk,
                             causal=causal, causal_offset=off,
                             window=window)

    @pl.when(block_live)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s += bias_ref[0, 0].astype(jnp.float32)
        s, live = _mask_block(
            s, b_q=i_g, b_k=j_g, bq=bq, bk=bk, q_len=q_len, kv_len=kv_len,
            causal=causal, causal_offset=off,
            q_seg=qseg_ref[0] if qseg_ref is not None else None,
            kv_seg=kseg_ref[0] if kseg_ref is not None else None,
            window=window,
        )
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        if dropout_rate > 0.0:
            bh = b * pl.num_programs(1) + h
            p = p * _dropout_keep(p.shape, dropout_rate, seed_ref[0, 0],
                                  bh, i_g * bq + seed_ref[0, 1],
                                  j_g * bk + seed_ref[0, 2])
        v = v_ref[0, 0]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → output 0
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


def _gqa_rep(heads: int, kv_heads: int) -> int:
    """Query-heads-per-kv-head ratio (1 = standard MHA). The kernels index
    the kv head as ``h // rep`` in their BlockSpec index maps, so GQA/MQA
    never materialize repeated K/V in HBM (the win over jnp.repeat)."""
    if heads % kv_heads != 0:
        raise ValueError(
            f"q heads ({heads}) must be a multiple of kv heads ({kv_heads})")
    return heads // kv_heads


def _fa_fwd(q, k, v, bias, q_seg, kv_seg, seed, scale, causal, dropout_rate,
            block_q, block_k, window=None, causal_offset=None,
            dyn_offset=None):
    batch, heads, q_len, d = q.shape
    kv_len = k.shape[2]
    rep = _gqa_rep(heads, k.shape[1])
    bq, bk = _block_sizes(q_len, kv_len, block_q, block_k, d=d,
                          itemsize=q.dtype.itemsize, bias=bias is not None)
    d_pad = _head_pad(d)

    qp = _pad_to(_pad_to(q, 2, bq), 3, d_pad)
    kp = _pad_to(_pad_to(k, 2, bk), 3, d_pad)
    vp = _pad_to(_pad_to(v, 2, bk), 3, d_pad)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    nq, nk = sq_p // bq, sk_p // bk
    banded = window is not None and dyn_offset is None
    if dyn_offset is None and causal_offset is None:
        causal_offset = kv_len - q_len   # cross-attention diagonal default

    # band-restricted k grid under a window: dead blocks don't exist, so
    # windowed attention is O(S*window) in DMA as well as FLOPs. A DYNAMIC
    # offset cannot position the band statically: full grid, with dead
    # blocks skipped (FLOPs saved, DMA not) by the kernel's block_live.
    nk_grid = (_band_width_blocks(bq + window - 1, bk, nk) if banded
               else nk)
    jmap = _band_index_map(bq=bq, bk=bk, n_limit=nk,
                           causal_offset=causal_offset,
                           window=window if banded else None,
                           band_over="k")

    in_specs = [
        pl.BlockSpec((1, 1, bq, d_pad), lambda b, h, i, j: (b, h, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bk, d_pad),
                     lambda b, h, i, j: (b, h // rep, jmap(i, j), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bk, d_pad),
                     lambda b, h, i, j: (b, h // rep, jmap(i, j), 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [qp, kp, vp]
    if bias is not None:
        bias = jnp.broadcast_to(
            bias, (bias.shape[0], bias.shape[1], q_len, kv_len))
        bias = _pad_to(_pad_to(bias, 2, bq), 3, bk)
        bb, bh = bias.shape[0], bias.shape[1]
        in_specs.append(pl.BlockSpec(
            (1, 1, bq, bk),
            lambda b, h, i, j, bb=bb, bh=bh: (b % bb, h % bh, i, jmap(i, j)),
            memory_space=pltpu.VMEM))
        args.append(bias)
    if q_seg is not None:
        qsp = _pad_to(q_seg.astype(jnp.int32), 1, bq)
        ksp = _pad_to(kv_seg.astype(jnp.int32), 1, bk)
        # pad kv segments with -1 so padded keys never match a real segment
        if ksp.shape[1] != kv_seg.shape[1]:
            ksp = ksp.at[:, kv_seg.shape[1]:].set(-1)
        # q's ids ride as a (bq, 1) column and k's as a (1, bk) row (a
        # singleton dim each, so the blocks' last two dims satisfy Mosaic's
        # (8, 128)-or-full-dim rule): the compare broadcasts both and no
        # grid step turns a row of lanes into a column (PERF.md, PR 36)
        in_specs.append(pl.BlockSpec((1, bq, 1), lambda b, h, i, j: (b, i, 0),
                                     memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, h, i, j: (b, 0, jmap(i, j)),
            memory_space=pltpu.VMEM))
        args.extend([qsp[:, :, None], ksp[:, None]])
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec((1, 3), lambda b, h, i, j: (0, 0),
                                     memory_space=pltpu.SMEM))
        args.append(seed)
    if dyn_offset is not None:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, h, i, j: (0, 0),
                                     memory_space=pltpu.SMEM))
        args.append(dyn_offset.astype(jnp.int32).reshape(1, 1))

    def fn(*refs):
        it = iter(refs)
        q_ref, k_ref, v_ref = next(it), next(it), next(it)
        bias_ref = next(it) if bias is not None else None
        qseg_ref = next(it) if q_seg is not None else None
        kseg_ref = next(it) if q_seg is not None else None
        seed_ref = next(it) if dropout_rate > 0.0 else None
        off_ref = next(it) if dyn_offset is not None else None
        o_ref, lse_ref = next(it), next(it)
        acc_ref, m_ref, l_ref = next(it), next(it), next(it)
        _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, qseg_ref, kseg_ref, seed_ref,
                    off_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                    scale=scale, causal=causal, causal_offset=causal_offset,
                    q_len=q_len, kv_len=kv_len, bq=bq, bk=bk, nk=nk, nq=nq,
                    dropout_rate=dropout_rate, window=window, banded=banded)

    o, lse = _dispatch.pallas_call(
        fn,
        grid=(batch, heads, nq, nk_grid),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_pad), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, sq_p, d_pad), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d_pad), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        kernel="flash_fwd",
        interpret=_INTERPRET(),
    )(*args)
    return o[:, :, :q_len, :d], lse[:, :, :q_len, 0]


# =============================================================================
# backward
# =============================================================================

def _recompute_p(q_ref, k_ref, lse_ref, bias_ref, qseg_ref, kseg_ref, *,
                 scale, causal, causal_offset, kv_len, bq, bk, b_q, b_k,
                 window=None):
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if bias_ref is not None:
        s += bias_ref[0, 0].astype(jnp.float32)
    s, live = _mask_block(
        s, b_q=b_q, b_k=b_k, bq=bq, bk=bk, q_len=None, kv_len=kv_len,
        causal=causal, causal_offset=causal_offset,
        q_seg=qseg_ref[0] if qseg_ref is not None else None,
        kv_seg=kseg_ref[0] if kseg_ref is not None else None,
        window=window,
    )
    return jnp.where(live, jnp.exp(s - lse_ref[0, 0].reshape(-1, 1)), 0.0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref,
               dq_ref, dq_acc, *,
               scale, causal, causal_offset, kv_len, bq, bk, nk, nq,
               dropout_rate, window=None, banded=True):
    b, h, i, j = (pl.program_id(d) for d in range(4))
    off = off_ref[0, 0] if off_ref is not None else causal_offset
    i_g, j_g = _global_block_ids(i, j, bq=bq, bk=bk,
                                 causal_offset=causal_offset,
                                 window=window if banded else None,
                                 band_over="k")

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    block_live = _block_live(i_g, j_g, bq=bq, bk=bk, nq=nq, nk=nk,
                             causal=causal, causal_offset=off,
                             window=window)

    @pl.when(block_live)
    def _body():
        p = _recompute_p(q_ref, k_ref, lse_ref, bias_ref, qseg_ref, kseg_ref,
                         scale=scale, causal=causal,
                         causal_offset=off, kv_len=kv_len,
                         bq=bq, bk=bk, b_q=i_g, b_k=j_g, window=window)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            bh = b * pl.num_programs(1) + h
            dp = dp * _dropout_keep(dp.shape, dropout_rate, seed_ref[0, 0],
                                    bh, i_g * bq + seed_ref[0, 1],
                                    j_g * bk + seed_ref[0, 2])
        ds = p * (dp - delta_ref[0, 0].reshape(-1, 1)) * scale
        k = k_ref[0, 0]
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *,
                 scale, causal, causal_offset, kv_len, bq, bk, nq, nk,
                 dropout_rate, window=None, banded=True):
    # NOTE grid order: (b, h, j over k-blocks, i over q-blocks)
    b, h, j, i = (pl.program_id(d) for d in range(4))
    off = off_ref[0, 0] if off_ref is not None else causal_offset
    i_g, j_g = _global_block_ids(i, j, bq=bq, bk=bk,
                                 causal_offset=causal_offset,
                                 window=window if banded else None,
                                 band_over="q")

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    block_live = _block_live(i_g, j_g, bq=bq, bk=bk, nq=nq, nk=nk,
                             causal=causal, causal_offset=off,
                             window=window)

    @pl.when(block_live)
    def _body():
        p = _recompute_p(q_ref, k_ref, lse_ref, bias_ref, qseg_ref, kseg_ref,
                         scale=scale, causal=causal,
                         causal_offset=off, kv_len=kv_len,
                         bq=bq, bk=bk, b_q=i_g, b_k=j_g, window=window)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        if dropout_rate > 0.0:
            bh = b * pl.num_programs(1) + h
            keep = _dropout_keep(p.shape, dropout_rate, seed_ref[0, 0],
                                 bh, i_g * bq + seed_ref[0, 1],
                                 j_g * bk + seed_ref[0, 2])
            p_dropped = p * keep
        else:
            keep = None
            p_dropped = p
        dv_acc[...] += jax.lax.dot_general(
            p_dropped.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if keep is not None:
            dp = dp * keep
        ds = p * (dp - delta_ref[0, 0].reshape(-1, 1)) * scale
        q = q_ref[0, 0]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_impl(q, k, v, bias, q_seg, kv_seg, seed, scale, causal,
                 dropout_rate, block_q, block_k, o, lse, do,
                 delta_adjust=None, window=None, causal_offset=None,
                 dyn_offset=None):
    batch, heads, q_len, d = q.shape
    kv_len = k.shape[2]
    kv_heads = k.shape[1]
    rep = _gqa_rep(heads, kv_heads)
    bq, bk = _block_sizes(q_len, kv_len, block_q, block_k, d=d,
                          itemsize=q.dtype.itemsize, bias=bias is not None)
    d_pad = _head_pad(d)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if delta_adjust is not None:
        # an lse cotangent folds into the row correction:
        # ds = p*(dp - delta + dlse) = p*(dp - (delta - dlse))
        delta = delta + delta_adjust

    qp = _pad_to(_pad_to(q, 2, bq), 3, d_pad)
    kp = _pad_to(_pad_to(k, 2, bk), 3, d_pad)
    vp = _pad_to(_pad_to(v, 2, bk), 3, d_pad)
    dop = _pad_to(_pad_to(do, 2, bq), 3, d_pad)
    # pad lse with +inf → p = exp(s - inf) = 0 for padded q rows
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sq_p - q_len)),
                   constant_values=jnp.inf)[..., None]
    deltap = _pad_to(delta, 2, bq)[..., None]
    nq, nk = sq_p // bq, sk_p // bk
    banded = window is not None and dyn_offset is None
    if dyn_offset is None and causal_offset is None:
        causal_offset = kv_len - q_len

    if banded:
        nkg_dq = _band_width_blocks(bq + window - 1, bk, nk)
        nig_dkdv = _band_width_blocks(bk + window - 1, bq, nq)
    else:
        nkg_dq, nig_dkdv = nk, nq
    jmap_dq = _band_index_map(bq=bq, bk=bk, n_limit=nk,
                              causal_offset=causal_offset,
                              window=window if banded else None,
                              band_over="k")
    _imap = _band_index_map(bq=bq, bk=bk, n_limit=nq,
                            causal_offset=causal_offset,
                            window=window if banded else None,
                            band_over="q")

    def imap_dkdv(j, i):
        return _imap(i, j)

    base_args = [qp, kp, vp, dop, lsep, deltap]
    if bias is not None:
        bias_b = jnp.broadcast_to(
            bias, (bias.shape[0], bias.shape[1], q_len, kv_len))
        bias_p = _pad_to(_pad_to(bias_b, 2, bq), 3, bk)
        bb, bh = bias_p.shape[0], bias_p.shape[1]
        base_args.append(bias_p)
    if q_seg is not None:
        qsp = _pad_to(q_seg.astype(jnp.int32), 1, bq)
        ksp = _pad_to(kv_seg.astype(jnp.int32), 1, bk)
        if ksp.shape[1] != kv_seg.shape[1]:
            ksp = ksp.at[:, kv_seg.shape[1]:].set(-1)
        base_args.extend([qsp[:, :, None], ksp[:, None]])
    if dropout_rate > 0.0:
        base_args.append(seed)
    if dyn_offset is not None:
        base_args.append(dyn_offset.astype(jnp.int32).reshape(1, 1))

    def make_specs(idx_q, idx_k):
        """Index maps for one kernel given q-block/k-block extractors."""
        def qspec():
            return pl.BlockSpec((1, 1, bq, d_pad),
                                lambda *g: (g[0], g[1], idx_q(g), 0),
                                memory_space=pltpu.VMEM)

        def kspec():
            # kv head = q head // rep (GQA; rep=1 is standard MHA)
            return pl.BlockSpec((1, 1, bk, d_pad),
                                lambda *g: (g[0], g[1] // rep, idx_k(g), 0),
                                memory_space=pltpu.VMEM)

        def rspec():
            return pl.BlockSpec((1, 1, bq, 1),
                                lambda *g: (g[0], g[1], idx_q(g), 0),
                                memory_space=pltpu.VMEM)

        specs = [qspec(), kspec(), kspec(), qspec(), rspec(), rspec()]
        if bias is not None:
            specs.append(pl.BlockSpec(
                (1, 1, bq, bk),
                lambda *g: (g[0] % bb, g[1] % bh, idx_q(g), idx_k(g)),
                memory_space=pltpu.VMEM))
        if q_seg is not None:
            specs.append(pl.BlockSpec((1, bq, 1), lambda *g: (g[0], idx_q(g), 0),
                                      memory_space=pltpu.VMEM))
            specs.append(pl.BlockSpec((1, 1, bk), lambda *g: (g[0], 0, idx_k(g)),
                                      memory_space=pltpu.VMEM))
        if dropout_rate > 0.0:
            specs.append(pl.BlockSpec((1, 3), lambda *g: (0, 0),
                                      memory_space=pltpu.SMEM))
        if dyn_offset is not None:
            specs.append(pl.BlockSpec((1, 1), lambda *g: (0, 0),
                                      memory_space=pltpu.SMEM))
        return specs

    def split_refs(refs, n_out):
        it = iter(refs)
        ins = [next(it) for _ in range(6)]
        bias_ref = next(it) if bias is not None else None
        qseg_ref = next(it) if q_seg is not None else None
        kseg_ref = next(it) if q_seg is not None else None
        seed_ref = next(it) if dropout_rate > 0.0 else None
        off_ref = next(it) if dyn_offset is not None else None
        outs = [next(it) for _ in range(n_out)]
        scratch = list(it)
        return ins, bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref, \
            outs, scratch

    # ---- dq ----
    def dq_fn(*refs):
        ins, bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref, outs, \
            scratch = split_refs(refs, 1)
        _dq_kernel(*ins, bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref,
                   outs[0], scratch[0],
                   scale=scale, causal=causal, causal_offset=causal_offset,
                   kv_len=kv_len, bq=bq, bk=bk, nk=nk, nq=nq,
                   dropout_rate=dropout_rate, window=window, banded=banded)

    dq = _dispatch.pallas_call(
        dq_fn,
        grid=(batch, heads, nq, nkg_dq),
        in_specs=make_specs(lambda g: g[2], lambda g: jmap_dq(g[2], g[3])),
        out_specs=[pl.BlockSpec((1, 1, bq, d_pad),
                                lambda b, h, i, j: (b, h, i, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((batch, heads, sq_p, d_pad), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        kernel="flash_bwd_dq",
        interpret=_INTERPRET(),
    )(*base_args)[0]

    # ---- dk, dv ----
    def dkdv_fn(*refs):
        ins, bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref, outs, \
            scratch = split_refs(refs, 2)
        _dkdv_kernel(*ins, bias_ref, qseg_ref, kseg_ref, seed_ref, off_ref,
                     outs[0], outs[1], scratch[0], scratch[1],
                     scale=scale, causal=causal, causal_offset=causal_offset,
                     kv_len=kv_len, bq=bq, bk=bk, nq=nq, nk=nk,
                     dropout_rate=dropout_rate, window=window, banded=banded)

    dk, dv = _dispatch.pallas_call(
        dkdv_fn,
        grid=(batch, heads, nk, nig_dkdv),
        in_specs=make_specs(lambda g: imap_dkdv(g[2], g[3]),
                            lambda g: g[2]),
        out_specs=[
            pl.BlockSpec((1, 1, bk, d_pad), lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d_pad), lambda b, h, j, i: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, sk_p, d_pad), k.dtype),
            jax.ShapeDtypeStruct((batch, heads, sk_p, d_pad), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d_pad), jnp.float32),
            pltpu.VMEM((bk, d_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        kernel="flash_bwd_dkv",
        interpret=_INTERPRET(),
    )(*base_args)

    if rep > 1:
        # per-q-head dk/dv partials -> their kv head (fp32 accumulation);
        # identical math to jnp.repeat's VJP but without the forward ever
        # materializing repeated K/V
        dk = dk.astype(jnp.float32).reshape(
            batch, kv_heads, rep, *dk.shape[2:]).sum(axis=2).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(
            batch, kv_heads, rep, *dv.shape[2:]).sum(axis=2).astype(v.dtype)
    return (dq[:, :, :q_len, :d], dk[:, :, :kv_len, :d], dv[:, :, :kv_len, :d])


# =============================================================================
# custom-vjp entry
# =============================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _flash(q, k, v, bias, q_seg, kv_seg, seed, scale, causal, dropout_rate,
           block_q, block_k, window):
    o, _ = _fa_fwd(q, k, v, bias, q_seg, kv_seg, seed, scale, causal,
                   dropout_rate, block_q, block_k, window)
    return o


def _flash_fwd(q, k, v, bias, q_seg, kv_seg, seed, scale, causal,
               dropout_rate, block_q, block_k, window):
    o, lse = _fa_fwd(q, k, v, bias, q_seg, kv_seg, seed, scale, causal,
                     dropout_rate, block_q, block_k, window)
    return o, (q, k, v, bias, q_seg, kv_seg, seed, o, lse)


def _flash_bwd(scale, causal, dropout_rate, block_q, block_k, window,
               res, do):
    q, k, v, bias, q_seg, kv_seg, seed, o, lse = res
    dq, dk, dv = _fa_bwd_impl(q, k, v, bias, q_seg, kv_seg, seed, scale,
                              causal, dropout_rate, block_q, block_k,
                              o, lse, do, window=window)
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseg = None if q_seg is None else jnp.zeros_like(q_seg)
    dkseg = None if kv_seg is None else jnp.zeros_like(kv_seg)
    return dq, dk, dv, dbias, dseg, dkseg, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_with_lse(q, k, v, dyn_off, drop_meta, scale, causal, block_q,
                    block_k, window, causal_offset, dropout_rate):
    """(o, lse) variant for blockwise/ring composition: callers that merge
    partial attention results (ring attention over a context-sharded
    sequence) need the per-row logsumexp, and its cotangent folds into the
    backward's delta correction (see _fa_bwd_impl.delta_adjust).
    ``causal_offset``/``dyn_off`` override the cross-attention diagonal — a
    ring step attending an upstream chunk passes the global row offset so
    causal / window masking applies at GLOBAL positions; ``dyn_off`` is the
    TRACED (1, 1) i32 variant for offsets that depend on the device index
    (zigzag CP). ``drop_meta`` is a (1, 3) i32 [seed, global_row0,
    global_col0] so a CP-sharded sequence regenerates the exact
    single-device keep mask."""
    return _fa_fwd(q, k, v, None, None, None, drop_meta, scale, causal,
                   dropout_rate, block_q, block_k, window, causal_offset,
                   dyn_off)


def _flash_with_lse_fwd(q, k, v, dyn_off, drop_meta, scale, causal, block_q,
                        block_k, window, causal_offset, dropout_rate):
    o, lse = _fa_fwd(q, k, v, None, None, None, drop_meta, scale, causal,
                     dropout_rate, block_q, block_k, window, causal_offset,
                     dyn_off)
    return (o, lse), (q, k, v, dyn_off, drop_meta, o, lse)


def _flash_with_lse_bwd(scale, causal, block_q, block_k, window,
                        causal_offset, dropout_rate, res, cts):
    q, k, v, dyn_off, drop_meta, o, lse = res
    do, dlse = cts
    dq, dk, dv = _fa_bwd_impl(q, k, v, None, None, None, drop_meta, scale,
                              causal, dropout_rate, block_q, block_k,
                              o, lse, do,
                              delta_adjust=-dlse.astype(jnp.float32),
                              window=window, causal_offset=causal_offset,
                              dyn_offset=dyn_off)
    return dq, dk, dv, None, None


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def flash_attention_with_lse(q, k, v, *, scale: Optional[float] = None,
                             causal: bool = False,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             window: Optional[int] = None,
                             causal_offset=None,
                             dropout_rate: float = 0.0,
                             dropout_seed=0,
                             dropout_row0=0,
                             dropout_col0=0):
    """Flash attention returning ``(o, lse)`` — the building block for
    ring/blockwise attention (apex_tpu/ops/ring_attention.py). Fully
    differentiable including through the lse.

    ``window``/``causal_offset`` let a ring step apply GLOBAL-position
    causal+window masking to an upstream chunk (window requires causal).
    ``causal_offset`` may be a traced value (device-index-dependent
    offsets, zigzag CP): the kernel then masks via an SMEM scalar and the
    static band-grid restriction is disabled (dead blocks still skip their
    FLOPs via the liveness predicate).

    ``dropout_rate``/``dropout_seed`` with ``dropout_row0``/``dropout_col0``
    (global positions of this chunk's first q row / k col, traced OK) make
    the counter-based keep mask a function of GLOBAL coordinates — a ring
    of chunked calls reproduces exactly the mask one unsharded call draws,
    so CP attention dropout matches single-device (reference:
    multihead_attn's fused softmax-dropout under sequence sharding)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    d = q.shape[-1]
    scale = (1.0 / (d ** 0.5)) if scale is None else scale
    if causal_offset is None or isinstance(causal_offset, (int, np.integer)):
        dyn = None
        static_off = None if causal_offset is None else int(causal_offset)
    else:
        dyn = jnp.asarray(causal_offset, jnp.int32).reshape(1, 1)
        static_off = None
    meta = None
    if dropout_rate > 0.0:
        meta = jnp.stack([
            jnp.asarray(dropout_seed, jnp.int32).reshape(()),
            jnp.asarray(dropout_row0, jnp.int32).reshape(()),
            jnp.asarray(dropout_col0, jnp.int32).reshape(()),
        ]).reshape(1, 3)
    # under an lse cotangent the staged bwd re-runs the fwd kernel for
    # residuals and drops one twin; tpu_custom_call is side-effect-free
    # so XLA DCEs it — training-only path, not worth a custom_vjp split
    # tpu-lint: disable=ir-dead-output -- dead twin is DCE'd by XLA
    return _flash_with_lse(
        q, k, v, dyn, meta, float(scale), causal, block_q, block_k,
        None if window is None else int(window), static_off,
        float(dropout_rate))


def flash_attention(
    q,
    k,
    v,
    bias: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_seed: int = 0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
):
    """Flash attention: softmax(scale * q @ k^T + bias [masked]) @ v.

    Args:
      q: [batch, heads, q_len, head_dim].
      k, v: [batch, kv_heads, kv_len, head_dim] — ``kv_heads`` may DIVIDE
        ``heads`` (grouped-query / multi-query attention, beyond the
        reference's equal-heads kernels): the kernels index the kv head as
        ``h // (heads/kv_heads)`` in their block index maps, so GQA never
        materializes repeated K/V in HBM.
      bias: optional additive bias/mask broadcastable to
        [batch, heads, q_len, kv_len] (the reference's arbitrary attention
        mask, generic_scaled_masked_softmax); NOT differentiated (masks are
        constants in the reference API).
      segment_ids / kv_segment_ids: optional int32 [batch, len] varlen packing
        (reference fmha cu_seqlens, apex/contrib/csrc/fmha/fmha_api.cpp);
        tokens attend only within equal segment ids. kv_segment_ids defaults
        to segment_ids (self attention).
      causal: upper-triangular masking (scaled_upper_triang_masked_softmax).
      scale: softmax scale; default 1/sqrt(head_dim).
      dropout_rate/dropout_seed: attention-prob dropout (multihead_attn's
        fused softmax-dropout); the keep mask is regenerated in backward from
        the seed, never materialized.
      window: sliding-window width (Mistral-style, requires causal=True):
        query r attends keys [r-window+1, r]. The kernels' k/q grid
        dimension is RESTRICTED to the live band (``_global_block_ids``),
        so out-of-band blocks don't exist at all — neither their FLOPs nor
        their HBM->VMEM copies happen, and end-to-end cost scales
        O(S*window) instead of O(S^2/2). Beyond the reference's kernels
        (its fmha has no windowing at all).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (Mistral-style "
                             "sliding window over a causal sequence)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    # seed is a *traced* (1,3) SMEM scalar row [seed, row0, col0] so jitted
    # training steps can vary it per step without recompiling (unlike a
    # static-arg seed); row0/col0 are the global-position bases (0 here —
    # ring callers offset them per chunk via flash_attention_with_lse)
    seed = (jnp.stack([jnp.asarray(dropout_seed, jnp.int32).reshape(()),
                       jnp.zeros((), jnp.int32),
                       jnp.zeros((), jnp.int32)]).reshape(1, 3)
            if dropout_rate > 0.0 else None)
    return _flash(q, k, v, bias, segment_ids, kv_segment_ids, seed,
                  float(scale), bool(causal), float(dropout_rate),
                  block_q, block_k,
                  None if window is None else int(window))


def mha_reference(q, k, v, bias=None, segment_ids=None, kv_segment_ids=None,
                  *, causal=False, scale=None, dropout_rate=0.0,
                  dropout_seed=0, window=None):
    """Pure-jnp unfused reference (the 'impl=default' ground-truth path that
    the reference's tests compare the fast kernels against)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (same contract as "
                         "flash_attention)")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if segment_ids is not None and kv_segment_ids is None:
        kv_segment_ids = segment_ids
    if k.shape[1] != q.shape[1]:  # GQA ground truth: repeat kv heads
        rep = _gqa_rep(q.shape[1], k.shape[1])
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s += bias.astype(jnp.float32)
    q_len, kv_len = q.shape[2], k.shape[2]
    mask = jnp.ones((q_len, kv_len), bool)
    if causal:
        rows = jnp.arange(q_len)[:, None] + (kv_len - q_len)
        mask &= rows >= jnp.arange(kv_len)[None, :]
        if window is not None:
            mask &= jnp.arange(kv_len)[None, :] >= rows - (window - 1)
    mask = mask[None, None]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, None, :, None]
                       == kv_segment_ids[:, None, None, :])
    # same semantics as the kernel: masked entries contribute exactly zero
    # and fully-masked rows output exactly zero
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    p = e / jnp.where(denom == 0.0, 1.0, denom)
    if dropout_rate > 0.0:
        raise NotImplementedError(
            "reference path has no in-kernel PRNG; compare dropout runs "
            "statistically against the kernel instead")
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
