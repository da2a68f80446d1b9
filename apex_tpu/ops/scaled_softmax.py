"""Pallas fused scale+mask+softmax (fwd + bwd).

TPU rebuild of the three megatron softmax extensions (SURVEY.md §2.2):
``scaled_masked_softmax_cuda``, ``scaled_upper_triang_masked_softmax_cuda``,
``generic_scaled_masked_softmax_cuda`` (csrc/megatron/scaled_masked_softmax.h
and siblings — scale + {arbitrary | causal} mask + softmax, fwd/bwd, saving
the softmax output for backward). Unlike the reference there is no seqlen cap
(the CUDA fast path required sk <= 2k/4k); one kernel serves all shapes.

Used standalone by ``FusedScaleMaskSoftmax``
(apex/transformer/functional/fused_softmax.py); for full attention blocks the
softmax is folded into apex_tpu.ops.flash_attention instead.

Layout: x [b, np, sq, sk] (the reference's layout); mask broadcastable
[b or 1, 1, sq, sk], **True = masked out** (reference convention).
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

_INTERPRET = _dispatch.interpret

# the reference fills masked scores with -10000 (scaled_masked_softmax.h)
MASK_FILL = -10000.0


def _row_tile(sk: int, sq: int) -> int:
    return _dispatch.row_tile(sk, sq, cap=256)


def _fwd_kernel(x_ref, mask_ref, y_ref, *, scale, causal, sq, sk, tile):
    i = pl.program_id(2)
    x = x_ref[0, 0].astype(jnp.float32) * scale
    rows = lax.broadcasted_iota(jnp.int32, x.shape, 0) + i * tile
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    pad = cols >= sk
    if mask_ref is not None:
        x = jnp.where(mask_ref[0, 0] != 0, MASK_FILL, x)
    if causal:
        x = jnp.where(rows < cols, MASK_FILL, x)
    # padding columns must vanish entirely (not just MASK_FILL)
    x = jnp.where(pad, -jnp.inf, x)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    y = e / jnp.sum(e, axis=-1, keepdims=True)
    y_ref[0, 0] = y.astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref, *, scale):
    y = y_ref[0, 0].astype(jnp.float32)
    dy = dy_ref[0, 0].astype(jnp.float32)
    dot = jnp.sum(y * dy, axis=-1, keepdims=True)
    dx_ref[0, 0] = ((dy - dot) * y * scale).astype(dx_ref.dtype)


def _softmax_fwd(x, mask, scale, causal):
    b, np_, sq, sk = x.shape
    tile = _row_tile(sk, sq)
    sk_pad = _dispatch.round_up(sk, 128)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, _dispatch.round_up(sq, tile) - sq),
                     (0, sk_pad - sk)))
    nq = xp.shape[2] // tile

    in_specs = [pl.BlockSpec((1, 1, tile, sk_pad),
                             lambda b, h, i: (b, h, i, 0),
                             memory_space=pltpu.VMEM)]
    args = [xp]
    if mask is not None:
        mask = jnp.broadcast_to(mask, (mask.shape[0], 1, sq, sk)).astype(jnp.int8)
        mp = jnp.pad(mask, ((0, 0), (0, 0),
                            (0, xp.shape[2] - sq), (0, sk_pad - sk)))
        mb = mp.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, tile, sk_pad),
            lambda b, h, i, mb=mb: (b % mb, 0, i, 0),
            memory_space=pltpu.VMEM))
        args.append(mp)

    def fn(*refs):
        x_ref = refs[0]
        mask_ref = refs[1] if mask is not None else None
        y_ref = refs[-1]
        _fwd_kernel(x_ref, mask_ref, y_ref, scale=scale, causal=causal,
                    sq=sq, sk=sk, tile=tile)

    y = _dispatch.pallas_call(
        fn,
        grid=(b, np_, nq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tile, sk_pad),
                               lambda b, h, i: (b, h, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        kernel="scaled_softmax_fwd",
        interpret=_INTERPRET(),
    )(*args)
    return y[:, :, :sq, :sk]


def _softmax_bwd_impl(y, dy, scale):
    b, np_, sq, sk = y.shape
    tile = _row_tile(sk, sq)
    sk_pad = _dispatch.round_up(sk, 128)
    pad = ((0, 0), (0, 0), (0, _dispatch.round_up(sq, tile) - sq),
           (0, sk_pad - sk))
    yp, dyp = jnp.pad(y, pad), jnp.pad(dy, pad)
    nq = yp.shape[2] // tile
    spec = pl.BlockSpec((1, 1, tile, sk_pad), lambda b, h, i: (b, h, i, 0),
                        memory_space=pltpu.VMEM)
    dx = _dispatch.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(b, np_, nq),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(yp.shape, dy.dtype),
        kernel="scaled_softmax_bwd",
        interpret=_INTERPRET(),
    )(yp, dyp)
    return dx[:, :, :sq, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _scaled_softmax(x, mask, scale, causal):
    return _softmax_fwd(x, mask, scale, causal)


def _scaled_softmax_vfwd(x, mask, scale, causal):
    y = _softmax_fwd(x, mask, scale, causal)
    return y, (y, mask)


def _scaled_softmax_vbwd(scale, causal, res, dy):
    y, mask = res
    dx = _softmax_bwd_impl(y, dy, scale)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dx, dmask


_scaled_softmax.defvjp(_scaled_softmax_vfwd, _scaled_softmax_vbwd)


def scaled_masked_softmax(x, mask: Optional[jax.Array], scale: float = 1.0):
    """softmax(scale*x masked-filled where ``mask`` is True), last dim.

    Reference: csrc/megatron/scaled_masked_softmax.h (fwd/bwd) via
    ``ScaledMaskedSoftmax`` autograd fn in
    apex/transformer/functional/fused_softmax.py.
    """
    return _scaled_softmax(x, mask, float(scale), False)


def scaled_upper_triang_masked_softmax(x, scale: float = 1.0):
    """Causal softmax for [b, sq, sk] score tensors (attn_batches layout).

    Reference: csrc/megatron/scaled_upper_triang_masked_softmax.h via
    ``ScaledUpperTriangMaskedSoftmax``.
    """
    y = _scaled_softmax(x[:, None], None, float(scale), True)
    return y[:, 0]


def scaled_softmax(x, scale: float = 1.0):
    """No-mask variant (reference ``ScaledSoftmax``)."""
    return _scaled_softmax(x, None, float(scale), False)
