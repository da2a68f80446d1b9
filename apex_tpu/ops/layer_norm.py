"""Fused LayerNorm / RMSNorm Pallas kernels with custom VJP.

TPU-native rebuild of the reference's ``fused_layer_norm_cuda`` extension
(csrc/layer_norm_cuda.cpp:~300, csrc/layer_norm_cuda_kernel.cu:~900 — per-row
Welford mean/invvar with fp32 accumulation, affine/non-affine/RMS variants,
two-stage dgamma/dbeta reduction) and of ``fast_layer_norm``
(apex/contrib/csrc/layer_norm/ — the same math hand-tuned per hidden size).
One kernel family replaces both: rows are tiled into VMEM and the hidden dim
is reduced in fp32 on the VPU; the backward fuses dx with the dgamma/dbeta
row-reduction by accumulating partials across sequential grid steps (the
Pallas analog of the CUDA two-stage shared-memory reduction).

API semantics match apex/normalization/fused_layer_norm.py:
- fp32 accumulation regardless of input dtype; output in input dtype
- ``memory_efficient=True`` saves the *output* instead of the input and
  recomputes x-hat in backward (FusedLayerNormAffineFunction's
  memory_efficient flag). Caveat (inherent to the trick, same as the
  reference's kernel): x-hat is recovered as (y - beta) / gamma, so with
  16-bit activations and entries of gamma near zero the recovered x-hat —
  and hence d-gamma — loses precision (measured: exact in fp32; ~0.7% max
  rel err in bf16 with |gamma| >= 0.5; unusable when |gamma| ~ 1e-3). Keep
  gamma well-conditioned or use the default path in low precision.
- weight/bias may be fp32 while x is bf16 (the "Mixed" variants)
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


def _row_tile(n_cols: int, n_rows: int, bytes_per_el: int = 4) -> int:
    """Pick a row-tile so x-tile + scratch stay under the 16MB scoped-VMEM
    limit: the bwd kernel holds ~8 fp32 tile-sized arrays (x, dy, xhat, dx,
    partial dgamma/dbeta, temporaries), so cap tiles at 1MB each."""
    return _dispatch.row_tile(n_cols, n_rows, cap=256,
                              budget_bytes=1024 * 1024,
                              bytes_per_el=bytes_per_el)


# =============================================================================
# forward
# =============================================================================

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps, affine, rms):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(x * x, axis=-1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        xc = x - mean
        var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    if affine:
        w = w_ref[...].astype(jnp.float32)  # (1, cols)
        y = xhat * w
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
    else:
        y = xhat
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_fwd(x2d, weight, bias, eps, rms):
    rows, cols = x2d.shape
    affine = weight is not None
    tile = _row_tile(cols, rows)
    grid = (_dispatch.cdiv(rows, tile),)

    kernel = functools.partial(_ln_fwd_kernel, eps=eps, affine=affine, rms=rms)
    if not affine:
        def kernel_noaff(x_ref, y_ref, mean_ref, rstd_ref):
            _ln_fwd_kernel(x_ref, None, None, y_ref, mean_ref, rstd_ref,
                           eps=eps, affine=False, rms=rms)
        fn = kernel_noaff
        in_specs = [pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM)]
        args = (x2d,)
    elif bias is None:
        def kernel_nobias(x_ref, w_ref, y_ref, mean_ref, rstd_ref):
            _ln_fwd_kernel(x_ref, w_ref, None, y_ref, mean_ref, rstd_ref,
                           eps=eps, affine=True, rms=rms)
        fn = kernel_nobias
        in_specs = [
            pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ]
        args = (x2d, weight.reshape(1, cols))
    else:
        fn = kernel
        in_specs = [
            pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ]
        args = (x2d, weight.reshape(1, cols), bias.reshape(1, cols))

    y, mean, rstd = _dispatch.pallas_call(
        fn,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        kernel="layer_norm_fwd",
        interpret=_INTERPRET(),
    )(*args)
    return y, mean, rstd


# =============================================================================
# backward
# =============================================================================

def _ln_bwd_kernel(dy_ref, xhat_src_ref, mean_ref, rstd_ref, w_ref, b_ref,
                   dx_ref, dw_ref, db_ref, *, affine, rms, from_y, n_rows, tile):
    """dx for this row tile; dgamma/dbeta partials accumulated across the
    (sequential) grid — Pallas analog of csrc/layer_norm_cuda_kernel.cu's
    two-stage shared-memory reduction."""
    i = pl.program_id(0)
    dy = dy_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]  # (tile, 1) fp32
    cols = dy.shape[1]

    if affine:
        w = w_ref[...].astype(jnp.float32)  # (1, cols)
    else:
        w = jnp.ones((1, cols), jnp.float32)

    src = xhat_src_ref[...].astype(jnp.float32)
    if from_y:
        # memory_efficient: recompute xhat from the saved output
        if affine:
            b = b_ref[...].astype(jnp.float32) if b_ref is not None else 0.0
            xhat = (src - b) / w
        else:
            xhat = src
    else:
        mean = mean_ref[...] if not rms else 0.0
        xhat = (src - mean) * rstd

    # mask padded rows so dw/db partials are exact on ragged final tiles;
    # where-select, not multiply: OOB rows hold unspecified memory and
    # 0 * NaN = NaN would poison the cross-row dgamma/dbeta reduction
    row_ids = lax.broadcasted_iota(jnp.int32, dy.shape, 0) + i * tile
    valid = row_ids < n_rows
    dy = jnp.where(valid, dy, 0.0)
    xhat = jnp.where(valid, xhat, 0.0)

    wdy = dy * w
    c1 = jnp.mean(xhat * wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy, axis=-1, keepdims=True)
    if rms:
        dx = (wdy - xhat * c1) * rstd
    else:
        dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)

    if affine:
        @pl.when(i == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)
            if db_ref is not None:
                db_ref[...] = jnp.zeros_like(db_ref)

        dw_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
        if db_ref is not None:
            db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _ln_bwd(dy2d, saved, weight, bias, eps, rms, memory_efficient):
    xhat_src, mean, rstd = saved
    rows, cols = dy2d.shape
    affine = weight is not None
    has_bias = bias is not None
    tile = _row_tile(cols, rows)
    grid = (_dispatch.cdiv(rows, tile),)

    x_spec = pl.BlockSpec((tile, cols), lambda i: (i, 0), memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    v_spec = pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM)

    out_specs = [x_spec]
    out_shape = [jax.ShapeDtypeStruct((rows, cols), dy2d.dtype)]
    if affine:
        out_specs.append(v_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, cols), jnp.float32))
        if has_bias:
            out_specs.append(v_spec)
            out_shape.append(jax.ShapeDtypeStruct((1, cols), jnp.float32))

    needs_mean = mean is not None
    in_specs = [x_spec, x_spec]
    args = [dy2d, xhat_src]
    if needs_mean:
        in_specs.append(s_spec)
        args.append(mean)
    in_specs.append(s_spec)
    args.append(rstd)
    if affine:
        in_specs.append(v_spec)
        args.append(weight.reshape(1, cols))
        if has_bias and memory_efficient:
            in_specs.append(v_spec)
            args.append(bias.reshape(1, cols))

    def fn(*refs):
        it = iter(refs)
        dy_ref, src_ref = next(it), next(it)
        mean_ref = next(it) if needs_mean else None
        rstd_ref = next(it)
        w_ref = next(it) if affine else None
        b_ref = next(it) if (affine and has_bias and memory_efficient) else None
        dx_ref = next(it)
        dw_ref = next(it) if affine else None
        db_ref = next(it) if (affine and has_bias) else None
        _ln_bwd_kernel(dy_ref, src_ref, mean_ref, rstd_ref, w_ref, b_ref,
                       dx_ref, dw_ref, db_ref,
                       affine=affine, rms=rms, from_y=memory_efficient,
                       n_rows=rows, tile=tile)

    outs = _dispatch.pallas_call(
        fn,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        kernel="layer_norm_bwd",
        interpret=_INTERPRET(),
    )(*args)
    dx = outs[0]
    dw = outs[1].reshape(-1).astype(weight.dtype) if affine else None
    db = outs[2].reshape(-1).astype(bias.dtype) if (affine and has_bias) else None
    return dx, dw, db


# =============================================================================
# public custom-vjp ops
# =============================================================================

def _norm_impl(x, weight, bias, eps, rms, memory_efficient):
    shape = x.shape
    cols = shape[-1]
    x2d = x.reshape(-1, cols)
    y, mean, rstd = _ln_fwd(x2d, weight, bias, eps, rms)
    # mean is only consumed by the default (save-x) LayerNorm backward; drop
    # it otherwise so memory_efficient actually shrinks the residual set
    # (apex's memory_efficient discards mean the same way).
    keep_mean = mean if (not rms and not memory_efficient) else None
    return y.reshape(shape), (y if memory_efficient else x2d, keep_mean, rstd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_norm(x, weight, bias, eps, rms, memory_efficient):
    return _norm_impl(x, weight, bias, eps, rms, memory_efficient)[0]


def _fused_norm_fwd(x, weight, bias, eps, rms, memory_efficient):
    y, (src, mean, rstd) = _norm_impl(x, weight, bias, eps, rms, memory_efficient)
    src2d = src.reshape(-1, src.shape[-1])
    return y, (src2d, mean, rstd, weight, bias, x.shape)


def _fused_norm_bwd(eps, rms, memory_efficient, res, dy):
    src2d, mean, rstd, weight, bias, shape = res
    dy2d = dy.reshape(-1, shape[-1])
    dx, dw, db = _ln_bwd(dy2d, (src2d, mean, rstd), weight, bias, eps, rms, memory_efficient)
    return (dx.reshape(shape), dw, db)


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


def layer_norm(
    x,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
):
    """Fused LayerNorm over the last dimension.

    Reference API: apex/normalization/fused_layer_norm.py
    (FusedLayerNormAffineFunction / FusedLayerNormFunction).
    """
    if weight is None and bias is not None:
        raise ValueError("layer_norm: bias requires weight (the reference API has no bias-only variant)")
    return _fused_norm(x, weight, bias, float(eps), False, bool(memory_efficient))


def rms_norm(
    x,
    weight: Optional[jax.Array] = None,
    eps: float = 1e-5,
    memory_efficient: bool = False,
):
    """Fused RMSNorm over the last dimension.

    Reference API: apex/normalization/fused_layer_norm.py
    (FusedRMSNormAffineFunction / FusedRMSNormFunction).
    """
    return _fused_norm(x, weight, None, float(eps), True, bool(memory_efficient))
