"""NHWC GroupNorm (+ fused SiLU) with a Pallas forward kernel.

Reference: apex/contrib/csrc/group_norm/ (~2k LoC:
group_norm_nhwc_fwd/bwd*.cu, tuned for diffusion-model shapes) wrapped by
apex/contrib/group_norm/group_norm.py's ``GroupNorm`` (a torch GroupNorm
drop-in with ``act="silu"`` fusion).

TPU restatement: NHWC is already the natural TPU layout (channels on
lanes). The forward kernel processes one (sample, group) slab per grid step
— fp32 mean/var, normalize, affine, optional SiLU in a single VMEM pass —
and saves (mean, rstd) for the backward, which is the standard GroupNorm
two-reduction gradient expressed in jnp (XLA fuses it into two passes; the
reference's bwd kernels do the same reductions by hand). Shapes whose
per-group channel count isn't lane-aligned (cg % 128 != 0) or whose slab
exceeds VMEM fall back to the identical-math jnp path, mirroring the
reference's per-shape kernel dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch

_INTERPRET = _dispatch.interpret


def _silu(x):
    return x * jax.nn.sigmoid(x)


def group_norm_reference(x, weight, bias, num_groups, eps,
                         act: Optional[str] = None):
    """Pure-jnp GroupNorm (fp32 accumulation) — fallback path and the
    ground truth for kernel parity tests."""
    n, h, w, c = x.shape
    g = num_groups
    x32 = x.astype(jnp.float32).reshape(n, h * w, g, c // g)
    mean = x32.mean(axis=(1, 3), keepdims=True)
    var = ((x32 - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y.reshape(n, h, w, c)
    if weight is not None:
        y = y * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    if act == "silu":
        y = _silu(y)
    return y.astype(x.dtype)


def _gn_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref,
                   *, eps, act, affine):
    x = x_ref[0].astype(jnp.float32)            # (hw, cg) one (n, g) slab
    mean = jnp.mean(x)
    var = jnp.mean(x * x) - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    if act == "silu":
        y = _silu(y)
    y_ref[0] = y.astype(y_ref.dtype)
    # stats ride in ONE whole-array SMEM block (Mosaic rejects (1, 1)
    # grid-blocked outputs: block dims must be (8, 128)-divisible or equal
    # the array's — seen on the chip in round 3); each step writes its own row
    i = pl.program_id(0)
    mean_ref[i, 0] = mean
    rstd_ref[i, 0] = rstd


def _kernel_eligible(hw: int, cg: int) -> bool:
    return cg % 128 == 0 and hw % 8 == 0 and hw * cg * 4 <= 8 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def group_norm_nhwc(x, weight, bias, num_groups: int, eps: float = 1e-5,
                    act: Optional[str] = None):
    """GroupNorm over NHWC input; ``act='silu'`` fuses the activation.

    ``weight``/``bias`` may be None (no affine). Differentiable.
    """
    y, _ = _gn_fwd(x, weight, bias, num_groups, eps, act)
    return y


def _gn_fwd(x, weight, bias, num_groups, eps, act):
    if act not in (None, "", "silu"):
        raise ValueError(f"unsupported act {act!r} (reference: silu only)")
    n, h, w_, c = x.shape
    g = num_groups
    if c % g != 0:
        raise ValueError(f"channels {c} not divisible by groups {g}")
    cg = c // g
    hw = h * w_
    affine = weight is not None

    if not _kernel_eligible(hw, cg):
        y = group_norm_reference(x, weight, bias, g, eps, act)
        return y, None  # bwd recomputes stats (fallback shapes are small)

    x_slab = x.reshape(n, hw, g, cg).transpose(0, 2, 1, 3).reshape(
        n * g, hw, cg)
    if affine:
        w_slab = jnp.tile(weight.reshape(1, g, 1, cg), (n, 1, 1, 1)
                          ).reshape(n * g, 1, cg)
        b_slab = jnp.tile(bias.reshape(1, g, 1, cg), (n, 1, 1, 1)
                          ).reshape(n * g, 1, cg)
    else:
        w_slab = jnp.zeros((n * g, 1, cg), x.dtype)
        b_slab = jnp.zeros((n * g, 1, cg), x.dtype)

    y_slab, mean, rstd = _dispatch.pallas_call(
        functools.partial(_gn_fwd_kernel, eps=eps, act=act or None,
                          affine=affine),
        grid=(n * g,),
        in_specs=[
            pl.BlockSpec((1, hw, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, hw, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n * g, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n * g, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * g, hw, cg), x.dtype),
            jax.ShapeDtypeStruct((n * g, 1), jnp.float32),
            jax.ShapeDtypeStruct((n * g, 1), jnp.float32),
        ],
        kernel="group_norm_fwd",
        interpret=_INTERPRET(),
    )(x_slab, w_slab, b_slab)
    y = y_slab.reshape(n, g, hw, cg).transpose(0, 2, 1, 3).reshape(n, h, w_, c)
    return y, (mean.reshape(n, g), rstd.reshape(n, g))


def _gn_fwd_vjp(x, weight, bias, num_groups, eps, act):
    y, saved = _gn_fwd(x, weight, bias, num_groups, eps, act)
    return y, (x, weight, bias, saved)


def _gn_bwd_kernel(x_ref, dy_ref, w_ref, b_ref, mean_ref, rstd_ref,
                   dx_ref, dwp_ref, dbp_ref, *, act, affine, m):
    """One (n, g) slab in a single VMEM pass: silu grad, dw/db partials,
    the two group reductions, and dx — the Pallas answer to the reference's
    group_norm_nhwc_bwd kernels (one-pass vs XLA's ~30 tensor sweeps for
    the jnp formulation, measured via cost_analysis; docs/normalization.md)."""
    x = x_ref[0].astype(jnp.float32)
    dy = dy_ref[0].astype(jnp.float32)
    i = pl.program_id(0)                        # stats: whole-array SMEM block
    mean = mean_ref[i, 0]
    rstd = rstd_ref[i, 0]
    xhat = (x - mean) * rstd
    if act == "silu":
        wv = w_ref[0].astype(jnp.float32) if affine else 1.0
        bv = b_ref[0].astype(jnp.float32) if affine else 0.0
        y_pre = xhat * wv + bv
        sig = jax.nn.sigmoid(y_pre)
        dy = dy * (sig * (1.0 + y_pre * (1.0 - sig)))
    dwp_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbp_ref[0] = jnp.sum(dy, axis=0, keepdims=True)
    dyw = dy * w_ref[0].astype(jnp.float32) if affine else dy
    sum_dy = jnp.sum(dyw)
    sum_dy_xhat = jnp.sum(dyw * xhat)
    dx_ref[0] = (rstd * (dyw - sum_dy / m - xhat * sum_dy_xhat / m)
                 ).astype(dx_ref.dtype)


def _bwd_kernel_eligible(hw: int, cg: int) -> bool:
    # three live fp32 slabs (x, dy, dx) must fit VMEM alongside temps
    return cg % 128 == 0 and hw % 8 == 0 and hw * cg * 4 <= 2 * 1024 * 1024


def _gn_bwd(num_groups, eps, act, res, dy):
    x, weight, bias, saved = res
    n, h, w_, c = x.shape
    g = num_groups
    cg = c // g
    hw = h * w_
    affine = weight is not None

    if saved is None or not _bwd_kernel_eligible(hw, cg):
        return _gn_bwd_jnp(num_groups, eps, act, res, dy)

    mean, rstd = saved
    x_slab = x.reshape(n, hw, g, cg).transpose(0, 2, 1, 3).reshape(
        n * g, hw, cg)
    dy_slab = dy.reshape(n, hw, g, cg).transpose(0, 2, 1, 3).reshape(
        n * g, hw, cg)
    if affine:
        w_slab = jnp.tile(weight.reshape(1, g, 1, cg), (n, 1, 1, 1)
                          ).reshape(n * g, 1, cg)
        b_slab = jnp.tile(bias.reshape(1, g, 1, cg), (n, 1, 1, 1)
                          ).reshape(n * g, 1, cg)
    else:
        w_slab = jnp.zeros((n * g, 1, cg), x.dtype)
        b_slab = jnp.zeros((n * g, 1, cg), x.dtype)

    dx_slab, dwp, dbp = _dispatch.pallas_call(
        functools.partial(_gn_bwd_kernel, act=act or None, affine=affine,
                          m=float(hw * cg)),
        grid=(n * g,),
        in_specs=[
            pl.BlockSpec((1, hw, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, hw, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((n * g, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n * g, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, hw, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cg), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * g, hw, cg), x.dtype),
            jax.ShapeDtypeStruct((n * g, 1, cg), jnp.float32),
            jax.ShapeDtypeStruct((n * g, 1, cg), jnp.float32),
        ],
        kernel="group_norm_bwd",
        interpret=_INTERPRET(),
    )(x_slab, dy_slab, w_slab, b_slab,
      mean.reshape(n * g, 1), rstd.reshape(n * g, 1))

    dx = dx_slab.reshape(n, g, hw, cg).transpose(0, 2, 1, 3).reshape(
        n, h, w_, c)
    if affine:
        # cross-sample accumulation of the per-slab partials ([n*g, cg])
        dw = dwp.reshape(n, g * cg).sum(axis=0).astype(weight.dtype)
        db = dbp.reshape(n, g * cg).sum(axis=0).astype(bias.dtype)
    else:
        dw = db = None
    return dx, dw, db


def _gn_bwd_jnp(num_groups, eps, act, res, dy):
    """Standard GroupNorm gradient (the reference's bwd kernels compute the
    same two per-group reductions); SiLU grad folded in first. Fallback for
    non-lane-aligned / oversized slabs and for the jnp-forward path."""
    x, weight, bias, saved = res
    n, h, w_, c = x.shape
    g = num_groups
    cg = c // g
    hw = h * w_
    affine = weight is not None

    x32 = x.astype(jnp.float32).reshape(n, hw, g, cg)
    if saved is not None:
        mean, rstd = saved
        mean = mean.reshape(n, 1, g, 1)
        rstd = rstd.reshape(n, 1, g, 1)
    else:
        mean = x32.mean(axis=(1, 3), keepdims=True)
        var = ((x32 - mean) ** 2).mean(axis=(1, 3), keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * rstd

    dy32 = dy.astype(jnp.float32).reshape(n, hw, g, cg)
    if act == "silu":
        # y_pre = affine(xhat); recompute to route grad through silu
        wv = (weight.astype(jnp.float32).reshape(1, 1, g, cg)
              if affine else 1.0)
        bv = (bias.astype(jnp.float32).reshape(1, 1, g, cg)
              if affine else 0.0)
        y_pre = xhat * wv + bv
        sig = jax.nn.sigmoid(y_pre)
        dy32 = dy32 * (sig * (1.0 + y_pre * (1.0 - sig)))

    if affine:
        dw = jnp.sum(dy32 * xhat, axis=(0, 1)).reshape(c)
        db = jnp.sum(dy32, axis=(0, 1)).reshape(c)
        dyw = dy32 * weight.astype(jnp.float32).reshape(1, 1, g, cg)
        dw = dw.astype(weight.dtype)
        db = db.astype(bias.dtype)
    else:
        dw = db = None
        dyw = dy32

    m = hw * cg
    sum_dy = dyw.sum(axis=(1, 3), keepdims=True)
    sum_dy_xhat = (dyw * xhat).sum(axis=(1, 3), keepdims=True)
    dx = rstd * (dyw - sum_dy / m - xhat * sum_dy_xhat / m)
    dx = dx.reshape(n, h, w_, c).astype(x.dtype)
    return dx, dw, db


group_norm_nhwc.defvjp(_gn_fwd_vjp, _gn_bwd)
