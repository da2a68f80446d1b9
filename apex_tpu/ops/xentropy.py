"""Pallas fused softmax-cross-entropy with label smoothing.

TPU rebuild of ``xentropy_cuda`` (apex/contrib/csrc/xentropy/interface.cpp +
xentropy_kernel.cu — fused log-softmax + NLL + label smoothing that saves only
(logsumexp) instead of the full softmax, recomputing probabilities in the
backward; the memory saving over log_softmax+nll_loss is the point).

Semantics (matching the reference kernel):
  loss_i = lse_i - (1-smoothing) * x_i[y_i] - smoothing * mean_v(x_i[v])
  dx_i   = dLoss_i * (softmax(x_i) - (1-smoothing) * onehot(y_i) - smoothing/V)
Rows whose label equals ``padding_idx`` (if given) produce zero loss and zero
gradient.

The full vocab row lives in VMEM (a (8..64, V) fp32 tile — fine up to V in the
hundreds of thousands); logsumexp accumulates in fp32 regardless of input
dtype.
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


def _row_tile(vocab: int, rows: int) -> int:
    # budget sized so the ~5 fp32 intermediates the bwd kernel materializes
    # (x cast, p, onehot match, grad, dx) stay under the default 16MB scoped
    # VMEM limit at BERT/GPT vocab (~30-50k cols)
    return _dispatch.row_tile(vocab, rows, budget_bytes=1024 * 1024,
                              cap=128)


def _fwd_kernel(x_ref, lbl_ref, loss_ref, lse_ref, *, vocab, smoothing,
                padding_idx):
    x = x_ref[...].astype(jnp.float32)
    lbl = lbl_ref[...]  # (tile, 1) int32
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < vocab
    xm = jnp.where(valid, x, -jnp.inf)
    m = jnp.max(xm, axis=-1, keepdims=True)
    sumexp = jnp.sum(jnp.where(valid, jnp.exp(x - m), 0.0), axis=-1,
                     keepdims=True)
    lse = m + jnp.log(sumexp)
    x_t = jnp.sum(jnp.where(cols == lbl, x, 0.0), axis=-1, keepdims=True)
    loss = lse - (1.0 - smoothing) * x_t
    if smoothing > 0.0:
        mean_x = jnp.sum(jnp.where(valid, x, 0.0), axis=-1, keepdims=True) / vocab
        loss = loss - smoothing * mean_x
    if padding_idx is not None:
        loss = jnp.where(lbl == padding_idx, 0.0, loss)
    loss_ref[...] = loss
    lse_ref[...] = lse


def _bwd_kernel(x_ref, lbl_ref, lse_ref, dy_ref, dx_ref, *, vocab, smoothing,
                padding_idx):
    x = x_ref[...].astype(jnp.float32)
    lbl = lbl_ref[...]
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < vocab
    p = jnp.where(valid, jnp.exp(x - lse_ref[...]), 0.0)
    grad = p - (1.0 - smoothing) * (cols == lbl).astype(jnp.float32)
    if smoothing > 0.0:
        grad = grad - jnp.where(valid, smoothing / vocab, 0.0)
    grad = grad * dy_ref[...]
    if padding_idx is not None:
        grad = jnp.where(lbl == padding_idx, 0.0, grad)
    dx_ref[...] = grad.astype(dx_ref.dtype)


def _xent_fwd_call(logits2d, labels, smoothing, padding_idx):
    rows, vocab = logits2d.shape
    tile = _row_tile(vocab, rows)
    v_pad = _dispatch.round_up(vocab, 128)
    r_pad = _dispatch.round_up(rows, tile)
    xp = jnp.pad(logits2d, ((0, r_pad - rows), (0, v_pad - vocab)))
    # pad labels with -1: never matches a column, never equals padding_idx >= 0
    lp = jnp.pad(labels.astype(jnp.int32), (0, r_pad - rows),
                 constant_values=-1).reshape(-1, 1)
    grid = (r_pad // tile,)
    x_spec = pl.BlockSpec((tile, v_pad), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)

    loss, lse = _dispatch.pallas_call(
        functools.partial(_fwd_kernel, vocab=vocab, smoothing=smoothing,
                          padding_idx=padding_idx),
        grid=grid,
        in_specs=[x_spec, s_spec],
        out_specs=[s_spec, s_spec],
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((r_pad, 1), jnp.float32),
        ],
        kernel="xentropy_fwd",
        interpret=_INTERPRET(),
    )(xp, lp)
    return loss[:rows, 0], lse[:rows, 0]


def _xent_bwd_call(logits2d, labels, lse, dy, smoothing, padding_idx):
    rows, vocab = logits2d.shape
    tile = _row_tile(vocab, rows)
    v_pad = _dispatch.round_up(vocab, 128)
    r_pad = _dispatch.round_up(rows, tile)
    xp = jnp.pad(logits2d, ((0, r_pad - rows), (0, v_pad - vocab)))
    lp = jnp.pad(labels.astype(jnp.int32), (0, r_pad - rows),
                 constant_values=-1).reshape(-1, 1)
    # padded rows: lse=+inf → p=0; dy=0 anyway
    lsep = jnp.pad(lse, (0, r_pad - rows),
                   constant_values=jnp.inf).reshape(-1, 1)
    dyp = jnp.pad(dy.astype(jnp.float32), (0, r_pad - rows)).reshape(-1, 1)
    grid = (r_pad // tile,)
    x_spec = pl.BlockSpec((tile, v_pad), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    dx = _dispatch.pallas_call(
        functools.partial(_bwd_kernel, vocab=vocab, smoothing=smoothing,
                          padding_idx=padding_idx),
        grid=grid,
        in_specs=[x_spec, s_spec, s_spec, s_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(xp.shape, logits2d.dtype),
        kernel="xentropy_bwd",
        interpret=_INTERPRET(),
    )(xp, lp, lsep, dyp)
    return dx[:rows, :vocab]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent(logits2d, labels, smoothing, padding_idx):
    loss, _ = _xent_fwd_call(logits2d, labels, smoothing, padding_idx)
    return loss


def _xent_vfwd(logits2d, labels, smoothing, padding_idx):
    loss, lse = _xent_fwd_call(logits2d, labels, smoothing, padding_idx)
    return loss, (logits2d, labels, lse)


def _xent_vbwd(smoothing, padding_idx, res, dy):
    logits2d, labels, lse = res
    dx = _xent_bwd_call(logits2d, labels, lse, dy, smoothing, padding_idx)
    return dx, None


_xent.defvjp(_xent_vfwd, _xent_vbwd)


def softmax_cross_entropy(
    logits,
    labels,
    smoothing: float = 0.0,
    padding_idx: Optional[int] = None,
):
    """Fused label-smoothed softmax cross entropy, per-row losses (fp32).

    Args:
      logits: [..., vocab] any float dtype (fp32 accumulation inside).
      labels: [...] int class ids.
      smoothing: label-smoothing factor in [0, 1).
      padding_idx: rows with this label get zero loss/grad (reference:
        apex/contrib/xentropy/softmax_xentropy.py SoftmaxCrossEntropyLoss).
    """
    if not 0.0 <= smoothing < 1.0:
        raise ValueError(f"smoothing must be in [0, 1), got {smoothing}")
    vocab = logits.shape[-1]
    lead = logits.shape[:-1]
    loss = _xent(logits.reshape(-1, vocab), labels.reshape(-1),
                 float(smoothing),
                 None if padding_idx is None else int(padding_idx))
    return loss.reshape(lead)
