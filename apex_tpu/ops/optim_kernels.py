"""Fused optimizer-update Pallas kernels over flat parameter buffers.

TPU-native rebuild of apex's ``amp_C`` multi-tensor kernel family
(csrc/multi_tensor_adam.cu, multi_tensor_lamb.cu + _stage_1/_stage_2,
multi_tensor_novograd.cu, multi_tensor_sgd_kernel.cu,
multi_tensor_l2norm_kernel.cu, multi_tensor_scale_kernel.cu): one launch
updates every parameter of a network. Here the parameters live in one
lane-aligned ``(rows, 1024)`` fp32 buffer (see flat_buffer.py); kernels tile
rows into VMEM, read hyperparameters from SMEM, and compute per-tensor
reductions (LAMB trust ratios, NovoGrad per-layer moments, l2norms) with a
row->segment one-hot matmul on the MXU — replacing the CUDA per-chunk
shared-memory reductions. Inf/NaN detection (the ``noop_flag`` of the
reference) is fused into the stats kernel; update kernels take a ``noop``
scalar that turns the step into an identity (dynamic-loss-scaling skip).

All kernels donate p/m/v via input_output_aliases (no extra HBM copies).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch
from apex_tpu.ops.flat_buffer import LANE

_INTERPRET = _dispatch.interpret

STAT_SUMSQ_A = 0   # per-segment sum of squares of buffer A
STAT_SUMSQ_B = 1   # per-segment sum of squares of buffer B
STAT_NONFINITE = 2  # per-segment count of non-finite entries of buffer A
_STAT_ROWS = 8     # fp32 sublane minimum


def _seg_pad(num_segments: int) -> int:
    return max(128, _dispatch.round_up(num_segments, 128))


def _row_block(total_rows: int, n_bufs: int = 5) -> int:
    """Rows per grid step, sized to Mosaic's 16 MB scoped-VMEM stack.

    ``n_bufs`` counts the big (blk, LANE) fp32 blocks live per step (inputs
    + outputs). The Adam kernel (7 buffers + ~10 body temporaries) measured
    17.91 MB of scoped stack at blk=256 — over the limit (caught offline by
    tpu_aot.py at the BERT-Large buffer shape); halving the block halves the
    stack. Kernels with <=6 buffers fit at 256.
    """
    cap = 256 if n_bufs <= 6 else 128
    return min(cap, _dispatch.round_up(total_rows, 8))


def _grid(total_rows: int, blk: int):
    return (_dispatch.cdiv(total_rows, blk),)


def _smem_spec(n):
    return pl.BlockSpec((1, n), lambda i: (0, 0), memory_space=pltpu.SMEM)


def _buf_spec(blk):
    return pl.BlockSpec((blk, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM)


def _seg_spec(blk):
    return pl.BlockSpec((blk, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)


# =============================================================================
# segment stats: per-tensor sumsq (+ nonfinite count) in one pass
#   (reference: csrc/multi_tensor_l2norm_kernel.cu per_tensor=True, and the
#    noop_flag inf/nan detection of multi_tensor_scale_kernel.cu)
# =============================================================================

def _stats_kernel(a_ref, b_ref, seg_ref, out_ref, *, s_pad, total_rows, blk, with_b):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row_ids = lax.broadcasted_iota(jnp.int32, (blk, 1), 0) + i * blk
    valid = row_ids < total_rows  # (blk, 1) bool
    # Out-of-bounds rows of a partial final block read unspecified memory;
    # they must be where-selected to zero (a multiplicative mask would turn
    # NaN garbage into NaN: 0 * NaN = NaN).
    a = jnp.where(valid, a_ref[...].astype(jnp.float32), 0.0)

    seg = seg_ref[...]  # (blk, 1) int32
    one_hot = (seg == lax.broadcasted_iota(jnp.int32, (blk, s_pad), 1)).astype(jnp.float32)
    one_hot = one_hot * valid.astype(jnp.float32)

    sumsq_a = jnp.sum(a * a, axis=1)[None, :]      # (1, blk)
    nonfin = jnp.sum(1.0 - jnp.isfinite(a).astype(jnp.float32), axis=1)[None, :]
    rows = [sumsq_a]
    if with_b:
        b = jnp.where(valid, b_ref[...].astype(jnp.float32), 0.0)
        rows.append(jnp.sum(b * b, axis=1)[None, :])
    else:
        rows.append(jnp.zeros_like(sumsq_a))
    rows.append(nonfin)
    stat_rows = jnp.concatenate(rows + [jnp.zeros((_STAT_ROWS - 3, blk), jnp.float32)], axis=0)
    # (_STAT_ROWS, blk) @ (blk, s_pad) -> per-segment partials on the MXU
    out_ref[...] += jnp.dot(stat_rows, one_hot, preferred_element_type=jnp.float32)


def segment_stats(a, seg_rows, num_segments: int, b: Optional[jax.Array] = None):
    """Per-segment [sumsq(a), sumsq(b), nonfinite(a)] — one pass over HBM.

    Returns (``_STAT_ROWS``, s_pad) fp32; rows indexed by ``STAT_*``.
    """
    total_rows = a.shape[0]
    blk = _row_block(total_rows)
    s_pad = _seg_pad(num_segments)
    with_b = b is not None

    in_specs = [_buf_spec(blk)]
    args = [a]
    if with_b:
        in_specs.append(_buf_spec(blk))
        args.append(b)
    in_specs.append(_seg_spec(blk))
    args.append(seg_rows.reshape(-1, 1))

    def fn(*refs):
        if with_b:
            a_ref, b_ref, seg_ref, out_ref = refs
        else:
            a_ref, seg_ref, out_ref = refs
            b_ref = None
        _stats_kernel(a_ref, b_ref, seg_ref, out_ref,
                      s_pad=s_pad, total_rows=total_rows, blk=blk, with_b=with_b)

    return _dispatch.pallas_call(
        fn,
        grid=_grid(total_rows, blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((_STAT_ROWS, s_pad), jnp.float32),
        kernel="l2norm",
        interpret=_INTERPRET(),
    )(*args)


def global_grad_norm_and_finite(g_flat, seg_rows, num_segments):
    """Global L2 norm of the flat grad buffer + all-finite flag (fused pass)."""
    stats = segment_stats(g_flat, seg_rows, num_segments)
    gnorm_sq = jnp.sum(stats[STAT_SUMSQ_A])
    finite = jnp.sum(stats[STAT_NONFINITE]) == 0.0
    return jnp.sqrt(gnorm_sq), finite, stats


# =============================================================================
# Adam / AdamW  (reference: csrc/multi_tensor_adam.cu, apex FusedAdam)
# =============================================================================

_ADAM_HP = 9  # b1, b2, eps, wd, lr, rbc1, rbc2, grad_scale, noop


def _adam_kernel(hp_ref, g_ref, p_ref, m_ref, v_ref, seg_ref, wd_ref,
                 p_out, m_out, v_out, *, adam_w, per_tensor_wd, s_pad):
    b1 = hp_ref[0, 0]
    b2 = hp_ref[0, 1]
    eps = hp_ref[0, 2]
    if per_tensor_wd:
        blk = g_ref.shape[0]
        one_hot = (seg_ref[...] == lax.broadcasted_iota(jnp.int32, (blk, s_pad), 1)).astype(jnp.float32)
        wd = jnp.sum(one_hot * wd_ref[0:1, :], axis=1, keepdims=True)  # (blk, 1)
    else:
        wd = hp_ref[0, 3]
    lr = hp_ref[0, 4]
    rbc1 = hp_ref[0, 5]   # 1/(1-b1^t)
    rbc2 = hp_ref[0, 6]   # 1/(1-b2^t)
    gscale = hp_ref[0, 7]  # unscale * clip factor
    noop = hp_ref[0, 8]

    g = g_ref[...].astype(jnp.float32) * gscale
    p = p_ref[...]
    if not adam_w:
        g = g + wd * p  # L2 mode (reference ADAM_MODE_1)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m * rbc1
    vhat = v * rbc2
    update = mhat / (jnp.sqrt(vhat) + eps)
    if adam_w:
        update = update + wd * p  # decoupled (reference ADAM_MODE_0 / adam_w_mode)
    # where-select (not arithmetic blend): with non-finite grads a 0*inf
    # blend would write NaNs; noop must leave state bit-identical.
    skip = noop > 0.0
    p_out[...] = jnp.where(skip, p, p - lr * update)
    m_out[...] = jnp.where(skip, m_ref[...], m)
    v_out[...] = jnp.where(skip, v_ref[...], v)


def adam_update(g, p, m, v, *, beta1, beta2, eps, weight_decay, lr, step,
                grad_scale=None, noop=None, adam_w_mode=True, bias_correction=True,
                seg_rows=None, num_segments=None):
    """One fused Adam(W) step over flat buffers. Scalars may be traced.

    ``weight_decay`` may be a scalar, or a (num_segments,) per-tensor vector
    when ``seg_rows``/``num_segments`` are given (apex param-group parity).

    Returns (p, m, v) — inputs are donated/aliased.
    """
    total_rows = p.shape[0]
    blk = _row_block(total_rows, n_bufs=7)  # g,p,m,v in + p,m,v out
    one = jnp.float32(1.0)
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        rbc1 = one / (one - jnp.asarray(beta1, jnp.float32) ** step)
        rbc2 = one / (one - jnp.asarray(beta2, jnp.float32) ** step)
    else:
        rbc1 = rbc2 = one

    wd = jnp.asarray(weight_decay, jnp.float32)
    per_tensor_wd = wd.ndim > 0
    if per_tensor_wd and (seg_rows is None or num_segments is None):
        raise ValueError("per-tensor weight_decay requires seg_rows and num_segments")
    s_pad = _seg_pad(num_segments) if per_tensor_wd else 128

    hp = jnp.stack([
        jnp.asarray(beta1, jnp.float32), jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.zeros((), jnp.float32) if per_tensor_wd else wd,
        jnp.asarray(lr, jnp.float32), rbc1, rbc2,
        one if grad_scale is None else jnp.asarray(grad_scale, jnp.float32),
        jnp.zeros((), jnp.float32) if noop is None else jnp.asarray(noop, jnp.float32),
    ]).reshape(1, _ADAM_HP)

    in_specs = [_smem_spec(_ADAM_HP)] + [_buf_spec(blk)] * 4
    args = [hp, g, p, m, v]
    aliases = {2: 0, 3: 1, 4: 2}
    if per_tensor_wd:
        wd_mat = jnp.zeros((_STAT_ROWS, s_pad), jnp.float32).at[0, :num_segments].set(wd)
        in_specs += [_seg_spec(blk),
                     pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM)]
        args += [seg_rows.reshape(-1, 1), wd_mat]

    def fn(*refs):
        if per_tensor_wd:
            hp_ref, g_ref, p_ref, m_ref, v_ref, seg_ref, wd_ref, po, mo, vo = refs
        else:
            hp_ref, g_ref, p_ref, m_ref, v_ref, po, mo, vo = refs
            seg_ref = wd_ref = None
        _adam_kernel(hp_ref, g_ref, p_ref, m_ref, v_ref, seg_ref, wd_ref,
                     po, mo, vo, adam_w=adam_w_mode,
                     per_tensor_wd=per_tensor_wd, s_pad=s_pad)

    return _dispatch.pallas_call(
        fn,
        grid=_grid(total_rows, blk),
        in_specs=in_specs,
        out_specs=[_buf_spec(blk)] * 3,
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 3,  # tpu-lint: disable=pallas-dtype-drift -- fp32 master params/state by contract
        input_output_aliases=aliases,
        kernel="adam",
        interpret=_INTERPRET(),
    )(*args)


# =============================================================================
# SGD (+momentum/nesterov)  (reference: csrc/multi_tensor_sgd_kernel.cu)
# =============================================================================

_SGD_HP = 6  # lr, momentum, dampening, wd, nesterov, noop(+first_run via mu scale)


def _sgd_kernel(hp_ref, g_ref, p_ref, m_ref, p_out, m_out, *, use_momentum):
    lr = hp_ref[0, 0]
    mu = hp_ref[0, 1]
    damp = hp_ref[0, 2]
    wd = hp_ref[0, 3]
    nesterov = hp_ref[0, 4]
    noop = hp_ref[0, 5]

    g = g_ref[...].astype(jnp.float32)
    p = p_ref[...]
    g = g + wd * p
    if use_momentum:
        m = mu * m_ref[...] + (1.0 - damp) * g
        d = nesterov * (g + mu * m) + (1.0 - nesterov) * m
    else:
        m = m_ref[...]
        d = g
    skip = noop > 0.0
    p_out[...] = jnp.where(skip, p, p - lr * d)
    m_out[...] = jnp.where(skip, m_ref[...], m)


def sgd_update(g, p, m, *, lr, momentum=0.0, dampening=0.0, weight_decay=0.0,
               nesterov=False, noop=None, step=None):
    """``step`` (1-based) reproduces the torch/apex first-use rule: the
    momentum buffer is initialized with the raw gradient (no dampening) on
    the first step."""
    total_rows = p.shape[0]
    blk = _row_block(total_rows)
    damp = jnp.asarray(dampening, jnp.float32)
    if step is not None:
        damp = jnp.where(jnp.asarray(step, jnp.float32) <= 1.0, 0.0, damp)
    hp = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(momentum, jnp.float32),
        damp, jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(1.0 if nesterov else 0.0, jnp.float32),
        jnp.zeros((), jnp.float32) if noop is None else jnp.asarray(noop, jnp.float32),
    ]).reshape(1, _SGD_HP)
    use_momentum = not (isinstance(momentum, (int, float)) and momentum == 0.0)

    return _dispatch.pallas_call(
        functools.partial(_sgd_kernel, use_momentum=use_momentum),
        grid=_grid(total_rows, blk),
        in_specs=[_smem_spec(_SGD_HP)] + [_buf_spec(blk)] * 3,
        out_specs=[_buf_spec(blk)] * 2,
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 2,  # tpu-lint: disable=pallas-dtype-drift -- fp32 master params/momentum by contract
        input_output_aliases={2: 0, 3: 1},
        kernel="sgd",
        interpret=_INTERPRET(),
    )(hp, g, p, m)


# =============================================================================
# LAMB  (reference: csrc/multi_tensor_lamb.cu — phase 1 computes the adam-style
#        direction + per-tensor ||p|| and ||u||; phase 2 applies trust ratio)
# =============================================================================

_LAMB_HP = 9  # b1, b2, eps, beta3, rbc1, rbc2, grad_scale, noop, (unused)


def _lamb_phase1_kernel(hp_ref, g_ref, p_ref, m_ref, v_ref, seg_ref, wd_ref,
                        u_out, m_out, v_out, stats_out, *, s_pad, total_rows, blk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        stats_out[...] = jnp.zeros_like(stats_out)

    b1 = hp_ref[0, 0]
    b2 = hp_ref[0, 1]
    eps = hp_ref[0, 2]
    beta3 = hp_ref[0, 3]  # grad_averaging ? (1-b1) : 1  (reference semantics)
    rbc1 = hp_ref[0, 4]
    rbc2 = hp_ref[0, 5]
    gscale = hp_ref[0, 6]
    noop = hp_ref[0, 7]

    g = g_ref[...].astype(jnp.float32) * gscale
    p = p_ref[...]
    seg_one_hot = (seg_ref[...] == lax.broadcasted_iota(jnp.int32, (blk, s_pad), 1)).astype(jnp.float32)
    # per-tensor weight decay (apex expresses this via param groups; here it is
    # a per-segment vector gathered through the same one-hot)
    wd = jnp.sum(seg_one_hot * wd_ref[0:1, :], axis=1, keepdims=True)  # (blk, 1)
    m = b1 * m_ref[...] + beta3 * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mhat = m * rbc1
    vhat = v * rbc2
    u = mhat / (jnp.sqrt(vhat) + eps) + wd * p

    skip = noop > 0.0
    u_out[...] = jnp.where(skip, 0.0, u)
    m_out[...] = jnp.where(skip, m_ref[...], m)
    v_out[...] = jnp.where(skip, v_ref[...], v)

    row_ids = lax.broadcasted_iota(jnp.int32, (blk, 1), 0) + i * blk
    valid = row_ids < total_rows
    one_hot = seg_one_hot * valid.astype(jnp.float32)
    # where-select (not multiply): OOB rows may hold NaN garbage
    p_safe = jnp.where(valid, p, 0.0)
    u_safe = jnp.where(valid & jnp.logical_not(skip), u, 0.0)
    sumsq_p = jnp.sum(p_safe * p_safe, axis=1)[None, :]
    sumsq_u = jnp.sum(u_safe * u_safe, axis=1)[None, :]
    stat_rows = jnp.concatenate(
        [sumsq_p, sumsq_u, jnp.zeros((_STAT_ROWS - 2, blk), jnp.float32)], axis=0
    )
    stats_out[...] += jnp.dot(stat_rows, one_hot, preferred_element_type=jnp.float32)


def _lamb_phase2_kernel(hp_ref, u_ref, p_ref, ratio_ref, seg_ref, p_out, *, s_pad, blk):
    lr = hp_ref[0, 0]
    noop = hp_ref[0, 1]
    one_hot = (seg_ref[...] == lax.broadcasted_iota(jnp.int32, (blk, s_pad), 1)).astype(jnp.float32)
    # gather per-row trust ratio: (blk, s_pad) * (1, s_pad) summed over segs
    ratio = jnp.sum(one_hot * ratio_ref[0:1, :], axis=1, keepdims=True)  # (blk, 1)
    p = p_ref[...]
    p_out[...] = jnp.where(noop > 0.0, p, p - lr * ratio * u_ref[...])


def lamb_update(g, p, m, v, seg_rows, num_segments, *, beta1, beta2, eps,
                weight_decay, lr, step, grad_scale=None, noop=None,
                bias_correction=True, grad_averaging=True, use_nvlamb=False,
                stats_psum_axis=None):
    """Fused LAMB step: phase-1 kernel (direction + per-tensor norms on the
    MXU) then phase-2 kernel (trust-ratio apply). Mirrors the two-stage
    structure of csrc/multi_tensor_lamb.cu.

    ``weight_decay`` may be a scalar or a (num_segments,) per-tensor vector
    (apex expresses the latter via param groups).

    Trust ratio: ||p|| / ||u|| where defined; 1.0 otherwise (and for tensors
    excluded unless use_nvlamb — reference semantics).

    ``stats_psum_axis``: when the flat buffers are ROW-SHARDS of a larger
    buffer (ZeRO: DistributedFusedLAMB), per-tensor ||p||/||u|| partials must
    be summed across shard ranks between the phases — the analog of the
    reference's allreduce between multi_tensor_lamb_stage_1 and _stage_2
    (apex/contrib/optimizers/distributed_fused_lamb.py).
    """
    total_rows = p.shape[0]
    # phase 1 holds SEVEN big (blk, LANE) fp32 buffers (g,p,m,v in +
    # u,m,v out) — the same count that pushed Adam to 17.91 MB of scoped
    # VMEM at blk=256; cap the block at 128 (ADVICE r5)
    blk = _row_block(total_rows, n_bufs=7)
    s_pad = _seg_pad(num_segments)
    one = jnp.float32(1.0)
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        rbc1 = one / (one - jnp.asarray(beta1, jnp.float32) ** step)
        rbc2 = one / (one - jnp.asarray(beta2, jnp.float32) ** step)
    else:
        rbc1 = rbc2 = one
    beta3 = (one - jnp.asarray(beta1, jnp.float32)) if grad_averaging else one
    noop_s = jnp.zeros((), jnp.float32) if noop is None else jnp.asarray(noop, jnp.float32)
    hp1 = jnp.stack([
        jnp.asarray(beta1, jnp.float32), jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32), beta3,
        rbc1, rbc2,
        one if grad_scale is None else jnp.asarray(grad_scale, jnp.float32),
        noop_s, jnp.zeros((), jnp.float32),
    ]).reshape(1, _LAMB_HP)

    wd = jnp.asarray(weight_decay, jnp.float32)
    if wd.ndim == 0:
        wd_vec = jnp.full((num_segments,), wd, jnp.float32)
    else:
        wd_vec = wd
    wd_mat = jnp.zeros((_STAT_ROWS, s_pad), jnp.float32).at[0, :num_segments].set(wd_vec)

    seg2d = seg_rows.reshape(-1, 1)
    u, m, v, stats = _dispatch.pallas_call(
        functools.partial(_lamb_phase1_kernel, s_pad=s_pad, total_rows=total_rows, blk=blk),
        grid=_grid(total_rows, blk),
        in_specs=[_smem_spec(_LAMB_HP)] + [_buf_spec(blk)] * 4 + [_seg_spec(blk)]
        + [pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM)],
        out_specs=[_buf_spec(blk)] * 3
        + [pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 3  # tpu-lint: disable=pallas-dtype-drift -- fp32 master params/state by contract
        + [jax.ShapeDtypeStruct((_STAT_ROWS, s_pad), jnp.float32)],
        input_output_aliases={3: 1, 4: 2},
        kernel="lamb_phase1",
        interpret=_INTERPRET(),
    )(hp1, g, p, m, v, seg2d, wd_mat)

    if stats_psum_axis is not None:
        stats = lax.psum(stats, stats_psum_axis)
    p_norm = jnp.sqrt(stats[0])  # (s_pad,)
    u_norm = jnp.sqrt(stats[1])
    # reference trust-ratio rule (multi_tensor_lamb.cu): ratio = ||p||/||u||
    # when both norms > 0, else 1 — and with use_nvlamb=False (default) the
    # ratio is only applied to weight-decayed tensors; decay-excluded tensors
    # (wd == 0) get ratio 1.
    ratio = jnp.where((p_norm > 0.0) & (u_norm > 0.0), p_norm / jnp.maximum(u_norm, 1e-30), 1.0)
    if not use_nvlamb:
        wd_full = jnp.zeros((s_pad,), jnp.float32).at[:num_segments].set(wd_vec)
        ratio = jnp.where(wd_full > 0.0, ratio, 1.0)
    ratio_mat = jnp.zeros((_STAT_ROWS, s_pad), jnp.float32).at[0].set(ratio)

    hp2 = jnp.stack([jnp.asarray(lr, jnp.float32), noop_s]).reshape(1, 2)
    p_new = _dispatch.pallas_call(
        functools.partial(_lamb_phase2_kernel, s_pad=s_pad, blk=blk),
        grid=_grid(total_rows, blk),
        in_specs=[_smem_spec(2), _buf_spec(blk), _buf_spec(blk),
                  pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
                  _seg_spec(blk)],
        out_specs=_buf_spec(blk),
        out_shape=jax.ShapeDtypeStruct(p.shape, jnp.float32),  # tpu-lint: disable=pallas-dtype-drift -- fp32 master params by contract
        input_output_aliases={2: 0},
        kernel="lamb_phase2",
        interpret=_INTERPRET(),
    )(hp2, u, p, ratio_mat, seg2d)
    return p_new, m, v


# =============================================================================
# NovoGrad  (reference: csrc/multi_tensor_novograd.cu — per-tensor 2nd moment)
# =============================================================================

_NVG_HP = 7  # b1, beta3, eps(unused: folded into vden), wd, lr, grad_scale, noop


def _novograd_kernel(hp_ref, g_ref, p_ref, m_ref, vden_ref, seg_ref,
                     p_out, m_out, *, s_pad, blk):
    b1 = hp_ref[0, 0]
    beta3 = hp_ref[0, 1]  # grad_averaging ? (1-b1) : 1
    wd = hp_ref[0, 3]
    lr = hp_ref[0, 4]
    gscale = hp_ref[0, 5]
    noop = hp_ref[0, 6]

    g = g_ref[...].astype(jnp.float32) * gscale
    p = p_ref[...]
    one_hot = (seg_ref[...] == lax.broadcasted_iota(jnp.int32, (blk, s_pad), 1)).astype(jnp.float32)
    vden = jnp.sum(one_hot * vden_ref[0:1, :], axis=1, keepdims=True)  # sqrt(v_t)+eps per row
    gn = g / vden + wd * p
    m = b1 * m_ref[...] + beta3 * gn
    skip = noop > 0.0
    p_out[...] = jnp.where(skip, p, p - lr * m)
    m_out[...] = jnp.where(skip, m_ref[...], m)


def novograd_update(g, p, m, v_per_tensor, seg_rows, num_segments, *, beta1, beta2,
                    eps, weight_decay, lr, step, grad_scale=None, noop=None,
                    grad_averaging=True, init_zero=False):
    """Fused NovoGrad step. ``v_per_tensor`` is the (num_segments,) per-tensor
    second moment ||g||^2 EMA (reference keeps one float per tensor).

    Returns (p, m, v_per_tensor).
    """
    total_rows = p.shape[0]
    blk = _row_block(total_rows)
    s_pad = _seg_pad(num_segments)

    gnorm, finite, stats = global_grad_norm_and_finite(g, seg_rows, num_segments)
    gs = jnp.float32(1.0) if grad_scale is None else jnp.asarray(grad_scale, jnp.float32)
    g_sumsq = stats[STAT_SUMSQ_A][:num_segments] * gs * gs
    step = jnp.asarray(step, jnp.float32)
    b2 = jnp.asarray(beta2, jnp.float32)
    # reference first-step rule: v_1 = ||g||^2 unless init_zero (then the EMA
    # runs from zero: v_1 = (1-b2)||g||^2) — apex fused_novograd.py init_zero
    first = (1.0 - b2) * g_sumsq if init_zero else g_sumsq
    v_new = jnp.where(step <= 1.0, first, b2 * v_per_tensor + (1.0 - b2) * g_sumsq)
    vden = jnp.sqrt(v_new) + jnp.asarray(eps, jnp.float32)
    vden_mat = jnp.zeros((_STAT_ROWS, s_pad), jnp.float32).at[0, :num_segments].set(vden)

    noop_s = jnp.zeros((), jnp.float32) if noop is None else jnp.asarray(noop, jnp.float32)
    b1 = jnp.asarray(beta1, jnp.float32)
    beta3 = (1.0 - b1) if grad_averaging else jnp.float32(1.0)
    hp = jnp.stack([
        b1, beta3, jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32), jnp.asarray(lr, jnp.float32),
        gs, noop_s,
    ]).reshape(1, _NVG_HP)

    p_new, m_new = _dispatch.pallas_call(
        functools.partial(_novograd_kernel, s_pad=s_pad, blk=blk),
        grid=_grid(total_rows, blk),
        in_specs=[_smem_spec(_NVG_HP), _buf_spec(blk), _buf_spec(blk), _buf_spec(blk),
                  pl.BlockSpec((_STAT_ROWS, s_pad), lambda i: (0, 0), memory_space=pltpu.VMEM),
                  _seg_spec(blk)],
        out_specs=[_buf_spec(blk)] * 2,
        out_shape=[jax.ShapeDtypeStruct(p.shape, jnp.float32)] * 2,  # tpu-lint: disable=pallas-dtype-drift -- fp32 master params/momentum by contract
        input_output_aliases={2: 0, 3: 1},
        kernel="novograd",
        interpret=_INTERPRET(),
    )(hp, g, p, m, vden_mat, seg_rows.reshape(-1, 1))
    v_out = jnp.where(noop_s > 0.0, v_per_tensor, v_new)
    return p_new, m_new, v_out


# =============================================================================
# scale (amp unscale with found-inf)  (reference: multi_tensor_scale_kernel.cu)
# =============================================================================

def _scale_kernel(hp_ref, x_ref, y_out):
    y_out[...] = x_ref[...].astype(jnp.float32) * hp_ref[0, 0]


def multi_tensor_scale(x, scale):
    """out = x * scale over a flat buffer (one launch)."""
    total_rows = x.shape[0]
    blk = _row_block(total_rows)
    hp = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    return _dispatch.pallas_call(
        _scale_kernel,
        grid=_grid(total_rows, blk),
        in_specs=[_smem_spec(1), _buf_spec(blk)],
        out_specs=_buf_spec(blk),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),  # tpu-lint: disable=pallas-dtype-drift -- amp unscale emits fp32 master grads
        kernel="scale",
        interpret=_INTERPRET(),
    )(hp, x)
