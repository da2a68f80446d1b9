"""int8 quantized matmul (W8A8): true int8 MXU dots for serving.

Beyond reference (apex has no quantization/inference story) — this is the
TPU-native int8 recipe (the AQT pattern): per-output-channel symmetric
weight scales computed offline, DYNAMIC per-token activation scales
computed on the fly, ``int8 x int8 -> int32`` accumulation on the MXU,
then one fused dequant multiply. Weights stream from HBM at 1 byte/elem —
a 4x (vs fp32) / 2x (vs bf16) cut in the weight-fetch bandwidth that
bounds single-token decode.

Inference-only: ``round`` has zero gradient, so a quantized layer cannot
train (the tensor-parallel layers raise if asked to).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops._dispatch import interpret, pallas_call, round_up


def quantize_weight(w, *, axis: int = 1):
    """Symmetric per-output-channel int8: ``w (out, in) -> (q int8 (out,
    in), scale f32 (out,))`` with ``w ≈ q * scale[:, None]``."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.squeeze(axis).astype(jnp.float32)


# --------------------------------------------------------------------------
# quantized KV pages (docs/serving.md "Quantized KV pages")
# --------------------------------------------------------------------------
# The paged pool stores K/V narrow (int8 or fp8 e4m3) with one symmetric
# f32 scale per (page, kv_head) living beside the block table; the paged
# kernel folds the scale into its score/value dots, so a full-precision
# pool is never materialized. Same AQT recipe as the W8A8 path above,
# page-granular instead of channel-granular.

_KV_QMAX = {"int8": 127.0, "fp8": 448.0}          # e4m3 finite max


def resolve_kv_dtype(kv_dtype):
    """Map a user-facing ``kv_dtype`` to ``(jnp dtype, qmax)``.

    ``None`` -> ``None`` (full-precision pool). Accepts ``"int8"`` /
    ``jnp.int8`` and ``"fp8"`` / ``"e4m3"`` / ``jnp.float8_e4m3fn``.
    Raises a NAMED ValueError for anything else — never a silent
    full-precision fallback — and for fp8 on a jax/ml_dtypes build that
    lacks ``float8_e4m3fn``.
    """
    if kv_dtype is None:
        return None
    name = kv_dtype if isinstance(kv_dtype, str) else \
        jnp.dtype(kv_dtype).name
    if name == "int8":
        return jnp.int8, _KV_QMAX["int8"]
    if name in ("fp8", "e4m3", "float8_e4m3fn"):
        if not hasattr(jnp, "float8_e4m3fn"):
            raise ValueError(
                "kv-dtype-unsupported: fp8 KV pages need "
                "jnp.float8_e4m3fn (ml_dtypes); this build lacks it — "
                "use kv_dtype='int8'")
        return jnp.float8_e4m3fn, _KV_QMAX["fp8"]
    raise ValueError(
        f"kv-dtype-unsupported: kv_dtype={kv_dtype!r} is not a "
        f"quantized page dtype (expected None, 'int8', or 'fp8'/'e4m3')")


def kv_qmax(dtype) -> float:
    """qmax of a quantized page dtype already in the pool (int8 -> 127,
    e4m3 -> 448); raises on a non-quantized dtype."""
    name = jnp.dtype(dtype).name
    if name == "int8":
        return _KV_QMAX["int8"]
    if name == "float8_e4m3fn":
        return _KV_QMAX["fp8"]
    raise ValueError(f"kv-dtype-unsupported: {name} is not a quantized "
                     f"KV page dtype")


def is_quantized_kv(dtype) -> bool:
    name = jnp.dtype(dtype).name
    return name == "int8" or name.startswith("float8")


def kv_cast(x, qdtype, qmax):
    """Cast an already-scale-normalized tensor to the page dtype:
    round+clip for int8, saturate-clip for fp8 (the cast rounds)."""
    if jnp.dtype(qdtype) == jnp.int8:
        return jnp.clip(jnp.round(x), -qmax, qmax).astype(jnp.int8)
    return jnp.clip(x, -qmax, qmax).astype(qdtype)


def kv_quantize(x, qdtype, qmax, *, axes):
    """Symmetric quantization over ``axes``: returns ``(q, scale)`` with
    ``x ≈ q.astype(f32) * scale`` (scale broadcast over ``axes``). An
    all-zero group gets scale 0 and quantizes to exact zeros (dequant by
    multiply restores them exactly)."""
    xf = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = amax / qmax
    inv = jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    return kv_cast(xf * inv, qdtype, qmax), scale


def int8_matmul(x, qw, scale):
    """``y = x @ dequant(qw).T`` via an int8 MXU dot.

    x: (..., in) float; qw: (out, in) int8; scale: (out,) f32 per-channel.
    Per-token activation scales (amax/127) quantize x on the fly; the
    contraction accumulates in int32; the result dequantizes by
    ``sx * scale`` and casts back to x.dtype.
    """
    xf = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                     1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / sx), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, qw,
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * scale.astype(jnp.float32)) \
        .astype(x.dtype)


# --------------------------------------------------------------------------
# quantized weight streaming (docs/serving.md "Quantized weight streaming")
# --------------------------------------------------------------------------
# The serving decode step is weight-bound: every step streams the full
# block-linear weight set from HBM. These helpers store those weights
# narrow — int8 / fp8 e4m3 per-output-channel, or int4 nibbles with
# per-(out-channel, group) scales — and the fused Pallas kernel below
# dequantizes in VMEM right next to the contraction, so a full-precision
# weight tree is never materialized (the weight analog of the quantized
# KV pages above).

_WEIGHT_QMAX = {"int8": 127.0, "fp8": 448.0, "int4": 7.0}


def resolve_weight_dtype(mode) -> Optional[str]:
    """Map a user-facing weight-quantization ``mode`` to its canonical
    kind: ``"int8"``, ``"fp8"``, or ``"int4"``.

    ``None``/``False`` -> ``None`` (full-precision weights); ``True`` is
    the back-compat alias for ``"int8"`` (the historical ``quantize_int8``
    switch). Accepts ``"int8"``/``jnp.int8`` and ``"fp8"``/``"e4m3"``/
    ``jnp.float8_e4m3fn``. Raises a NAMED ValueError for anything else —
    never a silent full-precision fallback — and for fp8 on a
    jax/ml_dtypes build that lacks ``float8_e4m3fn``.
    """
    if mode is None or mode is False:
        return None
    if mode is True:
        return "int8"
    name = mode if isinstance(mode, str) else jnp.dtype(mode).name
    if name == "int8":
        return "int8"
    if name in ("fp8", "e4m3", "float8_e4m3fn"):
        if not hasattr(jnp, "float8_e4m3fn"):
            raise ValueError(
                "weight-dtype-unsupported: fp8 weight buffers need "
                "jnp.float8_e4m3fn (ml_dtypes); this build lacks it — "
                "use 'int8'")
        return "fp8"
    if name == "int4":
        return "int4"
    raise ValueError(
        f"weight-dtype-unsupported: mode={mode!r} is not a quantized "
        f"weight dtype (expected None, 'int8', 'fp8'/'e4m3', or 'int4')")


def weight_storage_dtype(kind: str):
    """jnp dtype a quantized weight buffer is stored as (int4 packs two
    nibbles per uint8 byte)."""
    return {"int8": jnp.int8,
            "fp8": getattr(jnp, "float8_e4m3fn", None),
            "int4": jnp.uint8}[kind]


def validate_int4_group(in_features: int, group_size: int) -> None:
    """Named errors for the int4 grouping contract: power-of-two group,
    ``in_features`` an exact multiple of it."""
    if group_size < 2 or (group_size & (group_size - 1)) != 0:
        raise ValueError(
            f"int4-group-invalid: group_size={group_size} must be a "
            "power of two >= 2")
    if in_features % group_size:
        raise ValueError(
            f"int4-group-invalid: in_features={in_features} is not a "
            f"multiple of group_size={group_size}")


def quantize_weight_fp8(w, *, axis: int = 1):
    """Symmetric per-output-channel fp8 e4m3: ``w (out, in) -> (q e4m3,
    scale f32 (out,))`` with ``w ≈ q.astype(f32) * scale[:, None]``."""
    resolve_weight_dtype("fp8")            # raises on builds without e4m3
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _WEIGHT_QMAX["fp8"]
    q = jnp.clip(w / scale, -_WEIGHT_QMAX["fp8"], _WEIGHT_QMAX["fp8"]) \
        .astype(jnp.float8_e4m3fn)
    return q, scale.squeeze(axis).astype(jnp.float32)


def pack_int4(q, *, group_size: int):
    """Pack int4 values ``q (out, in)`` (each in [-8, 7]) into uint8
    nibbles, GROUP-LOCALLY: byte ``j`` of a group's ``group_size // 2``
    bytes holds the group's value ``j`` (low nibble, biased +8) and its
    value ``j + group_size//2`` (high nibble). Packing never crosses a
    group boundary, so a contiguous slice of whole groups along the
    packed axis IS the packed form of those groups — tensor-parallel
    row-sharding slices packed weights directly (serving/tp.py)."""
    out, n = q.shape
    validate_int4_group(n, group_size)
    h = group_size // 2
    qg = q.astype(jnp.int32).reshape(out, n // group_size, group_size)
    packed = (qg[..., :h] + 8) | ((qg[..., h:] + 8) << 4)
    return packed.astype(jnp.uint8).reshape(out, n // 2)


def unpack_int4(packed, *, group_size: int):
    """Inverse of :func:`pack_int4`: ``(out, n//2) uint8 -> (out, n)
    int8`` values in [-8, 7], same group-local layout."""
    out, half = packed.shape
    h = group_size // 2
    p = packed.astype(jnp.int32).reshape(out, half // h, h)
    lo = (p & 15) - 8
    hi = (p >> 4) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8) \
        .reshape(out, 2 * half)


def quantize_weight_int4(w, *, group_size: int = 128):
    """Symmetric per-(out-channel, group) int4: ``w (out, in) ->
    (packed uint8 (out, in//2), scales f32 (n_groups, out))`` with
    ``n_groups = in // group_size`` and, within group ``g``,
    ``w[o, g*gs:(g+1)*gs] ≈ q * scales[g, o]``.

    The scale layout keeps the OUT channel minor (lane-friendly Mosaic
    blocks; shards ``P(model)`` with the output axis under column-
    parallel TP) and the group axis major (contiguous slices of whole
    groups are a row-parallel rank's exact scales). Each group packs its
    own two halves together (:func:`pack_int4`), so the packed bytes of
    group ``g`` are the contiguous columns ``[g*gs//2, (g+1)*gs//2)``.
    """
    w = jnp.asarray(w, jnp.float32)
    out, n = w.shape
    validate_int4_group(n, group_size)
    ng = n // group_size
    wg = w.reshape(out, ng, group_size)
    amax = jnp.max(jnp.abs(wg), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _WEIGHT_QMAX["int4"]
    q = jnp.clip(jnp.round(wg / scale), -7, 7).reshape(out, n)
    return pack_int4(q.astype(jnp.int8), group_size=group_size), \
        scale[:, :, 0].T.astype(jnp.float32)


def dequantize_weight(qw, scale):
    """Reference dequantizer for every storage kind — the parity oracle
    for the fused kernel. int8/fp8: ``(out, in) x (out,)``; int4-packed:
    ``(out, in//2) uint8 x (n_groups, out)``. Returns f32 ``(out, in)``."""
    if qw.dtype == jnp.uint8:
        out, half = qw.shape
        ng = scale.shape[0]
        gs = 2 * half // ng
        vals = unpack_int4(qw, group_size=gs).reshape(out, ng, gs)
        return (vals.astype(jnp.float32)
                * scale.T[:, :, None]).reshape(out, 2 * half)
    return qw.astype(jnp.float32) * scale[:, None].astype(jnp.float32)


# --- the fused dequant-matmul decode kernel -------------------------------

def _block_out(out: int) -> int:
    """Output-channel tile: 256 when it divides (two 128-lane registers),
    else 128, else the full dim (sub-tile dims must equal the array's —
    tiny test models; interpret mode only)."""
    for b in (256, 128):
        if out % b == 0:
            return b
    return out


def _fused_wq_kernel(x_ref, w_ref, s_ref, o_ref):
    """Per-channel (int8/fp8) body: widen the weight block in VMEM, one
    MXU dot, scale as the output epilogue — no fp weight ever in HBM."""
    xf = x_ref[...].astype(jnp.float32)
    wf = w_ref[...].astype(jnp.float32)
    acc = jax.lax.dot_general(xf, wf, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = acc * s_ref[...]          # (1, block_out) broadcasts


def _fused_w4_kernel(x_ref, w_ref, s_ref, o_ref, *, group_size: int,
                     n_groups: int):
    """int4-grouped body: unpack biased nibbles in VMEM, one small dot
    per group (statically unrolled) scaled by that group's (1, block_out)
    scale row. Group-local packing keeps every slice contiguous."""
    h = group_size // 2
    wi = w_ref[...].astype(jnp.int32)
    lo = ((wi & 15) - 8).astype(jnp.float32)
    hi = ((wi >> 4) - 8).astype(jnp.float32)
    xf = x_ref[...].astype(jnp.float32)
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for g in range(n_groups):
        wq = jnp.concatenate([lo[:, g * h:(g + 1) * h],
                              hi[:, g * h:(g + 1) * h]], axis=1)
        xg = xf[:, g * group_size:(g + 1) * group_size]
        acc += jax.lax.dot_general(
            xg, wq, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * s_ref[g:g + 1, :]
    o_ref[...] = acc


def fused_dequant_matmul(x, qw, scale):
    """``y = x @ dequant(qw).T`` with dequant fused into the kernel.

    x: ``(..., in)`` float; ``(qw, scale)`` from :func:`quantize_weight`
    (int8), :func:`quantize_weight_fp8` (e4m3), or
    :func:`quantize_weight_int4` (packed nibbles + grouped scales — the
    storage kind is inferred from the dtypes/shapes). The weights stream
    from HBM at their narrow width and widen only inside VMEM, block by
    block, next to the contraction — unlike :func:`int8_matmul` there is
    no per-call fp32 activation quantize/dequant roundtrip, so the
    result equals the dequantizing reference to f32 dot accuracy
    (weight-only quantization, W8A16-style). Result dtype follows x.
    """
    from jax.experimental import pallas as pl

    int4 = qw.dtype == jnp.uint8
    out = qw.shape[0]
    n_in = 2 * qw.shape[1] if int4 else qw.shape[1]
    lead = x.shape[:-1]
    if x.shape[-1] != n_in:
        raise ValueError(
            f"fused_dequant_matmul: x has {x.shape[-1]} features, the "
            f"quantized weight dequantizes to (out={out}, in={n_in})")
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, n_in)
    m_pad = round_up(max(m, 1), 8)
    if m_pad != m:
        x2 = jnp.pad(x2, ((0, m_pad - m), (0, 0)))
    bo = _block_out(out)
    grid = (out // bo,)
    if int4:
        ng = scale.shape[0]
        gs = n_in // ng
        kernel = lambda *refs: _fused_w4_kernel(*refs, group_size=gs,
                                                n_groups=ng)
        w_spec = pl.BlockSpec((bo, n_in // 2), lambda j: (j, 0))
        s2 = scale                               # (n_groups, out)
        s_spec = pl.BlockSpec((ng, bo), lambda j: (0, j))
    else:
        kernel = _fused_wq_kernel
        w_spec = pl.BlockSpec((bo, n_in), lambda j: (j, 0))
        s2 = scale.astype(jnp.float32).reshape(1, out)
        s_spec = pl.BlockSpec((1, bo), lambda j: (0, j))
    y = pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, out), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((m_pad, n_in), lambda j: (0, 0)),
                  w_spec, s_spec],
        out_specs=pl.BlockSpec((m_pad, bo), lambda j: (0, j)),
        kernel="dequant_matmul",
        interpret=interpret(),
    )(x2, qw, s2.astype(jnp.float32))
    return y[:m].reshape(*lead, out).astype(x.dtype)


# --- per-layer-class precision policy (the amp opt-level analog) ----------

@dataclasses.dataclass(frozen=True)
class WeightPrecisionPolicy:
    """Which precision each layer CLASS serves at (PAPER.md's ``apex.amp``
    O0–O3 opt levels, restated for weight streaming):

    ==============  =============================================
    layer class     precision
    ==============  =============================================
    embeddings      fp (``param_dtype``) — lookup, never streamed hot
    norms, biases   fp (``param_dtype``) — O(hidden) bytes, accuracy-critical
    lm head         fp (``param_dtype``) — logit fidelity
    block linears   ``linears``: None | 'int8' | 'fp8' | 'int4'
    ==============  =============================================

    ``group_size`` applies to the int4-grouped path only (power of two;
    per-(out-channel, group) scales). ``quantize_int8=True`` on a model
    config is the back-compat alias for ``WeightPrecisionPolicy('int8')``.
    """

    linears: Optional[str] = "int8"
    group_size: int = 128

    def __post_init__(self):
        kind = resolve_weight_dtype(self.linears)
        object.__setattr__(self, "linears", kind)
        if kind == "int4" and (self.group_size < 2
                               or self.group_size & (self.group_size - 1)):
            raise ValueError(
                f"int4-group-invalid: group_size={self.group_size} must "
                "be a power of two >= 2")

    @staticmethod
    def resolve(policy: Optional["WeightPrecisionPolicy"],
                quantize_int8: bool) -> Optional["WeightPrecisionPolicy"]:
        """The ONE resolution rule for a model config carrying both the
        legacy ``quantize_int8`` flag and a ``weight_policy``: the flag
        is the int8-everywhere policy; setting both to conflicting
        answers is a named error, never a silent pick."""
        if policy is not None and policy.linears is None:
            policy = None
        if policy is None:
            return WeightPrecisionPolicy("int8") if quantize_int8 else None
        if quantize_int8 and policy.linears != "int8":
            raise ValueError(
                "weight-policy-conflict: quantize_int8=True is the "
                f"int8-everywhere policy but weight_policy asks for "
                f"{policy.linears!r} — set one, not both")
        return policy
