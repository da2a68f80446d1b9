"""Matmul wrappers: the reference's float32 at ``highest``, and the
control's nearest precision below the configuration's bfloat16 — float8
(e4m3) inputs with per-tensor scaling, accumulated in float32.

The control is the reference with ``matmul_fp8`` in ``matmul``'s place: the
step that would tempt a later PR.  Its backward passes straight through the
rounding, as fp8 training recipes do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def matmul(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _round_fp8(x):
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(FP8).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def matmul_fp8(a, b):
    return matmul(_round_fp8(a), _round_fp8(b))


MATMULS = {"float32": matmul, "fp8": matmul_fp8}
