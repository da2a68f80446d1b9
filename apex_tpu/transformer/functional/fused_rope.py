"""Fused rotary positional embedding.

Reference: ``fused_rotary_positional_embedding`` extension
(csrc/megatron/fused_rotary_positional_embedding.h/.cpp/_cuda.cu — RoPE apply
fwd/bwd, cached cos/sin variant). On TPU this is a pure elementwise rewrite
that XLA fuses into the surrounding matmuls, so there is deliberately no
Pallas kernel: a hand kernel would only block fusion (SURVEY.md §2.2 row
"fused_rotary_positional_embedding"). Gradients come from autodiff of the
same expression, which matches the reference backward (rotation transposed).

Layout matches the reference: t [sq, b, np, hn], freqs [sq, 1, 1, hn2<=hn];
only the first hn2 features are rotated (partial-rotary supported).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate((-x2, x1), axis=-1)


def fused_apply_rotary_pos_emb(t, freqs):
    """Apply RoPE with freqs in radians (reference fused_apply_rotary_pos_emb).

    t: [sq, b, np, hn]; freqs: [sq, 1, 1, hn2], hn2 <= hn, hn2 even.
    """
    hn2 = freqs.shape[-1]
    rot, pass_through = t[..., :hn2], t[..., hn2:]
    cos = jnp.cos(freqs).astype(t.dtype)
    sin = jnp.sin(freqs).astype(t.dtype)
    rot = rot * cos + _rotate_half(rot) * sin
    if pass_through.shape[-1] == 0:
        return rot
    return jnp.concatenate((rot, pass_through), axis=-1)


def fused_apply_rotary_pos_emb_cached(t, cos_, sin_):
    """Cached-cos/sin variant (reference fused_apply_rotary_pos_emb_cached)."""
    hn2 = cos_.shape[-1]
    rot, pass_through = t[..., :hn2], t[..., hn2:]
    rot = rot * cos_.astype(t.dtype) + _rotate_half(rot) * sin_.astype(t.dtype)
    if pass_through.shape[-1] == 0:
        return rot
    return jnp.concatenate((rot, pass_through), axis=-1)


def rope_inv_freq(dim: int, theta: float) -> np.ndarray:
    """``theta ** (-2i / dim)`` for the ``dim // 2`` rotated pairs, float32,
    computed on the host (a constant of the program that uses it)."""
    return (1.0 / np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ).astype(np.float32)


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0,
                  truncate: bool = True) -> np.ndarray:
    """YaRN's frequencies (Peng et al. 2023, as ``transformers``'
    ``_compute_yarn_parameters`` computes them): pair ``i`` blends the
    interpolated frequency ``inv_freq_i / factor`` and the plain one over a
    linear ramp between the pairs that make ``beta_fast`` and ``beta_slow``
    turns in ``original_max_position_embeddings`` positions: pairs faster
    than ``beta_fast`` turns stay plain, pairs slower than ``beta_slow``
    are interpolated. Static: it does not follow the length seen. The
    caller multiplies cos and sin by the configuration's
    ``attention_factor``."""
    def correction_dim(turns):
        return (dim * math.log(original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - np.float32(low))
                   / np.float32(high - low), 0, 1)
    plain = rope_inv_freq(dim, theta)
    return (plain / np.float32(factor) * ramp
            + plain * (1 - ramp)).astype(np.float32)
