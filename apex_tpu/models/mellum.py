"""``mellum`` decoder (Mellum 2, JetBrains): grouped-query attention whose
layers are of two KINDS, mixed layer by layer, over routed experts in every
layer with a softmax top-k router.

The fourth decoder family next to ``gpt.py``, ``llama.py`` and
``glm4_moe_lite.py``, and the first whose layers do not all read the same
span of the cache:

- ``layer_types[i]`` is ``"sliding_attention"`` (causal, and a query at
  position ``p`` reads positions ``(p - sliding_window, p]``) or
  ``"full_attention"`` (causal). The published model has three sliding
  layers to one full, seven times.
- RoPE is per layer TYPE too: every layer rotates by ``rope_theta``; a type
  listed in ``yarn`` (the full layers) uses YaRN's blended frequencies
  (``fused_rope.yarn_inv_freq``: static, not by the length seen) with cos
  and sin times its ``attention_factor``; the other rotates plainly.
  Rotate-half pairing ``(d, d + head_dim/2)``, the repo's convention.
- every layer's MLP is ``sum_{e in top k} w_e SwiGLU_e(x)``, ``w`` the
  float32 softmax over ALL experts, the ``k`` largest, renormalised
  (``transformer/moe``: :class:`SoftmaxTopKRouter` before the dropless
  grouped products). No shared expert, no leading dense layer.

What the pool needs is stated once: ``layer_windows`` (one entry a layer,
``None`` = full). ``serving/kv_pool.layer_groups`` reads it: the full
layers are the block table's group, the sliding layers a ring group of
``ceil(window / page_size) + 1`` pages a slot, and a decode step hands
each layer its own group's table (``generation.paged_layer_tables``). An
admission runs flash attention over the contiguous prompt, banded on the
sliding layers, and writes the full layers whole and the sliding layers'
last window. Lock-step ``generate`` and a model-only forward take the same
kernels over a contiguous buffer that holds every position of every layer.

Norm statistics, RoPE angles, the router and its softmax are float32;
weights and matmuls ``config.dtype``. The published config names no
per-head q/k norm and this holds none; the multi-token-prediction head its
model card mentions is not in the config and is not held.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.models.generation import (advance_cache, cached_attention,
                                        check_chunk_bounds, is_paged,
                                        is_static_prefill, layer_cache,
                                        paged_layer_tables,
                                        update_layer_cache,
                                        update_paged_layer_cache)
from apex_tpu.models.glm4_moe_lite import Embedding
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops import flash_attention
from apex_tpu.ops.paged_attention import paged_attention
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached, rope_inv_freq, yarn_inv_freq)
from apex_tpu.transformer.moe.dropless import DroplessMoEMLP, Linear

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """One layer type's ``rope_parameters`` of ``rope_type: "yarn"``."""

    factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782    # 0.1 ln(factor) + 1


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896     # one expert's SwiGLU
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ((SLIDING,) * 3 + (FULL,)) * 7
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 131072
    rope_theta: float = 500000.0
    # layer types whose RoPE is YaRN's; every other rotates plainly
    yarn: Tuple[Tuple[str, YarnScaling], ...] = ((FULL, YarnScaling()),)
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    tensor_parallel_size: int = 1

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} "
                             f"entries for {self.num_layers} layers")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")

    @property
    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """How far back each layer reads (``None``: everything): the
        pool's one statement of the kinds of layer
        (``serving/kv_pool.layer_groups``)."""
        return tuple(self.sliding_window if t == SLIDING else None
                     for t in self.layer_types)

    @property
    def routed_expert_bytes(self) -> int:
        """Bytes of ONE routed expert's three matrices as held."""
        return (3 * self.hidden_size * self.moe_intermediate_size
                * jnp.dtype(self.param_dtype).itemsize)


def mellum_tiny_config(**overrides) -> MellumConfig:
    """One period (S S S F), window 8, 8 experts top 2."""
    base = MellumConfig(
        vocab_size=128, hidden_size=64, moe_intermediate_size=48,
        num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
        layer_types=(SLIDING,) * 3 + (FULL,), sliding_window=8,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=128,
        yarn=((FULL, YarnScaling(factor=4.0,
                                 original_max_position_embeddings=32,
                                 attention_factor=1.1386294361119891)),),
        dtype=jnp.float32, param_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


def rope_tables(cfg: MellumConfig, pos):
    """``{layer type: (cos, sin)}`` for absolute positions ``pos`` (b, s),
    each (b, s, 1, head_dim) float32: the type's own frequencies, cos and
    sin times its attention factor where it has one."""
    out = {}
    yarn = dict(cfg.yarn)
    for kind in dict.fromkeys(cfg.layer_types):
        y = yarn.get(kind)
        inv = rope_inv_freq(cfg.head_dim, cfg.rope_theta) if y is None \
            else yarn_inv_freq(
                cfg.head_dim, cfg.rope_theta, factor=y.factor,
                original_max_position_embeddings=(
                    y.original_max_position_embeddings),
                beta_fast=y.beta_fast, beta_slow=y.beta_slow)
        ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv)
        ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
        scale = jnp.float32(1.0 if y is None else y.attention_factor)
        out[kind] = (jnp.cos(ang) * scale, jnp.sin(ang) * scale)
    return out


def _rotate(x, cos, sin):
    """Rotate-half RoPE of ``x`` (b, s, heads, d) in float32."""
    return fused_apply_rotary_pos_emb_cached(
        x.astype(jnp.float32), cos, sin).astype(x.dtype)


class MellumAttention(nn.Module):
    """GQA with the layer's own ``window`` (``None``: full). ``rope`` is
    the layer type's ``(cos, sin)``; ``cache`` a per-layer view
    (``generation.layer_cache``) or None."""

    config: MellumConfig
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x, rope, cache=None):
        cfg = self.config
        b, s, e = x.shape
        h, kv, d, pd = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.param_dtype)
        # one product for q, k and v: rows [q heads | k heads | v heads]
        qkv = Linear((h + 2 * kv) * d, e, pd, name="qkv_proj")(x)
        qkv = qkv.reshape(b, s, h + 2 * kv, d)
        q = _rotate(qkv[:, :, :h], *rope).transpose(0, 2, 1, 3)
        k = _rotate(qkv[:, :, h:h + kv], *rope).transpose(0, 2, 1, 3)
        v = qkv[:, :, h + kv:].transpose(0, 2, 1, 3)
        if cache is None:
            ctx = flash_attention(q, k, v, causal=True, window=self.window)
        elif is_paged(cache):
            # the table and length are the layer's own group's: the block
            # table, or the slots' rings seen from the band's first page
            cache = update_paged_layer_cache(cache, k, v)
            ctx = paged_attention(q, cache["k_pages"], cache["v_pages"],
                                  cache["block_tables"], cache["len"] + s,
                                  window=self.window)
        else:
            prefill = is_static_prefill(cache, s)
            cache = update_layer_cache(cache, k, v)
            ctx = flash_attention(q, k, v, causal=True, window=self.window) \
                if prefill else cached_attention(q, cache, window=self.window)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        out = Linear(e, h * d, pd, name="o_proj")(ctx.astype(x.dtype))
        return out if cache is None else (out, cache)


class MellumBlock(nn.Module):
    config: MellumConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, rope, cache=None):
        cfg = self.config
        e, pd = cfg.hidden_size, cfg.param_dtype
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="input_norm")(x).astype(x.dtype)
        attn = MellumAttention(cfg, cfg.layer_windows[self.layer_idx],
                               name="attn")
        if cache is None:
            x = x + attn(hn, rope)
        else:
            out, cache = attn(hn, rope, cache)
            x = x + out
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="post_norm")(x).astype(x.dtype)
        x = x + DroplessMoEMLP(
            hidden_size=e, ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, k=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob, params_dtype=pd,
            router="softmax", name="moe")(hn)
        return x if cache is None else (x, cache)


class MellumModel(nn.Module):
    """Decoder-only LM -> logits [B, S, vocab]; with ``cache=`` the
    incremental-decode entry point: ``(logits, updated cache)``.
    ``logits_positions`` (b, k) runs the head at those chunk positions
    alone (an admission reads one of thousands)."""

    config: MellumConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, logits_positions=None):
        cfg = self.config
        dt = resolve_compute_dtype(cfg.dtype)
        b, s = input_ids.shape
        x = Embedding(cfg.vocab_size, cfg.hidden_size, cfg.param_dtype,
                      name="embed_tokens")(input_ids).astype(dt)
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        tables = [None] * cfg.num_layers
        if cache is None:
            if s > cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence {s} exceeds max_position_embeddings="
                    f"{cfg.max_position_embeddings}")
            pos = jnp.broadcast_to(steps, (b, s))
        elif is_paged(cache):
            # an s-token block per SLOT at its own positions [len, len+s);
            # a layer of a ring group writes and reads through its group's
            # view of them
            pos = jnp.clip(cache["len"][:, None] + steps, 0,
                           cfg.max_position_embeddings - 1)
            tables = paged_layer_tables(cache, cfg, s)
        else:
            t0 = check_chunk_bounds(cache, s, cfg.max_position_embeddings)
            pos = jnp.broadcast_to(t0 + steps, (b, s))
        rope = rope_tables(cfg, pos)
        new_layers = []
        for i, kind in enumerate(cfg.layer_types):
            blk = MellumBlock(cfg, layer_idx=i, name=f"layer_{i}")
            if cache is None:
                x = blk(x, rope[kind])
            else:
                x, lc = blk(x, rope[kind],
                            cache=layer_cache(cache, i, tables[i]))
                new_layers.append(lc)
        if logits_positions is not None:
            x = jnp.take_along_axis(x, logits_positions[..., None], axis=1)
        x = FusedRMSNorm(cfg.hidden_size, eps=cfg.rms_eps,
                         param_dtype=cfg.param_dtype,
                         name="final_norm")(x).astype(dt)
        logits = Linear(cfg.vocab_size, cfg.hidden_size, cfg.param_dtype,
                        name="lm_head")(x)
        if cache is None:
            return logits
        return logits, advance_cache(cache, new_layers, s)
