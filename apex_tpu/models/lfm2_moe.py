"""``lfm2_moe`` decoder (LFM2-MoE, Liquid AI): gated short-convolution
layers that keep a WINDOW of two tokens a sequence, three to one GQA layer,
over a leading dense SwiGLU layer and then routed experts.

The sixth decoder family next to ``gpt.py``, ``llama.py``,
``glm4_moe_lite.py``, ``mellum.py`` and ``qwen3_next.py``. The kinds of
layer are the config's own ``layer_types`` (``"conv"`` or
``"full_attention"``, one a layer) and ``num_dense_layers`` (the first that
many have a dense SwiGLU of ``intermediate_size``, the rest routed experts);
no index arithmetic decides them.

- **Conv layer** (:class:`ShortConv`): ``[B | C | x] = n W_in``, ``u = B
  x``, a causal depthwise convolution of ``conv_L_cache`` taps over ``u``
  (no bias, no activation), ``y = C v``, ``W_out`` (``ops/short_conv.py``).
  What it keeps between calls is the last ``conv_L_cache - 1`` values of
  ``u``: its window, a row a sequence, in the compute dtype.
- **Attention layer** (:class:`Lfm2Attention`): q, k, v, o with no bias;
  RMSNorm of ``q`` and ``k`` per head before RoPE (rotate-half over the
  whole head, theta ``rope_theta``, unscaled); causal GQA at scale
  ``head_dim^-0.5``. The pool stores ``k`` after its norm and RoPE, and
  ``v``.
- **Routed layer**: a float32 sigmoid router whose ``k`` largest of ``s +
  b`` are chosen (``b`` the learned expert bias) and weighted by ``s``
  renormalised over the chosen with ``router_norm_eps`` (1e-6, where GLM's
  is 1e-20) times ``routed_scaling_factor``, over SwiGLU experts of
  ``moe_intermediate_size``; no shared expert.

Layer ``i`` is ``h = x + mixer_i(N(x))``, ``out = h + ffn_i(N(h))``; a
final RMSNorm (the published ``embedding_norm``) and the head TIED to the
embedding. Norm weights are held as the effective scale; norm statistics,
RoPE angles, the router and the convolution's sums are float32, weights and
matmuls ``config.dtype``.

What the pool needs is stated once: ``layer_states`` (one entry a layer,
``None`` for an attention layer: its K and V go to pages by the block
table; for a conv layer its window). ``serving/kv_pool.layer_groups`` reads
it. A conv layer is handed its slots' windows and neither table nor
lengths: ``short_conv`` runs from the window it is handed (zeros at a
prompt's start) over one token a slot or a whole prompt, and returns the
window at each row's true end (``prompt_lengths``: a prompt padded to its
page bucket).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.models.generation import (StateTensor, advance_cache,
                                        cached_attention, check_chunk_bounds,
                                        is_paged, is_static_prefill,
                                        layer_cache, layer_state,
                                        update_layer_cache,
                                        update_paged_layer_cache)
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops import flash_attention
from apex_tpu.ops.paged_attention import paged_attention
from apex_tpu.ops.short_conv import short_conv
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached, rope_inv_freq)
from apex_tpu.transformer.moe.dropless import DroplessMoEMLP, Linear, SwiGLU

CONV, FULL = "conv", "full_attention"

#: the published period after the two dense layers: three conv layers to
#: one attention layer (``layer_types`` of the public config)
PUBLISHED_LAYER_TYPES = (CONV, CONV) + (FULL, CONV, CONV, CONV) * 9 + (
    FULL, CONV)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776       # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1536    # one expert's SwiGLU
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    conv_L_cache: int = 3
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    router_norm_eps: float = 1e-6
    max_position_embeddings: int = 128000
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    tensor_parallel_size: int = 1

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: a layer "
                             f"is {CONV!r} or {FULL!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def is_dense_layer(self, i: int) -> bool:
        return i < self.num_dense_layers

    @property
    def layer_states(self) -> Tuple[Optional[Tuple[StateTensor, ...]], ...]:
        """What each layer keeps per sequence beside the paged K and V
        (``None``: nothing, an attention layer): the pool's one statement
        of the kinds of layer (``serving/kv_pool.layer_groups``)."""
        window = (StateTensor("conv_state",
                              (self.conv_L_cache - 1, self.hidden_size),
                              resolve_compute_dtype(self.dtype)),)
        return tuple(window if t == CONV else None for t in self.layer_types)

    @property
    def routed_expert_bytes(self) -> int:
        """Bytes of ONE routed expert's three matrices as held."""
        return (3 * self.hidden_size * self.moe_intermediate_size
                * jnp.dtype(self.param_dtype).itemsize)


def lfm2_moe_tiny_config(**overrides) -> Lfm2MoeConfig:
    """One dense conv layer, then conv, attention, conv over 8 experts top
    4; heads of 16."""
    base = Lfm2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, layer_types=(CONV, FULL, CONV, CONV),
        num_dense_layers=1, num_heads=4, num_kv_heads=2, num_experts=8,
        max_position_embeddings=256, dtype=jnp.float32,
        param_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


def rope_table(cfg: Lfm2MoeConfig, pos):
    """``(cos, sin)`` for absolute positions ``pos`` (b, s), each (b, s, 1,
    head_dim) float32."""
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        rope_inv_freq(cfg.head_dim, cfg.rope_theta))
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    return fused_apply_rotary_pos_emb_cached(
        x.astype(jnp.float32), cos, sin).astype(x.dtype)


class TiedEmbedding(nn.Module):
    """The token embedding, which is also the head (``attend``)."""

    num_embeddings: int
    features: int
    params_dtype: Any = jnp.float32

    def setup(self):
        self.weight = self.param("weight", nn.initializers.normal(0.02),
                                 (self.num_embeddings, self.features),
                                 self.params_dtype)

    def __call__(self, ids):
        return jnp.take(self.weight, ids, axis=0)

    def attend(self, x):
        return jax.lax.dot_general(x, self.weight.astype(x.dtype),
                                   (((x.ndim - 1,), (1,)), ((), ())))


class ShortConv(nn.Module):
    """The conv layer's mixer. ``state``: the layer's ``{"conv_state"}`` (a
    row a sequence) or None (a forward pass from an empty window that keeps
    nothing)."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, state=None, lengths=None):
        cfg = self.config
        b, s, e = x.shape
        pd = cfg.param_dtype
        bcx = Linear(3 * e, e, pd, name="in_proj")(x)
        w = self.param("conv_weight", nn.initializers.normal(0.02),
                       (cfg.conv_L_cache, e), pd).astype(x.dtype)
        y, window = short_conv(
            bcx, w, None if state is None else state["conv_state"], lengths)
        out = Linear(e, e, pd, name="out_proj")(y)
        return out if state is None else (out, {"conv_state": window})


class Lfm2Attention(nn.Module):
    """The attention layer's mixer. ``rope``: ``(cos, sin)``; ``cache`` a
    per-layer view (``generation.layer_cache``) or None."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, rope, cache=None):
        cfg = self.config
        b, s, e = x.shape
        h, kv, d, pd = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.param_dtype)
        q = Linear(h * d, e, pd, name="q_proj")(x).reshape(b, s, h, d)
        k = Linear(kv * d, e, pd, name="k_proj")(x).reshape(b, s, kv, d)
        v = Linear(kv * d, e, pd, name="v_proj")(x).reshape(b, s, kv, d)
        q = FusedRMSNorm(d, eps=cfg.rms_eps, param_dtype=pd,
                         name="q_layernorm")(q).astype(x.dtype)
        k = FusedRMSNorm(d, eps=cfg.rms_eps, param_dtype=pd,
                         name="k_layernorm")(k).astype(x.dtype)
        q = _rotate(q, *rope).transpose(0, 2, 1, 3)
        k = _rotate(k, *rope).transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        if cache is None:
            ctx = flash_attention(q, k, v, causal=True)
        elif is_paged(cache):
            cache = update_paged_layer_cache(cache, k, v)
            ctx = paged_attention(q, cache["k_pages"], cache["v_pages"],
                                  cache["block_tables"], cache["len"] + s)
        else:
            prefill = is_static_prefill(cache, s)
            cache = update_layer_cache(cache, k, v)
            ctx = flash_attention(q, k, v, causal=True) if prefill \
                else cached_attention(q, cache)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        out = Linear(e, h * d, pd, name="out_proj")(ctx.astype(x.dtype))
        return out if cache is None else (out, cache)


class Lfm2MoeBlock(nn.Module):
    config: Lfm2MoeConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, rope, cache=None, lengths=None):
        cfg = self.config
        e, pd = cfg.hidden_size, cfg.param_dtype
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="operator_norm")(x).astype(x.dtype)
        if cfg.layer_types[self.layer_idx] == CONV:
            out = ShortConv(cfg, name="conv")(hn, cache, lengths)
        else:
            out = Lfm2Attention(cfg, name="self_attn")(hn, rope, cache)
        if cache is not None:
            out, cache = out
        x = x + out
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="ffn_norm")(x).astype(x.dtype)
        if cfg.is_dense_layer(self.layer_idx):
            x = x + SwiGLU(e, cfg.intermediate_size, pd,
                           name="feed_forward")(hn)
        else:
            x = x + DroplessMoEMLP(
                hidden_size=e, ffn_hidden_size=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts, k=cfg.num_experts_per_tok,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_eps=cfg.router_norm_eps, params_dtype=pd,
                name="moe")(hn)
        return x if cache is None else (x, cache)


class Lfm2MoeModel(nn.Module):
    """Decoder-only LM -> logits [B, S, vocab]; with ``cache=`` the
    incremental-decode entry point: ``(logits, updated cache)``.
    ``logits_positions`` (b, k) runs the head at those chunk positions
    alone. ``prompt_lengths`` (b,): the rows' true token counts where the
    chunk is padded (an admission at its page bucket); the conv layers'
    windows are taken there."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, logits_positions=None,
                 prompt_lengths=None):
        cfg = self.config
        dt = resolve_compute_dtype(cfg.dtype)
        b, s = input_ids.shape
        embed = TiedEmbedding(cfg.vocab_size, cfg.hidden_size,
                              cfg.param_dtype, name="embed_tokens")
        x = embed(input_ids).astype(dt)
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        if cache is None:
            if s > cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence {s} exceeds max_position_embeddings="
                    f"{cfg.max_position_embeddings}")
            pos = jnp.broadcast_to(steps, (b, s))
        elif is_paged(cache):
            pos = jnp.clip(cache["len"][:, None] + steps, 0,
                           cfg.max_position_embeddings - 1)
        else:
            t0 = check_chunk_bounds(cache, s, cfg.max_position_embeddings)
            pos = jnp.broadcast_to(t0 + steps, (b, s))
        rope = rope_table(cfg, pos)
        if prompt_lengths is not None:
            prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
        new_layers = []
        for i, kind in enumerate(cfg.layer_types):
            blk = Lfm2MoeBlock(cfg, layer_idx=i, name=f"layer_{i}")
            if cache is None:
                x = blk(x, rope)
                continue
            view = layer_state(cache, i) if kind == CONV \
                else layer_cache(cache, i)
            x, lc = blk(x, rope, cache=view, lengths=prompt_lengths)
            new_layers.append(lc)
        if logits_positions is not None:
            x = jnp.take_along_axis(x, logits_positions[..., None], axis=1)
        x = FusedRMSNorm(cfg.hidden_size, eps=cfg.rms_eps,
                         param_dtype=cfg.param_dtype,
                         name="embedding_norm")(x).astype(dt)
        logits = embed.attend(x)
        if cache is None:
            return logits
        return logits, advance_cache(cache, new_layers, s)
