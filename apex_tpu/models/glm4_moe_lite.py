"""``glm4_moe_lite`` decoder: multi-head LATENT attention (MLA, DeepSeek-V2)
over one leading dense SwiGLU layer and then shared + routed expert layers
with a sigmoid-with-bias top-k router (DeepSeek-V3's ``noaux_tc``).

The third decoder family next to ``gpt.py`` and ``llama.py``, and the first
whose cache is not per-head K and V. What makes it MLA exists only where
there is a cache, so the two attention paths share their weights and differ
in form:

- **Expanded** (no cache, and every prefill): ``[k_nope_h; v_h] = c_kv
  W_ukv,h`` is materialized for the chunk and attention is ordinary causal
  flash attention at head width ``qk_nope + qk_rope`` over ``k_h = [k_nope_h;
  k_rope]`` (the one rotated ``k_rope`` broadcast to every head). A prefill
  WRITES only the latent entry ``[c_kv; k_rope]`` to its cache.
- **Absorbed** (paged decode): ``W_uk`` folds into the query (``q_lat_h =
  q_nope_h W_uk,h^T``) and ``W_uv`` into the output (``o_h = (P_h c_kv)
  W_uv,h``), so a step attends the latent entries themselves through
  ``ops.paged_latent_attention`` — every page read once, as keys (the whole
  entry) and as values (its ``c_kv`` columns) — and never expands one.

The cache entry is stated once, ``kv_latent_width`` (``kv_lora_rank +
qk_rope_head_dim``); ``serving/kv_pool.layout_of`` reads it and the pool,
the contiguous prefill buffer, the page scatter and the frontend's byte
counters follow (the cache-layout seam). A tail prefilled behind a
prefix-cache hit, and lock-step ``generate``'s decode steps, attend a
contiguous latent buffer in the expanded form with a position mask.

Scores, softmax, norms, RoPE angles and the router are float32; weights and
matmuls are ``config.dtype``. RoPE pairs dims ``(i, i + d/2)`` (rotate-half,
the repo's ``fused_rope`` convention).

:class:`LatentAttention` also serves Ling's MLA layers
(``models/bailing_hybrid.py``), whose config states what GLM's has no field
for (read as off): ``q_lora_rank`` None (one ``q_proj`` of the hidden
state, no low-rank query), ``rope_interleave`` (the rotated dims come in
adjacent pairs, ``(2i, 2i + 1)``, and are gathered into halves before
rotate-half, DeepSeek-V3's ``rope_interleave``) and ``q_head_norm`` with
``head_gate`` (an RMSNorm of each head's whole query, and each head's
context times ``sigmoid(w_h . x)`` before ``o_proj``). Multi-token prediction
(``num_nextn_predict_layers``) is not held: it adds nothing to the forward
pass and needs speculation over a latent pool (ROADMAP.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.models.generation import (_masked_attention_core,
                                        advance_cache, check_chunk_bounds,
                                        is_paged, is_static_prefill,
                                        layer_cache,
                                        update_paged_layer_cache)
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops import flash_attention
from apex_tpu.ops.paged_latent_attention import paged_latent_attention
from apex_tpu.transformer.moe.dropless import (DroplessMoEMLP, Linear,
                                               SwiGLU)


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240       # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 1536    # one expert's SwiGLU
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    max_position_embeddings: int = 202752
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    tensor_parallel_size: int = 1        # a latent pool has no head to shard

    @property
    def head_dim(self) -> int:
        """Query/key width of a head in the expanded form."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_latent_width(self) -> int:
        """What a layer stores per token, for ALL heads: the normalized
        ``c_kv`` and the rotated ``k_rope`` (the cache-layout seam's one
        statement; ``serving/kv_pool.layout_of``)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def routed_expert_bytes(self) -> int:
        """Bytes of ONE routed expert's three matrices as held."""
        return (3 * self.hidden_size * self.moe_intermediate_size
                * jnp.dtype(self.param_dtype).itemsize)

    def is_dense_layer(self, i: int) -> bool:
        return i < self.first_k_dense_replace


def glm4_moe_lite_tiny_config(**overrides) -> Glm4MoeLiteConfig:
    base = Glm4MoeLiteConfig(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=48, num_layers=3, num_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=128, dtype=jnp.float32,
        param_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


def _rope(x, pos, theta: float):
    """Rotate-half RoPE in float32. ``x``: (b, s, ..., d); ``pos``: (b, s)
    absolute positions."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv            # (b, s, d/2)
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (d,))
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = xf * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)
    return out.astype(x.dtype)


def _halves(x):
    """Adjacent pairs ``(2i, 2i + 1)`` of the last axis gathered into
    halves ``(i, i + d/2)``: interleaved RoPE as rotate-half (the rotated
    output stays in halves, for queries and keys alike)."""
    d = x.shape[-1]
    return x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)


class LatentAttention(nn.Module):
    """MLA: see the module docstring. ``pos`` is (b, s) absolute positions;
    ``cache`` a per-layer view (``generation.layer_cache``) or None.
    ``config``: a :class:`Glm4MoeLiteConfig`, or any config with its
    attention fields and Ling's three options
    (``bailing_hybrid.BailingHybridConfig``)."""

    config: Any

    @nn.compact
    def __call__(self, x, pos, cache=None):
        cfg = self.config
        b, s, e = x.shape
        h, nope, rope, vd = (cfg.num_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        rank, pd = cfg.kv_lora_rank, cfg.param_dtype
        scale = 1.0 / (cfg.head_dim ** 0.5)
        ling = {name: getattr(cfg, name, False)
                for name in ("rope_interleave", "q_head_norm", "head_gate")}
        pairs = _halves if ling["rope_interleave"] else (lambda t: t)

        if cfg.q_lora_rank is None:
            q = Linear(h * cfg.head_dim, e, pd, name="q_proj")(x)
        else:
            c_q = FusedRMSNorm(cfg.q_lora_rank, eps=cfg.rms_eps,
                               param_dtype=pd, name="q_a_norm")(
                Linear(cfg.q_lora_rank, e, pd, name="q_a_proj")(x)
            ).astype(x.dtype)
            q = Linear(h * cfg.head_dim, cfg.q_lora_rank, pd,
                       name="q_b_proj")(c_q)
        q = q.reshape(b, s, h, cfg.head_dim)
        if ling["q_head_norm"]:
            q = FusedRMSNorm(cfg.head_dim, eps=cfg.rms_eps, param_dtype=pd,
                             name="q_norm")(q).astype(x.dtype)
        q_nope, q_rope = q[..., :nope], _rope(pairs(q[..., nope:]), pos,
                                              cfg.rope_theta)
        ckv = Linear(rank + rope, e, pd, name="kv_a_proj")(x)
        c_kv = FusedRMSNorm(rank, eps=cfg.rms_eps, param_dtype=pd,
                            name="kv_a_norm")(ckv[..., :rank]).astype(x.dtype)
        k_rope = _rope(pairs(ckv[..., rank:]), pos,
                       cfg.rope_theta)                        # (b, s, rope)
        w_ukv = KvUp(h * (nope + vd), rank, pd, name="kv_b_proj")() \
            .astype(x.dtype).reshape(h, nope + vd, rank)

        def expand(c, kr):
            """``k`` (b, h, t, nope+rope) and ``v`` (b, h, t, vd) of the
            latent entries ``c`` (b, t, rank), ``kr`` (b, t, rope)."""
            kv = jnp.einsum("btr,hdr->bhtd", c, w_ukv)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                kr[:, None], (b, h) + kr.shape[1:])], axis=-1)
            return k, kv[..., nope:]

        q_full = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
        if cache is None:
            k, v = expand(c_kv, k_rope)
            ctx = _flash(q_full, k, v, scale)
        elif is_paged(cache):
            # absorbed decode over the latent pages: the entry is written
            # lane-padded as the pool stores it, the query meets it as
            # [q_nope W_uk^T ; q_rope ; 0] and the c_kv columns of the same
            # pages are the values
            stored = cache["latent_pages"].shape[-1]
            entry = _entry(c_kv, k_rope, stored)
            cache = update_paged_layer_cache(cache, entry.transpose(0, 2, 1, 3))
            q_lat = jnp.einsum("bshn,hnr->bhsr", q_nope, w_ukv[:, :nope])
            q_abs = _pad_last(jnp.concatenate(
                [q_lat, q_rope.transpose(0, 2, 1, 3)], -1), stored)
            o_lat = paged_latent_attention(
                q_abs, cache["latent_pages"], cache["block_tables"],
                cache["len"] + s, value_width=rank, scale=scale)
            ctx = jnp.einsum("bhsr,hvr->bhsv", o_lat, w_ukv[:, nope:])
        else:
            # contiguous latent buffer (the admit programs' staging cache,
            # lock-step generate): write the chunk's entries at the offset
            stored = cache["latent"].shape[-1]
            entry = _entry(c_kv, k_rope, stored).transpose(0, 2, 1, 3)
            prefill = is_static_prefill(cache, s)
            cache = dict(cache, latent=jax.lax.dynamic_update_slice(
                cache["latent"], entry.astype(cache["latent"].dtype),
                (0, 0, cache["len"], 0)))
            if prefill:
                k, v = expand(c_kv, k_rope)
                ctx = _flash(q_full, k, v, scale)
            else:
                # a chunk behind a past (a prefix-cache hit's tail, a decode
                # step of generate): expanded attention over the whole
                # buffer under an absolute-position mask, which also hides
                # the unwritten tail
                buf = cache["latent"][:, 0]                  # (b, T, stored)
                k, v = expand(buf[..., :rank], buf[..., rank:rank + rope])
                t_max = buf.shape[1]
                pos_q = cache["len"] + jnp.arange(s, dtype=jnp.int32)[:, None]
                mask = jnp.arange(t_max, dtype=jnp.int32)[None, :] <= pos_q
                ctx = _masked_attention_core(
                    q_full, k, _pad_last(v, k.shape[-1]),
                    mask[None, None, None], scale=scale)[..., :vd]
        ctx = ctx.transpose(0, 2, 1, 3)                      # (b, s, h, vd)
        if ling["head_gate"]:
            ctx = ctx * jax.nn.sigmoid(Linear(h, e, pd, name="g_proj")(x)
                                       .astype(jnp.float32))[..., None] \
                .astype(ctx.dtype)
        ctx = ctx.reshape(b, s, h * vd)
        out = Linear(e, h * vd, pd, name="o_proj")(ctx.astype(x.dtype))
        return out if cache is None else (out, cache)


class KvUp(nn.Module):
    """``W_ukv`` as a parameter of its own module (``kv_b_proj/weight``),
    handed out whole: the two attention forms slice it differently."""

    out_features: int
    in_features: int
    params_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self):
        return self.param("weight", nn.initializers.normal(0.02),
                          (self.out_features, self.in_features),
                          self.params_dtype)


class Embedding(nn.Module):
    num_embeddings: int
    features: int
    params_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids):
        w = self.param("weight", nn.initializers.normal(0.02),
                       (self.num_embeddings, self.features),
                       self.params_dtype)
        return jnp.take(w, ids, axis=0)


def _pad_last(x, width: int):
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _entry(c_kv, k_rope, stored: int):
    """The latent cache entry (b, s, 1, stored): ``[c_kv; k_rope; 0...]``."""
    return _pad_last(jnp.concatenate([c_kv, k_rope], -1), stored)[:, :, None]


def _flash(q, k, v, scale):
    """Causal flash attention where the value width differs from the key
    width: the kernel takes one head width, so ``v`` rides zero-padded to
    the key width and the padding is cut from the output."""
    vd = v.shape[-1]
    out = flash_attention(q, k, _pad_last(v, k.shape[-1]), causal=True,
                          scale=scale)
    return out[..., :vd]


class Glm4MoeLiteBlock(nn.Module):
    config: Glm4MoeLiteConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, pos, cache=None):
        cfg = self.config
        e, pd = cfg.hidden_size, cfg.param_dtype
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="input_norm")(x).astype(x.dtype)
        attn = LatentAttention(cfg, name="attn")
        if cache is None:
            x = x + attn(hn, pos)
        else:
            out, cache = attn(hn, pos, cache)
            x = x + out
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="post_norm")(x).astype(x.dtype)
        if cfg.is_dense_layer(self.layer_idx):
            x = x + SwiGLU(e, cfg.intermediate_size, pd, name="mlp")(hn)
        else:
            x = x + DroplessMoEMLP(
                hidden_size=e, ffn_hidden_size=cfg.moe_intermediate_size,
                num_experts=cfg.n_routed_experts, k=cfg.num_experts_per_tok,
                shared_experts=cfg.n_shared_experts,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                params_dtype=pd, name="moe")(hn)
        return x if cache is None else (x, cache)


class Glm4MoeLiteModel(nn.Module):
    """Decoder-only LM -> logits [B, S, vocab]; with ``cache=`` the
    incremental-decode entry point the serving engine and ``generate``
    drive: ``(logits, updated cache)``. ``logits_positions`` (b, k) runs
    the head at those chunk positions alone -> logits [B, k, vocab]: an
    admission reads one position of a prompt of thousands, and the whole
    chunk's logits at this vocabulary would be gigabytes."""

    config: Glm4MoeLiteConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, logits_positions=None):
        cfg = self.config
        dt = resolve_compute_dtype(cfg.dtype)
        b, s = input_ids.shape
        x = Embedding(cfg.vocab_size, cfg.hidden_size, cfg.param_dtype,
                      name="embed_tokens")(input_ids).astype(dt)
        steps = jnp.arange(s, dtype=jnp.int32)[None, :]
        if cache is None:
            if s > cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence {s} exceeds max_position_embeddings="
                    f"{cfg.max_position_embeddings}")
            pos = jnp.broadcast_to(steps, (b, s))
        elif is_paged(cache):
            # an s-token block per SLOT at its own positions [len, len+s)
            pos = jnp.clip(cache["len"][:, None] + steps, 0,
                           cfg.max_position_embeddings - 1)
        else:
            t0 = check_chunk_bounds(cache, s, cfg.max_position_embeddings)
            pos = jnp.broadcast_to(t0 + steps, (b, s))
        new_layers = []
        for i in range(cfg.num_layers):
            blk = Glm4MoeLiteBlock(cfg, layer_idx=i, name=f"layer_{i}")
            if cache is None:
                x = blk(x, pos)
            else:
                x, lc = blk(x, pos, cache=layer_cache(cache, i))
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
