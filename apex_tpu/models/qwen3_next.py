"""``qwen3_next`` decoder (Qwen3-Next, Qwen): gated delta-rule layers that
keep ONE recurrent state a sequence, three to one gated full-attention
layer, over routed experts in every layer.

The fifth decoder family next to ``gpt.py``, ``llama.py``,
``glm4_moe_lite.py`` and ``mellum.py``, and the first with layers that store
NO token: layer ``i`` is ``"linear_attention"`` unless ``(i + 1) %
full_attention_interval == 0``, which is ``"full_attention"``.

- **Linear layer** (:class:`GatedDeltaNet`): ``[q | k | v | z] = n W_qkvz``,
  ``[b | a] = n W_ba``; ``[q | k | v]`` goes through a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps, no bias, then SiLU; ``q``
  and ``k`` (``linear_num_key_heads`` heads, each shared by ``value heads /
  key heads`` value heads) are L2-normalised per head, ``q`` scaled by
  ``dk^-0.5``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; the gated delta rule (``ops/gated_delta.py``) in float32; per
  head ``o <- w o / rms(o) silu(z)``; ``W_out``. What it keeps between
  calls is per SEQUENCE, whatever the sequence's length: the float32 state
  ``(value heads, dk, dv)`` and the last ``taps - 1`` inputs of the
  convolution.
- **Full layer** (:class:`GatedAttention`): ``W_q n`` is ``heads x 2
  head_dim``, a head's first half its query and its second half its output
  gate; RMSNorm of ``q`` and ``k`` per head; RoPE on the first
  ``partial_rotary_factor x head_dim`` dims alone (rotate-half inside
  them), the rest pass; causal GQA; ``out = W_o (attn sigmoid(gate))``. The
  pool stores ``k`` after its norm and RoPE, and ``v``.
- **Routed layer**: float32 softmax over ALL ``num_experts``, the
  ``num_experts_per_tok`` largest renormalised, over SwiGLU experts of
  ``moe_intermediate_size`` of which this chip holds ``experts_held`` from
  ``first_expert`` (``transformer/moe/dropless``: a pair whose expert is
  elsewhere costs no row and adds nothing), plus one shared SwiGLU times
  ``sigmoid(w_g . n')``.

What the pool needs is stated once: ``layer_states`` (one entry a layer,
``None`` for a full layer: its K and V go to pages by the block table; for
a linear layer the tensors it keeps a slot). ``serving/kv_pool.
layer_groups`` reads it. A linear layer is handed its slots' state and
neither table nor lengths: one token a slot is ``gated_delta_step`` (a
Pallas kernel, the state updated in place), more is ``gated_delta_chunk``
from the state it is handed (zeros at a prompt's start). ``prompt_lengths``
marks where each row's true tokens end, so that a prompt padded to its page
bucket leaves the state, and the convolution's tail, as its last true token
left them.

Norm weights are held as the EFFECTIVE scale (the published layers compute
``x_hat (1 + w)``: a loader adds the one). Norm statistics, RoPE angles, the
router, ``g``, ``beta`` and the recurrent state are float32; weights and
matmuls ``config.dtype``. The fused projections' columns are laid out ``q |
k | v | z`` and ``b | a`` (the checkpoint interleaves them by key head: a
permutation a loader undoes). The multi-token-prediction module is not
held.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.models.generation import (advance_cache, cached_attention,
                                        check_chunk_bounds, is_paged,
                                        is_static_prefill, layer_cache,
                                        layer_state, update_layer_cache,
                                        update_paged_layer_cache)
from apex_tpu.models.glm4_moe_lite import Embedding
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops import flash_attention
from apex_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step
from apex_tpu.ops.paged_attention import paged_attention
from apex_tpu.transformer.functional.fused_rope import (
    fused_apply_rotary_pos_emb_cached, rope_inv_freq)
from apex_tpu.transformer.moe.dropless import DroplessMoEMLP, Linear

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class StateTensor:
    """One tensor a layer keeps per sequence: ``(slots,) + shape`` in the
    engine's cache, ``(batch,) + shape`` in a contiguous one."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    # the full layers' gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    # the linear layers' gated delta rule
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # routed experts: the router's width, and the share this chip holds
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None      # None: all ``num_experts``
    first_expert: int = 0
    max_position_embeddings: int = 262144
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    tensor_parallel_size: int = 1

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_key_heads must divide "
                             "linear_num_value_heads")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is held as whole multiples "
                             "of moe_intermediate_size")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            FULL if (i + 1) % self.full_attention_interval == 0 else LINEAR
            for i in range(self.num_layers))

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    @property
    def layer_states(self) -> Tuple[Optional[Tuple[StateTensor, ...]], ...]:
        """What each layer keeps per sequence beside the paged K and V
        (``None``: nothing, a full layer): the pool's one statement of the
        kinds of layer (``serving/kv_pool.layer_groups``)."""
        state = (
            StateTensor("delta_state",
                        (self.linear_num_value_heads,
                         self.linear_key_head_dim,
                         self.linear_value_head_dim), jnp.float32),
            StateTensor("conv_state",
                        (self.linear_conv_kernel_dim - 1, self.conv_dim),
                        resolve_compute_dtype(self.dtype)))
        return tuple(state if t == LINEAR else None
                     for t in self.layer_types)

    @property
    def routed_expert_bytes(self) -> int:
        """Bytes of ONE routed expert's three matrices as held."""
        return (3 * self.hidden_size * self.moe_intermediate_size
                * jnp.dtype(self.param_dtype).itemsize)


def qwen3_next_tiny_config(**overrides) -> Qwen3NextConfig:
    """One period (L L L F), 16 experts top 4 all held, partial rotary on 8
    of 16 dims."""
    base = Qwen3NextConfig(
        vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=16, partial_rotary_factor=0.5,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=8,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, max_position_embeddings=256,
        dtype=jnp.float32, param_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


def rope_table(cfg: Qwen3NextConfig, pos):
    """``(cos, sin)`` for absolute positions ``pos`` (b, s), each (b, s, 1,
    rotary dims) float32: the rotated dims alone, so the rest of a head
    passes (``fused_apply_rotary_pos_emb_cached``)."""
    rot = int(cfg.head_dim * cfg.partial_rotary_factor)
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(
        rope_inv_freq(rot, cfg.rope_theta))
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    return fused_apply_rotary_pos_emb_cached(
        x.astype(jnp.float32), cos, sin).astype(x.dtype)


def _l2norm(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


class GatedDeltaNet(nn.Module):
    """The linear layer's mixer. ``state``: the layer's ``{"delta_state",
    "conv_state"}`` (a row a sequence) or None (a forward pass from an empty
    memory that keeps nothing)."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, state=None, lengths=None):
        cfg = self.config
        b, s, e = x.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps, pd = cfg.linear_conv_kernel_dim, cfg.param_dtype
        f32 = jnp.float32
        qkvz = Linear(cfg.conv_dim + cfg.value_dim, e, pd,
                      name="in_proj_qkvz")(x)
        ba = Linear(2 * hv, e, pd, name="in_proj_ba")(x).astype(f32)
        conv_w = self.param("conv_weight", nn.initializers.normal(0.02),
                            (cfg.conv_dim, taps), pd)
        a_log = self.param("A_log", nn.initializers.normal(0.02), (hv,), pd)
        dt_bias = self.param("dt_bias", nn.initializers.normal(0.02),
                             (hv,), pd)
        norm_w = self.param("norm_weight", nn.initializers.ones, (dv,), pd)

        mixed, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
        # the convolution's window: the last ``taps - 1`` inputs before this
        # call (zeros at a sequence's start), then the call's own
        tail = jnp.zeros((b, taps - 1, cfg.conv_dim), mixed.dtype) \
            if state is None else state["conv_state"].astype(mixed.dtype)
        window = jnp.concatenate([tail, mixed], axis=1)
        conv = sum(window[:, j:j + s].astype(f32)
                   * conv_w[:, j].astype(f32) for j in range(taps))
        conv = jax.nn.silu(conv)
        q = _l2norm(conv[..., :cfg.key_dim].reshape(b, s, hk, dk)) \
            * dk ** -0.5
        k = _l2norm(conv[..., cfg.key_dim:2 * cfg.key_dim]
                    .reshape(b, s, hk, dk))
        v = conv[..., 2 * cfg.key_dim:].reshape(b, s, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :hv])
        g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
            ba[..., hv:] + dt_bias.astype(f32))

        s0 = None if state is None else state["delta_state"]
        if s == 1 and s0 is not None:
            o, s1 = gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0])
            o = o[:, None]
        else:
            o, s1 = gated_delta_chunk(q, k, v, g, beta, initial_state=s0,
                                      lengths=lengths)
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps)
        o = o * norm_w.astype(f32) * jax.nn.silu(
            z.reshape(b, s, hv, dv).astype(f32))
        out = Linear(e, cfg.value_dim, pd, name="out_proj")(
            o.reshape(b, s, hv * dv).astype(x.dtype))
        if state is None:
            return out
        # the window's last ``taps - 1`` TRUE inputs
        if lengths is None:
            tail = window[:, s:]
        else:
            tail = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
                w, n, taps - 1, axis=0))(window, lengths)
        return out, {"delta_state": s1,
                     "conv_state": tail.astype(state["conv_state"].dtype)}


class GatedAttention(nn.Module):
    """The full layer's mixer. ``rope``: ``(cos, sin)`` of the rotated
    dims; ``cache`` a per-layer view (``generation.layer_cache``) or
    None."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, rope, cache=None):
        cfg = self.config
        b, s, e = x.shape
        h, kv, d, pd = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.param_dtype)
        # a head's 2d outputs: its query, then its gate
        qg = Linear(h * 2 * d, e, pd, name="q_proj")(x).reshape(b, s, h,
                                                                 2 * d)
        q, gate = qg[..., :d], qg[..., d:].reshape(b, s, h * d)
        k = Linear(kv * d, e, pd, name="k_proj")(x).reshape(b, s, kv, d)
        v = Linear(kv * d, e, pd, name="v_proj")(x).reshape(b, s, kv, d)
        q = FusedRMSNorm(d, eps=cfg.rms_eps, param_dtype=pd,
                         name="q_norm")(q).astype(x.dtype)
        k = FusedRMSNorm(d, eps=cfg.rms_eps, param_dtype=pd,
                         name="k_norm")(k).astype(x.dtype)
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
        ctx = ctx * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dtype)
        out = Linear(e, h * d, pd, name="o_proj")(ctx.astype(x.dtype))
        return out if cache is None else (out, cache)


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, rope, cache=None, lengths=None):
        cfg = self.config
        e, pd = cfg.hidden_size, cfg.param_dtype
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="input_norm")(x).astype(x.dtype)
        if cfg.layer_types[self.layer_idx] == LINEAR:
            out = GatedDeltaNet(cfg, name="mixer")(hn, cache, lengths)
        else:
            out = GatedAttention(cfg, name="attn")(hn, rope, cache)
        if cache is not None:
            out, cache = out
        x = x + out
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="post_norm")(x).astype(x.dtype)
        x = x + DroplessMoEMLP(
            hidden_size=e, ffn_hidden_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, k=cfg.num_experts_per_tok,
            shared_experts=(cfg.shared_expert_intermediate_size
                            // cfg.moe_intermediate_size),
            norm_topk_prob=cfg.norm_topk_prob, params_dtype=pd,
            router="softmax", held=cfg.experts_held,
            first=cfg.first_expert, shared_gate=True, name="moe")(hn)
        return x if cache is None else (x, cache)


class Qwen3NextModel(nn.Module):
    """Decoder-only LM -> logits [B, S, vocab]; with ``cache=`` the
    incremental-decode entry point: ``(logits, updated cache)``.
    ``logits_positions`` (b, k) runs the head at those chunk positions
    alone. ``prompt_lengths`` (b,): the rows' true token counts where the
    chunk is padded (an admission at its page bucket); positions at or past
    them leave every linear layer's state untouched."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, cache=None, logits_positions=None,
                 prompt_lengths=None):
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
            blk = Qwen3NextBlock(cfg, layer_idx=i, name=f"layer_{i}")
            if cache is None:
                x = blk(x, rope)
                continue
            view = layer_state(cache, i) if kind == LINEAR \
                else layer_cache(cache, i)
            x, lc = blk(x, rope, cache=view, lengths=prompt_lengths)
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
