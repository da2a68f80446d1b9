"""``bailing_hybrid`` decoder (Ling-3.0-flash, inclusionAI): Kimi delta
attention layers that keep ONE recurrent state a sequence, five to one
multi-head latent attention layer over latent pages, a leading dense SwiGLU
layer and then routed experts chosen in groups.

The seventh decoder family next to ``gpt.py``, ``llama.py``,
``glm4_moe_lite.py``, ``mellum.py``, ``qwen3_next.py`` and ``lfm2_moe.py``,
and the first whose layers of tokens store LATENT entries beside a state
group. Layer ``i`` of the published stack is MLA if ``(i + 1) %
layer_group_size == 0`` and KDA otherwise; a held cut starts at published
layer ``first_layer``, so held layer ``j`` is published layer ``first_layer
+ j``. Held layer ``j`` is dense if ``j < first_k_dense_replace``.

- **KDA layer** (:class:`KimiDeltaAttention`, Kimi delta attention): ``q,
  k, v = W_{q,k,v} n`` (``heads x kda_head_dim`` each); a causal depthwise
  convolution of ``conv_size`` taps over the ``3 heads dk`` channels, no
  bias, then SiLU; ``q`` and ``k`` L2-normalised per head, ``q`` times
  ``dk^-1/2``; ``beta = sigmoid(W_b n)``, one a head; a log decay per KEY
  CHANNEL, ``g = kda_lower_bound sigmoid(exp(A_log_h) (W_f n + dt_bias))``
  in ``(kda_lower_bound, 0)`` (Kimi's safe gate; ``W_f`` full rank); the
  gated delta rule ``S <- (I - beta k k^T) Diag(exp g) S + beta k v^T``,
  ``o = S^T q`` (``ops/gated_delta.py``, float32); per head ``o <- w o /
  rms(o) sigmoid(W_go n)``; ``W_o``. What it keeps between calls is per
  SEQUENCE: the float32 state ``(heads, dk, dv)`` and the last ``conv_size
  - 1`` inputs of the convolution.
- **MLA layer** (``glm4_moe_lite.LatentAttention`` with Ling's options):
  ``q = W_q n`` with no low rank, an RMSNorm of each head's whole query
  (``use_qk_norm``), ``kv_lora_rank`` latent plus ``qk_rope_head_dim``
  rotated dims, RoPE on adjacent pairs (``rope_interleave``), scale
  ``(qk_nope + qk_rope)^-1/2``, and each head's context times ``sigmoid(w_h
  . n)`` before ``W_o`` (the head-wise gate). The pool stores the latent
  entry, one a token, ``kv_latent_width`` wide.
- **Routed layer**: a float32 sigmoid router over ALL ``num_experts``
  outputs, ``s + b`` (``b`` the selection bias) scored in ``n_group``
  groups of consecutive experts by each group's two largest, the
  ``topk_group`` best groups kept and the ``num_experts_per_tok`` largest
  chosen inside them (DeepSeek-V3's ``noaux_tc``); weights ``s``
  renormalised over the chosen, times ``routed_scaling_factor``; SwiGLU
  experts of ``moe_intermediate_size`` of which this chip holds
  ``experts_held`` from ``first_expert``; plus one UNGATED shared SwiGLU of
  ``num_shared_experts x moe_intermediate_size``. A clamped SwiGLU (a
  non-zero ``expert_swiglu_limit`` of a held layer) is not held and is
  refused.

Layer ``j`` is ``h = x + mixer_j(N(x))``, ``out = h + ffn_j(N(h))``; a final
RMSNorm and an untied head. Norm weights are held as the effective scale;
norm statistics, RoPE angles, the router, ``g``, ``beta`` and the recurrent
state are float32, weights and matmuls ``config.dtype``. ``dt_bias`` is held
as its offset from ``dt_bias_center`` (0 for published weights, which a
loader takes as they are; a seeded stack centres it where a token's decay
keeps a memory of tens to hundreds of tokens). The three convolutions'
taps are held as one ``(3 heads dk, taps)`` matrix, ``q | k | v``. The
multi-token-prediction module is not held.

What the pool needs is stated twice and read once
(``serving/kv_pool.layer_groups``): ``layer_states`` (a KDA layer's two
tensors, ``None`` for an MLA layer) and ``kv_latent_width`` (what an MLA
layer stores a token). A KDA layer is handed its slots' state and neither
table nor lengths: one token a slot is ``gated_delta_step``, more is
``gated_delta_chunk`` from the state it is handed (zeros at a prompt's
start); ``prompt_lengths`` marks where each row's true tokens end, so that
a prompt padded to its page bucket leaves the state, and the convolution's
tail, as its last true token left them.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.models.generation import (StateTensor, advance_cache,
                                        check_chunk_bounds, is_paged,
                                        layer_cache, layer_state)
from apex_tpu.models.glm4_moe_lite import Embedding, LatentAttention
from apex_tpu.models.qwen3_next import causal_conv_silu, l2norm
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.gated_delta import gated_delta_chunk, gated_delta_step
from apex_tpu.transformer.moe.dropless import DroplessMoEMLP, Linear, SwiGLU

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144        # the leading dense layers' SwiGLU
    moe_intermediate_size: int = 768     # one expert's SwiGLU
    num_layers: int = 42
    first_layer: int = 0                 # published index of held layer 0
    layer_group_size: int = 6
    first_k_dense_replace: int = 2
    num_heads: int = 32
    # the KDA layers
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_lower_bound: float = -5.0
    dt_bias_center: float = 0.0
    # the MLA layers (the fields ``LatentAttention`` reads)
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    rope_interleave: bool = True
    q_head_norm: bool = True
    head_gate: bool = True
    # routed experts: the router's width, and the share this chip holds
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_shared_experts: int = 1
    experts_held: Optional[int] = None   # None: all ``num_experts``
    first_expert: int = 0
    # the published per-layer SwiGLU clamps (0: none); a held layer's must
    # be 0
    expert_swiglu_limit: Tuple[float, ...] = ()
    shared_expert_swiglu_limit: Tuple[float, ...] = ()
    max_position_embeddings: int = 262144
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    tensor_parallel_size: int = 1        # a latent pool has no head to shard

    def __post_init__(self):
        held = range(self.first_layer, self.first_layer + self.num_layers)
        for name in ("expert_swiglu_limit", "shared_expert_swiglu_limit"):
            limits = getattr(self, name)
            clamped = [i for i in held if i < len(limits) and limits[i]]
            if clamped:
                raise ValueError(
                    f"{name}_list is {limits[clamped[0]]} at published layer "
                    f"{clamped[0]}: a clamped SwiGLU is not held (held "
                    f"layers {held.start} .. {held.stop - 1} must read 0)")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(
            MLA if (self.first_layer + j + 1) % self.layer_group_size == 0
            else KDA for j in range(self.num_layers))

    def is_dense_layer(self, j: int) -> bool:
        return j < self.first_k_dense_replace

    @property
    def head_dim(self) -> int:
        """An MLA head's query/key width in the expanded form."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kda_dim(self) -> int:
        """Width of each of a KDA layer's ``q``, ``k``, ``v``."""
        return self.num_heads * self.kda_head_dim

    @property
    def kv_latent_width(self) -> int:
        """What an MLA layer stores per token, for all heads: the normalized
        ``c_kv`` and the rotated ``k_rope`` (``serving/kv_pool.layout_of``)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def layer_states(self) -> Tuple[Optional[Tuple[StateTensor, ...]], ...]:
        """What each layer keeps per sequence beside the latent pages
        (``None``: nothing, an MLA layer): the pool's one statement of the
        kinds of layer (``serving/kv_pool.layer_groups``)."""
        d = self.kda_head_dim
        state = (
            StateTensor("delta_state", (self.num_heads, d, d), jnp.float32),
            StateTensor("conv_state", (self.conv_size - 1, 3 * self.kda_dim),
                        resolve_compute_dtype(self.dtype)))
        return tuple(state if t == KDA else None for t in self.layer_types)

    @property
    def routed_expert_bytes(self) -> int:
        """Bytes of ONE routed expert's three matrices as held."""
        return (3 * self.hidden_size * self.moe_intermediate_size
                * jnp.dtype(self.param_dtype).itemsize)


def bailing_hybrid_tiny_config(**overrides) -> BailingHybridConfig:
    """Four layers, K K M K (a group of three from published layer 0): a
    dense KDA layer, then routed KDA, MLA, KDA; 16 experts top 4 in 4
    groups of which 2 are kept, all held; float32."""
    base = BailingHybridConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_layers=4, layer_group_size=3,
        first_k_dense_replace=1, num_heads=4, kda_head_dim=16,
        q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0,
        num_experts=16, num_experts_per_tok=4, n_group=4, topk_group=2,
        max_position_embeddings=256, dt_bias_center=-6.0,
        dtype=jnp.float32, param_dtype=jnp.float32)
    return dataclasses.replace(base, **overrides)


class KimiDeltaAttention(nn.Module):
    """The KDA layer's mixer. ``state``: the layer's ``{"delta_state",
    "conv_state"}`` (a row a sequence) or None (a forward pass from an empty
    memory that keeps nothing)."""

    config: BailingHybridConfig

    @nn.compact
    def __call__(self, x, state=None, lengths=None):
        cfg = self.config
        b, s, e = x.shape
        h, d, taps = cfg.num_heads, cfg.kda_head_dim, cfg.conv_size
        width, pd, f32 = cfg.kda_dim, cfg.param_dtype, jnp.float32
        mixed = jnp.concatenate(
            [Linear(width, e, pd, name=f"{n}_proj")(x) for n in "qkv"], -1)
        conv_w = self.param("conv_weight", nn.initializers.normal(0.02),
                            (3 * width, taps), pd)
        a_log = self.param("A_log", nn.initializers.normal(0.02), (h,), pd)
        dt_bias = self.param("dt_bias", nn.initializers.normal(0.02),
                             (width,), pd)
        norm_w = self.param("norm_weight", nn.initializers.ones, (d,), pd)

        conv, tail = causal_conv_silu(
            mixed, conv_w, None if state is None else state["conv_state"],
            lengths)
        q = l2norm(conv[..., :width].reshape(b, s, h, d)) * d ** -0.5
        k = l2norm(conv[..., width:2 * width].reshape(b, s, h, d))
        v = conv[..., 2 * width:].reshape(b, s, h, d)
        beta = jax.nn.sigmoid(
            Linear(h, e, pd, name="b_proj")(x).astype(f32))
        f = Linear(width, e, pd, name="f_proj")(x).astype(f32) \
            + dt_bias.astype(f32) + cfg.dt_bias_center
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None] * f.reshape(b, s, h, d))

        s0 = None if state is None else state["delta_state"]
        if s == 1 and s0 is not None:
            o, s1 = gated_delta_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0])
            o = o[:, None]
        else:
            o, s1 = gated_delta_chunk(q, k, v, g, beta, initial_state=s0,
                                      lengths=lengths)
        o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.rms_eps)
        o = o * norm_w.astype(f32) * jax.nn.sigmoid(
            Linear(width, e, pd, name="g_proj")(x).astype(f32)
            .reshape(b, s, h, d))
        out = Linear(e, width, pd, name="o_proj")(
            o.reshape(b, s, width).astype(x.dtype))
        if state is None:
            return out
        return out, {"delta_state": s1,
                     "conv_state": tail.astype(state["conv_state"].dtype)}


class BailingHybridBlock(nn.Module):
    config: BailingHybridConfig
    layer_idx: int = 0

    @nn.compact
    def __call__(self, x, pos, cache=None, lengths=None):
        cfg = self.config
        e, pd = cfg.hidden_size, cfg.param_dtype
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="input_norm")(x).astype(x.dtype)
        if cfg.layer_types[self.layer_idx] == KDA:
            out = KimiDeltaAttention(cfg, name="mixer")(hn, cache, lengths)
        else:
            out = LatentAttention(cfg, name="attn")(hn, pos, cache)
        if cache is not None:
            out, cache = out
        x = x + out
        hn = FusedRMSNorm(e, eps=cfg.rms_eps, param_dtype=pd,
                          name="post_norm")(x).astype(x.dtype)
        if cfg.is_dense_layer(self.layer_idx):
            x = x + SwiGLU(e, cfg.intermediate_size, pd, name="mlp")(hn)
        else:
            x = x + DroplessMoEMLP(
                hidden_size=e, ffn_hidden_size=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts, k=cfg.num_experts_per_tok,
                shared_experts=cfg.num_shared_experts,
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                params_dtype=pd, held=cfg.experts_held,
                first=cfg.first_expert, name="moe")(hn)
        return x if cache is None else (x, cache)


class BailingHybridModel(nn.Module):
    """Decoder-only LM -> logits [B, S, vocab]; with ``cache=`` the
    incremental-decode entry point: ``(logits, updated cache)``.
    ``logits_positions`` (b, k) runs the head at those chunk positions
    alone. ``prompt_lengths`` (b,): the rows' true token counts where the
    chunk is padded (an admission at its page bucket); positions at or past
    them leave every KDA layer's state untouched."""

    config: BailingHybridConfig

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
        if prompt_lengths is not None:
            prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
        new_layers = []
        for j, kind in enumerate(cfg.layer_types):
            blk = BailingHybridBlock(cfg, layer_idx=j, name=f"layer_{j}")
            if cache is None:
                x = blk(x, pos)
                continue
            view = layer_state(cache, j) if kind == KDA \
                else layer_cache(cache, j)
            x, lc = blk(x, pos, cache=view, lengths=prompt_lengths)
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
