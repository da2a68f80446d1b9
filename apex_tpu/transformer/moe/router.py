"""Top-k expert router + auxiliary balancing losses.

Beyond-reference extension (SURVEY.md §2.4 names EP as reference-absent):
the reference apex has no mixture-of-experts machinery, but the driver-facing
parallelism surface (dp/tp/pp/sp/ep) treats expert parallelism as first-class,
so the router/dispatch stack lives here under ``apex_tpu.transformer`` next to
the other Megatron-shaped pieces.

Design notes (TPU-first):
- Routing math is fp32 regardless of the compute dtype: top-k gating and the
  softmax over experts are tiny (T x E) but numerically load-bearing — bf16
  logits visibly perturb expert choice near ties.
- Everything is static-shape: top_k, one_hot and cumsum over a fixed expert
  count; no data-dependent shapes, so the whole router traces into one XLA
  program (no host round-trips per step).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def router_z_loss(logits: jnp.ndarray) -> jnp.ndarray:
    """Mean squared logsumexp of the router logits (ST-MoE z-loss).

    Penalizes drifting logit scale, which otherwise pushes the fp32 softmax
    toward saturation. ``logits``: (tokens, experts) fp32.
    """
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse * lse)


def load_balancing_loss(probs: jnp.ndarray,
                        expert_mask: jnp.ndarray) -> jnp.ndarray:
    """Switch-Transformer load-balance loss: ``E * sum_e f_e * P_e``.

    ``probs``: (tokens, E) fp32 router probabilities.
    ``expert_mask``: (tokens, E) 0/1 — token t routed to expert e (any of its
    top-k slots). ``f_e`` is the fraction of routed (token, slot) assignments
    landing on e; ``P_e`` the mean router probability for e. Minimized (=1.0)
    at a uniform assignment; differentiable through ``P_e`` only, like the
    original.
    """
    num_experts = probs.shape[-1]
    f = jnp.mean(expert_mask.astype(jnp.float32), axis=0)
    f = f / jnp.maximum(jnp.sum(f), 1e-9)          # normalize over k slots
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(lax.stop_gradient(f) * p)


class TopKRouter(nn.Module):
    """Linear gate -> fp32 softmax over experts.

    Returns ``(probs, logits)`` both fp32, shape (tokens, num_experts). The
    dispatch/combine construction lives in
    :mod:`apex_tpu.transformer.moe.layer` so the router stays reusable for
    dropless variants.
    """

    num_experts: int
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        w = self.param("weight", nn.initializers.lecun_normal(),
                       (self.num_experts, x.shape[-1]), self.params_dtype)
        # router GEMM in fp32: (T, d) x (d, E) is negligible FLOPs but the
        # probabilities steer everything downstream
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32).T
        probs = nn.softmax(logits, axis=-1)
        return probs, logits


class SigmoidBiasTopKRouter(nn.Module):
    """Sigmoid scores with a selection bias (DeepSeek-V3's ``noaux_tc``
    with one group): ``s = sigmoid(x W^T)`` in fp32; the ``k`` largest of
    ``s + b`` are chosen, ``b`` the learned ``e_score_correction_bias``
    that balances load without an auxiliary loss; the chosen experts'
    weights are ``s`` WITHOUT ``b``, renormalized to sum 1 where
    ``norm_topk_prob``, times ``routed_scaling_factor``.

    Returns ``(idx, weights)``: ``(tokens, k)`` int32 expert ids and their
    fp32 weights. No capacity, no dropping: what consumes them decides how
    the (token, expert) pairs are computed
    (:mod:`apex_tpu.transformer.moe.dropless`).
    """

    num_experts: int
    k: int
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        w = self.param("weight", nn.initializers.lecun_normal(),
                       (self.num_experts, x.shape[-1]), self.params_dtype)
        b = self.param("e_score_correction_bias", nn.initializers.zeros,
                       (self.num_experts,), self.params_dtype)
        scores = jax.nn.sigmoid(
            x.astype(jnp.float32) @ w.astype(jnp.float32).T)
        _, idx = lax.top_k(scores + b.astype(jnp.float32), self.k)
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        if self.norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return idx, weights * self.routed_scaling_factor


class SoftmaxTopKRouter(nn.Module):
    """Softmax scores, the ``k`` largest (Qwen3-MoE's router): ``p =
    softmax(x W^T)`` in fp32 over ALL experts; the ``k`` largest are
    chosen and their weights are their own ``p``, renormalized to sum 1
    where ``norm_topk_prob``. No bias, no scaling factor.

    Returns ``(idx, weights)`` as :class:`SigmoidBiasTopKRouter` does, so
    the two are interchangeable before
    :mod:`apex_tpu.transformer.moe.dropless`.
    """

    num_experts: int
    k: int
    norm_topk_prob: bool = True
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        w = self.param("weight", nn.initializers.lecun_normal(),
                       (self.num_experts, x.shape[-1]), self.params_dtype)
        probs = jax.nn.softmax(
            x.astype(jnp.float32) @ w.astype(jnp.float32).T, axis=-1)
        weights, idx = lax.top_k(probs, self.k)
        if self.norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        return idx, weights
