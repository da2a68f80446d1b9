"""Dropless routed experts: sort the (token, expert) pairs by expert and run
one grouped product per projection over all experts.

Serving cannot use :class:`~apex_tpu.transformer.moe.layer.MoEMLP`'s
capacity dispatch: its ``(T, E, C)`` one-hot is gigabytes at a 16k-token
prefill and it drops tokens past capacity. Here nothing has a capacity: the
``T * k`` pairs are sorted by expert (stable, so a token's pairs keep their
order), the rows gathered, and ``jax.lax.ragged_dot`` multiplies each
expert's contiguous run of rows with that expert's matrix — on the TPU one
Mosaic kernel per projection (XLA names them ``ragged-dot-*``; PERF.md
section 3), whose work follows the rows routed, not ``E x T``. The same
code serves an admit program's thousands of tokens and the 32 rows of a
decode step inside the engine's scan.

What the routing did is sown into the ``routing`` collection as one int32
vector per layer (:data:`ROUTING_STATS`), so a caller that makes the
collection mutable (the engine's decode chunk) gets it back with the
tokens; every other caller pays nothing.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer.moe.router import (SigmoidBiasTopKRouter,
                                             SoftmaxTopKRouter)

#: the ``routing`` collection's vector, per expert layer and call: pairs
#: routed (rows x k), distinct experts with at least one row, and the
#: fullest expert's rows — over every row of the call, idle slots included
ROUTING_STATS = ("expert_pairs_routed", "experts_hit", "expert_load_max")
ROUTING_COLLECTION = "routing"


def grouped_experts(x, idx, weights, gate, up, down):
    """``sum_i weights[t, i] * SwiGLU_{idx[t, i]}(x[t])`` for every row.

    ``x``: (T, d); ``idx``/``weights``: (T, k); ``gate``/``up``:
    (E, d, m); ``down``: (E, m, d). Returns ``(y (T, d) fp32, sizes (E,))``
    with ``sizes`` the rows each expert got."""
    t, k = idx.shape
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)               # pairs by expert
    rows = x[order // k]                                 # (T*k, d)
    sizes = jnp.bincount(flat, length=gate.shape[0]).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        mid = jax.nn.silu(lax.ragged_dot(rows, gate, sizes)) \
            * lax.ragged_dot(rows, up, sizes)
        out = lax.ragged_dot(mid, down, sizes)           # (T*k, d)
    # back to (token, choice) order: a gather through the inverse
    # permutation, so the combine is a fixed-order sum and not a scatter-add
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=order.dtype))
    out = out[inverse].reshape(t, k, -1).astype(jnp.float32)
    return (out * weights[..., None]).sum(1), sizes


class DroplessMoEMLP(nn.Module):
    """``sum_i w_i E_i(x) + E_shared(x)``: top-k routing by ``router``
    (``"sigmoid_bias"``: :class:`SigmoidBiasTopKRouter`, the default;
    ``"softmax"``: :class:`SoftmaxTopKRouter`, which has no scaling
    factor), SwiGLU experts of width ``ffn_hidden_size`` stacked ``(E, in,
    out)``, and ``shared_experts`` always-on experts fused into one SwiGLU
    of their summed width."""

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    k: int
    shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    params_dtype: jnp.dtype = jnp.float32
    router: str = "sigmoid_bias"

    def _router(self):
        common = dict(norm_topk_prob=self.norm_topk_prob,
                      params_dtype=self.params_dtype, name="router")
        if self.router == "softmax":
            return SoftmaxTopKRouter(self.num_experts, self.k, **common)
        if self.router != "sigmoid_bias":
            raise ValueError(f"unknown router {self.router!r} (has "
                             f"'sigmoid_bias', 'softmax')")
        return SigmoidBiasTopKRouter(
            self.num_experts, self.k,
            routed_scaling_factor=self.routed_scaling_factor, **common)

    @nn.compact
    def __call__(self, x):
        lead, d = x.shape[:-1], x.shape[-1]
        e, m = self.num_experts, self.ffn_hidden_size
        xt = x.reshape(-1, d)
        idx, weights = self._router()(xt)
        gate, up, down = ExpertStack(e, d, m, self.params_dtype,
                                     name="experts")()
        y, sizes = grouped_experts(xt, idx, weights, gate.astype(x.dtype),
                                   up.astype(x.dtype), down.astype(x.dtype))
        self.sow(ROUTING_COLLECTION, "stats", jnp.stack([
            jnp.int32(idx.size), (sizes > 0).sum().astype(jnp.int32),
            sizes.max()]))
        y = y.astype(x.dtype)
        if self.shared_experts:
            y = y + SwiGLU(d, m * self.shared_experts, self.params_dtype,
                           name="shared")(xt)
        return y.reshape(*lead, d)


class SwiGLU(nn.Module):
    """``W_down(silu(W_gate x) * W_up x)``, weights held (out, in)."""

    hidden_size: int
    ffn_hidden_size: int
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        d, m = self.hidden_size, self.ffn_hidden_size
        gate, up, down = (
            Linear(m, d, self.params_dtype, name="gate_proj"),
            Linear(m, d, self.params_dtype, name="up_proj"),
            Linear(d, m, self.params_dtype, name="down_proj"))
        return down(jax.nn.silu(gate(x)) * up(x))


class Linear(nn.Module):
    """``x W^T`` with ``W`` held (out, in) and no bias."""

    out_features: int
    in_features: int
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.normal(0.02),
                       (self.out_features, self.in_features),
                       self.params_dtype)
        return lax.dot_general(x, w.astype(x.dtype),
                               (((x.ndim - 1,), (1,)), ((), ())))


class ExpertStack(nn.Module):
    """The routed experts' three matrices, stacked over the experts."""

    num_experts: int
    hidden_size: int
    ffn_hidden_size: int
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        e, d, m = self.num_experts, self.hidden_size, self.ffn_hidden_size
        init = nn.initializers.normal(0.02)
        return (self.param("gate_proj", init, (e, d, m), self.params_dtype),
                self.param("up_proj", init, (e, d, m), self.params_dtype),
                self.param("down_proj", init, (e, m, d), self.params_dtype))
