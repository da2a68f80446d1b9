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

A SHARE OF THE EXPERTS. A chip of a deployment that spreads a layer's
experts over several chips holds ``held`` of them, ``first .. first + held -
1`` (:class:`DroplessMoEMLP` ``held=``, ``first=``). The router is whole: all
``num_experts`` outputs, the ``k`` largest, renormalised over those ``k`` and
never over the held ones. A pair whose expert lies on another chip gets
weight 0 and NO ROW in the grouped products: it sorts behind the last held
expert's run and no group's size counts it, so the products' work follows
the held pairs alone. What the absent experts would add is the other chips'
to compute and the exchange's to bring; nothing here stands in for either.
"""

from __future__ import annotations

from typing import Optional

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
#: what a layer that holds a SHARE of its experts sows: the three above over
#: the held pairs, experts and rows, then the pairs whose expert is elsewhere
SHARE_ROUTING_STATS = ROUTING_STATS + ("expert_pairs_elsewhere",)
ROUTING_COLLECTION = "routing"


def grouped_experts(x, idx, weights, gate, up, down, *, first=None):
    """``sum_i weights[t, i] * SwiGLU_{idx[t, i]}(x[t])`` for every row.

    ``x``: (T, d); ``idx``/``weights``: (T, k); ``gate``/``up``:
    (E, d, m); ``down``: (E, m, d). Returns ``(y (T, d) fp32, sizes (E,))``
    with ``sizes`` the rows each expert got.

    ``first`` (an int; None: the stack is all the experts): the stack holds
    experts ``first .. first + E - 1`` of those ``idx`` names. A pair whose
    expert is not among them counts in no group, so it costs no row of the
    products, and adds nothing to ``y``."""
    t, k = idx.shape
    held = gate.shape[0]
    flat = idx.reshape(-1)
    if first is not None:
        flat = flat - first
        # elsewhere: behind the last held expert's run, in no group
        flat = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(flat, stable=True)               # pairs by expert
    rows = x[order // k]                                 # (T*k, d)
    sizes = jnp.bincount(flat, length=held).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        mid = jax.nn.silu(lax.ragged_dot(rows, gate, sizes)) \
            * lax.ragged_dot(rows, up, sizes)
        out = lax.ragged_dot(mid, down, sizes)           # (T*k, d)
    if first is not None:
        # the rows past the last group are no product's: whatever the
        # kernel left there must not meet even a weight of 0
        out = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], out, 0)
        weights = jnp.where(flat.reshape(t, k) < held, weights, 0.0)
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
    of their summed width, times ``sigmoid(w_g . x)`` where ``shared_gate``.
    ``held`` (None: all) and ``first``: the share of the experts this chip
    holds (module docstring): the stack is ``held`` experts, the router all
    ``num_experts`` outputs."""

    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    k: int
    shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    params_dtype: jnp.dtype = jnp.float32
    router: str = "sigmoid_bias"
    held: Optional[int] = None
    first: int = 0
    shared_gate: bool = False

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
        share = self.held is not None
        if share and not 0 <= self.first <= e - self.held:
            raise ValueError(f"experts {self.first} .. {self.first} + "
                             f"{self.held} are not among {e}")
        gate, up, down = ExpertStack(self.held if share else e, d, m,
                                     self.params_dtype, name="experts")()
        y, sizes = grouped_experts(xt, idx, weights, gate.astype(x.dtype),
                                   up.astype(x.dtype), down.astype(x.dtype),
                                   first=self.first if share else None)
        stats = [jnp.int32(idx.size), (sizes > 0).sum().astype(jnp.int32),
                 sizes.max()]
        if share:
            stats[0] = sizes.sum()
            stats.append(jnp.int32(idx.size) - sizes.sum())
        self.sow(ROUTING_COLLECTION, "stats", jnp.stack(stats))
        y = y.astype(x.dtype)
        if self.shared_experts:
            shared = SwiGLU(d, m * self.shared_experts, self.params_dtype,
                            name="shared")(xt)
            if self.shared_gate:
                shared = shared * jax.nn.sigmoid(
                    Linear(1, d, self.params_dtype, name="shared_gate")(xt)
                    .astype(jnp.float32)).astype(x.dtype)
            y = y + shared
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
