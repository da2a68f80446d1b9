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

THE SERVED LAYOUT. XLA's ragged-dot kernel takes its tile from the operands'
widths: on K and on N, the largest power of two up to 512 that divides the
width (``ragged_dot_tiling`` of the compiled call, jax 0.9.0 / libtpu 0.0.34,
a described v5e; rows x K x N):

=============================  ===============  ===========
rows x (E, K, N)               tiling           weight tile
=============================  ===============  ===========
384 x (64, 2304, 896)          128,256,128      64 KB
384 x (64, 896, 2304)          128,128,256      64 KB
384 x (64, 2304, 1024)         128,256,512      256 KB
384 x (64, 1024, 2304)         128,512,256      256 KB
128 x (64, 2048, 1536)         128,512,512      512 KB
131072 x (64, 2304, 896)       512,256,128      64 KB
=============================  ===============  ===========

and a grid step costs about :data:`GRID_STEP_S` whatever it moves (the
ledger's PR 39 lines: Mellum's 96,768 grid steps a decode step in 31.5 ms
against 7.74 ms of bytes, 0.25 us a step; GLM's ~9,756 in 8.35 ms against
6.2, 0.22 us). So :func:`served_width` picks, from the shapes alone, the
width ``w >= m`` of an expert's hidden layer that minimises the modelled
time of its three products, bytes at :data:`HBM_BYTES_PER_S` plus grid
steps, and :func:`lay_out_experts` holds every stack whose ``w`` is wider
zero-padded to it: gate and up ``(E, d, w)`` with zero columns ``m ..
w - 1``, down ``(E, w, d)`` with zero rows there. ``silu(0) * 0 = 0`` and a
zero row of down adds exactly 0, so the layer computes what the published
one does, in the same precision. Mellum's 2304 x 896 is served at 1024
(14 % more bytes for a quarter of the grid steps); GLM's 2048 x 1536 and
Qwen3-Next's 2048 x 512 tile at 512 already and are served as published;
2048 x 768 stays 768 (its padding would cost more bytes than its tile
saves). The parameters a model DECLARES stay the published ``(E, d, m)``:
the laid-out stacks live in the collection :data:`SERVED_COLLECTION`, which
:class:`ExpertStack` reads where the variables carry it. The serving
engine lays out the tree it is handed, in place and one matrix at a time
(it takes ownership of the tree; docs/serving.md "Routed experts' served
layout"); training, lock-step ``generate`` and every other caller keep the
published layout. A counter of expert bytes (``routed_expert_bytes``)
counts the published bytes: what the routing must read, not the zeros.

THE SERVED ROWS. XLA takes the row tile by the same rule, from the row
count (``ragged_dot_tiling`` of ``(rows, 2048) x (64, 2048, 1536)``, the
same versions):

=====  ====  =====  ====  ======  ====
rows   tile  rows   tile  rows    tile
=====  ====  =====  ====  ======  ====
128    128   768    256   4224    128
384    128   1024   512   8448    256
512    512   1152   128   16384   512
520    8     2048   512   16512   128
576    64    4096   512
640    128
=====  ====  =====  ====  ======  ====

A product of ``K`` in and ``N`` out on a row tile ``tm`` then makes ``v =
groups hit + ceil(rows / tm) - 1`` (group, row tile) visits, each a pass of
``tm`` rows through the MXU and a read of the expert's ``K x N`` weights,
and rows in no group cost nothing. LFM2's decode step routes 128 slots x
top 4 = 512 rows, ~8 a group, so each of its 64 groups ran a whole 512-row
tile: 1.2 ms a product (the ledger's PR 41 line) where 640 rows on 128-row
tiles take 0.73 (timed alone on the chip, PERF.md section 6). Timed alone
at every (rows, stack) the routed cells compile, at tm 128, 256 and 512, a
visit costs its MXU pass AND its weights' read, not the larger of them:
``t = v (tm 2 K N / MXU_FLOPS_PER_S + K N itemsize / HBM_BYTES_PER_S + (K /
t(K)) (N / t(N)) GRID_STEP_S)`` ranks the three tiles as the chip does at
all 19 shapes (the pick at most 1.5 % over the fastest), where the larger
of the first two terms picked tiles up to 18 % slower. It ranks tiles and
does not predict a time: on the four routed cells' decode products it
reads 1.2-1.5 x high (ms a product, model / measured: GLM 0.79 / 0.56,
Qwen3-Next 0.45 / 0.31, Mellum 0.85 / 0.70, LFM2 1.71 / 1.20 at 512 rows
and 0.97 / 0.67 at 640), where the larger of the two terms read within
10 % of the first four. So :func:`served_rows` hands the products, from
the shapes alone, the row count of ``rows`` and the smallest odd
multiples of 128, 256 and 512 not under it that this model times least,
the rows past ``rows`` zeros in no group (a share of the experts is
priced on the rows its groups can expect): LFM2's 512 decode rows are
handed 640; GLM's 128, Mellum's 384 and
Qwen3-Next's 640 tile 128 high already and are handed as they are; an
admission's rows go to 128-row tiles up to ~4k rows over a 64 x 2048 x 1536
stack, 256 up to ~16k, and keep 512 above. The K and N tiles do not change,
so no sum changes its order.
"""

from __future__ import annotations

from collections.abc import Mapping
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
#: where an expert stack laid out at its served width lives
#: (:func:`lay_out_experts`), mirroring the stack's path under ``params``
SERVED_COLLECTION = "served_experts"
#: an expert stack's three matrices, as :class:`ExpertStack` names them
STACK = ("gate_proj", "up_proj", "down_proj")
#: the modelled cost of one grid step of XLA's ragged-dot kernel, seconds
#: (module docstring: the ledger's PR 39 readings, 0.22 and 0.25 us)
GRID_STEP_S = 0.23e-6
#: the v5e's HBM bandwidth, bytes a second
HBM_BYTES_PER_S = 819e9
#: the v5e's MXU peak in bfloat16, FLOP a second
MXU_FLOPS_PER_S = 197e12


def _tile(width: int) -> int:
    """The tile XLA's ragged-dot kernel takes on an axis of ``width`` (the
    rows too): the largest power of two up to 512 that divides it."""
    return min(width & -width, 512)


def served_width(d: int, m: int, itemsize: int = 2) -> int:
    """The hidden width ``w >= m`` at which an expert of ``d`` in and out
    and ``m`` hidden is served: of ``m`` and ``m`` rounded up to a multiple
    of 128, 256 and 512, the one whose three products take the least
    modelled time, ``3 d w itemsize / HBM_BYTES_PER_S + GRID_STEP_S x 3 (d
    / t(d)) (w / t(w))`` with ``t`` :func:`_tile` (module docstring);
    the narrowest of equals."""
    def seconds(w):
        steps = 3 * (d // _tile(d)) * (w // _tile(w))
        return 3 * d * w * itemsize / HBM_BYTES_PER_S + GRID_STEP_S * steps

    widths = {m} | {-(-m // a) * a for a in (128, 256, 512)}
    return min(sorted(widths), key=seconds)


def served_rows(rows: int, experts: int, d: int, w: int,
                itemsize: int = 2, grouped: Optional[int] = None) -> int:
    """The row count ``R >= rows`` the grouped products of ``rows`` routed
    rows over ``experts`` experts of ``d`` in and out and ``w`` hidden are
    handed (the rows past ``rows`` lie in no group): of ``rows`` and, for
    ``tm`` 128, 256 and 512, the smallest odd multiple of ``tm`` not under
    ``rows`` (whose row tile is ``tm``), the one whose three products take
    the least modelled time (module docstring); ``rows`` where it ties.
    ``grouped`` (None: ``rows``): the rows the groups can expect, where the
    stack is a share of the router's experts.

    A product of ``K`` in and ``N`` out on a row tile ``tm`` visits ``v =
    min(experts, grouped) + ceil(grouped / tm) - 1`` (group, row tile)
    pairs and takes ``v (tm 2 K N / MXU_FLOPS_PER_S + K N itemsize /
    HBM_BYTES_PER_S + (K / t(K)) (N / t(N)) GRID_STEP_S)``; a tile under
    128 rows is charged the MXU's full 128-row pass."""
    grouped = rows if grouped is None else grouped

    def seconds(served):
        tm = _tile(served)
        visits = min(experts, grouped) + -(-grouped // tm) - 1
        total = 0.0
        for k, n in ((d, w), (d, w), (w, d)):
            mxu = max(tm, 128) * 2 * k * n / MXU_FLOPS_PER_S
            hbm = k * n * itemsize / HBM_BYTES_PER_S
            steps = (k // _tile(k)) * (n // _tile(n))
            total += visits * (mxu + hbm + steps * GRID_STEP_S)
        return total

    padded = sorted({(-(-rows // tm) | 1) * tm for tm in (128, 256, 512)})
    return min([rows] + padded, key=seconds)


def _grouped(rows: int, held: int, num_experts: Optional[int]) -> int:
    """The rows a stack of ``held`` of the router's ``num_experts`` experts
    can expect in its groups (None: the stack is all of them)."""
    return rows if num_experts is None else -(-rows * held // num_experts)


def _stacks(tree, path=()):
    """``(path, node)`` of every expert stack under ``tree``: a dict of
    exactly :data:`STACK`, three-dimensional each."""
    if not isinstance(tree, Mapping):
        return
    if set(tree) == set(STACK) and all(
            getattr(tree[n], "ndim", 0) == 3 for n in STACK):
        yield path, tree
        return
    for key, node in tree.items():
        yield from _stacks(node, path + (key,))


def _pad(x, axis: int, width: int):
    """``x`` zero-padded along ``axis`` to ``width`` (a shape stays a
    shape), and DONE: a pad dispatched behind work still running on the
    device allocates its output at once and frees its input only when it
    has run, so two dozen of them queued behind the weights' making held
    every stack twice (15.04 GB at Mellum's cell against 11.84)."""
    shape = x.shape[:axis] + (width,) + x.shape[axis + 1:]
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(shape, x.dtype)
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, width - x.shape[axis])
    return jnp.pad(x, pads).block_until_ready()


def expert_widths(variables) -> dict:
    """``{layer: {"published": m, "served": w, "bytes_added": n}}`` for
    every expert stack of ``variables`` (``layer`` its '/'-joined path):
    ``w`` what :func:`served_width` gives the stack's shapes, ``n`` the
    zeros laying it out adds. A stack already laid out (by an earlier
    engine handed the same tree) reads ``published`` None: its shapes no
    longer say it."""
    out = {}
    variables = variables or {}
    for path, stack in _stacks(variables.get("params")):
        e, d, m = stack["gate_proj"].shape
        itemsize = jnp.dtype(stack["gate_proj"].dtype).itemsize
        w = served_width(d, m, itemsize)
        out["/".join(map(str, path))] = {
            "published": m, "served": w,
            "bytes_added": 3 * e * d * (w - m) * itemsize}
    for path, stack in _stacks(variables.get(SERVED_COLLECTION)):
        out["/".join(map(str, path))] = {
            "published": None, "served": stack["gate_proj"].shape[2],
            "bytes_added": None}
    return out


def expert_rows(variables, k: int, tokens: Mapping,
                num_experts: Optional[int] = None) -> dict:
    """``{name: (rows, served, tile)}`` for each ``name: T`` of ``tokens``:
    the ``T * k`` rows a call over ``T`` tokens routes, the row count
    :func:`served_rows` hands its grouped products at the shapes and dtype
    of ``variables``' expert stacks (a share of the router's
    ``num_experts``, as :func:`grouped_experts` is told), and the row tile
    XLA takes from that count. Empty where ``variables`` hold no stack."""
    variables = variables or {}
    stacks = [s for c in ("params", SERVED_COLLECTION)
              for _, s in _stacks(variables.get(c))]
    if not stacks:
        return {}
    e, d, w = stacks[0]["gate_proj"].shape
    itemsize = jnp.dtype(stacks[0]["gate_proj"].dtype).itemsize
    out = {}
    for name, t in tokens.items():
        served = served_rows(t * k, e, d, w, itemsize,
                             grouped=_grouped(t * k, e, num_experts))
        out[name] = (t * k, served, _tile(served))
    return out


def lay_out_experts(variables) -> dict:
    """Hold every expert stack of ``variables`` whose :func:`served_width`
    is wider than published zero-padded to it, IN PLACE: the stack leaves
    ``params`` and its padded matrices go to :data:`SERVED_COLLECTION`
    under the same path, one matrix at a time, so at most one matrix is
    held twice (the caller's tree gives up the published one; whoever
    hands the tree over hands it to the layout). Returns
    :func:`expert_widths` as it read before. ``None`` or a tree with no
    ``params`` (a trace-only engine) is left as it is."""
    widths = expert_widths(variables)
    if not widths:
        return widths
    served = variables.get(SERVED_COLLECTION, {})
    for path, stack in list(_stacks(variables["params"])):
        w = widths["/".join(map(str, path))]["served"]
        if w == stack["gate_proj"].shape[2]:
            continue
        parent = variables["params"]
        for key in path[:-1]:
            parent = parent[key]
        if not all(isinstance(t, dict) for t in (variables, parent, stack)):
            raise TypeError(
                "the expert stacks are laid out in place: hand the engine "
                "a tree of dicts (flax.core.unfreeze), not a FrozenDict")
        node = served
        for key in path:
            node = node.setdefault(key, {})
        for name in STACK:
            node[name] = _pad(stack.pop(name), 1 if name == "down_proj"
                              else 2, w)
        del parent[path[-1]]
        variables[SERVED_COLLECTION] = served
    return widths


def grouped_experts(x, idx, weights, gate, up, down, *, first=None,
                    num_experts=None):
    """``sum_i weights[t, i] * SwiGLU_{idx[t, i]}(x[t])`` for every row.

    ``x``: (T, d); ``idx``/``weights``: (T, k); ``gate``/``up``:
    (E, d, m); ``down``: (E, m, d). Returns ``(y (T, d) fp32, sizes (E,))``
    with ``sizes`` the rows each expert got.

    ``first`` (an int; None: the stack is all the experts): the stack holds
    experts ``first .. first + E - 1`` of the ``num_experts`` those ``idx``
    name. A pair whose expert is not among them counts in no group, so it
    costs no row of the products, and adds nothing to ``y``.

    The products see the ``T*k`` rows followed by zero rows, in no group,
    up to :func:`served_rows` (a share expecting ``E / num_experts`` of the
    rows in groups): XLA's row tile follows the count."""
    t, k = idx.shape
    held, d, w = gate.shape
    flat = idx.reshape(-1)
    if first is not None:
        flat = flat - first
        # elsewhere: behind the last held expert's run, in no group
        flat = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(flat, stable=True)               # pairs by expert
    rows = x[order // k]                                 # (T*k, d)
    sizes = jnp.bincount(flat, length=held).astype(jnp.int32)
    served = served_rows(t * k, held, d, w, jnp.dtype(gate.dtype).itemsize,
                         grouped=_grouped(t * k, held, num_experts))
    rows = jnp.pad(rows, ((0, served - t * k), (0, 0)))
    with jax.named_scope("moe_experts"):
        mid = jax.nn.silu(lax.ragged_dot(rows, gate, sizes)) \
            * lax.ragged_dot(rows, up, sizes)
        out = lax.ragged_dot(mid, down, sizes)[:t * k]   # (T*k, d)
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
    ``norm_eps``: what the sigmoid router adds to the chosen weights' sum
    before it renormalises them; ``n_group`` / ``topk_group``: its groups
    of experts (:func:`~apex_tpu.transformer.moe.router.group_limited`).
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
    norm_eps: float = 1e-20
    params_dtype: jnp.dtype = jnp.float32
    router: str = "sigmoid_bias"
    held: Optional[int] = None
    first: int = 0
    shared_gate: bool = False
    n_group: int = 1
    topk_group: int = 1

    def _router(self):
        common = dict(norm_topk_prob=self.norm_topk_prob,
                      params_dtype=self.params_dtype, name="router")
        if self.router == "softmax":
            if self.n_group > 1:
                raise ValueError("groups of experts are the sigmoid "
                                 "router's (noaux_tc), not the softmax's")
            return SoftmaxTopKRouter(self.num_experts, self.k, **common)
        if self.router != "sigmoid_bias":
            raise ValueError(f"unknown router {self.router!r} (has "
                             f"'sigmoid_bias', 'softmax')")
        return SigmoidBiasTopKRouter(
            self.num_experts, self.k,
            routed_scaling_factor=self.routed_scaling_factor,
            norm_eps=self.norm_eps, n_group=self.n_group,
            topk_group=self.topk_group, **common)

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
                                   first=self.first if share else None,
                                   num_experts=e if share else None)
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
    """The routed experts' three matrices, stacked over the experts: the
    published ``(E, d, m)`` parameters, or the stack as the serving engine
    laid it out where the variables carry :data:`SERVED_COLLECTION`."""

    num_experts: int
    hidden_size: int
    ffn_hidden_size: int
    params_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        if self.has_variable(SERVED_COLLECTION, STACK[0]):
            # the serving engine's layout (lay_out_experts): the same
            # experts zero-padded to a width the grouped products tile well
            return tuple(self.get_variable(SERVED_COLLECTION, n)
                         for n in STACK)
        e, d, m = self.num_experts, self.hidden_size, self.ffn_hidden_size
        init = nn.initializers.normal(0.02)
        return (self.param("gate_proj", init, (e, d, m), self.params_dtype),
                self.param("up_proj", init, (e, d, m), self.params_dtype),
                self.param("down_proj", init, (e, m, d), self.params_dtype))
