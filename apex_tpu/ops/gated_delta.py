"""The gated delta rule (Gated DeltaNet, Yang et al. 2024): the recurrence of
a linear-attention layer, in the two forms serving needs.

Per head, a float32 state ``S`` of ``(dk, dv)`` and per token ``t`` a query
and key ``q_t``, ``k_t`` of ``dk`` values, a value ``v_t`` of ``dv``, a log
decay ``g_t <= 0`` and a write strength ``beta_t`` in ``(0, 1)``:

    S <- exp(g_t) S              the memory fades
    u  = S^T k_t                 what the memory holds under this key
    S <- S + k_t (beta_t (v_t - u))^T      the correction (the delta rule)
    o_t = S^T q_t                read

:func:`gated_delta_reference` is that statement under ``lax.scan``, one
token a step: the twin the other two are held to.

:func:`gated_delta_step` is one token for every slot of a decode step: a
Pallas kernel (label ``gated_delta_step``) whose grid runs over the slots.
One grid step reads a slot's whole ``(heads, dk, dv)`` state, applies the
four lines above head by head on the vector unit and writes the state back
to where it came from (``input_output_aliases``: the state is the engine's
per-slot cache, and the decode chunk's ``lax.scan`` carries it through this
call without a copy). The kernel is bound by the state's bytes, 2 x
``heads x dk x dv x 4`` a slot; ``q``, ``k``, ``v``, the decay and ``beta``
are a hundredth of that. ``k`` and ``q`` arrive as COLUMNS (``(dk, heads)``:
``dk`` on sublanes, one lane a head), ``v``, ``exp(g)`` and ``beta`` as ROWS
of ``dv`` lanes, so that every product in the body is a broadcast of a
column or a row over the ``(dk, dv)`` tile and both reductions run over
sublanes.

:func:`gated_delta_chunk` is the chunked form for a prompt: inside a chunk
of ``CHUNK`` tokens the corrections ``w_j = beta_j (v_j - u_j)`` solve the
unit lower-triangular system ``(I + A) W = beta (V - exp(G) K S_0)``,
``A[j, l] = beta_j exp(G_j - G_l) (k_j . k_l)`` for ``l < j`` and ``G`` the
chunk's cumulative ``g``, so the sequential corrections become two products
with ``(I + A)^-1`` and everything but ``S_0`` is computed for many chunks
at once; between chunks the state is carried by a scan whose step is four
``(chunk, dk) x (dk, dv)`` products. Every decay ratio is formed as
``exp(G_i - G_j)`` for ``i >= j`` and never as ``exp(-G_j)`` alone: at a
decay rate of 16 and a step of 0.1 a chunk's ``exp(-G)`` is ``exp(102)``,
past float32. ``lengths`` marks the true end of each row: positions at or
past it get ``beta = 0`` and ``g = 0`` and leave the state as it was (a
prompt padded to its page bucket). It is chunked ``jax.numpy``, not a Pallas
kernel (ROADMAP.md, Speed queue).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import _dispatch

_INTERPRET = _dispatch.interpret

#: tokens of one chunk of :func:`gated_delta_chunk`, and chunks whose
#: state-free part is computed at once (1024 tokens: 16 MB a tensor at 32
#: heads of 128, where a whole 16k prompt would be 268 MB a tensor)
CHUNK = 64
_CHUNKS_AT_ONCE = 16


def _repeat_heads(x, heads: int):
    """``(..., hk, d)`` -> ``(..., heads, d)``: value head ``h`` reads key
    head ``h // (heads / hk)``."""
    hk = x.shape[-2]
    return x if hk == heads else jnp.repeat(x, heads // hk, axis=-2)


def gated_delta_reference(q, k, v, g, beta, initial_state=None):
    """The rule token by token. ``q``, ``k``: ``(b, s, hk, dk)`` with
    ``hk`` dividing ``h``; ``v``: ``(b, s, h, dv)``; ``g``, ``beta``:
    ``(b, s, h)``; ``initial_state``: ``(b, h, dk, dv)`` (zeros if None).
    Returns ``(o (b, s, h, dv) float32, state (b, h, dk, dv) float32)``."""
    b, s, h, dv = v.shape
    f32 = jnp.float32
    q, k = (_repeat_heads(x.astype(f32), h) for x in (q, k))
    state = jnp.zeros((b, h, q.shape[-1], dv), f32) \
        if initial_state is None else initial_state.astype(f32)

    def one(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        u = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t,
                                   b_t[..., None] * (v_t - u))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0)
               for x in (q, k, v, g, beta))
    state, o = lax.scan(one, state, xs)
    return jnp.moveaxis(o, 0, 1), state


# --- one token a slot: the decode step's kernel -------------------------------


def _step_kernel(state_ref, cols_ref, rows_ref, out_state_ref, o_ref, *,
                 heads: int):
    for h in range(heads):
        s = state_ref[0, h]                              # (dk, dv)
        k_col = cols_ref[0, 0, :, h:h + 1]               # (dk, 1)
        q_col = cols_ref[0, 1, :, h:h + 1]
        v_row = rows_ref[0, 0, h:h + 1, :]               # (1, dv)
        decay = rows_ref[0, 1, h:h + 1, :]
        beta = rows_ref[0, 2, h:h + 1, :]
        s = s * decay
        u = jnp.sum(s * k_col, axis=0, keepdims=True)    # (1, dv)
        s = s + k_col * (beta * (v_row - u))
        out_state_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True)


def gated_delta_step(state, q, k, v, g, beta):
    """One token a slot, the state updated in place.

    ``state``: ``(slots, h, dk, dv)`` float32; ``q``, ``k``: ``(slots, hk,
    dk)`` with ``hk`` dividing ``h``; ``v``: ``(slots, h, dv)``; ``g``,
    ``beta``: ``(slots, h)``. Returns ``(o (slots, h, dv) float32, the new
    state)``; the new state aliases ``state``'s buffer where the caller
    donates it (the engine's decode chunk does)."""
    return _step(state, q, k, v, g, beta, interpret=_INTERPRET())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(state, q, k, v, g, beta, *, interpret):
    slots, h, dk, dv = state.shape
    f32 = jnp.float32
    if state.dtype != f32:
        raise ValueError(f"the recurrent state is float32, got {state.dtype}")
    if v.shape != (slots, h, dv) or g.shape != (slots, h) \
            or beta.shape != (slots, h) or q.shape != k.shape \
            or q.shape[0] != slots or q.shape[2] != dk or h % q.shape[1]:
        raise ValueError(
            f"gated_delta_step: state {state.shape} does not go with q "
            f"{q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta "
            f"{beta.shape}")
    # columns: (slots, 2, dk, h), one lane a head; rows: (slots, 3, h, dv)
    cols = jnp.stack([_repeat_heads(x.astype(f32), h).transpose(0, 2, 1)
                      for x in (k, q)], axis=1)
    wide = (slots, h, dv)
    rows = jnp.stack([
        v.astype(f32),
        jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], wide),
        jnp.broadcast_to(beta.astype(f32)[..., None], wide)], axis=1)
    state_spec = pl.BlockSpec((1, h, dk, dv), lambda b: (b, 0, 0, 0))
    new_state, o = _dispatch.pallas_call(
        functools.partial(_step_kernel, heads=h),
        grid=(slots,),
        in_specs=[state_spec,
                  pl.BlockSpec((1, 2, dk, h), lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((1, 3, h, dv), lambda b: (b, 0, 0, 0))],
        out_specs=[state_spec,
                   pl.BlockSpec((1, h, dv), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct(wide, f32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # a slot's state in and out, double-buffered: 4 x h*dk*dv*4
            vmem_limit_bytes=max(32 << 20, 6 * h * dk * dv * 4)),
        kernel="gated_delta_step",
        interpret=interpret,
    )(state, cols, rows)
    return o, new_state


# --- a prompt: the chunked form ------------------------------------------------


def _chunk_block(state, block, *, heads: int):
    """``_CHUNKS_AT_ONCE`` chunks (or fewer) of one row block: everything
    that does not need the state for all of them at once, then the state
    through them one chunk a step. ``block``: q, k ``(b, n, c, hk, dk)``,
    v ``(b, n, c, h, dv)``, g, beta ``(b, n, c, h)``, all float32."""
    q, k, v, g, beta = block
    q, k = _repeat_heads(q, heads), _repeat_heads(k, heads)
    c = q.shape[2]
    # heads before the chunk's tokens: (b, n, h, c, .)
    q, k, v = (x.transpose(0, 1, 3, 2, 4) for x in (q, k, v))
    g, beta = g.transpose(0, 1, 3, 2), beta.transpose(0, 1, 3, 2)
    cum = jnp.cumsum(g, axis=-1)                         # G_1 .. G_c
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp(G_i - G_j) for i >= j, 0 above the diagonal: masked BEFORE the
    # exponential, whose argument is positive and unbounded there
    ratio = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("bnhik,bnhjk->bnhij", k, k)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[..., None] * ratio * kk, 0.0)
    # (I + A)^-1 applied to beta V and to beta exp(G) K: the corrections
    # are W = U - Wk S_0
    rhs = jnp.concatenate(
        [beta[..., None] * v,
         (beta * jnp.exp(cum))[..., None] * k], axis=-1)
    solved = lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, wk = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    attn = ratio * jnp.einsum("bnhik,bnhjk->bnhij", q, k)
    q_in = q * jnp.exp(cum)[..., None]                   # reads S_0
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # writes S_c
    total = jnp.exp(cum[..., -1])                        # (b, n, h)

    def one(state, x):
        u_c, wk_c, attn_c, q_c, k_c, total_c = x
        w = u_c - jnp.einsum("bhck,bhkv->bhcv", wk_c, state)
        o = jnp.einsum("bhck,bhkv->bhcv", q_c, state) \
            + jnp.einsum("bhij,bhjv->bhiv", attn_c, w)
        state = state * total_c[..., None, None] \
            + jnp.einsum("bhck,bhcv->bhkv", k_c, w)
        return state, o

    xs = tuple(jnp.moveaxis(x, 1, 0)
               for x in (u, wk, attn, q_in, k_out, total))
    state, o = lax.scan(one, state, xs)
    return state, jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)


def gated_delta_chunk(q, k, v, g, beta, *, initial_state=None,
                      lengths=None):
    """The rule over ``s`` tokens a row in chunks of ``CHUNK``; shapes as
    :func:`gated_delta_reference`. ``lengths``: ``(b,)`` true tokens a row
    (None: all ``s``); a position at or past it changes no state, and its
    output is not meant to be read. Returns ``(o (b, s, h, dv) float32,
    state (b, h, dk, dv) float32)``."""
    b, s, h, dv = v.shape
    dk = q.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if lengths is not None:
        live = jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
        g = jnp.where(live[..., None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    per = CHUNK * _CHUNKS_AT_ONCE
    pad = -s % (CHUNK if s <= per else per)
    if pad:
        # beta = 0 and g = 0: padding is the identity on the state
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (s + pad) // CHUNK
    at_once = min(n, _CHUNKS_AT_ONCE)

    def blocked(x):
        # (blocks, b, chunks at once, chunk, ...): the scan runs over blocks
        x = x.reshape(b, n // at_once, at_once, CHUNK, *x.shape[2:])
        return jnp.moveaxis(x, 1, 0)

    state = jnp.zeros((b, h, dk, dv), f32) if initial_state is None \
        else initial_state.astype(f32)
    state, o = lax.scan(functools.partial(_chunk_block, heads=h), state,
                        tuple(blocked(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s + pad, h, dv)
    return o[:, :s], state

