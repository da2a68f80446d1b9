"""The gated short convolution (LFM2's ``conv`` mixer): a causal depthwise
convolution of a few taps between two elementwise gates.

Per channel and token ``t``, from the three thirds ``B_t``, ``C_t``, ``x_t``
of the mixer's input projection (each ``hidden`` wide), with ``L`` taps
``w_0 .. w_{L-1}``:

    u_t = B_t x_t                          the gated input
    v_t = sum_j w_j u_{t - L + 1 + j}      u is zero before the sequence
    y_t = C_t v_t

What it keeps between calls is a sequence's WINDOW: its last ``L - 1``
values of ``u``, oldest first, in the dtype ``u`` is held in. ``u`` is
rounded to that dtype before the convolution reads it, so a prompt and the
one-token decode steps after it see the same values; the products and the
sum are float32.

:func:`short_conv` is plain XLA, one form for a (padded) prompt and for a
decode step's one token a slot: the convolution over the chunk from the
window it is handed, and the window at each row's TRUE end (``lengths``; a
prompt padded to its page bucket). In the decode chunk it is a few fused
elementwise ops over ``(slots, 3 hidden)`` rows and ``(slots, L - 1,
hidden)`` windows, which the chunk's ``lax.scan`` carries. It has no
Pallas kernel: at 128 slots x 2048 channels in bfloat16 XLA's fusion moves
a layer's update (4.2 MB) in 5.7 us on a TPU v5e, against 5.1 us at the
HBM's bandwidth, and a kernel of its own took 6.7 us.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def short_conv(bcx, weight, window=None, lengths=None):
    """The convolution over a chunk of ``s`` tokens a row.

    ``bcx``: ``(b, s, 3 hidden)``; ``weight``: ``(taps, hidden)``;
    ``window``: ``(b, taps - 1, hidden)`` of the tokens before the chunk
    (None: a sequence's start, zeros in ``bcx``'s dtype); ``lengths``:
    ``(b,)`` true tokens a row (None: all ``s``). Returns ``(y (b, s,
    hidden) in bcx's dtype, the window at each row's true end)``; outputs
    at or past a row's length are not meant to be read."""
    b, s, three_h = bcx.shape
    taps, hidden = weight.shape
    if three_h != 3 * hidden or (window is not None and window.shape != (
            b, taps - 1, hidden)):
        raise ValueError(
            f"short_conv: bcx {bcx.shape}, window "
            f"{None if window is None else window.shape} and weight "
            f"{weight.shape} do not go together: (b, s, 3 hidden), (b, "
            f"taps - 1, hidden), (taps, hidden)")
    f32 = jnp.float32
    if window is None:
        window = jnp.zeros((b, taps - 1, hidden), bcx.dtype)
    parts = bcx.astype(f32)
    u = (parts[..., :hidden] * parts[..., 2 * hidden:]).astype(window.dtype)
    full = jnp.concatenate([window, u], axis=1)        # (b, taps - 1 + s, h)
    v = weight[0].astype(f32) * full[:, 0:s].astype(f32)
    for j in range(1, taps):
        v = v + weight[j].astype(f32) * full[:, j:j + s].astype(f32)
    y = (parts[..., hidden:2 * hidden] * v).astype(bcx.dtype)
    if lengths is None:
        return y, full[:, s:]
    # the window's last ``taps - 1`` TRUE values: positions len .. len +
    # taps - 2 of ``full``, which is shifted by the window's own rows
    return y, jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, taps - 1, axis=0))(full, jnp.asarray(lengths, jnp.int32))
