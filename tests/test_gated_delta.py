"""``ops/gated_delta.py`` on the CPU: the chunked form and the one-token
kernel (Pallas, interpret mode) against the token-by-token recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.gated_delta import (CHUNK, gated_delta_chunk,
                                      gated_delta_reference,
                                      gated_delta_step)

#: float32 on both sides, sums in another order: outputs of order 0.3 agree
#: to a few 1e-7 a chunk; the same inputs rounded to bfloat16 read 1e-3
#: (``test_bfloat16_inputs_fail_the_tolerance``)
TOL = 5e-6


def _inputs(b, s, hk, h, dk, dv, *, rate=1.0, seed=0):
    """Unit keys, queries of norm ``dk^-0.5``, ``g = -rate * softplus(.)``
    (about ``-0.7 rate`` a token), ``beta`` in (0, 1), a state to start
    from."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, hk, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -rate * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("length", [1, 5, CHUNK, CHUNK + 1, 100,
                                    16 * CHUNK, 17 * CHUNK + 6])
@pytest.mark.parametrize("rate", [1.0, 3.0])
def test_the_chunked_rule_is_the_recurrence(length, rate):
    """Lengths that are and are not multiples of the chunk, and of the
    block of chunks computed at once; at ``rate`` 3 a chunk's cumulative
    ``g`` is about -135, whose ``exp(-G)`` is past float32: the ratios are
    formed as ``exp(G_i - G_j)``."""
    q, k, v, g, beta, state = _inputs(2, length, 2, 4, 16, 8, rate=rate)
    assert rate < 3 or float(-g.sum(1).min()) > 89 or length < CHUNK
    want_o, want_s = gated_delta_reference(q, k, v, g, beta, state)
    got_o, got_s = gated_delta_chunk(q, k, v, g, beta, initial_state=state)
    assert np.isfinite(np.asarray(got_o)).all()
    assert float(jnp.abs(got_o - want_o).max()) < TOL
    assert float(jnp.abs(got_s - want_s).max()) < TOL


def test_the_chunked_rule_starts_from_an_empty_memory_by_default():
    q, k, v, g, beta, _ = _inputs(1, 70, 2, 2, 16, 8)
    want_o, want_s = gated_delta_reference(q, k, v, g, beta)
    got_o, got_s = gated_delta_chunk(q, k, v, g, beta)
    assert float(jnp.abs(got_o - want_o).max()) < TOL
    assert float(jnp.abs(got_s - want_s).max()) < TOL


@pytest.mark.parametrize("true_len", [1, 37, 64, 99])
def test_positions_past_the_true_length_leave_the_state_alone(true_len):
    """A prompt padded to its page bucket: what lies at or past ``lengths``
    changes neither the state nor the outputs before it."""
    q, k, v, g, beta, state = _inputs(2, 100, 2, 4, 16, 8, seed=1)
    lengths = jnp.asarray([true_len, 100])
    got_o, got_s = gated_delta_chunk(q, k, v, g, beta, initial_state=state,
                                     lengths=lengths)
    cut = lambda x: x[:1, :true_len]  # noqa: E731
    want_o, want_s = gated_delta_reference(
        cut(q), cut(k), cut(v), cut(g), cut(beta), state[:1])
    assert float(jnp.abs(got_o[:1, :true_len] - want_o).max()) < TOL
    assert float(jnp.abs(got_s[:1] - want_s).max()) < TOL
    whole_o, whole_s = gated_delta_reference(q, k, v, g, beta, state)
    assert float(jnp.abs(got_s[1] - whole_s[1]).max()) < TOL
    assert float(jnp.abs(got_o[1] - whole_o[1]).max()) < TOL


@pytest.mark.parametrize("slots,hk,h", [(3, 2, 4), (1, 4, 4), (5, 1, 2)])
def test_the_one_token_kernel_is_one_step_of_the_recurrence(slots, hk, h):
    q, k, v, g, beta, state = _inputs(slots, 1, hk, h, 16, 8, seed=2)
    want_o, want_s = gated_delta_reference(q, k, v, g, beta, state)
    got_o, got_s = gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0],
                                    g[:, 0], beta[:, 0])
    assert got_o.shape == (slots, h, 8) and got_s.shape == state.shape
    assert float(jnp.abs(got_o - want_o[:, 0]).max()) < 1e-6
    assert float(jnp.abs(got_s - want_s).max()) < 1e-6


def test_steps_after_a_chunk_continue_it():
    """Admission's chunked rule hands its state to decode's kernel: 70
    tokens chunked and then 9 single steps are the recurrence over 79."""
    q, k, v, g, beta, state = _inputs(2, 79, 2, 4, 16, 8, seed=3)
    want_o, want_s = gated_delta_reference(q, k, v, g, beta, state)
    head = lambda x: x[:, :70]  # noqa: E731
    _, got = gated_delta_chunk(head(q), head(k), head(v), head(g),
                               head(beta), initial_state=state)
    for t in range(70, 79):
        o, got = gated_delta_step(got, q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t])
        assert float(jnp.abs(o - want_o[:, t]).max()) < TOL
    assert float(jnp.abs(got - want_s).max()) < TOL


def test_bfloat16_inputs_fail_the_tolerance():
    q, k, v, g, beta, state = _inputs(2, 100, 2, 4, 16, 8)
    want_o, _ = gated_delta_reference(q, k, v, g, beta, state)
    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    got_o, _ = gated_delta_chunk(low(q), low(k), low(v), g, beta,
                                 initial_state=state)
    assert float(jnp.abs(got_o - want_o).max()) > 50 * TOL


def test_the_kernel_refuses_what_it_cannot_hold():
    q, k, v, g, beta, state = _inputs(2, 1, 2, 4, 16, 8)
    with pytest.raises(ValueError, match="float32"):
        gated_delta_step(state.astype(jnp.bfloat16), q[:, 0], k[:, 0],
                         v[:, 0], g[:, 0], beta[:, 0])
    with pytest.raises(ValueError, match="does not go with"):
        gated_delta_step(state, q[:, 0], k[:, 0], v[:, 0, :3], g[:, 0],
                         beta[:, 0])
