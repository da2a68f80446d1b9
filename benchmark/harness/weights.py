"""Weights and token ids from ``--seed``, made by the benchmark itself.

The program and the plain reference each call :func:`make_weights` with
their own table of ``name -> (shape, dtype)``; a leaf's values depend only
on the seed and its name, so the two sides agree without either taking
anything the other made.  One jitted call makes the whole tree on the
device, in the dtype it is served in.

Values: matrices and embeddings N(0, 0.02) (BERT's and GPT-2's published
``initializer_range``); norm scales 1 + N(0, 0.02); biases N(0, 0.02) and
not the published zeros, so that a dropped bias shows in the comparison.
"""

from __future__ import annotations

import zlib
from typing import Dict, Tuple

import numpy as np

STD = 0.02


def seed_words(seed: int, n: int = 2) -> Tuple[int, ...]:
    """``n`` 31-bit words from any whole-number seed (the driver's seeds do
    not fit 32 signed bits)."""
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return tuple(int(w) & 0x7FFFFFFF for w in state)


def host_rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of one seed."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, zlib.crc32(stream.encode())])


def leaf_kind(name: str, ndim: int) -> str:
    if ndim >= 2:
        return "matrix"
    if "norm" in name.lower() and name.endswith("weight"):
        return "scale"
    return "bias"


def make_weights(table: Dict[str, tuple], seed: int):
    """``{name: array}`` for ``table = {name: (shape, dtype)}``."""
    import jax
    import jax.numpy as jnp

    names = sorted(table)

    def build(key):
        out = {}
        for name in names:
            shape, dtype = table[name]
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            x = STD * jax.random.normal(k, shape, jnp.float32)
            if leaf_kind(name, len(shape)) == "scale":
                x = 1.0 + x
            out[name] = x.astype(dtype)
        return out

    return jax.jit(build)(jax.random.PRNGKey(seed_words(seed, 1)[0]))


def table_named(tree) -> Dict[str, object]:
    """``name -> leaf`` of a pytree, named by '/'-joined keys."""
    import jax

    return {leaf_name(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def table_of(tree) -> Dict[str, tuple]:
    """``name -> (shape, dtype)`` of a program's parameter pytree (arrays or
    ``ShapeDtypeStruct``)."""
    return {name: (tuple(x.shape), x.dtype)
            for name, x in table_named(tree).items()}


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def as_tree(like, flat: Dict[str, object]):
    """``flat`` arranged as the pytree ``like``."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda p, _: flat[leaf_name(p)], like)


def make_like(like, seed: int):
    """Weights from the seed in the shapes, dtypes and arrangement of the
    program's parameter pytree ``like``."""
    return as_tree(like, make_weights(table_of(like), seed))

