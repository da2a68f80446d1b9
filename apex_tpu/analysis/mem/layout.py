"""TPU tiled-layout padding math and the chip's HBM size, for the mem
tier.

On-chip arrays are stored in (sublane, lane) tiles: the minor dimension
pads to a multiple of 128 lanes, the second-minor to a multiple of the
dtype's sublane count — 8 rows of 4-byte elements, 16 of 2-byte, 32 of
1-byte (narrower dtypes pack more rows per physical sublane, so the
minimum tile covers more of them). Every dimension above the second-
minor is untiled and costs its logical extent.

The practical consequence this module exists to price (docs/
tp_serving.md "Pool sizing"): a ``head_dim=64`` KV pool pays 2x its
logical bytes on chip — 64 lanes pad to 128 — which is how PR 10's
first 512-slot acceptance pool OOM'd a 16 GiB chip at 25.6 GiB
"logical" 12.8. Bandwidth and roofline math follow the LOGICAL bytes a
program streams; this helper answers the other question — the bytes the
array OCCUPIES — which is the one HBM/VMEM fit proofs need.

Stdlib-only on purpose: callers hand in plain shapes + an object with
``itemsize`` (a numpy/jax dtype) or an aval.
"""

from __future__ import annotations

from typing import Sequence, Tuple

#: HBM of one TPU v5e chip, the chip every fit proof here is made for
#: (Google Cloud documentation, "TPU v5e": 16 GB)
HBM_BYTES_V5E = 16 * 1024 ** 3

LANE = 128          #: minor-dim tile width (all dtypes)
_SUBLANE_4B = 8     #: second-minor tile height for 4-byte elements


def _itemsize(dtype) -> int:
    size = getattr(dtype, "itemsize", None)
    if size is None:
        # extended dtypes (PRNG keys) carry no itemsize; 4 B/elem is a
        # stand-in for what is metadata-sized
        return 4
    return max(int(size), 1)


def sublane_multiple(dtype) -> int:
    """Second-minor tile height for ``dtype``: 8 (f32/i32), 16 (bf16),
    32 (int8/fp8/bool). 8-byte dtypes still tile at 8 rows."""
    return _SUBLANE_4B * max(4 // _itemsize(dtype), 1)


def _round_up(n: int, multiple: int) -> int:
    return -(-int(n) // multiple) * multiple


def padded_shape(shape: Sequence[int], dtype) -> Tuple[int, ...]:
    """``shape`` with the minor dim padded to 128 and the second-minor
    to the dtype's sublane multiple. Rank 0/1 arrays only pad the minor
    dim (they occupy a single sublane row; modeling the full 8-row tile
    would call every small 1-D table an 8x blowup, which is noise at the
    sizes such arrays actually have)."""
    dims = [int(d) for d in shape]
    if not dims:
        return ()
    dims[-1] = _round_up(dims[-1], LANE)
    if len(dims) >= 2:
        dims[-2] = _round_up(dims[-2], sublane_multiple(dtype))
    return tuple(dims)


def tiled_padded_bytes(shape: Sequence[int], dtype) -> int:
    """Physical HBM/VMEM bytes of one array in TPU tiled layout."""
    n = 1
    for d in padded_shape(shape, dtype):
        n *= d
    return n * _itemsize(dtype)


def logical_bytes(shape: Sequence[int], dtype) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n * _itemsize(dtype)


def aval_padded_bytes(aval) -> int:
    """``tiled_padded_bytes`` over an aval / ShapeDtypeStruct; objects
    without shape+dtype (tokens, opaque effects) cost 0."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return 0
    return tiled_padded_bytes(getattr(aval, "shape", ()), dt)


def aval_logical_bytes(aval) -> int:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return 0
    return logical_bytes(getattr(aval, "shape", ()), dt)
