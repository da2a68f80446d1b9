"""Flattened parameter buffers — the TPU analog of apex's multi-tensor apply.

Reference: csrc/multi_tensor_apply.cuh (~130 lines) dispatches one CUDA kernel
over a chunked list-of-tensor-pointers so a whole optimizer step is a handful
of launches (capped by depth_to_max_tensors ~30-110 per launch). On TPU the
same amortization is achieved differently: every tensor in a pytree is padded
to a lane-aligned length and concatenated once into a single fp32 buffer
viewed as ``(rows, LANE)``; optimizer kernels then run ONE Pallas launch over
row tiles. Per-tensor reductions (LAMB trust ratios, NovoGrad per-layer norms)
use a row->segment map: each 1024-element row belongs to exactly one tensor,
so per-segment partial sums become a small one-hot matmul on the MXU.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANE = 1024  # elements per row: 8 sublanes x 128 lanes (fp32 min tile)


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Static layout of a flattened pytree."""

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]          # unpadded element counts
    row_offsets: Tuple[int, ...]    # starting row of each tensor
    row_counts: Tuple[int, ...]     # rows occupied by each tensor
    total_rows: int

    @property
    def num_tensors(self) -> int:
        return len(self.shapes)

    @property
    def total_elements(self) -> int:
        return self.total_rows * LANE

    def segment_rows(self) -> np.ndarray:
        """int32 (total_rows,) mapping each row to its tensor index."""
        seg = np.zeros(self.total_rows, np.int32)
        for i, (off, cnt) in enumerate(zip(self.row_offsets, self.row_counts)):
            seg[off : off + cnt] = i
        return seg


def build_spec(tree) -> FlatSpec:
    leaves, treedef = jax.tree.flatten(tree)
    shapes, dtypes, sizes, row_offsets, row_counts = [], [], [], [], []
    row = 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape)) if leaf.ndim else 1
        rows = max(1, -(-n // LANE))
        shapes.append(tuple(leaf.shape))
        dtypes.append(leaf.dtype)
        sizes.append(n)
        row_offsets.append(row)
        row_counts.append(rows)
        row += rows
    return FlatSpec(
        treedef=treedef,
        shapes=tuple(shapes),
        dtypes=tuple(dtypes),
        sizes=tuple(sizes),
        row_offsets=tuple(row_offsets),
        row_counts=tuple(row_counts),
        total_rows=row,
    )


def flatten(tree, spec: FlatSpec, dtype=jnp.float32) -> jax.Array:
    """Concatenate a pytree into one padded ``(total_rows, LANE)`` buffer.

    Built as a concat of per-leaf ``(rows_i, LANE)`` blocks along axis 0 —
    never as one giant 1D array. A full-buffer 1D<->2D reshape is NOT a
    bitcast under TPU tiled layouts, and with an odd ``total_rows`` the
    backend lowers it through a relayout whose intermediate allocates
    ~64x the buffer (observed on-chip: an f32[N/2, 2] relayout tile-padded
    2->128 lanes = 86 GB for BERT-Large, round 3). Row-space
    concat keeps every reshape leaf-local.
    """
    leaves = jax.tree.leaves(tree)
    parts: List[jax.Array] = []
    for leaf, n, rows in zip(leaves, spec.sizes, spec.row_counts):
        v = leaf.reshape(-1).astype(dtype)
        pad = rows * LANE - n
        if pad:
            v = jnp.concatenate([v, jnp.zeros((pad,), dtype)])
        parts.append(v.reshape(rows, LANE))
    return jnp.concatenate(parts, axis=0)


def unflatten(flat: jax.Array, spec: FlatSpec, dtypes: Sequence[Any] | None = None):
    """Slice a ``(total_rows, LANE)`` buffer back into the original pytree.

    Row-sliced per leaf (2D static slices) so the only 1D reshapes are
    leaf-sized — see ``flatten`` for why a full-buffer 1D view is
    catastrophic under TPU tiled layouts.
    """
    leaves = []
    for shape, dt, n, off, cnt in zip(
        spec.shapes,
        dtypes if dtypes is not None else spec.dtypes,
        spec.sizes,
        spec.row_offsets,
        spec.row_counts,
    ):
        chunk = flat[off:off + cnt].reshape(cnt * LANE)
        leaves.append(chunk[:n].reshape(shape).astype(dt))
    return jax.tree.unflatten(spec.treedef, leaves)
