"""Tensor-parallel paged serving: one logical engine over a ``tp`` mesh.

The single-chip :class:`~apex_tpu.serving.scheduler.PagedDecodeEngine`
owns three kinds of state: the paged KV pool (big, device), the model
variables (big, device), and the block-table/free-stack/slot metadata
(small, effectively host). Megatron-style tensor parallelism
(``apex_tpu/transformer/tensor_parallel``) already shards the model's
attention heads and MLP columns over the ``model`` axis — and GQA head
groups partition the SAME way, so the paged pool shards along its
kv-head axis with zero change to the paging logic:

- **K/V pool**: global ``(num_pages, num_kv_heads // pack, page_size,
  d * pack)`` (``pack`` heads side by side in a 128-lane row where it
  divides ONE chip's heads: ``kv_pool.heads_per_row``),
  sharded ``P(None, tp)`` — each chip holds ``num_kv_heads/tp`` heads of
  EVERY page, i.e. ``1/tp`` of the pool bytes. A model whose pool misses
  one chip's 16 GiB fits the mesh (the acceptance case in ``tpu_aot.py``
  compiles an 18 GiB-unsharded pool for ``v5e:2x4``).
- **Block tables / free stack / lengths / refcounts**: replicated. The
  host admission/retirement/preemption logic is chip-count-blind — the
  frontend, policy, prefix cache, and scenario stack compose untouched
  (they only see the engine interface).
- **Programs**: every engine program — admit, shared-prefix admit, the
  ``sync_every``-step decode scan, and the pool-maintenance ops — goes
  through the base engine's ``_compile`` seam, which this subclass
  wraps in ``shard_map`` over the mesh with per-role PartitionSpecs.
  Inside, the models' existing TP layers emit the Megatron collectives
  (QKV/MLP column-parallel → local heads, row-parallel all-reduce), the
  Pallas paged-attention kernel iterates its ``(kv_head, page)`` grid
  over the LOCAL head group, and greedy/sampled token selection gathers
  the vocab-parallel logits so every chip picks the identical token —
  no collective sampling step, and the replicated small state advances
  identically everywhere.

``tp=1`` reduces to the single-chip engine token-identically (psum /
all-gather over a size-1 axis are identity); TP=2 greedy decode is
pinned token-identical to the single-chip engine on the forced
8-CPU-device mesh in ``tests/test_tp_serving.py``.

Quantized-weight trees (``docs/serving.md`` "Quantized weight
streaming") shard through this module UNCHANGED: int8/fp8 leaves slice
along the same output/input channel axes as their fp counterparts, each
scale follows its weight's output-channel axis (replicated where the
weight is row-parallel), and int4's group-local nibble packing makes a
contiguous slice of whole groups along the packed axis exactly the
packed form of that shard — so ``infer_variable_specs`` /
``shard_model_variables`` need no quantization cases, and TP=2 over the
int8 tree is pinned token-identical to the single-chip int8 engine
(``tests/test_quantized_weights.py``).

Construction::

    cfg    = gpt2_small_config(tensor_parallel_size=2)
    model  = GPTModel(cfg)
    mesh   = tp_mesh(2)
    # shard a tp=1 checkpoint's full weights over the mesh
    v_tp, _ = shard_model_variables(model, v_full, mesh)
    engine = TensorParallelPagedEngine(model, v_tp, mesh=mesh,
                                       num_slots=..., page_size=16)
    outs, stats = engine.run(requests)      # or drive a ServingFrontend

An ``AbstractMesh`` (or ``abstract=True`` with a real/topology mesh)
builds a TRACE-ONLY engine — no buffers, ``ShapeDtypeStruct`` cache —
which is how the IR lint harness registers the TP programs devicelessly
and how ``tpu_aot.py`` AOT-compiles them for the v5e topology.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.mesh import MODEL_AXIS
from apex_tpu.serving import kv_pool
from apex_tpu.serving.scheduler import PagedDecodeEngine

__all__ = ["TensorParallelPagedEngine", "tp_mesh", "abstract_tp_mesh",
           "infer_variable_specs", "shard_model_variables"]

#: fused-projection params whose leading dim concatenates N logical
#: matrices (GPT's qkv, Llama's kv_proj / gate_up_proj). Megatron layout
#: gives each rank ITS heads' slice of EVERY chunk, so sharding a tp=1
#: checkpoint must interleave per-chunk blocks rank-major first — a
#: contiguous row split would hand rank 0 all of q and none of v.
FUSED_PARAM_CHUNKS = {"qkv": 3, "kv_proj": 2, "gate_up_proj": 2}


def tp_mesh(tp: int, devices=None, axis_name: str = MODEL_AXIS) -> Mesh:
    """A serving mesh: the first ``tp`` devices on one ``axis_name``
    axis (TP peers want adjacent devices — shortest ICI hops for the
    per-layer all-reduces, the same ordering argument as
    ``apex_tpu.mesh.build_mesh``)."""
    devices = list(devices) if devices is not None else jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if len(devices) < tp:
        raise RuntimeError(
            f"tensor-parallel serving needs {tp} devices, have "
            f"{len(devices)} (on CPU: XLA_FLAGS="
            "--xla_force_host_platform_device_count=8)")
    return Mesh(np.asarray(devices[:tp]), (axis_name,))


def abstract_tp_mesh(tp: int, axis_name: str = MODEL_AXIS):
    """A deviceless ``AbstractMesh`` for trace-only TP engines (the IR
    lint harness traces the shard_map programs on any host, with any
    device count — no real mesh required)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh((tp,), (axis_name,))


# --------------------------------------------------------------------------
# variable sharding
# --------------------------------------------------------------------------

def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _abs_init(model):
    """Abstract variable tree of ``model`` (shapes only; the flax init
    clamp path is allowed outside shard_map, so TP configs eval_shape
    fine)."""
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


def _split_axis(name: str, full, local, tp: int) -> int:
    """The one axis along which ``full`` (tp=1 shape) shards into
    ``local`` (per-rank shape): ``full[ax] == tp * local[ax]`` with
    every other dim equal."""
    candidates = [ax for ax in range(full.ndim)
                  if full.shape[ax] == tp * local.shape[ax]
                  and all(full.shape[i] == local.shape[i]
                          for i in range(full.ndim) if i != ax)]
    if len(candidates) != 1:
        raise ValueError(
            f"cannot infer the shard axis of {name!r}: tp=1 shape "
            f"{full.shape} vs tp={tp} shard {local.shape}")
    return candidates[0]


def infer_variable_specs(model, axis_name: str = MODEL_AXIS
                         ) -> Tuple[object, object]:
    """``(abs_full, specs)`` for a TP model's variables: the tp=1 twin's
    full (GLOBAL) shapes as a ``ShapeDtypeStruct`` tree, and the
    PartitionSpec per leaf — ``P(..., axis_name, ...)`` at the dim the
    TP layer shards (column/row/vocab split, inferred by which dim
    shrank between the tp=1 and tp=``n`` shard shapes), ``P()`` for
    replicated leaves (norms, biases, position table). The specs are
    both the ``shard_map`` in-spec for the ``variables`` argument of
    every engine program and the NamedSharding layout
    :func:`shard_model_variables` installs."""
    cfg = model.config
    tp = cfg.tensor_parallel_size
    abs_local = _abs_init(model)
    if tp == 1:
        return abs_local, jax.tree.map(lambda _: P(), abs_local)
    model1 = type(model)(dataclasses.replace(cfg, tensor_parallel_size=1))
    abs_full = _abs_init(model1)

    def spec_of(path, full, local):
        if full.shape == local.shape:
            return P()
        ax = _split_axis(_path_name(path), full, local, tp)
        return P(*(axis_name if i == ax else None
                   for i in range(full.ndim)))

    specs = jax.tree_util.tree_map_with_path(spec_of, abs_full, abs_local)
    return abs_full, specs


def _interleave_fused(leaf, ax: int, tp: int, chunks: int):
    """Reorder a fused ``chunks``-way projection so a contiguous 1/tp
    block along ``ax`` is one rank's Megatron shard (its slice of every
    chunk): ``[q | k | v]`` -> ``[q0 k0 v0 | q1 k1 v1 | ...]``.
    HOST-side numpy on purpose — see :func:`shard_model_variables`."""
    leaf = np.moveaxis(leaf, ax, 0)
    n = leaf.shape[0]
    per = n // (chunks * tp)
    rest = leaf.shape[1:]
    leaf = leaf.reshape(chunks, tp, per, *rest)
    leaf = np.swapaxes(leaf, 0, 1).reshape((n,) + tuple(rest))
    return np.moveaxis(leaf, 0, ax)


def shard_model_variables(model, variables, mesh,
                          axis_name: str = MODEL_AXIS):
    """Shard a tp=1 checkpoint's FULL variable tree over ``mesh`` for
    ``model`` (whose config carries ``tensor_parallel_size`` = the
    mesh's ``axis_name`` size). Returns ``(variables, specs)`` where
    every sharded leaf is a global array laid out so each rank's shard
    is exactly what the TP layers expect — fused projections
    (:data:`FUSED_PARAM_CHUNKS`) are interleaved per-chunk first — and
    replicated leaves live on every device. The sharded engine given
    these weights computes the SAME function as the tp=1 engine given
    ``variables`` (token-identical greedy decode,
    ``tests/test_tp_serving.py``)."""
    cfg = model.config
    tp = cfg.tensor_parallel_size
    abs_full, specs = infer_variable_specs(model, axis_name=axis_name)

    def put(path, leaf, ref, spec):
        # stage through HOST numpy: device_put from a host array lands
        # each chip's 1/tp slice directly, whereas a jnp view would
        # first materialize the FULL leaf on the default device — the
        # same OOM class init_paged_cache avoids for the pool
        leaf = np.asarray(leaf)
        if tuple(leaf.shape) != tuple(ref.shape):
            raise ValueError(
                f"variable {_path_name(path)!r} has shape {leaf.shape}; "
                f"expected the tp=1 FULL shape {ref.shape} (pass the "
                "unsharded checkpoint — this helper does the slicing)")
        sharded = any(s == axis_name for s in spec)
        if sharded:
            name = _path_name(path)
            chunks = next((c for key, c in FUSED_PARAM_CHUNKS.items()
                           if key in name), 1)
            if chunks > 1:
                ax = next(i for i, s in enumerate(spec) if s == axis_name)
                leaf = _interleave_fused(leaf, ax, tp, chunks)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    out = jax.tree_util.tree_map_with_path(put, variables, abs_full, specs)
    return out, specs


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class TensorParallelPagedEngine(PagedDecodeEngine):
    """One logical continuous-batching paged engine over a ``tp`` mesh.

    Drop-in for :class:`PagedDecodeEngine` — ``run()``, the
    ``ServingFrontend``, preemption, the prefix cache, sliding-window
    paging, and the scenario stack all compose unchanged (they drive the
    same compiled-program seams; the sharding lives entirely below
    them). ``model.config.tensor_parallel_size`` must equal the mesh's
    ``axis_name`` axis size, and ``variables`` must already be sharded
    (:func:`shard_model_variables`).

    ``abstract=True`` (implied by an ``AbstractMesh``) builds the
    trace-only form: no device buffers, ``ShapeDtypeStruct`` cache,
    ``variables=None`` — for the IR lint harness and the deviceless
    AOT tier. Such an engine cannot ``run()``.
    """

    def __init__(self, model, variables, *, mesh=None,
                 abstract: bool = False, **kwargs):
        cfg = model.config
        tp = cfg.tensor_parallel_size
        axis = kwargs.get("axis_name", MODEL_AXIS)
        self.mesh = mesh if mesh is not None else tp_mesh(tp,
                                                          axis_name=axis)
        mesh_tp = dict(self.mesh.shape).get(axis)
        if mesh_tp != tp:
            raise ValueError(
                f"config.tensor_parallel_size={tp} but the mesh's "
                f"{axis!r} axis has size {mesh_tp} — the model's shard "
                "shapes and the engine's head sharding would disagree")
        self.tp_world = tp
        self.abstract = bool(abstract) or not isinstance(self.mesh, Mesh)
        # quantized pools add per-(page, kv_head) scale arrays, which
        # shard P(None, axis) with the pages they scale — per-chip pool
        # bytes stay 1/tp of the (already ~2x smaller) global pool
        self._cache_specs = kv_pool.cache_specs(
            cfg, axis_name=axis, kv_dtype=kwargs.get("kv_dtype"))
        # tiered pool (docs/serving.md "Tiered KV pool"): gather/promote
        # tile batches shard along the kv-head axis with the pages they
        # were cut from — each chip demotes/promotes its own head-shard,
        # and the host tier holds every page at FULL head width
        self._tile_specs = kv_pool.tile_specs(
            cfg, axis_name=axis, kv_dtype=kwargs.get("kv_dtype"))
        _, self._var_specs = infer_variable_specs(model, axis_name=axis)
        # speculative decode: the draft pool and draft variables shard
        # over the SAME mesh (the draft model's own head/column layout),
        # so the s>1 verify and the draft loop run under one shard_map
        draft = kwargs.get("draft_model")
        self._draft_cache_specs = self._draft_var_specs = None
        if draft is not None:
            if draft.config.tensor_parallel_size != tp:
                raise ValueError(
                    f"draft model has tensor_parallel_size="
                    f"{draft.config.tensor_parallel_size}, target has "
                    f"{tp} — both must shard over the same mesh")
            self._draft_cache_specs = kv_pool.cache_specs(
                draft.config, axis_name=axis,
                kv_dtype=kwargs.get("kv_dtype"))
            _, self._draft_var_specs = infer_variable_specs(
                draft, axis_name=axis)
        super().__init__(model, variables, **kwargs)

    # --- the two seams the base engine exposes -----------------------------

    def _make_cache(self, num_slots, num_pages, page_size,
                    max_pages_per_seq, config=None):
        return kv_pool.init_paged_cache(
            config if config is not None else self.cfg, num_slots,
            num_pages=num_pages, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq, mesh=self.mesh,
            axis_name=self.axis_name, abstract=self.abstract,
            kv_dtype=self.kv_dtype)

    def _compile(self, fn, in_roles, out_roles, donate=()):
        """shard_map ``fn`` over the mesh: the cache argument/result
        takes the head-sharded pool specs, the variables the inferred
        Megatron layout, everything else replicates. Outputs declared
        replicated really are — block-table/free-stack arithmetic is
        deterministic and runs on identical inputs everywhere, and
        token selection gathers the vocab-parallel logits before the
        argmax/categorical draw (``models/generation.py``) — so
        ``check_vma=False`` (the repo-wide setting; interpreted Pallas
        kernels cannot run under the vma checker) asserts nothing
        false."""
        spec_of = {"cache": self._cache_specs, "vars": self._var_specs,
                   "draft_cache": self._draft_cache_specs,
                   "draft_vars": self._draft_var_specs,
                   "tiles": self._tile_specs, "rep": P()}
        in_specs = tuple(spec_of[r] for r in in_roles)
        out_specs = tuple(spec_of[r] for r in out_roles)
        if len(out_specs) == 1:
            out_specs = out_specs[0]
        body = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
        return jax.jit(body, donate_argnums=donate)
