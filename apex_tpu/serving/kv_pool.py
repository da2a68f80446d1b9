"""Paged KV pool: static page-granular cache storage + block tables.

Layout (the PAGED cache pytree — a drop-in ``cache=`` argument for the
models' incremental-decode path, recognized by its ``block_tables`` key):

    pcache = {
      "layers": [{"k_pages": (num_pages, kv_local // pack, page_size,
                              d * pack),
                  "v_pages": ...,
                  # quantized pools only (init_paged_cache(kv_dtype=),
                  # pack == 1):
                  "k_scales": (num_pages, kv_local) f32, "v_scales": ...}]
                * num_layers,
      "block_tables": (num_slots, max_pages_per_seq) int32,
      "len":          (num_slots,) int32   # tokens written per slot
      "alloc_pages":  (num_slots,) int32,  # pages OWNED per slot
      "shared_pages": (num_slots,) int32,  # leading SHARED (cached) entries
      "page_ref":     (num_pages,) int32,  # active readers per shared page
      "free_stack":   (num_pages,) int32,  # stack[0:free_top] = free pages
      "free_top":     () int32,
    }

THE CACHE-LAYOUT SEAM. What a layer stores per token, and how wide, is
stated ONCE: :func:`layout_of` reads it from the model's config
(:class:`CacheLayout`) and everything that shapes, sizes or walks a page —
:func:`init_paged_cache`, :func:`page_bytes`, :func:`prefill_into_pages`,
``models/generation.update_paged_layer_cache`` and ``init_cache``, the
shared-admit gather, the host tier's tiles, the frontend's per-token bytes
— goes through it. Two layouts exist: per-head K and V (above: tensors
``k``/``v``, ``num_kv_heads`` heads of ``head_dim``), and ONE LATENT ENTRY
per token (a config that states ``kv_latent_width``, MLA's normalized
``c_kv`` beside the rotated ``k_rope``: one tensor ``latent``, one shared
"head", its rows lane-padded in the pool to a multiple of 128 — 576 values
stored 640 wide — so that ``ops.paged_latent_attention`` contracts whole
lane tiles; the padding is zeros and is never counted as a token's bytes).
A layer dict then holds ``{"latent_pages": (num_pages, 1, page_size,
stored)}`` and every pool op below, which moves page NAMES and walks
``for key in layer``, runs unchanged. A latent pool has one head, so it
cannot shard over a tensor-parallel mesh, and it has no quantized form:
both refuse with :class:`LatentPoolUnsupported` where the engine is built.

GROUPS OF LAYERS. Layers that store the same thing and read the same span
of it (equal :class:`CacheLayout` and window: :func:`layer_groups`) form a
GROUP, and a group has its own pool tensors, block table and page count. A
model whose layers are all alike — every model but one with
``layer_windows`` — is ONE group: the pytree above, the free stack and,
where the one group has a window, the drop-behind-the-band protocol
(``drop_slot_pages``), all as they were. A model that mixes windowed and
full layers (``models/mellum.py``: three ``sliding_attention`` layers to
one ``full_attention``) has one FULL group, which is everything above
(``block_tables`` ... ``free_top`` are its state, ``num_pages`` its pages,
a request's worst case allocated at admission), and one RING group per
window, which owns no entry of that state at all:

* a ring layer's pools hold ``1 + num_slots * R`` pages: the null page
  (every writer's sink, as in the full group) and ``R =
  ring_pages(window, page_size) = ceil(window / page_size) + 1`` pages a
  slot, slot ``b``'s at ``1 + b*R ..``, for the engine's lifetime. The
  ``+ 1`` is the page rounding: a band of ``window`` positions that does
  not start on a page boundary straddles one page more than it fills.
  ``sync_every`` adds nothing to it: the ring is overwritten in place by
  the decode steps themselves, inside the chunk's scan, and no host
  action stands between two steps (a drop-behind pool would have to hold
  the ``sync_every`` tokens of a chunk on top);
* logical page ``p`` of a slot lives in ring page ``p mod R``. The table
  is arithmetic on the slot's length (:func:`ring_view`), not storage:
  the view a layer writes and reads through starts at the band's first
  live page ``f`` (entry ``i`` is ring page ``(f + i) mod R``) with the
  length counted from ``f * page_size``, so ``ops.paged_write`` and
  ``ops.paged_attention(window=)`` are called as they are — the causal
  mask and the band are differences of positions — and the table the
  decode kernel keeps in scalar memory is ``num_slots x R``, not
  ``num_slots x max_pages``;
* nothing is allocated at admission and nothing released at retirement:
  an admission writes the last ``R`` pages' worth of its prompt
  (:func:`prefill_into_pages`), a retired slot's ring is simply written
  over by the next request. A slot never owns more than ``R`` pages of a
  ring group, at any moment.

Whatever shares or hands on pages — the radix prefix cache, the host tier,
speculation's rollback, chunked prefill — cannot do so with a page that is
overwritten ``R`` pages later, so the engine refuses each with a ring (or
a windowed) group, by name; so do quantized pages and a tensor-parallel
mesh (``ROADMAP.md``).

A THIRD KIND OF GROUP STORES NO TOKEN AT ALL: a STATE group
(``models/qwen3_next.py``: three ``linear_attention`` layers to one
``full_attention``; ``models/lfm2_moe.py``: three gated ``conv`` layers to
one). A config states ``layer_states`` (one entry a layer: ``None``, or the
tensors the layer keeps per SEQUENCE, ``models.generation.StateTensor``
each: a name, a shape and a dtype), and such a layer's dict holds exactly
those tensors, ``(num_slots,) + shape`` (a gated delta-rule layer: its
float32 recurrent state and the last inputs of its short convolution; a
gated conv layer: its two-token window alone), whatever the sequences'
lengths. A state group is sized by ``num_slots`` alone (:func:`state_bytes`,
beside :func:`page_bytes`), and is no part of the free stack, the block
table, :func:`alloc_slot` / :func:`release_slot`, :func:`defrag_map` or
:func:`gather_pages`: slot ``b``'s row is the state of whatever request
holds slot ``b``. An admission OVERWRITES the row whole with the state its
prompt ends in (:func:`prefill_into_pages`), so retirement need not clear
it; a decode step updates it in place (``ops.gated_delta_step`` aliases it,
and the chunk's scan carries every state row without a copy). What a state
cannot do: be shared by prefix (the state at a prefix's end is not kept),
be rolled back past a rejected draft block, be handed from chunk to chunk of a
chunked prefill through the paged ``s > 1`` path, go to the host tier,
shard over a tensor-parallel mesh, or stand beside quantized pages: the
engine refuses each by name (:class:`StateGroupUnsupported`;
``ROADMAP.md``). The layers beside a state group that store tokens store
them in the model-wide layout, per-head pages or LATENT pages alike
(``models/bailing_hybrid.py``: Kimi delta attention layers beside one MLA
layer's latent entries).

A POOL ROW IS 128 LANES WHERE IT CAN BE. A per-head pool whose head width
divides 128 holds ``pack = 128 // width`` heads side by side in one row
(:func:`heads_per_row`, the one place ``pack`` is decided: from the
width, the LOCAL kv-head count and whether the pool is quantized, and
from nothing else; two 64-wide heads a row at GPT-2 large). Row ``j``
holds heads ``j*pack .. j*pack + pack - 1``, head ``j*pack + p`` in lanes
``[p*width, (p+1)*width)``. Why: a tensor whose minor dimension is 128
lanes lies ROW-MAJOR by the device's default, which is how every Mosaic
call takes it, so no program re-lays the pool where it begins or ends;
a 64-wide one lies page-axis-minor-most, and every program copied every
layer's K and V pool twice (docs/serving.md "Page-pool layout"). The
models and ``update_paged_layer_cache`` still speak per HEAD, ``(b,
heads, s, d)``: ``ops.paged_write`` and ``ops.paged_attention`` read
``pack`` off the shapes they are handed, and the one other reader of a
page's inside (the shared-admit gather) unpacks through
``ops.paged_write.unpack_heads``. A quantized pool keeps one head a row
(its scales are per ``(page, kv_head)`` and its writers are scatters of
their own), as does a head count ``pack`` does not divide.

``alloc_pages`` tracks ownership, not occupancy: the scheduler allocates a
request's worst case (``ceil((prompt+max_new)/page_size)``) up front, so a
slot owns pages its length has not reached yet — free/defrag must treat
those as live (freeing by ``ceil(len/page_size)`` would leak the tail).
It bounds the slot's row RANGE, not a live-page count: a sliding-window
slot's leading entries may be NULLED mid-flight (``drop_slot_pages`` —
pages below the attention band return to the stack early) and
``release_slot`` skips null entries inside the range.

Prefix caching (``serving/prefix_cache.py``) adds page SHARING on top of
ownership: a slot's block-table row is ``[shared cached pages | owned
private pages | null...]``. The first ``shared_pages[slot]`` entries are
owned by the radix prefix cache and only READ by the slot (pages are
position-indexed, and the decode step never writes below a slot's length,
so read-only sharing is safe); ``page_ref`` counts, per page, how many
active slots currently share it — the eviction guard: a cached page may
return to the free stack only at refcount 0. Shared entries are installed
by ``alloc_slot_shared`` (refcount +1) and released by ``free_slot`` /
``release_slot`` (refcount -1, page NOT pushed to the free stack — the
cache still holds it).

Page 0 is the reserved NULL page: never allocated, and every dead block
table entry (idle slot, tail of a short sequence) points at it, so index
maps and masked writes always resolve to a valid page — static shapes,
no bounds branches. It is a SINK, not untouched storage: idle/done slots
write their fill tokens' K/V there and attend over it (outputs masked or
discarded) — no LIVE sequence ever reads it, but its contents are
arbitrary finite garbage, so never repurpose it as zeroed or poisonable
storage. The free list is a fixed-size int32 stack; alloc pops
``n`` pages off the top with a masked gather, free pushes them back with
a masked ``mode="drop"`` scatter — both jittable at one shape forever
(the ``n`` is a traced scalar, the mask is what varies).

The lane-alignment discipline mirrors ``ops/flat_buffer.py``: a page tile
is ``(page_size, head_dim * pack)``, so ``page_size`` must be a sublane
multiple (8) and should be >= 16 for bf16 pools.

Tensor parallelism (``serving/tp.py``, docs/tp_serving.md): with
``init_paged_cache(..., mesh=)`` the pool is allocated GLOBALLY at the
full ``num_kv_heads`` and sharded along the head axis over the mesh's
``tp`` axis (:func:`cache_specs`; the axis counts rows of ``pack``
heads, and ``pack`` divides the LOCAL head count, so a row never
straddles two chips) — each chip holds its
``num_kv_heads/tp`` head group of every page, while block tables / free
stack / lengths / refcounts stay replicated, so every pure-JAX pool op
in this module runs unchanged inside ``shard_map`` (none of them index
the head axis).

Quantized pools (``init_paged_cache(kv_dtype="int8"|"fp8")``,
docs/serving.md "Quantized KV pages"): pages store K/V narrow with one
symmetric f32 scale per ``(page, kv_head)`` beside the block table
(``k_scales``/``v_scales``, shape ``(num_pages, kv_local)``). The pool
ops here stay DTYPE-BLIND — they move page *names*, and a page's scale
rides with the page: alloc resets a fresh private page's scales to 0,
defrag gathers scales through the same permutation as the pages, and
shared (prefix-cached) pages keep their scales across sharers. Under TP
the scales shard along the same kv-head axis as the pages (dim 1).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.mesh import MODEL_AXIS
from apex_tpu.ops._dispatch import cdiv, round_up
from apex_tpu.ops.paged_write import paged_write
from apex_tpu.ops.quant import kv_cast, kv_qmax, resolve_kv_dtype
from apex_tpu.transformer.utils import divide
from apex_tpu.utils import metrics


class RingGroupUnsupported(ValueError):
    """``ring-group-unsupported``: a pool with a ring group (windowed
    layers beside full ones) was asked for what a page that is overwritten
    ``R`` pages later cannot give."""

    def __init__(self, what: str):
        super().__init__(
            f"ring-group-unsupported: {what} does not compose with a "
            "model that mixes windowed and full attention layers — its "
            "windowed layers hold a fixed ring of pages a slot "
            "(kv_pool.layer_groups), written over as the band moves on")


class StateGroupUnsupported(ValueError):
    """``state-group-unsupported``: a pool with a state group (layers that
    keep one state a slot and no token) was asked for what a state cannot
    give."""

    def __init__(self, what: str, why: str = ""):
        super().__init__(
            f"state-group-unsupported: {what} does not compose with a "
            "model whose recurrent layers keep one state a slot and no "
            "token (kv_pool.layer_groups)"
            + (f": {why}" if why else ""))


class LatentPoolUnsupported(ValueError):
    """``latent-pool-unsupported``: a pool of latent entries was asked to
    shard over a tensor-parallel mesh or to hold quantized pages."""

    def __init__(self, what: str):
        super().__init__(
            f"latent-pool-unsupported: {what} — a latent pool holds one "
            "entry per token for ALL heads (no kv-head axis to shard, no "
            "per-(page, kv_head) scale to quantize by); serve it on one "
            "chip per replica with kv_dtype=None")


#: lanes of one vector register row: a tensor whose minor dimension is this
#: wide lies row-major by the device's default layout
_LANES = 128


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """What ONE layer stores per token: ``tensors`` names the stored
    tensors (a contiguous prefill cache holds them under these names, the
    pool under ``<name>_pages``, quantized scales under ``<name>_scales``),
    each ``heads`` heads (all chips together) of ``width`` values, held in
    pool rows of ``stored`` lanes."""

    tensors: Tuple[str, ...]
    heads: int
    width: int
    stored: int

    @property
    def latent(self) -> bool:
        return self.tensors == ("latent",)


def layout_of(config) -> CacheLayout:
    """The one statement of the cache layout: a config with
    ``kv_latent_width`` stores one latent entry of that width per token
    (lane-padded in the pool); any other stores K and V per kv head."""
    latent = getattr(config, "kv_latent_width", None)
    if latent:
        return CacheLayout(("latent",), 1, int(latent),
                           round_up(int(latent), _LANES))
    return CacheLayout(("k", "v"),
                       getattr(config, "num_kv_heads", config.num_heads),
                       config.head_dim, config.head_dim)


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """Layers alike in what they store and how far back they read: their
    ``layout``, their ``window`` (``None``: everything), their indices,
    and whether the group is held as per-slot RINGS of pages (a windowed
    group beside others) or by the block table and the free stack (the
    full group; the one group of a model whose layers are all alike, with
    a window or without). A STATE group stores no token: ``state`` names
    the tensors its layers keep a slot (each with ``name``, ``shape``,
    ``dtype``), and it has neither layout nor window."""

    layout: Optional[CacheLayout]
    window: Optional[int]
    layers: Tuple[int, ...]
    ring: bool
    state: Tuple = ()


def layer_groups(config) -> Tuple[LayerGroup, ...]:
    """The groups of ``config``'s layers, in order of first appearance. A
    config states per-layer windows as ``layer_windows`` (one entry a
    layer, ``None`` = full); one without has ONE group, whose window is
    its model-wide ``sliding_window`` if it has that. A config states the
    layers that keep a state and no token as ``layer_states`` (one entry a
    layer, ``None`` = a layer of pages): they form STATE groups, one per
    distinct statement."""
    windows = getattr(config, "layer_windows", None)
    if windows is None:
        windows = (getattr(config, "sliding_window", None),) \
            * config.num_layers
    states = getattr(config, "layer_states", None) \
        or (None,) * config.num_layers
    for name, per_layer in (("layer_windows", windows),
                            ("layer_states", states)):
        if len(per_layer) != config.num_layers:
            raise ValueError(f"{name} has {len(per_layer)} entries for "
                             f"{config.num_layers} layers")
    layout = layout_of(config)
    by_kind = {}
    for i, (w, st) in enumerate(zip(windows, states)):
        by_kind.setdefault((None, tuple(st)) if st else (w, ()), []
                           ).append(i)
    mixed = len({w for w, st in by_kind if not st}) > 1
    return tuple(
        LayerGroup(None, None, tuple(ls), False, st) if st
        else LayerGroup(layout, w, tuple(ls), mixed and w is not None)
        for (w, st), ls in by_kind.items())


def state_layers(config) -> dict:
    """``{layer index: the tensors it keeps a slot}`` over ``config``'s
    state groups (empty for a model whose layers all store tokens)."""
    return {i: g.state for g in layer_groups(config) if g.state
            for i in g.layers}


def state_bytes(config, num_slots: int = 1, *,
                group: Optional[LayerGroup] = None) -> int:
    """Bytes the state groups (or the one ``group``) hold for ``num_slots``
    slots over all their layers, beside :func:`page_bytes`: sized by the
    slots alone, whatever the contexts' lengths (0 for a model with no such
    layer)."""
    groups = layer_groups(config) if group is None else (group,)
    return num_slots * sum(
        len(g.layers) * int(np.prod(t.shape)) * jnp.dtype(t.dtype).itemsize
        for g in groups for t in g.state)


def ring_pages(window: int, page_size: int) -> int:
    """``R``: pages of a ring group one slot holds (module docstring)."""
    return cdiv(window, page_size) + 1


def _ring_table(slot, first, count: int, ring: int):
    # ring pages of logical pages first .. first + count - 1 of ``slot``
    # (both may be vectors over slots): page 0 is the pool's null page
    i = jnp.arange(count, dtype=jnp.int32)
    return (1 + jnp.asarray(slot, jnp.int32)[..., None] * ring
            + (jnp.asarray(first, jnp.int32)[..., None] + i) % ring)


def ring_view(lengths, *, window: int, page_size: int):
    """``(block_tables (slots, R), lengths (slots,))`` through which a ring
    group's layers write and read a decode step: the table starts at the
    first page the step's band reaches and the lengths count from that
    page's first position. ``lengths``: the slots' tokens written BEFORE
    the step (``cache["len"]``); the step is one token a slot."""
    lengths = lengths.astype(jnp.int32)
    ring = ring_pages(window, page_size)
    first = jnp.maximum(lengths - window + 1, 0) // page_size
    table = _ring_table(jnp.arange(lengths.shape[0]), first, ring, ring)
    return table, lengths - first * page_size


def pool_key(name: str) -> str:
    return name + "_pages"


def scale_key(name: str) -> str:
    return name + "_scales"


def pool_tensors(layer) -> Tuple[str, ...]:
    """The layout's tensor names as a layer dict (pool or per-layer view)
    holds them, in the layout's order."""
    return tuple(k[:-len("_pages")] for k in layer if k.endswith("_pages"))


def heads_per_row(width: int, kv_local: int, *, quantized: bool = False
                  ) -> int:
    """``pack``: how many heads one pool row holds side by side. The ONE
    place it is decided, from what the pool is: ``width`` (a head's stored
    lanes), ``kv_local`` (ONE chip's kv heads: a row never straddles two
    chips) and whether the pages are quantized. ``128 // width`` where the
    width divides 128 and that count divides the heads; else 1 (a head or
    a latent entry of 128 lanes or more, a width like 96, an odd head
    count, and a quantized pool, whose scales are per ``(page,
    kv_head)``)."""
    if quantized or width >= _LANES or _LANES % width:
        return 1
    pack = _LANES // width
    return pack if kv_local % pack == 0 else 1


def _pool_shape(num_pages: int, heads: int, page_size: int, stored: int,
                pack: int):
    # the ONE place a page's shape is built: ``heads`` heads of ``stored``
    # lanes, ``pack`` of them to a row
    return (num_pages, heads // pack, page_size, stored * pack)


def a_layer_of_pages(cache) -> dict:
    """The first layer dict that holds pages (a state group's hold none)."""
    return next(lc for lc in cache["layers"] if pool_tensors(lc))


def a_pool(cache, layer: Optional[int] = None):
    """One of a layer's pools (default: the first layer's that has pages).
    A group's pools share one shape and dtype; groups differ in their page
    count alone."""
    layer = a_layer_of_pages(cache) if layer is None \
        else cache["layers"][layer]
    return layer[pool_key(pool_tensors(layer)[0])]


def heads_per_row_of(cache, config) -> int:
    """The ``pack`` a built cache holds (its pool's lanes over the
    layout's stored width): what the frontend's stats report."""
    return a_pool(cache).shape[3] // layout_of(config).stored


def page_size_of(cache) -> int:
    return a_pool(cache).shape[2]


def num_pages_of(cache) -> int:
    """Pages of the block table's group (the full group; a ring group's
    count is its pools' own)."""
    return cache["page_ref"].shape[0]


def pages_for(length, page_size: int):
    """Pages needed for ``length`` tokens (traced or static)."""
    if isinstance(length, int):
        return cdiv(length, page_size)
    return (length + page_size - 1) // page_size


def cache_specs(config, axis_name: str = MODEL_AXIS, *, kv_dtype=None):
    """PartitionSpec pytree mirroring the paged-cache structure for a
    tensor-parallel mesh (``serving/tp.py``): the per-layer K/V pools
    shard along the kv-HEAD axis (dim 1, which counts rows of ``pack``
    heads: :func:`heads_per_row` — each chip holds
    ``num_kv_heads/tp`` heads of EVERY page, so its pool shard is
    ``1/tp`` the bytes), while the block tables, free stack, lengths,
    and refcounts stay replicated (the host admission/retirement logic
    reads them and is chip-count-blind). The tree is both the
    ``shard_map`` in/out spec for every engine program and the
    ``NamedSharding`` layout of the global cache.

    ``kv_dtype``: non-None adds the quantized pool's per-layer
    ``k_scales``/``v_scales`` ``(num_pages, kv)`` entries, sharded along
    the same kv-head axis (dim 1) as the pages — per-chip scale bytes
    halve with the pool shard."""
    rep = PartitionSpec()
    layer = _layer_specs(config, axis_name, kv_dtype)
    return {
        "layers": [dict(layer) for _ in range(config.num_layers)],
        "block_tables": rep, "len": rep, "alloc_pages": rep,
        "shared_pages": rep, "page_ref": rep, "free_stack": rep,
        "free_top": rep,
    }


def _layer_specs(config, axis_name: str, kv_dtype) -> dict:
    """One layer's (or one tile batch's) specs: every stored tensor, and
    its scales where quantized, shards along the kv-head axis (dim 1)."""
    layout = layout_of(config)
    if layout.latent:
        raise LatentPoolUnsupported(
            "tensor-parallel specs were asked of a latent pool")
    if state_layers(config):
        raise StateGroupUnsupported("a tensor-parallel mesh")
    kv = PartitionSpec(None, axis_name)
    layer = {pool_key(n): kv for n in layout.tensors}
    if kv_dtype is not None:
        layer.update({scale_key(n): kv for n in layout.tensors})
    return layer


def init_paged_cache(config, num_slots: int, *, num_pages: int,
                     page_size: int = 16,
                     max_pages_per_seq: Optional[int] = None, dtype=None,
                     kv_dtype=None, mesh=None,
                     axis_name: str = MODEL_AXIS,
                     abstract: bool = False):
    """Allocate the shared page pool + empty slot state.

    ``num_pages`` includes the reserved null page 0, so the usable
    capacity is ``(num_pages - 1) * page_size`` tokens across all
    in-flight sequences. ``max_pages_per_seq`` bounds one sequence's block
    table (default: enough for ``max_position_embeddings``).

    ``mesh`` (a ``Mesh`` or ``AbstractMesh`` whose ``axis_name`` axis has
    size ``config.tensor_parallel_size``) allocates the GLOBAL
    tensor-parallel pool instead: the K/V pools hold ALL
    ``num_kv_heads`` and are sharded along the head axis per
    :func:`cache_specs` — each chip's shard is its local head group, so
    a pool that misses one chip's HBM fits the mesh — and everything
    else is replicated. ``abstract=True`` (implied by an
    ``AbstractMesh``) returns ``ShapeDtypeStruct`` leaves instead of
    materializing — the trace/AOT-compile form (a real ``Mesh`` stamps
    the NamedShardings on the structs; an ``AbstractMesh`` cannot).

    ``kv_dtype`` (``"int8"`` / ``"fp8"``, docs/serving.md "Quantized KV
    pages"): store the pages at the narrow dtype with per-``(page,
    kv_head)`` symmetric f32 scales (``k_scales``/``v_scales``) in each
    layer dict — roughly 2x the slots per pool byte at bf16 parity
    tolerance. Mutually exclusive with ``dtype`` (the page dtype IS the
    quantized dtype)."""
    if kv_dtype is not None and dtype is not None:
        raise ValueError("kv-dtype-conflict: pass dtype= OR kv_dtype=, "
                         "not both — a quantized pool's page dtype is "
                         "the quantized dtype")
    quant = resolve_kv_dtype(kv_dtype)
    if page_size % 8 != 0:
        raise ValueError(f"page_size must be a sublane multiple (8), got "
                         f"{page_size}")
    if num_pages < 2:
        raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
    layout = layout_of(config)
    if layout.latent and (quant is not None or mesh is not None
                          or config.tensor_parallel_size != 1):
        raise LatentPoolUnsupported(
            f"kv_dtype={kv_dtype!r}" if quant is not None
            else "a tensor-parallel mesh")
    groups = layer_groups(config)
    if any(g.ring for g in groups) and (
            quant is not None or mesh is not None
            or config.tensor_parallel_size != 1):
        raise RingGroupUnsupported(
            f"kv_dtype={kv_dtype!r}" if quant is not None
            else "a tensor-parallel mesh")
    states = state_layers(config)
    if states and (quant is not None or mesh is not None
                   or config.tensor_parallel_size != 1):
        raise StateGroupUnsupported(
            f"kv_dtype={kv_dtype!r}" if quant is not None
            else "a tensor-parallel mesh")
    kv_local = divide(layout.heads, config.tensor_parallel_size)
    kv_dim = kv_local
    if mesh is not None:
        tp_world = dict(mesh.shape).get(axis_name)
        if tp_world is None:
            raise ValueError(f"mesh has no {axis_name!r} axis (axes: "
                             f"{tuple(dict(mesh.shape))})")
        if tp_world != config.tensor_parallel_size:
            raise ValueError(
                f"mesh {axis_name!r} axis size {tp_world} != "
                f"config.tensor_parallel_size="
                f"{config.tensor_parallel_size} — the model's shard "
                "shapes and the pool's head sharding would disagree")
        kv_dim = kv_local * tp_world            # the GLOBAL head count
    if quant is not None:
        dt = quant[0]
    else:
        dt = dtype if dtype is not None \
            else resolve_compute_dtype(config.dtype)
    if max_pages_per_seq is None:
        max_pages_per_seq = cdiv(config.max_position_embeddings, page_size)
    shape = _pool_shape(
        num_pages, kv_dim, page_size, layout.stored,
        heads_per_row(layout.stored, kv_local, quantized=quant is not None))
    scale_shape = (num_pages, kv_dim)
    names = layout.tensors
    if mesh is not None and (abstract or not isinstance(mesh, Mesh)):
        # trace/AOT form: no buffers, just (sharded) shapes
        specs = cache_specs(config, axis_name, kv_dtype=kv_dtype)
        stamp = isinstance(mesh, Mesh)

        def sds(sh, dt_, spec):
            sharding = NamedSharding(mesh, spec) if stamp else None
            return jax.ShapeDtypeStruct(sh, dt_, sharding=sharding)

        kv_spec = specs["layers"][0][pool_key(names[0])]
        rep = PartitionSpec()

        def layer_sds():
            lc = {pool_key(n): sds(shape, dt, kv_spec) for n in names}
            if quant is not None:
                lc.update({scale_key(n): sds(scale_shape, jnp.float32,
                                             kv_spec) for n in names})
            return lc

        return {
            "layers": [layer_sds() for _ in range(config.num_layers)],
            "block_tables": sds((num_slots, max_pages_per_seq), jnp.int32,
                                rep),
            "len": sds((num_slots,), jnp.int32, rep),
            "alloc_pages": sds((num_slots,), jnp.int32, rep),
            "shared_pages": sds((num_slots,), jnp.int32, rep),
            "page_ref": sds((num_pages,), jnp.int32, rep),
            "free_stack": sds((num_pages,), jnp.int32, rep),
            "free_top": sds((), jnp.int32, rep),
        }
    # a ring group's layers hold the null page and R pages a slot, whatever
    # ``num_pages`` is: that buys pages of the block table's group alone
    ring_shape = {
        i: (1 + num_slots * ring_pages(g.window, page_size),) + shape[1:]
        for g in groups if g.ring for i in g.layers}

    def build():
        def layer_buf(i):
            if i in states:
                # a row a slot, for the engine's lifetime: no page, no
                # table entry, nothing the free stack knows of
                return {t.name: jnp.zeros((num_slots,) + tuple(t.shape),
                                          t.dtype) for t in states[i]}
            lc = {pool_key(n): jnp.zeros(ring_shape.get(i, shape), dt)
                  for n in names}
            if quant is not None:
                lc.update({scale_key(n): jnp.zeros(scale_shape, jnp.float32)
                           for n in names})
            return lc
        layers = [layer_buf(i) for i in range(config.num_layers)]
        return {
            "layers": layers,
            "block_tables": jnp.zeros((num_slots, max_pages_per_seq),
                                      jnp.int32),
            "len": jnp.zeros((num_slots,), jnp.int32),
            "alloc_pages": jnp.zeros((num_slots,), jnp.int32),
            "shared_pages": jnp.zeros((num_slots,), jnp.int32),
            "page_ref": jnp.zeros((num_pages,), jnp.int32),
            # pages 1..num_pages-1 free; popped from the top of the stack
            "free_stack": jnp.arange(1, num_pages + 1, dtype=jnp.int32
                                     ) % num_pages,
            "free_top": jnp.asarray(num_pages - 1, jnp.int32),
        }

    if mesh is None:
        return build()
    # allocate ALREADY sharded (jit with out_shardings): materializing
    # the global pool on one device first would OOM at exactly the
    # shapes TP exists for (a pool bigger than one chip's HBM)
    shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                             cache_specs(config, axis_name,
                                         kv_dtype=kv_dtype),
                             is_leaf=lambda x: isinstance(
                                 x, PartitionSpec))
    return jax.jit(build, out_shardings=shardings)()


def free_page_count(cache):
    return cache["free_top"]


def observe_pool(cache, labels: Optional[dict] = None) -> dict:
    """Publish the pool's health gauges (docs/observability.md catalog):
    ``kv_pool.free_pages``, ``kv_pool.pages_total`` (usable, i.e. minus
    the null page), ``kv_pool.shared_pages_active`` (pages with
    ``page_ref > 0`` — currently shared by live readers), and
    ``kv_pool.page_refs_total`` (sum of active refcounts); with a state
    group also ``kv_pool.state_bytes`` (what its layers hold for all
    slots). ``labels``
    distinguishes pools (the engine passes its ``engine`` label — two
    engines' pools must not clobber one gauge). HOST-side only: reads
    two small device arrays (a scalar and the per-page refcounts) — the
    scheduler calls it at its sync boundaries, never from jitted code.
    Returns the gauge values as a dict."""
    refs = np.asarray(cache["page_ref"])
    vals = {
        "kv_pool.free_pages": int(np.asarray(cache["free_top"])),
        "kv_pool.pages_total": num_pages_of(cache) - 1,
        "kv_pool.shared_pages_active": int((refs > 0).sum()),
        "kv_pool.page_refs_total": int(refs.sum()),
        # what the state groups' layers hold for all slots
        "kv_pool.state_bytes": int(sum(
            x.nbytes for lc in cache["layers"] if not pool_tensors(lc)
            for x in lc.values())),
    }
    if not vals["kv_pool.state_bytes"]:
        # a model with no such layer publishes the four gauges it had
        del vals["kv_pool.state_bytes"]
    for name, v in vals.items():
        metrics.gauge(name, labels=labels).set(v)
    return vals


def _reset_page_scales(cache, page_ids):
    """Zero the quantized-pool scales of freshly allocated PRIVATE pages
    (no-op on a full-precision pool). The requantize-on-grow append and
    the prefill scatter both trust scale 0 == "page holds nothing yet";
    a previous occupant's stale scale would silently inflate the new
    occupant's quantization grid. ``page_ids`` may contain 0 (the null
    page) for masked-out entries — page 0's scale is garbage like its
    contents and is never read by a live slot."""
    if "k_scales" not in cache["layers"][0]:
        return cache["layers"]
    zero = jnp.zeros(page_ids.shape + cache["layers"][0]["k_scales"]
                     .shape[1:], jnp.float32)
    return [dict(lc, k_scales=lc["k_scales"].at[page_ids].set(zero),
                 v_scales=lc["v_scales"].at[page_ids].set(zero))
            for lc in cache["layers"]]


def alloc_slot(cache, slot, n_pages):
    """Pop ``n_pages`` pages off the free stack and install them as slot
    ``slot``'s block table row (entries past ``n_pages`` point at the null
    page). ``slot``/``n_pages`` may be traced. The CALLER must ensure
    ``free_page_count(cache) >= n_pages`` (the scheduler's admission
    check) — the stack read clamps, so an over-alloc would silently hand
    out duplicate pages."""
    bt, stack, top = (cache["block_tables"], cache["free_stack"],
                      cache["free_top"])
    max_pages = bt.shape[1]
    num_pages = stack.shape[0]
    idx = jnp.arange(max_pages, dtype=jnp.int32)
    take = idx < n_pages
    src = jnp.clip(top - 1 - idx, 0, num_pages - 1)
    row = jnp.where(take, stack[src], 0)
    out = dict(cache)
    out["free_top"] = top - jnp.asarray(n_pages, jnp.int32)
    out["block_tables"] = bt.at[slot].set(row)
    out["alloc_pages"] = cache["alloc_pages"].at[slot].set(
        jnp.asarray(n_pages, jnp.int32))
    out["shared_pages"] = cache["shared_pages"].at[slot].set(0)
    out["layers"] = _reset_page_scales(cache, row)
    return out


def alloc_slot_shared(cache, slot, shared_row, n_shared, n_private):
    """Install slot ``slot``'s block table row as ``[shared cached pages |
    freshly popped private pages | null...]``: the first ``n_shared``
    entries come from ``shared_row`` (physical pages the prefix cache
    holds — refcount +1 each, NOT popped from the stack), the next
    ``n_private`` pop off the free stack as in ``alloc_slot``. Same caller
    contract: ``free_page_count(cache) >= n_private``."""
    bt, stack, top = (cache["block_tables"], cache["free_stack"],
                      cache["free_top"])
    max_pages = bt.shape[1]
    num_pages = stack.shape[0]
    n_shared = jnp.asarray(n_shared, jnp.int32)
    n_private = jnp.asarray(n_private, jnp.int32)
    idx = jnp.arange(max_pages, dtype=jnp.int32)
    take_priv = jnp.logical_and(idx >= n_shared, idx < n_shared + n_private)
    src = jnp.clip(top - 1 - (idx - n_shared), 0, num_pages - 1)
    row = jnp.where(idx < n_shared, shared_row,
                    jnp.where(take_priv, stack[src], 0))
    out = dict(cache)
    out["free_top"] = top - n_private
    out["block_tables"] = bt.at[slot].set(row)
    out["alloc_pages"] = cache["alloc_pages"].at[slot].set(n_private)
    out["shared_pages"] = cache["shared_pages"].at[slot].set(n_shared)
    ref_ids = jnp.where(idx < n_shared, shared_row, num_pages)  # OOB drops
    out["page_ref"] = cache["page_ref"].at[ref_ids].add(1, mode="drop")
    # only the freshly popped PRIVATE pages reset their scales — the
    # shared prefix pages keep theirs (shared pages are shared scales).
    # Gated so the fp pool's program never carries the dead page-id
    # select (the helper itself no-ops on fp pools, its argument not)
    if "k_scales" in cache["layers"][0]:
        out["layers"] = _reset_page_scales(
            cache, jnp.where(take_priv, row, 0))
    return out


def release_slot(cache, slot, keep):
    """Retire slot ``slot`` with page-level disposition: every table entry
    in the slot's ``shared + owned`` range with ``keep[j]`` False returns
    to the free stack; entries with ``keep[j]`` True leave the slot WITHOUT
    touching the stack (they are — or just became — prefix-cache property).
    The leading ``shared_pages[slot]`` entries additionally drop their
    ``page_ref`` by 1 (this slot stops reading them; whether they were
    kept or freed is the CALLER's eviction decision — the prefix cache
    only frees them at refcount 0). Resets the row/len/alloc/shared."""
    bt, stack, top = (cache["block_tables"], cache["free_stack"],
                      cache["free_top"])
    max_pages = bt.shape[1]
    num_pages = stack.shape[0]
    row = bt[slot]
    sh = cache["shared_pages"][slot]
    total = sh + cache["alloc_pages"][slot]
    idx = jnp.arange(max_pages, dtype=jnp.int32)
    # entries inside the owned range may already be NULL: a sliding-window
    # slot drops pages below its attention band mid-flight
    # (``drop_slot_pages``) — those freed already and must not push the
    # null page onto the stack here
    nonnull = row != 0
    freeable = jnp.logical_and(
        jnp.logical_and(idx < total, jnp.logical_not(keep)), nonnull)
    n_free = jnp.sum(freeable.astype(jnp.int32))
    pos = jnp.cumsum(freeable.astype(jnp.int32)) - 1
    dst = jnp.where(freeable, top + pos, num_pages)   # OOB -> dropped
    out = dict(cache)
    out["free_stack"] = stack.at[dst].set(row, mode="drop")
    out["free_top"] = top + n_free
    ref_ids = jnp.where(jnp.logical_and(idx < sh, nonnull), row, num_pages)
    out["page_ref"] = cache["page_ref"].at[ref_ids].add(-1, mode="drop")
    out["block_tables"] = bt.at[slot].set(jnp.zeros((max_pages,), jnp.int32))
    out["len"] = cache["len"].at[slot].set(0)
    out["alloc_pages"] = cache["alloc_pages"].at[slot].set(0)
    out["shared_pages"] = cache["shared_pages"].at[slot].set(0)
    return out


def free_slot(cache, slot):
    """Retire slot ``slot``: push ALL its owned pages (``alloc_pages``,
    not just the length-covered prefix) back onto the free stack, reset
    its block table row to the null page, and zero its length. Shared
    (prefix-cached) leading entries are NOT pushed — they stay cache
    property and only drop their refcount (``release_slot`` with
    ``keep = shared prefix``); without prefix caching ``shared_pages`` is
    0 and this frees exactly the owned set as before."""
    max_pages = cache["block_tables"].shape[1]
    keep = (jnp.arange(max_pages, dtype=jnp.int32)
            < cache["shared_pages"][slot])
    return release_slot(cache, slot, keep)


def drop_slot_pages(cache, slot, upto):
    """Free the pages behind slot ``slot``'s leading ``upto`` block-table
    entries and null the entries — the sliding-window page-eviction trick
    (docs/serving.md): once a page's positions all sit at or below the
    attention band's floor, no future decode step of this slot can read
    it (the band only moves forward), so the page is dead storage and
    returns to the free stack. Entries already dropped (null) are
    skipped, so repeated calls with a monotonically growing ``upto`` free
    each page exactly once; a windowed slot's steady-state footprint is
    O(window) pages regardless of generation length — the paged analog of
    the rolling ring buffer.

    CALLER contract: the dropped entries must be PRIVATE pages (the
    engine refuses ``prefix_cache`` for sliding-window models, so a
    windowed slot never holds shared entries) and fully below the band.
    ``alloc_pages`` is NOT decremented — it bounds the slot's row RANGE,
    and ``release_slot`` skips the nulled entries at retirement."""
    bt, stack, top = (cache["block_tables"], cache["free_stack"],
                      cache["free_top"])
    max_pages = bt.shape[1]
    num_pages = stack.shape[0]
    row = bt[slot]
    idx = jnp.arange(max_pages, dtype=jnp.int32)
    droppable = jnp.logical_and(idx < jnp.asarray(upto, jnp.int32),
                                row != 0)
    n = jnp.sum(droppable.astype(jnp.int32))
    pos = jnp.cumsum(droppable.astype(jnp.int32)) - 1
    dst = jnp.where(droppable, top + pos, num_pages)  # OOB -> dropped
    out = dict(cache)
    out["free_stack"] = stack.at[dst].set(row, mode="drop")
    out["free_top"] = top + n
    out["block_tables"] = bt.at[slot].set(jnp.where(droppable, 0, row))
    return out


#: pages moved per gather/promote program call (docs/serving.md "Tiered
#: KV pool"): the fixed tile-batch shape keeps both programs at ONE
#: compile each — a demote/promote of any depth is a loop of these
HOST_COPY_CHUNK = 8


def tile_specs(config, axis_name: str = MODEL_AXIS, *, kv_dtype=None):
    """PartitionSpec pytree for one gather/promote tile batch (the
    ``gather_pages`` result / ``promote_pages`` operand): per-layer
    ``(HOST_COPY_CHUNK, kv // pack, page_size, d * pack)`` K/V tiles (pool
    pages as held) shard along the
    kv-HEAD axis (dim 1) exactly like the pool pages they were cut from,
    so under TP each chip gathers/scatters its own head-shard and the
    host tier holds the pages at FULL head width (``serving/tp.py``
    maps the ``"tiles"`` compile role to this tree)."""
    layer = _layer_specs(config, axis_name, kv_dtype)
    return [dict(layer) for _ in range(config.num_layers)]


def gather_pages(cache, pages):
    """Read ``HOST_COPY_CHUNK`` pages' K/V tiles (and, quantized pools,
    their per-``(page, kv_head)`` scales) out of the pool — the demote
    half of the tiered pool (docs/serving.md "Tiered KV pool"): the
    frontend dispatches this BEFORE ``evict_pages`` returns the ids to
    the free stack, so program order on the device stream guarantees the
    copy reads the pages before any re-allocation overwrites them.
    ``pages`` is a fixed ``(HOST_COPY_CHUNK,)`` int32 row, null-padded —
    a null entry gathers page 0's garbage, which the caller discards.
    Pure read: the cache is NOT donated (it stays live)."""
    pages = jnp.asarray(pages, jnp.int32)
    return [{key: lc[key][pages] for key in lc} for lc in cache["layers"]]


def promote_pages(cache, pages, n, tiles):
    """Scatter ``n`` host-resident page tiles into freshly popped pages
    — the promote half of the tiered pool. ``pages`` holds the physical
    destinations (the caller host-reads the top ``n`` free-stack entries,
    exactly the pages this op's ``free_top -= n`` retires from the free
    set — the same pop discipline as ``alloc_slot``, with the ids read
    host-side so the tile write and the stack accounting cannot
    disagree); entries past ``n`` sink to the null page like every other
    masked pool write. The tiles are the raw pool-dtype bytes (and f32
    scales) ``gather_pages`` demoted, written back verbatim — promote is
    bit-stable by construction, never a requantization. The promoted
    pages carry ``page_ref == 0``: they become prefix-cache property
    (the radix tree grafts them via ``insert_promoted``), and sharers
    refcount them through ``alloc_slot_shared`` as usual."""
    pages = jnp.asarray(pages, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    idx = jnp.arange(pages.shape[0], dtype=jnp.int32)
    dst = jnp.where(idx < n, pages, 0)
    out = dict(cache)
    out["layers"] = [
        {key: lc[key].at[dst].set(tile[key].astype(lc[key].dtype))
         for key in lc}
        for lc, tile in zip(cache["layers"], tiles)]
    out["free_top"] = cache["free_top"] - n
    return out


def evict_pages(cache, pages_row, n):
    """Push the first ``n`` entries of ``pages_row`` back onto the free
    stack — the prefix cache evicting refcount-0 pages it owns. The CALLER
    (the cache's LRU walk) guarantees the pages are reachable from no
    block table and have ``page_ref == 0``; this is the stack push only."""
    stack, top = cache["free_stack"], cache["free_top"]
    num_pages = stack.shape[0]
    n = jnp.asarray(n, jnp.int32)
    idx = jnp.arange(pages_row.shape[0], dtype=jnp.int32)
    dst = jnp.where(idx < n, top + idx, num_pages)    # OOB -> dropped
    out = dict(cache)
    out["free_stack"] = stack.at[dst].set(pages_row, mode="drop")
    out["free_top"] = top + n
    return out


def defrag_map(cache, extra_live=None, *, rings: Tuple[int, ...] = ()):
    """Compact live pages to the low end of the pool (stable order),
    rebuild the free stack from actual liveness, and return
    ``(cache, new_idx)`` where ``new_idx[old_page] = new_page`` — the
    remap a host-side prefix cache needs to follow its pages.

    With a block-table indirection fragmentation never costs correctness
    or speed — any free page is as good as another — but compaction keeps
    the live set prefix-dense (cheap pool-prefix checkpointing / shrink)
    and doubles as a leak collector: a page reachable from no slot's table
    returns to the free stack even if an earlier free miscounted. O(pool)
    gather per layer — an explicit maintenance op, not a per-step one.

    ``extra_live``: optional ``(num_pages,)`` bool mask of pages live for
    reasons no block table shows — the prefix cache's refcount-0 resident
    pages. Omitting it with a prefix cache attached would collect the
    cache's pages as leaks (and hand them out while the radix tree still
    names them).

    ``rings``: the layers of ring groups (static). Their pools are no part
    of the block table's pages and stay as they are; so does a state
    group's layer, which holds no page at all."""
    bt = cache["block_tables"]
    num_pages = num_pages_of(cache)
    max_pages = bt.shape[1]

    # liveness bound = SHARED + OWNED entries (a slot's
    # preallocated-but-unwritten tail is live: its future tokens land
    # there; its shared prefix is live: its reads land there)
    n_used = cache["shared_pages"] + cache["alloc_pages"]
    used_entries = (jnp.arange(max_pages, dtype=jnp.int32)[None, :]
                    < n_used[:, None])                       # (slots, mp)
    live = jnp.zeros((num_pages,), bool).at[
        jnp.where(used_entries, bt, 0)].set(True)
    if extra_live is not None:
        live = jnp.logical_or(live, extra_live)
    live = live.at[0].set(True)                  # null page stays page 0
    n_live = jnp.sum(live.astype(jnp.int32))
    new_idx = jnp.where(live, jnp.cumsum(live.astype(jnp.int32)) - 1,
                        n_live + jnp.cumsum((~live).astype(jnp.int32)) - 1
                        ).astype(jnp.int32)
    old_of_new = jnp.zeros((num_pages,), jnp.int32).at[new_idx].set(
        jnp.arange(num_pages, dtype=jnp.int32))

    out = dict(cache)
    # a page's scale moves with the page through the same permutation —
    # remapped quantized contents stay bit-identical to pre-defrag
    out["layers"] = [
        lc if i in rings or not pool_tensors(lc)
        else {key: lc[key][old_of_new] for key in lc}
        for i, lc in enumerate(cache["layers"])]
    out["block_tables"] = jnp.where(used_entries, new_idx[bt], 0)
    out["page_ref"] = cache["page_ref"][old_of_new]
    idx = jnp.arange(num_pages, dtype=jnp.int32)
    out["free_stack"] = jnp.where(idx < num_pages - n_live, n_live + idx, 0)
    out["free_top"] = (num_pages - n_live).astype(jnp.int32)
    return out, new_idx


def defrag(cache, extra_live=None, *, rings: Tuple[int, ...] = ()):
    """``defrag_map`` without the remap (callers with no host-side page
    names to rewrite)."""
    return defrag_map(cache, extra_live, rings=rings)[0]


def prefill_into_pages(cache, slot, contig_layers, s0, *, start=0,
                       groups: Optional[Tuple[LayerGroup, ...]] = None):
    """Write a CONTIGUOUS prefill cache (the models' flash-prefill
    output: per layer the layout's tensors, ``k``/``v`` or ``latent``, each
    of shape ``(1, heads, len_bucket, stored)``, per HEAD whatever the pool
    packs into a row: the write lays them side by side)
    into slot ``slot``'s already-allocated pages, and set its length to
    ``s0`` (traced OK; positions past ``s0`` — prompt-bucket padding —
    are not written: their steps sink to the null page). Position ``p``
    lands in table entry ``p // page_size`` at offset ``p % page_size``.

    ``start``: first position to write (default 0). A shared-prefix
    admission prefills only the uncached tail — positions below ``start``
    are the prefix-cache pages the slot merely reads, and MUST NOT be
    written (they are shared, and the partially-computed prefix slots of
    the contiguous buffer may hold gathered — not recomputed — values
    anyway); they sink to the null page like bucket padding.

    The write is ``ops.paged_write``, whole pages in place and row-major
    like a decode step's (docs/serving.md "Page-pool layout"); a
    QUANTIZED pool quantizes whole table entries and keeps its own
    scatter (no cell runs it).

    ``groups`` (``layer_groups(config)``, static; needed where one of them
    is a ring): a RING group's layers get the last ``R`` pages' worth of
    the buffer, the pages that hold everything the first decode step's
    band reaches, written through the slot's ring view; the positions
    before them are never written, there or anywhere.

    A STATE group's layer (its dict holds no ``*_pages``) gets the buffer's
    own tensors, row 0 of each: the state the prompt ended in overwrites
    the slot's row whole, whatever the last request left there."""
    names = pool_tensors(a_layer_of_pages(cache))
    out = dict(cache)
    out["len"] = cache["len"].at[slot].set(jnp.asarray(s0, jnp.int32))
    row = jax.lax.dynamic_slice_in_dim(cache["block_tables"], slot, 1, axis=0)
    if "k_scales" in cache["layers"][0]:
        out["layers"] = _prefill_quantized_pages(
            cache, names, row[0], contig_layers, s0, start)
        return out
    origin = jnp.zeros((1,), jnp.int32)   # the buffer starts at position 0
    keys = [pool_key(n) for n in names]
    ring_of = {i: g for g in groups or () if g.ring for i in g.layers}
    ps = page_size_of(cache)

    def ring_write(lc, src, window):
        # logical pages first .. first + count - 1 of the buffer: all of
        # it where it fits the ring, else the R pages that end with the
        # buffer's last (at or below the band's first live page, so the
        # band's pages are among them)
        ring = ring_pages(window, ps)
        pages = cdiv(src[names[0]].shape[2], ps)
        count = min(pages, ring)
        first = jnp.clip(
            jnp.maximum(jnp.asarray(s0, jnp.int32) - window + 1, 0) // ps,
            0, pages - count)
        table = _ring_table(slot, first, count, ring).reshape(1, count)
        chunks = [src[n] for n in names]
        if count < pages:
            # (a buffer that is no whole number of pages is padded to
            # one, so that the slice never runs off its end)
            pad = pages * ps - chunks[0].shape[2]
            chunks = [jax.lax.dynamic_slice_in_dim(
                jnp.pad(c, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else c,
                first * ps, count * ps, axis=2) for c in chunks]
        return paged_write([lc[k] for k in keys], chunks, table, origin,
                           stop=s0 - first * ps)

    def state_write(lc, src):
        return {name: jax.lax.dynamic_update_slice_in_dim(
            held, src[name].astype(held.dtype), slot, axis=0)
            for name, held in lc.items()}

    out["layers"] = [
        state_write(lc, src) if not pool_tensors(lc) else
        dict(zip(keys,
                 ring_write(lc, src, ring_of[i].window) if i in ring_of
                 else paged_write([lc[k] for k in keys],
                                  [src[n] for n in names], row, origin,
                                  start=start, stop=s0)))
        for i, (lc, src) in enumerate(zip(cache["layers"], contig_layers))]
    return out


def _prefill_quantized_pages(cache, names, row, contig_layers, s0, start):
    """``prefill_into_pages`` for a quantized pool: quantize-on-write
    (docs/serving.md "Quantized KV pages"): each
    written table entry gets a fresh per-(page, kv_head) symmetric
    scale from ITS tokens' amax — alloc reset these pages to scale
    0, so set (not max) is exact. Entries below ``start`` (shared
    prefix pages) and bucket padding have no valid positions: their
    writes sink to the null page and their scale row targets page 0
    — shared pages keep their shared scales."""
    ps = page_size_of(cache)
    max_pages = row.shape[0]
    len_bucket = contig_layers[0][names[0]].shape[2]
    pos = jnp.arange(len_bucket, dtype=jnp.int32)
    valid = jnp.logical_and(pos >= start, pos < s0)
    phys = jnp.where(valid, row[jnp.clip(pos // ps, 0, max_pages - 1)], 0)
    off = pos % ps
    qmax = kv_qmax(cache["layers"][0]["k_pages"].dtype)
    nb = cdiv(len_bucket, ps)
    pad = nb * ps - len_bucket
    valid_p = jnp.pad(valid, (0, pad))
    ent_any = valid_p.reshape(nb, ps).any(axis=1)          # (nb,)
    page_e = jnp.where(ent_any, row[:nb], 0)
    ent_of = jnp.clip(pos // ps, 0, nb - 1)

    def scatter_q(pages, scales, x):
        xf = x.astype(jnp.float32)           # (len_bucket, kv, d)
        ax = jnp.where(valid[:, None, None], jnp.abs(xf), 0.0)
        ax = jnp.pad(ax, ((0, pad), (0, 0), (0, 0)))
        amax = ax.reshape(nb, ps, *x.shape[1:]).max(axis=(1, 3))
        sc = amax / qmax                                   # (nb, kv)
        inv = jnp.where(sc > 0, 1.0 / jnp.maximum(sc, 1e-30), 0.0)
        q = kv_cast(xf * inv[ent_of][:, :, None], pages.dtype, qmax)
        return (pages.at[phys, :, off, :].set(q),
                scales.at[page_e].set(
                    jnp.where(ent_any[:, None], sc, 0.0)))

    new_layers = []
    for lc, src in zip(cache["layers"], contig_layers):
        new = {}
        for name in names:
            x = src[name][0].transpose(1, 0, 2)  # (len_bucket, heads, d)
            new[pool_key(name)], new[scale_key(name)] = scatter_q(
                lc[pool_key(name)], lc[scale_key(name)], x)
        new_layers.append(new)
    return new_layers


# --------------------------------------------------------------------------
# pool sizing (the capacity lever the quantized pool exists for)
# --------------------------------------------------------------------------

def page_bytes(config, page_size: int = 16, *, kv_dtype=None,
               dtype=None, layers: Optional[int] = None) -> int:
    """Pool bytes ONE page costs across all layers that hold pages (or
    across ``layers`` of them: one group's, ``len(group.layers)``; a state
    group's bytes are :func:`state_bytes`): what the layout's
    tensors store for ``page_size`` tokens at the pool dtype (per-head K
    and V tiles; a latent pool's one entry at its stated width — the lane
    padding of a latent row is the pool's, not a token's), plus —
    quantized pools — their f32 per-(page, kv_head) scale entries. The honest per-page denominator
    for capacity planning: at ``page_size=16, head_dim=64`` an int8 page
    costs ``(16*64 + 4) / (2*16*64) ≈ 0.502`` of a bf16 page, which is
    where the ~2x slot capacity comes from."""
    quant = resolve_kv_dtype(kv_dtype)
    if quant is not None:
        dt = quant[0]
    else:
        dt = dtype if dtype is not None \
            else resolve_compute_dtype(config.dtype)
    layout = layout_of(config)
    kv_local = divide(layout.heads, config.tensor_parallel_size)
    per_tensor = kv_local * page_size * layout.width * \
        jnp.dtype(dt).itemsize
    if quant is not None:
        per_tensor += kv_local * jnp.dtype(jnp.float32).itemsize
    if layers is None:
        # every layer that stores tokens (a state group's store none)
        layers = config.num_layers - len(state_layers(config))
    return len(layout.tensors) * per_tensor * layers


def max_slots_for_pool_bytes(config, pool_bytes: int, *,
                             pages_per_slot: int, page_size: int = 16,
                             kv_dtype=None, dtype=None) -> int:
    """How many ``pages_per_slot``-page slots a ``pool_bytes`` budget
    admits (the null page 0 is carved out first). Holding ``pool_bytes``
    fixed, ``kv_dtype='int8'`` admits ~2x the slots of the bf16 pool —
    the acceptance pin in ``tests/test_quantized_kv.py``."""
    pb = page_bytes(config, page_size, kv_dtype=kv_dtype, dtype=dtype)
    num_pages = pool_bytes // pb
    return max(int(num_pages - 1) // pages_per_slot, 0)
