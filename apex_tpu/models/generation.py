"""Autoregressive decoding with a static-shape KV cache (beyond reference).

The reference (apex) is a training-utilities library and ships no inference
path; a complete framework needs one. This module is the TPU-first decode
design:

- **Static shapes everywhere**: the cache is allocated once at
  ``(batch, kv_heads_local, max_len, head_dim)`` per layer; each step
  writes its chunk with ``lax.dynamic_update_slice`` and attends over the
  full buffer with an absolute-position mask. No growing arrays, no
  recompilation per step.
- **Prefill rides the flash kernel**: the cache length starts as a STATIC
  Python 0 and stays static under plain-int arithmetic, so the first
  (prompt) chunk is provably past-free at trace time and the blocks route
  it through the same Pallas flash attention as training — O(tile) memory
  instead of materializing ``(b, kv, rep, s, max_len)`` scores. Decode
  steps (traced length inside ``lax.scan``) use the masked dot-product
  over the cache, where the score tensor is a thin ``s=1`` slab.
- **One compiled loop**: the decode loop is a ``lax.scan`` over steps, so
  the whole ``generate`` call is a single XLA program (jittable end to
  end); the per-step cache update aliases in place under XLA.
- **Tensor-parallel native**: caches hold the LOCAL kv-head shard (GQA
  divides kv heads over the ``model`` axis exactly like training), and
  sampling all-gathers only the final-position vocab-parallel logits
  (payload ``[batch, vocab]``) — the replicated PRNG key then makes every
  rank sample the same token.
- **GQA/MQA without expansion**: queries reshape to
  ``(b, kv, rep, s, d)`` and contract against the unexpanded K/V cache —
  the cache stays ``num_kv_heads``-sized in HBM (Llama/Mistral GQA).

Prefill and decode share one model entry point: ``model.apply(variables,
ids, cache=cache)`` returns ``(vocab-parallel logits, updated cache)`` for
any chunk length, so chunked/speculative decoding composes for free. While
the cache length is static (prefill + chunked continuation outside the
scan) out-of-range chunks raise at trace time; once the length is traced
(inside ``generate``'s scan) bounds are enforced by ``generate`` itself —
callers driving ``apply`` directly with a traced length own that check
(``lax.dynamic_slice`` clamps silently).

Context parallelism does not compose with incremental decoding (the cache
is position-contiguous per device); the models raise on that combination.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.amp.policy import resolve_compute_dtype
from apex_tpu.mesh import MODEL_AXIS
from apex_tpu.ops import quant
from apex_tpu.ops.paged_write import paged_write
from apex_tpu.transformer.tensor_parallel.mappings import (
    axis_is_bound as _axis_bound,
    gather_from_tensor_model_parallel_region,
)
from apex_tpu.transformer.utils import divide


# --- cache structure ---------------------------------------------------------
#
# cache = {"layers": [{"k": (b, kv_local, T, d), "v": ...}] * num_layers,
#          "len":   tokens already written — a Python int while static
#                   (prefill, chunked continuation), an int32 scalar inside
#                   the decode scan}
#
# The per-layer view handed to a decoder block adds the current length so
# the block can place its chunk: {"k", "v", "len"}.


def init_cache(config, batch: int, max_len: int, *, dtype=None):
    """Allocate an all-zeros KV cache for ``batch`` sequences of up to
    ``max_len`` total tokens (prompt + generated). Inside shard_map with
    the ``model`` axis bound, ``config.tensor_parallel_size`` kv-head
    shards divide exactly as in training.

    With ``config.rolling_cache`` (sliding-window models), the buffer is a
    ROLLING ring of ``sliding_window`` slots instead of ``max_len`` —
    O(window) HBM for arbitrarily long decodes (the Mistral serving
    pattern); writes wrap modulo the window and the mask reconstructs each
    slot's absolute position."""
    # what a layer stores per token is the pool's statement, shared with
    # the paged cache this buffer is scattered into (imported here: the
    # serving package imports this module)
    from apex_tpu.serving.kv_pool import layout_of, state_layers

    layout = layout_of(config)
    kv_local = divide(layout.heads, config.tensor_parallel_size)
    dt = dtype if dtype is not None else resolve_compute_dtype(config.dtype)
    states = state_layers(config)
    t_buf = max_len
    if getattr(config, "rolling_cache", False):
        if not getattr(config, "sliding_window", None):
            raise ValueError("rolling_cache requires sliding_window")
        # ALWAYS window-sized: a ring shorter than the window would
        # silently drop reachable positions once decoding passes its size
        t_buf = config.sliding_window
    shape = (batch, kv_local, t_buf, layout.stored)
    # a layer that keeps a state and no token (kv_pool.layer_groups) holds
    # its state's tensors, a row a sequence, whatever ``max_len`` is
    layers = [{t.name: jnp.zeros((batch,) + tuple(t.shape), t.dtype)
               for t in states[i]} if i in states
              else {name: jnp.zeros(shape, dt) for name in layout.tensors}
              for i in range(config.num_layers)]
    return {"layers": layers, "len": 0}


def cache_max_len(cache) -> int:
    for lc in cache["layers"]:
        if "k" in lc or "latent" in lc:
            return (lc["k"] if "k" in lc else lc["latent"]).shape[2]
    raise ValueError("no layer of this cache stores tokens")


def check_chunk_bounds(cache, s: int, max_position_embeddings: int, *,
                       rolling: bool = False):
    """Model-level guard for a chunk of length ``s``: while the cache
    length is static, out-of-range chunks (past the position table or the
    cache buffer) raise at trace time — the decode-path analog of the
    training forward's explicit position checks. Returns the offset.
    A ``rolling`` buffer wraps, so only the position cap applies."""
    t0 = cache["len"]
    t_max = cache_max_len(cache)
    if isinstance(t0, int):
        if t0 + s > max_position_embeddings:
            raise ValueError(
                f"decode chunk [{t0}, {t0 + s}) exceeds "
                f"max_position_embeddings={max_position_embeddings}")
        if not rolling and t0 + s > t_max:
            raise ValueError(
                f"decode chunk [{t0}, {t0 + s}) exceeds the cache buffer "
                f"(max_len={t_max}); allocate a larger init_cache")
    elif not rolling and s > t_max:
        raise ValueError(f"chunk length {s} exceeds cache max_len={t_max}")
    return t0


def is_paged(cache) -> bool:
    """True for a paged serving cache (``apex_tpu/serving/kv_pool.py``):
    per-layer page pools + per-SLOT block tables and lengths, recognized
    by the ``block_tables`` key. ``cache["len"]`` is then a
    ``(num_slots,)`` vector, not a scalar."""
    return "block_tables" in cache


def layer_cache(cache, i: int, table=None):
    """Per-layer view for decoder block ``i`` (adds the shared length —
    and, for a paged cache, the shared block tables). ``table``: the
    ``(block_tables, len)`` of the layer's OWN group where the pool holds
    more than one (:func:`paged_layer_tables`); the block writes and reads
    through them and never tells the difference."""
    lc = dict(cache["layers"][i])
    lc["len"] = cache["len"]
    if table is not None:
        lc["block_tables"], lc["len"] = table
    elif is_paged(cache):
        lc["block_tables"] = cache["block_tables"]
    return lc


@dataclasses.dataclass(frozen=True)
class StateTensor:
    """One tensor a layer keeps per sequence, as a config's ``layer_states``
    states it (``serving/kv_pool.layer_groups`` reads it): ``(slots,) +
    shape`` in the engine's cache, ``(batch,) + shape`` in a contiguous
    one."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any


def layer_state(cache, i: int):
    """Per-layer view for a block that keeps a STATE and no token
    (``serving/kv_pool.layer_groups``: a linear-attention or a gated conv
    layer): its tensors, a row a sequence (a slot, in the engine's cache),
    and neither table nor lengths: what it keeps does not depend on where a
    sequence stands."""
    return dict(cache["layers"][i])


def paged_layer_tables(cache, config, s: int):
    """One entry a layer for :func:`layer_cache`'s ``table``: ``None`` for
    a layer of the block table's own group, and for a layer of a RING
    group (windowed layers beside full ones: ``serving/kv_pool.
    layer_groups``) the group's view of its slots' rings at this step,
    computed once a group."""
    from apex_tpu.serving.kv_pool import (layer_groups, page_size_of,
                                          ring_view)

    tables = [None] * config.num_layers
    for group in layer_groups(config):
        if not group.ring:
            continue
        if s != 1:
            raise NotImplementedError(
                f"a ring group takes one token a slot and step, got a "
                f"chunk of {s}: a ring holds the band of ONE query "
                f"position (speculation and chunked prefill are refused "
                f"where the engine is built)")
        view = ring_view(cache["len"], window=group.window,
                         page_size=page_size_of(cache))
        for i in group.layers:
            tables[i] = view
    return tables


def is_static_prefill(lc, s: int) -> bool:
    """True when this chunk is provably the first tokens in the cache AT
    TRACE TIME — the blocks then attend with the training flash kernel
    (past-free, O(tile) memory) instead of the dense cached path."""
    return isinstance(lc["len"], int) and lc["len"] == 0 and s > 1


def update_layer_cache(lc, k_chunk, v_chunk):
    """Write a ``(b, kv, s, d)`` K/V chunk at offset ``len`` and return the
    updated per-layer view. XLA aliases the update in place inside jit.

    TRACED-length caveat: with a sealed (traced) ``len`` the bounds cannot
    be checked at trace time and ``lax.dynamic_update_slice`` CLAMPS an
    out-of-range start, silently overwriting the newest cache entries —
    callers driving ``model.apply`` inside their own scan own the bound
    (``generate`` enforces it up front; static lengths raise in
    ``check_chunk_bounds``)."""
    t0 = lc["len"]
    start = (0, 0, t0, 0)
    out = dict(lc)  # preserve extra entries (e.g. T5's cross ck/cv)
    out["k"] = lax.dynamic_update_slice(lc["k"],
                                        k_chunk.astype(lc["k"].dtype), start)
    out["v"] = lax.dynamic_update_slice(lc["v"],
                                        v_chunk.astype(lc["v"].dtype), start)
    return out


def _append_quantized_pages(pages, scales, chunk, bt, t, ps, max_pages,
                            qmax):
    """Quantized-pool append with REQUANTIZE-ON-GROW (docs/serving.md
    "Quantized KV pages"): the ``s <= page_size`` chunk spans at most the
    boundary page and its successor, so two sequential rounds each (1)
    take the per-(slot, kv_head) amax of the new tokens landing in that
    page, (2) grow the page's symmetric scale monotonically
    (``new = max(old, amax/qmax)``), (3) rescale the page's EXISTING
    quantized contents onto the grown grid (ratio 1 — the common case —
    is a bit-exact rewrite), and (4) merge the new tokens quantized at
    the new scale. Only pages at or past ``len // page_size`` are ever
    touched, so full pages — the prefix cache's sharing unit and the
    preemption spill set — stay bit-stable forever."""
    slots, kvh, s, d = chunk.shape
    cf = chunk.astype(jnp.float32)
    pos = t[:, None] + jnp.arange(s, dtype=t.dtype)[None, :]  # (slots, s)
    base = t // ps
    sl = jnp.arange(slots)
    for j in (0, 1):
        ent = base + j
        pg = jnp.take_along_axis(
            bt, jnp.clip(ent, 0, max_pages - 1)[:, None], axis=1)[:, 0]
        in_pg = (pos // ps) == ent[:, None]                  # (slots, s)
        has = in_pg.any(axis=1)
        amax = jnp.where(in_pg[:, None, :, None], jnp.abs(cf), 0.0
                         ).max(axis=(2, 3))                  # (slots, kv)
        old = scales[pg]
        new = jnp.where(has[:, None], jnp.maximum(old, amax / qmax), old)
        ratio = jnp.where(new > 0, old / jnp.maximum(new, 1e-30), 0.0)
        tile = pages[pg].astype(jnp.float32) * ratio[:, :, None, None]
        tile_q = quant.kv_cast(tile, pages.dtype, qmax)
        inv = jnp.where(new > 0, 1.0 / jnp.maximum(new, 1e-30), 0.0)
        qtok = quant.kv_cast(cf * inv[:, :, None, None], pages.dtype,
                             qmax)
        # members scatter at their in-page offset; non-members drop at
        # the out-of-range offset ps
        off = jnp.where(in_pg, pos % ps, ps)                 # (slots, s)
        tile_q = tile_q.at[sl[:, None], :, off, :].set(
            qtok.transpose(0, 2, 1, 3), mode="drop")
        # distinct live slots own distinct pages; idle/done rows collide
        # only on the garbage null page 0, which no live slot reads
        pages = pages.at[pg].set(tile_q)
        scales = scales.at[pg].set(new)
    return pages, scales


def update_paged_layer_cache(lc, *chunks):
    """Write one ``(slots, heads, s, d)`` chunk per stored tensor of the
    layer's layout (``k_chunk, v_chunk`` for per-head K and V; the one
    latent entry for a latent pool — ``kv_pool.layout_of``, in the order
    the layer view holds its ``*_pages``) into the page pool at each
    slot's current length: slot ``b``'s chunk position ``i`` lands in page
    ``block_tables[b, (len_b + i) // page_size]`` at offset
    ``(len_b + i) % page_size``. Distinct slots own distinct pages
    (callers keep ``s <= page_size``, the paged kernel's own bound); an
    idle slot (block table row all null-page) writes into the reserved
    page 0, which no live sequence ever reads.

    The write is ``ops.paged_write``: in place and ROW-MAJOR, as the
    decode kernels read the pool. A scatter over the head axis here set
    the layout of the whole program's pool, and a copy of every layer's
    pool stood in front of every kernel of every step (docs/serving.md
    "Page-pool layout").

    A QUANTIZED pool (``k_scales`` in the layer view) quantizes on write:
    the chunk's pages requantize-on-grow through
    :func:`_append_quantized_pages` (whole pages rewritten along the
    leading axis: another write, and no cell's), and the per-page scales
    ride the layer view back to the model's ``paged_attention`` call."""
    # the pool's own names (imported here: the serving package imports
    # this module)
    from apex_tpu.serving.kv_pool import pool_key, pool_tensors, scale_key

    names = pool_tensors(lc)
    if len(names) != len(chunks):
        raise ValueError(f"the layer stores {names}, got {len(chunks)} "
                         f"chunk(s) to write")
    pools = [pool_key(n) for n in names]
    out = dict(lc)
    if "k_scales" in lc:
        ps = lc[pools[0]].shape[2]
        qmax = quant.kv_qmax(lc[pools[0]].dtype)
        for name, key, chunk in zip(names, pools, chunks):
            out[key], out[scale_key(name)] = _append_quantized_pages(
                lc[key], lc[scale_key(name)], chunk, lc["block_tables"],
                lc["len"], ps, lc["block_tables"].shape[1], qmax)
        return out
    out.update(zip(pools, paged_write(
        [lc[key] for key in pools], chunks, lc["block_tables"], lc["len"])))
    return out


def update_layer_cache_rolling(lc, k_chunk, v_chunk):
    """Ring-buffer write: the chunk's positions land at ``pos % R``. Only
    the LAST ``min(s, R)`` chunk positions are kept (earlier ones would
    collide with slots later writes need, and a window model never reads
    past its band anyway). Duplicate-free scatter indices by construction."""
    t0 = lc["len"]
    r = lc["k"].shape[2]
    s = k_chunk.shape[2]
    keep = min(s, r)
    k_tail = k_chunk[:, :, s - keep:, :]
    v_tail = v_chunk[:, :, s - keep:, :]
    idx = (t0 + (s - keep) + jnp.arange(keep, dtype=jnp.int32)) % r
    out = dict(lc)
    out["k"] = lc["k"].at[:, :, idx, :].set(k_tail.astype(lc["k"].dtype))
    out["v"] = lc["v"].at[:, :, idx, :].set(v_tail.astype(lc["v"].dtype))
    return out


def _masked_attention_core(q, k, v, mask, *, scale, bias=None):
    """Shared GQA dot-product core for the cached paths: fp32 scores +
    accumulation, queries grouped against the unexpanded kv-head buffer,
    ``mask`` broadcastable to ``(b, kv, rep, s, T)``."""
    b, h, s, d = q.shape
    kv = k.shape[1]
    rep = divide(h, kv)
    t_max = k.shape[2]

    qf = q.reshape(b, kv, rep, s, d).astype(jnp.float32)
    scores = jnp.einsum("bkrsd,bktd->bkrst", qf, k.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    scores = scores * (jnp.float32(scale) if scale is not None
                       else 1.0 / jnp.sqrt(jnp.float32(d)))
    if bias is not None:
        bb = jnp.broadcast_to(bias.astype(jnp.float32), (b, h, s, t_max))
        scores = scores + bb.reshape(b, kv, rep, s, t_max)
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkrst,bktd->bkrsd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, h, s, d).astype(q.dtype)


def cached_attention_rolling(q, lc, *, window: int,
                             scale: Optional[float] = None):
    """Single-step (``s=1``) attention over the rolling ring: slot ``j``'s
    absolute position is reconstructed from the write pointer
    (``last - ((last - j) mod R)``), masked to the causal window band and
    to written slots. Multi-token chunks are unsupported on the ring (a
    later in-chunk write would overwrite a slot an earlier query needs)."""
    k, v, t0 = lc["k"], lc["v"], lc["len"]
    if q.shape[2] != 1:
        raise NotImplementedError(
            "rolling cache supports single-token decode steps only "
            "(prefill rides the flash kernel; chunked continuation / "
            "speculative verification need the full buffer)")
    r = k.shape[2]
    last = t0                                  # this step's absolute position
    slots = jnp.arange(r, dtype=jnp.int32)
    p_j = last - ((last - slots) % r)          # slot -> absolute position
    mask = jnp.logical_and(p_j >= 0, p_j > last - window)
    return _masked_attention_core(q, k, v, mask[None, None, None, None],
                                  scale=scale)


def advance_cache(cache, new_layers, s: int):
    """Model-level reassembly after all blocks ran a chunk of length s.
    Plain-int arithmetic keeps a static length static across chunks; the
    per-layer entries keep everything but the shared keys (length, paged
    block tables) — including model-specific extras like T5's cross
    ``ck``/``cv``. Top-level extras (a paged cache's block tables and free
    list) pass through untouched; a paged ``len`` is a per-slot vector and
    advances elementwise."""
    out = dict(cache)
    out["layers"] = [{k: v for k, v in lc.items()
                      if k not in ("len", "block_tables")}
                     for lc in new_layers]
    out["len"] = cache["len"] + s
    return out


def seal_cache(cache):
    """Convert a static length to a traced int32 scalar so the cache can be
    a ``lax.scan`` carry (the decode loop's representation)."""
    return dict(cache, len=jnp.asarray(cache["len"], jnp.int32))


def cached_attention(q, lc, *, window: Optional[int] = None, bias=None,
                     scale: Optional[float] = None):
    """Masked dot-product attention of a ``(b, h, s, d)`` query chunk at
    absolute positions ``[len, len+s)`` against the full cache buffer.

    The causal mask is over ABSOLUTE positions (key j visible to query at
    global position p iff ``p - window < j <= p``), which simultaneously
    hides the not-yet-written tail of the static buffer. GQA contracts the
    grouped queries against the unexpanded kv-head cache. fp32 scores and
    accumulation (same numerics contract as the flash kernel). ``bias``
    (broadcastable to ``(b, h, s, t_max)``, e.g. T5 relative-position
    bias) adds to the scaled scores before masking — the cached analog of
    the flash kernel's additive slot."""
    k, v, t0 = lc["k"], lc["v"], lc["len"]
    s = q.shape[2]
    t_max = k.shape[2]
    pos_q = t0 + jnp.arange(s, dtype=jnp.int32)[:, None]      # (s, 1)
    pos_k = jnp.arange(t_max, dtype=jnp.int32)[None, :]       # (1, T)
    mask = pos_k <= pos_q
    if window is not None:
        mask = jnp.logical_and(mask, pos_k > pos_q - window)
    return _masked_attention_core(q, k, v, mask[None, None, None],
                                  scale=scale, bias=bias)


# --- sampling + the generate loop -------------------------------------------


def _greedy_token(logits, axis_name):
    """fp32 argmax over (possibly vocab-parallel) logits' last axis —
    the shared greedy primitive for sampling and speculative verify."""
    if _axis_bound(axis_name):
        logits = gather_from_tensor_model_parallel_region(logits, axis_name)
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def _sample_token(last_logits, step_key, *, temperature, top_k, top_p,
                  axis_name):
    """One token per batch row from final-position (possibly vocab-parallel)
    logits. Greedy at temperature 0; otherwise top-k/top-p/categorical.
    Inside a TP region the gather makes logits (and the replicated key makes
    the draw) identical on every rank."""
    if not temperature:
        return _greedy_token(last_logits, axis_name)
    if _axis_bound(axis_name):
        last_logits = gather_from_tensor_model_parallel_region(
            last_logits, axis_name)
    logits = last_logits.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        # nucleus: keep the smallest prefix of the sorted distribution with
        # cumulative mass > top_p (the first token always survives: the
        # EXCLUSIVE cumsum below is 0.0 < top_p for it)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        mass_before = jnp.cumsum(probs, axis=-1) - probs
        cutoff_idx = jnp.sum((mass_before < top_p).astype(jnp.int32),
                             axis=-1, keepdims=True) - 1
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(step_key, logits, axis=-1).astype(jnp.int32)


def validate_sampling(temperature, top_k, top_p, rng):
    """Shared sampling-knob validation for the decode loops; returns the
    effective rng."""
    if temperature and rng is None:
        raise ValueError("sampling (temperature > 0) needs an explicit rng")
    if not temperature and (top_k is not None or top_p is not None
                            or rng is not None):
        # the mirror-image misuse: sampling knobs with greedy decoding
        # would be silently ignored
        raise ValueError("top_k/top_p/rng require temperature > 0 (greedy "
                         "decoding at temperature=0 ignores them)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        # top_p <= 0 would otherwise hit the exclusive-cumsum edge (no row
        # below the threshold -> index -1 -> smallest logit as cutoff) and
        # silently sample the FULL distribution
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    return rng if rng is not None else jax.random.PRNGKey(0)


def decode_loop(step_apply, prefill_logits, cache, max_new_tokens: int, *,
                temperature, top_k, top_p, rng, eos_token_id, axis_name):
    """The shared sampled-decode scan (decoder-only AND encoder-decoder
    models): ``step_apply(tok_(b,), cache) -> (logits_(b,1,V), cache)``.
    Samples the first token from ``prefill_logits[:, -1]``, then scans
    single-token steps; EOS rows keep emitting EOS. Returns the
    ``(b, max_new_tokens)`` generated tokens."""
    b = prefill_logits.shape[0]

    def sample(last, i):
        return _sample_token(last, jax.random.fold_in(rng, i),
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, axis_name=axis_name)

    tok0 = sample(prefill_logits[:, -1], 0)
    done0 = (tok0 == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((b,), bool)

    def step(carry, i):
        cache, tok, done = carry
        step_logits, cache = step_apply(tok, cache)
        nxt = sample(step_logits[:, 0], i)
        if eos_token_id is not None:
            nxt = jnp.where(done, jnp.int32(eos_token_id), nxt)
            done = jnp.logical_or(done, nxt == eos_token_id)
        return (cache, nxt, done), nxt

    if max_new_tokens > 1:
        # without an eos the `done` carry is vestigial (never read) —
        # kept so the scan signature is identical across eos modes
        _, rest = lax.scan(step, (cache, tok0, done0),
                           jnp.arange(1, max_new_tokens))
        return jnp.concatenate([tok0[:, None], rest.T], axis=1)
    return tok0[:, None]


def generate(model, variables, prompt_ids, max_new_tokens: int, *,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             rng=None, eos_token_id: Optional[int] = None,
             axis_name: str = MODEL_AXIS, paged: bool = False,
             num_slots: Optional[int] = None, page_size: int = 16,
             prefix_cache: bool = False, kv_dtype=None):
    """Prefill the prompt (flash-kernel path), then scan ``max_new_tokens``
    single-token decode steps. Returns ``(batch, prompt_len +
    max_new_tokens)`` token ids (prompt included). After ``eos_token_id``
    a row keeps emitting EOS.

    Jittable end to end (``max_new_tokens`` static). Works plain, under
    ``jit`` with a dp-sharded batch, or inside ``shard_map`` with the
    ``model`` axis bound (vocab-/head-sharded decode).

    ``paged=True`` routes the batch through the continuous-batching
    serving engine (``apex_tpu/serving``): each row becomes a queued
    request over ``num_slots`` decode slots (default: the batch size)
    backed by a paged KV pool — same greedy output, but EOS rows retire
    and free their slot/pages instead of padding to ``max_new_tokens``.
    Host-driven (not jittable as one program); greedy path is
    token-identical to the lock-step scan. ``prefix_cache=True`` (paged
    only) additionally shares cached K/V pages across requests with a
    common prompt prefix — same outputs, prefill skipped for the shared
    pages (``apex_tpu/serving/prefix_cache.py``). ``kv_dtype`` (paged
    only) stores the pool's K/V pages quantized (``"int8"`` or
    ``"fp8"``/``"e4m3"``) with per-(page, kv_head) scales, dequantized
    inside the paged kernel — greedy output then matches the fp pool to
    tolerance, not bit-exactly (docs/serving.md "Quantized KV pages")."""
    if prefix_cache and not paged:
        raise ValueError("prefix_cache requires paged=True (sharing lives "
                         "in the page pool)")
    if kv_dtype is not None and not paged:
        raise ValueError("kv-dtype-unsupported: kv_dtype requires "
                         "paged=True (quantized K/V lives in the page "
                         "pool; the lock-step cache is full-precision)")
    if paged:
        from apex_tpu.serving import generate_paged

        # same bounds contract as the lock-step path (max_len has no
        # paged meaning beyond validation — the pool allocates by need)
        validate_decode_bounds(prompt_ids.shape[1], max_new_tokens,
                               model.config.max_position_embeddings,
                               max_len)
        return generate_paged(
            model, variables, prompt_ids, max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=rng,
            eos_token_id=eos_token_id, axis_name=axis_name,
            num_slots=num_slots, page_size=page_size,
            prefix_cache=prefix_cache, kv_dtype=kv_dtype)
    cfg = model.config
    b, s0 = prompt_ids.shape
    t_max = validate_decode_bounds(s0, max_new_tokens,
                                   cfg.max_position_embeddings, max_len)
    rng = validate_sampling(temperature, top_k, top_p, rng)

    cache = init_cache(cfg, b, t_max)
    logits, cache = model.apply(variables, prompt_ids, cache=cache)
    cache = seal_cache(cache)  # static len -> scan-carry representation

    gen = decode_loop(
        lambda tok, c: model.apply(variables, tok[:, None], cache=c),
        logits, cache, max_new_tokens, temperature=temperature, top_k=top_k,
        top_p=top_p, rng=rng, eos_token_id=eos_token_id, axis_name=axis_name)
    return jnp.concatenate([prompt_ids.astype(jnp.int32), gen], axis=1)


# --- beam search -------------------------------------------------------------


def validate_decode_bounds(s0: int, max_new_tokens: int,
                           max_position_embeddings: int,
                           max_len=None) -> int:
    """Shared prompt/cap/buffer validation for the decode entry points;
    returns the effective cache length."""
    # decode bounds are Python ints by contract; under
    # jit(partial(generate, ...)) they concretize at trace time (static),
    # tpu-lint: disable=host-sync-in-jit -- never against a device value
    total = s0 + int(max_new_tokens)
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if total > max_position_embeddings:
        raise ValueError(
            f"prompt ({s0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_position_embeddings={max_position_embeddings}")
    t_max = total if max_len is None else int(max_len)  # tpu-lint: disable=host-sync-in-jit -- static bound, see above
    if t_max < total:
        raise ValueError(f"max_len={t_max} < prompt + max_new_tokens={total}")
    return t_max


def repeat_cache(cache, times: int):
    """Replicate every per-sequence cache row ``times`` along the leading
    dim (row layout ``[b0 x times, b1 x times, ...]``) — beam search
    prefills ONCE at batch b and fans the cache out to b*W afterwards
    instead of running W identical prompt forwards."""
    def rep(t):
        return jnp.repeat(t, times, axis=0) if hasattr(t, "ndim") \
            and t.ndim >= 1 else t

    return {"layers": [jax.tree.map(rep, lc) for lc in cache["layers"]],
            "len": cache["len"]}


def _gather_beam_cache(cache, parent, batch: int, num_beams: int):
    """Reorder every (batch*num_beams)-leading-dim cache buffer by the
    chosen parents — the beam-search analog of rollback: surviving beams
    inherit their parent's K/V (and any extras like T5's cross ck/cv)."""
    flat = (jnp.arange(batch)[:, None] * num_beams + parent).reshape(-1)
    bw = batch * num_beams

    def reorder(t):
        return t[flat] if (hasattr(t, "ndim") and t.ndim >= 1
                           and t.shape[0] == bw) else t

    return {"layers": [jax.tree.map(reorder, lc) for lc in cache["layers"]],
            "len": cache["len"]}


def _gathered_log_softmax(logits, axis_name):
    if _axis_bound(axis_name):
        logits = gather_from_tensor_model_parallel_region(logits, axis_name)
    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def beam_search_loop(step_apply, prefill_logits, cache, max_new_tokens: int,
                     *, batch: int, num_beams: int, eos_token_id=None,
                     length_penalty: float = 1.0, length_offset: int = 0,
                     axis_name: str = MODEL_AXIS):
    """Static-shape beam search over a ``(batch*num_beams)``-row cache.

    The beams FOLD INTO THE BATCH dimension, so every step is one batched
    forward (MXU-friendly) and beam reordering is a gather over the cache's
    leading dim (``_gather_beam_cache``). Scan-collected (token, parent)
    backpointers are unwound after the loop — no growing arrays anywhere.
    Finished beams extend only with EOS at zero added score. Final ranking
    divides by ``(length_offset + gen_length)^length_penalty`` where
    ``gen_length`` counts generated tokens up to and including the first
    EOS. ``length_offset`` DEFAULTS TO 0 — the normalizer is the generated
    length only, matching transformers >= 4.36 (``BeamSearchScorer``
    divides by ``cur_len + 1 - decoder_prompt_len``, i.e. prompt and
    decoder-start excluded; ADVICE r5 — the r4 full-hypothesis offset was
    pre-4.36 legacy semantics). Penalty 0 = pure sum-logprob; the offset
    knob remains for callers that want the legacy normalizer.
    Returns ``(sequences (batch, num_beams, max_new_tokens),
    scores (batch, num_beams))``, best beam first.

    ``step_apply(tokens_(batch*num_beams,), cache) -> (logits_(bw,1,V),
    cache)`` — the same contract as ``decode_loop``; ``prefill_logits``
    are the prompt logits with the prompt REPLICATED per beam (row layout
    ``[b0 x W, b1 x W, ...]``)."""
    b, w = batch, num_beams
    neg = jnp.float32(-1e30)   # -inf breaks top_k ties; large-negative safe

    logp0 = _gathered_log_softmax(prefill_logits[:, -1], axis_name)
    vocab = logp0.shape[-1]
    logp0 = logp0.reshape(b, w, vocab)
    # all beams start identical: only beam 0 may seed, else W duplicates
    seed_mask = jnp.where(jnp.arange(w)[None, :, None] == 0, 0.0, neg)
    scores, idx = lax.top_k((logp0 + seed_mask).reshape(b, w * vocab), w)
    tok = (idx % vocab).astype(jnp.int32)                    # (b, w)
    # no cache gather here: at the first expansion every beam's rows are
    # identical prefill replicas, so any reorder is a value-level no-op
    done = (tok == eos_token_id) if eos_token_id is not None \
        else jnp.zeros((b, w), bool)

    def step(carry, _):
        cache, scores, tok, done = carry
        logits, cache = step_apply(tok.reshape(b * w), cache)
        logp = _gathered_log_softmax(logits[:, 0], axis_name)
        logp = logp.reshape(b, w, vocab)
        if eos_token_id is not None:
            # finished beams: EOS-extension only, at no cost — the beam
            # persists in the pool with a frozen score
            eos_only = jnp.full((vocab,), neg).at[eos_token_id].set(0.0)
            logp = jnp.where(done[..., None], eos_only[None, None], logp)
        cand = (scores[..., None] + logp).reshape(b, w * vocab)
        scores, idx = lax.top_k(cand, w)
        tok = (idx % vocab).astype(jnp.int32)
        parent = idx // vocab
        done = jnp.take_along_axis(done, parent, axis=1)
        if eos_token_id is not None:
            done = jnp.logical_or(done, tok == eos_token_id)
        cache = _gather_beam_cache(cache, parent, b, w)
        return (cache, scores, tok, done), (tok, parent)

    if max_new_tokens > 1:
        (_, scores, _, _), (toks, parents) = lax.scan(
            step, (cache, scores, tok, done), None,
            length=max_new_tokens - 1)
    else:
        toks = jnp.zeros((0, b, w), jnp.int32)
        parents = jnp.zeros((0, b, w), jnp.int32)

    # unwind backpointers (python loop over the STATIC step count)
    seq = [None] * max_new_tokens
    beam_idx = jnp.broadcast_to(jnp.arange(w)[None], (b, w))
    for t in range(max_new_tokens - 1, 0, -1):
        seq[t] = jnp.take_along_axis(toks[t - 1], beam_idx, axis=1)
        beam_idx = jnp.take_along_axis(parents[t - 1], beam_idx, axis=1)
    seq[0] = jnp.take_along_axis(tok, beam_idx, axis=1)
    seqs = jnp.stack(seq, axis=-1)                           # (b, w, T)

    if eos_token_id is not None and length_penalty:
        is_eos = seqs == eos_token_id
        # length incl. the first EOS; max_new_tokens when none
        first_eos = jnp.argmax(is_eos, axis=-1) + 1
        lengths = jnp.where(is_eos.any(axis=-1), first_eos, max_new_tokens)
    else:
        lengths = jnp.full((b, w), max_new_tokens)
    lengths = lengths + length_offset  # 0 by default: generated-only (HF)
    final = scores / (lengths.astype(jnp.float32) ** jnp.float32(
        length_penalty))
    order = jnp.argsort(-final, axis=1)
    seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
    return seqs, jnp.take_along_axis(final, order, axis=1)


def generate_beam(model, variables, prompt_ids, max_new_tokens: int, *,
                  num_beams: int, eos_token_id=None,
                  length_penalty: float = 1.0, max_len=None,
                  axis_name: str = MODEL_AXIS):
    """Beam-search decoding for the decoder-only families: replicate the
    prompt per beam, prefill once, run ``beam_search_loop``. Returns
    ``(sequences (b, num_beams, prompt+max_new), scores (b, num_beams))``,
    best beam first (prompt included in the sequences)."""
    cfg = model.config
    b, s0 = prompt_ids.shape
    if num_beams < 1:
        raise ValueError("num_beams must be >= 1")
    t_max = validate_decode_bounds(s0, max_new_tokens,
                                   cfg.max_position_embeddings, max_len)

    # prefill ONCE at batch b; the beams only diverge after the first
    # expansion, so the cache/logits fan out by replication
    cache = init_cache(cfg, b, t_max)
    logits, cache = model.apply(variables, prompt_ids, cache=cache)
    cache = seal_cache(repeat_cache(cache, num_beams))
    logits = jnp.repeat(logits[:, -1:], num_beams, axis=0)   # (b*w, 1, V)
    seqs, scores = beam_search_loop(
        lambda tok, c: model.apply(variables, tok[:, None], cache=c),
        logits, cache, max_new_tokens, batch=b, num_beams=num_beams,
        eos_token_id=eos_token_id, length_penalty=length_penalty,
        axis_name=axis_name)
    prompt_rep = jnp.broadcast_to(prompt_ids[:, None].astype(jnp.int32),
                                  (b, num_beams, s0))
    return jnp.concatenate([prompt_rep, seqs], axis=-1), scores


# --- speculative decoding ----------------------------------------------------


def rollback_cache(cache, new_len):
    """Rewind a cache to ``new_len`` tokens. O(1): entries past the length
    are already invisible to ``cached_attention``'s absolute-position mask
    and will be overwritten by the next chunk write — rejection rollback is
    just the scalar assignment. (The static-buffer design's payoff.)"""
    return dict(cache, len=new_len)


# module-level jits so the compiled draft/verify programs are shared across
# speculative_generate calls (a per-call closure would re-trace every
# request and bake the weights in as constants)
@functools.partial(jax.jit, static_argnames=("model", "k", "axis_name"))
def _spec_draft_propose(model, variables, dc, first_tok, *, k, axis_name):
    """k draft steps from first_tok: returns (cache at +k tokens, proposals
    d_1..d_{k-1}); the k-th step only advances the draft cache so a
    fully-accepted round leaves it consistent."""
    def one(carry, _):
        dc, tok = carry
        lg, dc = model.apply(variables, tok[:, None], cache=dc)
        return (dc, _greedy_token(lg[:, 0], axis_name)), tok
    (dc, _), toks = lax.scan(one, (dc, first_tok), None, length=k)
    return dc, toks[1:].T                          # (b, k-1) proposals


@functools.partial(jax.jit, static_argnames=("model", "axis_name"))
def _spec_verify(model, variables, tc, chunk, *, axis_name):
    """Target forward on the (b, k) chunk [x_t, d_1..d_{k-1}]: argmax
    predictions for positions t+1..t+k."""
    lg, tc = model.apply(variables, chunk, cache=tc)
    return tc, _greedy_token(lg, axis_name)        # (b, k) argmax tokens


def speculative_generate(model, variables, draft_model, draft_variables,
                         prompt_ids, max_new_tokens: int, *, k: int = 4,
                         axis_name: str = MODEL_AXIS):
    """Greedy speculative decoding: a cheap DRAFT model proposes ``k - 1``
    tokens per round; the target verifies them in ONE ``k``-token chunk
    (an MXU-friendly matmul instead of ``k`` sequential s=1 steps) and
    accepts the longest prefix matching its own argmax. Rejected positions
    roll both caches back (``rollback_cache``) — output is EXACTLY the
    target's greedy decode, for any draft model; the draft only changes
    how many target steps are saved. (Exactness assumes the s=k verify
    forward and the s=1 decode forward agree numerically — guaranteed in
    fp32; under bf16 XLA may tile the two shapes differently, so a
    near-tied argmax can flip and the output is then "target greedy under
    chunked evaluation" rather than bitwise-equal to ``generate``.)

    Batched rows accept the minimum match count across the batch (the
    per-round bonus token — the target's own argmax after the accepted
    prefix — keeps every round's progress >= 1 token/row). Host loop over
    rounds (the accept count is data-dependent); the per-round programs
    are shape-stable, so each jits once. Greedy only; EOS rows are not
    early-stopped (slice the output yourself)."""
    cfg = model.config
    b, s0 = prompt_ids.shape
    total = s0 + int(max_new_tokens)
    if k < 2:
        raise ValueError("k must be >= 2 (k-1 draft proposals per round)")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    for c in (cfg, draft_model.config):
        # + k: the last round's verification chunk may SPAN positions past
        # the final token before rollback discards them — a chunk crossing
        # the position table's end would make dynamic_slice clamp the
        # whole chunk's positions (corrupting kept tokens too)
        if total + k > c.max_position_embeddings:
            raise ValueError(
                f"prompt ({s0}) + max_new_tokens ({max_new_tokens}) + "
                f"k ({k}) speculative slack exceeds "
                f"max_position_embeddings={c.max_position_embeddings}")

    # + k slack: a round's verification chunk may write up to k tokens past
    # the final accepted position before the rollback discards them
    t_cache = init_cache(cfg, b, total + k)
    d_cache = init_cache(draft_model.config, b, total + k)
    logits, t_cache = model.apply(variables, prompt_ids, cache=t_cache)
    _, d_cache = draft_model.apply(draft_variables, prompt_ids, cache=d_cache)
    t_cache, d_cache = seal_cache(t_cache), seal_cache(d_cache)

    produced = []
    n_out = 0
    next_tok = _greedy_token(logits[:, -1], axis_name)  # guaranteed correct
    while n_out < max_new_tokens:
        x_t = next_tok
        d_cache, props = _spec_draft_propose(
            draft_model, draft_variables, d_cache, x_t, k=k,
            axis_name=axis_name)
        chunk = jnp.concatenate([x_t[:, None], props], axis=1)
        t_cache, preds = _spec_verify(model, variables, t_cache, chunk,
                                      axis_name=axis_name)
        # leading matches of proposals vs target argmax, min over rows
        # (host sync: the accept count steers the Python loop)
        match = (props == preds[:, :-1]).astype(jnp.int32)   # (b, k-1)
        m = int(jnp.min(jnp.sum(jnp.cumprod(match, axis=1), axis=1)))
        produced.append(jnp.concatenate([x_t[:, None], props[:, :m]], axis=1))
        n_out += m + 1
        new_len = t_cache["len"] - (k - (m + 1))   # back to t + 1 + m tokens
        t_cache = rollback_cache(t_cache, new_len)
        d_cache = rollback_cache(d_cache, new_len)
        # the target's own argmax after the accepted prefix is both the
        # round's bonus guarantee and the next round's first token
        next_tok = preds[:, m]
    gen = jnp.concatenate(produced, axis=1)[:, :max_new_tokens]
    return jnp.concatenate([prompt_ids.astype(jnp.int32), gen], axis=1)
