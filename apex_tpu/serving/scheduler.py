"""Continuous-batching decode engine over the paged KV pool.

Iteration-level scheduling (Orca, Yu et al. 2022): a fixed array of
``num_slots`` decode slots advances one token per engine step in a SINGLE
jitted program; at step boundaries the host retires finished slots (EOS or
token budget — their pages return to the free stack immediately) and
admits queued requests into the vacancies. Short requests therefore never
pad to the batch's longest, and a drained slot is re-filled instead of
idling until the batch ends — the two wastes of lock-step ``generate``.

Static shapes throughout: admission PREFILLS through the models' existing
contiguous flash path at a page-size-rounded prompt bucket (one compile
per bucket, reused forever), scatters the resulting K/V into the slot's
pages, and the decode step is one program at one shape. Inside the step
scan the carry holds per-slot (token, EOS-done mask, remaining-token
count) — a finished slot keeps emitting EOS at its frozen state until the
host syncs, exactly like ``decode_loop``'s EOS rows, so ``sync_every > 1``
trades host syncs for (bounded) post-finish padding steps.

Sampling reuses ``models/generation``'s helpers. Greedy decode is
token-identical to per-request lock-step ``generate``; sampled decode
derives each request's key stream from ``fold_in(rng, request_index)`` so
outputs are SCHEDULING-INVARIANT (they depend on the request and the key,
not on which slot or step the request landed in — stronger than lock-step,
whose draws change with batch composition).

The host scheduling loop itself lives in ``serving/frontend.py``
(:class:`~apex_tpu.serving.frontend.ServingFrontend`): streaming ingest,
priority/deadline admission (``serving/policy.py``), page-spilling
preemption, and a pump that overlaps host-side retirement/admission work
with the next jitted decode chunk. :meth:`PagedDecodeEngine.run` is a
thin closed-loop wrapper over that frontend — this module owns the
engine STATE (pool, prefix cache, compiled admit/step programs,
observability identity) the frontend drives.

Every program is compiled through two overridable seams —
``_make_cache`` (pool allocation) and ``_compile`` (role-tagged jit),
beside a third that lays out the weights (``_lay_out_experts``) —
which is how ``serving/tp.py``'s
:class:`~apex_tpu.serving.tp.TensorParallelPagedEngine` runs the SAME
scheduler over a tensor-parallel mesh: head-sharded pool, shard_mapped
programs, replicated scheduling state (docs/tp_serving.md).

``prefix_cache=True`` adds cross-request KV reuse (RadixAttention, Zheng
et al. 2023; ``serving/prefix_cache.py``): admission walks a radix tree
of cached full pages, points the slot's block table at the matched pages
(refcounted, read-only) and prefills only the uncached tail through
``make_shared_admit``; retirement moves the request's full-page prefix
into the tree instead of the free stack, and the stack is replenished by
LRU eviction of refcount-0 cached pages on demand. Greedy outputs stay
token-identical to the cache-off engine: the shared pages replay
bitwise-stored K/V, never re-derived. (The re-prefilled TAIL of a hit
rides dense cached attention where the cold path rides the flash kernel
— exact in fp32; under bf16 the two summation orders can differ in low
bits, so a near-tied argmax could flip, the same caveat as
``speculative_generate``'s chunked-verify exactness note.)
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import itertools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.mesh import MODEL_AXIS
from apex_tpu.models.generation import (_greedy_token, _sample_token,
                                        init_cache, validate_sampling)
from apex_tpu.obs.events import EventLog
from apex_tpu.obs.spans import SpanTracer
from apex_tpu.ops._dispatch import round_up
from apex_tpu.ops.paged_write import unpack_heads
from apex_tpu.ops.quant import resolve_kv_dtype
from apex_tpu.serving import kv_pool
from apex_tpu.serving.host_tier import HostPageTier
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.transformer.moe.dropless import (ROUTING_COLLECTION,
                                               SHARE_ROUTING_STATS,
                                               expert_rows, lay_out_experts)

#: run() counters in the instrument registry (``serving.<name>``); the
#: per-run stats dict is the DELTA of these across the run — the registry
#: is the state of record, the dict a derived view
_RUN_COUNTERS = ("admitted", "retired", "decode_steps", "busy_slot_steps",
                 "prefix_hits", "prefill_tokens_total",
                 "prefill_tokens_computed", "evicted_pages",
                 "deferred_admissions", "defrag_runs",
                 "preemptions", "resumes", "backpressure_spills",
                 "deadline_misses",
                 "tpot_slo_misses", "window_dropped_pages",
                 "spec_rounds", "spec_tokens", "chunked_prefills",
                 "prefill_chunks",
                 # the pump's own account, as counters of host seconds
                 # (docs/frontend.md "Measuring the pump": the iterations'
                 # host work and its four top-level phases, the waits, the
                 # bubbles), the two halves of TTFT summed over requests,
                 # and the K/V bytes the decode steps' attention must read
                 # and the bytes the kernel's page blocks move for them.
                 # Every counter that describes a decode chunk (the steps,
                 # the bytes, the routing below) is added when the chunk is
                 # HARVESTED: they all count the same completed chunks
                 "pump_iterations", "pump_host_seconds",
                 "pump_dispatch_seconds", "pump_harvest_seconds",
                 "pump_housekeeping_seconds",
                 "pump_admission_seconds", "pump_blocked_seconds",
                 "pump_bubble_seconds", "queue_wait_seconds",
                 "first_token_wait_seconds", "kv_bytes_attended",
                 "kv_bytes_fetched",
                 # kv_bytes_attended split by kind of layer (full /
                 # windowed: kv_pool.layer_groups), and per chunk the
                 # bytes of the pages the decoding slots own in all groups
                 # x sync_every beside the sum of their context lengths x
                 # sync_every: their ratio is what a live token costs
                 "kv_full_bytes_attended", "kv_window_bytes_attended",
                 "kv_bytes_held_steps", "context_token_steps",
                 # what the decode steps' routing did (models with routed
                 # experts; ``transformer/moe/dropless.ROUTING_STATS``, in
                 # its order, summed over expert layers and decode steps,
                 # every row of a step counted, idle slots too) and the
                 # expert weights the hit experts made the steps read
                 "expert_pairs_routed", "experts_hit", "expert_load_max",
                 "expert_bytes_read",
                 # a chip that holds a SHARE of a layer's experts counts the
                 # four above over the held ones, and here the routed pairs
                 # whose expert lies on another chip; and per chunk the
                 # bytes the state groups' layers (kv_pool.layer_groups: one
                 # recurrent state a slot) read and write for the decoding
                 # slots x sync_every
                 "expert_pairs_elsewhere", "state_bytes_moved",
                 # seconds the process spent in Python's collector while
                 # the background pump ran (any thread's collection holds
                 # the interpreter, the pump's included)
                 "gc_pause_seconds")

#: per-request latency histograms (``serving.<name>``, log-bucketed ms)
_RUN_HISTOGRAMS = ("ttft_ms", "tpot_ms", "queue_wait_ms", "decode_step_ms")

#: per-process engine ids, the ``engine`` label on run counters
_ENGINE_IDS = itertools.count()


@dataclasses.dataclass
class Request:
    """One decode request: a 1-D int32 prompt and its token budget, plus
    the serving front-end's optional scheduling fields (defaults keep
    every pre-frontend call site constructing unchanged).

    - ``priority``: scheduling class, larger int = more important. The
      front-end serves the pending queue highest-priority first and may
      preempt a strictly-lower-priority RUNNING request for a blocked
      higher-priority one (``serving/policy.py``). 0 (the default) is
      plain FIFO traffic.
    - ``deadline_ms``: a TTFT service-level objective — the request
      should see its first token within ``deadline_ms`` of its arrival.
      Breaks ties inside a priority class (earliest deadline first) and
      arms preemption when the request would otherwise sit blocked past
      it. Missing the deadline never drops the request; misses are
      counted (``serving.deadline_misses``). None = no SLO.
    - ``arrival_time``: when the request entered the system, in the
      monotonic ``time.perf_counter`` timebase (NOT wall clock) so
      deadlines survive clock steps. None = stamped at ``submit()``;
      trace replays pass explicit values.
    - ``tpot_slo_ms``: a steady-state time-per-output-token SLO. Checked
      once at retirement against the request's lifecycle TPOT
      (docs/observability.md); a miss increments
      ``serving.tpot_slo_misses`` and feeds the rolling
      ``serving.slo_burn`` gauge — the request is never truncated.
      None = no TPOT SLO.
    - ``trace_id``: process-independent trace identity (32 lowercase hex
      chars, the ``traceparent`` trace-id field). Minted router-side for
      routed requests (``obs/fleet.py``), carried over HTTP as a
      ``traceparent`` header, and tagged onto the tracer's ``enqueue``
      span on every replica that ever holds the request — the key
      ``stitch_traces()`` merges failed-over span fragments on. None =
      untraced (single-process callers lose nothing).
    """

    prompt: Any                      # (s0,) int array
    max_new_tokens: int
    priority: int = 0
    deadline_ms: Optional[float] = None
    arrival_time: Optional[float] = None
    tpot_slo_ms: Optional[float] = None
    trace_id: Optional[str] = None


def _donate_cache():
    # buffer donation keeps the page pool in place across step/admit calls
    # on TPU; the CPU backend has no donation and would warn every call
    return (0,) if jax.default_backend() == "tpu" else ()


def prompt_bucket(s0: int, page_size: int, max_positions: int) -> int:
    """The admission compile-key bucket for a raw prompt length: pad up
    to a whole page (capped at the position table) so one program serves
    every length in the page — the compile-count contract the IR tier's
    ``gpt2s_engine_admit_bucketed`` case traces at two same-bucket
    lengths (``ir-compile-key-cardinality``). Admission and the lint
    harness MUST share this function: the contract is only binding on
    the engine if the engine's own bucketing is what gets traced."""
    return min(round_up(max(s0, 1), page_size), max_positions)


def _logits_at(model, variables, ids, cache, last):
    """``(logits [1, V] at chunk position ``last``, cache)`` of one
    admission forward. An admit program reads ONE position's logits; a
    model whose ``__call__`` takes ``logits_positions`` runs its head there
    alone (at a 150k vocabulary the logits of a 16k-token prompt are 4.7 GB
    that nothing reads), any other computes them all and is sliced, as
    before."""
    params = inspect.signature(type(model).__call__).parameters
    if "logits_positions" in params:
        # a model whose layers keep a recurrent state is also told where
        # the prompt ends inside its page bucket: padding must not reach
        # the state (attention masks it; a recurrence does not)
        extra = {"prompt_lengths": jnp.reshape(last + 1, (1,))} \
            if "prompt_lengths" in params else {}
        logits, cache = model.apply(
            variables, ids, cache=cache,
            logits_positions=jnp.reshape(last, (1, 1)), **extra)
        return logits[:, 0], cache
    logits, cache = model.apply(variables, ids, cache=cache)
    return lax.dynamic_slice_in_dim(logits, last, 1, axis=1)[:, 0], cache


def _bucket_match_pages(m: int) -> int:
    """Round a radix match depth DOWN to a power of two pages. Retirement
    inserts prompts AND generated tokens, so raw match depths take many
    distinct values — and every distinct ``t_start`` is a fresh
    shared-admit XLA compile stalling the admission loop. The power-of-two
    floor bounds the compile-key set at ``log2(max_pages)`` per tail
    bucket, at the cost of re-prefilling at most half the matched pages
    (none at all for power-of-two-page shared headers, the common case)."""
    return 1 << (m.bit_length() - 1) if m > 0 else 0


def make_shared_admit(model, *, t_start: int, tail_bucket: int,
                      first_token=None, axis_name: str = MODEL_AXIS):
    """Build the shared-prefix admission program (one compile per
    ``(t_start, tail_bucket)`` pair, cached by the engine; also the
    ``tpu_aot.py`` sweep's prefix-cached decode case).

    The matched prefix (``t_start`` tokens = ``t_start/page_size`` whole
    cached pages) is GATHERED from the pool into a contiguous buffer, and
    the model forward runs over ONLY the ``tail_bucket``-padded uncached
    tail with the buffer as its KV cache at static length ``t_start`` —
    the tail attends over the shared prefix through the models' existing
    cached path, but the prefix contributes zero forward FLOPs. The tail's
    K/V then scatters into the slot's private pages
    (``prefill_into_pages(start=t_start)`` — shared pages are never
    written: copy-on-write at page granularity, the partially-filled
    boundary page is always private) and the first token samples from the
    prompt-final logits.

    Returns ``admit(cache, variables, tail_ids, s0, slot, shared_row,
    n_private, req_key, samp0=0) -> (cache, tok0)`` where ``shared_row``
    is a ``(max_pages,)`` int32 row whose first ``t_start/page_size``
    entries are the matched physical pages and ``samp0`` is the sampled
    first token's index in the request's key stream (nonzero only for a
    preemption resume, which continues the stream where the preempted
    segment stopped — scheduling invariance holds across preemption)."""
    cfg = model.config
    if t_start < 1 or tail_bucket < 1:
        raise ValueError("shared admission needs t_start >= 1 matched "
                         "tokens and tail_bucket >= 1 tail tokens")
    if first_token is None:
        def first_token(last, _key, _samp0=0):
            return _greedy_token(last, axis_name)
    bucket = t_start + tail_bucket

    def admit(cache, variables, tail_ids, s0, slot, shared_row, n_private,
              req_key, samp0=0):
        ps = kv_pool.page_size_of(cache)
        if t_start % ps:
            raise ValueError(f"t_start={t_start} must be a page multiple "
                             f"({ps})")
        m = t_start // ps
        contig = init_cache(cfg, 1, bucket)
        layers = []
        for pool_lc, lc in zip(cache["layers"], contig["layers"]):
            def gathered(pages, dst, scales=None):
                # (m, heads // pack, ps, d * pack) page tiles, one head a
                # row again (the pool's pack, off the two shapes) -> the
                # buffer's leading t_start positions; a quantized pool
                # dequantizes by its gathered per-(page, kv_head) scales
                # on the way out
                pages = unpack_heads(pages,
                                     pages.shape[3] // dst.shape[3])
                kv, d = pages.shape[1], pages.shape[3]
                if scales is not None:
                    pages = pages.astype(jnp.float32) * \
                        scales[:, :, None, None]
                block = pages.transpose(1, 0, 2, 3).reshape(
                    1, kv, t_start, d)
                return dst.at[:, :, :t_start, :].set(
                    block.astype(dst.dtype))
            # every tensor the layout stores (k and v, or one latent
            # entry), each from its own pool
            layers.append({
                name: gathered(
                    pool_lc[kv_pool.pool_key(name)][shared_row[:m]],
                    lc[name],
                    pool_lc[kv_pool.scale_key(name)][shared_row[:m]]
                    if kv_pool.scale_key(name) in pool_lc else None)
                for name in lc})
        # static len t_start: the tail chunk is a chunked continuation —
        # bounds check at trace time, dense cached attention over the
        # buffer (the flash path needs len 0, which the prefix occupies)
        contig = {"layers": layers, "len": t_start}
        last, contig = _logits_at(model, variables, tail_ids, contig,
                                  s0 - t_start - 1)
        cache = kv_pool.alloc_slot_shared(cache, slot, shared_row, m,
                                          n_private)
        cache = kv_pool.prefill_into_pages(cache, slot, contig["layers"],
                                           s0, start=t_start)
        tok0 = first_token(last, req_key, samp0)[0]
        return cache, tok0

    return admit


def make_prefill_chunk(model, *, chunk: int, first_token=None,
                       axis_name: str = MODEL_AXIS):
    """Build the chunked-prefill step program (one compile per engine;
    also the ``tpu_aot.py`` sweep's chunked-prefill case).

    One call pushes the next ``chunk`` prompt tokens of ONE slot through
    the model's PAGED s>1 path: a slot view (the shared pools plus the
    slot's own block-table row and length) rides ``model.apply`` exactly
    like a decode step, so the chunk's K/V lands directly in the slot's
    pages — no contiguous staging buffer, no scatter — and the per-query
    causal band (``len - s + i``) keeps position ``i`` from seeing
    positions beyond itself inside the chunk. The final chunk of a
    prompt is zero-padded to ``chunk`` tokens; padding rows write
    garbage K/V at positions >= the true length, which the length
    update below never exposes (the causal band reads strictly below
    ``len``, and the next chunk or first decode step overwrites them).

    Returns ``prefill_step(cache, variables, ids, slot, valid, req_key,
    samp0) -> (cache, tok0)``: ``ids`` is ``(1, chunk)``, ``valid`` the
    chunk's true token count, and ``tok0`` the first-token sample off
    logit ``valid - 1`` — meaningful only on the prompt's final chunk
    (earlier chunks' tok0 is discarded by the frontend)."""
    if chunk < 1:
        raise ValueError("prefill chunk must be >= 1 token")
    if first_token is None:
        def first_token(last, _key, _samp0=0):
            return _greedy_token(last, axis_name)

    def prefill_step(cache, variables, ids, slot, valid, req_key, samp0):
        view = {
            "layers": cache["layers"],
            "block_tables": lax.dynamic_slice_in_dim(
                cache["block_tables"], slot, 1, axis=0),
            "len": lax.dynamic_slice_in_dim(cache["len"], slot, 1, axis=0),
        }
        logits, view = model.apply(variables, ids, cache=view)
        # advance by the TRUE token count, not the padded chunk width:
        # padded positions stay above len and are never read
        cache = dict(cache, layers=view["layers"],
                     len=cache["len"].at[slot].add(valid))
        last = lax.dynamic_slice_in_dim(logits, valid - 1, 1, axis=1)[:, 0]
        tok0 = first_token(last, req_key, samp0)[0]
        return cache, tok0

    return prefill_step


class PagedDecodeEngine:
    """Continuous-batching greedy/sampled decode over ``num_slots`` slots.

    ``run(requests)`` processes the whole queue and returns
    ``(outputs, stats)`` where ``outputs[i]`` is request ``i``'s generated
    tokens (up to and including its first EOS) and ``stats`` counts engine
    decode steps — the serving cost driver lock-step padding inflates.

    The engine takes ownership of ``variables`` (and ``draft_variables``):
    an expert stack that tiles better wider is laid out in place, zero-
    padded, into the collection ``served_experts`` (docs/serving.md
    "Routed experts' served layout"); the tree then serves the same
    function through any model call, and no longer holds the published
    stack.
    """

    def __init__(self, model, variables, *, num_slots: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_pages_per_seq: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, rng=None,
                 sync_every: int = 1, axis_name: str = MODEL_AXIS,
                 prefix_cache: bool = False,
                 draft_model=None, draft_variables=None, draft_len: int = 0,
                 prefill_chunk: Optional[int] = None, kv_dtype=None,
                 draft_kv_dtype="match",
                 host_tier_bytes: Optional[int] = None):
        cfg = model.config
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        # quantized KV pages (docs/serving.md "Quantized KV pages"):
        # resolve eagerly so an unsupported kv_dtype raises a NAMED
        # ValueError here — never a silent full-precision fallback
        resolve_kv_dtype(kv_dtype)
        self.kv_dtype = kv_dtype
        # the draft pool mirrors the target pool page-for-page AND
        # dtype-for-dtype: one capacity/cost story covers both pools, so
        # a divergent draft dtype is a named config error, not a knob
        if draft_kv_dtype == "match":
            draft_kv_dtype = kv_dtype
        if draft_len > 0 and draft_kv_dtype != kv_dtype:
            raise ValueError(
                f"kv-dtype-mismatch: the speculative draft pool must "
                f"share the target pool's kv_dtype (target "
                f"{kv_dtype!r}, draft {draft_kv_dtype!r}) — the pools "
                f"mirror each other slot-for-slot and page-for-page")
        self.model = model
        self.variables = variables
        self.cfg = cfg
        self.num_slots = num_slots
        self.page_size = page_size
        self.eos_token_id = eos_token_id
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.rng = validate_sampling(temperature, top_k, top_p, rng)
        self.sync_every = sync_every
        self.axis_name = axis_name
        # what the layers hold, by kind (kv_pool.layer_groups). A model
        # whose layers are all alike is ONE group; where that group has a
        # window (``self.window``), the paged kernel bands attention to it
        # and the frontend drops pages below the band at sync boundaries
        # (kv_pool.drop_slot_pages) — O(window) live pages per slot.
        # CONTRACT: a config EXPOSING ``sliding_window`` (or per-layer
        # ``layer_windows``) promises its model's paged branch passes
        # ``window=`` to ``paged_attention`` — the drop frees pages the
        # band can no longer read, so an unbanded paged path under that
        # attribute would read freed null pages. A model that MIXES
        # windowed and full layers holds each windowed group as per-slot
        # rings of pages (``self.ring_layers``), written over in place by
        # the decode steps: nothing to drop. Either way a page of a
        # windowed group is freed or overwritten while its request runs,
        # so it cannot be shared cache property: the radix prefix cache,
        # speculation and chunked prefill are each refused below for ANY
        # windowed group, by the group's name.
        self.groups = kv_pool.layer_groups(cfg)
        self.window = (self.groups[0].window if len(self.groups) == 1
                       else None)
        self.ring_layers = tuple(i for g in self.groups if g.ring
                                 for i in g.layers)
        windowed = "; ".join(
            f"layers {list(g.layers)} read a window of {g.window}"
            for g in self.groups if g.window is not None)
        # a STATE group (layers that keep one recurrent state a slot and no
        # token) is overwritten whole by an admission and updated in place
        # by every decode step. What that rules out, each refused here by
        # the group's name: sharing a prefix's pages (the state at the
        # prefix's end is not kept), speculation (a rejected block cannot
        # be rolled back out of a state), chunked prefill (the state would
        # be handed from chunk to chunk through the paged s > 1 path) and
        # the host tier; quantized pages and a tensor-parallel mesh are
        # refused where the pool is made (kv_pool.init_paged_cache)
        stateful = "; ".join(
            f"layers {list(g.layers)} keep "
            f"{', '.join(t.name for t in g.state)} a slot"
            for g in self.groups if g.state)
        if stateful:
            for what, asked, why in (
                    ("prefix_cache", prefix_cache,
                     "the radix cache keys on pages, and the state at a "
                     "prefix's end is not kept"),
                    ("speculative decode (draft_len)", draft_len > 0,
                     "a rejected draft block cannot be rolled back out "
                     "of a state"),
                    ("prefill_chunk", prefill_chunk is not None,
                     "the state would be handed from chunk to chunk "
                     "through the paged s > 1 path"),
                    ("host_tier_bytes", bool(host_tier_bytes),
                     "the host tier files pages under the radix cache's "
                     "nodes")):
                if asked:
                    raise kv_pool.StateGroupUnsupported(
                        what, f"{why} ({stateful})")
        if prefix_cache and windowed:
            raise ValueError(
                f"prefix_cache does not compose with sliding-window "
                f"layers ({windowed}): the engine drops or overwrites a "
                f"windowed group's pages once they fall below the "
                f"attention band, and such a page cannot be shared "
                f"radix-cache property (decode windowed models with "
                f"prefix_cache=False)")
        # in-engine speculative decode (docs/serving.md): every engine
        # step drafts ``draft_len`` tokens per slot through a small draft
        # model's own paged pool and verifies the block in ONE
        # s = draft_len + 1 paged target step — the s>1 kernel
        # generalization is what makes the verify a single program at one
        # shape. Acceptance is per-slot (continuous batching never stalls
        # a slot on its neighbours' rejections, unlike lock-step
        # ``speculative_generate``'s min-over-batch).
        self.draft_model = draft_model
        self.draft_variables = draft_variables
        self.draft_len = draft_len
        self.prefill_chunk = prefill_chunk
        if draft_len < 0:
            raise ValueError("draft_len must be >= 0")
        if draft_len > 0:
            if draft_model is None:
                raise ValueError(
                    "draft_len > 0 needs a draft_model (and its "
                    "draft_variables) to propose tokens")
            if temperature:
                raise ValueError(
                    "in-engine speculative decode is greedy-only: "
                    "acceptance compares draft proposals against the "
                    "target's greedy predictions (set temperature=0)")
            if prefix_cache:
                raise ValueError(
                    "speculative decode does not compose with "
                    "prefix_cache yet: shared pages would need a second "
                    "refcounted draft-pool mirror (run one or the other)")
            if any(g.state
                   for g in kv_pool.layer_groups(draft_model.config)):
                raise kv_pool.StateGroupUnsupported(
                    "a draft model for speculative decode",
                    "a rejected draft block cannot be rolled back out of "
                    "the draft's state")
            if windowed or any(
                    g.window is not None
                    for g in kv_pool.layer_groups(draft_model.config)):
                raise ValueError(
                    f"speculative decode does not support sliding-window "
                    f"layers ({windowed or 'the draft model has them'}): "
                    f"the pages below the band are dropped or written "
                    f"over, and a rejected draft block could not be "
                    f"rolled back over them (use a full-attention target "
                    f"and draft)")
            if prefill_chunk is not None:
                raise ValueError(
                    "speculative decode and chunked prefill are mutually "
                    "exclusive engine modes for now (pick one)")
            if draft_len + 1 > page_size:
                raise ValueError(
                    f"draft_len + 1 = {draft_len + 1} exceeds the paged "
                    f"kernel's query-block limit page_size={page_size}")
        # chunked prefill (Sarathi-style): admission feeds long prompts
        # through the PAGED path in fixed ``prefill_chunk``-token pieces
        # interleaved with decode chunks, so a long prompt never
        # monopolizes the device between two decode steps (TTFT tail)
        if prefill_chunk is not None:
            if not 1 <= prefill_chunk <= page_size:
                raise ValueError(
                    f"prefill_chunk must be in 1..page_size ({page_size}), "
                    f"got {prefill_chunk}: chunks ride the paged kernel's "
                    f"query block, which is capped at one page")
            if windowed:
                raise ValueError(
                    f"chunked prefill does not support sliding-window "
                    f"layers yet ({windowed}): in-progress chunks hold "
                    f"positions the window-page dropper would free, or a "
                    f"ring would write over, mid-prefill (use monolithic "
                    f"admission for windowed models)")
        if max_pages_per_seq is None:
            max_pages_per_seq = kv_pool.cdiv(cfg.max_position_embeddings,
                                             page_size)
        if num_pages is None:
            # worst case: every slot holds a max-length sequence (+ null)
            num_pages = 1 + num_slots * max_pages_per_seq
        # before the pool: the layout holds one matrix twice at a time
        self.expert_layout = self._lay_out_experts(variables)
        self._lay_out_experts(draft_variables)
        self.cache = self._make_cache(num_slots, num_pages, page_size,
                                      max_pages_per_seq)
        # the draft pool mirrors the target pool's geometry slot-for-slot
        # and page-for-page: one allocation decision covers both
        self.draft_cache = (self._make_cache(num_slots, num_pages,
                                             page_size, max_pages_per_seq,
                                             config=draft_model.config)
                            if draft_len > 0 else None)
        # observability (docs/observability.md): a bounded postmortem
        # event ring for the engine's lifetime, and the last run's span
        # tracer (fresh per run; run(tracer=...) injects one). Every
        # serving/pool/prefix instrument carries this ``engine`` label
        # so concurrent engines in one process never mix each other's
        # increments, distributions, or pool-health levels
        self.events = EventLog(capacity=4096)
        self.tracer: Optional[SpanTracer] = None
        self.obs_labels = {"engine": str(next(_ENGINE_IDS))}
        # cross-request KV reuse: the host radix tree naming cached pages
        self.prefix = (PrefixCache(page_size,
                                   metrics_labels=self.obs_labels)
                       if prefix_cache else None)
        # tiered pool (docs/serving.md "Tiered KV pool"): a host-RAM
        # byte-budgeted LRU under the device pool — evicted radix pages
        # demote (gather -> host) instead of dropping, and a later hit
        # promotes into fresh pages instead of re-prefilling. Keyed by
        # radix-node identity, so it REQUIRES the prefix cache: without
        # the tree there is no name to file a demoted page under.
        if host_tier_bytes is not None and host_tier_bytes > 0:
            if self.prefix is None:
                raise ValueError(
                    "host_tier_bytes requires prefix_cache=True: the "
                    "tier files demoted pages under radix-node identity "
                    "(their token path), which only the prefix cache "
                    "names")
            self.host_tier = HostPageTier(host_tier_bytes,
                                          page_size=page_size,
                                          metrics_labels=self.obs_labels)
        else:
            self.host_tier = None
        self._admit_jit = {}             # prompt bucket -> compiled admit
        self._shared_admit_jit = {}      # (t_start, tail_bucket) -> admit
        self._spec_admit_jit = {}        # prompt bucket -> spec admit
        self._step_jit = None
        self._spec_step_jit = None
        self._chunk_jit = None
        donate = _donate_cache()
        self._free_jit = self._compile(
            kv_pool.free_slot, ("cache", "rep"), ("cache",), donate)
        self._release_jit = self._compile(
            kv_pool.release_slot, ("cache", "rep", "rep"), ("cache",),
            donate)
        self._evict_jit = self._compile(
            kv_pool.evict_pages, ("cache", "rep", "rep"), ("cache",),
            donate)
        defrag_map = kv_pool.defrag_map
        if self.ring_layers:
            def defrag_map(cache, extra_live):
                return kv_pool.defrag_map(cache, extra_live,
                                          rings=self.ring_layers)
        self._defrag_jit = self._compile(
            defrag_map, ("cache", "rep"), ("cache", "rep"), donate)
        self._drop_jit = self._compile(
            kv_pool.drop_slot_pages, ("cache", "rep", "rep"), ("cache",),
            donate)
        if self.host_tier is not None:
            # the tiered pool's two device programs, each ONE compile:
            # demote depth and promote depth are DATA (a null-padded
            # HOST_COPY_CHUNK page row + a traced count), never a compile
            # key. The gather is a pure READ — donating the cache to it
            # would free the pool out from under the engine.
            self._gather_jit = self._compile(
                kv_pool.gather_pages, ("cache", "rep"), ("tiles",))
            self._promote_jit = self._compile(
                kv_pool.promote_pages, ("cache", "rep", "rep", "tiles"),
                ("cache",), donate)
        if draft_len > 0:
            # draft-pool mirrors of the maintenance programs, compiled
            # through the same seam under the draft roles so TP shards
            # them with the DRAFT config's head count
            self._draft_free_jit = self._compile(
                kv_pool.free_slot, ("draft_cache", "rep"),
                ("draft_cache",), donate)
            self._draft_defrag_jit = self._compile(
                kv_pool.defrag_map, ("draft_cache", "rep"),
                ("draft_cache", "rep"), donate)
        if prefill_chunk is not None:
            # chunked admission allocates the slot's pages up front (the
            # whole-prompt page need is known) but starts at len 0 —
            # chunks advance len as they land; alloc_slot itself never
            # touches len, so set it explicitly on both variants
            def chunk_alloc(cache, slot, n_pages):
                cache = kv_pool.alloc_slot(cache, slot, n_pages)
                return dict(cache, len=cache["len"].at[slot].set(0))

            def chunk_alloc_shared(cache, slot, shared_row, n_shared,
                                   n_private):
                ps = kv_pool.page_size_of(cache)
                cache = kv_pool.alloc_slot_shared(cache, slot, shared_row,
                                                  n_shared, n_private)
                return dict(cache, len=cache["len"].at[slot].set(
                    n_shared * ps))

            self._chunk_alloc_jit = self._compile(
                chunk_alloc, ("cache", "rep", "rep"), ("cache",), donate)
            self._chunk_alloc_shared_jit = self._compile(
                chunk_alloc_shared, ("cache",) + ("rep",) * 4, ("cache",),
                donate)

    # --- compilation seams (overridden by serving/tp.py) --------------------

    def _lay_out_experts(self, variables):
        """Lay out ``variables``' expert stacks as the engine serves them
        (:func:`~apex_tpu.transformer.moe.dropless.lay_out_experts`, in
        place); returns ``{layer: widths}``."""
        return lay_out_experts(variables)

    def expert_rows(self) -> dict:
        """``{"decode": (rows, served, tile), "admit": {bucket: (rows,
        served, tile)}}``: the rows a layer's grouped products route in a
        decode step (a token a slot; ``draft_len + 1`` with a draft) and in
        each admit program compiled so far, the rows they are handed
        (:func:`~apex_tpu.transformer.moe.dropless.served_rows`) and the
        row tile that count gets; empty for a model with no routed
        experts."""
        k = getattr(self.cfg, "num_experts_per_tok", None)
        if not k:
            return {}
        # the router's experts, where a configuration holds a share of them
        router = getattr(self.cfg, "num_experts", None)
        decode = expert_rows(self.variables, k, {
            "decode": self.num_slots * (self.draft_len + 1)}, router)
        if not decode:
            return {}
        return {"decode": decode["decode"],
                "admit": expert_rows(self.variables, k,
                                     {b: b for b in sorted(self._admit_jit)},
                                     router)}

    def _make_cache(self, num_slots, num_pages, page_size,
                    max_pages_per_seq, config=None):
        """Allocate a paged cache for ``config`` (default: the target
        model's — speculative engines call this a second time with the
        draft model's config for the draft pool). The single-chip engine
        holds the whole pool on the default device;
        :class:`~apex_tpu.serving.tp.TensorParallelPagedEngine`
        overrides this to allocate one GLOBAL pool whose K/V head axis
        is sharded over its ``tp`` mesh."""
        return kv_pool.init_paged_cache(
            config if config is not None else self.cfg, num_slots,
            num_pages=num_pages, page_size=page_size,
            max_pages_per_seq=max_pages_per_seq, kv_dtype=self.kv_dtype)

    def _compile(self, fn, in_roles, out_roles, donate=()):
        """The single seam every engine program is compiled through.

        ``in_roles`` / ``out_roles`` name each positional argument /
        result of ``fn``: ``"cache"`` (the paged pool pytree),
        ``"vars"`` (the model variables), ``"draft_cache"`` /
        ``"draft_vars"`` (the speculative draft model's pool and
        variables), ``"rep"`` (a replicated host-side value — tokens,
        slot indices, masks, keys). The
        single-chip engine ignores the roles and plain-jits;
        :class:`~apex_tpu.serving.tp.TensorParallelPagedEngine` wraps
        ``fn`` in ``shard_map`` over its mesh with per-role
        PartitionSpecs, so every program — pool maintenance included —
        runs SPMD over the same sharded state."""
        del in_roles, out_roles
        return jax.jit(fn, donate_argnums=donate)

    # --- request-key sampling (scheduling-invariant streams) ----------------

    def _first_token(self, last_logits, req_key, samp0=0):
        # ``samp0``: the token's index in the request's fold_in key
        # stream — 0 at a cold admission, the resume point after a
        # preemption (so preempted/resumed sampled decode draws the SAME
        # stream as an uninterrupted run: scheduling invariance)
        if not self.temperature:
            return _greedy_token(last_logits, self.axis_name)
        return _sample_token(last_logits,
                             jax.random.fold_in(req_key, samp0),
                             temperature=self.temperature, top_k=self.top_k,
                             top_p=self.top_p, axis_name=self.axis_name)

    # --- compiled programs --------------------------------------------------

    def _admit_fn(self, bucket: int):
        """Compile (once per prompt bucket): contiguous flash prefill at
        ``bucket`` tokens, page alloc + scatter, first-token sample."""
        if bucket in self._admit_jit:
            return self._admit_jit[bucket]
        model = self.model                       # static via closure

        def admit(cache, variables, ids, s0, slot, n_pages, req_key,
                  samp0=0):
            contig = init_cache(self.cfg, 1, bucket)
            last, contig = _logits_at(model, variables, ids, contig, s0 - 1)
            cache = kv_pool.alloc_slot(cache, slot, n_pages)
            cache = kv_pool.prefill_into_pages(
                cache, slot, contig["layers"], s0, groups=self.groups)
            tok0 = self._first_token(last, req_key, samp0)[0]
            return cache, tok0

        fn = self._compile(admit, ("cache", "vars") + ("rep",) * 6,
                           ("cache", "rep"), _donate_cache())
        self._admit_jit[bucket] = fn
        return fn

    def _admit_shared_fn(self, t_start: int, tail_bucket: int):
        """Compile (once per ``(t_start, tail_bucket)``): the shared-prefix
        admission — gather matched pages, tail-only prefill, page-pool
        scatter, first-token sample (``make_shared_admit``)."""
        key = (t_start, tail_bucket)
        if key not in self._shared_admit_jit:
            fn = make_shared_admit(self.model, t_start=t_start,
                                   tail_bucket=tail_bucket,
                                   first_token=self._first_token,
                                   axis_name=self.axis_name)
            self._shared_admit_jit[key] = self._compile(
                fn, ("cache", "vars") + ("rep",) * 7, ("cache", "rep"),
                _donate_cache())
        return self._shared_admit_jit[key]

    def _prefill_chunk_fn(self):
        """Compile (once): one ``prefill_chunk``-token chunk of one
        slot's prompt through the paged s>1 path
        (``make_prefill_chunk``)."""
        if self._chunk_jit is None:
            fn = make_prefill_chunk(self.model, chunk=self.prefill_chunk,
                                    first_token=self._first_token,
                                    axis_name=self.axis_name)
            self._chunk_jit = self._compile(
                fn, ("cache", "vars") + ("rep",) * 5, ("cache", "rep"),
                _donate_cache())
        return self._chunk_jit

    def _spec_admit_fn(self, bucket: int):
        """Compile (once per prompt bucket): the speculative twin of
        ``_admit_fn`` — the same contiguous target prefill + scatter,
        plus the SAME prompt prefilled through the draft model into the
        draft pool (both pools share the slot's page indices, so one
        alloc decision covers both). tok0 comes from the TARGET: the
        emitted stream is always target-greedy."""
        if bucket in self._spec_admit_jit:
            return self._spec_admit_jit[bucket]
        model, draft = self.model, self.draft_model

        def admit(cache, dcache, variables, dvariables, ids, s0, slot,
                  n_pages, req_key, samp0=0):
            contig = init_cache(self.cfg, 1, bucket)
            logits, contig = model.apply(variables, ids, cache=contig)
            last = lax.dynamic_slice_in_dim(logits, s0 - 1, 1, axis=1)[:, 0]
            cache = kv_pool.alloc_slot(cache, slot, n_pages)
            cache = kv_pool.prefill_into_pages(cache, slot,
                                               contig["layers"], s0)
            contig_d = init_cache(draft.config, 1, bucket)
            _, contig_d = draft.apply(dvariables, ids, cache=contig_d)
            dcache = kv_pool.alloc_slot(dcache, slot, n_pages)
            dcache = kv_pool.prefill_into_pages(dcache, slot,
                                                contig_d["layers"], s0)
            tok0 = self._first_token(last, req_key, samp0)[0]
            return cache, dcache, tok0

        donate = (0, 1) if jax.default_backend() == "tpu" else ()
        fn = self._compile(
            admit, ("cache", "draft_cache", "vars", "draft_vars")
            + ("rep",) * 6, ("cache", "draft_cache", "rep"), donate)
        self._spec_admit_jit[bucket] = fn
        return fn

    # --- pool maintenance ---------------------------------------------------

    def _leak_suspected(self, free: int, active) -> bool:
        """True when host liveness accounting says more pages should be
        free than the stack shows — a free miscount somewhere; ``defrag``
        rebuilds the stack from actual liveness and recovers them.
        ``active``: the frontend's slot -> entry map (entries expose
        ``n_private``, the pages the slot owns)."""
        owned = sum(rec.n_private for rec in active.values())
        cached = len(self.prefix) if self.prefix is not None else 0
        usable = kv_pool.num_pages_of(self.cache) - 1    # null page
        return usable - owned - cached > free

    def _defrag_now(self):
        """Run ``defrag_map`` with the prefix cache's resident pages as
        extra liveness (they appear in no block table but must survive),
        then remap the radix tree through the returned page permutation."""
        num_pages = kv_pool.num_pages_of(self.cache)
        extra = np.zeros((num_pages,), bool)
        if self.prefix is not None:
            extra[self.prefix.pages()] = True
        self.cache, new_idx = self._defrag_jit(self.cache,
                                               jnp.asarray(extra))
        if self.prefix is not None:
            self.prefix.remap(np.asarray(new_idx))
        if self.draft_len:
            # the draft pool's alloc/free mirrors the target pool's
            # call-for-call, so it fragments identically — compact it in
            # the same maintenance pass (no prefix pages to pin: the
            # spec engine refuses prefix_cache)
            self.draft_cache, _ = self._draft_defrag_jit(
                self.draft_cache,
                jnp.asarray(np.zeros((num_pages,), bool)))

    def _step_fn(self):
        """Compile (once): ``sync_every`` decode steps as a ``lax.scan``
        whose carry holds the paged cache and per-slot (token, done mask,
        remaining-token count)."""
        if self._step_jit is not None:
            return self._step_jit
        model = self.model
        eos = self.eos_token_id

        def one_step(variables, carry, _):
            cache, tok, done, n_left, req_keys, samp_i = carry
            len_before = cache["len"]
            # what the step's routing did rides back with its tokens: the
            # layers sow one ROUTING_STATS vector each (SHARE_ROUTING_STATS
            # where a layer holds a share of its experts), summed here (a
            # model without routed experts sows nothing: an empty tuple)
            (logits, cache), sown = model.apply(
                variables, tok[:, None], cache=cache,
                mutable=[ROUTING_COLLECTION])
            routed = jax.tree.leaves(sown)
            routed = sum(routed[1:], routed[0]) if routed else ()
            # freeze done/idle slots' lengths: their forward ran (static
            # shapes) against the null-page sink, but their position must
            # not creep — unbounded growth would walk the position table
            # and scale null-page attention work with idle time
            cache = dict(cache, len=jnp.where(done, len_before,
                                              cache["len"]))
            last = logits[:, 0]
            if not self.temperature:
                nxt = _greedy_token(last, self.axis_name)
            else:
                # key = fold_in(request key, the request's OWN token
                # index) -> draws are scheduling-invariant (independent of
                # slot, step, and batch composition)
                keys = jax.vmap(jax.random.fold_in)(req_keys, samp_i)
                nxt = jax.vmap(
                    lambda lg, k: _sample_token(
                        lg[None], k, temperature=self.temperature,
                        top_k=self.top_k, top_p=self.top_p,
                        axis_name=self.axis_name)[0])(last, keys)
            fill = jnp.int32(eos if eos is not None else 0)
            nxt = jnp.where(done, fill, nxt)
            n_left = jnp.where(done, n_left, n_left - 1)
            samp_i = samp_i + 1
            if eos is not None:
                done = jnp.logical_or(done, nxt == eos)
            done = jnp.logical_or(done, n_left <= 0)
            return (cache, nxt, done, n_left, req_keys, samp_i), (nxt, routed)

        def step(cache, variables, tok, done, n_left, req_keys, samp_i):
            # greedy mode never reads req_keys; the carry layout stays
            # identical across greedy/sampled so both share one step
            (cache, tok, done, n_left, _, samp_i), (toks, routed) = lax.scan(
                functools.partial(one_step, variables),
                (cache, tok, done, n_left, req_keys, samp_i),
                None, length=self.sync_every)
            return cache, tok, done, n_left, samp_i, toks, routed

        self._step_jit = self._compile(
            step, ("cache", "vars") + ("rep",) * 5,
            ("cache",) + ("rep",) * 6, _donate_cache())
        return self._step_jit

    def _spec_step_fn(self):
        """Compile (once): ``sync_every`` speculative rounds as a
        ``lax.scan``. One round = ``draft_len`` single-token draft steps
        over the draft pool, then ONE ``s = draft_len + 1`` paged target
        step verifying the block, then a PER-SLOT rollback of both
        pools to their accepted lengths.

        Invariant carried between rounds (same as lock-step
        ``speculative_generate``): each live slot holds a PENDING token
        — emitted to the caller but in NEITHER cache. The round writes
        it as the verify chunk's first position, so the chunk is
        ``[pending, d1 .. d_{draft_len}]`` and the target's greedy
        prediction at chunk position ``i`` continues the true prefix —
        emitted tokens are exactly the target's sequential greedy
        stream, token-identical to the non-speculative engine. Per-slot
        acceptance ``e`` (1..k accepted tokens, 0 for done slots) rides
        the scan output next to the predictions; both pools roll back
        to ``len0 + e`` (chunk prefix kept, new pending token
        ``preds[e-1]`` left unwritten — the invariant restored)."""
        if self._spec_step_jit is not None:
            return self._spec_step_jit
        model, draft = self.model, self.draft_model
        eos = self.eos_token_id
        k = self.draft_len + 1
        arange = jnp.arange(self.num_slots)

        def one_round(variables, dvariables, carry, _):
            cache, dcache, tok, done, n_left = carry
            len0, dlen0 = cache["len"], dcache["len"]

            def draft_step(dcarry, _):
                dc, t_in = dcarry
                lg, dc = draft.apply(dvariables, t_in[:, None], cache=dc)
                nxt = _greedy_token(lg[:, 0], self.axis_name)
                return (dc, nxt), t_in

            # stacked INPUTS of k draft steps = [pending, d1..d_{k-1}]:
            # the k-th draft output is never proposed, but its k cache
            # writes are exactly the chunk — the draft pool stays in
            # lock-step with the target pool through the shared rollback
            (dcache, _), toks_in = lax.scan(draft_step, (dcache, tok),
                                            None, length=k)
            chunk = toks_in.transpose(1, 0)                  # (slots, k)

            logits, cache = model.apply(variables, chunk, cache=cache)
            preds = _greedy_token(logits, self.axis_name)    # (slots, k)
            props = chunk[:, 1:]
            # accepted proposals = longest matching prefix against the
            # target's own predictions; +1 for the bonus target token
            m = jnp.sum(jnp.cumprod(
                (props == preds[:, :-1]).astype(jnp.int32), axis=1),
                axis=1)
            e = jnp.minimum(m + 1, n_left)
            if eos is not None:
                iseos = preds == eos
                has_eos = jnp.any(iseos, axis=1)
                eos_idx = jnp.argmax(iseos, axis=1)
                # never emit past the first EOS prediction
                e = jnp.minimum(e, jnp.where(has_eos, eos_idx + 1, k))
            e = jnp.where(done, 0, e)
            # per-slot rollback of BOTH pools: chunk[:e] stays, the new
            # pending token preds[e-1] stays unwritten; done slots
            # freeze at len0 (their forward wrote only above-len
            # garbage, same as the non-speculative step's frozen slots)
            cache = dict(cache, len=len0 + e)
            dcache = dict(dcache, len=dlen0 + e)
            fill = jnp.int32(eos if eos is not None else 0)
            tok = jnp.where(done, fill,
                            preds[arange, jnp.clip(e - 1, 0, k - 1)])
            n_left = n_left - e
            if eos is not None:
                done = jnp.logical_or(
                    done, jnp.logical_and(has_eos, e == eos_idx + 1))
            done = jnp.logical_or(done, n_left <= 0)
            return (cache, dcache, tok, done, n_left), (preds, e)

        def step(cache, dcache, variables, dvariables, tok, done, n_left):
            ((cache, dcache, tok, done, n_left),
             (toks, counts)) = lax.scan(
                functools.partial(one_round, variables, dvariables),
                (cache, dcache, tok, done, n_left), None,
                length=self.sync_every)
            return cache, dcache, tok, done, n_left, toks, counts

        donate = (0, 1) if jax.default_backend() == "tpu" else ()
        self._spec_step_jit = self._compile(
            step, ("cache", "draft_cache", "vars", "draft_vars")
            + ("rep",) * 3,
            ("cache", "draft_cache") + ("rep",) * 5, donate)
        return self._spec_step_jit

    # --- the host scheduling loop -------------------------------------------

    def _validate_request(self, r: Request) -> None:
        """Reject a request the engine could never serve (position-table
        overflow, block-table overflow, empty budget) — raised at
        ``submit()``/``run()`` time, before any device work."""
        cfg, ps = self.cfg, self.page_size
        max_pages = self.cache["block_tables"].shape[1]
        s0 = int(np.asarray(r.prompt).shape[0])
        if r.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if s0 + r.max_new_tokens > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt ({s0}) + max_new_tokens ({r.max_new_tokens}) "
                f"exceeds max_position_embeddings="
                f"{cfg.max_position_embeddings}")
        if kv_pool.pages_for(s0 + r.max_new_tokens, ps) > max_pages:
            raise ValueError(
                f"request needs more than max_pages_per_seq="
                f"{max_pages} pages")
        if self.draft_len:
            # a speculative round may write up to draft_len tokens past
            # the final emitted one before rollback discards them — the
            # position table and block table must absorb the overshoot
            # in BOTH models (mirrors speculative_generate's bound)
            k = self.draft_len + 1
            lim = min(cfg.max_position_embeddings,
                      self.draft_model.config.max_position_embeddings)
            if s0 + r.max_new_tokens + k > lim:
                raise ValueError(
                    f"prompt ({s0}) + max_new_tokens "
                    f"({r.max_new_tokens}) + draft block ({k}) exceeds "
                    f"max_position_embeddings={lim} under speculative "
                    f"decode")
            if kv_pool.pages_for(s0 + r.max_new_tokens + k, ps) > max_pages:
                raise ValueError(
                    f"request + draft-block overshoot needs more than "
                    f"max_pages_per_seq={max_pages} pages under "
                    f"speculative decode")

    def run(self, requests: Sequence[Request], *,
            tracer: Optional[SpanTracer] = None, policy=None):
        """Drain the request queue; returns ``(outputs, stats)``.

        A thin closed-loop wrapper over the serving front-end
        (``serving/frontend.py``): every request is submitted to a fresh
        :class:`~apex_tpu.serving.frontend.ServingFrontend` (so its
        tracer and stats are run-scoped) and the pump is driven
        synchronously until the queue drains — ``run()`` therefore
        exercises exactly the code path a streaming server does,
        including the pipelined decode-chunk pump and, when ``policy``
        enables it and requests carry priorities/deadlines, preemption.
        ``policy`` defaults to
        :class:`~apex_tpu.serving.policy.PriorityDeadlinePolicy`, which
        on plain requests (priority 0, no deadlines) reduces to the
        engine's original FIFO order and never preempts.

        ``outputs[i]``: np.int32 generated tokens for request ``i`` —
        length ``max_new_tokens``, or shorter when the request hit EOS
        (the EOS token is included). ``stats``: engine counters for this
        run, DERIVED from the ``serving.*`` instrument registry
        (``apex_tpu.utils.metrics``) as the delta of each counter across
        the run — ``decode_steps`` / ``admitted`` / ``retired`` /
        ``peak_slots_in_use`` / ``slot_occupancy``, the prefix-cache
        counters (``prefix_hits``, ``prefix_hit_rate``,
        ``prefill_tokens_{total,computed,skipped}``, ``evicted_pages``,
        ``prefix_cached_pages``), the maintenance counters
        (``deferred_admissions``, ``defrag_runs``), the frontend
        counters (``preemptions``, ``resumes``, ``deadline_misses``,
        ``peak_queue_depth``), and this run's latency percentiles
        (``ttft_ms_p50/p95``, ``tpot_ms_p50/p95``,
        ``queue_wait_ms_p50/p95``, ``decode_step_ms_p50/p95``). Every
        numeric stat is also recorded as a ``serving.<name>`` raw series.

        Per-request lifecycle spans (enqueue → admit → prefill →
        first_token → decode → [preempt → preempted → resume →] retire)
        land in a fresh :class:`~apex_tpu.obs.spans.SpanTracer` kept as
        ``self.tracer`` (pass ``tracer=`` to supply your own);
        scheduling events append to the engine-lifetime ``self.events``
        ring (docs/observability.md).
        """
        # the frontend lives below the engine module (it drives the
        # engine's compiled programs); import here to avoid the cycle
        from apex_tpu.serving.frontend import ServingFrontend

        # validate the whole batch up front: a bad request raises before
        # any of its siblings start (the pre-frontend contract)
        for r in requests:
            self._validate_request(r)
        frontend = ServingFrontend(self, policy=policy, tracer=tracer)
        handles = [frontend.submit(r, request_id=i)
                   for i, r in enumerate(requests)]
        frontend.drain()
        outputs = [np.asarray(h.result(timeout=0), np.int32)
                   for h in handles]
        return outputs, frontend.stats()


# the host scheduling loop driving the jitted admit/step programs;
# tpu-lint: host-boundary -- never traced (jit of paged generate is
# unsupported by contract: the engine syncs at every chunk boundary)
def generate_paged(model, variables, prompt_ids, max_new_tokens: int, *,
                   temperature: float = 0.0, top_k: Optional[int] = None,
                   top_p: Optional[float] = None, rng=None,
                   eos_token_id: Optional[int] = None,
                   axis_name: str = MODEL_AXIS,
                   num_slots: Optional[int] = None, page_size: int = 16,
                   num_pages: Optional[int] = None, sync_every: int = 1,
                   prefix_cache: bool = False, return_stats: bool = False,
                   kv_dtype=None):
    """`generate`-shaped front end over the engine.

    ``prompt_ids`` may be a rectangular ``(batch, s0)`` array (the
    ``generate`` contract — returns ``(batch, s0 + max_new_tokens)`` with
    prompts included and EOS padding after a row finishes, matching
    lock-step output exactly under greedy decode) or a list of 1-D
    prompts of MIXED lengths (returns a list of 1-D outputs).
    ``prefix_cache=True`` turns on cross-request shared-prefix KV reuse
    (same outputs, fewer prefill tokens on shared-prefix workloads)."""
    rect = hasattr(prompt_ids, "ndim") and prompt_ids.ndim == 2
    prompts = [np.asarray(p, np.int32).reshape(-1)
               for p in (prompt_ids if not rect else np.asarray(prompt_ids))]
    engine = PagedDecodeEngine(
        model, variables,
        num_slots=num_slots if num_slots is not None else len(prompts),
        page_size=page_size, num_pages=num_pages,
        eos_token_id=eos_token_id, temperature=temperature, top_k=top_k,
        top_p=top_p, rng=rng, sync_every=sync_every, axis_name=axis_name,
        prefix_cache=prefix_cache, kv_dtype=kv_dtype)
    reqs = [Request(prompt=p, max_new_tokens=max_new_tokens)
            for p in prompts]
    outs, stats = engine.run(reqs)

    fill = eos_token_id if eos_token_id is not None else 0
    full = []
    for p, g in zip(prompts, outs):
        g = np.asarray(g, np.int32)
        pad = np.full((max_new_tokens - g.shape[0],), fill, np.int32)
        full.append(np.concatenate([p, g, pad]))
    if rect:
        out = jnp.asarray(np.stack(full))
        return (out, stats) if return_stats else out
    out = [jnp.asarray(f) for f in full]
    return (out, stats) if return_stats else out
