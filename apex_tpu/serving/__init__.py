"""The serving subsystem: paged KV cache + continuous-batching decode.

Lock-step ``generate`` allocates one monolithic ``(batch, kv, max_len, d)``
cache per request batch and pads every sequence to the longest — a finished
sequence wastes its slot (and its cache HBM) until the whole batch drains.
This package replaces that with the two serving-stack staples:

- **Paged KV cache** (``kv_pool``): per-layer K/V live in a static
  ``(num_pages, kv // pack, page_size, d * pack)`` pool (``pack`` heads
  side by side in a 128-lane row: ``kv_pool.heads_per_row``); each
  sequence owns
  ``ceil(len/page_size)`` pages named by an int32 block table. Alloc /
  free / defrag are pure-JAX index ops over a fixed-size free stack — no
  shape ever changes, so nothing recompiles at admission or retirement.
  (vLLM / PagedAttention, Kwon et al. 2023.)
- **Continuous batching** (``scheduler``): a fixed-size SLOT array of
  in-flight sequences; at step boundaries finished slots retire (pages
  freed) and queued requests admit into the vacancy — iteration-level
  scheduling (Orca, Yu et al. 2022). The decode step itself is one jitted
  program over the slot array, with per-slot lengths, EOS masks, and
  remaining-token counts carried through a ``lax.scan``.

- **Shared-prefix caching** (``prefix_cache``): a host-side radix tree
  over token ids whose nodes name pool pages already holding that
  prefix's K/V — requests sharing a system prompt / few-shot header skip
  its prefill entirely, sharing the pages read-only under per-page int32
  refcounts with LRU eviction at refcount 0 (RadixAttention, Zheng et
  al. 2023). Opt-in via ``PagedDecodeEngine(..., prefix_cache=True)`` /
  ``generate(..., paged=True, prefix_cache=True)``.

- **Tiered KV pool** (``host_tier``): a byte-budgeted host-RAM LRU
  under the device pool — refcount-0 radix pages evicted under pressure
  DEMOTE (async gather to pinned host memory, raw pool-dtype bytes +
  scales) instead of dropping, and a later hit on a host-resident node
  PROMOTES into freshly popped pages instead of re-prefilling; a
  preemption spill's pages ride the same path, so a resume promotes
  instead of recomputing. Opt-in via ``PagedDecodeEngine(...,
  host_tier_bytes=...)`` (requires ``prefix_cache=True``;
  docs/serving.md "Tiered KV pool").

- **Async front-end** (``frontend`` + ``policy``): streaming ingest
  (``submit()`` returns a per-token :class:`StreamHandle`), a
  priority/deadline admission policy, preemption that spills a victim's
  full pages back through the prefix cache (resumption is a cache hit),
  and a pump that overlaps host-side retirement/admission work with the
  next jitted decode chunk. ``PagedDecodeEngine.run`` is a thin
  closed-loop wrapper over it (docs/frontend.md).

- **Tensor parallelism** (``tp``): ``TensorParallelPagedEngine`` runs
  ONE logical engine over a ``tp``-axis mesh — the pool's K/V shard
  along the kv-head axis (each chip holds ``1/tp`` the pool bytes),
  block tables and scheduling stay replicated/host-side, and every
  engine program runs under ``shard_map`` with the models' Megatron TP
  layers (docs/tp_serving.md).

- **Data-parallel replication** (``router`` + ``faults``): N
  frontend+engine replicas (each optionally TP) behind one
  :class:`ReplicaRouter` — queue-depth load balancing, rendezvous-hash
  prefix-affinity routing, overload shedding with retry-after,
  graceful drain, and supervised failure recovery (a dead replica's
  in-flight requests resume on survivors with their generated tokens
  folded into the prompt; exhausted recovery fails handles with a
  terminal :class:`ServingError`, never a hang). ``faults`` makes the
  failures seeded, replayable scenario inputs (docs/router.md).

- **HTTP/SSE surface** (``http`` + ``aio``): a stdlib-asyncio server
  exposing ``POST /v1/generate`` token streaming (plus health, metrics,
  and cost endpoints on the same port) over :class:`AsyncStreamHandle`,
  an awaitable adapter on the thread-based pump. Admission ties to the
  frontend's ``backpressure_window`` — a stalled reader spills its slot
  through the preemption path instead of pinning pages for a socket —
  and a client disconnect cancels at the next sync boundary and frees
  everything. :class:`HttpReplicaClient` wraps a remote server in the
  frontend surface so a :class:`ReplicaRouter` can supervise N
  networked replicas exactly like in-process ones (docs/http.md).

The decode attention is ``apex_tpu.ops.paged_attention`` — a Pallas kernel
that gathers pages via the block table with scalar-prefetch index maps.
"""

from apex_tpu.serving.aio import AsyncStreamHandle  # noqa: F401
from apex_tpu.serving.faults import (  # noqa: F401
    NETWORK_FAULT_KINDS,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    InjectedFault,
)
from apex_tpu.serving.http import (  # noqa: F401
    HttpReplicaClient,
    HttpServingServer,
)
from apex_tpu.serving.frontend import (  # noqa: F401
    ServingError,
    ServingFrontend,
    StreamHandle,
)
from apex_tpu.serving.host_tier import HostPageTier  # noqa: F401
from apex_tpu.serving.kv_pool import (  # noqa: F401
    alloc_slot,
    alloc_slot_shared,
    defrag,
    defrag_map,
    drop_slot_pages,
    evict_pages,
    free_page_count,
    free_slot,
    init_paged_cache,
    pages_for,
    prefill_into_pages,
    release_slot,
)
from apex_tpu.serving.policy import PriorityDeadlinePolicy  # noqa: F401
from apex_tpu.serving.router import (  # noqa: F401
    OverloadError,
    ReplicaRouter,
    RouterHandle,
    RouterPolicy,
)
from apex_tpu.serving.prefix_cache import PrefixCache  # noqa: F401
from apex_tpu.serving.scheduler import (  # noqa: F401
    PagedDecodeEngine,
    Request,
    generate_paged,
    make_shared_admit,
)
from apex_tpu.serving.tp import (  # noqa: F401
    TensorParallelPagedEngine,
    shard_model_variables,
    tp_mesh,
)
