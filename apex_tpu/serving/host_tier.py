"""Host-RAM spill tier under the device page pool (docs/serving.md
"Tiered KV pool").

At long contexts the page pool, not the weights, caps ``num_slots``
(docs/tp_serving.md "Pool sizing"), and before this tier an evicted
radix page or a discarded preemption spill was simply recomputed — the
eviction-churn scenario lights ``prefix_cache.churn`` exactly there.
A page copied back over the host link stands in for re-prefilling its
tokens (which of the two is cheaper on the chip is not measured), so
refcount-0 pages the device pool can no longer afford DEMOTE here and
PROMOTE back into freshly allocated pages on the next prefix hit or
preemption resume, instead of being thrown away.

What this class is: pure host-side bookkeeping — a byte-budgeted LRU
over demoted page payloads, keyed by radix-node identity (the page's
full root->node token-key path, so a tier hit means exactly what a tree
hit means: these positions, these tokens). The payload is the page's
RAW pool-dtype bytes plus, on quantized pools, its per-``(page,
kv_head)`` f32 scales — an int8/fp8 page demotes and promotes
losslessly, and promote never requantizes (the PR 14 bit-stability
invariant: a full page's bytes are written once and never rewritten).

What this class is NOT: a device actor. Every device mutation stays in
``kv_pool`` ops the scheduler jits (``gather_pages`` on demote,
``promote_pages`` on promote); the engine's fixed-shape programs are
untouched and there is no copy-drain thread — demoted tiles arrive as
ASYNC device arrays (the gather is dispatched at a sync boundary,
before the eviction returns the pages to the free stack) and
``drain()`` converts them to host numpy inside the pump's
double-buffered host-work slot, while the next decode chunk runs.

Defrag composes for free: the tier names pages by TOKENS, not by
physical page id, so ``kv_pool.defrag_map`` has nothing here to remap —
promotion always pops fresh pages from the (possibly compacted) free
stack.

Instruments (docs/observability.md catalog): ``pool.host_tier_*`` —
resident bytes/pages gauges, demote/promote/lookup/hit/evicted
counters, and demote/promote copy-ms histograms.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.utils import metrics

__all__ = ["HostPageTier"]

#: a page's tier key: its radix path — the page-sized token-id runs from
#: the tree root down to (and including) the page's own run
PathKey = Tuple[Tuple[int, ...], ...]


class HostPageTier:
    """Byte-budgeted host-RAM LRU of demoted KV pages.

    Thread-safety: all tier state (the LRU map, the pending-demote list,
    the byte gauge) is guarded by ``self._lock``. The pump owns the
    call sites today, but the tier keeps the same single-lock discipline
    as the frontend's ingest side so the conc lint can pin its guard
    map (``tests/test_conc_lint.py``)."""

    def __init__(self, budget_bytes: int, *, page_size: int,
                 metrics_labels: Optional[dict] = None):
        if budget_bytes < 1:
            raise ValueError(
                f"host_tier budget_bytes must be >= 1, got {budget_bytes}")
        self.budget_bytes = int(budget_bytes)
        self.page_size = page_size
        self._lock = threading.Lock()
        # path-key -> (per-layer numpy payload dicts, payload bytes);
        # insertion order IS the LRU order (move_to_end on every hit)
        self._entries: "OrderedDict[PathKey, Tuple[List[dict], int]]" = \
            OrderedDict()
        self._resident_bytes = 0
        # demotes whose device->host copy is still in flight: each item
        # is (path keys, async device tile pytree, n live pages, t0)
        self._pending: List[tuple] = []
        labels = dict(metrics_labels) if metrics_labels else None
        self._g_bytes = metrics.gauge("pool.host_tier_resident_bytes",
                                      labels=labels)
        self._g_pages = metrics.gauge("pool.host_tier_resident_pages",
                                      labels=labels)
        self._c = {name: metrics.counter(f"pool.host_tier_{name}",
                                         labels=labels)
                   for name in ("demotes", "promotes", "lookups", "hits",
                                "evicted_pages")}
        self._c0 = {name: c.value for name, c in self._c.items()}
        self._h_demote = metrics.histogram("pool.host_tier_demote_copy_ms",
                                           labels=labels)
        self._h_promote = metrics.histogram("pool.host_tier_promote_copy_ms",
                                            labels=labels)

    # --- introspection ------------------------------------------------------

    def __len__(self) -> int:
        """Resident (drained) pages — pending demotes not yet counted."""
        with self._lock:
            return len(self._entries)

    @property
    def resident_bytes(self) -> int:
        with self._lock:
            return self._resident_bytes

    def stats(self) -> Dict[str, float]:
        """The tier's lifetime totals in ``stats()`` shape (the frontend
        merges these into the engine-stats dict as ``host_tier_*``)."""
        d = {name: c.value - self._c0[name] for name, c in self._c.items()}
        with self._lock:
            resident = self._resident_bytes
            pages = len(self._entries)
        return {
            "host_tier_resident_bytes": int(resident),
            "host_tier_resident_pages": int(pages),
            "host_tier_demotes": int(d["demotes"]),
            "host_tier_promotes": int(d["promotes"]),
            "host_tier_evicted_pages": int(d["evicted_pages"]),
            "host_tier_promote_hit_rate": (d["hits"]
                                           / max(d["lookups"], 1)),
        }

    def _observe_locked(self) -> None:
        self._g_bytes.set(self._resident_bytes)
        self._g_pages.set(len(self._entries))

    # --- demote (device -> host) --------------------------------------------

    def put_pending(self, keys: Sequence[PathKey], tiles, n: int) -> None:
        """Record one dispatched ``kv_pool.gather_pages`` batch: ``keys``
        name the first ``n`` tile rows (the rest is null-page padding).
        The device arrays stay ASYNC — nothing blocks here; ``drain()``
        converts them at the pump's host-work slot."""
        if n == 0:
            return
        with self._lock:
            self._pending.append((tuple(keys[:n]), tiles, n,
                                  time.perf_counter()))
        self._c["demotes"].inc(n)

    def drain(self) -> None:
        """Convert every pending demote's device tiles to host numpy
        (blocking only for copies not already complete — the histogram
        records the blocked span), split the batch into per-page LRU
        entries, and evict over-budget pages oldest-first."""
        with self._lock:
            pending, self._pending = self._pending, []
        for keys, tiles, n, _t0 in pending:
            t_enter = time.perf_counter()
            host = [{name: np.asarray(arr) for name, arr in lc.items()}
                    for lc in tiles]
            self._h_demote.observe((time.perf_counter() - t_enter) * 1e3)
            for i, key in enumerate(keys):
                payload = [{name: arr[i] for name, arr in lc.items()}
                           for lc in host]
                nbytes = sum(a.nbytes for lc in payload
                             for a in lc.values())
                if nbytes > self.budget_bytes:
                    continue             # one page over budget: drop it
                with self._lock:
                    old = self._entries.pop(key, None)
                    if old is not None:
                        self._resident_bytes -= old[1]
                    self._entries[key] = (payload, nbytes)
                    self._resident_bytes += nbytes
        self._evict_over_budget()

    def _evict_over_budget(self) -> None:
        evicted = 0
        with self._lock:
            while self._resident_bytes > self.budget_bytes and self._entries:
                _, (_, nbytes) = self._entries.popitem(last=False)
                self._resident_bytes -= nbytes
                evicted += 1
            self._observe_locked()
        if evicted:
            self._c["evicted_pages"].inc(evicted)

    # --- promote (host -> device) -------------------------------------------

    def run_length(self, base: PathKey, keys: Sequence[Tuple[int, ...]],
                   ) -> int:
        """How many consecutive pages past the tree-matched depth are
        resident: the longest r such that ``base + keys[:j+1]`` is held
        for every ``j < r``. One ``lookups`` tick per call (and a
        ``hits`` tick when r > 0): ``promote_hit_rate`` is hits over
        lookups. Bumps the run's LRU position."""
        r = 0
        path = tuple(base)
        with self._lock:
            for key in keys:
                path = path + (key,)
                if path not in self._entries:
                    break
                self._entries.move_to_end(path)
                r += 1
        self._c["lookups"].inc()
        if r:
            self._c["hits"].inc()
        return r

    def pop(self, path: PathKey) -> Optional[List[dict]]:
        """Take ownership of a resident page's payload (the promote
        path): removes the entry — the bytes are about to live in a
        device page the radix tree names, so keeping the host copy would
        double-count the budget. Returns None on a miss (the caller
        re-prefills instead)."""
        with self._lock:
            hit = self._entries.pop(path, None)
            if hit is None:
                return None
            self._resident_bytes -= hit[1]
            self._observe_locked()
        self._c["promotes"].inc()
        return hit[0]

    def observe_promote_ms(self, ms: float) -> None:
        """Record one promote batch's host->device copy span (the
        frontend times the dispatch-to-visible window at the sync
        boundary it already sits on)."""
        self._h_promote.observe(ms)
