"""Arithmetic from timestamps to the end-to-end metrics."""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100), nearest-rank on the sorted values:
    the smallest value with at least q% of the samples at or below it.  A
    missed request is ``inf`` and sorts last."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


@dataclasses.dataclass
class RequestTimes:
    """One request as the client saw it (seconds on the host clock)."""

    due: float                       # when it was due / submitted
    first: Optional[float] = None    # first token arrived
    last: Optional[float] = None     # last token arrived
    n_out: int = 0
    done: bool = False
    failed: bool = False
    #: (time, tokens so far) at every delivery
    counts: List[Tuple[float, int]] = dataclasses.field(default_factory=list)

    def deliver(self, now: float, n: int) -> None:
        """``n`` tokens have arrived by ``now``."""
        if n > self.n_out:
            if self.first is None:
                self.first = now
            self.last, self.n_out = now, n
            self.counts.append((now, n))

    def delivered_in(self, t0: float, t1: float) -> int:
        """Tokens that arrived in [t0, t1]."""
        total, before = 0, 0
        for t, n in self.counts:
            if t0 <= t <= t1:
                total += n - before
            before = n
        return total

    def ttft_ms(self) -> float:
        if self.failed or self.first is None:
            return math.inf
        return (self.first - self.due) * 1e3

    def tpot_ms(self) -> float:
        """(t_last - t_first) / (n_out - 1); a failed or unfinished request
        is missed; a one-token request has no inter-token time."""
        if self.failed or not self.done:
            return math.inf
        if self.n_out < 2:
            return math.nan
        return (self.last - self.first) / (self.n_out - 1) * 1e3


def serve_metrics(requests: Sequence[RequestTimes], t0: float,
                  t1: float) -> dict:
    """The serving cells' end-to-end metrics over the window [t0, t1].

    - tokens/s: output tokens delivered to the clients in the window, by
      finished and unfinished requests alike, over the window's whole time
      (at under one request a second, counting whole requests would quantize
      the rate by several per cent);
    - ttft p95: over every request whose first token was due in the window
      (first token arrived in it, or the request failed in it);
    - tpot p95: over every request that ended in the window.
    """
    ended = [r for r in requests
             if (r.done or r.failed) and r.last is not None
             and t0 <= r.last <= t1]
    completed = [r for r in ended if r.done and not r.failed]
    firsts = [r for r in requests
              if (r.first is not None and t0 <= r.first <= t1)
              or (r.failed and r.first is None)]
    tpots = [x for x in (r.tpot_ms() for r in ended) if not math.isnan(x)]
    out = {
        "serve_tokens_per_s": sum(r.delivered_in(t0, t1)
                                  for r in requests) / (t1 - t0),
        "completed": len(completed),
        "failed": sum(r.failed for r in requests),
        "ttft_samples": len(firsts),
        "tpot_samples": len(tpots),
    }
    if firsts:
        out["ttft_p95_ms"] = percentile([r.ttft_ms() for r in firsts], 95)
        out["ttft_p50_ms"] = percentile([r.ttft_ms() for r in firsts], 50)
    if tpots:
        out["tpot_p95_ms"] = percentile(tpots, 95)
        out["tpot_p50_ms"] = percentile(tpots, 50)
    return out


def iqr_share(values: Sequence[float]) -> float:
    """The contract's spread: (Q3 - Q1) / median with Python's quartiles."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
