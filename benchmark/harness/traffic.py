"""The one general traffic generator: every mix is a data file it reads.

Copied in idea from ``apex_tpu/serving/scenarios/traces.py`` (``Arrival``,
``Lengths``), with one change the contract asks for: a seed does not change
the work, only its order.  Lengths are the fixed quantiles of the mix's
distributions over a ``cycle`` of requests; the seed permutes each cycle and
draws the token ids.

Serving mix (``kind: "serve"``)::

    arrival        {"kind": "closed", "clients": 16, "think_s": 0.0}
                   {"kind": "poisson", "rate_rps": 4.0}
    prompt_lengths {"96": 0.15, ...}           discrete, by share
    output_lengths {"kind": "lognormal", "mean": 128, "sigma": 0.5,
                    "lo": 32, "hi": 256}  | {"kind": "fixed", "mean": 16}
    shared_prefix  {"tenants": 8, "tokens": 512}      optional
    cycle          requests per cycle

Training mix (``kind: "train"``): ``batch``, ``seq_len``, ``mlm_per_seq``
and the optimizer's hyper-parameters; the generator makes the batches.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, Iterator, List, Optional

import numpy as np

from benchmark.harness.weights import host_rng


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request of a serving mix."""

    index: int
    prompt_len: int
    new_tokens: int
    tenant: int = -1                 # >= 0: starts with that tenant's prefix
    due_s: Optional[float] = None    # open loop: offset from the start


def _apportion(shares: Dict[str, float], n: int) -> List[int]:
    """``n`` values in the given shares (largest remainder)."""
    keys = sorted(shares, key=int)
    total = sum(shares.values())
    exact = [shares[k] / total * n for k in keys]
    counts = [int(x) for x in exact]
    by_rest = sorted(range(len(keys)), key=lambda i: exact[i] - counts[i],
                     reverse=True)
    for i in by_rest[:n - sum(counts)]:
        counts[i] += 1
    return [int(k) for k, c in zip(keys, counts) for _ in range(c)]


def _quantile_lengths(dist: dict, n: int) -> List[int]:
    kind = dist.get("kind", "lognormal")
    if kind == "fixed":
        return [int(dist["mean"])] * n
    if kind == "discrete":
        return _apportion(dist["shares"], n)
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    sigma = float(dist["sigma"])
    mu = np.log(float(dist["mean"])) - sigma ** 2 / 2.0
    norm = statistics.NormalDist()
    vals = [np.exp(mu + sigma * norm.inv_cdf((i + 0.5) / n))
            for i in range(n)]
    return [int(np.clip(int(v), dist["lo"], dist["hi"])) for v in vals]


def cycle_shapes(mix: dict) -> List[tuple]:
    """The ``cycle`` (prompt_len, new_tokens, tenant) triples every seed
    serves, paired by a fixed permutation that belongs to the mix."""
    n = int(mix["cycle"])
    prompts = _apportion(mix["prompt_lengths"], n)
    outs = _quantile_lengths(mix["output_lengths"], n)
    pairing = np.random.default_rng(int(mix.get("pairing", 0))).permutation(n)
    outs = [outs[i] for i in pairing]
    shared = mix.get("shared_prefix")
    tenants = [(i % shared["tenants"]) if shared else -1 for i in range(n)]
    return list(zip(prompts, outs, tenants))


def distinct_prompt_lengths(mix: dict) -> List[int]:
    return sorted({p for p, _, _ in cycle_shapes(mix)})


class ServeTraffic:
    """Requests of one serving mix for one seed, cycle after cycle."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.shapes = cycle_shapes(mix)
        self._order = host_rng(seed, "order")
        self._tokens = host_rng(seed, "tokens")
        self._arrivals = host_rng(seed, "arrivals")
        shared = mix.get("shared_prefix")
        self.prefixes = []
        if shared:
            self.prefixes = [self._ids(shared["tokens"])
                             for _ in range(shared["tenants"])]
        self._clock = 0.0

    def _ids(self, n: int) -> np.ndarray:
        # ids 4.. keep clear of the special tokens a tokenizer reserves
        return self._tokens.integers(4, self.vocab, n).astype(np.int32)

    def prompt(self, spec: Spec) -> np.ndarray:
        if spec.tenant < 0:
            return self._ids(spec.prompt_len)
        head = self.prefixes[spec.tenant][:spec.prompt_len]
        return np.concatenate([head, self._ids(spec.prompt_len - len(head))])

    def __iter__(self) -> Iterator[Spec]:
        arrival = self.mix["arrival"]
        index = 0
        while True:
            for j in self._order.permutation(len(self.shapes)):
                p, o, tenant = self.shapes[j]
                due = None
                if arrival["kind"] == "poisson":
                    self._clock += self._arrivals.exponential(
                        1.0 / arrival["rate_rps"])
                    due = self._clock
                yield Spec(index, p, o, tenant, due)
                index += 1


def train_hyper(mix: dict) -> dict:
    """The optimizer's hyper-parameters of a training mix, by the names the
    plain reference's LAMB step reads."""
    return {"lr": mix["lr"], "beta1": mix["betas"][0],
            "beta2": mix["betas"][1], "eps": mix["eps"],
            "weight_decay": mix["weight_decay"],
            "max_grad_norm": mix["max_grad_norm"]}


def train_batches(mix: dict, vocab: int, type_vocab: int, seed: int,
                  count: int) -> List[Dict[str, np.ndarray]]:
    """``count`` host batches of one training mix; every row differs.

    The same fields ``apex_tpu.models.synthetic_batch`` makes (the gathered
    max_predictions_per_seq view included), as numpy arrays: the feed
    ``device_put``s one per step."""
    rng = host_rng(seed, "batches")
    b, s, k = int(mix["batch"]), int(mix["seq_len"]), int(mix["mlm_per_seq"])
    out = []
    for _ in range(count):
        ids = rng.integers(4, vocab, size=(b, s))
        positions = np.sort(
            np.argsort(rng.random((b, s)), axis=1)[:, :k], axis=1)
        gathered = np.take_along_axis(ids, positions, axis=1)
        dense = np.zeros_like(ids)
        np.put_along_axis(dense, positions, gathered, axis=1)
        out.append({
            "input_ids": ids.astype(np.int32),
            "token_type_ids": rng.integers(0, type_vocab, size=(b, s))
            .astype(np.int32),
            "attention_mask": np.ones((b, s), np.int32),
            "mlm_labels": dense.astype(np.int32),
            "mlm_positions": positions.astype(np.int32),
            "mlm_gathered_labels": gathered.astype(np.int32),
            "nsp_labels": rng.integers(0, 2, size=(b,)).astype(np.int32),
        })
    return out
