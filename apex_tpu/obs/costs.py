"""Analytical roofline cost model over the lint harness's jaxprs.

The IR lint tier (``analysis/ir/harness.py``) already traces every real
entry point in the repo — kernels, fused optimizers, the serving
engine's admission/decode programs — into jaxprs on CPU, devicelessly.
This module walks those same jaxprs and prices them: per-equation FLOPs,
HBM bytes moved, peak live bytes, and arithmetic intensity, rolled up
into a per-program roofline estimate against a declared chip profile
(v5e by default: 394 TFLOP/s bf16, 819 GB/s HBM). The numbers are
modelled, not measured: deterministic functions of the staged programs,
so the cost ledger (``obs/ledger.py``) can gate on them exactly.

Counting conventions (fixed — the ledger's exactness depends on them
being revision-stable, not on them being cycle-accurate):

- ``dot_general``: ``2 · prod(batch) · prod(lhs free) · prod(rhs free)
  · prod(contract)`` FLOPs (multiply+add).
- elementwise primitives (transcendentals included): one FLOP per
  output element.
- reductions / cumulative ops: one FLOP per *operand* element.
- layout/movement ops (reshape, transpose, gather, slice, convert, …):
  zero FLOPs.
- HBM bytes: every non-literal operand read once + every result written
  once per execution — an upper bound under XLA fusion, but a
  *consistent* one, and exact for the weight/KV streams that dominate
  serving decode.
- ``scan`` bodies multiply by ``length`` (weights close over the body,
  so the weight stream is charged once per step — the physical HBM
  behavior of TPU decode); ``while`` bodies are charged one trip (noted
  in the report); ``cond`` charges its most expensive branch;
  ``pallas_call`` uses the kernel's declared ``cost_estimate`` when
  present, else walks the kernel jaxpr times the grid.
- peak live bytes: a liveness sweep over the top-level equation list
  (inner-jaxpr scratch is not modeled — pool/weight residency dominates
  every program here).
- all bytes are LOGICAL (what the program streams), not tiled-padded
  (what arrays occupy on chip). The padding math lives once, in
  ``apex_tpu/analysis/mem/layout.py``; the mem lint tier prices the
  padded side for HBM-fit proofs, and reports here carry a note when
  the two diverge materially.

``python -m apex_tpu.obs.costs`` emits the report (text, or ``--json``)
covering EVERY registered case, including the decode chunk's
weight-vs-KV byte split — the number behind docs/serving.md's
"weight-bound decode" claim.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["ChipProfile", "PROFILES", "EqnCost", "CaseCost",
           "cost_of_jaxpr", "cost_report", "decode_split",
           "tp_decode_split", "spec_decode_split", "host_tier_split",
           "ledger_metrics", "main"]

GIB = 1024 ** 3


# --------------------------------------------------------------------------
# chip profiles
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipProfile:
    """Peak rates for one accelerator. ``flops_per_sec`` is keyed by the
    model's dtype classes (``bf16`` covers fp16 too, ``int8`` the 8-bit
    integer MXU path, ``f32`` everything wider); unknown dtypes price at
    the f32 rate — conservative for the roofline.
    ``host_link_bytes_per_sec`` is the host<->device DMA stream (PCIe
    for the inference parts) the tiered KV pool's demote/promote copies
    ride — two orders of magnitude under HBM, which is exactly why the
    tier only ever moves whole pages at sync boundaries."""

    name: str
    flops_per_sec: Dict[str, float]
    hbm_bytes_per_sec: float
    hbm_bytes: int
    host_link_bytes_per_sec: float = 32e9

    def peak_flops(self, dtype_key: str) -> float:
        return self.flops_per_sec.get(dtype_key,
                                      self.flops_per_sec["f32"])


#: pluggable profile registry (``--profile``); numbers are the public
#: per-chip peak specs (host link: PCIe gen3 x16 ~32 GB/s on v5e/v4
#: hosts, gen4 x16 ~64 GB/s on v5p)
PROFILES: Dict[str, ChipProfile] = {
    "v5e": ChipProfile("v5e",
                       {"bf16": 394e12, "f32": 197e12, "int8": 788e12},
                       hbm_bytes_per_sec=819e9, hbm_bytes=16 * GIB,
                       host_link_bytes_per_sec=32e9),
    "v5p": ChipProfile("v5p",
                       {"bf16": 459e12, "f32": 229e12, "int8": 918e12},
                       hbm_bytes_per_sec=2765e9, hbm_bytes=95 * GIB,
                       host_link_bytes_per_sec=64e9),
    "v4": ChipProfile("v4",
                      {"bf16": 275e12, "f32": 137e12, "int8": 275e12},
                      hbm_bytes_per_sec=1228e9, hbm_bytes=32 * GIB,
                      host_link_bytes_per_sec=32e9),
}


def _dtype_key(dtype) -> str:
    name = str(getattr(dtype, "name", dtype))
    if name in ("bfloat16", "float16"):
        return "bf16"
    # extended dtypes (PRNG keys) have no ``kind`` — price at f32
    if getattr(dtype, "kind", "") in "iu" \
            and getattr(dtype, "itemsize", 0) == 1:
        return "int8"
    return "f32"


# --------------------------------------------------------------------------
# per-equation pricing
# --------------------------------------------------------------------------

#: primitives priced at one FLOP per OPERAND element
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "reduce_window_sum",
    "reduce_window_max",
})

#: pure data movement / layout — zero FLOPs, bytes still counted
_ZERO_FLOP_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "gather",
    "scatter", "convert_element_type", "bitcast_convert_type", "copy",
    "copy_p", "iota", "rev", "pad", "select_n", "stop_gradient",
    "device_put", "split", "expand_dims", "real", "imag",
    "reduce_precision", "clamp_gradient", "tie_in", "opt_barrier",
    "optimization_barrier",
    # pallas/state ref ops: loads/stores are data movement, not math
    "get", "swap", "load", "store", "masked_load", "masked_swap",
    "addupdate", "broadcast_to",
})

#: params that hold a sub-jaxpr in higher-order primitives we recurse
#: into generically (multiplier 1)
_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                 "body_jaxpr")


def _aval_elems(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _aval_bytes(aval) -> int:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return 0
    # extended dtypes (PRNG keys) have no itemsize; 4 B/elem is close
    # enough for what is always metadata-sized state
    itemsize = getattr(dt, "itemsize", 4)
    return _aval_elems(aval) * int(itemsize)


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


def _eqn_flops(eqn) -> int:
    """FLOPs of one leaf equation per the module's conventions."""
    name = eqn.primitive.name
    if name == "dot_general":
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        batch = 1
        for d in lb:
            batch *= int(lhs.shape[d])
        contract = 1
        for d in lc:
            contract *= int(lhs.shape[d])
        lhs_free = _aval_elems(lhs) // max(batch * contract, 1)
        rhs_free = _aval_elems(rhs) // max(batch * contract, 1)
        return 2 * batch * lhs_free * rhs_free * contract
    if name == "conv_general_dilated":
        out = eqn.outvars[0].aval
        rhs = eqn.invars[1].aval
        # 2 · output elements · kernel taps per output feature
        taps = _aval_elems(rhs) // max(int(rhs.shape[
            eqn.params["dimension_numbers"].rhs_spec[0]]), 1)
        return 2 * _aval_elems(out) * taps
    if name in _ZERO_FLOP_PRIMS:
        return 0
    if name in _REDUCE_PRIMS:
        return sum(_aval_elems(v.aval) for v in eqn.invars
                   if not _is_literal(v))
    # elementwise default: one FLOP per output element
    return sum(_aval_elems(v.aval) for v in eqn.outvars)


def _eqn_bytes(eqn) -> int:
    read = sum(_aval_bytes(v.aval) for v in eqn.invars
               if not _is_literal(v))
    written = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    return read + written


def _eqn_dtype_key(eqn) -> str:
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            return _dtype_key(dt)
    return "f32"


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

@dataclasses.dataclass
class EqnCost:
    """One leaf equation's aggregate cost (already multiplied through
    enclosing scan lengths / pallas grids)."""

    primitive: str
    flops: int
    bytes: int
    dtype_key: str
    count: int = 1
    anchor: Optional[Tuple[str, int]] = None     # (repo-rel file, line)


class _Walk:
    """Accumulator for one program: leaf costs keyed by
    (primitive, anchor) so repeated equations fold together."""

    def __init__(self, root: Optional[Path]):
        self.root = root
        self.leaves: Dict[Tuple[str, Optional[Tuple[str, int]], str],
                          EqnCost] = {}
        self.notes: List[str] = []

    def _anchor(self, eqn) -> Optional[Tuple[str, int]]:
        if self.root is None:
            return None
        from apex_tpu.analysis.ir.ir_report import eqn_anchor
        return eqn_anchor(eqn, self.root)

    def add(self, eqn, mult: int, flops: int, nbytes: int) -> None:
        key = (eqn.primitive.name, self._anchor(eqn), _eqn_dtype_key(eqn))
        leaf = self.leaves.get(key)
        if leaf is None:
            self.leaves[key] = EqnCost(
                primitive=key[0], flops=flops * mult, bytes=nbytes * mult,
                dtype_key=key[2], count=mult, anchor=key[1])
        else:
            leaf.flops += flops * mult
            leaf.bytes += nbytes * mult
            leaf.count += mult

    # -- recursion ---------------------------------------------------------

    def walk(self, jaxpr, mult: int = 1) -> None:
        for eqn in jaxpr.eqns:
            self._walk_eqn(eqn, mult)

    def _walk_eqn(self, eqn, mult: int) -> None:
        name = eqn.primitive.name
        if name == "shard_map":
            # the body's avals are the LOCAL shard shapes, so a sharded
            # program's flops/bytes price PER CHIP — the per-device
            # roofline a TP mesh actually runs (docs/tp_serving.md)
            self.notes.append(
                "shard_map body priced per chip (local shard shapes)")
            self.walk(eqn.params["jaxpr"], mult)
            return
        if name == "scan":
            length = int(eqn.params.get("length", 1))
            self.walk(eqn.params["jaxpr"].jaxpr, mult * length)
            return
        if name == "while":
            self.notes.append(
                "while loop charged one trip (trip count unknown)")
            self.walk(eqn.params["cond_jaxpr"].jaxpr, mult)
            self.walk(eqn.params["body_jaxpr"].jaxpr, mult)
            return
        if name == "cond":
            # charge the most expensive branch
            best: Optional[_Walk] = None
            best_cost = -1.0
            for br in eqn.params["branches"]:
                sub = _Walk(self.root)
                sub.walk(br.jaxpr, mult)
                cost = sum(l.flops + l.bytes for l in sub.leaves.values())
                if cost > best_cost:
                    best, best_cost = sub, cost
            if best is not None:
                self._merge(best)
            return
        if name == "pallas_call":
            self._walk_pallas(eqn, mult)
            return
        inner = [eqn.params[k] for k in _JAXPR_PARAMS if k in eqn.params]
        if not inner:
            # any other higher-order primitive: recurse into every
            # (Closed)Jaxpr-valued param rather than treating the call
            # as an opaque leaf
            for v in eqn.params.values():
                if hasattr(v, "eqns") \
                        or hasattr(getattr(v, "jaxpr", None), "eqns"):
                    inner.append(v)
        if inner:
            for sub in inner:
                self.walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub,
                          mult)
            return
        self.add(eqn, mult, _eqn_flops(eqn), _eqn_bytes(eqn))

    def _walk_pallas(self, eqn, mult: int) -> None:
        est = eqn.params.get("cost_estimate")
        nbytes = _eqn_bytes(eqn)     # operands/results cross HBM once
        if est is not None and getattr(est, "flops", None) is not None:
            flops = int(est.flops) + int(getattr(est, "transcendentals",
                                                 0) or 0)
            ba = getattr(est, "bytes_accessed", None)
            if ba:
                nbytes = int(ba)
            self.add(eqn, mult, flops, nbytes)
            return
        grid = 1
        gm = eqn.params.get("grid_mapping")
        for d in getattr(gm, "grid", ()) or ():
            if isinstance(d, int):
                grid *= d
        sub = _Walk(self.root)
        sub.walk(eqn.params["jaxpr"], mult * grid)
        kernel_flops = sum(l.flops for l in sub.leaves.values())
        self.add(eqn, mult, kernel_flops // max(mult, 1), nbytes)
        self.notes.extend(sub.notes)

    def _merge(self, other: "_Walk") -> None:
        for key, leaf in other.leaves.items():
            mine = self.leaves.get(key)
            if mine is None:
                self.leaves[key] = leaf
            else:
                mine.flops += leaf.flops
                mine.bytes += leaf.bytes
                mine.count += leaf.count
        self.notes.extend(other.notes)


def _peak_live_bytes(jaxpr) -> int:
    """Liveness sweep over the top-level equation list: a var is live
    from its definition (program entry for inputs/consts) to its last
    use (program exit for outputs). Inner-jaxpr scratch is not modeled."""
    last_use: Dict[object, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = n
    live_bytes: Dict[object, int] = {
        v: _aval_bytes(v.aval)
        for v in list(jaxpr.invars) + list(jaxpr.constvars)
        if v in last_use}
    cur = sum(live_bytes.values())
    peak = cur
    for i, eqn in enumerate(jaxpr.eqns):
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        peak = max(peak, cur + out_bytes)
        for v in eqn.outvars:
            if last_use.get(v, i) > i:
                live_bytes[v] = _aval_bytes(v.aval)
                cur += live_bytes[v]
        for v in eqn.invars:
            if not _is_literal(v) and last_use.get(v) == i \
                    and v in live_bytes:
                cur -= live_bytes.pop(v)
    return peak


# --------------------------------------------------------------------------
# per-case rollup
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CaseCost:
    name: str
    domain: str
    flops: int
    hbm_bytes: int
    peak_live_bytes: int
    arith_intensity: float
    flop_time_ms: float
    byte_time_ms: float
    predicted_ms: float
    bound: str                       # "compute" | "memory"
    by_primitive: Dict[str, Dict[str, int]]
    top_eqns: List[dict]
    notes: List[str]

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def cost_of_jaxpr(closed, profile: ChipProfile, *,
                  root: Optional[Path] = None, name: str = "<program>",
                  domain: str = "ops", top_k: int = 5) -> CaseCost:
    """Price one ClosedJaxpr against ``profile``. ``root`` enables
    source-line attribution (anchors resolved like IR lint findings).

    All byte counts here are LOGICAL — the bytes the program streams,
    which is what bandwidth/roofline math wants. On chip, arrays occupy
    their TPU tiled-layout PADDED size (minor dim to 128 lanes, second-
    minor to the dtype's sublane multiple); when that gap is material
    for the program's boundary arrays, a note says so and points at the
    mem lint tier, which prices the padded side (HBM *fit*, not
    bandwidth — ``apex_tpu/analysis/mem/layout.py`` is the one place
    the padding math lives)."""
    from apex_tpu.analysis.mem.layout import (aval_logical_bytes,
                                              aval_padded_bytes)

    w = _Walk(root)
    w.walk(closed.jaxpr)
    b_logical = b_padded = 0
    for v in list(closed.jaxpr.invars) + list(closed.jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None:
            b_logical += aval_logical_bytes(aval)
            b_padded += aval_padded_bytes(aval)
    if b_logical and b_padded >= 1.25 * b_logical:
        w.notes.append(
            f"tiled layout: boundary arrays occupy "
            f"{b_padded / GIB:.3f} GiB on chip vs {b_logical / GIB:.3f} "
            f"GiB logical ({b_padded / b_logical:.2f}x) — bytes here "
            f"price the logical stream; the mem lint tier prices the "
            f"padded residency")
    flops = sum(l.flops for l in w.leaves.values())
    nbytes = sum(l.bytes for l in w.leaves.values())
    flop_t = sum(l.flops / profile.peak_flops(l.dtype_key)
                 for l in w.leaves.values())
    byte_t = nbytes / profile.hbm_bytes_per_sec
    # roofline per equation: each leaf pays the slower of its two walls
    pred_s = sum(max(l.flops / profile.peak_flops(l.dtype_key),
                     l.bytes / profile.hbm_bytes_per_sec)
                 for l in w.leaves.values())
    by_prim: Dict[str, Dict[str, int]] = {}
    for leaf in w.leaves.values():
        slot = by_prim.setdefault(leaf.primitive,
                                  {"flops": 0, "bytes": 0, "count": 0})
        slot["flops"] += leaf.flops
        slot["bytes"] += leaf.bytes
        slot["count"] += leaf.count
    ranked = sorted(
        w.leaves.values(),
        key=lambda l: -max(l.flops / profile.peak_flops(l.dtype_key),
                           l.bytes / profile.hbm_bytes_per_sec))
    top = []
    for leaf in ranked[:top_k]:
        t_us = 1e6 * max(leaf.flops / profile.peak_flops(leaf.dtype_key),
                         leaf.bytes / profile.hbm_bytes_per_sec)
        top.append({
            "primitive": leaf.primitive, "flops": leaf.flops,
            "bytes": leaf.bytes, "count": leaf.count,
            "dtype": leaf.dtype_key, "predicted_us": round(t_us, 3),
            "file": leaf.anchor[0] if leaf.anchor else None,
            "line": leaf.anchor[1] if leaf.anchor else None,
        })
    return CaseCost(
        name=name, domain=domain, flops=flops, hbm_bytes=nbytes,
        peak_live_bytes=_peak_live_bytes(closed.jaxpr),
        arith_intensity=flops / nbytes if nbytes else 0.0,
        flop_time_ms=flop_t * 1e3, byte_time_ms=byte_t * 1e3,
        predicted_ms=pred_s * 1e3,
        bound="compute" if flop_t >= byte_t else "memory",
        by_primitive=by_prim, top_eqns=top,
        notes=sorted(set(w.notes)))


# --------------------------------------------------------------------------
# the decode chunk's weight-vs-KV byte split
# --------------------------------------------------------------------------

def _kv_step_bytes_max(cache):
    """Worst-case KV pool bytes one decode step reads: per layer, each
    slot's kernel reads its block-table row — at most
    ``max_pages_per_seq`` pages — bounded by the pool size (page 0 is
    the null sink). Page bytes derive from the pages array's ACTUAL
    dtype (``_aval_bytes``), so a quantized int8/fp8 pool prices 2-4x
    narrower than bf16/f32 without a special case; a quantized pool's
    per-(page, kv_head) scale reads (``k_scales``/``v_scales``, one f32
    row per page read) are counted on top. Returns ``(kv_bytes,
    pool_pages)``; shared by the single-chip and tensor-parallel splits
    so the bound can never drift between them."""
    num_slots, max_pages = cache["block_tables"].shape
    kv_step = 0
    pool_pages = None
    for layer in cache["layers"]:
        for key in ("k_pages", "v_pages"):
            pages = layer[key]
            pool_pages = int(pages.shape[0])
            page_bytes = _aval_bytes(pages) // pool_pages
            scales = layer.get(key[0] + "_scales")
            if scales is not None:
                page_bytes += _aval_bytes(scales) // pool_pages
            kv_step += min(pool_pages - 1, num_slots * max_pages) \
                * page_bytes
    return kv_step, pool_pages


def decode_split(prog) -> dict:
    """The serving decode chunk's per-step HBM traffic, split into the
    weight stream vs the (worst-case) KV page reads — computed from the
    case's abstract arguments, so docs/serving.md's "weight-bound
    decode" claim is a number, not prose. ``prog`` is the
    ``gpt2s_engine_decode_chunk`` CaseProgram (args: cache, variables,
    per-slot state)."""
    import jax

    cache, dvars = prog.args[0], prog.args[1]
    # per-LEAF bytes at each leaf's ACTUAL dtype (_aval_bytes), never
    # param-count x model dtype: a quantized tree (int8/fp8 weights, f32
    # scale rows, uint8 int4 nibbles) reports its true stream, scale
    # reads included — the w8/w4 ratio pins divide these numbers
    weight_bytes = sum(_aval_bytes(leaf)
                      for leaf in jax.tree.leaves(dvars))
    num_slots, max_pages = cache["block_tables"].shape
    kv_step, pool_pages = _kv_step_bytes_max(cache)
    total = weight_bytes + kv_step
    return {
        "weight_bytes_per_step": int(weight_bytes),
        "kv_bytes_per_step_max": int(kv_step),
        "weight_fraction": weight_bytes / total if total else 0.0,
        "num_slots": int(num_slots), "max_pages_per_seq": int(max_pages),
        "pool_pages": pool_pages,
    }


def tp_decode_split(prog, profile: ChipProfile,
                    tp_worlds=(1, 2, 4)) -> dict:
    """Per-CHIP HBM traffic of the tensor-parallel decode chunk at
    tp = 1/2/4 — the sharding story as numbers (docs/tp_serving.md):
    head-sharded weights and K/V pages divide by ``tp``, replicated
    leaves (norms, biases, position table) do not, so both the per-chip
    byte stream and the weight fraction are computed, not prose.
    ``prog`` is the ``tp2_engine_decode_chunk`` CaseProgram; its
    builder-attached ``meta`` carries the sharded/replicated weight
    split (``analysis/ir/harness.py`` — the jaxpr alone cannot say
    which leaf shards). Also prices the mesh-tp per-chip step against
    ``profile``'s HBM bandwidth (decode is memory-bound) — the banded
    ledger metric ``tp2.paged_decode.predicted_step_ms``."""
    meta = prog.meta or {}
    cache = prog.args[0]
    num_slots = cache["block_tables"].shape[0]
    kv_step_total, pool_pages = _kv_step_bytes_max(cache)
    sharded_w = int(meta["sharded_weight_bytes"])
    repl_w = int(meta["replicated_weight_bytes"])
    mesh_tp = int(meta["tp"])
    per_tp = {}
    for tp in tp_worlds:
        w = sharded_w / tp + repl_w
        kv = kv_step_total / tp
        total = w + kv
        per_tp[str(tp)] = {
            "weight_bytes_per_chip_per_step": int(w),
            "kv_bytes_per_chip_per_step_max": int(kv),
            "hbm_bytes_per_chip_per_step": int(total),
            "weight_fraction": w / total if total else 0.0,
        }
    at_mesh = per_tp[str(mesh_tp)]
    predicted_ms = (at_mesh["hbm_bytes_per_chip_per_step"]
                    / profile.hbm_bytes_per_sec * 1e3)
    return {
        "tp_mesh": mesh_tp,
        "num_slots": int(num_slots),
        "pool_pages": pool_pages,
        "per_tp": per_tp,
        "predicted_step_ms_per_chip": predicted_ms,
    }


def spec_decode_split(prog, profile: ChipProfile) -> dict:
    """The speculative round's weight economics (ISSUE 13): one round
    streams the target weights ONCE (the ``s = k`` verify step) plus
    the draft weights ``k`` times (the draft scan), and emits between 1
    and ``k`` accepted tokens — so the per-ACCEPTED-token weight stream
    is ``(W_target + k * W_draft) / a`` at acceptance length ``a``.
    Decode is weight-bound (``decode_split``), so this ratio against
    the non-speculative per-token stream (``W_target``) IS the speedup
    model: speculation pays whenever ``k * W_draft < (a - 1) *
    W_target``. ``prog`` is the ``gpt2s_engine_spec_step_chunk``
    CaseProgram; its builder-attached ``meta`` carries the two weight
    byte counts and ``k`` (``analysis/ir/harness.py``). Also prices the
    per-acceptance-point round time against ``profile``'s HBM
    bandwidth — the banded ledger metrics
    ``spec_decode.predicted_step_ms_a<a>``."""
    meta = prog.meta or {}
    k = int(meta["k"])
    target_w = int(meta["target_weight_bytes"])
    draft_w = int(meta["draft_weight_bytes"])
    cache, dcache = prog.args[0], prog.args[1]
    kv_target, _ = _kv_step_bytes_max(cache)
    kv_draft, _ = _kv_step_bytes_max(dcache)
    # per round: one target verify pass + k draft passes, each reading
    # its pool's worst-case pages
    round_bytes = (target_w + kv_target) + k * (draft_w + kv_draft)
    round_weight = target_w + k * draft_w
    per_acceptance = {}
    for a in range(1, k + 1):
        per_acceptance[str(a)] = {
            "weight_bytes_per_accepted_token": int(round_weight // a),
            "hbm_bytes_per_accepted_token": int(round_bytes // a),
            "predicted_step_ms": (round_bytes / a
                                  / profile.hbm_bytes_per_sec * 1e3),
        }
    return {
        "k": k, "draft_len": k - 1,
        "target_weight_bytes": target_w,
        "draft_weight_bytes": draft_w,
        "round_weight_bytes": int(round_weight),
        "round_hbm_bytes": int(round_bytes),
        "per_acceptance": per_acceptance,
        # the breakeven acceptance length: smallest a whose per-token
        # weight stream beats the non-speculative W_target
        "breakeven_acceptance": next(
            (a for a in range(1, k + 1)
             if round_weight // a < target_w), None),
    }


def host_tier_split(prog, profile: ChipProfile) -> dict:
    """The tiered KV pool's host-link DMA stream (ISSUE 17): one
    demote (``gather_pages``) or promote (``promote_pages``) moves a
    null-padded ``HOST_COPY_CHUNK`` batch of pages' K/V tiles — plus
    per-(page, kv_head) scale rows on quantized pools — across the
    host link, priced against ``profile.host_link_bytes_per_sec``
    rather than HBM. ``prog`` is the ``gpt2s_host_tier_gather``
    CaseProgram (args: cache, page row); the chunk bytes are the
    gather's output tree evaluated abstractly off the cache leaves, so
    the number tracks the pool dtype (an int8 pool moves narrow tiles
    and f32 scales). The chunk time is what one promote adds to the
    admission it extends — the banded ledger metric
    ``host_tier.promote_chunk_predicted_ms``."""
    import jax

    from apex_tpu.serving import kv_pool

    cache, row = prog.args[0], prog.args[1]
    tiles = jax.eval_shape(kv_pool.gather_pages, cache, row)
    chunk_bytes = sum(_aval_bytes(leaf)
                      for leaf in jax.tree.leaves(tiles))
    chunk_pages = int(row.shape[0])
    dma_ms = chunk_bytes / profile.host_link_bytes_per_sec * 1e3
    return {
        "chunk_pages": chunk_pages,
        "chunk_bytes": int(chunk_bytes),
        "bytes_per_page": int(chunk_bytes // chunk_pages),
        "host_link_bytes_per_sec": float(profile.host_link_bytes_per_sec),
        "predicted_chunk_dma_ms": dma_ms,
    }


# --------------------------------------------------------------------------
# whole-registry report
# --------------------------------------------------------------------------

def cost_report(root, *, profile: str = "v5e", case: Optional[str] = None,
                top_k: int = 5) -> dict:
    """Trace every registered analysis case (or one, ``case=``) and
    price it. Returns the JSON-ready report document; a case that fails
    to trace lands in ``errors`` instead of killing the run."""
    from apex_tpu.analysis.ir.harness import analysis_cases, build_case_ir

    root = Path(root).resolve()
    prof = PROFILES[profile]
    cases = analysis_cases(root)
    if case is not None:
        cases = [c for c in cases if c.name == case]
        if not cases:
            raise ValueError(f"unknown case: {case}")
    out_cases: List[dict] = []
    errors: List[dict] = []
    split = None
    tp_split = None
    spec_split = None
    int8kv_split = None
    int8kv_tp_split = None
    w8_split = None
    w4_split = None
    w8_tp_split = None
    host_split = None
    for c in cases:
        try:
            ir = build_case_ir(c)
            cost = cost_of_jaxpr(ir.closed, prof, root=root, name=c.name,
                                 domain=c.domain, top_k=top_k)
            out_cases.append(cost.to_json())
            if c.name == "gpt2s_engine_decode_chunk":
                # per-STEP split, read straight off the abstract args
                split = decode_split(ir.prog)
            if c.name == "tp2_engine_decode_chunk":
                # per-CHIP split of the SHARDED decode chunk
                tp_split = tp_decode_split(ir.prog, prof)
            if c.name == "gpt2s_engine_spec_step_chunk":
                # per-ACCEPTED-TOKEN split of the speculative round
                spec_split = spec_decode_split(ir.prog, prof)
            if c.name == "gpt2s_int8kv_engine_decode_chunk":
                # same split over the QUANTIZED pool: the narrow KV
                # stream + scale reads (docs/serving.md)
                int8kv_split = decode_split(ir.prog)
            if c.name == "tp2_int8kv_engine_decode_chunk":
                int8kv_tp_split = tp_decode_split(ir.prog, prof)
            if c.name == "gpt2s_w8_engine_decode_chunk":
                # split over the QUANTIZED weight tree: int8 block
                # linears + f32 scale rows, fp everything else — the
                # per-leaf dtype bytes ARE the narrow stream
                w8_split = decode_split(ir.prog)
            if c.name == "gpt2s_w4_engine_decode_chunk":
                w4_split = decode_split(ir.prog)
            if c.name == "tp2_w8_engine_decode_chunk":
                w8_tp_split = tp_decode_split(ir.prog, prof)
            if c.name == "gpt2s_host_tier_gather":
                # the demote/promote DMA chunk over the host link
                host_split = host_tier_split(ir.prog, prof)
        except Exception as e:       # noqa: BLE001 — report, don't crash
            errors.append({"case": c.name,
                           "error": f"{type(e).__name__}: {e}"})
    totals = {
        "flops": sum(c["flops"] for c in out_cases),
        "hbm_bytes": sum(c["hbm_bytes"] for c in out_cases),
        "predicted_ms": sum(c["predicted_ms"] for c in out_cases),
    }
    by_domain: Dict[str, Dict[str, float]] = {}
    for c in out_cases:
        slot = by_domain.setdefault(
            c["domain"], {"flops": 0, "hbm_bytes": 0, "predicted_ms": 0.0,
                          "cases": 0})
        slot["flops"] += c["flops"]
        slot["hbm_bytes"] += c["hbm_bytes"]
        slot["predicted_ms"] += c["predicted_ms"]
        slot["cases"] += 1
    return {"schema": 1, "profile": dataclasses.asdict(prof),
            "root": str(root), "cases": out_cases, "totals": totals,
            "by_domain": by_domain, "decode_split": split,
            "tp_decode_split": tp_split,
            "spec_decode_split": spec_split,
            "int8kv_decode_split": int8kv_split,
            "int8kv_tp_decode_split": int8kv_tp_split,
            "w8_decode_split": w8_split,
            "w4_decode_split": w4_split,
            "w8_tp_decode_split": w8_tp_split,
            "host_tier_split": host_split,
            "errors": errors}


def ledger_metrics(report: dict) -> Dict[str, float]:
    """Flatten a report into the deterministic ``cost.*`` metric set the
    perf ledger stores and gates on exactly."""
    m: Dict[str, float] = {
        "cost.total_flops": float(report["totals"]["flops"]),
        "cost.total_hbm_bytes": float(report["totals"]["hbm_bytes"]),
        "cost.total_predicted_ms": float(report["totals"]["predicted_ms"]),
    }
    for dom, slot in sorted(report.get("by_domain", {}).items()):
        m[f"cost.domain.{dom}.predicted_ms"] = float(slot["predicted_ms"])
    for c in report["cases"]:
        m[f"cost.case.{c['name']}.flops"] = float(c["flops"])
        m[f"cost.case.{c['name']}.predicted_ms"] = float(c["predicted_ms"])
    split = report.get("decode_split")
    if split:
        m["cost.decode.weight_bytes_per_step"] = \
            float(split["weight_bytes_per_step"])
        m["cost.decode.kv_bytes_per_step_max"] = \
            float(split["kv_bytes_per_step_max"])
        m["cost.decode.weight_fraction"] = float(split["weight_fraction"])
    tsplit = report.get("tp_decode_split")
    if tsplit:
        for tp, slot in sorted(tsplit["per_tp"].items()):
            m[f"cost.tp_decode.hbm_bytes_per_chip_per_step_tp{tp}"] = \
                float(slot["hbm_bytes_per_chip_per_step"])
            m[f"cost.tp_decode.weight_fraction_tp{tp}"] = \
                float(slot["weight_fraction"])
        # deliberately NOT cost.*-prefixed: the per-chip step time is the
        # tp2 serving headline and gates on the direction-aware ±band
        # (lower-better "_ms"), not the exact-match ratchet
        m["tp2.paged_decode.predicted_step_ms"] = \
            float(tsplit["predicted_step_ms_per_chip"])
    qsplit = report.get("int8kv_decode_split")
    if qsplit:
        m["cost.decode.int8_kv.kv_bytes_per_step_max"] = \
            float(qsplit["kv_bytes_per_step_max"])
        m["cost.decode.int8_kv.weight_fraction"] = \
            float(qsplit["weight_fraction"])
        if split:
            # the PR's acceptance number: the narrow pool's per-step KV
            # stream as a fraction of the fp pool's (<= 0.55 pinned by
            # tests/test_quantized_kv.py)
            m["cost.decode.int8_kv.kv_bytes_ratio_vs_fp"] = \
                float(qsplit["kv_bytes_per_step_max"]) / \
                float(split["kv_bytes_per_step_max"])
    qtsplit = report.get("int8kv_tp_decode_split")
    if qtsplit:
        for tp, slot in sorted(qtsplit["per_tp"].items()):
            m[f"cost.tp_decode.int8_kv.kv_bytes_per_chip_per_step_tp"
              f"{tp}"] = float(slot["kv_bytes_per_chip_per_step_max"])
            m[f"cost.tp_decode.int8_kv.weight_fraction_tp{tp}"] = \
                float(slot["weight_fraction"])
    wsplit = report.get("w8_decode_split")
    if wsplit:
        m["cost.decode.w8.weight_bytes_per_step"] = \
            float(wsplit["weight_bytes_per_step"])
        m["cost.decode.w8.weight_fraction"] = \
            float(wsplit["weight_fraction"])
        if split:
            # the PR's acceptance number: the quantized tree's per-step
            # weight stream as a fraction of the fp tree's (<= 0.55
            # pinned by tests/test_quantized_weights.py)
            m["cost.decode.w8.weight_bytes_ratio_vs_bf16"] = \
                float(wsplit["weight_bytes_per_step"]) / \
                float(split["weight_bytes_per_step"])
    w4split = report.get("w4_decode_split")
    if w4split:
        m["cost.decode.w4.weight_bytes_per_step"] = \
            float(w4split["weight_bytes_per_step"])
        if split:
            # int4 nibbles + per-group scale reads, vs the same fp tree
            # (<= 0.35 pinned by tests/test_quantized_weights.py)
            m["cost.decode.w4.weight_bytes_ratio_vs_bf16"] = \
                float(w4split["weight_bytes_per_step"]) / \
                float(split["weight_bytes_per_step"])
    wtsplit = report.get("w8_tp_decode_split")
    if wtsplit:
        for tp, slot in sorted(wtsplit["per_tp"].items()):
            m[f"cost.tp_decode.w8.hbm_bytes_per_chip_per_step_tp{tp}"] = \
                float(slot["hbm_bytes_per_chip_per_step"])
            m[f"cost.tp_decode.w8.weight_fraction_tp{tp}"] = \
                float(slot["weight_fraction"])
    hsplit = report.get("host_tier_split")
    if hsplit:
        m["cost.decode.host_tier.chunk_bytes"] = \
            float(hsplit["chunk_bytes"])
        m["cost.decode.host_tier.bytes_per_page"] = \
            float(hsplit["bytes_per_page"])
        # same banding rationale as tp2.paged_decode above: the promote
        # chunk's host-link DMA span is a headline ms and gates on the
        # direction-aware band, not the exact-match ratchet
        m["host_tier.promote_chunk_predicted_ms"] = \
            float(hsplit["predicted_chunk_dma_ms"])
    ssplit = report.get("spec_decode_split")
    if ssplit:
        m["cost.spec_decode.k"] = float(ssplit["k"])
        m["cost.spec_decode.round_weight_bytes"] = \
            float(ssplit["round_weight_bytes"])
        m["cost.spec_decode.round_hbm_bytes"] = \
            float(ssplit["round_hbm_bytes"])
        for a, slot in sorted(ssplit["per_acceptance"].items()):
            m[f"cost.spec_decode.weight_bytes_per_token_a{a}"] = \
                float(slot["weight_bytes_per_accepted_token"])
            # same banding rationale as tp2.paged_decode above: the
            # per-acceptance-point round time is a headline, not a hash
            m[f"spec_decode.predicted_step_ms_a{a}"] = \
                float(slot["predicted_step_ms"])
    return m


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def _fmt_qty(v: float, unit: str = "") -> str:
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"),
                          (1e3, "K")):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{suffix}{unit}"
    return f"{v:.1f}{unit}"


def _text_report(report: dict) -> str:
    prof = report["profile"]
    lines = [
        f"apex-tpu cost model — profile {prof['name']} "
        f"({prof['flops_per_sec']['bf16'] / 1e12:.0f} TFLOP/s bf16, "
        f"{prof['hbm_bytes_per_sec'] / 1e9:.0f} GB/s HBM)",
        "",
        f"{'case':44s} {'domain':10s} {'flops':>9s} {'bytes':>9s} "
        f"{'AI':>7s} {'pred':>9s} bound",
    ]
    for c in sorted(report["cases"], key=lambda c: -c["predicted_ms"]):
        lines.append(
            f"{c['name']:44s} {c['domain']:10s} "
            f"{_fmt_qty(c['flops']):>9s} {_fmt_qty(c['hbm_bytes'], 'B'):>9s} "
            f"{c['arith_intensity']:7.2f} {c['predicted_ms']:8.3f}ms "
            f"{c['bound']}")
    t = report["totals"]
    lines += ["", f"total: {_fmt_qty(t['flops'])} flops, "
                  f"{_fmt_qty(t['hbm_bytes'], 'B')} moved, "
                  f"{t['predicted_ms']:.3f} ms predicted across "
                  f"{len(report['cases'])} programs"]
    split = report.get("decode_split")
    if split:
        lines += [
            "",
            "decode chunk per-step HBM traffic "
            f"(slots={split['num_slots']}):",
            f"  weights {_fmt_qty(split['weight_bytes_per_step'], 'B')} "
            f"vs KV <= {_fmt_qty(split['kv_bytes_per_step_max'], 'B')} "
            f"-> weight fraction {split['weight_fraction']:.3f} "
            "(weight-bound decode, docs/serving.md)",
        ]
    qsplit = report.get("int8kv_decode_split")
    if qsplit:
        ratio = (qsplit["kv_bytes_per_step_max"]
                 / split["kv_bytes_per_step_max"]) if split else None
        lines.append(
            "  int8-kv pool: KV <= "
            f"{_fmt_qty(qsplit['kv_bytes_per_step_max'], 'B')}/step"
            + (f" ({ratio:.3f}x the fp pool's stream, scales included)"
               if ratio is not None else ""))
    tsplit = report.get("tp_decode_split")
    if tsplit:
        lines += [
            "",
            "tensor-parallel decode chunk, per-chip HBM/step "
            f"(slots={tsplit['num_slots']}, mesh tp={tsplit['tp_mesh']}):",
        ]
        for tp, slot in sorted(tsplit["per_tp"].items(), key=lambda kv:
                               int(kv[0])):
            lines.append(
                f"  tp={tp}: weights "
                f"{_fmt_qty(slot['weight_bytes_per_chip_per_step'], 'B')}"
                f" + KV <= "
                f"{_fmt_qty(slot['kv_bytes_per_chip_per_step_max'], 'B')}"
                f" = {_fmt_qty(slot['hbm_bytes_per_chip_per_step'], 'B')}"
                f"/chip/step, weight fraction "
                f"{slot['weight_fraction']:.3f}")
        lines.append(
            f"  predicted step @ mesh tp: "
            f"{tsplit['predicted_step_ms_per_chip']:.3f} ms/chip "
            "(HBM-bound)")
    ssplit = report.get("spec_decode_split")
    if ssplit:
        lines += [
            "",
            "speculative round, per-accepted-token weight stream "
            f"(k={ssplit['k']}, round "
            f"{_fmt_qty(ssplit['round_weight_bytes'], 'B')} weights):",
        ]
        for a, slot in sorted(ssplit["per_acceptance"].items(),
                              key=lambda kv: int(kv[0])):
            lines.append(
                f"  a={a}: "
                f"{_fmt_qty(slot['weight_bytes_per_accepted_token'], 'B')}"
                f"/token, {slot['predicted_step_ms']:.3f} ms "
                f"(non-spec {_fmt_qty(ssplit['target_weight_bytes'], 'B')}"
                "/token)")
        lines.append(
            f"  breakeven acceptance: {ssplit['breakeven_acceptance']} "
            "(docs/serving.md)")
    top = []
    for c in report["cases"]:
        for e in c["top_eqns"]:
            top.append((e["predicted_us"], c["name"], e))
    top.sort(key=lambda t: -t[0])
    if top:
        lines += ["", "top equations (roofline time):"]
        for t_us, cname, e in top[:10]:
            where = f"{e['file']}:{e['line']}" if e["file"] else "<jax>"
            lines.append(
                f"  {t_us:10.1f}us {e['primitive']:18s} "
                f"x{e['count']:<5d} {_fmt_qty(e['flops']):>9s} "
                f"{_fmt_qty(e['bytes'], 'B'):>9s}  {cname}  {where}")
    for err in report["errors"]:
        lines.append(f"ERROR {err['case']}: {err['error']}")
    return "\n".join(lines) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser(
        prog="python -m apex_tpu.obs.costs",
        description="Roofline cost report over every lint-harness "
                    "program (docs/observability.md)")
    parser.add_argument("--root", default=None,
                        help="repo root (default: the package's repo)")
    parser.add_argument("--profile", default="v5e",
                        choices=sorted(PROFILES))
    parser.add_argument("--case", default=None,
                        help="price a single registered case")
    parser.add_argument("--top-k", type=int, default=5)
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="also write the full JSON report")
    args = parser.parse_args(argv)
    root = Path(args.root) if args.root \
        else Path(__file__).resolve().parents[2]
    report = cost_report(root, profile=args.profile, case=args.case,
                         top_k=args.top_k)
    sys.stdout.write(_text_report(report))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"[costs] JSON report written to {args.json}")
    return 1 if report["errors"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
