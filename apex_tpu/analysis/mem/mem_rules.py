"""Rule definitions for the tpu-lint mem tier (``mem-*`` namespace).

Eight rules over one :class:`MemContext` (a traced case + its static
memory estimate + its declared budget):

fit proofs
    ``mem-hbm-over-budget``        raw padded peak exceeds the chip
    ``mem-scan-carry-double-buffer``  fits, until the scan's double-
                                   buffered carry is charged (the
                                   docs/tp_serving.md pool-sizing rule)
    ``mem-vmem-over-budget``       a pallas_call's blocks overflow the
                                   16 MiB scoped-VMEM stack
    ``mem-padding-blowup``         an array pays >= 2x its logical
                                   bytes in tile padding (the d=64 pool)

sharding contracts
    ``mem-spec-indivisible``       declared spec axes don't divide the
                                   mesh (caught BEFORE shard_map's own
                                   opaque trace error)
    ``mem-replicated-no-collective``  a replicated output depends on a
                                   sharded input with no collective on
                                   the path (check_vma=False hides it)
    ``mem-donation-spec-mismatch`` a donated sharded buffer has no
                                   same-spec output to alias in place
    ``mem-scale-shard-drift``      a quantization scale doesn't shard
                                   with its weight's axis (PR 16
                                   invariant)

The two HBM rules are deliberately DISJOINT: over-budget fires only
when the no-double-buffer peak already misses, the scan-carry rule only
when double buffering is the difference — so each failure names the
lesson that was violated, and each rule is individually load-bearing.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from apex_tpu.analysis.mem.estimator import (MemEstimate, ShardMapInfo,
                                             VMEM_BUDGET_BYTES)

GIB = 1024 ** 3
MIB = 1024 ** 2

#: padding-blowup thresholds: ratio is the lesson (2x), the waste floor
#: keeps lint-scale fixtures (tiny pools, small tables) quiet — the rule
#: is about buffers that matter to a 16 GiB chip
PAD_BLOWUP_RATIO = 2.0
PAD_BLOWUP_MIN_WASTE_BYTES = 64 * MIB

#: primitives that make a sharded value consistent across the axis —
#: crossing one of these blesses a replicated output's data path
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "all_gather", "all_gather_invariant", "all_to_all",
    "ppermute", "pbroadcast", "psum_scatter", "reduce_scatter", "pmin",
    "pmax", "pgather",
})


def _fmt_bytes(n: int) -> str:
    if n >= GIB:
        return f"{n / GIB:.2f} GiB"
    return f"{n / MIB:.1f} MiB"


@dataclasses.dataclass
class MemContext:
    """Everything one rule may consult for one case."""

    ir: object                    # CaseIR
    est: MemEstimate
    budget_bytes: int
    budget_label: str             # "v5e" / "declared"

    @property
    def meta(self) -> dict:
        return self.ir.prog.meta or {}

    def aligned_leaves(self) -> Optional[List[
            Tuple[str, object, Dict[int, Tuple[str, ...]]]]]:
        """``(path_label, global_aval, {dim: axes})`` per argument leaf,
        via the whole-program shard_map whose operand count matches the
        flattened argument tree — None when there is no such alignment
        (non-sharded program, or consts broke positionality)."""
        leaves = self.est.arg_leaves
        if not leaves:
            return None
        for info in self.est.shard_maps:
            if len(info.in_names) != len(leaves) or \
                    len(info.eqn.invars) != len(leaves):
                continue
            return [(label, info.eqn.invars[i].aval, info.in_axes(i))
                    for i, (label, _leaf, _arg) in enumerate(leaves)]
        return None


@dataclasses.dataclass
class RawMemFinding:
    """Pre-anchor finding: the report maps ``eqn`` through source_info
    (case-origin fallback when None)."""

    message: str
    eqn: object = None


@dataclasses.dataclass
class MemRule:
    name: str
    severity: str
    summary: str
    check: Callable[[MemContext], List[RawMemFinding]]


MEM_RULES: Dict[str, MemRule] = {}


def mem_rule(name: str, severity: str, summary: str):
    def wrap(fn):
        MEM_RULES[name] = MemRule(name, severity, summary, fn)
        return fn
    return wrap


# --------------------------------------------------------------------------
# fit proofs
# --------------------------------------------------------------------------

@mem_rule("mem-hbm-over-budget", "error",
          "static per-chip peak HBM (tiled-padded, liveness-swept) "
          "exceeds the case's declared chip budget")
def _hbm_over_budget(ctx: MemContext) -> List[RawMemFinding]:
    est = ctx.est
    if est.peak_no_db_bytes <= ctx.budget_bytes:
        return []
    return [RawMemFinding(
        f"{est.scope} peak HBM {_fmt_bytes(est.peak_no_db_bytes)} "
        f"(tiled-padded, before scan double-buffering) exceeds the "
        f"{ctx.budget_label} budget {_fmt_bytes(ctx.budget_bytes)} — "
        f"shard further, quantize, or shrink the resident state")]


@mem_rule("mem-scan-carry-double-buffer", "error",
          "the program fits only if XLA's double-buffered scan carry is "
          "ignored — the docs/tp_serving.md pool-sizing rule")
def _scan_carry_double_buffer(ctx: MemContext) -> List[RawMemFinding]:
    est = ctx.est
    if not (est.peak_no_db_bytes <= ctx.budget_bytes < est.peak_bytes):
        return []
    return [RawMemFinding(
        f"{est.scope} peak {_fmt_bytes(est.peak_no_db_bytes)} fits the "
        f"{ctx.budget_label} budget {_fmt_bytes(ctx.budget_bytes)}, but "
        f"XLA double-buffers the scan carry "
        f"(+{_fmt_bytes(est.scan_carry_extra_bytes)}) for a true peak of "
        f"{_fmt_bytes(est.peak_bytes)} — size the pool shard to ~half "
        f"the free HBM (docs/tp_serving.md 'Pool sizing')")]


@mem_rule("mem-vmem-over-budget", "error",
          "a pallas_call's block working set overflows the 16 MiB "
          "scoped-VMEM stack")
def _vmem_over_budget(ctx: MemContext) -> List[RawMemFinding]:
    out: List[RawMemFinding] = []
    for call in ctx.est.vmem:
        if call.est_bytes <= VMEM_BUDGET_BYTES:
            continue
        out.append(RawMemFinding(
            f"pallas_call {call.kernel_name!r}: {call.n_blocks} blocks "
            f"x{call.buffering} grid buffering = "
            f"{_fmt_bytes(call.est_bytes)} VMEM > "
            f"{_fmt_bytes(VMEM_BUDGET_BYTES)} — shrink the block shape "
            f"(Mosaic will reject or spill this at compile)",
            eqn=call.eqn))
    return out


@mem_rule("mem-padding-blowup", "warning",
          "a boundary array pays >= 2x its logical bytes in TPU tile "
          "padding (e.g. a head_dim-64 pool)")
def _padding_blowup(ctx: MemContext) -> List[RawMemFinding]:
    out: List[RawMemFinding] = []
    for arr in ctx.est.boundary:
        if arr.logical_bytes <= 0:
            continue
        waste = arr.padded_bytes - arr.logical_bytes
        if arr.padded_bytes < PAD_BLOWUP_RATIO * arr.logical_bytes or \
                waste < PAD_BLOWUP_MIN_WASTE_BYTES:
            continue
        out.append(RawMemFinding(
            f"{arr.kind} array {arr.label} {arr.shape} {arr.dtype}: "
            f"tiled layout pads {_fmt_bytes(arr.logical_bytes)} logical "
            f"to {_fmt_bytes(arr.padded_bytes)} on chip "
            f"({arr.padded_bytes / arr.logical_bytes:.1f}x) — lane-align "
            f"the minor dims (docs/tp_serving.md: a d=64 pool pays 2x)"))
    return out


# --------------------------------------------------------------------------
# sharding contracts
# --------------------------------------------------------------------------

def _spec_dims(spec) -> List[Tuple[int, Tuple[str, ...]]]:
    """PartitionSpec -> [(dim, axis names)] for sharded dims."""
    out = []
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        out.append((d, tuple(str(a) for a in axes)))
    return out


def _declared_specs(ctx: MemContext):
    """Zip declared ``meta['arg_specs']`` with the argument leaves:
    yields ``(label, aval, spec)`` per (leaf, PartitionSpec) pair."""
    import jax

    specs = ctx.meta.get("arg_specs")
    if specs is None:
        return
    for i, arg in enumerate(ctx.ir.prog.args):
        if i >= len(specs) or specs[i] is None:
            continue
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        spec_leaves = jax.tree_util.tree_leaves(
            specs[i], is_leaf=lambda s: hasattr(s, "index") or s is None)
        if len(flat) != len(spec_leaves):
            continue                       # malformed declaration: skip
        for (path, leaf), spec in zip(flat, spec_leaves):
            if spec is None or not hasattr(leaf, "shape"):
                continue
            name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            yield (f"arg{i}" + (f"/{name}" if name else ""), leaf, spec)


@mem_rule("mem-spec-indivisible", "error",
          "a declared operand PartitionSpec axis does not divide the "
          "mesh axis size into the operand's dimension")
def _spec_indivisible(ctx: MemContext) -> List[RawMemFinding]:
    mesh_axes = ctx.meta.get("mesh_axes") or {}
    if not mesh_axes:
        return []
    out: List[RawMemFinding] = []
    for label, aval, spec in _declared_specs(ctx):
        shape = tuple(getattr(aval, "shape", ()))
        for d, axes in _spec_dims(spec):
            total = 1
            for a in axes:
                total *= int(mesh_axes.get(a, 1))
            if d >= len(shape) or total <= 1:
                continue
            if int(shape[d]) % total:
                out.append(RawMemFinding(
                    f"{label} {shape}: dim {d} (size {shape[d]}) is "
                    f"declared sharded over {'*'.join(axes)} = {total} "
                    f"chips, which does not divide it — shard_map will "
                    f"refuse this program at trace time"))
    return out


def _contains_collective(eqn) -> bool:
    from apex_tpu.analysis.mem.estimator import iter_eqns

    if eqn.primitive.name in COLLECTIVE_PRIMS:
        return True
    for sub, _ in _iter_subs(eqn):
        for e in iter_eqns(sub):
            if e.primitive.name in COLLECTIVE_PRIMS:
                return True
    return False


def _iter_subs(eqn):
    from apex_tpu.analysis.mem.estimator import _sub_jaxprs

    return _sub_jaxprs(eqn)


@mem_rule("mem-replicated-no-collective", "error",
          "a shard_map output declared replicated depends on a sharded "
          "input with no collective on the path (check_vma=False makes "
          "this a silent cross-chip divergence)")
def _replicated_no_collective(ctx: MemContext) -> List[RawMemFinding]:
    out: List[RawMemFinding] = []
    for info in ctx.est.shard_maps:
        sharded_in = {info.body.invars[i]
                      for i in range(len(info.body.invars))
                      if i < len(info.in_names) and info.in_names[i]}
        if not sharded_in:
            continue
        producer = {}
        for eqn in info.body.eqns:
            for v in eqn.outvars:
                producer[v] = eqn
        for o, outvar in enumerate(info.body.outvars):
            if o < len(info.out_names) and info.out_names[o]:
                continue                       # output is sharded: fine
            if not hasattr(outvar, "count"):
                continue                       # literal output
            # reverse BFS: does this replicated output reach a sharded
            # input without crossing a collective?
            stack, seen, tainted = [outvar], set(), False
            while stack and not tainted:
                v = stack.pop()
                if id(v) in seen:
                    continue
                seen.add(id(v))
                if v in sharded_in:
                    tainted = True
                    break
                eqn = producer.get(v)
                if eqn is None or _contains_collective(eqn):
                    continue                   # input/const, or blessed
                stack.extend(u for u in eqn.invars
                             if hasattr(u, "count"))
            if tainted:
                out.append(RawMemFinding(
                    f"shard_map output {o} is declared replicated "
                    f"(out spec {{}}) but depends on a sharded input "
                    f"with no psum/all_gather on the path — each chip "
                    f"returns a DIFFERENT value and check_vma=False "
                    f"asserts nothing", eqn=info.eqn))
    return out


@mem_rule("mem-donation-spec-mismatch", "error",
          "a donated sharded buffer has no output with the same "
          "shape+dtype+spec to alias — the donation cannot happen "
          "in place")
def _donation_spec_mismatch(ctx: MemContext) -> List[RawMemFinding]:
    leaves = ctx.est.arg_leaves
    donate = ctx.ir.prog.donate
    if not donate or not leaves:
        return []
    out: List[RawMemFinding] = []
    for info in ctx.est.shard_maps:
        if len(info.in_names) != len(leaves) or \
                len(info.eqn.invars) != len(leaves):
            continue
        # output alias budget: (shape, dtype, frozen dim->axes)
        budget: Dict[tuple, int] = {}
        for o, outvar in enumerate(info.eqn.outvars):
            aval = getattr(outvar, "aval", None)
            if getattr(aval, "dtype", None) is None:
                continue
            key = (tuple(aval.shape), str(aval.dtype),
                   tuple(sorted((d, tuple(a)) for d, a in
                                info.out_axes(o).items())))
            budget[key] = budget.get(key, 0) + 1
        for pos, (label, _leaf, arg_i) in enumerate(leaves):
            if arg_i not in donate:
                continue
            axes = info.in_axes(pos)
            if not axes:
                continue                   # replicated: ir tier's job
            aval = info.eqn.invars[pos].aval
            if getattr(aval, "dtype", None) is None:
                continue
            key = (tuple(aval.shape), str(aval.dtype),
                   tuple(sorted((d, tuple(a)) for d, a in axes.items())))
            if budget.get(key, 0) > 0:
                budget[key] -= 1
                continue
            spec = ", ".join(f"dim{d}:{'*'.join(a)}"
                             for d, a in sorted(axes.items()))
            out.append(RawMemFinding(
                f"donated sharded buffer {label} {tuple(aval.shape)} "
                f"({spec}) has no output with the same shape+dtype+spec "
                f"to alias — the donation is dead weight and the chip "
                f"holds both copies", eqn=info.eqn))
    return out


#: (scale leaf key -> its weight partner's key) — the repo's two
#: quantized families: KV pools pair k/v_scales with k/v_pages
#: (serving/kv_pool.py), quantized linears pair scale with weight
#: (transformer/tensor_parallel/layers.py `_quantized_params`)
_SCALE_PARTNERS = (("k_scales", "k_pages"), ("v_scales", "v_pages"),
                   ("scale", "weight"), ("w_scale", "w"))


@mem_rule("mem-scale-shard-drift", "error",
          "a quantization scale does not shard with its weight's axis "
          "(the PR 16 invariant: scales follow the channels they scale)")
def _scale_shard_drift(ctx: MemContext) -> List[RawMemFinding]:
    aligned = ctx.aligned_leaves()
    if not aligned:
        return []
    by_path = {label: (aval, axes) for label, aval, axes in aligned}
    out: List[RawMemFinding] = []
    for label, scale_aval, scale_axes in aligned:
        head, _, key = label.rpartition("/")
        partner_key = dict(_SCALE_PARTNERS).get(key)
        if partner_key is None:
            continue
        partner = by_path.get(f"{head}/{partner_key}" if head
                              else partner_key)
        if partner is None:
            continue
        w_aval, w_axes = partner
        w_shape = tuple(getattr(w_aval, "shape", ()))
        s_shape = tuple(getattr(scale_aval, "shape", ()))
        s_axis_names = {a for axes in scale_axes.values() for a in axes}
        w_axis_names = {a for axes in w_axes.values() for a in axes}
        # every weight axis whose sharded dim the scale MIRRORS (same
        # extent appears in the scale's shape) must shard the scale too;
        # axes over dims the scale lacks (e.g. row-parallel input
        # channels vs a per-out-channel scale) legitimately replicate.
        # The extent match must be UNAMBIGUOUS: a square row-parallel
        # weight (1024, 1024) sharded on its input dim has a (1024,)
        # per-out-channel scale that mirrors the OTHER dim — matching on
        # a repeated extent would call every such scale drifted
        for d, axes in w_axes.items():
            if d >= len(w_shape) or w_shape[d] not in s_shape or \
                    w_shape.count(w_shape[d]) > 1:
                continue
            for a in axes:
                if a not in s_axis_names:
                    out.append(RawMemFinding(
                        f"scale {label} {s_shape} replicates over "
                        f"{a!r} while its weight {head or label}/"
                        f"{partner_key} {w_shape} shards dim {d} "
                        f"(size {w_shape[d]}) on it — each chip would "
                        f"scale its shard with the WRONG rows "
                        f"(docs/tp_serving.md: scales follow their "
                        f"weight's axis)"))
        for a in sorted(s_axis_names - w_axis_names):
            out.append(RawMemFinding(
                f"scale {label} {s_shape} shards over {a!r} but its "
                f"weight {head or label}/{partner_key} {w_shape} does "
                f"not — the scale rows no longer line up with the "
                f"weight shard"))
    return out
