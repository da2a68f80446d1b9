"""Static per-chip memory estimation for the tpu-lint mem tier.

Three computations over one traced case (a :class:`CaseIR` from the IR
harness — the mem tier deliberately re-uses the same registry/trace
path so "registered for lint" means "covered by the fit proof"):

- **per-chip peak HBM** (:func:`estimate_case`): a liveness sweep over
  the top-level equations (a) pricing every array at its TPU
  tiled-layout PADDED size (``layout.py``), (b) analyzing a
  shard_map-wrapped program at its body's LOCAL shard shapes — per-chip
  bytes, (c) charging each
  ``lax.scan`` an extra copy of its carry (XLA double-buffers the
  decode scan's pool carry — the PR 10 lesson), and (d) crediting
  in-place updates: a scatter/dynamic_update_slice/scan whose output
  matches a buffer dying at that equation writes it in place instead of
  allocating, provided the buffer is writable (an intermediate or a
  donated input) — the static analogue of ``memory_analysis()``'s
  ``alias_bytes`` term, applied per equation so a chain of per-layer
  pool updates isn't credited once globally.
  Both the with- and without-double-buffer peaks are kept so the rules
  can say WHICH lesson a budget miss violates.

- **per-``pallas_call`` VMEM** (:class:`VmemCall`): block shape x dtype
  per operand at padded tile sizes, x2 when a non-trivial grid pipelines
  (Mosaic double-buffers grid blocks), vs the 16 MiB scoped-VMEM
  budget — the ``_check_block_mappings``/scoped-vmem overflow class
  (the r5 Adam regression, the PR 14 scale-view bring-up) before any
  compile.

- **sharding contracts** (:class:`ShardMapInfo`): every ``shard_map``
  equation's mesh axis sizes + per-operand ``in_specs``/``out_specs``,
  aligned positionally with the case's argument tree paths so rules can
  talk about ``cache/layers/0/k_scales`` rather than ``invar 17``.

Everything here is trace-only (CPU, AbstractMesh-friendly): no TPU, no
compile.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from apex_tpu.analysis.mem.layout import (aval_logical_bytes,
                                          aval_padded_bytes,
                                          tiled_padded_bytes)

#: Mosaic's scoped-VMEM stack per core — the budget the r5 Adam kernel
#: overflowed at block 256 and every ``_check_block_mappings`` failure
#: ultimately traces back to.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024


# --------------------------------------------------------------------------
# jaxpr plumbing
# --------------------------------------------------------------------------

_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                 "body_jaxpr")


def _sub_jaxprs(eqn):
    """(sub_jaxpr, is_pallas_kernel) pairs under one equation."""
    is_pallas = eqn.primitive.name == "pallas_call"
    for key in _JAXPR_PARAMS:
        sub = eqn.params.get(key)
        if sub is None:
            continue
        inner = getattr(sub, "jaxpr", sub)
        if inner is not None:
            yield inner, is_pallas
    for sub in eqn.params.get("branches", ()):
        inner = getattr(sub, "jaxpr", sub)
        if inner is not None:
            yield inner, is_pallas


def iter_eqns(jaxpr, *, into_pallas: bool = False):
    """Every equation under ``jaxpr``, recursively (pallas kernel bodies
    skipped unless asked — their "arrays" are VMEM refs, not HBM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub, is_pallas in _sub_jaxprs(eqn):
            if is_pallas and not into_pallas:
                continue
            yield from iter_eqns(sub, into_pallas=into_pallas)


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


def unwrap_trivial(jaxpr):
    """Descend through single-equation pjit/closed-call wrappers:
    ``make_jaxpr(jax.jit(f))`` stages one pjit eqn whose body is the
    program. Stops at the first level that has real structure."""
    depth = 0
    while depth < 8 and len(jaxpr.eqns) == 1 and \
            jaxpr.eqns[0].primitive.name in ("jit", "closed_call",
                                             "custom_jvp_call",
                                             "custom_vjp_call",
                                             "remat", "checkpoint"):
        eqn = jaxpr.eqns[0]
        sub = next((s for s, _ in _sub_jaxprs(eqn)), None)
        if sub is None or len(sub.invars) != len(eqn.invars):
            break
        jaxpr = sub
        depth += 1
    return jaxpr


# --------------------------------------------------------------------------
# shard_map contracts
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ShardMapInfo:
    """One ``shard_map`` equation's declared contract."""

    eqn: object
    mesh_axes: Dict[str, int]            # axis name -> size
    in_names: Tuple[dict, ...]           # per operand: {dim: (axes...)}
    out_names: Tuple[dict, ...]
    body: object                         # the body jaxpr (LOCAL shapes)

    def in_axes(self, pos: int) -> Dict[int, Tuple[str, ...]]:
        return dict(self.in_names[pos]) if pos < len(self.in_names) else {}

    def out_axes(self, pos: int) -> Dict[int, Tuple[str, ...]]:
        return dict(self.out_names[pos]) \
            if pos < len(self.out_names) else {}


def _spec_names(spec) -> Dict[int, Tuple[str, ...]]:
    """``PartitionSpec`` -> ``{dim: (axis names...)}`` over the dims it
    shards."""
    return {dim: entry if isinstance(entry, tuple) else (entry,)
            for dim, entry in enumerate(spec) if entry is not None}


def shard_map_infos(closed) -> List[ShardMapInfo]:
    out: List[ShardMapInfo] = []
    for eqn in iter_eqns(unwrap_trivial(closed.jaxpr)):
        if eqn.primitive.name != "shard_map":
            continue
        mesh_axes = {str(k): int(v)
                     for k, v in eqn.params["mesh"].shape.items()}
        body = eqn.params["jaxpr"]
        out.append(ShardMapInfo(
            eqn=eqn, mesh_axes=mesh_axes,
            in_names=tuple(map(_spec_names, eqn.params["in_specs"])),
            out_names=tuple(map(_spec_names, eqn.params["out_specs"])),
            body=getattr(body, "jaxpr", body)))
    return out


def arg_leaf_paths(prog) -> List[Tuple[str, object, int]]:
    """Flatten the case's argument tuple to ``(path, aval, arg_index)``
    leaves in jaxpr-invar order (``make_jaxpr`` flattens positionally)."""
    import jax

    leaves: List[Tuple[str, object, int]] = []
    for i, arg in enumerate(prog.args):
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in flat:
            name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in path)
            leaves.append((f"arg{i}" + (f"/{name}" if name else ""),
                           leaf, i))
    return leaves


# --------------------------------------------------------------------------
# the padded liveness sweep
# --------------------------------------------------------------------------

def _scan_carry_extra(eqn) -> int:
    """Padded bytes of one scan's carry — the extra in-flight copy XLA's
    double buffering holds while the next iteration's carry is built."""
    if eqn.primitive.name != "scan":
        return 0
    nc = int(eqn.params.get("num_consts", 0))
    ncarry = int(eqn.params.get("num_carry", 0))
    carry = list(eqn.invars)[nc:nc + ncarry]
    return sum(aval_padded_bytes(v.aval) for v in carry
               if not _is_literal(v))


#: primitives XLA reliably updates IN PLACE when a dying operand buffer
#: of the output's exact shape+dtype is writable: the pool scatter /
#: dynamic-update-slice class, the scan/while carry, and the masked
#: select that implements conditional updates. Deliberately narrow —
#: a dot_general can't overwrite its own operand.
_INPLACE_PRIMS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min",
    "scatter-max", "dynamic_update_slice", "scan", "while", "select_n",
    "copy", "jit", "closed_call",
})


def _padded_liveness(jaxpr, owned_inputs=frozenset()
                     ) -> Tuple[int, int, int, int]:
    """(peak_with_double_buffer, peak_without, scan_carry_extra_max,
    inplace_credit_total) over the top-level equation list at padded
    sizes. Inner-jaxpr scratch is not modeled; two refinements over a
    plain liveness sweep:

    - each scan charges an extra copy of its carry (XLA's double
      buffering);
    - an in-place-capable equation whose output matches a buffer dying
      at that very equation does NOT allocate, provided the dying
      buffer is writable — an intermediate, or a DONATED program input
      (``owned_inputs``). This is how the per-layer pool scatters and
      the scan carry alias in the compiled program; a donated input
      with no matching update keeps both copies (the donation was
      ineffective)."""
    last_use: Dict[object, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last_use[v] = n
    live: Dict[object, int] = {
        v: aval_padded_bytes(v.aval)
        for v in list(jaxpr.invars) + list(jaxpr.constvars)
        if v in last_use}
    writable = set(owned_inputs)
    cur = sum(live.values())
    peak_db = peak = cur
    carry_max = 0
    credit_total = 0
    for i, eqn in enumerate(jaxpr.eqns):
        dying: Dict[Tuple[tuple, str], int] = {}
        if eqn.primitive.name in _INPLACE_PRIMS:
            seen = set()
            for v in eqn.invars:
                if _is_literal(v) or id(v) in seen:
                    continue
                seen.add(id(v))
                if last_use.get(v) == i and v in live and v in writable:
                    aval = v.aval
                    if getattr(aval, "dtype", None) is None:
                        continue
                    key = (tuple(aval.shape), str(aval.dtype))
                    dying[key] = dying.get(key, 0) + 1
        out_bytes = 0
        for v in eqn.outvars:
            b = aval_padded_bytes(v.aval)
            aval = getattr(v, "aval", None)
            key = (tuple(getattr(aval, "shape", ())),
                   str(getattr(aval, "dtype", None)))
            if dying.get(key, 0) > 0:
                dying[key] -= 1
                credit_total += b
                continue                   # writes the dying buffer
            out_bytes += b
        extra = _scan_carry_extra(eqn)
        carry_max = max(carry_max, extra)
        peak = max(peak, cur + out_bytes)
        peak_db = max(peak_db, cur + out_bytes + extra)
        for v in eqn.outvars:
            if last_use.get(v, i) > i:
                live[v] = aval_padded_bytes(v.aval)
                cur += live[v]
        for v in eqn.invars:
            if not _is_literal(v) and last_use.get(v) == i and v in live:
                cur -= live.pop(v)
        writable.update(v for v in eqn.outvars if not _is_literal(v))
    return peak_db, peak, carry_max, credit_total


# --------------------------------------------------------------------------
# per-pallas_call VMEM
# --------------------------------------------------------------------------

@dataclasses.dataclass
class VmemCall:
    eqn: object
    kernel_name: str
    est_bytes: int               # sum of padded block bytes x buffering
    buffering: int               # 2 when a non-trivial grid pipelines
    n_blocks: int
    grid: Tuple[Optional[int], ...]   # None: a traced (dynamic) bound


def vmem_calls(closed) -> List[VmemCall]:
    out: List[VmemCall] = []
    for eqn in iter_eqns(unwrap_trivial(closed.jaxpr)):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        # a traced bound (the paged kernels' walk axis) has no size to
        # read: None, and it pipelines like any axis longer than 1
        grid = tuple(int(g) if isinstance(g, int) else None
                     for g in gm.grid)
        total = 0
        n_blocks = 0
        for bm in gm.block_mappings:
            # the VMEM block as the kernel sees it: grid-mapped
            # (squeezed) dims are already dropped from its shape
            block = bm.block_aval
            total += tiled_padded_bytes(tuple(block.shape), block.dtype)
            n_blocks += 1
        buffering = 2 if any(g is None or g > 1 for g in grid) else 1
        name = eqn.params["name"] \
            or eqn.params["jaxpr"].debug_info.func_name
        out.append(VmemCall(eqn=eqn, kernel_name=name,
                            est_bytes=total * buffering,
                            buffering=buffering, n_blocks=n_blocks,
                            grid=grid))
    return out


# --------------------------------------------------------------------------
# the per-case estimate
# --------------------------------------------------------------------------

@dataclasses.dataclass
class BoundaryArray:
    """One program-boundary array (input or output) at the analyzed
    scope's shapes — LOCAL shard shapes for shard_map programs."""

    label: str
    kind: str                    # "in" | "out"
    shape: Tuple[int, ...]
    dtype: str
    logical_bytes: int
    padded_bytes: int


@dataclasses.dataclass
class MemEstimate:
    """The mem tier's static memory model of one traced case."""

    scope: str                   # "per-chip" | "global"
    peak_bytes: int              # padded, double-buffered, alias-credited
    peak_no_db_bytes: int        # same sweep without the scan 2x
    scan_carry_extra_bytes: int
    alias_bytes: int             # in-place-update bytes credited
    boundary: List[BoundaryArray]
    vmem: List[VmemCall]
    shard_maps: List[ShardMapInfo]
    arg_leaves: List[Tuple[str, object, int]]
    notes: List[str]


def _analyzed_jaxpr(closed, infos: List[ShardMapInfo]):
    """The jaxpr whose boundary IS a chip's resident set: the body of a
    whole-program shard_map (local shard shapes), else the (unwrapped)
    top level. "Whole-program" = the unwrapped level is exactly one
    shard_map equation."""
    top = unwrap_trivial(closed.jaxpr)
    if len(top.eqns) == 1 and top.eqns[0].primitive.name == "shard_map":
        for info in infos:
            if info.eqn is top.eqns[0]:
                return unwrap_trivial(info.body), "per-chip"
        body = top.eqns[0].params.get("jaxpr")
        return unwrap_trivial(getattr(body, "jaxpr", body)), "per-chip"
    return top, "global"


def _donated_positions(prog) -> List[int]:
    """Flattened invar positions of the donated argument indices."""
    if not prog.donate:
        return []
    try:
        import jax
    except Exception:
        return []
    positions: List[int] = []
    offset = 0
    for i, arg in enumerate(prog.args):
        n = len(jax.tree_util.tree_leaves(arg))
        if i in prog.donate:
            positions.extend(range(offset, offset + n))
        offset += n
    return positions


def estimate_case(ir) -> MemEstimate:
    """Build the full static estimate for one traced case (a CaseIR)."""
    infos = shard_map_infos(ir.closed)
    jaxpr, scope = _analyzed_jaxpr(ir.closed, infos)
    owned = {jaxpr.invars[p] for p in _donated_positions(ir.prog)
             if p < len(jaxpr.invars)}
    peak_db, peak, carry, alias = _padded_liveness(jaxpr, owned)
    leaves = arg_leaf_paths(ir.prog)
    notes: List[str] = []
    if scope == "per-chip":
        notes.append("shard_map body analyzed at local shard shapes "
                     "(per-chip bytes)")

    def _label(kind: str, idx: int) -> str:
        if kind == "in" and idx < len(leaves) \
                and len(leaves) == len(jaxpr.invars):
            return leaves[idx][0]
        return f"{kind}[{idx}]"

    boundary: List[BoundaryArray] = []
    for kind, vs in (("in", jaxpr.invars), ("out", jaxpr.outvars)):
        for idx, v in enumerate(vs):
            if _is_literal(v):
                continue
            aval = v.aval
            if getattr(aval, "dtype", None) is None:
                continue
            boundary.append(BoundaryArray(
                label=_label(kind, idx), kind=kind,
                shape=tuple(aval.shape), dtype=str(aval.dtype),
                logical_bytes=aval_logical_bytes(aval),
                padded_bytes=aval_padded_bytes(aval)))
    return MemEstimate(
        scope=scope,
        peak_bytes=peak_db,
        peak_no_db_bytes=peak,
        scan_carry_extra_bytes=carry,
        alias_bytes=alias,
        boundary=boundary,
        vmem=vmem_calls(ir.closed),
        shard_maps=infos,
        arg_leaves=leaves,
        notes=notes)
