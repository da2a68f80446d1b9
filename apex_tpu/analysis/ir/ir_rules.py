"""tpu-lint IR rules: hazards visible in the staged jaxpr, not the AST.

Each rule walks one :class:`~apex_tpu.analysis.ir.harness.CaseIR` (the
traced program of a registered entry point) and yields
:class:`RawFinding`\\ s — an offending equation (mapped to source by
``ir_report``) or ``None`` to anchor at the case's definition site.

The same precision bias as the AST tier, applied one layer down: every
check reads facts the trace PROVES (aval dtypes and byte sizes, scan
carry wiring, closed-over constants, effects), with byte thresholds
sized so only hot-path-relevant findings fire. Pallas kernel bodies are
NOT descended into — their internals are the kernel tests' and the AOT
sweep's domain; the IR tier judges the program *around* the kernels.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

from apex_tpu.analysis.ir.harness import MIB, CaseIR

#: bf16->f32 promotions below this many output bytes are noise;
#: above it the round-trip doubles a hot intermediate's HBM traffic
PROMOTION_BYTES = 8 * MIB
#: a closed-over constant this large belongs in the argument list
CONST_BYTES = 512 * 1024
#: broadcast-blowup: output >= FACTOR x largest non-literal input
#: AND at least this many bytes
BLOWUP_BYTES = 8 * MIB
BLOWUP_FACTOR = 32
#: expensive-output floor for the dead-computation rule
DEAD_BYTES = MIB
#: minor-dim transpose floor for the layout rule
TRANSPOSE_BYTES = MIB


@dataclasses.dataclass
class RawFinding:
    eqn: Optional[object]            # jaxpr eqn (source anchor) or None
    message: str


@dataclasses.dataclass(frozen=True)
class IRRule:
    name: str
    severity: str
    summary: str
    check: Callable                  # check(ir: CaseIR) -> Iterator


IR_RULES: Dict[str, IRRule] = {}


def ir_rule(name: str, severity: str, summary: str):
    def deco(fn):
        IR_RULES[name] = IRRule(name=name, severity=severity,
                                summary=summary, check=fn)
        return fn
    return deco


# --------------------------------------------------------------------------
# jaxpr plumbing
# --------------------------------------------------------------------------

def _sub_jaxprs(eqn) -> Iterator[object]:
    """Inner jaxprs of a higher-order eqn (NOT pallas_call kernels)."""
    if eqn.primitive.name == "pallas_call":
        return
    for key in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr"):
        v = eqn.params.get(key)
        if v is not None:
            yield getattr(v, "jaxpr", v)     # ClosedJaxpr -> Jaxpr
    for br in eqn.params.get("branches", ()):
        yield getattr(br, "jaxpr", br)


def _all_jaxprs(jaxpr) -> Iterator[object]:
    """This jaxpr and every nested one (scan/while/cond/pjit bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from _all_jaxprs(sub)


def _iter_eqns(jaxpr, in_loop: bool = False
               ) -> Iterator[Tuple[object, bool]]:
    """(eqn, inside-a-scan/while-body) over the whole nest."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        looping = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in _sub_jaxprs(eqn):
            yield from _iter_eqns(sub, looping)


def _is_var(v) -> bool:
    return hasattr(v, "aval") and not hasattr(v, "val")   # Var, not Literal


def _is_drop(v) -> bool:
    return type(v).__name__ == "DropVar"


def _nbytes(aval) -> int:
    try:
        size = 1
        for d in aval.shape:
            size *= int(d)
        return size * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


def _mib(n: int) -> str:
    return f"{n / MIB:.1f} MiB"


def _is_float(dt) -> bool:
    """True for any floating dtype INCLUDING the ml_dtypes extension
    types (bfloat16/fp8), whose numpy ``kind`` is not ``'f'``."""
    import jax.numpy as jnp

    try:
        return jnp.issubdtype(dt, jnp.floating)
    except TypeError:
        return False


def _float_leaf_dtypes(vars_) -> Set[str]:
    out: Set[str] = set()
    for v in vars_:
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and _is_float(dt):
            out.add(dt.name)
    return out


# --------------------------------------------------------------------------
# 1. ir-dtype-promotion-drift
# --------------------------------------------------------------------------

@ir_rule("ir-dtype-promotion-drift", "warning",
         "large bf16->fp32 promotion staged inside a bf16-in/bf16-out "
         "program — the round trip doubles a hot intermediate's bytes")
def check_promotion_drift(ir: CaseIR) -> Iterator[RawFinding]:
    jaxpr = ir.closed.jaxpr
    in_f = _float_leaf_dtypes(jaxpr.invars)
    out_f = _float_leaf_dtypes(jaxpr.outvars)
    if not in_f or not (in_f <= {"bfloat16", "float16"}):
        return
    if out_f - {"bfloat16", "float16"}:
        return                       # fp32 outputs are the declared deal
    for eqn, _ in _iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = eqn.params.get("new_dtype")
        src = getattr(eqn.invars[0].aval, "dtype", None)
        if new is None or src is None:
            continue
        if str(new) not in ("float32", "float64") \
                or src.name not in ("bfloat16", "float16"):
            continue
        nb = _nbytes(eqn.outvars[0].aval)
        if nb >= PROMOTION_BYTES:
            yield RawFinding(
                eqn,
                f"{src.name}->{new} promotion of a {_mib(nb)} "
                f"intermediate in a {'/'.join(sorted(in_f))}-in/"
                "bf16-out program — the compiler was handed a widened "
                "hot path (keep the accumulation, or suppress with the "
                "why)")


# --------------------------------------------------------------------------
# 2. ir-x64-leak
# --------------------------------------------------------------------------

_X64 = {"float64", "int64", "uint64", "complex128"}


@ir_rule("ir-x64-leak", "error",
         "a 64-bit dtype is staged into the program — double-width "
         "buffers and a disabled-x64 drift hazard")
def check_x64_leak(ir: CaseIR) -> Iterator[RawFinding]:
    jaxpr = ir.closed.jaxpr
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and dt.name in _X64:
            yield RawFinding(
                None, f"{dt.name} program boundary value "
                      f"(shape {tuple(v.aval.shape)}) — x64 leaked into "
                      "the staged program")
            break                    # boundary summary once per case
    for eqn, _ in _iter_eqns(jaxpr):
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and dt.name in _X64:
                yield RawFinding(
                    eqn, f"`{eqn.primitive.name}` stages a {dt.name} "
                         f"intermediate of shape {tuple(v.aval.shape)}")
                break


# --------------------------------------------------------------------------
# 3. ir-dead-output
# --------------------------------------------------------------------------

#: dead-output flags ONLY these: kernel launches and contractions XLA
#: either cannot freely DCE (opaque custom calls) or whose dead staging
#: signals a drifted contract. Dead PURE elementwise eqns (a grad-of-
#: loss primal, a dropped slice) are free for XLA to DCE — flagging
#: them would bury the real findings in artifacts of how grad stages.
_EXPENSIVE_PRIMS = {"scan", "while", "cond", "jit", "closed_call",
                    "core_call", "remat", "checkpoint", "dot_general",
                    "conv_general_dilated", "custom_jvp_call",
                    "custom_vjp_call", "pallas_call"}


def _dead_eqns(jaxpr, live_out: Optional[Set[int]] = None
               ) -> Iterator[Tuple[object, object]]:
    """(eqn, first dead outvar) for computation no consumer needs.

    ``live_out``: ids of this jaxpr's outvars that ARE consumed outside
    (None = all). Recurses into pjit/scan bodies with the outer
    liveness projected in, so an entire scan output nobody reads is
    caught along with the body computation feeding it.
    """
    live: Set[int] = {id(v) for v in jaxpr.outvars
                      if live_out is None or id(v) in live_out}
    alive_eqns: List[Tuple[object, bool]] = []
    for eqn in reversed(jaxpr.eqns):
        out_alive = [not _is_drop(v) and id(v) in live
                     for v in eqn.outvars]
        eqn_alive = any(out_alive) or bool(eqn.effects)
        alive_eqns.append((eqn, eqn_alive))
        if eqn_alive:
            for v in eqn.invars:
                if _is_var(v):
                    live.add(id(v))
    for eqn, eqn_alive in reversed(alive_eqns):
        if not eqn_alive:
            dead_v = next((v for v in eqn.outvars if not _is_drop(v)),
                          eqn.outvars[0] if eqn.outvars else None)
            yield eqn, dead_v
            continue
        # project outer liveness into pjit-like bodies (1:1 outputs)
        if eqn.primitive.name in ("jit", "closed_call", "core_call",
                                  "remat", "checkpoint"):
            for sub in _sub_jaxprs(eqn):
                if len(sub.outvars) != len(eqn.outvars):
                    continue
                inner_live = {id(sub.outvars[i])
                              for i, v in enumerate(eqn.outvars)
                              if not _is_drop(v) and id(v) in live}
                yield from _dead_eqns(sub, inner_live)
        # a live scan can still stack a ys nobody reads (its CARRY
        # outputs are intrinsic — next-iteration inputs — but an
        # unread stacked output is pure dead weight per iteration)
        elif eqn.primitive.name == "scan":
            k = eqn.params.get("num_carry", 0)
            for v in eqn.outvars[k:]:
                if not _is_drop(v) and id(v) not in live \
                        and _nbytes(v.aval) >= DEAD_BYTES:
                    yield eqn, v


@ir_rule("ir-dead-output", "warning",
         "expensive computation whose result no consumer reads — dead "
         "weight XLA may or may not DCE, and a drifted-contract smell")
def check_dead_output(ir: CaseIR) -> Iterator[RawFinding]:
    for eqn, dead_v in _dead_eqns(ir.closed.jaxpr):
        if eqn.primitive.name not in _EXPENSIVE_PRIMS:
            continue
        nb = _nbytes(getattr(dead_v, "aval", None)) if dead_v is not None \
            else 0
        what = f"a {_mib(nb)} result" if nb >= DEAD_BYTES \
            else "its result"
        yield RawFinding(
            eqn, f"`{eqn.primitive.name}` computes {what} no consumer "
                 "reads — dead computation carried in the program")


# --------------------------------------------------------------------------
# 4. ir-donation-ineffective
# --------------------------------------------------------------------------

@ir_rule("ir-donation-ineffective", "warning",
         "a donated input has no output of identical shape/dtype to "
         "alias — XLA keeps both buffers and the donation is a no-op")
def check_donation_ineffective(ir: CaseIR) -> Iterator[RawFinding]:
    if not ir.donated_avals:
        return
    budget: Dict[Tuple[tuple, str], int] = {}
    for v in ir.closed.jaxpr.outvars:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        key = (tuple(aval.shape), str(aval.dtype))
        budget[key] = budget.get(key, 0) + 1
    for leaf in ir.donated_avals:
        key = (tuple(leaf.shape), str(leaf.dtype))
        if budget.get(key, 0) > 0:
            budget[key] -= 1
            continue
        yield RawFinding(
            None,
            f"donated input (shape {key[0]}, {key[1]}) has no "
            "unmatched output of the same shape/dtype — XLA cannot "
            "alias it; drop the donation or return the updated buffer "
            "(cross-check: the AST tier's jit-donated-reuse guards the "
            "caller side)")


# --------------------------------------------------------------------------
# 5. ir-large-const-capture
# --------------------------------------------------------------------------

@ir_rule("ir-large-const-capture", "warning",
         "a closed-over array above the byte threshold is baked into "
         "the jaxpr as a constant — re-staged per trace, bloats every "
         "compile-cache entry")
def check_large_const(ir: CaseIR) -> Iterator[RawFinding]:
    for const in ir.closed.consts:
        nb = _nbytes(const)
        if nb >= CONST_BYTES:
            yield RawFinding(
                None,
                f"closed-over constant (shape "
                f"{tuple(getattr(const, 'shape', ()))}, "
                f"{getattr(const, 'dtype', '?')}, {_mib(nb)}) is baked "
                "into the jaxpr — pass it as an argument so it lives "
                "once on device")


# --------------------------------------------------------------------------
# 6. ir-broadcast-blowup
# --------------------------------------------------------------------------

@ir_rule("ir-broadcast-blowup", "warning",
         "an intermediate blows up far beyond its inputs via broadcast "
         "— a materialized tensor the math may not need")
def check_broadcast_blowup(ir: CaseIR) -> Iterator[RawFinding]:
    for eqn, _ in _iter_eqns(ir.closed.jaxpr):
        if eqn.primitive.name != "broadcast_in_dim":
            continue
        src = eqn.invars[0]
        if not _is_var(src):
            continue                  # literal fill (jnp.zeros) is fine
        in_nb = _nbytes(src.aval)
        out_nb = _nbytes(eqn.outvars[0].aval)
        if in_nb <= 128:
            continue                  # scalar/tiny seed: a fill, not a
        #                               relayout of real data
        if out_nb >= BLOWUP_BYTES and out_nb >= BLOWUP_FACTOR * in_nb:
            yield RawFinding(
                eqn,
                f"broadcast materializes {_mib(out_nb)} from "
                f"{_mib(in_nb)} (x{out_nb // max(in_nb, 1)}) — check "
                "whether the consumer could fuse the broadcast instead")


# --------------------------------------------------------------------------
# 7. ir-effectful-in-scan
# --------------------------------------------------------------------------

@ir_rule("ir-effectful-in-scan", "warning",
         "a callback/effectful primitive runs inside a scan/while body "
         "— host traffic on every iteration of the hot loop")
def check_effectful_in_scan(ir: CaseIR) -> Iterator[RawFinding]:
    def host_effects(eqn) -> bool:
        # named-axis effects are trace bookkeeping for collectives
        # (psum/all_gather/axis_index under shard_map) — on-device ICI
        # traffic, not host round-trips; a TP decode scan is SUPPOSED
        # to all-reduce every step
        return any("NamedAxis" not in type(e).__name__
                   for e in eqn.effects)

    for eqn, in_loop in _iter_eqns(ir.closed.jaxpr):
        if not in_loop:
            continue
        name = eqn.primitive.name
        if "callback" in name or name == "debug_print" \
                or (host_effects(eqn)
                    and name not in ("scan", "while", "cond", "jit")):
            yield RawFinding(
                eqn,
                f"`{name}` executes inside a scan/while body: one host "
                "round-trip per iteration (even the non-blocking "
                "metrics channel pays transfer+queue each step — keep "
                "it at chunk boundaries)")


# --------------------------------------------------------------------------
# 8. ir-compile-key-cardinality
# --------------------------------------------------------------------------

@ir_rule("ir-compile-key-cardinality", "error",
         "bucketed input variants staged MORE distinct programs than "
         "the case's compile-count contract allows")
def check_compile_cardinality(ir: CaseIR) -> Iterator[RawFinding]:
    if not ir.variant_closed:
        return

    def canon(closed) -> str:
        # custom_vjp/thunk params print as `<function f at 0x...>`;
        # addresses differ per trace even for IDENTICAL programs
        return re.sub(r"0x[0-9a-f]+", "0x", str(closed.jaxpr))

    distinct = {canon(c) for c in [ir.closed] + ir.variant_closed}
    allowed = ir.prog.max_traces
    if len(distinct) > allowed:
        yield RawFinding(
            None,
            f"{len(ir.variant_closed) + 1} bucketed shape variants "
            f"traced to {len(distinct)} distinct programs (contract: "
            f"<= {allowed}) — the bucketing is not collapsing compile "
            "keys; every live value becomes a fresh XLA compile")


# --------------------------------------------------------------------------
# 9. ir-transpose-heavy-layout
# --------------------------------------------------------------------------

@ir_rule("ir-transpose-heavy-layout", "warning",
         "a minor-dim transpose feeds a Pallas kernel — the relayout "
         "Mosaic pays on the (sublane, lane) dims, per call")
def check_transpose_layout(ir: CaseIR) -> Iterator[RawFinding]:
    for jaxpr in _all_jaxprs(ir.closed.jaxpr):
        transposed: Dict[int, Tuple[object, int]] = {}
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "transpose":
                continue
            perm = tuple(eqn.params.get("permutation", ()))
            rank = len(perm)
            if rank < 2 or (perm[-1] == rank - 1
                            and perm[-2] == rank - 2):
                continue              # minor (sublane, lane) dims intact
            nb = _nbytes(eqn.outvars[0].aval)
            if nb >= TRANSPOSE_BYTES:
                transposed[id(eqn.outvars[0])] = (eqn, nb)
        if not transposed:
            continue
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            for v in eqn.invars:
                if _is_var(v) and id(v) in transposed:
                    teqn, nb = transposed[id(v)]
                    yield RawFinding(
                        teqn,
                        f"{_mib(nb)} operand is transposed on its minor "
                        "dims immediately before a pallas_call — Mosaic "
                        "relayouts the (sublane, lane) tiles every "
                        "call; feed the kernel the native layout or "
                        "fold the transpose into the index map")
