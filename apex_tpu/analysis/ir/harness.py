"""Entry-point discovery + jaxpr construction for the tpu-lint IR tier.

The AST tier reads what the code *says*; this tier reads what JAX
actually *stages*. :func:`analysis_cases` is the declarative registry of
traceable entry points — every ``tpu_aot.kernel_cases()`` program
(kernels, fused optimizers, the lock-step decode programs, the
prefix-cached admission) plus serving programs the AOT sweep does not
carry: the engine's jitted multi-step decode chunk (the
``generate(paged=True)`` hot loop) and the bucketed admission program
with its compile-count contract. :func:`build_case_ir` turns one case
into a :class:`CaseIR` via ``jax.make_jaxpr`` over
``jax.ShapeDtypeStruct`` arguments — pure tracing, no TPU, no compile;
it runs in tier-1 on CPU in seconds.

Tracing forces ``APEX_TPU_FORCE_MOSAIC=1`` so ``ops/_dispatch`` stages
the real Pallas programs (the TPU path), not the CPU interpret fallback
— the jaxpr the rules see is the jaxpr the chip would get.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

#: byte size guards shared with ir_rules (import cycle-free home)
MIB = 1024 * 1024


@dataclasses.dataclass
class CaseProgram:
    """One traceable program: ``fn(*args)`` with abstract args."""

    fn: Callable
    args: tuple
    donate: Tuple[int, ...] = ()
    #: additional argument tuples that MUST trace to at most
    #: ``max_traces`` distinct jaxprs together with ``args`` — the
    #: compile-key-cardinality contract (bucketed shapes collapse)
    variants: Sequence[tuple] = ()
    max_traces: int = 1
    x64: bool = False
    #: builder-supplied side facts consumers cannot recover from the
    #: jaxpr: the mem tier reads ``arg_specs``, ``mesh_axes`` and
    #: ``hbm_budget_bytes``
    meta: Optional[dict] = None


@dataclasses.dataclass
class AnalysisCase:
    name: str
    domain: str                      # serving | models | ops | optimizers
    build: Callable[[], CaseProgram]


@dataclasses.dataclass
class CaseIR:
    """A traced case: the jaxpr bundle the IR rules consume."""

    case: AnalysisCase
    prog: CaseProgram
    closed: object                   # jax ClosedJaxpr
    variant_closed: List[object]
    donated_avals: List[object]      # flattened avals of donated args
    origin: Tuple[str, int]          # (abs file, line) of the case fn

    @property
    def name(self) -> str:
        return self.case.name

    @property
    def domain(self) -> str:
        return self.case.domain


def _origin_of(fn) -> Tuple[str, int]:
    """Best-effort def site of the case's program (partials and jit
    wrappers unwrapped) — the anchor for findings that have no single
    equation (donation, consts, cardinality)."""
    seen = 0
    while seen < 8:
        seen += 1
        if isinstance(fn, functools.partial):
            fn = fn.func
            continue
        inner = getattr(fn, "__wrapped__", None)
        if inner is not None and inner is not fn:
            fn = inner
            continue
        break
    code = getattr(fn, "__code__", None)
    if code is not None:
        return (code.co_filename, code.co_firstlineno)
    return (__file__, 1)


# --------------------------------------------------------------------------
# case registry
# --------------------------------------------------------------------------

#: kernel_cases() name -> domain (prefix match, first hit wins); the AOT
#: registry spans ops, optimizers, models and serving already — the IR
#: tier reuses it verbatim rather than maintaining a parallel list
_DOMAIN_PREFIXES = (
    ("optim_", "optimizers"),
    ("gpt2_small_decode", "models"),
    ("gpt2s_prefix_cached", "serving"),
    ("paged_attention", "serving"),
)


#: kernel_cases() names that analysis_cases() re-registers with a richer
#: CaseProgram (variants / max_traces); _aot_cases skips them so each
#: name appears exactly once in the registry
_RICHER_REGISTRATIONS = frozenset({
    "gpt2s_host_tier_gather",
    "gpt2s_host_tier_promote",
})


def _domain_for(name: str) -> str:
    for prefix, domain in _DOMAIN_PREFIXES:
        if name.startswith(prefix):
            return domain
    return "ops"


def _aot_cases(root: Path) -> List[AnalysisCase]:
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from apex_tpu.ops._dispatch import forced_mosaic

    # building the cases traces model inits: stage them like the rest
    with forced_mosaic():
        import tpu_aot

        cases = list(tpu_aot.kernel_cases())

    out: List[AnalysisCase] = []
    for case in cases:
        name, fn, args = case[0], case[1], tuple(case[2])
        if name in _RICHER_REGISTRATIONS:
            # analysis_cases() appends these by hand with variants and a
            # max_traces pin (the compile-key-cardinality probe) that the
            # bare AOT tuple can't carry — one registration per name, the
            # richer one wins
            continue
        donate = tuple(case[3]) if len(case) > 3 else ()

        def build(fn=fn, args=args, donate=donate) -> CaseProgram:
            return CaseProgram(fn=fn, args=args, donate=donate)

        out.append(AnalysisCase(name=name, domain=_domain_for(name),
                                build=build))
    return out


def _build_engine_chunk() -> CaseProgram:
    """The serving hot loop ``generate(paged=True)`` actually runs: the
    engine's jitted ``sync_every``-step ``lax.scan`` decode chunk, at a
    small GPT-2-small pool (tracing cost, not fidelity, scales with the
    pool)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, sync_every=4)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    args = (cache_abs, dvars,
            jax.ShapeDtypeStruct((4,), i32),        # tok
            jax.ShapeDtypeStruct((4,), jnp.bool_),  # done
            jax.ShapeDtypeStruct((4,), i32),        # n_left
            jax.ShapeDtypeStruct((4, 2), jnp.uint32),  # req_keys
            jax.ShapeDtypeStruct((4,), i32))        # samp_i
    return CaseProgram(fn=engine._step_fn(), args=args)


def _build_spec_engine_program() -> CaseProgram:
    """The IN-ENGINE speculative decode chunk (ISSUE 13): the jitted
    ``sync_every``-round scan where each round runs ``draft_len``
    single-token draft steps over the DRAFT pool and verifies the block
    in ONE ``s = draft_len + 1`` paged target step. The draft is a
    1-layer gpt2s-dims model — the shape regime where the round's
    weight stream (W_target + k * W_draft) amortized over >= 2 accepted
    tokens beats the non-speculative per-token stream. The two variants
    pin that per-slot decode state (tok/done/n_left) is TRACED, never a
    compile key: concrete values and abstract structs must stage ONE
    program."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    dcfg = _dc.replace(cfg, num_layers=1)
    draft = GPTModel(dcfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, sync_every=4,
                               draft_model=draft, draft_variables=None,
                               draft_len=1)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dcache_abs = jax.tree.map(sds, engine.draft_cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    ddvars = jax.eval_shape(lambda: draft.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    args = (cache_abs, dcache_abs, dvars, ddvars,
            jax.ShapeDtypeStruct((4,), i32),        # tok (pending)
            jax.ShapeDtypeStruct((4,), jnp.bool_),  # done
            jax.ShapeDtypeStruct((4,), i32))        # n_left
    variant = (cache_abs, dcache_abs, dvars, ddvars,
               np.zeros((4,), np.int32), np.zeros((4,), bool),
               np.full((4,), 7, np.int32))
    return CaseProgram(fn=engine._spec_step_fn(), args=args,
                       variants=[variant], max_traces=1)


def _build_prefill_chunk_program() -> CaseProgram:
    """The chunked-prefill step (ISSUE 13): one 16-token prompt chunk
    of one slot through the paged s>1 path. The two variants trace the
    program at concrete ``valid`` counts 5 and 7 — the chunk's true
    token count is a TRACED operand, so every prompt length shares ONE
    staged program per engine (the compile-key contract that lets the
    frontend interleave prefill chunks between decode chunks without
    recompiling)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, prefill_chunk=16)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    args = (cache_abs, dvars,
            jax.ShapeDtypeStruct((1, 16), i32),     # chunk ids
            jax.ShapeDtypeStruct((), i32),          # slot
            jax.ShapeDtypeStruct((), i32),          # valid
            jax.ShapeDtypeStruct((2,), jnp.uint32),  # req_key
            jax.ShapeDtypeStruct((), i32))          # samp0

    def variant_for(valid: int) -> tuple:
        return (cache_abs, dvars,
                np.zeros((1, 16), np.int32), np.int32(0),
                np.int32(valid), np.zeros((2,), np.uint32), np.int32(0))

    return CaseProgram(fn=engine._prefill_chunk_fn(), args=args,
                       variants=[variant_for(5), variant_for(7)],
                       max_traces=1)


def _build_host_tier_program(kind: str) -> CaseProgram:
    """The tiered KV pool's two device programs (ISSUE 17): the
    demote-side ``gather_pages`` (a pure READ — the cache is NOT
    donated; donating it would free the pool out from under the engine,
    which the aliasing rule must be able to see) and the promote-side
    ``promote_pages`` (cache donated, like every pool-mutating
    program). Both take a fixed null-padded ``HOST_COPY_CHUNK`` page
    row plus a traced count: demote/promote DEPTH is data, never a
    compile key — the two variants build their rows at different depths
    the way the frontend does and must collapse to one jaxpr, so a
    refactor that sizes the row by depth trips
    ir-compile-key-cardinality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving import kv_pool
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, prefix_cache=True,
                               host_tier_bytes=1 << 24)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    C = kv_pool.HOST_COPY_CHUNK

    def row_for(depth: int):
        row = np.zeros((C,), np.int32)
        row[:depth] = np.arange(1, depth + 1)
        return jnp.asarray(row)

    if kind == "gather":
        return CaseProgram(fn=engine._gather_jit,
                           args=(cache_abs, row_for(3)),
                           variants=[(cache_abs, row_for(7))],
                           max_traces=1)
    tiles_abs = jax.tree.map(sds, jax.eval_shape(
        kv_pool.gather_pages, cache_abs, row_for(3)))

    def args_for(depth: int) -> tuple:
        return (cache_abs, row_for(depth), jnp.int32(depth), tiles_abs)

    return CaseProgram(fn=engine._promote_jit, args=args_for(3),
                       variants=[args_for(7)], donate=(0,),
                       max_traces=1)


def _build_admit_bucketed() -> CaseProgram:
    """The engine's prompt-admission program, traced at two prompt
    lengths that land in the SAME bucket under the ENGINE'S OWN
    ``scheduler.prompt_bucket`` (the function ``run()`` pads with before
    its jit boundary — shared, not mirrored, so the contract is binding:
    if admission's bucketing ever stops collapsing raw lengths, the two
    variants stage distinct programs and ir-compile-key-cardinality
    fires)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import (PagedDecodeEngine,
                                            prompt_bucket)

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32

    def args_for(s0: int) -> tuple:
        bucket = prompt_bucket(s0, engine.page_size,
                               cfg.max_position_embeddings)
        return (cache_abs, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    bucket = prompt_bucket(90, engine.page_size,
                           cfg.max_position_embeddings)
    return CaseProgram(fn=engine._admit_fn(bucket), args=args_for(90),
                       variants=[args_for(93)], max_traces=1)


def _build_int8kv_engine_program(kind: str) -> CaseProgram:
    """The QUANTIZED-KV engine programs (docs/serving.md "Quantized KV
    pages"): the ``sync_every``-step decode chunk and the bucketed
    admission over an int8 page pool — the decode chunk stages the
    paged kernel WITH its per-(page, kv_head) scale operands and
    in-kernel dequant, the admission the quantize-on-write prefill
    scatter. Same compile-key contract as the fp cases (two same-bucket
    admission variants, ``max_traces=1``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import (PagedDecodeEngine,
                                            prompt_bucket)

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, sync_every=4,
                               kv_dtype="int8")
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    if kind == "decode":
        args = (cache_abs, dvars,
                jax.ShapeDtypeStruct((4,), i32),           # tok
                jax.ShapeDtypeStruct((4,), jnp.bool_),     # done
                jax.ShapeDtypeStruct((4,), i32),           # n_left
                jax.ShapeDtypeStruct((4, 2), jnp.uint32),  # req_keys
                jax.ShapeDtypeStruct((4,), i32))           # samp_i
        return CaseProgram(fn=engine._step_fn(), args=args)
    assert kind == "admit"

    def args_for(s0: int) -> tuple:
        bucket = prompt_bucket(s0, engine.page_size,
                               cfg.max_position_embeddings)
        return (cache_abs, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    bucket = prompt_bucket(90, engine.page_size,
                           cfg.max_position_embeddings)
    return CaseProgram(fn=engine._admit_fn(bucket), args=args_for(90),
                       variants=[args_for(93)], max_traces=1)


def _build_wq_engine_program(kind: str, policy: str) -> CaseProgram:
    """The QUANTIZED-WEIGHT engine programs (docs/serving.md "Quantized
    weight streaming"): the ``sync_every``-step decode chunk and the
    bucketed admission over a gpt2-small built with a
    ``WeightPrecisionPolicy`` — every block linear stages the fused
    dequant-matmul Pallas kernel (narrow weight + scale operands,
    dequant in VMEM next to the contraction), embeddings/norms/head
    stay fp. ``policy="int4"`` also drops the fp leaves to bf16 (the
    documented aggressive pairing). Same compile-key contract as the fp
    cases (two same-bucket admission variants, ``max_traces=1``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.ops.quant import WeightPrecisionPolicy
    from apex_tpu.serving.scheduler import (PagedDecodeEngine,
                                            prompt_bucket)

    extra = {"param_dtype": jnp.bfloat16} if policy == "int4" else {}
    cfg = gpt2_small_config(dtype=jnp.bfloat16,
                            weight_policy=WeightPrecisionPolicy(policy),
                            **extra)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, sync_every=4)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    if kind == "decode":
        args = (cache_abs, dvars,
                jax.ShapeDtypeStruct((4,), i32),           # tok
                jax.ShapeDtypeStruct((4,), jnp.bool_),     # done
                jax.ShapeDtypeStruct((4,), i32),           # n_left
                jax.ShapeDtypeStruct((4, 2), jnp.uint32),  # req_keys
                jax.ShapeDtypeStruct((4,), i32))           # samp_i
        return CaseProgram(fn=engine._step_fn(), args=args)
    assert kind == "admit"

    def args_for(s0: int) -> tuple:
        bucket = prompt_bucket(s0, engine.page_size,
                               cfg.max_position_embeddings)
        return (cache_abs, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    bucket = prompt_bucket(90, engine.page_size,
                           cfg.max_position_embeddings)
    return CaseProgram(fn=engine._admit_fn(bucket), args=args_for(90),
                       variants=[args_for(93)], max_traces=1)


def _build_frontend_program(kind: str) -> CaseProgram:
    """The serving FRONT-END's programs, bound through its own accessors
    (``ServingFrontend.admission_program`` / ``decode_program``) rather
    than the engine internals they delegate to — if the frontend's pump
    ever grows its own bucketing or decode wrapper, these cases trace
    what it actually dispatches, and ``ir-compile-key-cardinality``
    keeps binding the served compile-key contract."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.frontend import ServingFrontend
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cfg = gpt2_small_config(dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=4,
                               page_size=16, num_pages=33,
                               max_pages_per_seq=16, sync_every=4)
    frontend = ServingFrontend(engine)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 8), jnp.int32)))
    i32 = jnp.int32
    if kind == "decode":
        args = (cache_abs, dvars,
                jax.ShapeDtypeStruct((4,), i32),           # tok
                jax.ShapeDtypeStruct((4,), jnp.bool_),     # done
                jax.ShapeDtypeStruct((4,), i32),           # n_left
                jax.ShapeDtypeStruct((4, 2), jnp.uint32),  # req_keys
                jax.ShapeDtypeStruct((4,), i32))           # samp_i
        return CaseProgram(fn=frontend.decode_program(), args=args)
    assert kind == "admit"

    def args_for(s0: int) -> tuple:
        _, bucket = frontend.admission_program(s0)
        return (cache_abs, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    fn, _ = frontend.admission_program(90)
    return CaseProgram(fn=fn, args=args_for(90), variants=[args_for(93)],
                       max_traces=1)


def _build_llama_windowed_program(kind: str) -> CaseProgram:
    """The windowed-Llama PAGED serving programs (the model-coverage gap
    ISSUE 9 closed): the engine's admission + ``sync_every``-step decode
    chunk over a sliding-window tiny-Llama pool — the decode chunk
    stages the band-gated paged-attention kernel, the admission the
    window-banded flash prefill. Same compile-key contract as the GPT
    cases (two same-bucket admission variants, ``max_traces=1``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.llama import LlamaModel, llama_tiny_config
    from apex_tpu.serving.scheduler import (PagedDecodeEngine,
                                            prompt_bucket)

    cfg = llama_tiny_config(sliding_window=16)
    model = LlamaModel(cfg)
    engine = PagedDecodeEngine(model, variables=None, num_slots=2,
                               page_size=8, num_pages=17,
                               max_pages_per_seq=8, sync_every=2)
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    cache_abs = jax.tree.map(sds, engine.cache)
    dvars = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    i32 = jnp.int32
    if kind == "decode":
        args = (cache_abs, dvars,
                jax.ShapeDtypeStruct((2,), i32),           # tok
                jax.ShapeDtypeStruct((2,), jnp.bool_),     # done
                jax.ShapeDtypeStruct((2,), i32),           # n_left
                jax.ShapeDtypeStruct((2, 2), jnp.uint32),  # req_keys
                jax.ShapeDtypeStruct((2,), i32))           # samp_i
        return CaseProgram(fn=engine._step_fn(), args=args)
    assert kind == "admit"

    def args_for(s0: int) -> tuple:
        bucket = prompt_bucket(s0, engine.page_size,
                               cfg.max_position_embeddings)
        return (cache_abs, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    bucket = prompt_bucket(20, engine.page_size,
                           cfg.max_position_embeddings)
    return CaseProgram(fn=engine._admit_fn(bucket), args=args_for(20),
                       variants=[args_for(22)], max_traces=1)


def _build_tp_engine_program(kind: str, kv_dtype=None,
                             weight_policy=None) -> CaseProgram:
    """The TENSOR-PARALLEL serving programs (serving/tp.py,
    docs/tp_serving.md): the tp=2 engine's shard_map-wrapped admission
    and ``sync_every``-step decode chunk, traced over a deviceless
    ``AbstractMesh`` — the shard_map body (local-head paged attention,
    Megatron collectives, replicated pool bookkeeping) is exactly the
    dtype-drift and compile-key-cardinality surface this tier exists
    for, and it must lint on any host with any device count. Same
    bucketing contract as the single-chip cases (two same-bucket
    admission variants, ``max_traces=1``, bound through the engine's
    own ``prompt_bucket``/``_admit_fn``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel, gpt2_small_config
    from apex_tpu.serving.scheduler import prompt_bucket
    from apex_tpu.serving.tp import (TensorParallelPagedEngine,
                                     abstract_tp_mesh,
                                     infer_variable_specs)

    tp = 2
    pol = None
    if weight_policy is not None:
        from apex_tpu.ops.quant import WeightPrecisionPolicy
        pol = WeightPrecisionPolicy(weight_policy)
    cfg = gpt2_small_config(dtype=jnp.bfloat16, tensor_parallel_size=tp,
                            weight_policy=pol)
    model = GPTModel(cfg)
    engine = TensorParallelPagedEngine(
        model, variables=None, mesh=abstract_tp_mesh(tp), num_slots=4,
        page_size=16, num_pages=33, max_pages_per_seq=16, sync_every=4,
        kv_dtype=kv_dtype)
    dvars, var_specs = infer_variable_specs(model)

    # the declared sharding contract: the mem tier's spec rules
    # (mem-spec-indivisible & co.) check these against the mesh before
    # shard_map ever traces, and its HBM sweep scopes to per-chip bytes
    from jax.sharding import PartitionSpec as P

    meta = {"mesh_axes": {"model": tp}}
    i32 = jnp.int32
    if kind == "decode":
        args = (engine.cache, dvars,
                jax.ShapeDtypeStruct((4,), i32),           # tok
                jax.ShapeDtypeStruct((4,), jnp.bool_),     # done
                jax.ShapeDtypeStruct((4,), i32),           # n_left
                jax.ShapeDtypeStruct((4, 2), jnp.uint32),  # req_keys
                jax.ShapeDtypeStruct((4,), i32))           # samp_i
        meta["arg_specs"] = (engine._cache_specs, var_specs,
                             P(), P(), P(), P(), P())
        return CaseProgram(fn=engine._step_fn(), args=args, meta=meta)
    assert kind == "admit"
    meta["arg_specs"] = (engine._cache_specs, var_specs,
                         P(), P(), P(), P(), P(), P())

    def args_for(s0: int) -> tuple:
        bucket = prompt_bucket(s0, engine.page_size,
                               cfg.max_position_embeddings)
        return (engine.cache, dvars,
                jax.ShapeDtypeStruct((1, bucket), i32),   # padded ids
                jax.ShapeDtypeStruct((), i32),            # s0
                jax.ShapeDtypeStruct((), i32),            # slot
                jax.ShapeDtypeStruct((), i32),            # n_pages
                jax.ShapeDtypeStruct((2,), jnp.uint32),   # req_key
                jax.ShapeDtypeStruct((), i32))            # samp0
    bucket = prompt_bucket(90, engine.page_size,
                           cfg.max_position_embeddings)
    return CaseProgram(fn=engine._admit_fn(bucket), args=args_for(90),
                       variants=[args_for(93)], max_traces=1, meta=meta)


def _build_optimizer_update(kind: str) -> CaseProgram:
    """sgd/novograd fused-update steps over the flat-buffer layout
    (adam/lamb already arrive via ``kernel_cases``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import flat_buffer, optim_kernels

    f32 = jnp.float32
    tree = {"emb": (8192, 64), "w1": (768, 768), "b": (768,)}
    spec = flat_buffer.build_spec(
        {k: jax.ShapeDtypeStruct(s, f32) for k, s in tree.items()})
    seg = np.asarray(spec.segment_rows())
    buf = jax.ShapeDtypeStruct((spec.total_rows, flat_buffer.LANE), f32)
    if kind == "sgd":
        fn = functools.partial(optim_kernels.sgd_update, lr=1e-3,
                               momentum=0.9, weight_decay=1e-4)
        return CaseProgram(fn=fn, args=(buf, buf, buf), donate=(1, 2))
    assert kind == "novograd"

    def nv(g, p, m, v):
        return optim_kernels.novograd_update(
            g, p, m, v, jnp.asarray(seg), spec.num_tensors, beta1=0.95,
            beta2=0.98, eps=1e-8, weight_decay=1e-3, lr=1e-3, step=1)

    vbuf = jax.ShapeDtypeStruct((spec.num_tensors,), f32)
    return CaseProgram(fn=nv, args=(buf, buf, buf, vbuf),
                       donate=(1, 2, 3))


def analysis_cases(root) -> List[AnalysisCase]:
    """The IR tier's registry: every AOT kernel case + the serving-engine
    programs + the remaining fused-optimizer steps. Spans serving,
    models, ops and optimizers (asserted by the tier-1 suite)."""
    root = Path(root).resolve()
    cases = _aot_cases(root)
    cases.append(AnalysisCase("gpt2s_engine_decode_chunk", "serving",
                              _build_engine_chunk))
    cases.append(AnalysisCase("gpt2s_engine_admit_bucketed", "serving",
                              _build_admit_bucketed))
    cases.append(AnalysisCase("gpt2s_engine_spec_step_chunk", "serving",
                              _build_spec_engine_program))
    cases.append(AnalysisCase("gpt2s_engine_prefill_chunk", "serving",
                              _build_prefill_chunk_program))
    cases.append(AnalysisCase(
        "gpt2s_frontend_decode_chunk", "serving",
        lambda: _build_frontend_program("decode")))
    cases.append(AnalysisCase(
        "gpt2s_frontend_admit_bucketed", "serving",
        lambda: _build_frontend_program("admit")))
    cases.append(AnalysisCase(
        "llama_windowed_engine_decode_chunk", "serving",
        lambda: _build_llama_windowed_program("decode")))
    cases.append(AnalysisCase(
        "llama_windowed_engine_admit_bucketed", "serving",
        lambda: _build_llama_windowed_program("admit")))
    cases.append(AnalysisCase(
        "tp2_engine_decode_chunk", "serving",
        lambda: _build_tp_engine_program("decode")))
    cases.append(AnalysisCase(
        "tp2_engine_admit_bucketed", "serving",
        lambda: _build_tp_engine_program("admit")))
    cases.append(AnalysisCase(
        "gpt2s_host_tier_gather", "serving",
        lambda: _build_host_tier_program("gather")))
    cases.append(AnalysisCase(
        "gpt2s_host_tier_promote", "serving",
        lambda: _build_host_tier_program("promote")))
    cases.append(AnalysisCase(
        "gpt2s_int8kv_engine_decode_chunk", "serving",
        lambda: _build_int8kv_engine_program("decode")))
    cases.append(AnalysisCase(
        "gpt2s_int8kv_engine_admit_bucketed", "serving",
        lambda: _build_int8kv_engine_program("admit")))
    cases.append(AnalysisCase(
        "tp2_int8kv_engine_decode_chunk", "serving",
        lambda: _build_tp_engine_program("decode", kv_dtype="int8")))
    cases.append(AnalysisCase(
        "gpt2s_w8_engine_decode_chunk", "serving",
        lambda: _build_wq_engine_program("decode", "int8")))
    cases.append(AnalysisCase(
        "gpt2s_w8_engine_admit_bucketed", "serving",
        lambda: _build_wq_engine_program("admit", "int8")))
    cases.append(AnalysisCase(
        "gpt2s_w4_engine_decode_chunk", "serving",
        lambda: _build_wq_engine_program("decode", "int4")))
    cases.append(AnalysisCase(
        "tp2_w8_engine_decode_chunk", "serving",
        lambda: _build_tp_engine_program("decode", weight_policy="int8")))
    cases.append(AnalysisCase(
        "optim_sgd_momentum_buffer", "optimizers",
        lambda: _build_optimizer_update("sgd")))
    cases.append(AnalysisCase(
        "optim_novograd_buffer", "optimizers",
        lambda: _build_optimizer_update("novograd")))
    return cases


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------

def _trace(prog: CaseProgram, args: tuple):
    import contextlib

    import jax

    from apex_tpu.ops._dispatch import forced_mosaic

    ctx = jax.enable_x64(True) if prog.x64 \
        else contextlib.nullcontext()
    with forced_mosaic(), ctx:
        return jax.make_jaxpr(prog.fn)(*args)


def build_case_ir(case: AnalysisCase) -> CaseIR:
    """Trace one case (plus its cardinality variants) into a CaseIR."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")   # before jax wakes up
    import jax

    prog = case.build()
    closed = _trace(prog, prog.args)
    variant_closed = [_trace(prog, v) for v in prog.variants]
    donated = []
    for i in prog.donate:
        if 0 <= i < len(prog.args):
            # leaves are ShapeDtypeStructs/arrays: shape+dtype is all the
            # aliasing check needs
            donated.extend(jax.tree.leaves(prog.args[i]))
    return CaseIR(case=case, prog=prog, closed=closed,
                  variant_closed=variant_closed, donated_avals=donated,
                  origin=_origin_of(prog.fn))
