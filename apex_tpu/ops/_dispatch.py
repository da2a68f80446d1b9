"""Backend dispatch for Pallas kernels.

Kernels compile via Mosaic on TPU. Off-TPU (CPU tests, debugging) the same
kernels run through the Pallas interpreter so numerics tests cover the real
kernel code, not a separate fallback — replacing the reference's
"skip-if-extension-not-built" gating (apex/contrib/test SkipTestCase) with
run-everywhere kernels.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax


@functools.cache
def _backend() -> str:
    return jax.default_backend()


def interpret() -> bool:
    """True when pallas_call must run in interpreter mode (non-TPU backend).

    ``APEX_TPU_FORCE_MOSAIC=1`` (see :func:`forced_mosaic`) forces the
    Mosaic path even when the default backend is CPU — used by the offline
    AOT sweep (``tpu_aot.py``), which lowers kernels against a described TPU
    *topology* (``jax.experimental.topologies``) where
    ``jax.default_backend()`` still reports the host platform.
    """
    if os.environ.get("APEX_TPU_FORCE_INTERPRET") == "1":
        return True
    if os.environ.get("APEX_TPU_FORCE_MOSAIC") == "1":
        return False
    return _backend() != "tpu"


@contextlib.contextmanager
def forced_mosaic():
    """Stage the Mosaic kernel path although the default backend is the
    CPU, for the duration only (the lint/cost tracers and the AOT compiles
    for a described topology); restores the environment on exit.

    Exit also clears jax's trace caches: tracing through module-level jit
    wrappers bakes ``interpret=False`` into their cached jaxprs, and code
    EXECUTING the same op at the same shapes afterwards in this process
    would reuse the poisoned trace and fail on the CPU. Dropping the caches
    costs a re-trace, never correctness."""
    keys = ("APEX_TPU_FORCE_MOSAIC", "APEX_TPU_FORCE_INTERPRET")
    old = {k: os.environ.get(k) for k in keys}
    os.environ["APEX_TPU_FORCE_MOSAIC"] = "1"
    os.environ.pop("APEX_TPU_FORCE_INTERPRET", None)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        jax.clear_caches()


#: every kernel this package launches, by the label its ``pallas_call``
#: carries into the compiled program: ``metadata={"kernel": <label>}``
#: lands in the custom call's own HLO text as
#: ``kernel_metadata={"kernel":"<label>"}``, which is the op's name on the
#: device trace's ``XLA Ops`` line (docs/observability.md "Kernel
#: labels"). ``name=`` is NOT used: it enters the name stack and would
#: rename the op (``%attention.N`` -> ``%flash_fwd.N``) under readers that
#: find kernels by their flax module. A closed set — a new kernel adds its
#: label here first.
KERNEL_LABELS = (
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "paged_attention",
    "paged_window_attention", "paged_latent_attention", "paged_write",
    "gated_delta_step",
    "layer_norm_fwd", "layer_norm_bwd", "xentropy_fwd", "xentropy_bwd",
    "l2norm", "lamb_phase1", "lamb_phase2", "adam", "sgd", "novograd",
    "scale", "group_norm_fwd", "group_norm_bwd", "scaled_softmax_fwd",
    "scaled_softmax_bwd", "dequant_matmul")


def pallas_call(fn, *, kernel: str, out_shape, **kw):
    """``pl.pallas_call`` of the kernel body ``fn`` that names the kernel
    (``kernel``, one of :data:`KERNEL_LABELS`; an unknown label raises
    here, at trace time) and propagates varying-manual-axes (vma).

    Inside ``shard_map(check_vma=True)`` a pallas_call must declare how its
    outputs vary over mesh axes; the correct answer for our elementwise/
    row-tiled kernels is "varies over the union of the inputs' axes". This
    wrapper stamps that union onto every ShapeDtypeStruct in ``out_shape`` at
    call time, so all ops work under both jit and manual shard_map without
    per-site bookkeeping.
    """
    from jax.experimental import pallas as pl

    from jax import lax

    if kernel not in KERNEL_LABELS:
        raise ValueError(
            f"pallas_call: unknown kernel label {kernel!r}; add it to "
            f"apex_tpu.ops._dispatch.KERNEL_LABELS (has {KERNEL_LABELS})")

    def call(*args):
        vma = frozenset()
        for a in jax.tree.leaves(args):
            vma = vma | jax.typeof(a).vma

        def lift(a):
            # align every input to the union vma (a replicated operand next
            # to a varying one trips "varying manual axes must match" inside
            # the kernel body)
            missing = vma - jax.typeof(a).vma
            return lax.pcast(a, tuple(missing), to="varying") if missing else a

        def stamp(s):
            if isinstance(s, jax.ShapeDtypeStruct):
                return jax.ShapeDtypeStruct(s.shape, s.dtype, vma=vma)
            return s

        args = jax.tree.map(lift, args)
        os_ = jax.tree.map(stamp, out_shape)
        return pl.pallas_call(fn, out_shape=os_,
                              metadata={"kernel": kernel}, **kw)(*args)

    return call


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def row_tile(n_cols: int, n_rows: int, *, budget_bytes: int = 2 * 1024 * 1024,
             cap: int = 256, bytes_per_el: int = 4) -> int:
    """Row-tile size so one (tile, n_cols) fp32 block stays within a VMEM
    budget; multiple of 8 (sublane), bounded by ``cap`` and the row count."""
    tile = max(8, budget_bytes // max(1, n_cols * bytes_per_el))
    tile = min(tile, cap)
    tile = max(8, (tile // 8) * 8)
    return min(tile, round_up(n_rows, 8))
