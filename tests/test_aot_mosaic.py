"""Kernels that compile for the chip, kept among the tests.

Compiles a representative subset of the on-chip kernel configurations with
the installed TPU compiler against a v5e topology that is described, not
attached — Mosaic block rules, layouts, and scoped-VMEM limits all enforced
with no TPU. Pins the r5 Adam regression: at the BERT-Large buffer shape the
7-buffer Adam kernel overflowed Mosaic's 16 MB scoped-VMEM stack at block 256
(caught by this path, fixed via the n_bufs-aware ``_row_block``).

This is the ONE test file that describes a topology: only one process at a
time may load the TPU's library, so the call lives in a fixture (never at
import) and every such test lives here, on one xdist worker. The kernel
cases that compile in a few seconds run in tier-1; the multi-chip sweeps
are ``slow``. The full sweep (every config + the BERT-Large train step)
is ``python tpu_aot.py`` -> ``AOT_<tag>.json``.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CASE_NAMES = [
    "layer_norm_bwd",
    "flash_bwd_seq512",
    "flash_causal_dropout_bwd",
    "xentropy_bwd",
    "scaled_upper_triang_softmax",
    "optim_adam_bert_large_buffer",   # r5 scoped-VMEM regression pin
    "optim_lamb_bert_large_buffer",
    "group_norm_bwd_fp32",
    "flash_lse_bwd_with_lse_cotangent",
    "flash_window128_bwd",
    "gpt2_small_decode128_int8",      # serving path: scan decode + W8A8
    "paged_attention_gpt2s_decode",   # paged serving: scalar-prefetch gather
    "paged_attention_gpt2l_cell",     # the benchmark's serving cell: 20 heads x 8 pages a step
    "paged_latent_attention_glm_cell",  # the latent cell: 20 heads x one 640-lane entry
    "moe_grouped_experts_glm_decode",   # ragged_dot -> XLA's %ragged-dot-* Mosaic calls
    "gpt2s_prefix_cached_admit",      # prefix cache: tail-only admission
    "gpt2s_paged_spec_verify",        # s=4 query block: spec verify step
    "gpt2s_chunked_prefill_step",     # chunked prefill through the s>1 path
    "gpt2s_paged_decode_int8kv",      # quantized pool: in-kernel dequant
    "gpt2s_paged_decode_w8",          # w8 policy: fused dequant-matmul
    "gpt2s_fused_dequant_w4",         # int4 nibbles + grouped scales
    "gpt2s_host_tier_gather",         # tiered pool: demote-side page read
    "gpt2s_host_tier_promote",        # tiered pool: promote-side scatter
]

#: ISSUE 17: the tiered pool's copy programs are plain XLA data movers
#: by design — the pin is INVERTED (zero tpu_custom_call sites). A
#: Mosaic kernel appearing here must be acknowledged by moving the name
#: out of this set.
NO_MOSAIC_CASES = {"gpt2s_host_tier_gather", "gpt2s_host_tier_promote"}


#: cases measured above ~5 s on the sandbox CPU stay out of tier-1
SLOW_CASES = {"gpt2_small_decode128_int8"}


@pytest.fixture(scope="module")
def aot():
    """The process state these compiles need, for this module only: Pallas
    staged through Mosaic on a CPU default backend, and the persistent
    compilation cache off (an executable compiled for a described topology
    cannot be read back without a chip — it would only warn and recompile)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from apex_tpu.ops._dispatch import forced_mosaic

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with forced_mosaic():
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


def _describe(name):
    import tpu_aot

    try:
        return tpu_aot._topology(name)
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip(f"no {name} topology can be described here: {e}")


@pytest.fixture(scope="module")
def topo(aot):
    """Four described v5e chips: enough for every case but the ring."""
    return _describe("v5e:2x2")


@pytest.fixture(scope="module")
def topo8(aot):
    return _describe("v5e:2x4")


@pytest.fixture(scope="module")
def mesh(topo):
    import tpu_aot

    return tpu_aot._mesh(topo)


@pytest.fixture(scope="module")
def cases(aot):
    import tpu_aot

    return {name: (fn, structs, rest[0] if rest else ())
            for name, fn, structs, *rest in tpu_aot.kernel_cases()}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.slow) if n in SLOW_CASES else n
    for n in CASE_NAMES])
def test_kernel_compiles_to_mosaic_under_budget(name, mesh, cases):
    import tpu_aot

    fn, structs, donate = cases[name]
    r = tpu_aot.case_result(mesh, fn, structs, donate)
    assert r["ok"]
    if name in NO_MOSAIC_CASES:
        assert r["tpu_custom_call_sites"] == 0, (
            "a Mosaic kernel appeared in a plain-XLA copy program — "
            "move the name out of NO_MOSAIC_CASES to acknowledge it")
    else:
        assert r["tpu_custom_call_sites"] >= 1, (
            "kernel lowered without a tpu_custom_call — interpret-mode "
            "leak?")
    assert r["under_16gib_budget"], r
    # static perf-lint: no copy/transpose result over 256 MiB (the r3
    # 86 GB relayout class is visible in compiled text)
    assert not r["giant_copy_flags"], r["giant_copy_flags"]


@pytest.mark.slow
def test_multichip_ring_cp_compiles_for_tpu(topo8):
    """The context-parallel path has only ever RUN on the virtual CPU mesh
    (interpret mode); this pins that the same sharded program — ring
    attention rotating K/V by ppermute around Mosaic flash kernels —
    COMPILES for the real v5e topology."""
    import tpu_aot

    r = tpu_aot.multichip_aot(topo8, only=["cp2_ring_attention_grad"])
    r = r["cp2_ring_attention_grad"]
    assert r["ok"], r
    assert r["tpu_custom_call_sites"] >= 2, "flash kernels missing"
    assert r["collective_permutes"] >= 1, "ring rotation missing"


@pytest.mark.slow
def test_multichip_tp_paged_serving_compiles_for_tpu(topo):
    """ISSUE 10 acceptance: the tensor-parallel sharded admit + decode
    programs (serving/tp.py) AOT-compile for the described v5e
    topology with per-chip argument+output+temp bytes under the 16 GiB
    budget, at a shape where the UNSHARDED pool does NOT fit one chip —
    the model-size-ceiling claim of docs/tp_serving.md as a compile
    artifact. (tp=4, not 2: the decode scan
    double-buffers the pool carry, so a chip needs ~2x its shard —
    tpu_aot.py's shape comment records both compile-failure lessons.)
    Also requires the Megatron all-reduces and the Mosaic kernels
    (paged attention / flash prefill) to actually be present in the
    lowered program.

    ISSUE 18: the byte assertions are no longer hand-typed pins — the
    mem lint tier's STATIC per-chip estimate (traced on CPU, tiled-
    padded liveness sweep) must land within +/-20% of what the compiler
    measures, per case, in BOTH directions. If the model drifts (a new
    resident buffer the sweep misses) or the program drifts (a buffer
    the sweep still charges but the compiler elided), this fails and
    whichever side regressed has to be fixed — the lint tier's fit
    proofs are only worth trusting while this band holds."""
    import tpu_aot

    from apex_tpu.analysis.mem import ACCEPTANCE_TO_AOT, acceptance_estimates

    # the acceptance inequality's first half: one chip cannot hold the
    # unsharded pool (lane-exact tiles, so these bytes are physical)
    assert tpu_aot.tp_serving_pool_bytes() > tpu_aot.HBM_BUDGET

    est = acceptance_estimates(REPO)
    names = sorted(ACCEPTANCE_TO_AOT.values())
    assert sorted(est) == names
    r = tpu_aot.multichip_aot(topo, only=names)
    pool_shard = tpu_aot.tp_serving_pool_bytes() // tpu_aot.TP_SERVING_TP
    for name in names:
        c, e = r[name], est[name]
        assert c["ok"], c
        assert c["all_reduces"] >= 1, "Megatron TP collectives missing"
        assert c["tpu_custom_call_sites"] >= 1, (
            "Mosaic kernels missing — interpret-mode leak?")
        # static-vs-measured peak band (the mem tier's calibration pin)
        measured = c["peak_estimate_bytes"]
        assert e.scope == "per-chip", e
        assert 0.8 * measured <= e.peak_bytes <= 1.2 * measured, (
            f"{name}: static {e.peak_bytes:,} B vs AOT-measured "
            f"{measured:,} B drifted past +/-20%")
        # the budget verdict must agree on both sides, and the static
        # side's input working set carries at least this chip's pool
        # shard — the sharded pool is genuinely in the program
        static_under = e.peak_bytes <= tpu_aot.HBM_BUDGET
        assert static_under == bool(c["under_16gib_budget"]), (c, e)
        assert static_under, c
        static_in = sum(b.padded_bytes for b in e.boundary
                        if b.kind == "in")
        assert static_in >= pool_shard, (static_in, pool_shard)
        assert c["argument_bytes"] >= pool_shard, c
    # quantized weight streaming (docs/serving.md): the w8 decode chunk
    # carries the SAME sharded pool but int8 block-linear weights — the
    # per-chip footprint must genuinely drop vs the bf16 program, and
    # the static model must see the same ordering
    fp, w8 = r["tp4_paged_engine_decode_chunk"], r["tp4_paged_engine_decode_w8"]
    assert w8["argument_bytes"] < fp["argument_bytes"], (fp, w8)
    assert w8["peak_estimate_bytes"] < fp["peak_estimate_bytes"], (fp, w8)
    assert est["tp4_paged_engine_decode_w8"].peak_bytes < \
        est["tp4_paged_engine_decode_chunk"].peak_bytes, est


def _gpt2l_cell():
    """``gpt2-large.chat-closed16`` at its widths, 2 of 36 layers: 16
    slots, 20 heads of 64, the 2 GiB pool's 729 pages of 16, 64-page
    tables, ``sync_every`` 4."""
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(vocab_size=50304, hidden_size=1280, num_layers=2,
                    num_heads=20, max_position_embeddings=1024,
                    dtype=jnp.bfloat16, param_dtype=jnp.float32)
    return GPTModel(cfg), dict(slots=16, num_pages=729, max_pages=64)


def _glm_cell():
    """``glm-4.7-flash.docqa-closed32`` at its widths, one layer of each
    kind (dense, routed experts): 32 slots, one 640-lane latent entry a
    token, the 4 GiB pool's 38,837 pages, 2048-page tables."""
    from apex_tpu.models.glm4_moe_lite import (Glm4MoeLiteConfig,
                                               Glm4MoeLiteModel)

    cfg = Glm4MoeLiteConfig(num_layers=2, max_position_embeddings=32768)
    return Glm4MoeLiteModel(cfg), dict(slots=32, num_pages=38837,
                                       max_pages=2048)


#: name -> (the cell's model and pool, the engine program, its arguments
#: after ``(cache, variables)`` from the slot count)
POOL_PROGRAMS = {
    "gpt2l_decode_chunk": (_gpt2l_cell, "step"),
    "glm_decode_chunk": (_glm_cell, "step"),
    "gpt2l_admit256": (_gpt2l_cell, "admit"),
}


@pytest.mark.parametrize("name", list(POOL_PROGRAMS))
def test_no_program_re_lays_the_page_pool(name, mesh):
    """No program re-lays the page pool: not in its loop, not where it
    begins, not where it ends (docs/serving.md "Page-pool layout").

    Two things keep it so. Every program that writes the pool writes it
    ROW-MAJOR, the layout the decode kernels (like every Mosaic call)
    take it in: a scatter over the pool's head axis made XLA carry the
    pool ``{3,1,2,0}`` and put a ``copy`` of every layer's whole K and V
    pool in front of every kernel of every decode step, 18 of the 33 ms
    of a GPT-2 large step (PERF.md, PR 32). And the pool is HELD 128
    lanes wide (two 64-wide heads a row, ``kv_pool.heads_per_row``), so
    row-major is also the device's default layout for it between
    programs: a ``bf16[729,20,16,64]`` pool lay ``{0,3,2,1}`` there and
    every program copied every layer's pool once at its entry and once
    at its exit, 6 of a 15 ms step and 24 of an admission's 32 ms
    (PERF.md, PR 34).

    The decode chunk (``PagedDecodeEngine._step_fn()``) and an admit
    program, compiled for the described v5e at a cell's widths: no
    ``copy`` or ``transpose`` whose result has the pool's shape ANYWHERE
    in the program, no value of the pool's shape laid out ``{3,1,2,0}``,
    and every pool parameter and result of the entry computation
    row-major, ``{3,2,1,0}``. In both, the page write is a Mosaic call
    that aliases its pool operand."""
    import re

    import jax
    import jax.numpy as jnp

    import tpu_aot
    from apex_tpu.serving import kv_pool
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    cell, program = POOL_PROGRAMS[name]
    model, pool = cell()
    slots = pool["slots"]
    # the engine's own pool is small (it is never run); the compiled
    # program takes the cell's
    engine = PagedDecodeEngine(model, variables=None, num_slots=slots,
                               page_size=16, num_pages=slots + 1,
                               max_pages_per_seq=pool["max_pages"],
                               sync_every=4)
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        model.config, slots, num_pages=pool["num_pages"], page_size=16,
        max_pages_per_seq=pool["max_pages"]))
    i32 = jnp.int32
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), i32)))
    sds = jax.ShapeDtypeStruct
    if program == "step":
        fn = engine._step_fn()
        rest = [sds((slots,), i32), sds((slots,), jnp.bool_),
                sds((slots,), i32), sds((slots, 2), jnp.uint32),
                sds((slots,), i32)]
    else:
        fn = engine._admit_fn(256)
        rest = [sds((1, 256), i32), sds((), i32), sds((), i32),
                sds((), i32), sds((2,), jnp.uint32)]
    txt = tpu_aot.compile_replicated(mesh, fn, [cache, variables] + rest,
                                     (0,)).as_text()

    pools = {",".join(map(str, x.shape))
             for lc in cache["layers"] for x in lc.values()}
    assert len(pools) == 1, pools
    shape = re.escape(pools.pop())
    moved = [ln.strip()[:200] for ln in txt.splitlines()
             if re.search(rf"= \w+\[{shape}\]\S* (copy|transpose)\(", ln)]
    assert not moved, (
        f"{len(moved)} pool-shaped copies in the program (loop body: "
        f"{sum('while/body' in m for m in moved)}): {moved[:2]}")
    # the module's header line holds ``entry_computation_layout={(the
    # parameters)->(the results)}``
    entry = next(ln for ln in txt.splitlines()
                 if "entry_computation_layout" in ln)
    held = re.findall(rf"\w+\[{shape}\]\{{([\d,]+)", entry)
    # K and V (or one latent tensor) a layer, as parameter and as result
    assert len(held) == 2 * len(cache["layers"]) * len(cache["layers"][0])
    assert set(held) == {"3,2,1,0"}, (
        f"the pool enters or leaves the program laid out {set(held)}")
    twisted = re.findall(rf"\w+\[{shape}\]\{{3,1,2,0[^}}]*\}}", txt)
    assert not twisted, (
        f"{len(twisted)} pool-shaped values laid out {{3,1,2,0}}: "
        f"{twisted[:2]}")
    # the label's JSON opens on a line of its own, after the call's
    # attributes
    writes = re.findall(r'custom_call_target="tpu_custom_call"([^\n]*)\n'
                        r'"kernel":"paged_write"', txt)
    assert writes, "the page write is no Mosaic call"
    assert all("output_to_operand_aliasing" in attrs for attrs in writes), (
        "a page write does not alias its pool operand")
    if program == "step":
        # the work list of live blocks (ops/_page_walk.py) is the same for
        # every layer of a decode step: the program computes it ONCE (one
        # gather of the items' rows of 8 table entries), and both layers'
        # kernels run under the same traced grid bound
        n_items = slots * pool["max_pages"] // 8
        assert len(re.findall(rf"= s32\[{n_items},8\]\S* gather\(", txt)) == 1
        bounds = re.findall(r"custom-call\(([^,()]+), [^\n]*\n"
                            r'"kernel":"paged_(?:latent_)?attention"', txt)
        assert len(bounds) == 2 and bounds[0] == bounds[1], bounds


def _mellum_cell(periods=1):
    """``mellum2-12b-a2.5b.ide-closed48`` at its widths, ``periods`` of its
    layers (sliding, sliding, sliding, full): 48 slots, 4 kv heads of 128,
    the 2.5 GiB pool's 40,961 pages behind 2048-page tables for the full
    layer and rings of 65 pages a slot for the sliding ones."""
    from apex_tpu.models.mellum import MellumConfig, MellumModel

    cfg = MellumConfig(num_layers=4 * periods,
                       layer_types=MellumConfig().layer_types[:4 * periods],
                       max_position_embeddings=32768)
    return MellumModel(cfg), dict(slots=48, num_pages=40961, max_pages=2048)


@pytest.mark.parametrize("program", ["step", "admit"])
def test_two_groups_of_pages_compile_for_the_chip(program, mesh):
    """The decode chunk and an admission of a model that mixes windowed and
    full layers (``kv_pool.layer_groups``), compiled for the described v5e:
    the decode chunk holds BOTH paged-attention programs as Mosaic calls,
    banded (label ``paged_window_attention``, over the rings) and not
    (``paged_attention``, over the block table), three to one; the
    admission (4096 tokens: four windows, so the rings get a slice of the
    buffer) writes every layer's pages through the aliasing page write;
    and neither program re-lays either group's pool."""
    import re

    import jax
    import jax.numpy as jnp

    import tpu_aot
    from apex_tpu.serving import kv_pool
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    model, pool = _mellum_cell()
    slots = pool["slots"]
    engine = PagedDecodeEngine(model, variables=None, num_slots=slots,
                               page_size=16, num_pages=slots + 1,
                               max_pages_per_seq=pool["max_pages"],
                               sync_every=4)
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        model.config, slots, num_pages=pool["num_pages"], page_size=16,
        max_pages_per_seq=pool["max_pages"]))
    i32 = jnp.int32
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), i32)))
    sds = jax.ShapeDtypeStruct
    if program == "step":
        fn = engine._step_fn()
        rest = [sds((slots,), i32), sds((slots,), jnp.bool_),
                sds((slots,), i32), sds((slots, 2), jnp.uint32),
                sds((slots,), i32)]
    else:
        fn = engine._admit_fn(4096)
        rest = [sds((1, 4096), i32), sds((), i32), sds((), i32),
                sds((), i32), sds((2,), jnp.uint32)]
    txt = tpu_aot.compile_replicated(mesh, fn, [cache, variables] + rest,
                                     (0,)).as_text()

    pools = sorted({x.shape for lc in cache["layers"] for x in lc.values()})
    assert pools == [(1 + 48 * 65, 4, 16, 128), (40961, 4, 16, 128)]
    calls = re.findall(r'custom_call_target="tpu_custom_call"([^\n]*)\n'
                       r'"kernel":"(\w+)"', txt)
    labels = [label for _, label in calls]
    if program == "step":
        assert labels.count("paged_window_attention") == 3
        assert labels.count("paged_attention") == 1
    else:
        assert labels.count("flash_fwd") == 4
        assert not any(label.startswith("paged_") and label != "paged_write"
                       for label in labels)
    writes = [attrs for attrs, label in calls if label == "paged_write"]
    assert len(writes) == 4
    assert all("output_to_operand_aliasing" in attrs for attrs in writes)
    for shape in pools:
        shape = re.escape(",".join(map(str, shape)))
        moved = [ln.strip()[:200] for ln in txt.splitlines()
                 if re.search(rf"= \w+\[{shape}\]\S* (copy|transpose)\(",
                              ln)]
        assert not moved, moved[:2]
        entry = next(ln for ln in txt.splitlines()
                     if "entry_computation_layout" in ln)
        assert set(re.findall(rf"\w+\[{shape}\]\{{([\d,]+)", entry)) == {
            "3,2,1,0"}


def _qwen3_next_cell(periods=1):
    """``qwen3-next-80b-a3b.longchat-closed64`` at its widths, ``periods`` of
    its layers (linear, linear, linear, full): 64 slots, 128 of 512 experts
    held, a quarter of the vocabulary, 2 kv heads of 256 behind 2048-page
    tables over the 2 GiB pool's 32,769 pages, and for each linear layer a
    float32 state of 32 x 128 x 128 a slot."""
    from apex_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel

    cfg = Qwen3NextConfig(num_layers=4 * periods, vocab_size=37984,
                          experts_held=128,
                          max_position_embeddings=32768)
    return Qwen3NextModel(cfg), dict(slots=64, num_pages=32769,
                                     max_pages=2048)


@pytest.mark.parametrize("program", ["step", "admit"])
def test_a_state_group_beside_pages_compiles_for_the_chip(program, mesh):
    """The decode chunk and an admission of a model whose linear-attention
    layers keep one recurrent state a slot (``kv_pool.layer_groups``: a
    STATE group beside the block table's), compiled for the described v5e.

    The decode chunk holds one ``gated_delta_step`` Mosaic call a linear
    layer, each aliasing its state operand, and one unbanded
    ``paged_attention`` for the full layer, whose grid bound is traced and
    whose work list over 64 tables of 2048 entries fits the chip's scalar
    memory; NO ``copy`` or ``transpose``
    anywhere in the program has the state's shape, so the chunk's scan over
    steps and the loop over layers carry 1.6 GB of state where it lies; the
    state enters and leaves the program row-major. The admission (4096
    tokens) runs the chunked rule as plain XLA (no labelled kernel), flash
    attention for the full layer, and writes the slot's state row with no
    state-shaped copy either."""
    import re

    import jax
    import jax.numpy as jnp

    import tpu_aot
    from apex_tpu.serving import kv_pool
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    model, pool = _qwen3_next_cell()
    slots = pool["slots"]
    engine = PagedDecodeEngine(model, variables=None, num_slots=2,
                               page_size=16, num_pages=3,
                               max_pages_per_seq=pool["max_pages"],
                               sync_every=4)
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        model.config, slots, num_pages=pool["num_pages"], page_size=16,
        max_pages_per_seq=pool["max_pages"]))
    i32 = jnp.int32
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), i32)))
    sds = jax.ShapeDtypeStruct
    if program == "step":
        fn = engine._step_fn()
        rest = [sds((slots,), i32), sds((slots,), jnp.bool_),
                sds((slots,), i32), sds((slots, 2), jnp.uint32),
                sds((slots,), i32)]
    else:
        fn = engine._admit_fn(4096)
        rest = [sds((1, 4096), i32), sds((), i32), sds((), i32),
                sds((), i32), sds((2,), jnp.uint32)]
    compiled = tpu_aot.compile_replicated(mesh, fn,
                                          [cache, variables] + rest, (0,))
    txt = compiled.as_text()

    state = (slots, 32, 128, 128)
    assert [lc["delta_state"].shape for lc in cache["layers"][:3]] == [
        state] * 3
    assert sorted(cache["layers"][3]) == ["k_pages", "v_pages"]
    calls = re.findall(r'custom_call_target="tpu_custom_call"([^\n]*)\n'
                       r'"kernel":"(\w+)"', txt)
    labels = [label for _, label in calls]
    if program == "step":
        steps = [attrs for attrs, label in calls
                 if label == "gated_delta_step"]
        assert len(steps) == 3
        assert all("output_to_operand_aliasing" in attrs for attrs in steps)
        assert labels.count("paged_attention") == 1
        # the walk's scalar-prefetch operands (ops/_page_walk.py), which
        # Mosaic holds in the v5e's 1 MiB of SMEM for the whole call: a
        # traced grid bound, then the resolved pages of the worst case's
        # 64 x 256 work items (8 a block), their slots and blocks, the
        # slots' running offsets and lengths
        walk, = [attrs for attrs, label in calls
                 if label == "paged_attention"]
        operands = walk.split("operand_layout_constraints={")[1]
        scalars = re.findall(r"s32\[(\d*)\]", operands.split("bf16[")[0])
        assert scalars[0] == ""
        scalars = [int(n) for n in scalars[1:]]
        assert scalars == [64 * 256 * 8, 64 * 256, 64 * 256, 65, 64]
        assert 4 * sum(scalars) == 655_876 < 2 ** 20
    else:
        assert "gated_delta_step" not in labels
        assert labels.count("flash_fwd") == 1
    assert labels.count("paged_write") == 1
    for shape in (state, (pool["num_pages"], 2, 16, 256)):
        shape = re.escape(",".join(map(str, shape)))
        moved = [ln.strip()[:200] for ln in txt.splitlines()
                 if re.search(rf"= \w+\[{shape}\]\S* (copy|transpose)\(",
                              ln)]
        assert not moved, moved[:2]
        entry = next(ln for ln in txt.splitlines()
                     if "entry_computation_layout" in ln)
        assert set(re.findall(rf"\w+\[{shape}\]\{{([\d,]+)", entry)) == {
            "3,2,1,0"}
    # the whole program fits the chip beside what it is handed
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert peak < tpu_aot.HBM_BUDGET


@pytest.mark.parametrize("cell", [_mellum_cell, _qwen3_next_cell])
def test_full_layers_of_a_step_share_one_walk(cell, mesh):
    """The two claimed cells run two full-attention layers a decode step
    (two periods of their layers here): the work list of live blocks
    (``ops/_page_walk.py``) is the same for both, and the compiled decode
    chunk builds it ONCE — one gather of the items' rows of 8 table entries
    over the 2048-entry tables, both ``paged_attention`` calls under the
    same traced grid bound — and once more for Mellum's six banded calls
    over their 65-entry rings."""
    import re

    import jax
    import jax.numpy as jnp

    import tpu_aot
    from apex_tpu.serving import kv_pool
    from apex_tpu.serving.scheduler import PagedDecodeEngine

    model, pool = cell(periods=2)
    slots = pool["slots"]
    engine = PagedDecodeEngine(model, variables=None, num_slots=2,
                               page_size=16, num_pages=3,
                               max_pages_per_seq=pool["max_pages"],
                               sync_every=4)
    cache = jax.eval_shape(lambda: kv_pool.init_paged_cache(
        model.config, slots, num_pages=pool["num_pages"], page_size=16,
        max_pages_per_seq=pool["max_pages"]))
    i32 = jnp.int32
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), i32)))
    sds = jax.ShapeDtypeStruct
    rest = [sds((slots,), i32), sds((slots,), jnp.bool_),
            sds((slots,), i32), sds((slots, 2), jnp.uint32),
            sds((slots,), i32)]
    txt = tpu_aot.compile_replicated(mesh, engine._step_fn(),
                                     [cache, variables] + rest,
                                     (0,)).as_text()

    def bounds(label):
        return re.findall(r"custom-call\(([^,()]+), [^\n]*\n"
                          rf'"kernel":"{label}"', txt)

    def walks(n_items):
        return len(re.findall(rf"= s32\[{n_items},8\]\S* gather\(", txt))

    assert walks(slots * pool["max_pages"] // 8) == 1
    full = bounds("paged_attention")
    assert len(full) == 2 and full[0] == full[1], full
    banded = bounds("paged_window_attention")
    if cell is _mellum_cell:
        assert walks(slots * 9) == 1           # cdiv(65, 8) blocks a ring
        assert len(banded) == 6 and len(set(banded)) == 1, banded
        assert banded[0] != full[0]
    else:
        assert not banded


#: what ``ops/flash_attention.py``'s rule picks at the training cell's shape
#: (PERF.md section 6, PR 36): one (512, 512) tile a head, so a grid step a
#: head; the 64-wide head held 128 lanes wide; q's segment ids a column.
_HEAD = (1, 1, 512, 128)
_STAT = (1, 1, 512, 1)
_SEGS = [(1, 512, 1), (1, 1, 512)]
_BERT_CELL_FLASH = {
    "flash_fwd": [_HEAD] * 3 + _SEGS + [_HEAD, _STAT],
    "flash_bwd_dq": [_HEAD] * 4 + [_STAT] * 2 + _SEGS + [_HEAD],
    "flash_bwd_dkv": [_HEAD] * 4 + [_STAT] * 2 + _SEGS + [_HEAD] * 2,
}


@pytest.mark.parametrize("label", sorted(_BERT_CELL_FLASH))
def test_flash_bert_cell_compiles_to_the_rules_tile(label, mesh, bert_cell):
    """``flash_bert_cell_fwd_bwd``: each of the three labelled kernels is
    there once, on a grid of one step a head with the blocks above."""
    calls = [c for c in bert_cell["calls"] if c[0] == label]
    assert len(calls) == 1, [c[0] for c in bert_cell["calls"]]
    _, grid, blocks = calls[0]
    assert grid == (8, 16, 1, 1)
    assert blocks == _BERT_CELL_FLASH[label]


def test_flash_bert_cell_is_three_mosaic_calls(bert_cell):
    """Nothing but the three kernels is a Mosaic call: the benchmark reads
    every custom call inside the module ``attention`` as flash's."""
    assert bert_cell["text"].count(
        'custom_call_target="tpu_custom_call"') == 3
    assert sorted(c[0] for c in bert_cell["calls"]) == sorted(
        _BERT_CELL_FLASH)


@pytest.fixture(scope="module")
def bert_cell(mesh):
    import tpu_aot

    _, fn, structs = tpu_aot.bert_cell_flash_case()
    text = tpu_aot.compile_replicated(mesh, fn, structs).as_text()
    return {"text": text, "calls": tpu_aot.mosaic_calls(text)}
