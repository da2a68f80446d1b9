"""Kernel labels: every Pallas kernel names itself where it is launched
(``ops/_dispatch.pallas_call(..., kernel=<label>)``) and the label reaches
the program's text, where the device trace's ``XLA Ops`` line and the
benchmark's ``kernel_ms`` reader find it.  Also pins the names of the jitted
functions that the benchmark finds whole programs by."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch

f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32


def _sds(shape, dtype=f32):
    return jax.ShapeDtypeStruct(shape, dtype)


@functools.cache
def _cases():
    """label -> (function that reaches the kernel through a public op,
    argument structs), at tiny kernel-eligible shapes; ``label@cell`` is
    the same kernel at the shape a benchmark cell calls it with, so a
    block shape that Mosaic's rules refuse fails here first."""
    from apex_tpu.ops import (flash_attention, flat_buffer, optim_kernels,
                              paged_attention, paged_latent_attention,
                              softmax_cross_entropy)
    from apex_tpu.ops.gated_delta import gated_delta_step
    from apex_tpu.ops.group_norm import group_norm_nhwc
    from apex_tpu.ops.paged_write import paged_write
    from apex_tpu.ops.layer_norm import layer_norm
    from apex_tpu.ops.quant import fused_dequant_matmul
    from apex_tpu.ops.scaled_softmax import scaled_softmax

    def sq_sum(fn):
        return lambda *a: jnp.sum(fn(*a).astype(f32) ** 2)

    ln_args = [_sds((16, 256)), _sds((256,)), _sds((256,))]
    qkv = [_sds((1, 2, 128, 64), bf16)] * 3
    xent = [_sds((16, 512)), _sds((16,), i32)]
    gn = functools.partial(group_norm_nhwc, num_groups=2, eps=1e-5,
                           act="silu")
    gn_args = [_sds((1, 4, 4, 256)), _sds((256,)), _sds((256,))]
    soft = functools.partial(scaled_softmax, scale=0.5)
    soft_args = [_sds((1, 2, 16, 128), bf16)]

    spec = flat_buffer.build_spec({"w": _sds((64, 128)), "b": _sds((128,))})
    seg = np.asarray(spec.segment_rows())
    buf = _sds((spec.total_rows, flat_buffer.LANE))
    hp = dict(beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01, lr=1e-3,
              step=1)

    def lamb(g, p, m, v):
        return optim_kernels.lamb_update(g, p, m, v, jnp.asarray(seg),
                                         spec.num_tensors, **hp)

    def novograd(g, p, m, v):
        return optim_kernels.novograd_update(
            g, p, m, v, jnp.asarray(seg), spec.num_tensors, **hp)

    pages = _sds((9, 2, 16, 64), bf16)
    return {
        "flash_fwd": (flash_attention, qkv),
        "flash_bwd_dq": (jax.grad(sq_sum(flash_attention), (0, 1, 2)), qkv),
        "flash_bwd_dkv": (jax.grad(sq_sum(flash_attention), (0, 1, 2)), qkv),
        "paged_attention": (paged_attention, [
            _sds((2, 4, 1, 64), bf16), pages, pages, _sds((2, 4), i32),
            _sds((2,), i32)]),
        # GPT-2 large: 16 slots, 20 heads of 64, 64-page tables, 2 GiB pool
        "paged_attention@gpt2-large.chat-closed16": (paged_attention, [
            _sds((16, 20, 1, 64), bf16), _sds((729, 20, 16, 64), bf16),
            _sds((729, 20, 16, 64), bf16), _sds((16, 64), i32),
            _sds((16,), i32)]),
        # a call with a window carries a label of its own
        "paged_window_attention": (
            functools.partial(paged_attention, window=24), [
                _sds((2, 4, 1, 64), bf16), pages, pages, _sds((2, 4), i32),
                _sds((2,), i32)]),
        # the mixed cell: 48 slots, 32 query heads over 4 kv heads of 128;
        # the sliding layers' rings (65 pages a slot, window 1024) and the
        # full layers' block table (2048 entries, a 2.5 GiB pool)
        "paged_window_attention@mellum2-12b-a2.5b.ide-closed48": (
            functools.partial(paged_attention, window=1024), [
                _sds((48, 32, 1, 128), bf16),
                _sds((3121, 4, 16, 128), bf16),
                _sds((3121, 4, 16, 128), bf16), _sds((48, 65), i32),
                _sds((48,), i32)]),
        "paged_attention@mellum2-12b-a2.5b.ide-closed48": (paged_attention, [
            _sds((48, 32, 1, 128), bf16), _sds((40961, 4, 16, 128), bf16),
            _sds((40961, 4, 16, 128), bf16), _sds((48, 2048), i32),
            _sds((48,), i32)]),
        # the state cell's full layers: 64 slots, 16 query heads over 2 kv
        # heads of 256, 2048-entry tables over a 2 GiB pool
        "paged_attention@qwen3-next-80b-a3b.longchat-closed64": (
            paged_attention, [
                _sds((64, 16, 1, 256), bf16), _sds((32769, 2, 16, 256), bf16),
                _sds((32769, 2, 16, 256), bf16), _sds((64, 2048), i32),
                _sds((64,), i32)]),
        "gated_delta_step": (gated_delta_step, [
            _sds((2, 4, 16, 128)), _sds((2, 2, 16)), _sds((2, 2, 16)),
            _sds((2, 4, 128)), _sds((2, 4)), _sds((2, 4))]),
        # its linear layers: a 2 MiB float32 state a slot, 32 value heads
        # over 16 key heads of 128 x 128
        "gated_delta_step@qwen3-next-80b-a3b.longchat-closed64": (
            gated_delta_step, [
                _sds((64, 32, 128, 128)), _sds((64, 16, 128)),
                _sds((64, 16, 128)), _sds((64, 32, 128)), _sds((64, 32)),
                _sds((64, 32))]),
        # the per-channel cell's KDA layers: 128 slots, 32 heads of 128 x
        # 128, the decay a column of 128 a head
        "gated_delta_step@ling-3.0-flash.reason-closed128": (
            gated_delta_step, [
                _sds((128, 32, 128, 128)), _sds((128, 32, 128)),
                _sds((128, 32, 128)), _sds((128, 32, 128)),
                _sds((128, 32, 128)), _sds((128, 32))]),
        # its MLA layer: 32 heads, one 640-lane entry a token (512 of them
        # the values), 512-page tables over the 1 GiB pool's 58,255 pages
        "paged_latent_attention@ling-3.0-flash.reason-closed128": (
            functools.partial(paged_latent_attention, value_width=512), [
                _sds((128, 32, 1, 640), bf16),
                _sds((58255, 1, 16, 640), bf16), _sds((128, 512), i32),
                _sds((128,), i32)]),
        # the conv cell's attention layers: 128 slots, 32 query heads over
        # 8 kv heads of 64 held two a 128-lane row, 512-entry tables over a
        # 2 GiB pool
        "paged_attention@lfm2-24b-a2b.chat-closed128": (paged_attention, [
            _sds((128, 32, 1, 64), bf16), _sds((32769, 4, 16, 128), bf16),
            _sds((32769, 4, 16, 128), bf16), _sds((128, 512), i32),
            _sds((128,), i32)]),
        "paged_latent_attention": (
            functools.partial(paged_latent_attention, value_width=128), [
                _sds((2, 4, 1, 256), bf16), _sds((9, 1, 16, 256), bf16),
                _sds((2, 4), i32), _sds((2,), i32)]),
        # the latent cell: 32 slots, 20 heads, one 640-lane entry a token
        # (512 of them the values), 2048-page tables, a 4 GiB pool
        "paged_latent_attention@glm-4.7-flash.docqa-closed32": (
            functools.partial(paged_latent_attention, value_width=512), [
                _sds((32, 20, 1, 640), bf16),
                _sds((38837, 1, 16, 640), bf16), _sds((32, 2048), i32),
                _sds((32,), i32)]),
        "paged_write": (
            lambda k, v, ck, cv, bt, ln: paged_write([k, v], [ck, cv], bt,
                                                     ln), [
                pages, pages, _sds((2, 2, 1, 64), bf16),
                _sds((2, 2, 1, 64), bf16), _sds((2, 4), i32),
                _sds((2,), i32)]),
        # a decode step's token of 16 slots into the cell's K and V pools
        "paged_write@gpt2-large.chat-closed16": (
            lambda k, v, ck, cv, bt, ln: paged_write([k, v], [ck, cv], bt,
                                                     ln), [
                _sds((729, 20, 16, 64), bf16), _sds((729, 20, 16, 64), bf16),
                _sds((16, 20, 1, 64), bf16), _sds((16, 20, 1, 64), bf16),
                _sds((16, 64), i32), _sds((16,), i32)]),
        "layer_norm_fwd": (layer_norm, ln_args),
        "layer_norm_bwd": (jax.grad(sq_sum(layer_norm), (0, 1, 2)), ln_args),
        "xentropy_fwd": (softmax_cross_entropy, xent),
        "xentropy_bwd": (jax.grad(
            lambda x, y: softmax_cross_entropy(x, y).sum()), xent),
        "l2norm": (lambda g: optim_kernels.global_grad_norm_and_finite(
            g, jnp.asarray(seg), spec.num_tensors)[0], [buf]),
        "lamb_phase1": (lamb, [buf] * 4),
        "lamb_phase2": (lamb, [buf] * 4),
        "adam": (functools.partial(optim_kernels.adam_update, **hp),
                 [buf] * 4),
        "sgd": (functools.partial(optim_kernels.sgd_update, lr=0.1,
                                  momentum=0.9), [buf] * 3),
        "novograd": (novograd, [buf] * 3 + [_sds((spec.num_tensors,))]),
        "scale": (functools.partial(optim_kernels.multi_tensor_scale,
                                    scale=0.5), [buf]),
        "group_norm_fwd": (gn, gn_args),
        "group_norm_bwd": (jax.grad(sq_sum(gn), (0, 1, 2)), gn_args),
        "scaled_softmax_fwd": (soft, soft_args),
        "scaled_softmax_bwd": (jax.grad(sq_sum(soft)), soft_args),
        "dequant_matmul": (fused_dequant_matmul, [
            _sds((8, 256), bf16), _sds((128, 256), jnp.int8),
            _sds((128,))]),
    }


def test_the_cases_cover_the_closed_set():
    assert {c.partition("@")[0] for c in _cases()} == \
        set(_dispatch.KERNEL_LABELS)
    assert len(set(_dispatch.KERNEL_LABELS)) == len(_dispatch.KERNEL_LABELS)


@pytest.mark.parametrize("case", _dispatch.KERNEL_LABELS + (
    "paged_attention@gpt2-large.chat-closed16",
    "paged_latent_attention@glm-4.7-flash.docqa-closed32",
    "paged_write@gpt2-large.chat-closed16",
    "paged_window_attention@mellum2-12b-a2.5b.ide-closed48",
    "paged_attention@mellum2-12b-a2.5b.ide-closed48",
    "paged_attention@qwen3-next-80b-a3b.longchat-closed64",
    "gated_delta_step@qwen3-next-80b-a3b.longchat-closed64",
    "paged_attention@lfm2-24b-a2b.chat-closed128",
    "gated_delta_step@ling-3.0-flash.reason-closed128",
    "paged_latent_attention@ling-3.0-flash.reason-closed128"))
def test_label_reaches_the_lowered_program(case):
    """``metadata={"kernel": label}`` lands on the Mosaic custom call as
    ``kernel_metadata``; the benchmark's pattern finds it there."""
    fn, args = _cases()[case]
    label = case.partition("@")[0]
    # staged through Mosaic for the trace only; the exit clears jax's
    # trace caches, so nothing here leaks into tests that execute
    with _dispatch.forced_mosaic():
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    # MLIR escapes the JSON's quotes and newlines as \22 and \0A
    found = [blob.replace("\\22", '"').replace("\\0A", "\n") for blob in
             re.findall(r'kernel_metadata = "([^"]*)"', text)]
    assert found, f"no kernel_metadata in the program of {label}"
    labels = {m.group(1) for blob in found
              for m in re.finditer(r"kernel\W{1,8}(\w+)", blob)}
    assert label in labels
    assert labels <= set(_dispatch.KERNEL_LABELS)
    # the name stack is left alone: no scope is named after the label
    assert f"/{label}/" not in text


def test_pallas_call_refuses_a_missing_or_unknown_label():
    out = jax.ShapeDtypeStruct((8, 128), f32)

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    with pytest.raises(TypeError, match="kernel"):
        _dispatch.pallas_call(body, out_shape=out, interpret=True)
    with pytest.raises(ValueError, match="unknown kernel label 'copy'"):
        _dispatch.pallas_call(body, kernel="copy", out_shape=out,
                              interpret=True)
    x = jnp.ones((8, 128), f32)
    y = _dispatch.pallas_call(body, kernel="scale", out_shape=out,
                              interpret=True)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# -- the names the benchmark finds programs by ---------------------------------
#
# ``XLA Modules`` names a program ``jit_<function name>``; five per-layer
# metrics match ``^jit_loss_fn``, ``^jit__pure``, ``^jit_admit``,
# ``^jit_step`` (benchmark/layer_metrics/*.json).  A rename empties them
# silently, so it has to fail here first.

def _tiny_engine():
    from apex_tpu.models.gpt import GPTModel, gpt_tiny_config
    from apex_tpu.serving import PagedDecodeEngine

    cfg = gpt_tiny_config()
    model = GPTModel(cfg)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                               _sds((1, 8), i32))
    variables = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             variables)
    return PagedDecodeEngine(model, variables, num_slots=2, page_size=8,
                             sync_every=2)


def _grad_step_name():
    from apex_tpu.models import BertForPreTraining, make_pretrain_step
    from apex_tpu.models.bert import bert_tiny_config

    return make_pretrain_step(BertForPreTraining(bert_tiny_config())).__name__


def _optimizer_step_name():
    from apex_tpu.optimizers import FusedLAMB

    params = {"w": jnp.ones((8, 128), f32)}
    opt = FusedLAMB(params, lr=1e-3)
    opt.step({"w": jnp.ones((8, 128), f32)})
    return opt._jit_step.__name__


def _data_parallel_step_name():
    """The benchmark's own four-chip grad step (``runners/train.py``) keeps
    the one-chip step's name, so ``grad_step_ms.train`` reads
    ``jit_loss_fn`` on any number of chips."""
    from jax.sharding import Mesh

    from apex_tpu.mesh import DATA_AXIS
    from apex_tpu.models import BertForPreTraining, make_pretrain_step
    from apex_tpu.models.bert import bert_tiny_config
    from benchmark.runners import train

    model = BertForPreTraining(bert_tiny_config())
    mesh = Mesh(np.asarray(jax.devices()[:2]), (DATA_AXIS,))
    return train.data_parallel(make_pretrain_step(model), model,
                               mesh).__name__


@pytest.mark.parametrize("want,name_of", [
    ("loss_fn", _grad_step_name),
    ("loss_fn", _data_parallel_step_name),
    ("_pure", _optimizer_step_name),
    ("admit", lambda: _tiny_engine()._admit_fn(16).__name__),
    ("step", lambda: _tiny_engine()._step_fn().__name__),
])
def test_jitted_function_names_the_benchmark_depends_on(want, name_of):
    assert name_of() == want


def test_routed_experts_keep_the_names_their_metrics_read():
    """``moe_experts_ms.serve`` and ``moe_experts_roofline.serve`` find the
    routed products by the name XLA gives ``jax.lax.ragged_dot`` on the TPU
    (``%ragged-dot-*`` Mosaic calls: no label rides them), inside the
    program region ``moe_experts``; the counter metrics read
    ``_RUN_COUNTERS`` by name."""
    from apex_tpu.serving.scheduler import _RUN_COUNTERS
    from apex_tpu.transformer.moe import ROUTING_STATS, grouped_experts

    assert ROUTING_STATS == ("expert_pairs_routed", "experts_hit",
                             "expert_load_max")
    assert set(ROUTING_STATS + ("expert_bytes_read", "kv_bytes_attended",
                                "kv_bytes_fetched", "decode_steps")) \
        <= set(_RUN_COUNTERS)
    text = jax.jit(grouped_experts).trace(
        _sds((8, 128), bf16), _sds((8, 2), i32), _sds((8, 2)),
        _sds((4, 128, 256), bf16), _sds((4, 128, 256), bf16),
        _sds((4, 256, 128), bf16)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count("ragged_dot") >= 3
    assert "moe_experts" in text


# -- the counters the benchmark's readers name ----------------------------------

MIXED_CELL_COUNTERS = ("kv_full_bytes_attended", "kv_window_bytes_attended",
                       "kv_bytes_held_steps", "context_token_steps",
                       # the state cell's (PR 37)
                       "state_bytes_moved")


@pytest.mark.parametrize("counter", MIXED_CELL_COUNTERS)
def test_the_counters_of_the_groups_of_layers_are_run_counters(counter):
    """``paged_full_attention_roofline.serve``, ``paged_window_attention_
    roofline.serve`` and ``kv_bytes_per_context_token.serve`` read these by
    name from ``ServingFrontend.counter_deltas()``; a rename would leave
    them silent."""
    import glob
    import json
    import os

    from apex_tpu.serving.scheduler import _RUN_COUNTERS

    assert counter in _RUN_COUNTERS
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    named = set()
    for path in glob.glob(os.path.join(root, "benchmark", "layer_metrics",
                                       "*.serve.json")):
        with open(path, encoding="utf-8") as f:
            args = json.load(f).get("args", {})
        named |= {v for k, v in args.items()
                  if k in ("bytes_counter", "steps_counter", "numerator",
                           "denominator")}
    assert counter in named and named <= set(_RUN_COUNTERS)


def test_a_share_of_the_experts_counts_what_it_holds_under_these_names():
    """``expert_pairs_elsewhere`` is a run counter and the fourth number a
    layer that holds a share sows (``SHARE_ROUTING_STATS``); the three
    before it keep their names and order, so the metric files that read
    them read a share's held pairs, experts and rows."""
    from apex_tpu.serving.scheduler import _RUN_COUNTERS
    from apex_tpu.transformer.moe import ROUTING_STATS, SHARE_ROUTING_STATS

    assert SHARE_ROUTING_STATS == ROUTING_STATS + ("expert_pairs_elsewhere",)
    assert set(SHARE_ROUTING_STATS) <= set(_RUN_COUNTERS)
    assert "gated_delta_step" in _dispatch.KERNEL_LABELS
    # the chunked rule is plain XLA in this PR: no label of its own yet
    assert "gated_delta_chunk" not in _dispatch.KERNEL_LABELS


# -- the pump's spans and phase counters (PR 39) --------------------------------

PUMP_SPANS = ("dispatch", "harvest", "housekeeping", "admission",
              "wait_device", "dispatch.launch", "dispatch.account",
              "harvest.account", "retire", "retire.release",
              "admission.request", "admission.match", "admission.pool",
              "admission.launch", "admission.join", "admission.feed")


@pytest.mark.parametrize("span", PUMP_SPANS)
def test_the_pumps_spans_keep_the_names_the_idle_metrics_read(span):
    """``idle_admission_share.serve`` and ``idle_harvest_share.serve`` (and
    the ``breakdown`` of every traced serving run) find the pump's host
    spans by ``pump:<name>``: ``^pump:admission`` and
    ``^pump:(harvest|retire)``; ``trace_reduce.load`` keeps the prefix
    ``pump:``. A rename would move idle time between the two in silence."""
    import inspect
    import re

    from apex_tpu.serving import frontend

    assert frontend.PUMP_SPANS == PUMP_SPANS
    source = inspect.getsource(frontend.ServingFrontend)
    assert f'_phase("{span}")' in source
    assert '"pump:" + name' in inspect.getsource(
        frontend.ServingFrontend._phase)
    # each span is read by at most one of the two shares: its own stem's
    stem = span.split(".")[0]
    assert bool(re.search(r"^pump:admission", "pump:" + span)) == \
        (stem == "admission")
    assert bool(re.search(r"^pump:(harvest|retire)", "pump:" + span)) == \
        (stem in ("harvest", "retire"))


@pytest.mark.parametrize("phase", ["dispatch", "harvest", "housekeeping",
                                   "admission"])
def test_every_top_level_phase_feeds_the_counter_its_metric_reads(phase):
    """``pump_<phase>_ms.serve`` reads ``pump_<phase>_seconds`` over
    ``pump_iterations``; the phase's exit is where it is fed."""
    import json
    import os

    from apex_tpu.serving import frontend
    from apex_tpu.serving.scheduler import _RUN_COUNTERS

    counter = f"pump_{phase}_seconds"
    assert frontend._PHASE_COUNTERS[phase] == counter
    assert frontend._PHASE_COUNTERS["wait_device"] == "pump_blocked_seconds"
    assert {counter, "pump_iterations", "pump_host_seconds"} \
        <= set(_RUN_COUNTERS)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           f"pump_{phase}_ms.serve.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {"numerator": counter,
                            "denominator": "pump_iterations",
                            "scale": 1000.0}
