"""The runners rehearsed at tiny sizes on the CPU, through ``run.main`` with
only the look for a chip skipped: one well-formed last line; the control and
every fault a cell can have come out as not correct."""

import json

import numpy as np
import pytest

from bench_fixtures import (ROOT, TINY_SERVE_LIMIT, TINY_TRAIN_LIMIT,
                            cpu_devices, find_added_families,
                            forget_added_families, tiny_root)

from benchmark import families
from benchmark import run as bench_run
from benchmark.harness import compare, peaks, runtime, weights
from benchmark.references import gpt2 as gpt2_reference
from benchmark.runners import train

SEED = 2 ** 31 + 999


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A checkout with the tiny cells added; the chip's place taken by the
    CPU, its peak by the v5e's, the compile cache left off."""
    monkeypatch.setattr(runtime, "require_tpu", cpu_devices)
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: "off")
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5e"])
    monkeypatch.setattr(runtime, "trace_dir",
                        lambda: str(tmp_path / "trace"))
    root = tiny_root(tmp_path)
    find_added_families(monkeypatch, root)
    yield root
    forget_added_families()


def last_line(capsys, root, workload, trace=0, seconds=0.4):
    rc = bench_run.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", str(seconds), "--trace", str(trace)],
                        root=root)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1
    line = json.loads(lines[-1])
    assert list(line)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    return line


def test_train_rehearsal_ends_in_one_well_formed_line(capsys, root):
    line = last_line(capsys, root, "tiny-bert.pretrain")
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert line["attempted"] >= 4 and line["notes"]["window_compiles"] == 0
    assert set(line["checks"]) == {"loss1_gap", "grad_norm_gap",
                                   "grad_norm_p95_gap", "change_norm_gap",
                                   "last_loss_finite"}
    assert set(line["notes"]["read_not_held"]) >= {"loss2_gap", "loss3_gap"}
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_serve_traced_rehearsal_ends_in_one_well_formed_line(capsys, root):
    line = last_line(capsys, root, "tiny-gpt.chat", trace=1, seconds=1.5)
    assert line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # no device in the trace: only the counters had something to read
    assert set(line["metrics"]) == {"slot_occupancy.serve", "admitted.serve",
                                    "ttft_p50_ms.serve", "tpot_p50_ms.serve"}
    assert 0 < line["metrics"]["slot_occupancy.serve"]["value"] <= 100
    assert line["checks"]["served_logit_gap"]["value"] <= TINY_SERVE_LIMIT
    assert line["notes"]["window_compiles"] == 0


def test_serve_rehearsal_reports_its_end_to_end_metrics(capsys, root):
    line = last_line(capsys, root, "tiny-gpt.chat", seconds=1.5)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                    "tpot_p95_ms", "setup_s"}
    assert line["attempted"] == line["notes"]["completed"] > 0
    assert set(line["checks"]) == {"served_logit_gap", "failed_requests"}
    assert line["notes"]["judged_tokens"] > 0


def test_fault_optimizer_returns_its_state_unchanged(capsys, root,
                                                     monkeypatch):
    from apex_tpu.ops import flat_buffer
    from apex_tpu.optimizers import FusedLAMB

    monkeypatch.setattr(
        FusedLAMB, "step",
        lambda self, grads, **kw: flat_buffer.unflatten(self.master,
                                                        self.spec))
    line = last_line(capsys, root, "tiny-bert.pretrain")
    assert line["correct"] is False
    assert line["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(capsys, root, monkeypatch):
    import apex_tpu.models as models

    real = models.make_pretrain_step

    def half(model):
        step = real(model)
        return lambda p, b, s: step(
            p, {k: v[:v.shape[0] // 2] for k, v in b.items()}, s)

    monkeypatch.setattr(models, "make_pretrain_step", half)
    line = last_line(capsys, root, "tiny-bert.pretrain")
    assert line["correct"] is False
    assert line["checks"]["grad_norm_gap"]["value"] > 100 * TINY_TRAIN_LIMIT


def test_fault_a_served_token_altered(capsys, root, monkeypatch):
    from apex_tpu.serving.frontend import StreamHandle

    real = StreamHandle._finish
    monkeypatch.setattr(
        StreamHandle, "_finish",
        lambda self, output: real(self, (np.asarray(output) + 1) % 120))
    line = last_line(capsys, root, "tiny-gpt.chat", seconds=1.0)
    assert line["correct"] is False
    assert line["checks"]["served_logit_gap"]["value"] > 1000 * \
        TINY_SERVE_LIMIT


def test_control_lower_precision_fails_the_training_comparison(root):
    cell = bench_run.Cell.load("tiny-bert.pretrain", root)
    family = families.load(cell.config)
    batches = family.batches(cell.config, cell.mix, SEED, train.FOLLOWED)
    ref = family.follow(cell.config, cell.mix, SEED, batches)
    control = family.follow(cell.config, cell.mix, SEED, batches, "fp8")
    numbers = compare.train_numbers(control, ref)
    ok, checks = compare.verdict(
        dict(numbers, last_loss_finite=0.0), cell.mix["limits"])
    assert not ok
    assert numbers["grad_norm_gap"] > 10 * TINY_TRAIN_LIMIT


def test_control_lower_precision_fails_the_serving_comparison(root):
    """At each position of the same sequences the token that float8 puts
    first lies below the reference's best by more than the limit; the
    reference's own first token lies at 0."""
    cell = bench_run.Cell.load("tiny-gpt.chat", root)
    cfg = cell.config
    params = weights.make_weights(gpt2_reference.param_table(cfg), SEED)
    rng = np.random.default_rng(0)
    samples = [(rng.integers(4, 120, 1).astype(np.int32),
                rng.integers(4, 120, 120).astype(np.int32))
               for _ in range(4)]
    control = gpt2_reference.widest_gap(params, samples, cfg,
                                        precision="fp8")
    assert control["tokens"] == 480
    assert control["gap"] > 3 * TINY_SERVE_LIMIT
    ids = np.concatenate([samples[0][0], samples[0][1][:-1]])[None]
    pos = np.arange(120, dtype=np.int32)[None]
    best = np.argmax(gpt2_reference.logits_at(params, ids, pos, cfg), -1)
    own = gpt2_reference.served_gaps(params, ids, pos, best, cfg)
    assert float(np.max(own)) == 0.0


def test_gpt2_reference_agrees_with_the_program_forward(root):
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTModel

    cell = bench_run.Cell.load("tiny-gpt.chat", root)
    cfg = cell.config
    flat = weights.make_weights(gpt2_reference.param_table(cfg), SEED)
    model = families.load(cfg).model(cfg)
    assert isinstance(model, GPTModel)
    ids = np.arange(24, dtype=np.int32).reshape(2, 12) % cfg["vocab_size"]
    tree = {"params": {}}
    for name, x in flat.items():
        node = tree["params"]
        *parents, leaf = name.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = x
    got = model.apply(tree, jnp.asarray(ids))
    pos = np.tile(np.arange(12, dtype=np.int32), (2, 1))
    want = gpt2_reference.logits_at(flat, ids, pos, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- a second family, and the mesh -------------------------------------------------

def test_a_second_family_is_new_files_only_and_is_served_and_judged(
        capsys, root):
    """``tiny-other`` has ``model_type: "tinygpt"`` and none of GPT-2's key
    names: the runner finds ``families/tinygpt.py`` by that name, serves its
    model and has its reference judge the served tokens."""
    import filecmp
    import os

    for rel in ("runners/serve.py", "runners/train.py", "run.py",
                "families/__init__.py", "families/gpt2.py",
                "references/gpt2.py"):
        assert filecmp.cmp(os.path.join(root, "benchmark", rel),
                           os.path.join(ROOT, "benchmark", rel),
                           shallow=False)
    cell = bench_run.Cell.load("tiny-other.chat", root)
    assert "n_embd" not in cell.config and "vocab_size" not in cell.config
    family = families.load(cell.config)
    assert family.__file__.startswith(root)
    assert set(families.CONTRACT["serve"]) <= set(dir(family))
    line = last_line(capsys, root, "tiny-other.chat", seconds=1.5)
    assert line["correct"] is True and line["failed"] == 0
    assert line["notes"]["judged_tokens"] > 0
    assert line["checks"]["served_logit_gap"]["value"] <= TINY_SERVE_LIMIT
    # the same sizes under GPT-2's keys serve the same tokens: same weights
    # by name from the seed, ids drawn below the same vocabulary
    twin = last_line(capsys, root, "tiny-gpt.chat", seconds=1.5)
    assert twin["checks"]["served_logit_gap"]["value"] == pytest.approx(
        line["checks"]["served_logit_gap"]["value"], abs=1e-6)


def dp_programs(root, chips=4):
    cell = bench_run.Cell.load("tiny-bert.pretrain-dp4", root)
    family = families.load(cell.config)
    batches = family.batches(cell.config, cell.mix, SEED, train.FOLLOWED)
    return cell, batches, [
        train.Program(cell.config, cell.mix, SEED, cpu_devices(n))
        for n in (1, chips)]


def test_dp_step_matches_the_one_device_step_on_the_global_batch(root):
    cell, batches, (one, four) = dp_programs(root)
    assert one.mesh is None and four.mesh.shape == {"data": 4}
    beta1 = cell.mix["betas"][0]
    seen_one = train.first_steps(one, batches, beta1)
    seen_four = train.first_steps(four, batches, beta1)
    # float32 compute at this size: the mean of four chips' means against
    # the mean over the batch differs by rounding alone
    np.testing.assert_allclose(seen_four["losses"], seen_one["losses"],
                               rtol=2e-6)
    assert "replica_drift" not in seen_one
    assert seen_four["replica_drift"] == 0.0
    gaps = compare.leaf_gaps(seen_four["change_norms"],
                             seen_one["change_norms"])
    assert max(gaps.values()) < 1e-4
    # every chip holds its own rows of a batch and the whole of a parameter
    batch = __import__("jax").device_put(batches[0], four.feed)
    assert {s.data.shape for s in batch["input_ids"].addressable_shards} \
        == {(2, 32)}
    leaf = __import__("jax").tree.leaves(four.params)[0]
    assert len(leaf.addressable_shards) == 4
    assert {s.data.shape for s in leaf.addressable_shards} == {leaf.shape}
    # the trace finds the step by the one-chip step's name
    assert four.grad_step.__name__ == one.grad_step.__name__ == "loss_fn"


def test_dp_rehearsal_ends_in_one_well_formed_line(capsys, root):
    line = last_line(capsys, root, "tiny-bert.pretrain-dp4", trace=1)
    assert line["correct"] is True and line["device"]["count"] == 4
    assert line["checks"]["replica_drift"] == {"value": 0.0, "limit": 0.0}
    assert set(line["checks"]) == {"loss1_gap", "grad_norm_gap",
                                   "grad_norm_p95_gap", "change_norm_gap",
                                   "change_norm_p50_gap", "last_loss_finite",
                                   "replica_drift"}
    assert line["attempted"] >= 4 and line["notes"]["window_compiles"] == 0
    assert set(line["notes"]["phases"]) == {"program_s", "first_steps_s",
                                            "window_s", "reference_s"}
    # no device plane in a CPU trace: nothing for the trace's readers
    assert line["metrics"] == {}


def test_fault_the_exchange_between_chips_left_out(capsys, root,
                                                   monkeypatch):
    from apex_tpu.parallel import DistributedDataParallel

    monkeypatch.setattr(DistributedDataParallel, "allreduce_gradients",
                        lambda self, grads: grads)
    line = last_line(capsys, root, "tiny-bert.pretrain-dp4")
    assert line["correct"] is False
    assert line["checks"]["replica_drift"]["value"] > 1e-3
    assert line["checks"]["grad_norm_p95_gap"]["value"] > 100 * \
        TINY_TRAIN_LIMIT


def test_blockwise_reference_equals_the_whole_batch(root):
    """The reference's accumulation over blocks of rows is the whole batch's
    step where every row predicts as many positions."""
    cell = bench_run.Cell.load("tiny-bert.pretrain-dp4", root)
    family = families.load(cell.config)
    batches = family.batches(cell.config, cell.mix, SEED, train.FOLLOWED)
    blocks = family.follow(cell.config, cell.mix, SEED, batches)
    whole = family.follow(cell.config,
                          dict(cell.mix, reference_block_rows=None), SEED,
                          batches)
    np.testing.assert_allclose(blocks["losses"], whole["losses"], rtol=1e-6)
    assert max(compare.leaf_gaps(blocks["grad_norms"],
                                 whole["grad_norms"]).values()) < 1e-5
    assert max(compare.leaf_gaps(blocks["change_norms"],
                                 whole["change_norms"]).values()) < 1e-4
