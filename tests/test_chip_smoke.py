"""chip_smoke.py off the chip: its argument handling, its refusal to run on
the CPU, the compile-cache helper, and a tiny-config rehearsal of the very
phase functions the chip run calls (serve, train, and the two --multichip
phases on 4 of conftest's virtual devices)."""

import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from chip_smoke import ServeSizes, Spec, TrainSizes  # noqa: E402

TINY_SERVE = ServeSizes(
    traffic=(Spec(24, 6, header=True), Spec(8, 4), Spec(40, 8), Spec(8, 4),
             Spec(40, 8), Spec(24, 6, header=True, wave=1),
             Spec(24, 6, header=True, wave=1, http=True)),
    header_len=16, num_slots=2, page_size=8, sync_every=2,
    pool_bytes=1 << 20, stream_timeout_s=120.0)
TINY_TRAIN = TrainSizes(batch_size=8, seq_len=64, steps=5, lr=1e-3)


@pytest.mark.parametrize("argv,phases", [
    ([], ("serve", "train")),
    (["--phase", "train"], ("train",)),
    (["--phase", "train", "--phase", "serve"], ("serve", "train")),
    (["--multichip"], ("tp_serve", "dp_train")),
])
def test_phase_selection(argv, phases):
    assert chip_smoke.selected_phases(chip_smoke.parse_args(argv)) == phases


@pytest.mark.parametrize("argv", [
    ["--phase", "bench"], ["--multichip", "--phase", "serve"], ["--cpu"]])
def test_bad_arguments_are_refused(argv):
    with pytest.raises(SystemExit) as e:
        chip_smoke.parse_args(argv)
    assert e.value.code != 0


@pytest.mark.parametrize("argv", [[], ["--multichip"]])
def test_refuses_to_run_without_a_tpu(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert "TPU" in last and '"ok"' not in last
    with pytest.raises(ValueError):
        json.loads(last)


def test_compile_cache_is_left_alone_when_placed_from_outside(monkeypatch):
    from apex_tpu.utils import compile_cache

    def no_update(*a, **kw):
        raise AssertionError(f"set {a} although the variable is set")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert compile_cache.enable_compile_cache() == "/somewhere/else"


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    from apex_tpu.utils import compile_cache

    calls = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)] * 2


def test_smoke_traffic_meets_the_contract():
    """>= 8 requests over fewer slots, prompts 32-512, 16-64 new tokens, a
    >= 64-token header shared across waves, one request over HTTP."""
    sizes = ServeSizes()
    t = sizes.traffic
    assert len(t) >= 8 and len(t) > sizes.num_slots
    assert min(s.prompt_len for s in t) == 32
    assert max(s.prompt_len for s in t) == 512
    assert min(s.new_tokens for s in t) == 16
    assert max(s.new_tokens for s in t) == 64
    assert sizes.header_len >= 64 and sizes.pool_bytes >= 2 ** 30
    assert {s.wave for s in t if s.header} == {0, 1}
    assert sum(s.http for s in t) >= 1
    prompts = chip_smoke.build_requests(0, 50304, sizes)
    assert [len(p) for p in prompts] == [s.prompt_len for s in t]
    headers = [p[:sizes.header_len] for p, s in zip(prompts, t) if s.header]
    assert all(np.array_equal(h, headers[0]) for h in headers)


def test_serve_phase_rehearsal_tiny():
    from apex_tpu.models.gpt import gpt_tiny_config

    report = chip_smoke.serve_phase(gpt_tiny_config(), TINY_SERVE, seed=0)
    for n in range(2):
        assert report[f"pass{n}"] == {
            "streams": len(TINY_SERVE.traffic),
            "identical": len(TINY_SERVE.traffic), "near_ties": 0}
    assert report["stats"]["prefix_hits"] >= 4
    assert report["compiles"][0] > 0
    assert report["compiles"][1] == report["compiles"][0]
    assert report["pool"]["free_pages"] == (
        report["pool"]["usable_pages"] - report["pool"]["radix_pages"])
    # interpret mode has no Mosaic kernel: the check main() makes on the
    # chip must refuse this run
    assert report["kernel_sites"] == 0
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.require_kernels(report, "serve")


def test_stream_check_refuses_a_wrong_token():
    from apex_tpu.models.gpt import gpt_tiny_config

    model, variables = chip_smoke.gpt_weights(gpt_tiny_config(), 0)
    ref = chip_smoke.GreedyReference(model, variables)
    prompt = np.arange(8, dtype=np.int32)
    good = ref.generate(prompt, 6)
    assert ref.near_tie_excess(prompt, good) <= 1.0
    assert chip_smoke.check_streams(ref, [prompt], [good], [good], "ref") \
        == {"streams": 1, "identical": 1, "near_ties": 0}
    bad = good.copy()
    bad[3] = (bad[3] + 1) % 128
    with pytest.raises(AssertionError, match="diverges from ref at token 3"):
        chip_smoke.check_streams(ref, [prompt], [bad], [good], "ref")


def test_train_phase_rehearsal_tiny():
    from apex_tpu.models import bert_tiny_config

    report = chip_smoke.train_phase(bert_tiny_config(), TINY_TRAIN, seed=0)
    assert len(report["losses"]) == 5
    assert report["losses"][-1] < report["losses"][0]
    assert report["kernel_sites"] == 0 and report["optimizer_sites"] == 0
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.require_kernels(report, "train")


def test_multichip_tp_serve_rehearsal_on_virtual_devices():
    from apex_tpu.models.gpt import gpt_tiny_config

    report = chip_smoke.tp_serve_phase(gpt_tiny_config(), TINY_SERVE,
                                       seed=0, tp=4)
    assert report["stats"]["tp_world"] == 4
    for n in range(2):
        assert report[f"pass{n}"]["identical"] == len(TINY_SERVE.traffic)


def test_multichip_dp_train_rehearsal_on_virtual_devices():
    from apex_tpu.models import bert_tiny_config

    report = chip_smoke.dp_train_phase(bert_tiny_config(), TINY_TRAIN,
                                       seed=0, dp=4)
    np.testing.assert_allclose(report["losses"], report["one_chip_losses"],
                               rtol=chip_smoke.DP_LOSS_RTOL)
    assert "all-reduce" in report["hlo"]


def test_require_kernels_passes_a_program_with_kernels():
    chip_smoke.require_kernels({"kernel_sites": 3}, "serve")
    chip_smoke.require_kernels({"kernel_sites": 3, "optimizer_sites": 2},
                               "train")
    with pytest.raises(AssertionError):
        chip_smoke.require_kernels({"kernel_sites": 3, "optimizer_sites": 0},
                                   "train")
