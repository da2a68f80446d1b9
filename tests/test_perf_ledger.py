"""Cost ledger + regression gate (apex_tpu/obs/ledger.py).

Unit tier (synthetic metrics, no tracing): append/load round trips,
seeding from the driver's BENCH wrapper artifacts, and the check
semantics — deterministic ``cost.*`` metrics gate EXACTLY, wall-time
metrics gate direction-aware inside a band, informational counters never
gate. Acceptance tier: the committed ``COST_LEDGER.jsonl`` holds HEAD's
cost entry, and ``--check`` against HEAD's freshly computed cost report
exits 0 (a perturbed ledger exits 1) — run as a subprocess, as CI does.
"""

import json
import os
import subprocess
import sys

import pytest

from apex_tpu.obs import ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entry(metrics, kind="cost", tag="t0"):
    return {"schema": 1, "kind": kind, "tag": tag, "git_rev": "abc",
            "time_unix": 0.0, "metrics": metrics}


# --------------------------------------------------------------------------
# storage
# --------------------------------------------------------------------------

def test_append_and_load_roundtrip(tmp_path):
    path = tmp_path / "ledger.jsonl"
    e1 = ledger.append_entry(path, kind="cost", tag="r01",
                             metrics={"cost.x": 1.0}, root=REPO)
    e2 = ledger.append_entry(path, kind="bench", tag="r02",
                             metrics={"tok_per_sec": 10.0}, root=REPO,
                             meta={"note": "n"})
    entries = ledger.load(path)
    assert [e["tag"] for e in entries] == ["r01", "r02"]
    assert entries[0]["metrics"] == {"cost.x": 1.0}
    assert entries[1]["meta"] == {"note": "n"}
    assert e1["git_rev"] and e2["git_rev"]


def test_load_rejects_corrupt_lines(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps(_entry({"a": 1.0})) + "\nnot json\n")
    with pytest.raises(ValueError, match="corrupt"):
        ledger.load(path)
    path.write_text(json.dumps({"no": "metrics"}) + "\n")
    with pytest.raises(ValueError, match="without metrics"):
        ledger.load(path)


def test_bench_metrics_from_wrapper_and_jsonl(tmp_path):
    # the driver's BENCH_r0N.json wrapper shape
    wrapper = tmp_path / "BENCH_r03.json"
    wrapper.write_text(json.dumps({
        "n": 3, "rc": 0, "tail": "...",
        "parsed": {"metric": "bert_tokens_per_sec", "value": 123.4,
                   "error": "backend down"}}))
    m, meta = ledger.bench_metrics_from_file(wrapper)
    assert m == {"bert_tokens_per_sec": 123.4}
    assert meta["errors"] == ["backend down"]
    # the DECODE_*.json JSONL-of-records shape
    decode = tmp_path / "DECODE_r06.json"
    decode.write_text(
        json.dumps({"metric": "gpt2_decode_tokens_per_sec_per_chip",
                    "value": 50.0, "step_ms": 2.5}) + "\n"
        + json.dumps({"metric": "gpt2_frontend_decode_tokens_per_sec"
                              "_per_chip",
                      "value": 40.0, "pump.bubble_ms": 0.8,
                      "jit.compiles": 3}) + "\n")
    m2, _ = ledger.bench_metrics_from_file(decode)
    assert m2["gpt2_decode_tokens_per_sec_per_chip"] == 50.0
    assert m2["step_ms"] == 2.5
    assert m2["pump.bubble_ms"] == 0.8 and m2["jit.compiles"] == 3.0


def test_seed_history_from_banked_artifacts(tmp_path):
    root = tmp_path
    for n, parsed in ((1, None),
                      (2, {"metric": "m", "value": 0.0, "error": "down"}),
                      (3, {"metric": "m", "value": 7.0})):
        (root / f"BENCH_r0{n}.json").write_text(json.dumps(
            {"n": n, "rc": 1 if parsed is None else 0, "parsed": parsed}))
    path = root / "L.jsonl"
    seeded = ledger._seed_history(root, path)
    entries = ledger.load(path)
    assert seeded == 2                 # the parse-less round is skipped
    assert [e["tag"] for e in entries] == ["r02", "r03"]
    assert entries[1]["metrics"]["m"] == 7.0
    assert all(e["kind"] == "seed" for e in entries)
    # idempotent: a re-run appends nothing (no duplicate trajectory)
    assert ledger._seed_history(root, path) == 0
    assert len(ledger.load(path)) == 2


# --------------------------------------------------------------------------
# check semantics
# --------------------------------------------------------------------------

def test_check_exact_on_cost_metrics():
    entries = [_entry({"cost.total_flops": 100.0})]
    assert ledger.check({"cost.total_flops": 100.0}, entries) == []
    regs = ledger.check({"cost.total_flops": 100.1}, entries)
    assert len(regs) == 1 and regs[0].kind == "exact-drift"
    # drift DOWN trips too: any change must be appended, i.e. reviewed
    assert ledger.check({"cost.total_flops": 99.9}, entries)


def test_check_band_is_direction_aware():
    entries = [_entry({"decode_tokens_per_sec": 100.0,
                       "ttft_ms_p95": 50.0}, kind="bench")]
    # throughput: 25% drop fails, 15% drop passes, any rise passes
    assert ledger.check({"decode_tokens_per_sec": 75.0}, entries)
    assert not ledger.check({"decode_tokens_per_sec": 85.0}, entries)
    assert not ledger.check({"decode_tokens_per_sec": 300.0}, entries)
    # latency: 25% rise fails, 25% fall passes
    assert ledger.check({"ttft_ms_p95": 62.6}, entries)
    assert not ledger.check({"ttft_ms_p95": 37.5}, entries)
    # tightened band flips the verdict
    assert ledger.check({"decode_tokens_per_sec": 85.0}, entries,
                        band_pct=5.0)


def test_check_rates_gate_on_absolute_tolerance():
    """[0,1] ratios with small integer denominators (a ~8-deadline
    scenario quantizes miss_rate in 0.125 steps) use an absolute band —
    one noise-flipped request must not fail the round."""
    entries = [_entry({"scenario.x.deadline_miss_rate": 0.125,
                       "prefix_hit_rate": 0.9}, kind="bench")]
    # one extra miss (+0.125, a 100% relative jump) stays inside the
    # absolute tolerance; a wholesale collapse (+0.5) gates
    assert not ledger.check({"scenario.x.deadline_miss_rate": 0.25},
                            entries)
    assert ledger.check({"scenario.x.deadline_miss_rate": 0.625},
                        entries)
    # hit_rate is higher-better: small dips pass, a collapse gates
    assert not ledger.check({"prefix_hit_rate": 0.8}, entries)
    assert ledger.check({"prefix_hit_rate": 0.5}, entries)
    # a 0.0 miss-rate baseline is a healthy PERFECT score, not a
    # dead-round seed — a collapse from it must still gate (the
    # zero-baseline skip applies only to the relative-band metrics)
    entries0 = [_entry({"scenario.x.deadline_miss_rate": 0.0},
                       kind="bench")]
    assert ledger.check({"scenario.x.deadline_miss_rate": 1.0}, entries0)
    assert not ledger.check({"scenario.x.deadline_miss_rate": 0.125},
                            entries0)


def test_check_skips_informational_and_unmatched():
    entries = [_entry({"decode_steps": 40.0, "old_metric_ms": 1.0})]
    # unknown-direction counters and metrics missing on one side don't gate
    assert ledger.check({"decode_steps": 400.0,
                         "brand_new_metric_ms": 9.0}, entries) == []
    # a zero baseline (the failed-round seeds) never gates
    entries = [_entry({"tok_per_sec": 0.0}, kind="seed")]
    assert ledger.check({"tok_per_sec": 0.0}, entries) == []


def test_check_uses_most_recent_value_per_metric():
    entries = [_entry({"cost.a": 1.0}, tag="old"),
               _entry({"cost.a": 2.0}, tag="new")]
    assert ledger.check({"cost.a": 2.0}, entries) == []
    regs = ledger.check({"cost.a": 1.0}, entries)
    assert regs and "new" in regs[0].baseline_tag
    # a bench metric keeps gating even after many cost-only rounds
    # appended on top — baselines are
    # per-metric most-recent, not a fixed entry window
    entries = [_entry({"ttft_ms_p95": 50.0}, kind="bench", tag="bench")]
    entries += [_entry({"cost.a": 1.0}, tag=f"r{i}") for i in range(10)]
    regs = ledger.check({"ttft_ms_p95": 100.0, "cost.a": 1.0}, entries)
    assert [r.metric for r in regs] == ["ttft_ms_p95"]


# --------------------------------------------------------------------------
# CLI + acceptance (subprocess, like the CI gate)
# --------------------------------------------------------------------------

def _run_ledger(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "apex_tpu.obs.ledger", *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)


def test_committed_ledger_has_history_and_head_entry():
    """Acceptance: COST_LEDGER.jsonl exists and holds HEAD's cost entry —
    under its own name, never the driver's PERF_LEDGER.jsonl."""
    assert ledger.LEDGER_NAME == "COST_LEDGER.jsonl"
    entries = ledger.load(os.path.join(REPO, ledger.LEDGER_NAME))
    assert entries and {e["kind"] for e in entries} == {"cost"}
    head = entries[-1]
    assert any(k.startswith("cost.case.") for k in head["metrics"])
    assert "cost.decode.weight_fraction" in head["metrics"]


def test_cli_check_exit_codes_synthetic(tmp_path, capsys):
    """The gate's 0/1/2 contract without tracing: main() against a
    synthetic costs report + ledger (fast tier-1 twin of the
    subprocess acceptance test below)."""
    costs_json = tmp_path / "c.json"
    costs_json.write_text(json.dumps({
        "schema": 1, "totals": {"flops": 10, "hbm_bytes": 20,
                                "predicted_ms": 0.5},
        "by_domain": {}, "cases": [], "decode_split": None,
        "errors": []}))
    path = tmp_path / "L.jsonl"
    args = ["--root", REPO, "--ledger", str(path),
            "--costs", str(costs_json)]
    assert ledger.main(["--check", *args]) == 2       # missing ledger
    assert ledger.main(["--append", "--tag", "t1", *args]) == 0
    assert ledger.main(["--check", *args]) == 0       # clean re-run
    # seeded regression: perturb the entry, check must exit 1
    doc = json.loads(path.read_text())
    doc["metrics"]["cost.total_flops"] = 11.0
    path.write_text(json.dumps(doc) + "\n")
    assert ledger.main(["--check", *args]) == 1
    out = capsys.readouterr().out
    assert "cost.total_flops" in out and "--append" in out


@pytest.mark.slow
def test_check_clean_at_head_and_perturbed_trips(tmp_path):
    """Acceptance: a clean --check at HEAD exits 0; a seeded regression
    (perturbed last entry) exits nonzero. Runs the real CLI so the
    gate's environment is exactly what CI executes.

    If this fails after an intentional kernel/model change, the cost
    metrics moved: run  python -m apex_tpu.obs.ledger --append --tag
    <tag>  and commit the updated COST_LEDGER.jsonl (the cost delta
    then shows up as a reviewable line in the PR)."""
    costs_json = tmp_path / "costs.json"
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.obs.costs", "--json",
         str(costs_json)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]

    r = _run_ledger("--check", "--costs", str(costs_json))
    assert r.returncode == 0, r.stdout + r.stderr[-2000:]

    # perturb the newest cost entry -> exact-drift -> exit 1
    src = os.path.join(REPO, ledger.LEDGER_NAME)
    lines = open(src).read().splitlines()
    perturbed = tmp_path / "perturbed.jsonl"
    doc = json.loads(lines[-1])
    doc["metrics"]["cost.total_flops"] += 1.0
    perturbed.write_text("\n".join(lines[:-1]
                                   + [json.dumps(doc)]) + "\n")
    r = _run_ledger("--check", "--costs", str(costs_json),
                    "--ledger", str(perturbed))
    assert r.returncode == 1
    assert "cost.total_flops" in r.stdout

    # a missing ledger is a hard error — the trajectory must not
    # silently go empty again
    r = _run_ledger("--check", "--costs", str(costs_json),
                    "--ledger", str(tmp_path / "absent.jsonl"))
    assert r.returncode == 2
