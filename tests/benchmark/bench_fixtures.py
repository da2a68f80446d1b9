"""A temp copy of the benchmark with a tiny configuration, a cell and a
per-layer metric of each kind ADDED as new files and new entries: what a
later PR does.  No file of the copy is edited except ``BENCHMARK.json``,
which only grows."""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_BERT = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                 intermediate_size=128, max_position_embeddings=32,
                 vocab_size=250, held_vocab=256, compute_dtype="float32")
TINY_GPT = dict(n_embd=64, n_head=4, n_layer=2, n_positions=128, n_ctx=128,
                vocab_size=120, held_vocab=128, compute_dtype="float32")
TINY_TRAIN_LIMIT = 1e-4
TINY_SERVE_LIMIT = 1e-4

NEW_READER = '''"""Counts the requests admitted in the traced window."""


def read(reading, counter):
    counters = reading.get("counters")
    return None if not counters else float(counters[counter])
'''


from benchmark.run import load_json as _load  # noqa: E402


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def tiny_root(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    b = os.path.join(root, "benchmark")

    cfg = _load(os.path.join(b, "configs", "bert-large-uncased.json"))
    cfg.update(TINY_BERT)
    _dump(cfg, os.path.join(b, "configs", "tiny-bert.json"))
    cfg = _load(os.path.join(b, "configs", "gpt2-large.json"))
    cfg.update(TINY_GPT)
    _dump(cfg, os.path.join(b, "configs", "tiny-gpt.json"))

    mix = _load(os.path.join(b, "workloads",
                             "bert-large.pretrain-seq512.json"))
    mix.update(batch=4, seq_len=32, mlm_per_seq=8, traced_steps=2)
    mix["limits"].update({k: TINY_TRAIN_LIMIT for k in mix["limits"]
                          if k.endswith("_gap")})
    _dump(mix, os.path.join(b, "workloads", "tiny-bert.pretrain.json"))
    mix = _load(os.path.join(b, "workloads", "gpt2-large.chat-closed16.json"))
    mix.update(prompt_lengths={"16": 0.5, "40": 0.5},
               output_lengths={"kind": "lognormal", "mean": 12, "sigma": 0.5,
                               "lo": 4, "hi": 24},
               cycle=8, ramp_s=0.5, warm_up_max=40, traced_s=0.5,
               sampled_requests=3)
    mix["arrival"]["clients"] = 4
    mix["engine"].update(num_slots=4, pool_bytes=2 ** 20)
    mix["limits"]["served_logit_gap"] = TINY_SERVE_LIMIT
    _dump(mix, os.path.join(b, "workloads", "tiny-gpt.chat.json"))

    with open(os.path.join(b, "layer_metrics", "readers", "count.py"),
              "w", encoding="utf-8") as f:
        f.write(NEW_READER)
    _dump({"reader": "count", "args": {"counter": "admitted"}},
          os.path.join(b, "layer_metrics", "admitted.serve.json"))

    for name, runner in (("tiny-bert", "train"), ("tiny-gpt", "serve")):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "tiny",
            "file": f"benchmark/configs/{name}.json"})
    bench["workloads"] += [
        {"name": "tiny-bert.pretrain", "config": "tiny-bert",
         "traffic": "pretrain", "chips": 1, "why": "tiny"},
        {"name": "tiny-gpt.chat", "config": "tiny-gpt", "traffic": "chat",
         "chips": 1, "why": "tiny"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if any(w.startswith("bert-large") for w in m["workloads"]):
            m["workloads"].append("tiny-bert.pretrain")
        if any(w.startswith("gpt2-large") for w in m["workloads"]):
            m["workloads"].append("tiny-gpt.chat")
    bench["per_layer"].append({
        "name": "admitted.serve", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serving/frontend",
        "moves": "serve_tokens_per_s", "workloads": ["tiny-gpt.chat"]})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]
