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

# a second family, as a later PR would add it: files of its own under
# families/ and references/, a configuration whose keys are not GPT-2's
OTHER_GPT = dict(model_type="tinygpt", source="test", runner="serve",
                 width=64, heads=4, depth=2, positions=128, eps=1e-5,
                 vocab=120, rows_held=128, compute_dtype="float32",
                 param_dtype="float32", reduced={})

OTHER_REFERENCE = '''"""The tiny family's plain reference: GPT-2's equations under this
family's own key names."""

from benchmark.references import gpt2


def _keys(cfg):
    return {"n_embd": cfg["width"], "n_head": cfg["heads"],
            "n_layer": cfg["depth"], "n_positions": cfg["positions"],
            "layer_norm_epsilon": cfg["eps"], "held_vocab": cfg["rows_held"]}


def param_table(cfg):
    return gpt2.param_table(_keys(cfg))


def widest_gap(params, samples, cfg, precision="float32"):
    return gpt2.widest_gap(params, samples, _keys(cfg), precision=precision)
'''

OTHER_FAMILY = '''"""A family found by ``model_type: "tinygpt"``."""

from benchmark.harness import weights
from benchmark.references import tinygpt as reference


def program_config(cfg):
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["rows_held"], hidden_size=cfg["width"],
        num_layers=cfg["depth"], num_heads=cfg["heads"],
        max_position_embeddings=cfg["positions"], layernorm_eps=cfg["eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg):
    from apex_tpu.models.gpt import GPTModel

    return GPTModel(program_config(cfg))


def drawn_vocab(cfg):
    return cfg["vocab"]


def page_bytes(cfg, page_size):
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def forward_flops_per_token(cfg):
    return 2.0 * (cfg["depth"] * 12 * cfg["width"] ** 2
                  + cfg["rows_held"] * cfg["width"])


def judge(cfg, seed, samples, precision="float32"):
    params = weights.make_weights(reference.param_table(cfg), seed)
    return reference.widest_gap(params, samples, cfg, precision)
'''

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
    mix = _load(os.path.join(b, "workloads", "bert-large.pretrain-dp4.json"))
    mix.update(batch=8, seq_len=32, mlm_per_seq=8, traced_steps=2,
               reference_block_rows=2)
    mix["limits"].update({k: TINY_TRAIN_LIMIT for k in mix["limits"]
                          if k.endswith("_gap")})
    _dump(mix, os.path.join(b, "workloads", "tiny-bert.pretrain-dp4.json"))
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
    _dump(mix, os.path.join(b, "workloads", "tiny-other.chat.json"))
    _dump(OTHER_GPT, os.path.join(b, "configs", "tiny-other.json"))
    for package, text in (("families", OTHER_FAMILY),
                          ("references", OTHER_REFERENCE)):
        with open(os.path.join(b, package, "tinygpt.py"), "w",
                  encoding="utf-8") as f:
            f.write(text)

    with open(os.path.join(b, "layer_metrics", "readers", "count.py"),
              "w", encoding="utf-8") as f:
        f.write(NEW_READER)
    _dump({"reader": "count", "args": {"counter": "admitted"}},
          os.path.join(b, "layer_metrics", "admitted.serve.json"))

    for name in ("tiny-bert", "tiny-gpt", "tiny-other"):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "tiny",
            "file": f"benchmark/configs/{name}.json"})
    bench["workloads"] += [
        {"name": "tiny-bert.pretrain", "config": "tiny-bert",
         "traffic": "pretrain", "chips": 1, "why": "tiny"},
        {"name": "tiny-bert.pretrain-dp4", "config": "tiny-bert",
         "traffic": "pretrain-dp4", "chips": 4, "why": "tiny"},
        {"name": "tiny-gpt.chat", "config": "tiny-gpt", "traffic": "chat",
         "chips": 1, "why": "tiny"},
        {"name": "tiny-other.chat", "config": "tiny-other",
         "traffic": "chat", "chips": 1, "why": "tiny"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if "bert-large.pretrain-seq512" in m["workloads"]:
            m["workloads"].append("tiny-bert.pretrain")
        if "bert-large.pretrain-dp4" in m["workloads"]:
            m["workloads"].append("tiny-bert.pretrain-dp4")
        if any(w.startswith("gpt2-large") for w in m["workloads"]):
            m["workloads"] += ["tiny-gpt.chat", "tiny-other.chat"]
    bench["per_layer"].append({
        "name": "admitted.serve", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "serving/frontend",
        "moves": "serve_tokens_per_s",
        "workloads": ["tiny-gpt.chat", "tiny-other.chat"]})
    _dump(bench, os.path.join(root, "BENCHMARK.json"))
    return root


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


def find_added_families(monkeypatch, root):
    """Lets ``import benchmark.families.<new>`` find what ``tiny_root`` added
    to the copy, as it would find a file a later PR adds to the repo."""
    import benchmark.families
    import benchmark.references

    forget_added_families()
    for package in (benchmark.families, benchmark.references):
        added = os.path.join(root, "benchmark",
                             package.__name__.split(".")[-1])
        monkeypatch.setattr(package, "__path__",
                            list(package.__path__) + [added])


def forget_added_families():
    for package in ("families", "references"):
        sys.modules.pop(f"benchmark.{package}.tinygpt", None)
