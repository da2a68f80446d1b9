"""GPT-2 (``model_type: "gpt2"``) for the serving runner: the program's
``GPTModel`` and the plain reference ``references/gpt2.py``."""

from __future__ import annotations

import time
from typing import List

from benchmark.harness import flops, weights
from benchmark.references import gpt2 as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["held_vocab"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"],
        layernorm_eps=cfg["layer_norm_epsilon"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.gpt import GPTModel

    return GPTModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    # the published vocabulary: the lane padding up to ``held_vocab`` is
    # held and scored but never sent
    return cfg["vocab_size"]


def page_bytes(cfg: dict, page_size: int) -> int:
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def forward_flops_per_token(cfg: dict) -> float:
    return flops.gpt_forward_flops_per_token(
        hidden=cfg["n_embd"], layers=cfg["n_layer"], vocab=cfg["held_vocab"])


def judge(cfg: dict, seed: int, samples: List[tuple],
          precision: str = "float32") -> dict:
    """The whole float32 tree at once: 3.1 GB at the published sizes."""
    import jax

    t0 = time.perf_counter()
    params = weights.make_weights(reference.param_table(cfg), seed)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    out = reference.widest_gap(params, samples, cfg, precision=precision)
    out["weights_s"], out["judge_s"] = t1 - t0, time.perf_counter() - t1
    return out
