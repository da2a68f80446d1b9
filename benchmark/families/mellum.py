"""``model_type: "mellum"`` for the serving runner: the program's
``MellumModel`` (windowed and full attention layers mixed, each kind over
its own pages; softmax top-k routed experts) and the plain reference
``references/mellum.py``."""

from __future__ import annotations

import functools
import time
from typing import List

from benchmark.harness import weights
from benchmark.references import mellum as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.mellum import MellumConfig, YarnScaling

    yarn = tuple(
        (kind, YarnScaling(
            factor=float(rope["factor"]),
            original_max_position_embeddings=rope[
                "original_max_position_embeddings"],
            beta_fast=float(rope["beta_fast"]),
            beta_slow=float(rope["beta_slow"]),
            attention_factor=float(rope["attention_factor"])))
        for kind, rope in cfg["rope_parameters"].items()
        if rope["rope_type"] == "yarn")
    thetas = {rope["rope_theta"] for rope in cfg["rope_parameters"].values()}
    if len(thetas) != 1:
        raise ValueError(f"one rope_theta for all layer types, got {thetas}")
    return MellumConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(cfg["layer_types"]),
        sliding_window=cfg["sliding_window"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=float(thetas.pop()), yarn=yarn,
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.mellum import MellumModel

    return MellumModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    return cfg["vocab_size"]          # the whole vocabulary is held


def page_bytes(cfg: dict, page_size: int) -> int:
    """One page of the FULL-attention layers' group over its layers: the
    mix's ``pool_bytes`` buys pages of the block table's group, which is
    what the engine's ``num_pages`` counts.  The sliding layers' rings are
    the engine's own, sized from the window and the slots
    (``kv_pool.ring_pages``; the frontend's ``stats()["kv_groups"]`` states
    what each group holds)."""
    from apex_tpu.serving import kv_pool

    full = cfg["layer_types"].count("full_attention")
    return kv_pool.page_bytes(program_config(cfg), page_size, layers=full)


def attention_params(cfg: dict) -> int:
    """One layer's attention matrices: q, k, v and o."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return e * (h + 2 * kv) * d + h * d * e


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token's forward pass multiplies with: per layer
    the attention's matrices, the router and the ``num_experts_per_tok``
    routed experts a token is sent to (the ACTIVE ones); the head.
    Attention over the context is left out, so the MFU built on this is a
    lower bound."""
    e = cfg["hidden_size"]
    expert = 3 * e * cfg["moe_intermediate_size"]
    per_layer = (attention_params(cfg) + e * cfg["num_experts"]
                 + expert * cfg["num_experts_per_tok"])
    return 2.0 * (cfg["num_hidden_layers"] * per_layer
                  + cfg["vocab_size"] * e)


def judge(cfg: dict, seed: int, samples: List[tuple],
          precision: str = "float32", reference_logits=None) -> dict:
    """``gap`` is the MEAN gap over the served tokens, not the widest
    (``reference.mean_gap`` says why); ``where`` is the worst token's.  The
    reference makes its weights group by group (embedding, each layer, the
    head).  ``precision`` is one of ``reference.VARIANTS``."""
    t0 = time.perf_counter()
    out = reference.mean_gap(
        functools.partial(weights.make_weights, seed=seed), samples, cfg,
        precision=precision, reference_logits=reference_logits)
    out["judge_s"] = time.perf_counter() - t0       # weights included
    return out
