"""``model_type: "lfm2_moe"`` for the serving runner: the program's
``Lfm2MoeModel`` (gated short-convolution layers whose two-token window is
a row a slot beside the GQA layers' paged K and V; a sigmoid top-k router
with an expert bias over routed experts; the head tied to the embedding)
and the plain reference ``references/lfm2_moe.py``."""

from __future__ import annotations

import functools
import time
from typing import List

from benchmark.harness import weights
from benchmark.references import lfm2_moe as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.lfm2_moe import Lfm2MoeConfig

    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not held: the "
                         f"published value is 'default'")
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("the program holds the published conv_bias false "
                         "and use_expert_bias true")
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"], num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_norm_eps=reference.ROUTER_EPS,
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_theta=float(rope["rope_theta"]), rms_eps=cfg["norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.lfm2_moe import Lfm2MoeModel

    return Lfm2MoeModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    return cfg["vocab_size"]          # the whole vocabulary is held


def page_bytes(cfg: dict, page_size: int) -> int:
    """One page of the ATTENTION layers' group over its layers: the mix's
    ``pool_bytes`` buys pages of the block table's group.  The conv layers'
    windows are the engine's own on top, sized by the slots alone
    (``kv_pool.state_bytes``)."""
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def mixer_params(cfg: dict, kind: str) -> int:
    """One layer's mixer matrices (the taps and the norms' scales left
    out: they are not multiplied with)."""
    e = cfg["hidden_size"]
    if kind == reference.CONV:
        return 3 * e * e + e * e
    d = reference.head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return e * (2 * h + 2 * kv) * d


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token's forward pass multiplies with: per layer
    the mixer's matrices, the dense SwiGLU or the router and the
    ``num_experts_per_tok`` routed experts a token is sent to (the ACTIVE
    ones); the tied head.  Attention over the context is left out, so the
    MFU built on this is a lower bound."""
    e = cfg["hidden_size"]
    dense = 3 * e * cfg["intermediate_size"]
    routed = (e * cfg["num_experts"] + cfg["num_experts_per_tok"] * 3 * e
              * cfg["moe_intermediate_size"])
    total = sum(mixer_params(cfg, reference.layer_kind(cfg, n))
                + (dense if reference.is_dense(cfg, n) else routed)
                for n in range(cfg["num_hidden_layers"]))
    return 2.0 * (total + cfg["vocab_size"] * e)


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
