"""``model_type: "qwen3_next"`` for the serving runner: the program's
``Qwen3NextModel`` (gated delta-rule layers with one recurrent state a slot
beside the gated full-attention layers' paged K and V; a softmax top-k
router over a share of the experts plus a gated shared expert) and the plain
reference ``references/qwen3_next.py``.

A configuration file's ``num_experts`` is what the chip HOLDS; the router's
width is ``router_experts`` (the published count; absent: all are held) and
the first held expert ``first_expert``."""

from __future__ import annotations

import functools
import time
from typing import List

from benchmark.harness import weights
from benchmark.references import qwen3_next as reference


def program_config(cfg: dict):
    import jax.numpy as jnp

    from apex_tpu.models.qwen3_next import Qwen3NextConfig

    if cfg.get("rope_scaling"):
        raise ValueError("rope_scaling is not held: the published value is "
                         "null")
    routed = reference.router_experts(cfg)
    return Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=routed,
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        experts_held=(cfg["num_experts"]
                      if cfg["num_experts"] != routed else None),
        first_expert=cfg.get("first_expert", 0),
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]))


def model(cfg: dict):
    from apex_tpu.models.qwen3_next import Qwen3NextModel

    return Qwen3NextModel(program_config(cfg))


def drawn_vocab(cfg: dict) -> int:
    return cfg["vocab_size"]     # the slice of the vocabulary the chip holds


def page_bytes(cfg: dict, page_size: int) -> int:
    """One page of the FULL-attention layers' group over its layers: the
    mix's ``pool_bytes`` buys pages of the block table's group, which is
    what the engine's ``num_pages`` counts.  The linear layers' state is the
    engine's own on top, sized by the slots alone (``kv_pool.state_bytes``)."""
    from apex_tpu.serving import kv_pool

    return kv_pool.page_bytes(program_config(cfg), page_size)


def mixer_params(cfg: dict, kind: str) -> int:
    """One layer's mixer matrices (the vectors are not multiplied with)."""
    e = cfg["hidden_size"]
    if kind == reference.FULL:
        d, h, kv = (cfg["head_dim"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"])
        return e * (2 * h + 2 * kv) * d + h * d * e
    hk, hv, dk, dv = reference.linear_dims(cfg)
    return e * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dv * e


def forward_flops_per_token(cfg: dict) -> float:
    """2 x the parameters a token's forward pass multiplies with ON THIS
    CHIP: per layer the mixer's matrices, the whole router, the shared
    expert and its gate, and the routed experts a token is sent to that are
    held here, BY EXPECTATION ``num_experts_per_tok x num_experts /
    router_experts`` (2.5 of the ten at 128 of 512); the sliced head.
    Attention over the context and the delta rule's own products are left
    out, so the MFU built on this is a lower bound."""
    e = cfg["hidden_size"]
    routed = reference.router_experts(cfg)
    held_pairs = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
    moe = (e * routed + 3 * e * cfg["moe_intermediate_size"] * held_pairs
           + 3 * e * cfg["shared_expert_intermediate_size"] + e)
    total = sum(mixer_params(cfg, reference.layer_kind(cfg, n)) + moe
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
